"""The share of the window's operations that failed because of the outage:
ratings sent between ``t_kill - visible_within_s`` and the successor's
catch-up that were visible late, and requests that read a list without such
a rating, over everything attempted (the runner's count,
``runners/serve_stream_kill.py``).  A slower recovery is a larger share.  A
runner that kills nothing reports nothing."""


def read(ctx, name):
    failed = ctx.window.get("outage_failed")
    if failed is None or not ctx.window.get("attempted"):
        return None
    return failed / ctx.window["attempted"]
