"""How long a rating takes from its sending to the return of the engine's
commit listener (from then on a request of its user sees it), over the
ratings of the window: the percentile named by the suffix (``p50``,
``p95``).  A runner that streams nothing reports nothing."""

from benchmarks.harness import stats


def read(ctx, name):
    waits = ctx.window.get("visible_ms")
    if waits is None or not len(waits):
        return None
    return stats.percentile(waits, float(name.split(".", 1)[1][1:]))
