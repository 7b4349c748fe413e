"""How long the replacement of a killed stream task takes, from the
program's own spans of the traced window, summed over its recoveries:
``.total`` the ``stream/recover`` span (from the exception out of the
session's pump to the successor's first publication of a unit of its own),
``.restore`` and ``.state`` the resume's stages on the recovery thread
(``stream/recover/restore``: the store's newest overlay snapshot and the
units after it read and checked; ``stream/recover/state``: solved rows and
rating state rebuilt from them), ``.catchup`` the ``stream/recover/catchup``
span (first publication to a backlog under one micro-batch).  A window
without a recovery, or a program without the spans, reports nothing."""

SPAN = {"total": "stream/recover", "restore": "stream/recover/restore",
        "state": "stream/recover/state", "catchup": "stream/recover/catchup"}


def recoveries(ctx):
    """The ``stream/recover`` spans of the traced window."""
    return [e for e in ctx.program_spans if e["name"] == SPAN["total"]]


def summed(ctx, key):
    """``key`` of the window's ``stream/recover`` spans, summed; nothing
    where the window had no recovery."""
    spans = recoveries(ctx)
    return sum(e["args"].get(key, 0) for e in spans) if spans else None


def read(ctx, name):
    if not recoveries(ctx):  # a resume's own spans with no kill: set-up's
        return None
    durations = ctx.span_durations_ms(SPAN[name.split(".", 1)[1]])
    return sum(durations) * 1e-3 if durations else None
