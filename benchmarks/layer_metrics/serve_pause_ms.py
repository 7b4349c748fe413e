"""How much of the traced window's serve steps went to pauses, and how much
of that the program can name.  A step whose period (one ``serve/batch``
start to the next) exceeds 1.25 x the window's median period paused for its
excess over the median.  ``.named``: the part of each excess covered by what
the program's tracer saw pause the serving thread inside that period, a pass
of the collector (``runtime/gc``) or a wait for room behind the store's
writer (``checkpoint/backpressure``).  ``.unnamed``: the rest.  Both summed
over the window, in milliseconds per second of the steps' periods.  Nothing
on a program whose tracer hooks no collector (it can name no pause), or in a
window of fewer than three steps."""

from benchmarks.harness import host_spans, stats

OVER = 1.25
BACKPRESSURE = "checkpoint/backpressure"


def split(spans, gc_span):
    """(named, unnamed) in ms per s, or None where there is nothing to
    read."""
    mine = host_spans.on_serving_thread(spans)
    starts = sorted(e["ts"] for e in mine if e["name"] == "serve/batch")
    steps = list(zip(starts, starts[1:]))
    if gc_span is None or len(steps) < 2:
        return None
    mid = stats.median([b - a for a, b in steps])
    pauses = [(e["ts"], e["ts"] + e["dur"]) for e in mine
              if e["name"] in (gc_span, BACKPRESSURE)]
    named = unnamed = 0.0
    for a, b in steps:
        if b - a <= OVER * mid:
            continue
        excess = b - a - mid
        # overlaps once: a pass inside a wait is the wait's
        seen = min(excess, stats.union_length(
            [p for p in pauses if a <= p[0] < b]))
        named += seen
        unnamed += excess - seen
    seconds = (starts[-1] - starts[0]) * 1e-6
    return named * 1e-3 / seconds, unnamed * 1e-3 / seconds


def read(ctx, name):
    got = split(ctx.program_spans, host_spans.gc_span_name())
    if got is None:
        return None
    return got[("named", "unnamed").index(name.split(".", 1)[1])]
