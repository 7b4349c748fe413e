"""The whole serve step's share of the chip's peak: the least time a chip
could take over one batch (``roofline.serve_batch_floor_s``: one scan of the
rows the device holds at the HBM bandwidth, or the scores' operations at the
bf16 peak, whichever is longer) over the median batch period, from the start
of one ``serve/batch`` to the start of the next.  It is the scorer's roofline
share times the scorer's share of the period, and stands beside it: a change
that takes the scorer's custom call off the path, or renames it, leaves
``topk_roofline`` silent and is still bounded by this.  A program without
the ``serve/batch`` span reports nothing."""

from benchmarks.harness import roofline, stats
from benchmarks.layer_metrics import serve_span_ms


def read(ctx, name):
    floor = roofline.serve_batch_floor_s(ctx.window, ctx.config, ctx.peaks)
    periods = serve_span_ms.durations_ms(ctx.program_spans, "period")
    if floor is None or not periods:
        return None
    return 100.0 * floor / (stats.median(periods) * 1e-3)
