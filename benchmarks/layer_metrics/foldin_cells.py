"""Median over the traced window's micro-batches of the cells one
micro-batch's touched users hold (``cells`` on the ``stream/batch`` span:
the sum of their list lengths, the fold-in's real work).  A program without
the count reports nothing."""

from benchmarks.harness import stats
from benchmarks.layer_metrics import foldin_cells_device_ms


def read(ctx, name):
    cells = [a["cells"] for a in foldin_cells_device_ms.solved_batches(
        ctx.program_spans)]
    return stats.median(cells) if cells else None
