"""Median over the traced window's micro-batches of what the fold-in's
layout gathers and multiplies over what the data holds: ``padded_cells`` /
``cells`` on the ``stream/batch`` span (all programs of the micro-batch).
1 is no padding; a rectangle padded to its heaviest list reads tens.  A
program without the counts reports nothing."""

from benchmarks.harness import stats
from benchmarks.layer_metrics import foldin_cells_device_ms


def read(ctx, name):
    ratios = [a["padded_cells"] / a["cells"]
              for a in foldin_cells_device_ms.solved_batches(
                  ctx.program_spans) if "padded_cells" in a]
    return stats.median(ratios) if ratios else None
