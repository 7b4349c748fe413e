"""Share of the roofline of its USEFUL work that the fold-in reaches against
an int8 table: the floors of the traced window's micro-batches
(``roofline_foldin_q8.batch_floor_s`` of the data's counts on each
``stream/batch`` span: the cells' codes and scales as they are stored,
operands and solved rows at the HBM bandwidth against their Gram and solve
operations at the peak) over device 0's time in ALL the fold-in's programs
(``harness/foldin_modules.py``).  ``cells`` is the data's, so the share
reads the same work whatever implements the gather and cannot pass 100 %.
A trace without the programs, or spans that do not name an int8 table
(``table_dtype``), reports nothing."""

from benchmarks.harness import roofline_foldin_q8
from benchmarks.layer_metrics import foldin_cells_device_ms


def read(ctx, name):
    secs = foldin_cells_device_ms.fold_seconds(ctx.trace_data)
    if not secs:
        return None
    floors = [roofline_foldin_q8.batch_floor_s(a, ctx.peaks)
              for a in foldin_cells_device_ms.solved_batches(
                  ctx.program_spans)]
    floors = [f for f in floors if f is not None]
    if not floors:
        return None
    return 100.0 * sum(floors) / secs
