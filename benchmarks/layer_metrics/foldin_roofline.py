"""Share of its roofline that the fold-in program reaches: the floors of the
traced window's micro-batches (``roofline_foldin.batch_floor_s`` of the
counts on each ``stream/batch`` span: gathered rows, operands and solved rows
at the HBM bandwidth against Gram and solve operations at the peak) over the
device time of the program's runs.  Prewarm runs nothing inside the window,
so the runs are the micro-batches'.  A trace without the program, or a
program without the span's counts, reports nothing."""

from benchmarks.harness import roofline_foldin, shard_trace
from benchmarks.layer_metrics import foldin_device_ms, stream_span_ms


def read(ctx, name):
    secs, runs = shard_trace.program_seconds(ctx.trace_data,
                                             foldin_device_ms.PROGRAM)
    if not runs:
        return None
    floors = [roofline_foldin.batch_floor_s(e["args"], ctx.peaks)
              for e in stream_span_ms.batches(ctx.program_spans)]
    floors = [f for f in floors if f is not None]
    if not floors:
        return None
    # the floor of the runs traced, at the mean floor of the batches seen
    return 100.0 * (sum(floors) / len(floors)) * runs / secs
