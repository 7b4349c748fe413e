"""Share of its roofline that the top-K scorer reaches: one scan of the
padded item table per batch (plus the batch in and the selection out)
against the chip's HBM bandwidth, over the device time of the scorer's
custom calls.  The kernel is found by the name ``serving/topk_kernel.py``
gives its ``pallas_call`` today; renamed, it is no longer found, the metric
is no longer reported and the run is refused, which is the point."""

from benchmarks.harness import roofline, stats

TOPK = r"^_topk_call"


def read(ctx, name):
    sizes = ctx.window.get("batch_sizes")
    secs, calls = ctx.trace_data.kernel_seconds(TOPK)
    if not sizes or not calls:
        return None
    batch = max(8, 1 << (int(stats.median(sizes)) - 1).bit_length())
    cost = roofline.topk_cost(
        ctx.window["table_rows"], ctx.config["rank"], batch,
        ctx.window["k_pad"],
        {"float32": 4, "bfloat16": 2, "int8": 1}[ctx.config["table_dtype"]])
    return 100.0 * cost.floor_s(ctx.peaks) * calls / secs
