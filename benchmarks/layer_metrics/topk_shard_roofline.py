"""Share of its roofline that the shard scorer reaches on device 0: one scan
of a shard's slice of the item table per batch (``table_rows / shards`` rows,
plus the batch in and the selection out: ``roofline.topk_cost`` over the
slice) against one chip's HBM bandwidth, over the device time of the shard
program's scorer custom calls.  The kernel is found by the name the shard
entry gives it, ``_topk_shard_call.<n>``; a program without it (one device,
or before the table could be sharded) reports nothing.  The four chips scan
their slices at once, so the share of one is the share of each."""

from benchmarks.harness import roofline, shard_trace, stats


def read(ctx, name):
    sizes, shards = ctx.window.get("batch_sizes"), ctx.window.get("shards")
    secs, calls = ctx.trace_data.kernel_seconds(shard_trace.SCORER)
    if not sizes or not calls or not shards:
        return None
    batch = max(8, 1 << (int(stats.median(sizes)) - 1).bit_length())
    cost = roofline.topk_cost(
        ctx.window["table_rows"] // shards, ctx.config["rank"], batch,
        ctx.window["k_pad"],
        {"float32": 4, "bfloat16": 2, "int8": 1}[ctx.config["table_dtype"]])
    return 100.0 * cost.floor_s(ctx.peaks) * calls / secs
