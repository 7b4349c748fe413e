"""Share of its roofline that the top-K scorer reaches on device 0: one scan
of the rows the device holds per batch (``roofline.serve_batch_floor_s``: the
whole padded item table on one device, ``table_rows // shards`` over a mesh,
plus the batch in and the selection out, against one chip's HBM bandwidth)
over the device time of the scorer's custom calls.  The kernel is found by
either name the program gives it today (``shard_trace.SCORER``); renamed,
it is no longer found, the metric is no longer reported and the run is
refused, which is the point."""

from benchmarks.harness import roofline, shard_trace


def read(ctx, name):
    secs, calls = ctx.trace_data.kernel_seconds(shard_trace.SCORER)
    if not calls:
        return None
    floor = roofline.serve_batch_floor_s(ctx.window, ctx.config, ctx.peaks)
    return None if floor is None else 100.0 * floor * calls / secs
