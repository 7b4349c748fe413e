"""Device time per batch that device 0 spends building its slice of the
exclusion rectangle, in ms: fill, scatter and the copy into the scorer's
layout of ``_seen_tiles_shard_call`` (run once per piece of the batch's cell
list), over the batches the shard scorer ran.  A program with no such program
reports nothing."""

from benchmarks.harness import shard_trace


def read(ctx, name):
    build, runs = shard_trace.program_seconds(ctx.trace_data,
                                              shard_trace.BUILD_PROGRAM)
    _, batches = shard_trace.program_seconds(ctx.trace_data,
                                             shard_trace.SCORE_PROGRAM)
    if not runs or not batches:
        return None
    return 1e3 * build / batches
