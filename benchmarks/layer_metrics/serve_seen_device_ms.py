"""Device time per batch that the one-device build of the exclusion
rectangle takes, in ms: device 0's runs of every XLA module whose name holds
``_seen_tiles_call`` (fill, scatter and the copy into the scorer's layout;
one run a batch, or one a piece of the batch's cell list on a program that
cut it), over the runs of the modules whose name holds ``_topk_call`` (the
scorer's: one a batch).  The one-chip twin of ``serve_seen_build_ms.x4``,
whose shard programs carry other names and are not counted here.  A trace in
which either did not run reports nothing."""

from benchmarks.harness import shard_trace

BUILD_PROGRAM = "_seen_tiles_call"  # not ``_seen_tiles_shard_call``
SCORE_PROGRAM = "_topk_call"  # not ``_topk_shard_call``


def read(ctx, name):
    build, runs = shard_trace.program_seconds(ctx.trace_data, BUILD_PROGRAM)
    _, batches = shard_trace.program_seconds(ctx.trace_data, SCORE_PROGRAM)
    if not runs or not batches:
        return None
    return 1e3 * build / batches
