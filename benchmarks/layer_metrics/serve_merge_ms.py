"""Device time per batch that the shard program spends outside its scorer on
device 0, in ms: the two all_gathers of the per-shard [B, K] selections (with
the wait for the slowest chip), the merge ``top_k`` over [B, shards x K] and
the selection of the answer — the program's device time less its scorer's
custom calls, over its runs.  A program with no shard program reports
nothing."""

from benchmarks.harness import shard_trace


def read(ctx, name):
    whole, runs = shard_trace.program_seconds(ctx.trace_data,
                                              shard_trace.SCORE_PROGRAM)
    scorer, calls = ctx.trace_data.kernel_seconds(shard_trace.SCORER)
    if not runs or not calls:
        return None
    return 1e3 * (whole - scorer) / runs
