"""Device time of the fold-in program per micro-batch, in ms: device 0's
runs of the XLA module whose name holds ``_padded_fold`` (the jitted entry's
name: gather, Gram, solve and the sentinel's word in one program).  A trace
in which it never ran reports nothing."""

from benchmarks.harness import shard_trace

PROGRAM = "_padded_fold"


def read(ctx, name):
    secs, runs = shard_trace.program_seconds(ctx.trace_data, PROGRAM)
    return 1e3 * secs / runs if runs else None
