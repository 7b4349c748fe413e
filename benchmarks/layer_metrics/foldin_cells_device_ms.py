"""Device time of the fold-in per micro-batch, in ms, over ALL its programs:
device 0's runs of every XLA module ``harness/foldin_modules.py`` names (the
rectangle's one program, the cells route's Gram runs and its solve), over
the micro-batches the traced window committed.  A trace in which none ran,
or a program whose ``stream/batch`` span carries no ``cells``, reports
nothing."""

from benchmarks.harness import shard_trace
from benchmarks.harness.foldin_modules import FOLD_MODULES
from benchmarks.layer_metrics import stream_span_ms


def fold_seconds(trace) -> float:
    return sum(shard_trace.program_seconds(trace, m)[0] for m in FOLD_MODULES)


def solved_batches(spans) -> list:
    """The args of the window's micro-batches that solved something and say
    how many cells."""
    return [e["args"] for e in stream_span_ms.batches(spans)
            if e["args"].get("cells") and e["args"].get("touched")]


def read(ctx, name):
    secs, batches = fold_seconds(ctx.trace_data), solved_batches(
        ctx.program_spans)
    return 1e3 * secs / len(batches) if secs and batches else None
