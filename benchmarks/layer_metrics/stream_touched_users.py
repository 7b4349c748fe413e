"""Median over the traced window's micro-batches of the user rows one
micro-batch re-solves (``touched`` on the ``stream/batch`` span).  A program
without the span reports nothing."""

from benchmarks.harness import stats
from benchmarks.layer_metrics import stream_span_ms


def read(ctx, name):
    touched = [e["args"]["touched"]
               for e in stream_span_ms.batches(ctx.program_spans)
               if "touched" in e["args"]]
    return stats.median(touched) if touched else None
