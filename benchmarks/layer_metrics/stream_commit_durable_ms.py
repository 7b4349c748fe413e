"""From the hand-over of a micro-batch's commit unit to its being durable:
per ``checkpoint/write`` span of kind ``unit`` (a job of the store's writer
thread, ended by the rename and the directory's fsync) its ``queued_ms``
(from ``save_async`` taking the job, any wait for room included, to the
writer taking it up) plus the span.  ``.p50`` and ``.p95`` over the traced
window's units.  A program without the span reports nothing."""

from benchmarks.harness import stats


def durable_ms(spans):
    return [e["args"].get("queued_ms", 0.0) + e["dur"] * 1e-3 for e in spans
            if e["name"] == "checkpoint/write"
            and e["args"].get("kind") == "unit"]


def read(ctx, name):
    d = durable_ms(ctx.program_spans)
    q = {"p50": 50.0, "p95": 95.0}[name.split(".", 1)[1]]
    return stats.percentile(d, q) if d else None
