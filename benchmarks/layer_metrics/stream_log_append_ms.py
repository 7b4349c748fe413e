"""What a sending of ratings costs the serving thread on a log on disk: the
``stream/log/append`` spans of the traced window (one a ``send_many``: one
append and one fsync), ``.p50`` and ``.p95``.  A program without the span
reports nothing."""

from benchmarks.harness import stats


def read(ctx, name):
    d = ctx.span_durations_ms("stream/log/append")
    q = {"p50": 50.0, "p95": 95.0}[name.split(".", 1)[1]]
    return stats.percentile(d, q) if d else None
