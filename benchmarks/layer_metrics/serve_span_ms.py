"""Median over the traced window's batches of one stage of the serve batch
period, the stage taken from the metric's suffix.

A stage with a span of its own reads that span's duration.  ``outside`` runs
from the end of batch n's ``serve/batch`` to the start of batch n+1's
``serve/poll`` (the load generator's send and collect, on the server's
thread); ``period`` from the start of ``serve/batch`` n to the start of
``serve/batch`` n+1, which under the profiler is longer than
``max_batch / serve_req_per_s``.  A program without the span reports nothing.
"""

from benchmarks.harness import stats

SPAN_OF = {
    "poll": "serve/poll",
    "assemble": "serve/batch/assemble",
    "seen_tiles": "serve/batch/seen_tiles",
    "upload": "serve/batch/upload",
    "dispatch": "serve/batch/compute/dispatch",
    "fetch": "serve/batch/compute/fetch",
    "respond": "serve/batch/respond",
}


def by_batch(spans, name):
    """{batch ordinal: span} of the spans ``name`` that carry one."""
    return {e["args"]["batch"]: e for e in spans
            if e["name"] == name and "batch" in e.get("args", {})}


def durations_ms(spans, stage):
    """Every reading of ``stage`` among ``spans`` (the tracer's complete
    events, ``ts`` and ``dur`` in microseconds), in milliseconds."""
    if stage in SPAN_OF:
        return [e["dur"] * 1e-3 for e in spans if e["name"] == SPAN_OF[stage]]
    if stage == "period":
        starts = sorted(e["ts"] for e in spans if e["name"] == "serve/batch")
        return [(b - a) * 1e-3 for a, b in zip(starts, starts[1:])]
    if stage == "outside":
        batches = by_batch(spans, "serve/batch")
        polls = by_batch(spans, "serve/poll")
        return [(polls[n + 1]["ts"] - e["ts"] - e["dur"]) * 1e-3
                for n, e in sorted(batches.items()) if n + 1 in polls]
    raise ValueError(f"no stage {stage!r} of the serve batch")


def read(ctx, name):
    d = durations_ms(ctx.program_spans, name.split(".", 1)[1])
    return stats.median(d) if d else None
