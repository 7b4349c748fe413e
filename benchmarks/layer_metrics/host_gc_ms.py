"""What Python's cyclic collector takes of the serving thread's steps in the
traced window, from the program's ``runtime/gc`` spans (one a pass, on the
thread that ran it): the passes between the first step's start and the last
``serve/batch``'s end, so a pass while the profiler starts or while the run
prints its window is none of a step's.  ``.saturate``: their sum over the
window's ``serve/batch`` spans, milliseconds a step.  ``.longest``: the
longest single pass.  0 where the hook was in and no pass ran there (a
runner that keeps the collector off); nothing on a program whose tracer has
no such hook."""

from benchmarks.harness import host_spans


def passes_ms(spans, gc_span):
    """(durations of the passes inside the steps, serve/batch spans)."""
    mine = host_spans.on_serving_thread(spans)
    batches = [e for e in mine if e["name"] == "serve/batch"]
    if gc_span is None or not batches:
        return None
    lo = min(e["ts"] for e in mine
             if e["name"] in ("serve/poll", "serve/batch"))
    hi = max(e["ts"] + e["dur"] for e in batches)
    return [e["dur"] * 1e-3 for e in mine if e["name"] == gc_span
            and lo <= e["ts"] and e["ts"] + e["dur"] <= hi], len(batches)


def read(ctx, name):
    got = passes_ms(ctx.program_spans, host_spans.gc_span_name())
    if got is None:
        return None
    passes, batches = got
    if name.split(".", 1)[1] == "longest":
        return max(passes, default=0.0)
    return sum(passes) / batches
