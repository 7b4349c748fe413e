"""Median over the traced window's batches of the bytes the serve/batch/upload span hands to the runtime (the user batch and the seen-tile rectangle), in MB of 1e6 bytes."""

from benchmarks.harness import stats


def read(ctx, name):
    sizes = [e["args"]["bytes"] for e in ctx.program_spans
             if e["name"] == "serve/batch/upload" and "bytes" in e.get("args", {})]
    return stats.median(sizes) * 1e-6 if sizes else None
