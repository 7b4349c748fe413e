"""Median of the program's serve/batch/compute span (batch upload, scorer, fetch of the [B, K] result)."""

from benchmarks.harness import stats


def read(ctx, name):
    d = ctx.span_durations_ms("serve/batch/compute")
    return stats.median(d) if d else None
