"""Median over batches of serve/batch minus its serve/batch/compute child: polling, validation, gathers, seen tiles, serdes, produce."""

from benchmarks.harness import stats


def read(ctx, name):
    whole = ctx.span_durations_ms("serve/batch")
    compute = ctx.span_durations_ms("serve/batch/compute")
    if not whole or len(whole) != len(compute):
        return None
    return stats.median([w - c for w, c in zip(whole, compute)])
