"""Median number of requests the server coalesced into a batch inside the window."""

from benchmarks.harness import stats


def read(ctx, name):
    sizes = ctx.window.get("batch_sizes")
    return stats.median(sizes) if sizes else None
