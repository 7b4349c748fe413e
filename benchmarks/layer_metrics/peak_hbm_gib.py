"""memory_stats()["peak_bytes_in_use"] of the fullest chip, after the window."""

def read(ctx, name):
    return ctx.device["memory_peak_bytes"] / 2**30
