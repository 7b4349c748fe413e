"""Device trace: 1 - (union of the programs' intervals) / traced window."""

def read(ctx, name):
    d = ctx.device
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
