"""Commit units the killed task had handed to its writer, or committed in
memory, and the store never held (``lost_units`` of the traced window's
``stream/recover`` spans, summed): discarded at the kill, never shown to a
reader, replayed from the log by the successor.  Nothing where the window
had no recovery."""

from benchmarks.layer_metrics.stream_recover_s import summed


def read(ctx, name):
    return summed(ctx, "lost_units")
