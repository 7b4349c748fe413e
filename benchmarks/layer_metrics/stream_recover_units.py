"""Commit units a successor read from the store to resume (the ``units`` of
the traced window's ``stream/recover`` spans, summed): what bounds
``stream_recover_s.restore`` and ``.state``; at most about
``snapshot_every_units`` however long the stream has run.  Nothing where the
window had no recovery."""

from benchmarks.layer_metrics.stream_recover_s import summed


def read(ctx, name):
    return summed(ctx, "units")
