"""Share of the traced window's records that wrote a cell which already
held a value: ``rerated`` over ``records`` summed over the
``stream/batch/stage`` spans.  A program whose span carries no ``rerated``
reports nothing."""

from benchmarks.layer_metrics import stream_stale_share


def read(ctx, name):
    return stream_stale_share.share(ctx, "rerated")
