"""Share of the traced window's records that were outranked: ``stale`` over
``records`` summed over the ``stream/batch/stage`` spans (a record that
arrived after a newer event of its cell: consumed, committed, changes
nothing).  A program whose span carries no ``stale`` reports nothing."""


def share(ctx, count: str):
    spans = [e["args"] for e in ctx.program_spans
             if e["name"] == "stream/batch/stage" and count in e.get("args", {})]
    records = sum(a["records"] for a in spans)
    return sum(a[count] for a in spans) / records if records else None


def read(ctx, name):
    return share(ctx, "stale")
