"""How long the thread that commits waited for room behind the store's
writer: the sum of the traced window's ``checkpoint/backpressure`` spans
over the micro-batches it committed (the ``stream/batch`` spans that carry
``ordinal``).  0 where no hand-over waited.  A program whose writer has no
``checkpoint/write`` span reports nothing: it could not have shown a wait."""


def read(ctx, name):
    spans = ctx.program_spans
    batches = sum(1 for e in spans if e["name"] == "stream/batch"
                  and "ordinal" in e.get("args", {}))
    if not batches or not any(e["name"] == "checkpoint/write" for e in spans):
        return None
    return sum(e["dur"] for e in spans
               if e["name"] == "checkpoint/backpressure") * 1e-3 / batches
