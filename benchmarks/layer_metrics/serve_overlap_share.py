"""The share of the traced window's serve steps that ran with a batch in
flight on the device: of the program's ``serve/batch`` spans that carry
``overlapped``, those where it is true (the server polled, assembled,
uploaded and handed over this step's batch while the scorer of the batch
before it ran).  1 on a saturated server that keeps one batch ahead, 0 on
one that answers each batch before it polls the next; a program whose span
lacks the attribute reports nothing."""


def read(ctx, name):
    flags = [e["args"]["overlapped"] for e in ctx.program_spans
             if e["name"] == "serve/batch" and "overlapped" in e.get("args", {})]
    return sum(map(bool, flags)) / len(flags) if flags else None
