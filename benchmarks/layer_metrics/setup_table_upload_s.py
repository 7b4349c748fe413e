"""Seconds inside the program's ``serve/engine/table_upload`` span (the item
table staged and uploaded shard by shard, each shard landed before the next
is staged), summed over the set-up.  The runner keeps the tracer on while it
builds the engine in a traced run (``ctx.setup_spans``); a runner or a
program without the span reports nothing."""


def read(ctx, name):
    spans = [e for e in getattr(ctx, "setup_spans", ())
             if e["name"] == "serve/engine/table_upload"]
    return sum(e["dur"] for e in spans) * 1e-6 if spans else None
