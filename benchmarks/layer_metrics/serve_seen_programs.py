"""Runs of the exclusion rectangle's build program a batch: the mean, over
the traced window's ``serve/batch/seen_tiles`` spans, of ``programs`` where
the span has it (what the batch's rectangle will cost in runs of the scatter
program: one, unless its cell list passes the top rung of the pieces ladder)
and else of ``chunks`` (a program that ran its scatter once per piece of the
cell list counted the pieces, and they were its runs).  A window with no such
span, or a span with neither count, reports nothing."""


def read(ctx, name):
    runs = []
    for e in ctx.program_spans:
        if e["name"] != "serve/batch/seen_tiles":
            continue
        args = e.get("args", {})
        count = args.get("programs", args.get("chunks"))
        if count is not None:
            runs.append(count)
    return sum(runs) / len(runs) if runs else None
