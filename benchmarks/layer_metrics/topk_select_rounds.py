"""Median over the traced window's batches of the scorer's selection rounds per
tile scanned: ``select_rounds / tiles`` from the program's ``serve/batch/compute``
span (rounds run and tiles scanned, added up over the shards of a mesh).  The
fold ran K rounds on every tile before it gated them on the carry's K-th score;
a program whose span carries no such counts reports nothing."""

from benchmarks.harness import stats


def read(ctx, name):
    ratios = []
    for e in ctx.program_spans:
        args = e.get("args", {})
        if (e["name"] == "serve/batch/compute" and "select_rounds" in args
                and args.get("tiles")):
            ratios.append(args["select_rounds"] / args["tiles"])
    return stats.median(ratios) if ratios else None
