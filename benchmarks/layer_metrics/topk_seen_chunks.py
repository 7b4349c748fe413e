"""Median over the traced window's batches of the scorer's exclusion chunks per
tile scanned: ``seen_chunks / tiles`` from the program's ``serve/batch/compute``
span (chunks of 16 compare-and-selects over the score block that the kernel
ran, and tiles scanned, added up over the shards of a mesh).  The fold ran the
rectangle's whole width on every tile (1 chunk a tile at W = 16) before it ran
only the chunks a tile holds; a program whose span carries no such count
reports nothing."""

from benchmarks.harness import stats


def read(ctx, name):
    ratios = []
    for e in ctx.program_spans:
        args = e.get("args", {})
        if (e["name"] == "serve/batch/compute" and "seen_chunks" in args
                and args.get("tiles")):
            ratios.append(args["seen_chunks"] / args["tiles"])
    return stats.median(ratios) if ratios else None
