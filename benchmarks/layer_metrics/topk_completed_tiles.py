"""Median over the traced window's batches of the share of the scanned tiles on
which the scorer ran every one of its MXU passes: ``completed_tiles / tiles``
from the program's ``serve/batch/compute`` span (tiles completed and tiles
scanned, added up over the shards of a mesh).  An int8 tile runs the first of its
three passes and the other two, with its masks and its rounds, only where that
pass cannot rule the tile out.  A span that says how many passes a tile takes
(``score_passes``) and how many tiles were scanned but carries no such count is
a program that completes every tile: 1.0; one without ``tiles`` reports
nothing."""

from benchmarks.harness import stats


def read(ctx, name):
    ratios = []
    for e in ctx.program_spans:
        args = e.get("args", {})
        if (e["name"] == "serve/batch/compute" and "score_passes" in args
                and args.get("tiles")):
            ratios.append(
                args.get("completed_tiles", args["tiles"]) / args["tiles"])
    return stats.median(ratios) if ratios else None
