"""Median over the traced window's batches of the tiles the scorer folds per
grid step: ``tiles / grid_steps`` from the program's ``serve/batch/compute``
span (tiles of ``tile_m`` rows scanned and the kernel's grid steps, both added
up over the shards of a mesh).  A step streams a slab of G tiles, so this
reads G less the ragged last step's share (18,262 tiles in 1,142 steps of 16:
15.99); a program whose step is one tile, and whose span carries no
``grid_steps``, reports nothing."""

from benchmarks.harness import stats


def read(ctx, name):
    ratios = []
    for e in ctx.program_spans:
        args = e.get("args", {})
        if (e["name"] == "serve/batch/compute" and args.get("grid_steps")
                and "slab_tiles" in args and "tiles" in args):
            ratios.append(args["tiles"] / args["grid_steps"])
    return stats.median(ratios) if ratios else None
