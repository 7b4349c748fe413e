"""Mean over the traced window's batches of the tiles the scorer's grid ran
over the tiles it had to scan: ``grid_tiles / tiles`` from the program's
``serve/batch/compute`` span of a ranged batch (the rung of the ladder of
range lengths that the batch's department takes, over the tiles that hold a
row of the department).  1.0 is a grid that ends with its range; a ladder of
powers of two reads up to 2 (and past it where a short range straddles a
slab).  A program whose span carries no ``grid_tiles`` reports nothing."""


def read(ctx, name):
    pads = [e["args"]["grid_tiles"] / e["args"]["tiles"]
            for e in ctx.program_spans
            if e["name"] == "serve/batch/compute"
            and e.get("args", {}).get("grid_tiles")
            and e["args"].get("tiles")]
    return sum(pads) / len(pads) if pads else None
