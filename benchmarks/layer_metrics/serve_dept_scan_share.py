"""Mean over the traced window's batches of the share of the table that a
batch had to scan: ``range_tiles`` from the program's ``serve/batch`` span
(the tiles that hold a row of the department the batch's requests named)
over the table's tiles (the window's ``table_rows_whole`` over the
configuration's ``tile_m``).  The configuration's departments and the mix's
request shares give 0.128; 1.0 is a program that scans the whole table for a
department page.  A program whose span carries no such count (it serves no
departments), or a window without the table's rows, reports nothing."""


def read(ctx, name):
    whole = ctx.window.get("table_rows_whole")
    tiles = [e["args"]["range_tiles"] for e in ctx.program_spans
             if e["name"] == "serve/batch"
             and "range_tiles" in e.get("args", {})]
    if not tiles or not whole:
        return None
    table_tiles = whole / ctx.config["engine"]["tile_m"]
    return sum(tiles) / len(tiles) / table_tiles
