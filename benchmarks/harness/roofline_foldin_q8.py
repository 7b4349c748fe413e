"""``roofline_foldin_cells.cells_cost`` with bytes true to a row-quantized
table: the useful work of one fold-in micro-batch against int8 codes and a
float32 scale a row, from the data's own counts (``cells``, ``touched``,
``rank``):

- bytes: one item row gathered a cell as it is stored (k x 1 of codes + 4 of
  its scale), its operands (an index and a rating, 8) and the solved rows
  written (touched x k x 4);
- operations: ``roofline_foldin_cells``'s, unchanged (the Gram, the
  right-hand side and one solve a touched user).

``foldin_cells_roofline.foldin`` counts 512 B a gathered row and would read
this table's gather 3.7 x too high (140 B here).
"""

from __future__ import annotations

from benchmarks.harness import roofline_foldin_cells
from benchmarks.harness.roofline import Cost


def cells_cost(cells: int, touched: int, rank: int) -> Cost:
    return Cost(
        flops=roofline_foldin_cells.cells_cost(cells, touched, rank).flops,
        bytes=float(cells * (rank * 1 + 4 + 8) + touched * rank * 4))


def batch_floor_s(args: dict, pk):
    """The least seconds the chip could take over the useful work of the
    micro-batch one ``stream/batch`` span describes; None where the span
    does not say that the table is int8 (a program before PR 47, another
    table) or solved nothing."""
    if (args.get("table_dtype") != "int8" or not args.get("cells")
            or not args.get("touched")):
        return None
    return cells_cost(args["cells"], args["touched"],
                      args["rank"]).floor_s(pk)
