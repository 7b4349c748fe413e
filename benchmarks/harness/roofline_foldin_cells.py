"""Operations and bytes of the USEFUL work of one fold-in micro-batch, from
the data's own counts (``cells``: the sum of the touched users' list
lengths; ``touched``; ``rank``), so they read the same work whatever lays
the systems out:

- bytes: one item row gathered a cell (k x 4), its operands (an index and a
  rating, 8) and the solved rows written (touched x k x 4);
- operations: the Gram and right-hand side over the cells (2 cells k (k + 1))
  and one solve of a k x k system a touched user (2/3 k^3 + 2 k^2).

The floor is the longer of bytes at the chip's HBM bandwidth and operations
at the peak ``roofline.py`` counts the scorer's float32 ``HIGHEST`` matmul
against (one pass; the six passes are the program's).  Padding a layout
gathers and multiplies is no part of it, so the share of this floor in the
device's time cannot pass 100 % and falls with every padded cell.
"""

from __future__ import annotations

from benchmarks.harness.roofline import Cost


def cells_cost(cells: int, touched: int, rank: int) -> Cost:
    gram = 2.0 * cells * rank * (rank + 1)
    solve = touched * (2.0 / 3.0 * rank ** 3 + 2.0 * rank ** 2)
    return Cost(flops=gram + solve,
                bytes=float(cells * (rank * 4 + 8) + touched * rank * 4))


def batch_floor_s(args: dict, pk):
    """The least seconds the chip could take over the useful work of the
    micro-batch one ``stream/batch`` span describes; None where the span
    carries no ``cells`` (a program before PR 41) or solved nothing."""
    if not args.get("cells") or not args.get("touched"):
        return None
    return cells_cost(args["cells"], args["touched"],
                      args["rank"]).floor_s(pk)
