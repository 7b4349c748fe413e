"""Operations and bytes of one fold-in micro-batch, from the counts the
program's ``stream/batch`` span carries (``entities`` x ``width``: the padded
rectangle, ``rank``, ``gather_bytes``, ``operand_bytes``), so they stay true
whatever implements the fold:

- bytes: the item rows gathered for the rectangle, the rectangle's operands
  (indices, ratings, mask, counts) and the solved rows written;
- operations: the Gram and right-hand side of every entity over the padded
  width (2 E P k^2 + 2 E P k) and one LU solve of a k x k system each
  (2/3 k^3 + 2 k^2).

The floor is the longer of bytes at the chip's HBM bandwidth and operations
at the peak the scorer's floor counts its float32 ``HIGHEST`` matmul against
(``roofline.Peaks.bf16_flops``: one pass; the six passes are the program's).
"""

from __future__ import annotations

from benchmarks.harness.roofline import Cost


def foldin_cost(entities: int, width: int, rank: int, gather_bytes: int,
                operand_bytes: int) -> Cost:
    gram = 2.0 * entities * width * rank * (rank + 1)
    solve = entities * (2.0 / 3.0 * rank ** 3 + 2.0 * rank ** 2)
    return Cost(flops=gram + solve,
                bytes=float(gather_bytes + operand_bytes
                            + entities * rank * 4))


def batch_floor_s(args: dict, pk):
    """The least seconds the chip could take over the micro-batch one
    ``stream/batch`` span describes; None where it solved nothing."""
    if not args.get("entities"):
        return None
    return foldin_cost(args["entities"], args["width"], args["rank"],
                       args["gather_bytes"], args["operand_bytes"]).floor_s(pk)
