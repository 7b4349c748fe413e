"""Plain reference of a fold-in against a row-quantized item table, in
numpy, importing nothing of the program: the dequantized rows a sample of
users' lists name, made again from the seed block by block, so that
``reference_foldin.solve_row`` (the float64 solve of a user's ALS-WR normal
equations) runs over them unedited and no host buffer ever holds the table.

The table a folded-in row is held to is the table its user is scored
against: ``reference_q8.DequantizedBlocks``, int8 code x the row's float32
scale by the written rule.  ``RowTable(reference_q8.FactorBlocks(...), ids)``
is the same rows of the float32 factors the codes were made from: the
control a fold-in over the dequantized view must fail against.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness.reference_foldin import (  # noqa: F401  by import
    row_err, solve_row)
from benchmarks.harness.reference_q8 import BLOCK


class RowTable:
    """Rows ``ids`` of a lazy block table (``shape``, ``table[lo:hi]``),
    read in one pass over the blocks that hold any of them, a few blocks a
    read (one a thread of the table's pool); ``rows[item ids]`` looks them
    up, as a whole table would."""

    def __init__(self, blocks, ids, *, blocks_a_read: int | None = None):
        self.ids = np.unique(np.asarray(ids, np.int64))
        rows, rank = blocks.shape
        if self.ids.size and not 0 <= self.ids[0] <= self.ids[-1] < rows:
            raise IndexError("an item row outside the table")
        self.rows = np.empty((self.ids.shape[0], rank), np.float32)
        step = BLOCK * max(int(blocks_a_read or getattr(blocks, "threads", 1)),
                           1)
        self.reads = 0
        lo_at = 0
        while lo_at < self.ids.shape[0]:
            lo = int(self.ids[lo_at]) // BLOCK * BLOCK
            hi = min(lo + step, rows)
            hi_at = int(np.searchsorted(self.ids, hi))
            self.rows[lo_at:hi_at] = blocks[lo:hi][self.ids[lo_at:hi_at] - lo]
            self.reads += 1
            lo_at = hi_at

    def __getitem__(self, items) -> np.ndarray:
        items = np.asarray(items, np.int64)
        at = np.searchsorted(self.ids, items)
        if items.size and not np.array_equal(
                self.ids[np.minimum(at, self.ids.shape[0] - 1)], items):
            raise KeyError("an item row this table was not made for")
        return self.rows[at]
