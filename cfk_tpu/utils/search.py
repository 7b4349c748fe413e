"""Searches in the slices of a CSR, a whole batch of them at once.

A micro-batch looks a few hundred (row, item) cells up in lists of 1 to
10,000 items.  One ``np.searchsorted`` a cell is a numpy call a cell on the
serving thread; one bisection over all of them is a handful of numpy calls a
step and a dozen steps at most, whatever the batch.
"""

from __future__ import annotations

import numpy as np


# Under this many needles one ``np.searchsorted`` each is cheaper than the
# bisection's dozen steps of a handful of calls.
_FEW = 32


def bisect_slices(values: np.ndarray, lo: np.ndarray, hi: np.ndarray,
                  needles: np.ndarray) -> np.ndarray:
    """Per needle i, the first index in [lo[i], hi[i]) at which the
    ascending ``values[lo[i]:hi[i]]`` is not below it (hi[i] where all
    are).  A few needles are searched one by one: the bisection's steps
    cost the same for one needle as for hundreds."""
    if needles.shape[0] < _FEW:
        return np.fromiter(
            (a + np.searchsorted(values[a:b], x) for a, b, x in zip(
                lo.tolist(), hi.tolist(), needles.tolist())),
            np.int64, needles.shape[0])
    last = max(values.shape[0] - 1, 0)
    while True:
        live = lo < hi
        if not live.any():
            return lo
        mid = (lo + hi) >> 1
        less = live & (values[np.minimum(mid, last)] < needles)
        lo = np.where(less, mid + 1, lo)
        hi = np.where(live & ~less, mid, hi)


def csr_find(indptr: np.ndarray, values: np.ndarray, rows: np.ndarray,
             needles: np.ndarray) -> np.ndarray:
    """Where the CSR (every list strictly ascending) holds each (row,
    needle) cell, as an index into ``values``; -1 where it does not, a row
    past the CSR's last included."""
    held = rows < indptr.shape[0] - 1
    if not values.shape[0] or not held.any():
        return np.full(rows.shape[0], -1, np.int64)
    safe = np.where(held, rows, 0)
    end = np.where(held, indptr[safe + 1], 0)
    at = bisect_slices(values, np.where(held, indptr[safe], 0), end, needles)
    found = (at < end) & (
        values[np.minimum(at, values.shape[0] - 1)] == needles)
    return np.where(found, at, -1)
