"""Plain references: the same semantics, straightforwardly, importing
nothing of the program.

Serving is exact top-K: float32 scores of one user against every item row,
seen items masked, the K largest.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np


def exact_topk(user_vecs, table, seen, k: int, *, dtype=np.float32,
               block: int = 1 << 20):
    """(scores [n, k], all-item score lookup) of the exact top-K.

    ``seen[i]`` are user ``i``'s rated item rows (masked out).  Scores are
    ``dtype`` matmuls over blocks of item rows.  Returns the K best scores
    per user, descending, and the full score matrix rows needed to look up
    any returned id (kept as one [n, items] array of ``dtype``)."""
    u = np.asarray(user_vecs, dtype)
    n, items = u.shape[0], table.shape[0]
    scores = np.empty((n, items), dtype)
    for lo in range(0, items, block):
        scores[:, lo:lo + block] = u @ np.asarray(table[lo:lo + block], dtype).T
    for i, s in enumerate(seen):
        scores[i, s] = -np.inf
    # one row at a time on the host's cores: a partition is linear in the
    # items and numpy runs it outside the interpreter's lock
    with concurrent.futures.ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        part = list(pool.map(
            lambda row: np.partition(row, items - k)[items - k:], scores))
    best = -np.sort(-np.stack(part).astype(np.float32), axis=1)
    return best, scores


def topk_gaps(ids, vals, best, scores):
    """(rank_gap, score_err) of served ``ids``/``vals`` [n, k] against the
    exact top-K: how far the exact score at a served id lies below the exact
    j-th best (0 unless a better item was missed; ties cost nothing), and how
    far a served score lies from the exact score at its id — both as a share
    of max(|exact|, 1), worst over the sample."""
    at = np.take_along_axis(scores, ids.astype(np.int64), axis=1).astype(np.float64)
    at = -np.sort(-at, axis=1)
    want = best.astype(np.float64)
    scale = np.maximum(np.abs(want), 1.0)
    rank_gap = float(np.max((want - at) / scale))
    served = np.take_along_axis(scores, ids.astype(np.int64), axis=1)
    score_err = float(np.max(np.abs(vals.astype(np.float64) - served) / np.maximum(
        np.abs(served.astype(np.float64)), 1.0)))
    return max(rank_gap, 0.0), score_err


def invalid_id_sets(ids, seen, num_items: int, k: int) -> int:
    """How many served id sets are not 'K distinct in-range unseen rows'."""
    bad = 0
    for row, s in zip(ids, seen):
        row = np.asarray(row)
        ok = (row.size == k and row.min() >= 0 and row.max() < num_items
              and np.unique(row).size == k
              and not np.isin(row, s, assume_unique=False).any())
        bad += not ok
    return bad
