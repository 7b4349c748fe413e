"""The plain reference of a department page: the same semantics,
straightforwardly, importing nothing of the program.

A request names a user, a K and a department; the answer is the exact top-K
of the user's float32 scores against every item row of that department, the
user's own items masked.  Which rows a department holds is read from the
configuration's list alone (``ranges``): department d, by its place in the
list, is the rows ``[start_d, start_d + items_d)``.
"""

from __future__ import annotations

import numpy as np


def ranges(departments: list) -> list:
    """``[(lo, hi)]`` by department id: the item rows of each entry of the
    configuration's ``departments`` list, laid end to end in its order."""
    starts = np.concatenate(([0], np.cumsum([d["items"] for d in departments])))
    return [(int(lo), int(hi)) for lo, hi in zip(starts[:-1], starts[1:])]


def department_of_rows(departments: list) -> np.ndarray:
    """[items] int32: the department id of every item row."""
    return np.repeat(np.arange(len(departments), dtype=np.int32),
                     [d["items"] for d in departments])


def exact_topk(user_vecs, table, seen, k: int, lo: int, hi: int, *,
               dtype=np.float32):
    """(the K best scores [n, k], descending; the scores [n, hi - lo] of
    every row of the range, seen rows at -inf) of users against the rows
    ``[lo, hi)`` of ``table``.  ``seen[i]`` are user ``i``'s rated item rows,
    anywhere in the table."""
    u = np.asarray(user_vecs, dtype)
    scores = u @ np.asarray(table[lo:hi], dtype).T
    for i, s in enumerate(seen):
        s = np.asarray(s)
        scores[i, s[(s >= lo) & (s < hi)] - lo] = -np.inf
    n = hi - lo
    part = np.partition(scores, n - k, axis=1)[:, n - k:]
    return -np.sort(-part.astype(np.float32), axis=1), scores


def topk_gaps(ids, vals, best, scores, lo: int):
    """(rank_gap, score_err) of served ``ids``/``vals`` [n, k] against the
    exact top-K over the range that starts at row ``lo``, as
    ``reference.topk_gaps`` computes them over the whole table: how far the
    exact score at a served id lies below the exact j-th best (ties cost
    nothing), and how far a served score lies from the exact score at its
    id, both as a share of max(|exact|, 1), worst over the sample.  A set
    that holds an id outside the range has no score there: it is
    ``invalid_id_sets``' to count, over every answer, and is left out
    here."""
    at = np.asarray(ids, np.int64) - lo
    inside = ((at >= 0) & (at < scores.shape[1])).all(axis=1)
    if not inside.any():
        return 0.0, 0.0
    at, vals, best, scores = at[inside], vals[inside], best[inside], scores[inside]
    served = np.take_along_axis(scores, at, axis=1)
    have = -np.sort(-served.astype(np.float64), axis=1)
    want = best.astype(np.float64)
    rank_gap = float(np.max((want - have) / np.maximum(np.abs(want), 1.0)))
    score_err = float(np.max(
        np.abs(vals.astype(np.float64) - served)
        / np.maximum(np.abs(served.astype(np.float64)), 1.0)))
    return max(rank_gap, 0.0), score_err


def invalid_id_sets(ids, seen, bounds, k: int) -> int:
    """How many served id sets are not 'K distinct unseen rows of the range
    ``bounds[i]`` = (lo, hi)' (the named department's, or the whole table's
    for a request that named none)."""
    bad = 0
    for row, s, (lo, hi) in zip(ids, seen, bounds):
        row = np.asarray(row)
        ok = (row.size == k and row.min() >= lo and row.max() < hi
              and np.unique(row).size == k and not np.isin(row, s).any())
        bad += not ok
    return bad
