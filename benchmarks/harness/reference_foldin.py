"""Plain reference of a streamed fold-in: what a user's vector and answer
are as of a commit ordinal, in numpy, importing nothing of the program.

(a) A touched user's row is the float64 solve of its own ALS-WR normal
equations over the item rows of the float32 table and the user's list as of
the ordinal:

    (Y_S^T Y_S + lam n I) u = Y_S^T r,    n = |S|

(Zhou, Wilkinson, Schreiber, Pan, *Large-scale Parallel Collaborative
Filtering for the Netflix Prize*, 2008: the weighted-lambda regulariser),
with S the user's base list and every rating of the user committed at or
before the ordinal, a later rating of an item replacing an earlier one.

(b) That user's answer is the exact float32 top-K of the vector with the
list as of the ordinal excluded: ``reference.exact_topk`` / ``topk_gaps``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness.reference import (  # noqa: F401  (b), by import
    exact_topk, invalid_id_sets, topk_gaps)


def list_as_of(base_items, base_ratings, streamed, ordinal: int):
    """(item rows ascending, ratings) of one user's list as of a commit
    ordinal: the base cells, then the ``streamed`` cells ``(item, rating,
    commit ordinal)`` in the order they were sent, those committed after
    ``ordinal`` left out; a later cell of an item replaces an earlier one."""
    cells = dict(zip(np.asarray(base_items).tolist(),
                     np.asarray(base_ratings).tolist()))
    for item, rating, committed in streamed:
        if committed <= ordinal:
            cells[int(item)] = float(rating)
    items = np.asarray(sorted(cells), np.int64)
    return items, np.asarray([cells[i] for i in items.tolist()], np.float64)


def solve_row(table, items, ratings, lam: float, *, dtype=np.float64):
    """The row of (a).  ``dtype`` is the precision the gathered item rows
    and their Gram pass through before the float64 solve: float64 for the
    reference, a narrower one for the lower-precision control."""
    y = np.asarray(table[np.asarray(items, np.int64)]).astype(dtype)
    r = np.asarray(ratings, np.float64)
    gram = (y.T @ y).astype(np.float64)
    rhs = (y.T @ r.astype(dtype)).astype(np.float64)
    n = max(len(r), 1)
    return np.linalg.solve(gram + lam * n * np.eye(y.shape[1]), rhs)


def row_err(served, exact) -> float:
    """How far a folded-in row lies from the reference's, as a share of the
    row's largest entry (at least 1): worst entry."""
    served = np.asarray(served, np.float64)
    exact = np.asarray(exact, np.float64)
    return float(np.max(np.abs(served - exact))
                 / max(float(np.max(np.abs(exact))), 1.0))
