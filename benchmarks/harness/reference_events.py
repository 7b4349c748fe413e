"""Plain reference of a stream whose order is the events', not the
arrivals': what a user's list is as of a commit ordinal when records may be
appended after newer ones of their cell, in numpy, importing nothing of the
program.

From the base CSR and the events AS SENT (arrival order, each with its
event ``seq`` and the commit ordinal of the unit that consumed it):

- a cell holds the rating of the event with the highest ``seq`` among those
  committed at or before the ordinal, whatever order they arrived in, over
  its base rating (which every event outranks);
- an event is OUTRANKED, and changes nothing, when an event of its cell that
  arrived before it carries a ``seq`` at least as high (an equal ``seq`` is
  a retried append).  It is still consumed and committed, once.

A user's row as of an ordinal is ``reference_foldin.solve_row`` over that
list, unchanged: the float64 solve of its ALS-WR normal equations.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness.reference_foldin import (  # noqa: F401  by import
    row_err, solve_row)


def outranked(users, items, seqs) -> np.ndarray:
    """Which events (in arrival order) are outranked: an earlier arrival of
    the same (user, item) carries a ``seq`` at least as high."""
    users = np.asarray(users, np.int64)
    items = np.asarray(items, np.int64)
    seqs = np.asarray(seqs, np.int64)
    n = users.shape[0]
    out = np.zeros(n, bool)
    if not n:
        return out
    order = np.lexsort((np.arange(n), items, users))  # cell, then arrival
    u, i, s = users[order], items[order], seqs[order]
    start = np.ones(n, bool)
    start[1:] = (u[1:] != u[:-1]) | (i[1:] != i[:-1])
    # the highest seq among the cell's earlier arrivals: a running maximum
    # that starts anew at every cell
    group = np.cumsum(start) - 1
    span = np.int64(s.max() - s.min() + 1)
    lifted = (s - s.min()) + group * span  # groups apart, order kept within
    before = np.maximum.accumulate(lifted)
    prev = np.empty(n, np.int64)
    prev[0], prev[1:] = -1, before[:-1]
    prev[start] = -1
    out[order] = ~start & (prev >= lifted)
    return out


def winners(users, items, seqs) -> np.ndarray:
    """The index (in arrival order) of each written cell's winning event:
    the highest ``seq``, the earliest arrival among equals; one a cell,
    sorted by (user, item)."""
    users = np.asarray(users, np.int64)
    items = np.asarray(items, np.int64)
    seqs = np.asarray(seqs, np.int64)
    n = users.shape[0]
    if not n:
        return np.zeros(0, np.int64)
    order = np.lexsort((-np.arange(n), seqs, items, users))
    u, i = users[order], items[order]
    last = np.ones(n, bool)
    last[:-1] = (u[1:] != u[:-1]) | (i[1:] != i[:-1])
    return order[last]


def list_as_of(base_items, base_ratings, events, ordinal: int):
    """(item rows ascending, ratings) of one user's list as of a commit
    ordinal.  ``events``: the user's ``(item, rating, seq, commit
    ordinal)`` in the order they ARRIVED; those committed after ``ordinal``
    are left out, and of a cell's others the highest ``seq`` wins (the
    earlier arrival among equal ones)."""
    cells = {int(i): (float(r), -1) for i, r in
             zip(np.asarray(base_items).tolist(),
                 np.asarray(base_ratings).tolist())}
    for item, rating, seq, committed in events:
        if committed <= ordinal:
            held = cells.get(int(item))
            if held is None or int(seq) > held[1]:
                cells[int(item)] = (float(rating), int(seq))
    items = np.asarray(sorted(cells), np.int64)
    return items, np.asarray([cells[i][0] for i in items.tolist()],
                             np.float64)
