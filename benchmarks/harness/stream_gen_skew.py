"""Seeded events of a stream under the skew its users have: activity-weighted
users over heavy-tailed lists, a hot set that moves, re-ratings of held
cells (some as two events on one cell), and events that arrive later than
their place in event order.

``stream_gen.stream_ratings`` sends every (user, item) once, in order, on
time; here an event has a place in EVENT order (its ``seq``) and a place in
ARRIVAL order (where it is sent), and the two differ for the late ones.
What the source does not fix is the configuration's to state under
``assumed``; this file only draws it.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.harness.stream_gen import rating_values


@dataclasses.dataclass
class Events:
    """The stream in ARRIVAL order (the order it is sent in)."""

    users: np.ndarray   # raw ids; past the base's rows: a user not in it
    items: np.ndarray   # item rows
    values: np.ndarray  # float32 ratings 1..5
    seqs: np.ndarray    # the event's sequence number (its place in event order)
    new: np.ndarray     # from a user not in the base
    rerate: np.ndarray  # names a cell its user already holds
    late: np.ndarray    # sent later than its place in event order


def stream_events(indptr, items, n: int, *, seed: int, rating_rate: float,
                  new_user_share: float, hot_share: float, hot_users: int,
                  hot_period_s: float, rerate_share: float,
                  rerate_pair_share: float, rerate_pair_gap_s: float,
                  late_share: float, late_by_s, seq0: int = 0) -> Events:
    """``n`` events.  Event j (event order; due j / ``rating_rate`` seconds
    into the window; ``seq`` = ``seq0`` + j):

    - its user is the user of a uniformly drawn base cell (activity
      weighted); a ``hot_share`` come instead from the hot set of its
      period (``hot_users`` activity-weighted draws, redrawn every
      ``hot_period_s``); a ``new_user_share`` from users the base has never
      seen (raw ids past its rows, about three events each);
    - a ``rerate_share`` name a cell their user already holds (a base cell
      or one an earlier event of this stream wrote, uniformly) with a new
      value; a ``rerate_pair_share`` of THOSE come as two events on one
      cell, the second ``rerate_pair_gap_s`` later in event order with
      another value; every other event names a new (user, item) once, the
      item the item of a uniformly drawn base cell, redrawn while held;
    - a ``late_share`` are SENT later than their place by a delay uniform in
      ``late_by_s`` seconds, keeping their ``seq``.
    """
    rng = np.random.default_rng(seed)
    users_n, total = indptr.shape[0] - 1, int(indptr[-1])

    def active(k):
        return (np.searchsorted(indptr, rng.integers(0, total, k),
                                side="right") - 1).astype(np.int64)

    user = active(n)
    period = (np.arange(n) / rating_rate // hot_period_s).astype(np.int64)
    hot_sets = active((int(period[-1]) + 1 if n else 0) * hot_users).reshape(
        -1, hot_users)
    hot = rng.random(n) < hot_share
    user[hot] = hot_sets[period[hot], rng.integers(0, hot_users,
                                                   int(hot.sum()))]
    new = rng.random(n) < new_user_share
    user[new] = users_n + rng.integers(0, max(int(new.sum()) // 3, 1),
                                       int(new.sum()))
    # which events re-rate: single ones, and the first of a pair, whose
    # partner ``gap`` events later is made to name the same cell
    gap = max(int(round(rerate_pair_gap_s * rating_rate)), 1)
    draw = rng.random(n)
    lead = draw < rerate_share * rerate_pair_share / 2
    lead[max(n - gap, 0):] = False
    single = ~lead & (draw < rerate_share * (1 - rerate_pair_share / 2))
    leader_of = np.full(n, -1, np.int64)
    leader_of[np.flatnonzero(lead) + gap] = np.flatnonzero(lead)
    value = rating_values(n, seed=seed + 1)
    item = np.asarray(items[rng.integers(0, total, n)], np.int32)
    pick = rng.random(n)
    rerate = np.zeros(n, bool)
    wrote: dict[int, list] = {}
    taken: set = set()
    for j in range(n):
        if leader_of[j] >= 0:
            i = int(leader_of[j])
            if rerate[i]:  # its leader found a cell to re-rate
                user[j], item[j], new[j] = user[i], item[i], new[i]
                value[j] = value[i] % 5 + 1
                rerate[j] = True
                continue
        u = int(user[j])
        lo, hi = (int(indptr[u]), int(indptr[u + 1])) if u < users_n else (0, 0)
        mine = items[lo:hi]
        if lead[j] or single[j]:
            extra = wrote.get(u, ())
            held = hi - lo + len(extra)
            if held:
                at = int(pick[j] * held)
                item[j] = mine[at] if at < hi - lo else extra[at - (hi - lo)]
                rerate[j] = True
                continue
        while True:
            it = int(item[j])
            at = int(np.searchsorted(mine, it))
            if not (at < hi - lo and int(mine[at]) == it) \
                    and (u, it) not in taken:
                break
            item[j] = items[int(rng.integers(0, total))]
        taken.add((u, int(item[j])))
        wrote.setdefault(u, []).append(int(item[j]))
    late = rng.random(n) < late_share
    lo_s, hi_s = late_by_s
    delay = np.where(late, rng.uniform(lo_s, hi_s, n) * rating_rate, 0.0)
    arrival = np.argsort(np.arange(n) + delay, kind="stable")
    seqs = seq0 + np.arange(n, dtype=np.int64)
    return Events(users=user[arrival], items=item[arrival],
                  values=value[arrival], seqs=seqs[arrival],
                  new=new[arrival], rerate=rerate[arrival],
                  late=late[arrival])
