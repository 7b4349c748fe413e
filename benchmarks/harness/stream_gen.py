"""Seeded data of a stream of ratings over a catalogue's seen lists: the
base ratings' values, the streamed ratings, and the follow-up requests.

What the source does not fix is the configuration's to state under
``assumed``; this file only draws it.
"""

from __future__ import annotations

import numpy as np

# P(1 star) .. P(5 stars): the J-shape of Amazon reviews
STARS = np.array([0.09, 0.05, 0.09, 0.19, 0.58])


def rating_values(n: int, *, seed: int, block: int = 1 << 24) -> np.ndarray:
    """``n`` ratings 1..5 as float32, drawn by ``STARS``, block by block."""
    rng = np.random.default_rng(seed)
    cum = np.cumsum(STARS)
    cum[-1] = 1.0
    out = np.empty(n, np.float32)
    for lo in range(0, n, block):
        view = out[lo:lo + block]
        view[:] = np.searchsorted(
            cum, rng.random(view.shape[0], dtype=np.float32), side="right") + 1
    np.clip(out, 1.0, 5.0, out=out)
    return out


def stream_ratings(indptr, items, n: int, *, seed: int, new_user_share: float):
    """``n`` streamed ratings in the order they are sent: (user raw ids,
    item rows, ratings, which are from users not in the base).

    A rating's user is the user of a uniformly drawn base cell (activity
    weighted); a ``new_user_share`` of them come instead from users the base
    has never seen (raw ids past its rows, a few ratings each).  Its item is
    the item of another uniformly drawn base cell (the corpus's own
    popularity) redrawn while the user's base list, or an earlier rating of
    this stream, already holds it."""
    rng = np.random.default_rng(seed)
    users, total = indptr.shape[0] - 1, int(indptr[-1])
    user = (np.searchsorted(indptr, rng.integers(0, total, n), side="right")
            - 1).astype(np.int64)
    new = rng.random(n) < new_user_share
    # a new user sends about three ratings: ids drawn from a third as many
    fresh = users + rng.integers(0, max(int(new.sum()) // 3, 1), n)
    user = np.where(new, fresh, user)
    item = np.asarray(items[rng.integers(0, total, n)], np.int32)
    taken: set = set()
    for i in range(n):
        u = int(user[i])
        lo, hi = (int(indptr[u]), int(indptr[u + 1])) if u < users else (0, 0)
        mine = items[lo:hi]
        while True:
            it = int(item[i])
            at = int(np.searchsorted(mine, it))
            if not (at < hi - lo and int(mine[at]) == it) \
                    and (u, it) not in taken:
                break
            item[i] = items[int(rng.integers(0, total))]
        taken.add((u, int(item[i])))
    return user, item, rating_values(n, seed=seed + 1), new


def with_followups(users, *, rate: float, rating_users, rating_new,
                   rating_rate: float, share: float, delay_s: float,
                   seed: int):
    """The request users with a ``share`` of them replaced by follow-ups:
    request ``i``, due at ``i / rate``, asks for the user of the rating
    due ``delay_s`` earlier (the user who rated looks at the next page).
    A request with no such rating yet, or whose rating came from a user not
    in the base, keeps its own user.  Returns (users, which are follow-ups)."""
    rng = np.random.default_rng(seed)
    users = np.array(users, np.int64)
    want = rng.random(users.shape[0]) < share
    j = np.floor((np.arange(users.shape[0]) / rate - delay_s)
                 * rating_rate).astype(np.int64)
    ok = want & (j >= 0) & (j < len(rating_users))
    ok[ok] &= ~np.asarray(rating_new)[j[ok]]
    users[ok] = np.asarray(rating_users)[j[ok]]
    return users, ok
