"""Seeded seen lists with a power-law tail: P(length = n) proportional to
n^-s for n in 1..max_len DISTINCT items, s solved so that the mean is the
catalogue's; items by the control's popularity law (``datagen.seen_lists``:
log-uniform in popularity rank over a random permutation of the item rows).

``datagen.seen_lists`` draws geometric lengths cut at 64 and drops a list's
repeated draws; here a repeated draw is topped up, so a list holds exactly
the length it drew (a reviewer with 10,000 items holds most of the popular
head: a third of its draws repeat).
"""

from __future__ import annotations

import numpy as np

_ITEM_BITS = 24


def solve_exponent(mean_len: float, max_len: int) -> float:
    """The s for which sum(n * n^-s) / sum(n^-s) over 1..max_len is
    ``mean_len``, by bisection (the mean falls as s grows)."""
    n = np.arange(1, max_len + 1, dtype=np.float64)

    def mean(s):
        w = n ** -s
        return float((n * w).sum() / w.sum())

    lo, hi = 1.0, 4.0
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if mean(mid) > mean_len else (lo, mid)
    return 0.5 * (lo + hi)


def _draw_items(rng, perm, items: int, n: int) -> np.ndarray:
    rank = np.exp(rng.random(n) * np.log(items)).astype(np.int64) - 1
    return perm[np.clip(rank, 0, items - 1)]


def _held(sorted_keys: np.ndarray, keys: np.ndarray) -> np.ndarray:
    at = np.searchsorted(sorted_keys, keys)
    at[at == sorted_keys.shape[0]] = 0
    return sorted_keys[at] == keys if sorted_keys.shape[0] else np.zeros(
        keys.shape[0], bool)


def seen_lists(users: int, items: int, *, exponent: float, max_len: int,
               seed: int, tile_m: int = 512):
    """(item rows, indptr, facts): the CSR (items ascending per user, no
    item twice) and what the configuration states of it: the realized
    cells, the longest list, how many users hold more than 64 / 1,024 /
    4,096 cells and the share of the cells that are theirs, and the most
    cells one user has inside one scorer tile of ``tile_m`` rows."""
    if items > 1 << _ITEM_BITS:
        raise ValueError("seen_lists packs item rows into 24 bits")
    rng = np.random.default_rng(seed)
    n = np.arange(1, max_len + 1)
    p = n.astype(np.float64) ** -exponent
    lens = rng.choice(n, size=users, p=p / p.sum()).astype(np.int64)
    perm = rng.permutation(items).astype(np.int64)
    user = np.repeat(np.arange(users, dtype=np.int64), lens)
    keys = np.unique((user << _ITEM_BITS)
                     | _draw_items(rng, perm, items, user.shape[0]))
    del user
    extra = np.zeros(0, np.int64)  # the top-ups, ascending
    have = np.bincount(keys >> _ITEM_BITS, minlength=users)
    rounds = 0
    while True:
        short = np.flatnonzero(have < lens)
        if not short.size:
            break
        rounds += 1
        need = lens[short] - have[short]
        # twice the need and a few: a heavy list's draws mostly repeat
        who = np.repeat(short, 2 * need + 4)
        cand = (who << _ITEM_BITS) | _draw_items(rng, perm, items,
                                                 who.shape[0])
        # first draw of each key, in draw order; none the list holds
        _, first = np.unique(cand, return_index=True)
        cand = cand[np.sort(first)]
        cand = cand[~(_held(keys, cand) | _held(extra, cand))]
        owner = cand >> _ITEM_BITS
        # the first ``need`` of each user, in draw order
        order = np.argsort(owner, kind="stable")
        owner, cand = owner[order], cand[order]
        start = np.searchsorted(owner, owner)
        room = (lens - have)[owner]
        take = cand[np.arange(cand.shape[0]) - start < room]
        extra = np.union1d(extra, take)
        have += np.bincount(take >> _ITEM_BITS, minlength=users)
    keys = np.concatenate([keys, extra])
    keys.sort()
    if not np.array_equal(np.bincount(keys >> _ITEM_BITS, minlength=users),
                          lens):
        raise AssertionError("a list does not hold the length it drew")
    indptr = np.zeros(users + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    item = (keys & ((1 << _ITEM_BITS) - 1)).astype(np.int32)
    # cells of one user inside one tile: runs of equal (user, tile)
    tiles = -(-items // tile_m)
    cell_tile = (keys >> _ITEM_BITS) * tiles + item // tile_m
    edge = np.flatnonzero(np.concatenate(
        ([True], cell_tile[1:] != cell_tile[:-1], [True])))
    total = int(lens.sum())
    facts = {"cells": total, "longest": int(lens.max()),
             "top_up_rounds": rounds, "topped_up": int(extra.shape[0]),
             "most_cells_a_user_a_tile": int(np.diff(edge).max())}
    for cut in (64, 1024, 4096):
        heavy = lens > cut
        facts[f"users_over_{cut}"] = int(heavy.sum())
        facts[f"cell_share_over_{cut}"] = float(lens[heavy].sum() / total)
    return item, indptr, facts
