"""``seen_tail.seen_lists``'s law over a catalogue of any size, built a block
of users at a time: P(length = n) proportional to n^-s for n in 1..max_len
DISTINCT items; items log-uniform in popularity rank over a seeded
permutation of the item rows; a list's repeated draws topped up, so that a
list holds exactly the length it drew.

``seen_tail.seen_lists`` packs (user, item) into 24 bits of item and sorts
every cell at once: past 16.7 M items it raises, and 419 M cells of int64
keys with their sort's temporaries do not fit beside a 20 GB user table.
Here each block of ``users_per_block`` users is drawn from its own child
seed (so the lists do not depend on the thread count), holds ~11 M cells
while it is made, and leaves its int32 item rows and its facts behind, as
``seen_blocks.seen_lists_blocks`` does for the geometric law.
"""

from __future__ import annotations

import concurrent.futures

import numpy as np

CUTS = (64, 1024, 4096)


def _held(sorted_keys: np.ndarray, keys: np.ndarray) -> np.ndarray:
    if not sorted_keys.shape[0]:
        return np.zeros(keys.shape[0], bool)
    at = np.searchsorted(sorted_keys, keys)
    at[at == sorted_keys.shape[0]] = 0
    return sorted_keys[at] == keys


def _block(rng, perm, lens: np.ndarray, items: int, bits: int, tile_m: int):
    """One block's (item rows int32 in (user, item) order, top-up rounds,
    cells topped up, the most cells one user has inside one scorer tile)."""
    users = lens.shape[0]
    log_items = np.log(items)

    def draw(n):
        rank = np.exp(rng.random(n) * log_items).astype(np.int64) - 1
        return perm[np.clip(rank, 0, items - 1)].astype(np.int64)

    user = np.repeat(np.arange(users, dtype=np.int64), lens)
    keys = np.unique((user << bits) | draw(user.shape[0]))
    del user
    extra = np.zeros(0, np.int64)  # the top-ups, ascending
    have = np.bincount(keys >> bits, minlength=users)
    rounds = 0
    while True:
        short = np.flatnonzero(have < lens)
        if not short.size:
            break
        rounds += 1
        need = lens[short] - have[short]
        # twice the need and a few: a heavy list's draws mostly repeat
        who = np.repeat(short, 2 * need + 4)
        cand = (who << bits) | draw(who.shape[0])
        # first draw of each key, in draw order; none the list holds
        _, first = np.unique(cand, return_index=True)
        cand = cand[np.sort(first)]
        cand = cand[~(_held(keys, cand) | _held(extra, cand))]
        owner = cand >> bits
        # the first ``need`` of each user, in draw order
        order = np.argsort(owner, kind="stable")
        owner, cand = owner[order], cand[order]
        start = np.searchsorted(owner, owner)
        room = (lens - have)[owner]
        take = cand[np.arange(cand.shape[0]) - start < room]
        extra = np.union1d(extra, take)
        have += np.bincount(take >> bits, minlength=users)
    topped = int(extra.shape[0])
    keys = np.concatenate([keys, extra])
    keys.sort()
    if not np.array_equal(np.bincount(keys >> bits, minlength=users), lens):
        raise AssertionError("a list does not hold the length it drew")
    item = (keys & ((1 << bits) - 1)).astype(np.int32)
    # cells of one user inside one tile: runs of equal (user, tile)
    tiles = -(-items // tile_m)
    cell_tile = (keys >> bits) * tiles + item // tile_m
    del keys
    edge = np.flatnonzero(np.concatenate(
        ([True], cell_tile[1:] != cell_tile[:-1], [True])))
    return item, rounds, topped, int(np.diff(edge).max())


def seen_lists_blocks(users: int, items: int, *, exponent: float,
                      max_len: int, seed: int, tile_m: int = 512,
                      users_per_block: int = 1 << 20, threads: int = 3,
                      keep_items: bool = True):
    """(item rows int32, indptr int64 [users + 1], facts): the CSR (items
    ascending per user, no item twice) and what a configuration states of
    it: the realized cells, the longest list, the users holding more than
    64 / 1,024 / 4,096 cells and their share of the cells, the
    activity-weighted mean list (a cell's user drawn uniformly over the
    cells: sum of squares over sum), and the most cells one user has inside
    one scorer tile of ``tile_m`` rows.  ``keep_items=False`` keeps the
    facts alone (item rows None): what writing a configuration needs."""
    if items >= 1 << 31:
        raise ValueError("item rows are kept as int32")
    bits = max(int(items - 1).bit_length(), 1)
    if (users_per_block - 1).bit_length() + bits > 62:
        raise ValueError("a block's (user, item) keys must fit 62 bits")
    root = np.random.SeedSequence(seed)
    perm = np.random.default_rng(root.spawn(1)[0]).permutation(items).astype(
        np.int32)
    starts = range(0, users, users_per_block)
    seeds = root.spawn(len(starts))
    n = np.arange(1, max_len + 1)
    prob = n.astype(np.float64) ** -exponent
    prob /= prob.sum()

    def block(i_lo):
        i, lo = i_lo
        rng = np.random.default_rng(seeds[i])
        lens = rng.choice(n, size=min(users_per_block, users - lo),
                          p=prob).astype(np.int64)
        item, rounds, topped, per_tile = _block(rng, perm, lens, items, bits,
                                                tile_m)
        return (item if keep_items else None), lens, rounds, topped, per_tile

    with concurrent.futures.ThreadPoolExecutor(max(threads, 1)) as pool:
        parts = list(pool.map(block, enumerate(starts)))
    lens = np.concatenate([p[1] for p in parts])
    indptr = np.zeros(users + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    total = int(indptr[-1])
    facts = {"cells": total, "longest": int(lens.max()),
             "top_up_rounds": max(p[2] for p in parts),
             "topped_up": sum(p[3] for p in parts),
             "most_cells_a_user_a_tile": max(p[4] for p in parts),
             "activity_weighted_mean_list": float(
                 np.square(lens.astype(np.float64)).sum() / total)}
    for cut in CUTS:
        heavy = lens > cut
        facts[f"users_over_{cut}"] = int(heavy.sum())
        facts[f"cell_share_over_{cut}"] = float(lens[heavy].sum() / total)
    out = None
    if keep_items:
        out = np.empty(total, np.int32)
        for i, lo in enumerate(indptr[::users_per_block][:len(parts)]):
            item, parts[i] = parts[i][0], None  # one copy of a block at a time
            out[lo:lo + item.shape[0]] = item
    return out, indptr, facts
