"""Seen lists of a catalogue of any size, built in blocks of users.

``datagen.seen_lists`` packs (user, item) into one 64-bit key with 24 bits
for the item and sorts all cells at once; past 16.7 M items it raises.  This
is the same law — list lengths geometric from 1 with the catalogue's mean,
cut at ``max_len``; items log-uniform in popularity rank (Zipf, exponent 1)
over a seeded permutation of the item rows; a user's duplicates dropped —
drawn block by block (each block of users from its own child seed, so the
lists do not depend on the thread count), on the host's cores.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np


def seen_lists_blocks(users: int, items: int, mean_len: float, max_len: int,
                      *, seed: int, users_per_block: int = 1 << 20,
                      threads: int | None = None):
    """CSR (items int32 sorted ascending per user, no duplicates; indptr
    int64 [users + 1]) of what each user has already rated."""
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
    log_items = np.log(items)

    def block(i_lo):
        i, lo = i_lo
        n = min(users_per_block, users - lo)
        rng = np.random.default_rng(seeds[i])
        lens = np.minimum(rng.geometric(1.0 / mean_len, size=n), max_len)
        user = np.repeat(np.arange(n, dtype=np.int64), lens)
        rank = np.exp(rng.random(user.size) * log_items).astype(np.int64) - 1
        item = perm[np.clip(rank, 0, items - 1)].astype(np.int64)
        key = np.unique((user << bits) | item)
        counts = np.bincount(key >> bits, minlength=n)
        return (key & ((1 << bits) - 1)).astype(np.int32), counts

    threads = threads or max(1, min(16, (os.cpu_count() or 2) - 1))
    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        parts = list(pool.map(block, enumerate(starts)))
    indptr = np.zeros(users + 1, np.int64)
    np.cumsum(np.concatenate([c for _, c in parts]), out=indptr[1:])
    return np.concatenate([it for it, _ in parts]), indptr
