"""Seeded data, owned by the benchmark: the seen lists of a catalogue, factor
tables, request users.

The catalogue itself cannot be fetched here, so its seen lists are generated
at its published shape (users x items x ratings); what plays the part of
weights and traffic is drawn from ``--seed``.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np


def seen_lists(users: int, items: int, mean_len: float, max_len: int, *,
               seed: int):
    """CSR (items sorted ascending per user, no duplicates) of what each
    user has already rated: list lengths 1 + geometric with the catalogue's
    mean, cut at ``max_len``; items log-uniform in popularity rank (Zipf,
    exponent 1) over a random permutation of the item rows."""
    rng = np.random.default_rng(seed)
    lens = np.minimum(rng.geometric(1.0 / mean_len, size=users), max_len)
    total = int(lens.sum())
    user = np.repeat(np.arange(users, dtype=np.int64), lens)
    rank = np.exp(rng.random(total) * np.log(items)).astype(np.int64) - 1
    perm = rng.permutation(items).astype(np.int64)
    key = np.unique((user << 24) | perm[np.clip(rank, 0, items - 1)])
    if items > 1 << 24:
        raise ValueError("seen_lists packs item rows into 24 bits")
    item = (key & ((1 << 24) - 1)).astype(np.int32)
    counts = np.bincount(key >> 24, minlength=users)
    indptr = np.zeros(users + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    return item, indptr


def factor_table(rows: int, rank: int, *, seed: int, scale: float,
                 threads: int | None = None) -> np.ndarray:
    """[rows, rank] float32, uniform in ±scale/2, filled block by block on the
    host's cores: the engine takes host arrays, so a table made on the device
    would only be copied back (PERF.md, set-up)."""
    out = np.empty((rows, rank), np.float32)
    threads = threads or max(1, min(16, (os.cpu_count() or 2) - 1))
    block = 1 << 18
    starts = range(0, rows, block)
    seeds = np.random.SeedSequence(seed).spawn(len(starts))

    def fill(i_lo):
        i, lo = i_lo
        view = out[lo:lo + block]
        np.random.default_rng(seeds[i]).random(
            view.shape, dtype=np.float32, out=view)
        view -= 0.5
        view *= scale

    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, enumerate(starts)))
    return out


def zipf_users(num_users: int, n: int, *, seed: int, a: float) -> np.ndarray:
    """``n`` request users, Zipf(a) over the row space (as
    ``cfk_tpu/serving/loadgen.py::zipf_user_rows``)."""
    rng = np.random.default_rng(seed)
    return ((rng.zipf(a, size=n) - 1) % num_users).astype(np.int64)
