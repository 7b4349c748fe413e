"""Plain numpy reference of (sharded) exact top-K serving: it imports
nothing of the program.

Float32 scores of each user against every item row, the user's seen rows
masked, the K largest — computed block by block over the item rows, as the
shards of a row-sharded table would, keeping a running top-K and the exact
score at each served id, so its memory is users × block whatever the
catalogue's size.  ``benchmarks/harness/reference_blocks.py`` is the
benchmark's own copy.

The quantized case (``quantized_topk``): an int8 row-quantized table serves
the exact top-K of its DEQUANTIZED view — codes and scales by the written
rule, code × scale in float32 — so the reference quantizes by the rule,
dequantizes and runs the same blockwise top-K over that
(``benchmarks/harness/reference_q8.py`` is the benchmark's own copy).
"""

from __future__ import annotations

import numpy as np


def exact_topk_blocks(user_vecs, table, seen, k: int, served_ids=None, *,
                      block: int = 1 << 20):
    """(best [n, k] float32 descending, best_ids [n, k] int64, at).

    ``seen[i]`` are user ``i``'s rated item rows (masked out).  ``at``
    [n, j] is the exact score at ``served_ids[i, j]`` (−inf at a seen or
    out-of-range row), or None without ``served_ids``.  Equal scores keep
    the lower row, as a scan in ascending row order does; fewer than K
    candidates leave −inf / −1 at the tail.
    """
    u = np.asarray(user_vecs, np.float32)
    n, items = u.shape[0], table.shape[0]
    best = np.full((n, k), -np.inf, np.float32)
    best_ids = np.full((n, k), -1, np.int64)
    at = None
    if served_ids is not None:
        served_ids = np.asarray(served_ids, np.int64)
        at = np.full(served_ids.shape, -np.inf, np.float32)
    seen = [np.asarray(s, np.int64) for s in seen]
    for lo in range(0, items, block):
        hi = min(lo + block, items)
        scores = u @ np.asarray(table[lo:hi], np.float32).T  # [n, hi - lo]
        for i, s in enumerate(seen):
            scores[i, s[(s >= lo) & (s < hi)] - lo] = -np.inf
        if at is not None:
            r, c = np.nonzero((served_ids >= lo) & (served_ids < hi))
            at[r, c] = scores[r, served_ids[r, c] - lo]
        if lo == 0:
            take = min(k, hi - lo)
            cols = np.argpartition(scores, hi - lo - take, axis=1)[:, -take:]
            rows = np.repeat(np.arange(n), take)
            cols = cols.ravel()
        else:  # only what beats the running K-th best can enter
            rows, cols = np.nonzero(scores > best[:, -1:])
        # the running selection and the block's candidates, re-selected:
        # by user, then score descending, then row ascending
        cand_v = np.concatenate([best.ravel(), scores[rows, cols]])
        cand_i = np.concatenate([best_ids.ravel(), cols + lo])
        cand_u = np.concatenate([np.repeat(np.arange(n), k), rows])
        cand_i[np.isneginf(cand_v)] = -1  # a masked row is no candidate
        empty = cand_i < 0
        order = np.lexsort((np.where(empty, items, cand_i), -cand_v, cand_u))
        cand_v, cand_i, cand_u = cand_v[order], cand_i[order], cand_u[order]
        first = np.searchsorted(cand_u, np.arange(n))
        pick = first[:, None] + np.arange(k)[None]  # each user has >= k
        best, best_ids = cand_v[pick], cand_i[pick]
    return best, best_ids, at


def topk_gaps(vals, best, at):
    """(rank_gap, score_err) of served scores ``vals`` [n, k] whose exact
    scores are ``at``, against the exact top-K ``best``: how far the exact
    score at a served id lies under the exact j-th best (ties cost nothing;
    a seen id costs everything), and how far a served score lies from the
    exact score at its id, both over max(|exact|, 1), worst over the
    sample."""
    got = -np.sort(-at.astype(np.float64), axis=1)
    want = best.astype(np.float64)
    rank_gap = float(np.max((want - got) / np.maximum(np.abs(want), 1.0)))
    exact = at.astype(np.float64)
    with np.errstate(invalid="ignore"):
        err = np.abs(vals.astype(np.float64) - exact) / np.maximum(
            np.abs(exact), 1.0)
    score_err = float(np.max(np.where(np.isfinite(exact), err, np.inf)))
    return max(rank_gap, 0.0), score_err


def quantize_rows(f):
    """(codes [n, k] int8, scales [n] float32) by the written rule: a row's
    scale is its largest magnitude over 127 in float32 (1.0 for an all-zero
    row); a code is the row over its scale, rounded half to even and clipped
    to ±127."""
    f = np.asarray(f, np.float32)
    amax = np.max(np.abs(f), axis=1)
    scales = np.where(amax == 0, np.float32(1.0),
                      amax / np.float32(127.0)).astype(np.float32)
    codes = np.clip(np.rint(f / scales[:, None]), -127, 127).astype(np.int8)
    return codes, scales


def dequantize_rows(codes, scales):
    """The float32 view a quantized table's answers are exact against."""
    return codes.astype(np.float32) * scales[:, None]


def quantized_topk(user_vecs, table, seen, k: int, served_ids=None, *,
                   block: int = 1 << 20):
    """``exact_topk_blocks`` over the dequantized view of ``table``'s int8
    row quantization: what ``ServeEngine(table_dtype="int8")`` must answer."""
    return exact_topk_blocks(
        user_vecs, dequantize_rows(*quantize_rows(table)), seen, k,
        served_ids, block=block)
