"""Plain numpy reference of a row-quantized int8 item table: it imports
nothing of the program.

The written rule (``amazon23-serve-r128-int8``'s guarantee): a row's scale is
its largest magnitude over 127 in float32 (1.0 for an all-zero row), a code
is the row over its scale rounded half to even and clipped to +-127, and the
table that answers are exact against is the DEQUANTIZED view, code x scale in
float32.  ``DequantizedBlocks`` is that view of the seeded item factors as a
lazy table (``shape`` and ``table[lo:hi]``), so
``reference_blocks.exact_topk_blocks`` runs over it unedited and no host
buffer ever holds the table; ``FactorBlocks`` is the float32 factors
themselves, the same numbers ``datagen.factor_table`` makes, a row range at a
time.  The benchmark's own copy of ``tests/serve_reference.py``'s quantized
case: a PR may edit the program's tests, not this.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np

# ``datagen.factor_table`` fills blocks of this many rows, each from its own
# child seed: the same block from the same child seed is the same numbers
BLOCK = 1 << 18


def quantize_rows(f):
    """(codes [n, k] int8, scales [n] float32) of float32 rows, by the rule."""
    f = np.asarray(f, np.float32)
    amax = np.max(np.abs(f), axis=1)
    scales = np.where(amax == 0, np.float32(1.0),
                      amax / np.float32(127.0)).astype(np.float32)
    q = f / scales[:, None]  # one float32 temporary, rounded in place
    np.rint(q, out=q)
    np.clip(q, -127, 127, out=q)
    return q.astype(np.int8), scales


def dequantize_rows(codes, scales):
    """The float32 view answers are exact against: code x the row's scale."""
    return codes.astype(np.float32) * scales[:, None]


def round_to_the_view(f) -> None:
    """``f[:] = dequantize_rows(*quantize_rows(f))`` with no array-sized
    temporary: the same float32 operations in place (a code is a whole number
    within +-127, so float32 holds it as int8 does).  The lazy tables call it
    from a dozen threads; what they allocate and free at that rate the
    one-chip machine's host gives back too slowly (PERF.md section 6, PR 32)."""
    amax = np.maximum(f.max(axis=1), -f.min(axis=1))
    scales = np.where(amax == 0, np.float32(1.0),
                      amax / np.float32(127.0)).astype(np.float32)[:, None]
    np.divide(f, scales, out=f)
    np.rint(f, out=f)
    np.clip(f, -127, 127, out=f)
    np.multiply(f, scales, out=f)


class FactorBlocks:
    """``datagen.factor_table(rows, rank, seed=seed, scale=scale)`` as a lazy
    table: ``table[lo:hi]`` makes rows [lo, hi) anew, block by block on the
    host's cores and in place, each block through ``transform`` (in place
    too) where one is given.  With ``reuse`` the array returned is the
    table's own buffer, overwritten by the next read of the same length: for
    a caller that is done with one range before it asks for the next."""

    def __init__(self, rows: int, rank: int, *, seed: int, scale: float,
                 transform=None, threads: int | None = None,
                 reuse: bool = False):
        self.shape = (rows, rank)
        self.scale, self.transform = scale, transform
        self.buffer = np.zeros((0, rank), np.float32) if reuse else None
        self.threads = threads or max(1, min(16, (os.cpu_count() or 2) - 1))
        self.seeds = np.random.SeedSequence(seed).spawn(-(-rows // BLOCK))

    def _fill(self, i: int, out: np.ndarray) -> None:
        """Block ``i``, whole, into ``out``: as ``datagen.factor_table``."""
        np.random.default_rng(self.seeds[i]).random(
            out.shape, dtype=np.float32, out=out)
        out -= 0.5
        out *= self.scale
        if self.transform is not None:
            self.transform(out)

    def __getitem__(self, key: slice) -> np.ndarray:
        rows, rank = self.shape
        lo, hi, step = key.indices(rows)
        if step != 1:
            raise IndexError("a row range, not a stride")
        if hi <= lo:
            return np.zeros((0, rank), np.float32)
        if self.buffer is None:
            out = np.empty((hi - lo, rank), np.float32)
        else:
            if self.buffer.shape[0] < hi - lo:
                self.buffer = np.empty((hi - lo, rank), np.float32)
            out = self.buffer[:hi - lo]

        def fill(i):
            first, last = i * BLOCK, min((i + 1) * BLOCK, rows)
            if lo <= first and last <= hi:  # a whole block: made in place
                self._fill(i, out[first - lo:last - lo])
                return
            block = np.empty((last - first, rank), np.float32)
            self._fill(i, block)
            a, z = max(lo, first), min(hi, last)
            out[a - lo:z - lo] = block[a - first:z - first]

        blocks = range(lo // BLOCK, -(-hi // BLOCK))
        if len(blocks) == 1 or self.threads == 1:
            for i in blocks:
                fill(i)
        else:
            with concurrent.futures.ThreadPoolExecutor(self.threads) as pool:
                list(pool.map(fill, blocks))
        return out


class DequantizedBlocks(FactorBlocks):
    """The dequantized view of ``FactorBlocks``: every block quantized and
    dequantized by the rule where it is made."""

    def __init__(self, rows: int, rank: int, *, seed: int, scale: float,
                 threads: int | None = None, reuse: bool = False):
        super().__init__(rows, rank, seed=seed, scale=scale,
                         transform=round_to_the_view, threads=threads,
                         reuse=reuse)
