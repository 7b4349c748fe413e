"""Quantized HBM factor tables — the bytes lever under the gather floor.

The in-kernel gather (PR 4) put the tiled chunk bodies ON the gather
roofline; that floor itself is bytes-bound (every rating fetches one
factor row per side per iteration), so the remaining lever is making the
fetched rows smaller.  Following the approximate-computing MF line
(arXiv 1808.03843): the HBM-resident RAW table the gather kernels read is
stored bf16 (half the bytes) or int8 + one f32 per-row scale (a quarter,
plus 4 B/row), while every Gram/solve accumulation stays float32
in-register — the dequantize multiply rides the SAME per-entry premultiply
pass the kernels already run for the √aw weighting, so quantization adds
zero extra kernel passes.

This is distinct from ``ALSConfig.dtype`` (the persistent storage/exchange
dtype of the factor matrices): ``table_dtype`` quantizes only the
*gather operand* of each half-iteration — the solved (master) factors keep
the config dtype, so bf16/int8 tables compose with f32 masters.

Canonical dequant placement (the bit-exactness contract every path pins):

    scale fold FIRST:   wt' = wt · scale[nb]        (int8 only; no-op else)
    then one multiply:  g   = data[nb].astype(ct) · wt'

Both the XLA-gather schedule, the Mosaic in-kernel DMA gather, and their
CPU emulation twins compute exactly this, in exactly this order, so
factors are bit-identical across the gather knob for any table dtype
(``tests/test_quant_table.py``).  ``table_dtype="float32"`` is the
identity — the default path is bit-identical to pre-quantization behavior.
"""

from __future__ import annotations

import concurrent.futures
import os

import jax
import jax.numpy as jnp
import numpy as np

TABLE_DTYPES = ("float32", "bfloat16", "int8")

# int8 symmetric per-row scheme: q = round(f / s) clipped to ±127 with
# s = max|row| / 127.  127 (not 128) keeps the grid symmetric so -f
# quantizes to -q exactly — ALS factors are sign-symmetric by construction.
_INT8_LEVELS = 127.0


def resolve_table_dtype(table_dtype: str | None) -> str:
    """None → the f32 identity; otherwise validate the name."""
    if table_dtype is None:
        return "float32"
    if table_dtype not in TABLE_DTYPES:
        raise ValueError(
            f"table_dtype must be one of {TABLE_DTYPES}, got {table_dtype!r}"
        )
    return table_dtype


def table_itemsize(table_dtype: str | None) -> int:
    """Bytes per table element — what the roofline byte model charges the
    gather floor per fetched cell."""
    return {"float32": 4, "bfloat16": 2, "int8": 1}[
        resolve_table_dtype(table_dtype)
    ]


def quantize_table(
    table: jax.Array, table_dtype: str | None
) -> tuple[jax.Array, jax.Array | None]:
    """(data, scale) for the HBM-resident gather table.

    ``float32``  → (table, None) — identity (bit-identical default path).
    ``bfloat16`` → (bf16 cast, None) — the existing bf16-stream machinery
                   consumes it unchanged (``_gram_compute_dtype``).
    ``int8``     → (int8 rows, [F] f32 per-row scales).  All-zero rows get
                   scale 1.0 so their dequant stays exactly 0 without a
                   0/0.
    """
    td = resolve_table_dtype(table_dtype)
    if td == "float32":
        return table, None
    if td == "bfloat16":
        return table.astype(jnp.bfloat16), None
    f = table.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=-1)  # [F]
    # `amax == 0` (not `amax > 0`): a corrupt row's NaN amax must POISON
    # its scale — the `> 0` predicate is False for NaN and would launder
    # the row into finite codes × scale 1.0, invisible to every
    # downstream isfinite probe (the ring sentinel checks the scales, the
    # only int8 payload leaf that can go nonfinite).  Bit-identical for
    # finite rows.
    scale = jnp.where(amax == 0, 1.0, amax / _INT8_LEVELS)
    q = jnp.clip(
        jnp.round(f / scale[:, None]), -_INT8_LEVELS, _INT8_LEVELS
    ).astype(jnp.int8)
    return q, scale


# rows one host thread quantizes at a time (8 MB of float32 at rank 128)
_HOST_PIECE_ROWS = 1 << 14


def quantize_rows_host(rows: np.ndarray, *, threads: int | None = None):
    """``quantize_table(rows, "int8")`` in numpy, on the host's cores:
    (codes [n, k] int8, scales [n] float32) by the same written rule —
    scale = amax / 127 (1.0 for an all-zero row), code = round-half-even(f /
    scale) clipped to ±127.

    The serving engine quantizes its item table here, slice by slice, and
    uploads codes: IEEE float32 division, so a table quantized anywhere by
    the rule (numpy, XLA:CPU) is this table to the bit, which the chip's own
    divide does not promise (PERF.md section 6, PR 32, counts the codes that
    differ), and a quarter of the bytes cross to the device.  Rows are cut
    into pieces over a thread pool; numpy runs them outside the
    interpreter's lock."""
    rows = np.asarray(rows, np.float32)
    n = rows.shape[0]
    codes = np.empty(rows.shape, np.int8)
    scales = np.empty((n,), np.float32)

    def piece(lo):
        f = rows[lo:lo + _HOST_PIECE_ROWS]
        amax = np.abs(f).max(axis=-1)
        scale = np.where(amax == 0, np.float32(1.0),
                         amax / np.float32(_INT8_LEVELS)).astype(np.float32)
        q = f / scale[:, None]
        np.rint(q, out=q)
        np.clip(q, -_INT8_LEVELS, _INT8_LEVELS, out=q)
        codes[lo:lo + _HOST_PIECE_ROWS] = q
        scales[lo:lo + _HOST_PIECE_ROWS] = scale

    starts = range(0, n, _HOST_PIECE_ROWS)
    threads = threads or max(1, min(16, (os.cpu_count() or 2) - 1))
    if len(starts) <= 1 or threads == 1:
        for lo in starts:
            piece(lo)
    else:
        with concurrent.futures.ThreadPoolExecutor(threads) as pool:
            list(pool.map(piece, starts))
    return codes, scales


def dequantize_table(
    data: jax.Array, scale: jax.Array | None
) -> jax.Array:
    """The full dequantized table (f32 for int8, pass-through otherwise).

    Used where a whole-table consumer needs the values the kernels read —
    the iALS global Gram YᵀY and the subspace sweeps' score streams must
    see the SAME dequantized rows the Gram kernels gather, or the fallback
    and kernel paths drift (the per-interaction-score bug class this
    module's canonical ordering exists to prevent)."""
    if scale is None:
        return data
    return data.astype(jnp.float32) * scale[:, None]


def scale_with_zero_row(scale: jax.Array) -> jax.Array:
    """[F+1] scales with the virtual zero row appended (index F = the
    gather kernels' padding row; its scale is 0 so any folded weight at a
    padding slot is exactly 0 regardless of the mask value)."""
    return jnp.concatenate([scale, jnp.zeros((1,), scale.dtype)])


def fold_scale(
    wt: jax.Array, scale: jax.Array | None, nb: jax.Array
) -> jax.Array:
    """The canonical scale fold: per-entry weight × the indexed row's
    dequant scale (identity when the table carries no scale).  Runs FIRST,
    before the single g = data[nb]·wt multiply — every path (XLA gather,
    Mosaic DMA gather, emulation twins, subspace score streams) shares
    this order, which is what makes them bit-identical.  ``nb`` may use
    the virtual-zero-row convention (index F): the appended scale row is 0.
    """
    if scale is None:
        return wt
    return wt * scale_with_zero_row(scale)[nb].astype(wt.dtype)


def gather_operand_view(
    table: jax.Array, table_dtype: str | None
) -> jax.Array:
    """The dequantized values the gather kernels read, as a whole table —
    for consumers that need the full matrix rather than gathered rows: the
    iALS global Gram YᵀY and any score recomputation.  bf16 returns the
    bf16 cast (``global_gram`` runs its native bf16 path on it); int8
    returns the f32 dequantized rows; f32 is the identity."""
    data, scale = quantize_table(table, table_dtype)
    return dequantize_table(data, scale)


def validate_table_dtype_layout(table_dtype: str | None, layout: str) -> None:
    """int8 needs the per-row scale threaded through the half-step weight
    streams, which the tiled chunk bodies, the bucketed walk, and the
    subspace sweeps do; the padded/segment layouts' classic formulations
    have no symmetric weight channel to fold it into (their iALS Gram uses
    asymmetric operands), so int8 is refused there rather than silently
    dequantizing up front (which would defeat the bytes win).  bf16 is a
    plain dtype cast and works on every layout."""
    td = resolve_table_dtype(table_dtype)
    if td == "int8" and layout not in ("tiled", "bucketed"):
        raise ValueError(
            f"table_dtype='int8' supports layout='tiled'/'bucketed' (the "
            f"per-row scale rides their weight streams); layout={layout!r} "
            "should use 'bfloat16' or 'float32'"
        )
