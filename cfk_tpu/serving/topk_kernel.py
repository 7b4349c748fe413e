"""Pallas TPU kernel: batched score + streaming top-K over movie tiles.

The reference's only serving artifact is the dense U·Mᵀ CSV dump
(``processors/FeatureCollector.java:90-109``) — O(users × movies) memory
for any query, the one part of its design that cannot reach
millions-of-users traffic.  This kernel is the serving analog of the
training stack's chunked half-steps: for a [B, k] batch of user factors it
streams [T, k] movie-axis tiles of the (optionally quantized,
``ops.quant``) item factor table through VMEM, computes each [B, T] score
block on the MXU, and folds it into a running per-user K-selection carried
in VMEM — so the only thing that ever reaches HBM is the [B, K] result.
No [B, num_movies] score matrix exists anywhere, on-chip or off.

A grid step streams a SLAB of G consecutive tiles — the table's block is
[G·T, k], the scales' [G, 1, T], the rectangle's [G, W, B] — and folds them
in loops inside the body; the step's height is not the fold's tile.  What a
tile's fold does before it needs the carry, its score block and per-user
maximum, is done for a group of ``_GROUP_TILES`` tiles of the slab in
straight-line code, so that the MXU runs their blocks back to back and no
matmul waits for the scalar the tile before was gated on; then the tiles
take their turns at the carry in order (``_topk_kernel``; what each part
costs on the chip: PERF.md section 7, row 17).  G is as large as the table's
length and the VMEM budget allow (``slab_tiles``: a function of the shapes,
no knob; the ragged last step folds the tiles there are, its blocks clipped
at the table's end).  ``tile_m`` stays the tile of the fold, of the gate, of
the rectangle and of every count.

Per tile, everything MOVIE-MAJOR — [T, B] scores, [K, B] carry — so that
every dynamic index is a single-sublane ref row and every reduction runs
along sublanes, the forms Mosaic lowers:

- score block  S = tile · Uᵀ on the MXU (f32 accumulation).  A float32
  tile's block is one ``dot_general`` at ``Precision.HIGHEST``, six
  bfloat16 passes, and **runs only behind a first gate that one bfloat16
  pass decides**: the tile and ``u`` each rounded to bfloat16 (``u``'s
  once a call), their block's per-user maximum, plus a proven bound of
  how far the exact block can lie above it, from the tile's largest
  |entry| and two 1-norms of ``u`` made once a call (``_pass0_bound``,
  where the proof is; ``float_slack``).  Six tiles in ten of a serve
  cell's hold no row that can enter any user's top-K: they cost that one
  pass, 64 |.| and 64 maxima, no block, no mask and no round; a tile
  whose first gate opens runs the block as before, to the bit, the pass
  it was gated by a seventh (PERF.md section 6, PR 50).  An int8
  tile is not dequantized at all: a code is an integer in ±127, exact in
  bfloat16, and the row's scale comes out of the sum, so the block is
  three bfloat16 passes of the codes against the three bfloat16 pieces of
  U (``split_bf16x3``, made once a call: U is the resident operand), every
  product exact in the float32 accumulator, and one float32 multiply of
  the [T, B] block by the row's scale — float32 arithmetic on the
  dequantized view at half the six passes a float32 tile takes at
  ``Precision.HIGHEST`` (PERF.md section 6, PR 35).  **Only pass 0 runs
  on every int8 tile.**  What passes 1 and 2 can add to a row is bounded
  by the codes' range and the two low pieces' 1-norms (a [1, B] row made
  once a call, times the tile's largest scale: ``pass_slack``,
  ``_bound_max``, where the proof is), so pass 0's maximum plus that
  bound decides a first gate that is open wherever the exact gate would
  be; nine tiles in ten of a serve cell's hold no row that can enter any
  user's top-K, and they cost one pass, no mask and no round.  A tile
  whose first gate opens is completed, (P2 + P1) + P0 times the scale, to
  the bit of three passes run together, and folded like any other
  (``deferred_passes``; PERF.md section 6, PR 48).  The scales reach the
  kernel lane-dense, one [1, T] row a tile of a [NT, 1, T] view of the
  [M_pad] vector (a bitcast), and are turned to columns in register: a
  [M_pad, 1] operand is padded to 128 lanes a row in HBM, 4.8 GB copied
  per call for 37 MB of scales at 9.35 M rows (PERF.md section 6, PR 32),
- padding mask: global row ≥ ``num_movies`` → −inf (the table is padded
  to a tile multiple),
- exclusion mask: already-rated items are −inf'd in-register from a
  [NT, B, W] rectangle of in-tile rows — ``seen[b]``'s movie rows, already
  sorted, split at tile boundaries; W is the pow2-bucketed max per-(user,
  tile) seen count, so the mask pass is W compares of one [1, B] slot row
  against the tile's row iota, not a [B, S×T] blow-up.  The host only
  groups the batch's cells (``group_seen_cells``: a few thousand (tile,
  slot, position, in-tile row) columns); the rectangle itself — 299 MB at
  18,262 tiles × 256 slots × 16 — is filled and scattered on the device
  (``scatter_seen_cells``), and ``build_seen_tiles`` is the same rectangle
  in numpy, the tests' oracle,
- both masks only on the tiles that need them: a batch's few thousand
  cells touch under a tenth of 18,262 tiles, and only the last tile
  reaches past ``num_movies``.  Every other tile's slots are all padding,
  which no row equals, so it takes the fold's branch without the masks
  and loses nothing.  The branch is on a scalar known before the tile is
  scored: ``SeenTiles.hits``, one int32 a tile, made from the cell list
  beside the rectangle and scalar-prefetched into SMEM beside the row
  offset,
- K-selection merge, gated: the [K, B] carry is kept sorted (descending;
  equal scores by ascending row; empty slots at the tail), so its last
  row is each user's K-th score.  One pass takes the tile's per-user
  maximum, and only while some user's maximum is strictly above its K-th
  score does a selection round run: "largest remaining value, earliest
  position" inserted into the sorted carry by a one-sublane shift.  Equal
  scores resolve to the carry, then to the lower row, making tie order
  deterministic — the stable order of a top-k over [carry ‖ tile]
  (``lax.top_k`` has no Mosaic lowering; neither has ``dynamic_slice`` on
  values).  A stream in no particular order changes a user's top-K about
  K/i times in tile i, so most tiles cost the gating pass and no round;
  the kernel counts the rounds it ran and the tiles that ran any,
  likewise the exclusion chunks, and the tiles it completed
  (``topk_scores_counted``; ``ServeEngine.topk`` puts them on its compute
  span).

The merge step (``_score_tile_fold``) is ONE function shared by the Mosaic
kernel body and the XLA twin (``compat.emulate_topk_counted`` scans it over
the same tiles), so the two routes are bit-identical on the interpret path,
counts included — the same twin discipline as the Gram kernels.  The
kernel compiles for the v5e at f32/bf16/int8 and under the 2×2 shard_map
(``tests/test_chip_compile.py``) and matches the twin on the chip
(``tests/test_pallas_tpu.py``); what it costs there is in PERF.md
(sections 5 and 6, PRs 27, 31, 35, 38, 48 and 50; section 7, row 17).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from cfk_tpu.compat import match_varying, typeof_vma
from cfk_tpu.ops.pallas.interpret import resolve_interpret
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

# Exclusion-mask compare chunk: W seen slots are checked against the tile's
# row iota this many slots per loop trip (unrolled by hand), and W is padded
# to a multiple of it.
_SEEN_CHUNK = 16

# Pieces of ``seen_cell_capacity`` cells that one run of the rectangle's
# scatter program takes (``seen_piece_rung``): a batch's whole cell list goes
# into one run, padded up to the next rung, so that fill, scatter and layout
# copy are paid once a batch and the columns scattered stay under twice the
# cells; a list past the top rung takes the top program again, on top.
SEEN_PIECE_RUNGS = (1, 2, 4, 8, 16)

# Tiles a grid step may stream (``slab_tiles`` takes the largest that fits),
# and what a call may ask of the v5e's 128 MiB of VMEM.
_SLAB_LADDER = (16, 8, 4, 2, 1)
_VMEM_CAP = 110 << 20
# Tiles of a slab scored in straight-line code ahead of their gates (a
# rung of the ladder: it divides every G at or above it).
_GROUP_TILES = 8
# What a call counts (``_tile_counts``): selection rounds and tiles that ran
# any, exclusion chunks and tiles that ran any, tiles completed.
NUM_COUNTS = 5


def _pow2_ceil(x: int, floor: int = 1) -> int:
    out = max(floor, 1)
    while out < x:
        out *= 2
    return out


def serve_compute_dtype(table_dtype):
    """(compute dtype, matmul precision) for the score block — the serving
    analog of ``ops.solve._gram_compute_dtype``: f32 operands keep the
    full-precision MXU pass (bit-parity with the dense oracle), bf16 tables
    feed the MXU bf16 with f32 accumulation, int8 tables are scored in
    float32 arithmetic against the dequantized view (code × the row's
    scale).  What a narrower table buys is BYTES, held and scanned per
    batch: half at bf16, a quarter plus 4 B a row at int8.  At
    ``Precision.HIGHEST`` the scorer is bound by the MXU's passes, six over
    a float32 tile's block; an int8 code is exact in bfloat16, so an int8
    tile needs three (``score_passes``, ``split_bf16x3``).  Either costs
    ONE pass unless that pass cannot rule the tile out (``deferred_passes``:
    ``_bound_max`` for int8, ``_pass0_bound`` for float32, whose first pass
    is a seventh beside the block's six: 1, or 1 + 6): the answer is the
    three-pass and the six-pass one on every input (PERF.md sections 5 and
    6 hold what each costs on the chip).  The two
    control tests patch this function to ``(bfloat16, None)`` for int8: the
    fold then runs the arithmetic under the stated one, the dequantized
    tile and ``u`` each rounded to bfloat16, in one pass on every tile,
    with nothing deferred."""
    if table_dtype == jnp.bfloat16:
        return jnp.bfloat16, None
    return jnp.float32, lax.Precision.HIGHEST


def score_passes(table_dtype) -> int:
    """bfloat16 MXU passes the fold runs over one COMPLETED tile of such a
    table: 1 where the compute dtype is bfloat16; three for an int8 tile,
    whose codes have one piece against the three of ``u``; seven for a
    float32 tile: the one bfloat16 pass its first gate is read from, and
    behind that gate the ``dot_general`` at ``Precision.HIGHEST``, which
    runs six of the nine piece products of a float32 operand pair and
    cannot be split, so the first pass is not one of its six.  A bfloat16
    tile is always completed; an int8 or a float32 tile runs pass 0 on
    every tile and the others (``deferred_passes``) only where its first
    gate opens, so a call costs ``tiles + (score_passes - 1) x
    completed_tiles`` passes.  What ``ServeEngine`` puts on
    ``serve/batch/compute``, beside the count of completed tiles."""
    return 1 + len(deferred_passes(table_dtype))


def deferred_passes(table_dtype) -> tuple[int, ...]:
    """Which of a tile's ``score_passes`` wait behind its first gate: the
    passes that run only on a tile that pass 0 could not rule out.  Passes
    1 and 2 of an int8 tile scored in float32 arithmetic (the two low
    pieces of ``u``: ``_bound_max``); passes 1 to 6 of a float32 tile, the
    whole block at ``Precision.HIGHEST`` (``_pass0_bound``: pass 0 there is
    the tile and ``u`` each rounded to bfloat16); none of a bfloat16
    tile's, which has one pass, and none under the controls' one-pass
    bfloat16 arithmetic, which has no second pass to defer.  Read at trace
    time from the table's dtype: no knob."""
    ct, _ = serve_compute_dtype(table_dtype)
    if ct != jnp.float32:
        return ()
    if table_dtype == jnp.int8:
        return (1, 2)
    return (1, 2, 3, 4, 5, 6) if table_dtype == jnp.float32 else ()


def split_bf16x3(u):
    """``u`` (float32) as three bfloat16 pieces, [3, *u.shape], high to
    low, whose float32 sum is ``u`` bit for bit: each piece is the
    remainder rounded to 8 significant bits, and 24 are all a float32
    has.  ``reduce_precision`` does the rounding because a float32 →
    bfloat16 → float32 round trip is one XLA may drop as excess
    precision.  Piece 0 is ``u.astype(bfloat16)``."""
    u = u.astype(jnp.float32)
    pieces = []
    for _ in range(3):
        piece = lax.reduce_precision(u, exponent_bits=8, mantissa_bits=7)
        pieces.append(piece.astype(jnp.bfloat16))
        u = u - piece
    return jnp.stack(pieces)


# What an int8 code's magnitude cannot pass (−128 is a code too, though
# ``ops.quant`` writes none).
_CODE_MAX = 128.0


def pass_slack(pieces):
    """The [1, B] float32 row L that ``_bound_max`` adds, times a tile's
    largest scale, to the tile's pass-0 maximum: per user, ``_CODE_MAX`` x
    (1 + rho) x (||u1||_1 + ||u2||_1 + 2^-20 ||u0||_1) over the three
    bfloat16 ``pieces`` [3, B, k] of ``u`` as the fold reads them, rho =
    (2 k + 32) 2^-24.  Made once a call."""
    k = pieces.shape[-1]
    n0, n1, n2 = jnp.sum(jnp.abs(pieces.astype(jnp.float32)), axis=-1)
    rho = (2 * k + 32) * 2.0 ** -24
    return (_CODE_MAX * (1 + rho) * ((n1 + n2) + 2.0 ** -20 * n0))[None, :]


def float_slack(u, u0):
    """The two [1, B] float32 rows, [2, 1, B], that ``_pass0_bound`` adds to a
    float32 tile's pass-0 maximum, the first times h, half the spacing of
    bfloat16 numbers at the tile's largest |entry| a, the second times a:
    per user (1 + rho) ||u||_1 and (1 + rho) ((1 + 2^-8) ||u - u0||_1 +
    (10 k + 8) 2^-24 ||u||_1), ``u0`` [B, k] being ``u`` rounded to
    bfloat16 as pass 0 reads it (the difference is exact in float32), rho
    = (2 k + 32) 2^-24.  Made once a call."""
    k = u.shape[-1]
    eps = 2.0 ** -24
    n_u = jnp.sum(jnp.abs(u), axis=-1)
    n_d = jnp.sum(jnp.abs(u - u0.astype(jnp.float32)), axis=-1)
    grow = 1 + (2 * k + 32) * eps
    return jnp.stack([
        grow * n_u,
        grow * ((1 + 2.0 ** -8) * n_d + ((10 * k + 8) * eps) * n_u)],
    )[:, None, :]


def resident_operand(u, table_dtype):
    """What the fold reads as ``u`` for a table of this dtype, made once a
    call: the [B, k] batch itself; for int8 codes its three bfloat16
    pieces (``split_bf16x3``), [3, B, k]; and where passes are deferred
    (``deferred_passes``) a tuple that ends in the first gate's slack row:
    ``(pieces, pass_slack(pieces))`` for int8 codes, ``(u, u0,
    float_slack(u, u0))`` for a float32 table, ``u`` in float32 for the
    block and ``u0`` its first bfloat16 piece for pass 0."""
    if table_dtype != jnp.int8:
        if not deferred_passes(table_dtype):
            return u
        u = u.astype(jnp.float32)
        # piece 0 of ``split_bf16x3``, by the rounding XLA may not drop
        u0 = lax.reduce_precision(
            u, exponent_bits=8, mantissa_bits=7).astype(jnp.bfloat16)
        return u, u0, float_slack(u, u0)
    pieces = split_bf16x3(u)
    if deferred_passes(table_dtype):
        return pieces, pass_slack(pieces)
    return pieces


def _times_row_scale(x, scale):
    """``x`` [T, n] times its row's scale, ``scale`` [T, L] with the row's
    scale in every column: L = 1 (a column, broadcast), or one register's
    width of lanes applied to each L-column piece of ``x``."""
    n, l = x.shape[1], scale.shape[1]
    if l == 1 or n <= l:
        return x * scale[:, :n]
    return jnp.concatenate(
        [x[:, j:j + l] * scale[:, :min(l, n - j)] for j in range(0, n, l)],
        axis=1)


_DOT_DIMS = (((1,), (1,)), ((), ()))


def _bf16_pass(rows, piece):
    """One bfloat16 MXU pass: the raw [T, B] float32 sums of ``rows``
    [T, k] against ``piece`` [B, k], both bfloat16.  Every product is
    exact in the float32 accumulator."""
    return jax.lax.dot_general(
        rows, piece, dimension_numbers=_DOT_DIMS,
        preferred_element_type=jnp.float32)


def _code_pass(pieces, codes, piece):
    """``_bf16_pass`` of ``codes`` [T, k] (bfloat16: an int8 code is exact
    there) against piece ``piece`` of ``u``."""
    return _bf16_pass(codes, pieces[piece])


def _complete_scores(p0, pieces, tile, scale):
    """The exact [T, B] block of an int8 tile from pass 0's raw sums
    ``p0``: the two deferred passes, the three added low to high, (P2 +
    P1) + P0, then the block times the row's scale."""
    codes = tile.astype(jnp.bfloat16)
    return _times_row_scale(
        (_code_pass(pieces, codes, 2) + _code_pass(pieces, codes, 1)) + p0,
        scale)


def _bound_max(p0, scale, factor, slack):
    """[1, B]: per user a number no smaller than the maximum of the exact
    block ``_complete_scores(p0, ...)`` would hold, from pass 0 alone —
    the maximum of ``p0`` times the row's scale, plus ``factor`` [1, 1]
    (the largest |scale| among the tile's rows) times the call's slack
    row (``pass_slack``).

    Why it bounds.  Model: round to nearest, eps = 2^-24, |fl(x) − x| <=
    eps |x|; finite operands; no result in the subnormal range (the chip
    flushes those: scores under 2^-100 are outside the argument).  For row
    j and user b let c be the row's codes, |c_i| <= 128, s its scale, and
    u = u0 + u1 + u2 the pieces.  The MXU's sum for piece p adds exact
    products in float32 in some order: |Pp| <= 128 g ||up||_1 with g = (1
    + eps)^(k−1), whatever the order.  ``_complete_scores`` is E = fl(fl(fl(P2
    + P1) + P0) s), the exact block; this function's is S = fl(P0 s).
      D = fl(P2 + P1):  |D| <= (1 + eps)(|P1| + |P2|)
                            <= 128 g (1 + eps)(||u1||_1 + ||u2||_1)
      A = fl(D + P0):   |A − P0| <= |D| + eps |D + P0|
                                 <= (1 + eps)|D| + eps |P0|
      |E − S| <= |s| |A − P0| + eps |s| (|A| + |P0|)
              <= |s| ((1 + eps)|A − P0| + 2 eps |P0|)
              <= |s| ((1 + eps)^2 |D| + (3 eps + eps^2)|P0|)
              <= |s| 128 g ((1 + eps)^3 (||u1||_1 + ||u2||_1)
                            + 4 eps ||u0||_1)  =: |s| Lam_b,
    for a scale of either sign (rounding is odd), so over the tile's rows
    max_j E_j <= max_j S_j + f Lam_b, f = max_j |s_j|.  A maximum is exact.
    What is computed here is G = fl(M + fl(f L)) with M = max_j S_j, |M|
    <= f 128 g (1 + eps) ||u0||_1:
      G >= M + f L (1 − eps)^2 − eps f 128 g (1 + eps) ||u0||_1,
    which is >= M + f Lam_b once L >= 128 g ((1 + eps)^3 (||u1||_1 +
    ||u2||_1) + (5 eps + eps^2) ||u0||_1) / (1 − eps)^2.  ``pass_slack``'s
    L is 128 (1 + rho)(n1 + n2 + 16 eps n0) with the norms n summed in
    float32 (each >= (1 − eps)^(k−1) of the true one) and four roundings
    of its own: (1 + rho) with rho = (2 k + 32) eps covers g (1 − eps)^-(k−1)
    <= 1 + 2 k eps and the dozen single roundings (second-order terms k^2
    eps^2 stay under them for every rank below 2^14).  So G
    >= max_j E_j, and with the masks (which only lower a maximum) and a
    stale K-th score (which only rises): **where some user's exact,
    masked maximum is strictly above its K-th score, so is its G** — the
    gate on G may open where the exact gate is shut, never the other way.
    On the benchmark's tables f L is ~2e-3 against K-th scores near 0.6."""
    return _tile_max(_times_row_scale(p0, scale)) + factor * slack


def _pass0_bound(tile, u0, slack):
    """[1, B]: per user a number no smaller than the maximum of the exact
    block ``_tile_scores(u, tile)`` of a float32 ``tile`` [T, k], from ONE
    bfloat16 pass — the maximum of P0 = bf(tile) . ``u0`` (both operands
    rounded to bfloat16, float32 accumulation) plus what the call's two
    slack rows (``float_slack``: ``slack`` [2, 1, B], an array or the
    kernel's ref) make of the tile's largest |entry|.

    Why it bounds.  The model of ``_bound_max``: round to nearest, eps =
    2^-24, finite operands, nothing in the subnormal range; 2 < k < 2^12.
    For row v of the tile and user u let v0 = bf(v), u0 = bf(u), entry by
    entry, a = max |v_i| over the tile and h = 2^(floor(log2 a) - 8), half
    the spacing of bfloat16 numbers at a: |v_i - v0_i| <= h for every
    entry (the spacing does not shrink as a magnitude grows), h <= 2^-8 a,
    |v0_i| <= (1 + 2^-8) a.
      v.u - v0.u0 = (v - v0).u + v0.(u - u0), so
      |v.u - v0.u0| <= h ||u||_1 + (1 + 2^-8) a ||u - u0||_1.
    P0 adds the k exact products v0_i u0_i in float32 in some order:
      |P0 - v0.u0| <= k eps (1 + 2^-8)^2 a ||u||_1 <= 1.01 k eps a ||u||_1.
    The exact block's entry E is ``dot_general`` at ``Precision.HIGHEST``:
    a float32 sum, in some order, of k products v_i u_i each rounded once,
    or of the 6 k exact products of the operands' bfloat16 pieces with the
    three smallest kinds left out (v1 u2, v2 u1, v2 u2: under 2.1 eps |v_i
    u_i| together; the six kept are under 1.017 |v_i u_i| in magnitude):
      |E - v.u| <= (6.11 k + 2.1) eps a ||u||_1 <= 8 k eps a ||u||_1.
    Together, for every row, |E - P0| <= h ||u||_1 + a Lam_b, Lam_b = (1 +
    2^-8) ||u - u0||_1 + 9.01 k eps ||u||_1, so max_j E_j <= max_j P0_j +
    h ||u||_1 + a Lam_b; a maximum is exact, and so are a and h (a's
    exponent bits, times 2^-8).  What is computed here is G = fl(M + fl(fl(h
    N) + fl(a L))), M = max_j P0_j, |M| <= 1.01 a ||u||_1, N and L the
    slack rows:
      G >= M + (h N + a L)(1 - eps)^3 - 1.01 eps a ||u||_1
        >= M + h ||u||_1 + a Lam_b
    once N >= ||u||_1 / (1 - eps)^3 and L >= (Lam_b + 1.01 eps ||u||_1) /
    (1 - eps)^3.  ``float_slack``'s L has (10 k + 8) eps where that has
    (9.01 k + 1.01), and both rows have their norms summed in float32
    (each >= (1 - eps)^(k-1) of the true one; u - u0 is exact) and a few
    roundings of their own, all inside (1 + rho), rho = (2 k + 32) eps.
    So G >= max_j E_j, and as in ``_bound_max`` the masks only lower a
    maximum and a stale K-th score only rises: **the gate on G may open
    where the exact gate is shut, never the other way.**  What the form
    costs and what else was weighed (2^-8 a for h; the tile's largest row
    norm against 2-norms of u; norms kept as device state): PERF.md
    section 6, PR 50.  On the benchmark's tables h ||u||_1 + a L is ~8e-3
    against K-th scores near 0.53."""
    p0 = _bf16_pass(tile.astype(jnp.bfloat16), u0)
    # a down the lanes first: its exponent is then read in one register
    reach = jnp.max(jnp.abs(tile), axis=0, keepdims=True)  # [1, k]
    half = lax.bitcast_convert_type(
        lax.bitcast_convert_type(reach, jnp.int32) & 0x7F800000,
        jnp.float32) * 2.0 ** -8
    reach, half = (jnp.max(x, axis=1, keepdims=True) for x in (reach, half))
    return _tile_max(p0) + (half * slack[0] + reach * slack[1])


def _tile_scores(u, tile, scale):
    """The [T, B] float32 score block of one tile in one ``dot_general``,
    movie-major: ``tile`` [T, k] (f32 / bf16) against ``u`` [B, k] — a
    bfloat16 tile's one pass, a float32 tile's exact block behind its
    first gate.  An int8 tile comes here only under the
    controls' one-pass arithmetic (``serve_compute_dtype`` patched): the
    dequantized tile and piece 0 of ``u`` [3, B, k], each rounded to the
    compute dtype; ``scale`` f32 [T, 1] or [T, L] with the row's scale in
    every column (``_times_row_scale``)."""
    ct, prec = serve_compute_dtype(tile.dtype)
    if tile.dtype == jnp.int8:
        tile_f = _times_row_scale(tile.astype(jnp.float32),
                                  scale).astype(ct)
        u = u[0]
    else:
        tile_f = tile.astype(ct)
    return jax.lax.dot_general(
        tile_f, u.astype(ct),
        dimension_numbers=_DOT_DIMS,
        preferred_element_type=jnp.float32,
        precision=prec,
    )  # [T, B]


def _mask_scores(scores, seen_row, seen_width, tile_base, num_movies,
                 row_lo=None):
    """``scores`` [T, B] with −inf on the rows at or past ``num_movies``
    (of a ranged scan: the range's end) and under ``row_lo`` (a ranged
    scan's start; None: no such bound) and, with exclusion, on each user's
    seen rows: ``seen_row(j)`` → [1, B] int32 in-tile rows of exclusion slot
    j < the static ``seen_width`` (T = padding), or None."""
    t, b = scores.shape
    row = lax.broadcasted_iota(jnp.int32, (t, b), 0)  # in-tile row
    neg = jnp.float32(-jnp.inf)
    inside = tile_base + row < num_movies
    if row_lo is not None:
        inside &= tile_base + row >= row_lo
    scores = jnp.where(inside, scores, neg)
    if seen_row is None:
        return scores

    def mask_chunk(c, sc):
        # _SEEN_CHUNK slots per trip, fully unrolled (Mosaic's loop
        # lowering takes unroll=1 or a full unroll only) by the lowering,
        # not by Python: a slot's compare is traced once a program, and
        # tracing is what a warm start pays for (PERF.md section 6, PR 38)
        return lax.fori_loop(
            0, _SEEN_CHUNK,
            lambda j, sc: jnp.where(
                row == seen_row(c * _SEEN_CHUNK + j), neg, sc),
            sc, unroll=True)

    return lax.fori_loop(0, seen_width // _SEEN_CHUNK, mask_chunk, scores)


def _tile_max(sc):
    return jnp.max(sc, axis=0, keepdims=True)  # [1, B]


def _entrant(ms, kth):
    """Whether some user's tile maximum ``ms`` [1, B] is STRICTLY above
    its K-th score ``kth`` [1, B]: a scalar."""
    return jnp.max((ms > kth).astype(jnp.int32)) > 0


def _select_round(sc, cv, ci, ms, tile_base):
    """One selection round: each user's tile maximum ``ms`` (earliest row
    holding it) inserted into its sorted carry behind every value ≥ it — a
    shift by one sublane — and consumed from the tile: ``(sc, cv, ci,
    ms)`` after it."""
    t, b = sc.shape
    row = lax.broadcasted_iota(jnp.int32, (t, b), 0)
    first = lax.broadcasted_iota(jnp.int32, cv.shape, 0) == 0
    pos = jnp.min(jnp.where(sc == ms, row, t), axis=0, keepdims=True)
    # Slots whose value is >= the entrant stay; the entrant lands
    # in the first slot that is not, and the rest move one sublane
    # down.  A user with nothing to enter (ms <= its K-th score)
    # keeps every slot, so it needs no mask of its own — and its
    # consumed tile row could not have entered later either (the
    # K-th score only rises).
    stay = cv >= ms
    up_v, up_i = pltpu.roll(cv, 1, 0), pltpu.roll(ci, 1, 0)
    here = first | (up_v >= ms)
    cv = jnp.where(stay, cv, jnp.where(here, ms, up_v))
    ci = jnp.where(stay, ci, jnp.where(here, tile_base + pos, up_i))
    sc = jnp.where(row == pos, jnp.float32(-jnp.inf), sc)
    return sc, cv, ci, _tile_max(sc)


def _tile_counts(rounds, hit, seen_width, completed):
    """What one tile adds to the five counts: the selection rounds it ran
    and whether it ran any, the exclusion chunks of ``_SEEN_CHUNK`` slots
    it ran (its whole width, or none) and whether it ran any (``hit``: 0
    for a tile whose masks did not run), and whether every one of its
    ``score_passes`` ran (``completed``)."""
    return (rounds, (rounds > 0).astype(jnp.int32),
            hit * (seen_width // _SEEN_CHUNK), hit, completed)


def _score_tile_fold(carry_v, carry_i, u, tile, scale, seen_row, seen_width,
                     seen_hit, tile_base, *, tile_m, num_movies, k_top,
                     gate_kth, row_lo=None):
    """Fold one movie tile into the running top-K carry: ``(carry_v,
    carry_i, counts)``, ``counts`` five int32 scalars — the selection
    rounds this tile needed (0 for most tiles) and whether it ran any, the
    exclusion chunks of ``_SEEN_CHUNK`` slots it ran (its whole width, or
    none) and whether it ran any, and whether the tile was completed
    (every pass run): what ``topk_scores_counted`` adds up.

    The per-tile math as one function of one tile: what the XLA twin scans
    (``compat.emulate_topk_counted``).  The Mosaic kernel body runs the same
    pieces (``_tile_scores``, ``_code_pass``, ``_bound_max``,
    ``_pass0_bound``, ``_complete_scores``, ``_mask_scores``, ``_entrant``,
    ``_select_round``, ``_tile_counts``) on the same tiles in the same
    order, the score blocks of a few tiles ahead of their gates
    (``_topk_kernel``).  Everything is MOVIE-MAJOR ([T, B] scores,
    [K, B] carry): per-slot exclusion rows and per-round selections are
    then single-sublane ref rows broadcast down the tile, and every
    reduction runs along sublanes — the only forms of dynamic indexing and
    reduction this selection needs that Mosaic lowers (``lax.top_k``,
    ``dynamic_slice`` on values and lane-offset slices do not).

    ``carry_v`` [K, B] f32, ``carry_i`` [K, B] int32 (−1 empty), ``u`` as
    ``resident_operand`` makes it — [B, k]; for an int8 tile the three
    bfloat16 pieces [3, B, k] with the slack row, ``(pieces, slack)``; for
    a float32 tile ``(u, u0, slack)`` —
    ``tile`` [T, k] (f32/bf16/int8), ``scale`` f32 [T, 1] or [T, L] with
    the row's scale in every column (``_times_row_scale``), or None,
    ``seen_row(j)`` → [1, B] int32 in-tile rows of exclusion slot j < the
    static ``seen_width`` (T = padding), ``seen_hit`` scalar int32 =
    whether any of this tile's slots holds a cell (``SeenTiles.hits``) —
    both None without exclusion — tile_base scalar int32, T = ``tile_m``.

    With exclusion the fold is two branches of one ``lax.cond`` on a scalar
    known before the tile is scored: a tile that holds a cell or reaches
    past ``num_movies`` (the table's last) runs both masks, every other
    tile — nine in ten of a serve cell's — runs neither, and loses
    nothing: its slot rows are all T, which no row equals, and none of its
    rows is padding.  In a ranged scan (``row_lo`` given: a traced scalar,
    and ``num_movies`` then the range's end, traced too) the tile that
    holds the range's first row and the one that holds its last run the
    masks as the table's last tile does: rows outside ``[row_lo,
    num_movies)`` can enter no top-K.

    **An int8 or a float32 tile** (``deferred_passes``) runs pass 0
    alone, and everything else only behind its first gate: some user's
    bound — pass 0's maximum plus a proven bound of what the exact block
    can hold above it: ``_bound_max`` for int8 codes (what passes 1 and 2
    can add), ``_pass0_bound`` for a float32 tile (how far one bfloat16
    pass can lie under the block at ``Precision.HIGHEST``) — strictly
    above ``gate_kth`` [1, B], the K-th scores the gate reads: the kernel
    reads them as of the tile's group's start, and the twin hands the same
    row in (no fold without deferred passes reads it).  Where that gate
    is shut no row of the tile can enter any top-K (the bounds' argument)
    and the tile costs nothing more: no further pass, no masks (a mask
    only lowers a maximum), no rounds.  Where it is open the block is the
    exact one to the bit — an int8 tile's completed from pass 0's sums
    (``_complete_scores``), a float32 tile's the one ``dot_general`` it
    always was (``_tile_scores``) — masked if the tile is hit or the
    table's last, and folded as any other: the result and the selection's
    counts do not depend on the gate, the exclusion counts say what ran.

    The carry is SORTED: scores descending, equal scores by ascending
    global row, empty slots (−inf / −1) at the tail — so its last row is
    each user's K-th score, and a tile row can enter a user's top-K only
    by beating it.  After the masks one pass takes the tile's per-user
    maximum; while any user's maximum is STRICTLY above its K-th score, a
    selection round inserts that maximum (earliest row holding it) into
    the sorted carry behind every value ≥ it — a shift by one sublane —
    drops the last slot, and consumes the tile row by overwriting it with
    −inf.  At equal score the carry (an earlier row) stays ahead and a
    −inf row never enters, which is the stable order of a top-k over
    [carry ‖ tile] and keeps the −1 tail when fewer than K candidates
    exist.  The number of rounds is decided by the data: a tile no row of
    which can enter costs the one gating pass, the worst case (scores
    ascending along the table) min(K, T) rounds.
    """
    t = tile_m
    hit = jnp.int32(0) if seen_row is None else seen_hit
    # a tile without a cell that ends inside the table needs no mask;
    # without exclusion the padding compare is not worth a branch
    needs_mask = (True if seen_row is None
                  else (hit > 0) | (tile_base + t > num_movies))
    if row_lo is not None and needs_mask is not True:
        needs_mask |= tile_base < row_lo

    def rounds_on(scores, carry_v, carry_i):
        # Under shard_map's own tracing (the twin's sharded route) the
        # loop state varies over the mesh like the tile's scores do.
        rounds = match_varying(jnp.int32(0), scores)
        _, carry_v, carry_i, _, rounds = lax.while_loop(
            lambda st: _entrant(st[3], st[1][k_top - 1:]),
            lambda st: _select_round(*st[:4], tile_base) + (st[4] + 1,),
            (scores, carry_v, carry_i, _tile_max(scores), rounds))
        return carry_v, carry_i, rounds

    def masked_scores(scores):
        return _mask_scores(scores, seen_row, seen_width, tile_base,
                            num_movies, row_lo)

    def fold(masked):
        scores = _tile_scores(u, tile, scale)
        return rounds_on(masked_scores(scores) if masked else scores,
                         carry_v, carry_i)

    def fold_deferred():
        if tile.dtype == jnp.int8:
            pieces, slack = u
            p0 = _code_pass(pieces, tile.astype(jnp.bfloat16), 0)
            factor = jnp.max(jnp.abs(scale), axis=0, keepdims=True)[:, :1]
            bound = _bound_max(p0, scale, factor, slack)
            complete = lambda: _complete_scores(p0, pieces, tile, scale)
        else:
            u_full, u0, slack = u
            bound = _pass0_bound(tile, u0, slack)
            complete = lambda: _tile_scores(u_full, tile, scale)
        opened = _entrant(bound, gate_kth)

        def turn():
            scores = complete()
            if needs_mask is True:
                scores = masked_scores(scores)
            else:
                scores = lax.cond(needs_mask, masked_scores, lambda sc: sc,
                                  scores)
            return rounds_on(scores, carry_v, carry_i)

        cv, ci, rounds = lax.cond(
            opened, turn,
            lambda: (carry_v, carry_i, match_varying(jnp.int32(0), bound)))
        opened = opened.astype(jnp.int32)
        return cv, ci, rounds, hit * opened, opened

    if deferred_passes(tile.dtype):
        carry_v, carry_i, rounds, hit, completed = fold_deferred()
    else:
        completed = jnp.int32(1)
        if needs_mask is True:
            carry_v, carry_i, rounds = fold(True)
        else:
            carry_v, carry_i, rounds = lax.cond(
                needs_mask, lambda: fold(True), lambda: fold(False))
    return carry_v, carry_i, _tile_counts(rounds, hit, seen_width, completed)


def group_seen_cells(seen_movies, seen_indptr, batch_rows, *, num_movies,
                     tile_m, num_tiles: int | None = None,
                     min_width: int = _SEEN_CHUNK):
    """The batch's exclusion cells grouped at tile boundaries: ``(cells
    [4, n] int32, (NT, B, W))`` — the host's whole share of the rectangle.

    ``seen_movies``/``seen_indptr`` is the CSR of already-rated movie rows
    by user row (movie rows sorted ascending within each user — the
    ``StreamState.neighbors`` / ``eval.ranking`` convention);
    ``batch_rows`` [B] selects the batch.  Column j of ``cells`` says that
    rectangle entry ``[cells[0, j], cells[1, j], cells[2, j]]`` (movie
    tile, batch slot, position within that slot's seen movies inside the
    tile) holds in-tile column ``cells[3, j]``; movie rows at or past
    ``num_movies`` are dropped.  W is the pow2-bucketed max per-(slot,
    tile) count — pow2 so the rectangle shape, which is jit-static,
    converges onto a handful of compiled programs under live traffic (the
    PR 6 fold-in trick).
    """
    nt = -(-num_movies // tile_m) if num_tiles is None else num_tiles
    b = len(batch_rows)
    batch_rows = np.asarray(batch_rows, dtype=np.int64)
    counts = (seen_indptr[batch_rows + 1] - seen_indptr[batch_rows]).astype(
        np.int64
    )
    rows = np.repeat(np.arange(b, dtype=np.int64), counts)
    flat = np.concatenate([
        np.arange(seen_indptr[r], seen_indptr[r + 1], dtype=np.int64)
        for r in batch_rows
    ]) if counts.sum() else np.zeros(0, np.int64)
    mv = seen_movies[flat].astype(np.int64)
    keep = mv < num_movies
    rows, mv = rows[keep], mv[keep]
    tile_of = mv // tile_m
    # mv is sorted within each row, so (row, tile) groups are contiguous;
    # position within group = running index − group start.
    key = rows * nt + tile_of
    if key.size:
        starts = np.flatnonzero(np.concatenate(([True], key[1:] != key[:-1])))
        group_sizes = np.diff(np.concatenate((starts, [key.size])))
        pos = np.arange(key.size) - np.repeat(starts, group_sizes)
        width = int(group_sizes.max())
    else:
        pos = np.zeros(0, np.int64)
        width = 0
    cells = np.stack([tile_of, rows, pos, mv % tile_m]).astype(np.int32)
    return cells, (nt, b, _pow2_ceil(max(width, 1), min_width))


def seen_cell_capacity(batch: int) -> int:
    """Cells one piece of a batch's cell list holds: a function of the
    padded batch size alone, so the batch-size ladder
    ``ServeEngine.prewarm`` walks covers every cell-list shape the data
    can produce."""
    return batch * _SEEN_CHUNK


def seen_piece_rung(pieces: int) -> int:
    """Pieces one run of the scatter program takes for a cell list of
    ``pieces`` pieces: the first rung of ``SEEN_PIECE_RUNGS`` that holds
    them all, the top one for a list past it (which then takes several
    runs)."""
    return next((r for r in SEEN_PIECE_RUNGS if r >= pieces),
                SEEN_PIECE_RUNGS[-1])


def chunk_seen_cells(cells, capacity: int, num_tiles: int,
                     min_chunks: int = 1):
    """``cells`` [4, n] cut into [4, capacity] arrays, at least
    ``min_chunks`` of them.  The fill columns name tile ``num_tiles`` —
    out of range, so the scatter drops them."""
    n = cells.shape[1]
    chunks = []
    for i in range(max(-(-n // capacity), min_chunks)):
        chunk = np.zeros((4, capacity), np.int32)
        chunk[0] = num_tiles
        piece = cells[:, i * capacity:(i + 1) * capacity]
        chunk[:, :piece.shape[1]] = piece
        chunks.append(chunk)
    return chunks


class SeenTiles(NamedTuple):
    """The exclusion rectangle as the device holds it: ``slots`` [NT, B, W]
    int32 in-tile rows (``tile_m`` = padding) and ``hits`` [NT] int32, 1
    for a tile any of whose slots holds a cell — the scalar the scorer's
    fold branches on, made where the rectangle is made
    (``scatter_seen_cells``) so that the scorer need not read 299 MB again
    to find it."""

    slots: jax.Array
    hits: jax.Array


def scatter_seen_cells(cells, seen_tiles=None, *, shape, tile_m):
    """The [NT, B, W] exclusion rectangle, built where the kernel reads it,
    and which tiles of it hold a cell: a ``SeenTiles``.

    ``cells`` is one [4, n] array of ``chunk_seen_cells``: the batch's
    whole cell list, padded to a rung of ``SEEN_PIECE_RUNGS`` pieces (the
    server's, ``engine._seen_chunks``), so one run of one program fills,
    scatters and lays out the rectangle whatever the batch's cells.  Slot
    [t, b, w] is the w-th in-tile column of batch slot b's seen movies
    inside movie tile t, padded with ``tile_m`` (which no in-tile column
    equals); hit t is 1 where some column named tile t.  ``seen_tiles``
    None starts from the all-padding rectangle; one that an earlier run
    scattered into (a list past the top rung) takes this one on top.  No
    ``indices_are_sorted`` / ``unique_indices``: the chip's compiler folds
    the three indices into one and every dropped column into the same
    out-of-range value, which is neither, and with the hints the v5e wrote
    a wrong rectangle (PERF.md, PR 25).  Both scatters drop the same
    columns (those whose tile is out of range), so no slot is written in a
    tile without a hit."""
    if seen_tiles is None:
        seen_tiles = SeenTiles(jnp.full(shape, tile_m, jnp.int32),
                               jnp.zeros(shape[:1], jnp.int32))
    slots, hits = seen_tiles
    return SeenTiles(
        slots.at[cells[0], cells[1], cells[2]].set(cells[3], mode="drop"),
        hits.at[cells[0]].max(1, mode="drop"))


def build_seen_tiles(seen_movies, seen_indptr, batch_rows, *, num_movies,
                     tile_m, num_tiles: int | None = None,
                     min_width: int = _SEEN_CHUNK):
    """``scatter_seen_cells``'s rectangle in numpy, whole, on the host: the
    oracle the tests hold the device-built one to (at the serve cell's size
    it is 299 MB per batch, which is why nothing serves from it)."""
    cells, shape = group_seen_cells(
        seen_movies, seen_indptr, batch_rows, num_movies=num_movies,
        tile_m=tile_m, num_tiles=num_tiles, min_width=min_width,
    )
    out = np.full(shape, tile_m, dtype=np.int32)
    out[cells[0], cells[1], cells[2]] = cells[3]
    return out


def as_seen_tiles(seen_tiles, tile_m):
    """``seen_tiles`` as the scorer takes it — None, a ``SeenTiles``, or a
    bare [NT, B, W] rectangle — as a ``SeenTiles`` (or None).  A bare
    rectangle gets its hits by being read: 1 for a tile that holds anything
    but the ``tile_m`` padding, so they can never miss a cell.  That is
    what a caller without the cell list pays (the tests' numpy-built
    rectangles; ~1 ms a batch on the v5e at the serve cell's 299 MB, which
    is why the server's hits come from ``scatter_seen_cells``)."""
    if seen_tiles is None or isinstance(seen_tiles, SeenTiles):
        return seen_tiles
    return SeenTiles(
        seen_tiles,
        jnp.any(seen_tiles != tile_m, axis=(1, 2)).astype(jnp.int32))


def slab_tiles(num_tiles, batch, seen_width, rank, table_dtype, *,
               tile_m=512, k_top=16) -> int:
    """G: how many consecutive tiles one grid step of the scorer streams —
    the largest rung of ``_SLAB_LADDER`` that the table has tiles for and
    whose VMEM need (``_vmem_bytes``) stays inside ``_VMEM_CAP``.  The
    slab is what lets the body score ``_GROUP_TILES`` tiles back to back,
    ahead of their gates (``_topk_kernel``), and a grid step costs a
    little whatever its height (0.06 µs: PERF.md section 7, row 17), so
    the step is as tall as the budget and the table allow: the ladder's
    top at a serve cell's 18,262 tiles, NT's rung at a table of a
    handful, 1 where a wide rectangle leaves room for one tile only.  A
    function of the shapes alone; ``tile_m`` stays the tile of the fold,
    of the gate, of the rectangle and of every count."""
    for g in _SLAB_LADDER:
        if g <= max(num_tiles, 1) and _vmem_bytes(
                g, batch, seen_width, rank, table_dtype, tile_m=tile_m,
                k_top=k_top) <= _VMEM_CAP:
            return g
    return 1


def _vmem_bytes(g, batch, seen_width, rank, table_dtype, *, tile_m, k_top):
    """What a call at G = ``g`` asks of VMEM: the [K, B] result (2x for
    Mosaic's output double-buffer; the scratch carry is as large again),
    the slab of ``g`` streamed tiles with their scales and the slab's
    slice of the seen rectangle, each double-buffered, the [T, B] score
    blocks of a group in scratch, one more with its selection temporaries,
    and headroom."""
    out_bytes = 2 * batch * k_top * 8
    tile_bytes = tile_m * (rank * jnp.dtype(table_dtype).itemsize + 4)
    seen_bytes = batch * seen_width * 4
    return (2 * out_bytes + 2 * g * (tile_bytes + seen_bytes)
            + (min(g, _GROUP_TILES) + 8) * tile_m * batch * 4 + (16 << 20))


def _topk_kernel(off_ref, *refs, t, g, p, nt, k_top, num_movies, b,
                 with_seen, with_scale, resident, ranged=False):
    """Grid step i: fold the slab of movie tiles [i·G, min((i+1)·G, NT))
    into the resident [K, B] carry, a group of P tiles at a time.

    The step's height is not the fold's tile: the table's block is [G·T,
    k], the scales' [G, 1, T], the rectangle's [G, W, B], and a loop over
    the slab's G / P groups reads tile ``s`` of each.  What a tile's fold
    does before it needs the carry — the score block and its per-user
    maximum — is done for the P tiles of a group in straight-line code,
    into scratch (``sc_ref`` [P, T, B], ``ms_ref`` [P, 1, B]), with each
    tile's first gate as a scalar in SMEM (``gate_ref`` [P]): the MXU runs
    the P blocks back to back and no matmul waits for the scalar of the
    tile before — a scalar read out of a vector costs 0.22 µs when the
    next instruction waits for it, 4 ms a call at 18,262 tiles (PERF.md
    section 6, PR 38).  Then a second loop gives the tiles their turns at
    the carry, in order: a tile that holds a cell or reaches past
    ``num_movies`` has its block masked and its maximum taken again; a
    tile whose first gate was open runs ``_score_tile_fold``'s rounds on
    its block in scratch (``rounds_of``).  The first gate is read against
    the K-th scores as of the group's start and from the unmasked block:
    both can only make it open where the exact gate is shut (a K-th score
    only rises, a mask only lowers a maximum), never the other way, so the
    rounds run, their order and the counts are the twin's to the bit.  On
    the last step of a table whose NT is no multiple of G the blocks are
    clipped at the table's end and the tiles past NT are scored (whatever
    the buffers hold) and never folded, masked or counted.

    Where passes are deferred (``resident`` > 1: ``resident_operand``'s
    parts are the first operands, ``u`` and then what pass 0 reads — an
    int8 table's slack row [1, B] behind the three pieces, a float32
    table's ``u0`` and its two slack rows [2, 1, B] behind ``u``) the
    straight-line block runs pass 0 alone and the first gate is read from
    ``_bound_max`` / ``_pass0_bound``, which may open where the exact gate
    is shut and never the other way by the arguments written there.  An
    int8 tile's raw sums go to ``sc_ref``; a float32 tile's are dropped
    (its block cannot use them).  A tile whose first gate is open is
    completed in its turn from the slab still in VMEM (``_complete_scores``:
    passes 2 and 1 and the sums of pass 0 in scratch, to the bit of three
    passes run together; a float32 tile: ``_tile_scores``, the one
    ``dot_general`` of the body without a first gate), masked if it is hit
    or the table's last, and given its rounds; one whose gate is shut gets
    nothing more, no pass, no mask, no round.  The completion is traced
    once a program, inside the turn.

    The carry is two VMEM scratch blocks: step 0 initializes them, every
    tile merges into them, the last step copies the final state to the
    (constant-index, resident) output blocks.  ``off_ref`` (scalar-
    prefetched, [1] int32) is the shard's global row offset — 0 on a
    single device; under item-axis sharding each shard's tile n covers
    global movie rows [off + n·T, off + (n+1)·T).  With exclusion a second
    scalar-prefetched operand follows it, ``hits_ref`` ([NT] int32,
    ``SeenTiles.hits``): whether tile n holds a cell to mask.
    ``counts_ref`` (SMEM, [5] int32) accumulates the selection rounds run,
    the tiles that ran at least one, the exclusion chunks run
    (``_SEEN_CHUNK`` slots each, the rectangle's whole width on a tile
    whose masks ran) and the tiles that ran them, and the tiles completed
    (every tile where no pass is deferred) — tiles of T rows, whatever G
    and P are.

    A RANGED scan (``ranged``: the last scalar-prefetched operand is
    ``span_ref`` [4] int32 = the range's first and last slab of the table
    and its rows ``[row_lo, row_hi)``) runs a grid of its rung's steps over
    the slabs that hold the range: the index maps add ``span_ref[0]`` to
    the step (``topk_scores_counted``), so step i streams table slab
    ``span_ref[0] + i`` and rectangle slab i (the rectangle and the hits of
    such a call cover the rung's tiles only, tile 0 = the first tile of the
    range's first slab).  A step past the range's last slab does nothing: a
    scalar compare shuts it whole, and its blocks are the last slab's
    again, which the pipeline does not fetch twice.  Within the range's
    slabs a tile is folded, masked and counted only if it holds a row of
    the range, and the two tiles at the range's ends are masked by row as
    the table's last tile is: ``row_hi`` takes ``num_movies``' place in
    every compare, and rows under ``row_lo`` are shut the same way.
    """
    refs = list(refs)
    hits_ref = refs.pop(0) if with_seen else None
    span_ref = refs.pop(0) if ranged else None
    # ``resident_operand``'s parts: u, and where passes are deferred what
    # pass 0 reads beside it and the first gate's slack
    u_ref, *first_refs = (refs.pop(0) for _ in range(resident))
    defers = bool(first_refs)
    tbl_ref = refs.pop(0)
    scale_ref = refs.pop(0) if with_scale else None
    seen_ref = refs.pop(0) if with_seen else None
    (vals_ref, ids_ref, counts_ref, cv_ref, ci_ref, sc_ref, ms_ref,
     gate_ref) = refs
    i = pl.program_id(0)
    seen_width = seen_ref.shape[1] if with_seen else 0
    if ranged:
        row_lo, row_hi = span_ref[2], span_ref[3]
    else:
        row_lo, row_hi = None, num_movies

    def scale_rows(s):
        # the tile's scales are one lane-dense [1, T] row; the score block
        # wants them down its sublanes: broadcast down one register's
        # width of sublanes and transposed, every column of row r then
        # holds r's scale and the multiply, one for each 128-lane piece of
        # the block, needs no broadcast along lanes (a [T, 1] column
        # broadcast in the multiply: 41.5 against 35.2 ms a call at 9.35 M
        # rows, PERF.md section 6, PR 32)
        return jnp.broadcast_to(scale_ref[s], (128, t)).T

    def tile_rows(s):
        return tbl_ref[pl.ds(pl.multiple_of(s * t, t), t), :]

    @pl.when(i == 0)
    def _():
        cv_ref[...] = jnp.full((k_top, b), -jnp.inf, jnp.float32)
        ci_ref[...] = jnp.full((k_top, b), -1, jnp.int32)
        for j in range(NUM_COUNTS):
            counts_ref[j] = 0

    def rounds_of(j, tile_base):
        """Tile j of the group, whose first gate was open, against the
        carry as it stands: ``_score_tile_fold``'s rounds on the tile's
        block in scratch.  The first round is run before the exact gate is
        known and counted only if that gate was open: where it was shut
        no user has an entrant, every slot of every carry stays
        (``_select_round``), and the tile's consumed row could not have
        entered later either — so the scalar of the exact gate feeds the
        count alone and no vector work waits for it."""
        def select(state):
            cv, ci, ms, rounds, _ = state
            sc, cv, ci, ms = _select_round(sc_ref[j], cv, ci, ms, tile_base)
            sc_ref[j] = sc
            return (cv, ci, ms, rounds + 1,
                    _entrant(ms, cv[k_top - 1:]).astype(jnp.int32))

        # The carry lives in scratch, not in the output blocks: the loop's
        # state starts from it, and inside a compiled kernel under
        # shard_map a value read from an OUTPUT ref keeps the out_shape's
        # vma while everything computed from it has none (jax 0.9.0) — a
        # loop seeded with one could not typecheck.
        cv, ci, ms = cv_ref[...], ci_ref[...], ms_ref[j]
        open_ = _entrant(ms, cv[k_top - 1:]).astype(jnp.int32)
        # a do-while: the state's last word says whether another round
        # is due, and the first is (one trace of the round a program)
        cv, ci, _, rounds, _ = lax.while_loop(
            lambda st: st[4] > 0, select,
            (cv, ci, ms, open_ - 1, jnp.int32(1)))
        cv_ref[...] = cv
        ci_ref[...] = ci
        return rounds

    def fold_tile(j, counts, *, first):
        s = first + j
        n = i * g + s  # the tile's place in the table (or the shard)
        if ranged:
            # n is its place in the rectangle; the table's tile is that
            # many past the first tile of the range's first slab
            tile_base = off_ref[0] + (span_ref[0] * g + n) * t
            there = (tile_base + t > row_lo) & (tile_base < row_hi)
            hit = hits_ref[n] if with_seen else jnp.int32(0)
            edge = (tile_base + t > row_hi) | (tile_base < row_lo)
        else:
            tile_base = off_ref[0] + n * t
            there = True if nt % g == 0 else n < nt
            hit = (hits_ref[jnp.minimum(n, nt - 1)] if with_seen
                   else jnp.int32(0))
            edge = None
        opened = there & (gate_ref[j] > 0)
        needs_mask = there & ((hit > 0) | (
            tile_base + t > num_movies if edge is None else edge))

        def mask():
            sc = _mask_scores(
                sc_ref[j],
                (lambda w: seen_ref[s, pl.ds(w, 1), :]) if with_seen
                else None, seen_width, tile_base, row_hi, row_lo)
            sc_ref[j] = sc
            ms_ref[j] = _tile_max(sc)

        def turn():
            if defers:
                if with_scale:
                    sc = _complete_scores(sc_ref[j], u_ref[...],
                                          tile_rows(s), scale_rows(s))
                else:
                    sc = _tile_scores(u_ref[...], tile_rows(s), None)
                sc_ref[j] = sc
                ms_ref[j] = _tile_max(sc)
                pl.when(needs_mask)(mask)
            return rounds_of(j, tile_base)

        if not defers:
            pl.when(needs_mask)(mask)
        rounds = lax.cond(opened, turn, lambda: jnp.int32(0))
        # the tiles whose masks ran and whose every pass ran: behind the
        # first gate where passes are deferred, else all there are
        ran = jnp.asarray(opened if defers else there).astype(jnp.int32)
        tile_counts = _tile_counts(rounds, hit * ran, seen_width, ran)
        return tuple(c + d for c, d in zip(counts, tile_counts))

    def fold_group(q, counts):
        first = q * p  # the group's first tile within the slab
        kth = cv_ref[k_top - 1:, :]

        def score_tile(j, _):  # nothing here reads what a tile before wrote
            s = first + j
            if defers and with_scale:
                # pass 0 alone: its raw sums, and what the exact block's
                # maximum cannot pass
                slack_ref, = first_refs
                sc = _code_pass(u_ref[...],
                                tile_rows(s).astype(jnp.bfloat16), 0)
                factor = jnp.max(jnp.abs(scale_ref[s]), axis=1,
                                 keepdims=True)
                ms = _bound_max(sc, scale_rows(s), factor, slack_ref[...])
                sc_ref[j] = sc
            elif defers:
                # a float32 tile's one bfloat16 pass: nothing of it is
                # kept, the block behind the gate cannot use its sums
                u0_ref, slack_ref = first_refs
                ms = _pass0_bound(tile_rows(s), u0_ref[...], slack_ref)
            else:
                sc = _tile_scores(u_ref[...], tile_rows(s),
                                  scale_rows(s) if with_scale else None)
                ms = _tile_max(sc)
                ms_ref[j] = ms
                sc_ref[j] = sc
            gate_ref[j] = _entrant(ms, kth).astype(jnp.int32)
            return _

        # straight-line code for the P tiles, unrolled by the lowering:
        # traced once a program (as ``_mask_scores``'s slots are)
        lax.fori_loop(0, p, score_tile, 0, unroll=True)
        return lax.fori_loop(0, p, functools.partial(fold_tile, first=first),
                             counts)

    def fold_slab():
        counts = lax.fori_loop(0, g // p, fold_group,
                               (jnp.int32(0),) * NUM_COUNTS)
        for j, n in enumerate(counts):
            counts_ref[j] += n

    if ranged:
        pl.when(i <= span_ref[1] - span_ref[0])(fold_slab)
    else:
        fold_slab()

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        vals_ref[...] = cv_ref[...]
        ids_ref[...] = ci_ref[...]


def topk_scores_pallas(
    u: jax.Array,  # [B, k] user-factor batch (f32 or bf16)
    table: jax.Array,  # [M_pad, k] item table (f32 / bf16 / int8 codes)
    scale: jax.Array | None,  # [M_pad] f32 per-row int8 scales, else None
    seen_tiles,  # SeenTiles (scatter_seen_cells), a bare [NT, B, W], or None
    *,
    k_top: int,
    num_movies: int,
    tile_m: int = 512,
    row_offset=0,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(scores [B, K] f32 descending, movie rows [B, K] int32).

    Only the [B, K] selection reaches HBM — the out_specs below ARE the
    no-dense-score-matrix guarantee (``tests/test_serving.py`` additionally
    pins the emulation route's compiled temp memory below B·M).  Excluded
    and padding columns score −inf; when fewer than K candidates exist the
    tail ids are −1.  ``row_offset`` (python int or traced scalar) maps
    this table slice's rows to global movie rows — the item-axis sharded
    path (``parallel.spmd.serve_topk_sharded``) passes each shard's base
    row; ids come back global and ``num_movies`` stays the GLOBAL count.
    """
    return topk_scores_counted(
        u, table, scale, seen_tiles, k_top=k_top, num_movies=num_movies,
        tile_m=tile_m, row_offset=row_offset, interpret=interpret,
    )[:2]


def range_slabs(row_lo, row_hi, g, tile_m):
    """(first, last) slab of ``g`` tiles of ``tile_m`` rows that a scan of
    rows ``[row_lo, row_hi)`` streams: python ints or traced scalars.  An
    empty range keeps its first slab (every tile of it is then shut)."""
    first, last = row_lo // (g * tile_m), (row_hi - 1) // (g * tile_m)
    return first, (max if isinstance(last, int) else jnp.maximum)(last, first)


def range_tiles(row_lo: int, row_hi: int, tile_m: int) -> int:
    """The tiles of ``tile_m`` rows that hold a row of ``[row_lo, row_hi)``:
    what a ranged scan folds and counts."""
    return -(-row_hi // tile_m) - row_lo // tile_m


def topk_scores_counted(u, table, scale, seen_tiles, *, k_top, num_movies,
                        tile_m=512, row_offset=0, interpret=None,
                        rows=None, grid_tiles=None):
    """``topk_scores_pallas`` and what the data made it cost: ``(scores,
    movie rows, counts)``, counts [5] int32 = selection rounds run over the
    table's tiles, tiles that ran at least one (of ``M_pad / tile_m``),
    exclusion chunks run (``_SEEN_CHUNK`` compares each: the rectangle's
    width on every tile that holds a cell — on an int8 or a float32
    table, on those of them whose first gate opened) and tiles that ran
    them, and tiles completed (every pass run: all of a bfloat16 table's,
    of an int8 or a float32 table's those whose first gate opened).  What
    ``ServeEngine.topk`` puts on its compute span.  ``seen_tiles`` is a
    ``SeenTiles`` (``scatter_seen_cells``) or a bare [NT, B, W] rectangle
    (``as_seen_tiles``).

    ``rows`` = ``(row_lo, row_hi)`` (int32 scalars, traced or not) makes it
    a RANGED scan: the exact top-K among the table's rows ``[row_lo,
    row_hi)`` alone, ``row_hi <= num_movies``; every other row can enter no
    top-K and, but for the rest of the two slabs at the range's ends, is
    never read.  ``grid_tiles`` (static, a multiple of ``slab_tiles``'
    G) is then the tiles the grid runs, at least those of the slabs that
    hold the range (``range_slabs``): the rung of a short ladder, so that
    ranges of many lengths share a program; ``seen_tiles`` covers those
    ``grid_tiles`` tiles only, tile 0 = the first tile of the range's first
    slab, and the counts are over the tiles that hold a row of the range.
    The rows are this table's own: no ``row_offset``, no mesh.  Without
    ``rows`` the call lowers to the program it lowered to before there were
    ranges."""
    b, k = u.shape
    m_pad = table.shape[0]
    if m_pad % tile_m != 0:
        raise ValueError(
            f"table rows {m_pad} not divisible by tile_m {tile_m}; pad the "
            "table (serving.engine.pad_table does)"
        )
    if not 1 <= k_top:
        raise ValueError(f"k_top must be >= 1, got {k_top}")
    nt = m_pad // tile_m
    ranged = rows is not None
    if ranged and (grid_tiles is None or typeof_vma(table)):
        raise ValueError(
            "a ranged scan takes grid_tiles, and the rows of one device's "
            "own table: over a mesh every shard would need its own piece "
            "of the range (not built)")
    seen_tiles = as_seen_tiles(seen_tiles, tile_m)
    if seen_tiles is not None:
        slots, hits = seen_tiles
        # a ranged scan's rectangle covers the tiles its grid runs
        snt = grid_tiles if ranged else nt
        if slots.shape[:2] != (snt, b) or hits.shape != (snt,):
            raise ValueError(
                f"seen_tiles shapes {slots.shape}, {hits.shape} != "
                f"({snt}, {b}, W), ({snt},)"
            )
        if slots.shape[2] % _SEEN_CHUNK != 0:
            raise ValueError(
                f"seen_tiles width {slots.shape[2]} must be a multiple of "
                f"{_SEEN_CHUNK} (group_seen_cells pads it)"
            )
    if (scale is None) != (table.dtype != jnp.int8):
        raise ValueError(
            "per-row scale required exactly when the table is int8 "
            "(ops.quant.quantize_table provides it)"
        )
    interpret = resolve_interpret(interpret)
    if interpret and (typeof_vma(u) or typeof_vma(table)):
        # Same routing rule as the Gram kernels: sharded-interpret runs
        # take the bit-exact XLA twin (the table is the operand that
        # varies over the mesh; the batch is replicated).
        from cfk_tpu.compat import emulate_topk_counted

        return emulate_topk_counted(
            u, table, scale, seen_tiles, k_top=k_top,
            num_movies=num_movies, tile_m=tile_m, row_offset=row_offset,
        )
    # u is the resident operand: what the fold needs of it is made here,
    # once a call (an int8 table's three bfloat16 pieces, a float32
    # table's first piece, and the slack of the gate the deferred passes
    # wait behind)
    u = resident_operand(u, table.dtype)
    ops = list(u) if isinstance(u, tuple) else [u]  # resident
    resident = len(ops)
    seen_width = 0 if seen_tiles is None else slots.shape[2]
    # G tiles a grid step, from the shapes (``slab_tiles``)
    g = slab_tiles(nt, b, seen_width, k, table.dtype, tile_m=tile_m,
                   k_top=k_top)
    p = min(g, _GROUP_TILES)  # tiles scored ahead of their gates
    # index maps take the grid step and then the scalar-prefetch refs: the
    # row offset and, with exclusion, the tiles' hits.  Where G does not
    # divide NT the last step's blocks reach past the arrays and are
    # clipped; the kernel folds the tiles there are.
    if ranged:
        if grid_tiles % g:
            raise ValueError(
                f"grid_tiles {grid_tiles} is no multiple of the slab's {g} "
                "tiles (slab_tiles)")
        # the last prefetch ref is the span (first slab, last slab, row_lo,
        # row_hi): a step streams its slab of the range, and past the
        # range's last slab that one again, which costs no fetch
        slab = lambda i, pre: jnp.minimum(pre[-1][0] + i, pre[-1][1])
        own = lambda i, pre: jnp.minimum(i, pre[-1][1] - pre[-1][0])
    else:
        slab = own = lambda i, pre: i
    in_specs = [pl.BlockSpec(r.shape, lambda i, *_, n=r.ndim: (0,) * n)
                for r in ops]
    in_specs.append(
        pl.BlockSpec((g * tile_m, k), lambda i, *pre: (slab(i, pre), 0)))
    ops.append(table)  # streamed in slabs
    prefetch = [jnp.asarray(row_offset, jnp.int32).reshape(1)]
    if scale is not None:
        # lane-dense: [NT, 1, T] is the [M_pad] vector itself in HBM, where
        # [M_pad, 1] would be padded to 128 lanes a row
        in_specs.append(pl.BlockSpec(
            (g, 1, tile_m), lambda i, *pre: (slab(i, pre), 0, 0)))
        ops.append(scale.astype(jnp.float32).reshape(nt, 1, tile_m))
    if seen_tiles is not None:
        prefetch.append(hits)
        # slot-major for the kernel: one exclusion slot = one [1, B] row
        in_specs.append(pl.BlockSpec(
            (g, seen_width, b), lambda i, *pre: (own(i, pre), 0, 0)))
        ops.append(jnp.swapaxes(slots, 1, 2))
    if ranged:
        row_lo, row_hi = (jnp.asarray(r, jnp.int32) for r in rows)
        prefetch.append(jnp.stack(
            [*range_slabs(row_lo, row_hi, g, tile_m), row_lo, row_hi]))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=min(
                _vmem_bytes(g, b, seen_width, k, table.dtype, tile_m=tile_m,
                            k_top=k_top),
                _VMEM_CAP,
            )
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(grid_tiles // g if ranged else pl.cdiv(nt, g),),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((k_top, b), lambda i, *_: (0, 0)),
            pl.BlockSpec((k_top, b), lambda i, *_: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[pltpu.VMEM((k_top, b), jnp.float32),
                        pltpu.VMEM((k_top, b), jnp.int32),
                        # a group's score blocks and their maxima
                        pltpu.VMEM((p, tile_m, b), jnp.float32),
                        pltpu.VMEM((p, 1, b), jnp.float32),
                        pltpu.SMEM((p,), jnp.int32)],
    )
    # under shard_map the selection varies over the mesh like the table
    # slice it was scored from (as in ops.pallas.gram_kernel)
    vma = typeof_vma(table)
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d, vma=vma)) if vma else (
        lambda s, d: jax.ShapeDtypeStruct(s, d)
    )
    vals, ids, counts = pl.pallas_call(
        functools.partial(
            _topk_kernel, t=tile_m, g=g, p=p, nt=nt, k_top=k_top,
            num_movies=num_movies, b=b, with_seen=seen_tiles is not None,
            with_scale=scale is not None, resident=resident,
            ranged=ranged,
        ),
        grid_spec=grid_spec,
        out_shape=(mk((k_top, b), jnp.float32), mk((k_top, b), jnp.int32),
                   mk((NUM_COUNTS,), jnp.int32)),
        interpret=bool(interpret),
        **kwargs,
    )(*prefetch, *ops)
    return vals.T, ids.T, counts
