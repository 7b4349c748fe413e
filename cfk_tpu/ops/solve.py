"""Batched ALS-WR normal-equation solves — the FLOP hot spot.

TPU-native re-design of the per-entity EJML solve in the reference
(``processors/MFeatureCalculator.java:85-99`` / ``UFeatureCalculator.java:85-99``):

    V = UᵀR;  A = UᵀU;  A += λ·n_ratings·I;  m = A⁻¹V        (per entity)

Instead of a HashMap accumulate-until-complete per entity, all entities of a
shard are solved at once: one gather of neighbor factors into a
[E, P, k] tensor, two einsums (MXU matmuls) for all Gram matrices and
right-hand sides, and a batched Cholesky solve of the k×k systems.  The
reference's explicit matrix inverse becomes a Cholesky factorization (A is
SPD by construction); float32 throughout, matching EJML's FMatrixRMaj.

ALS-WR weighted regularization λ·n_ratings·I is exact reference semantics;
the regularizer is floored at λ·1 only for all-padding rows (n = 0), which
the reference cannot have (its HashMap only ever contains rated entities).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from cfk_tpu.compat import match_varying as _match_varying


def _gram_compute_dtype(fixed_factors):
    """(compute dtype, einsum precision) for Gram/RHS contractions.

    float32 factors: full-float32 MXU passes (precision="highest") — the
    default bf16 passes would perturb the normal equations ~1e-2 relative
    and break parity with the reference's float32 EJML math.

    bfloat16 factors (the at-scale storage mode): feed the MXU bf16
    directly with float32 accumulation, at twice the MXU rate and half the
    gather traffic (profiled: the f32 upcast fusion was the single hottest
    op in the full-Netflix iteration).  For the UNWEIGHTED Gram A = Σ ffᵀ
    and the rating RHS this is bit-identical to upcasting first — bf16×bf16
    products are exact in the float32 accumulator, and star/half-star
    ratings fit bf16's 8-bit mantissa exactly (measured: medium-config RMSE
    unchanged to the last printed digit).  The iALS confidence
    pre-multiplies (gm·(c−1) etc.) DO round each weighted product to bf16
    before the matmul in this mode — ~0.4% relative on those Gram entries,
    on top of the storage rounding the caller already opted into.
    """
    if fixed_factors.dtype == jnp.bfloat16:
        return jnp.bfloat16, None
    return jnp.float32, "highest"


def gather_rows(fixed, neighbor_idx: jax.Array) -> jax.Array:
    """Rows ``neighbor_idx`` of the side held fixed.  ``fixed`` is the
    [F, k] factor array, gathered as it is (a trainer's table: its dtype is
    the Gram's compute dtype, ``_gram_compute_dtype``), or the pair a
    serving engine holds its item table as (``ServeEngine.fold_table``):
    ``(data [F, k], scale [F] float32 or None)``.  A pair's rows come back
    in float32, whatever it stores: ``data[idx]`` upcast, times the row's
    scale where there is one (an int8 table's code x scale, one float32
    rounding: the dequantized view the scorer's epilogue computes).  Codes
    and scales are gathered by the same indices; no float32 copy of the
    table, or of a block of it, is made beside the gathered rows."""
    if not isinstance(fixed, tuple):
        return fixed[neighbor_idx]
    data, scale = fixed
    rows = data[neighbor_idx].astype(jnp.float32)
    if scale is None:
        return rows
    return rows * scale[neighbor_idx][..., None]


def table_parts(fixed) -> tuple:
    """(data [F, k], scale [F] or None) of a fixed side in either form
    ``gather_rows`` takes: what a caller reads the rank, the stored dtype
    and a gathered row's bytes from."""
    return fixed if isinstance(fixed, tuple) else (fixed, None)


def gather_gram(
    fixed_factors,  # [F, k] factors of the side held fixed, or (data, scale)
    neighbor_idx: jax.Array,  # [E, P] int32
    rating: jax.Array,  # [E, P] float32 (0 at padding)
    mask: jax.Array,  # [E, P] float32 (1 = real)
) -> tuple[jax.Array, jax.Array]:
    """Compute Gram matrices A = Σ f fᵀ and RHS b = Σ r·f for every entity.

    Returns (A [E, k, k], b [E, k]).  The gather + einsum pair is what XLA
    tiles onto the MXU; padding rows contribute zero via the mask.
    ``fixed_factors`` in either form ``gather_rows`` takes: an engine's pair
    gives float32 rows and so the ``HIGHEST`` contraction, whatever the
    table stores.
    """
    gathered = gather_rows(fixed_factors, neighbor_idx)  # [E, P, k]
    ct, prec = _gram_compute_dtype(gathered)
    gm = gathered.astype(ct) * mask[..., None].astype(ct)
    a = jnp.einsum(
        "epk,epl->ekl", gm, gm,
        preferred_element_type=jnp.float32, precision=prec,
    )
    b = jnp.einsum(
        "epk,ep->ek", gm, rating.astype(ct),
        preferred_element_type=jnp.float32, precision=prec,
    )
    return a, b


def spd_solve_route(a: jax.Array, b: jax.Array) -> str:
    """Which way ``batched_spd_solve`` factors ``a`` [E, k, k] against ``b``
    [E, k], from what it can see of them: ``"lanes"``, the Pallas Cholesky
    with the batch along the lanes (``ops.pallas.solve_kernel.
    cholesky_solve_lanes``), on a TPU backend for float32 systems of
    ``k <= 128``, ``k % 8 == 0``; ``"xla"``, XLA's ``cholesky`` and two
    ``triangular_solve`` calls, everywhere else.  Shapes and dtypes are
    enough: ``jax.ShapeDtypeStruct``s do (``streaming.foldin`` names the
    route on its span that way)."""
    from cfk_tpu.ops.pallas.solve_kernel import CHOL_MAX_RANK

    k = a.shape[-1]
    lanes = (
        jax.default_backend() == "tpu"
        and len(a.shape) == 3 and len(b.shape) == 2
        and a.dtype == jnp.float32 and b.dtype == jnp.float32
        and k <= CHOL_MAX_RANK and k % 8 == 0
    )
    return "lanes" if lanes else "xla"


def batched_spd_solve(a: jax.Array, b: jax.Array) -> jax.Array:
    """Solve A x = b for a batch of SPD k×k systems via Cholesky
    (``A = L Lᵀ``, forward and back substitution, no pivoting).

    a: [E, k, k], b: [E, k] → x: [E, k].  One algorithm, vectorised two
    ways (``spd_solve_route``): XLA's custom calls walk the columns with one
    system's column in the vector unit at a time (6.3 ms for 256 systems of
    128 on a v5e), the lane-batched kernel walks them with 128 systems
    abreast (0.28 ms: PERF.md section 6, PR 40).  The same float32 normal
    equations either way; the two agree to rounding, not to the bit.
    """
    if spd_solve_route(a, b) == "lanes":
        from cfk_tpu.ops.pallas.solve_kernel import cholesky_solve_lanes

        return cholesky_solve_lanes(a, b)
    chol = jnp.linalg.cholesky(a)
    y = lax.linalg.triangular_solve(
        chol, b[..., None], left_side=True, lower=True, transpose_a=False
    )
    x = lax.linalg.triangular_solve(
        chol, y, left_side=True, lower=True, transpose_a=True
    )
    return x[..., 0]


def gather_gram_implicit(
    fixed_factors: jax.Array,  # [F, k]
    neighbor_idx: jax.Array,  # [E, P]
    confidence_m1: jax.Array,  # [E, P] c−1 = α·r at observed cells, 0 at padding
    mask: jax.Array,  # [E, P]
) -> tuple[jax.Array, jax.Array]:
    """Per-entity observed-part Gram for implicit ALS (Hu et al. 2008).

    Returns (A_obs [E,k,k], b [E,k]) with A_obs = Σ (c−1)·f fᵀ over observed
    neighbors and b = Σ c·f (preferences are 1 at observed cells).  The full
    normal matrix is A = YᵀY + A_obs + λI where YᵀY is the *global* Gram over
    all fixed-side rows — computed once per half-iteration (the O(k²)
    speedup trick), not per entity.
    """
    ct, prec = _gram_compute_dtype(fixed_factors)
    gathered = fixed_factors[neighbor_idx].astype(ct)
    gm = gathered * mask[..., None].astype(ct)
    gw = gm * confidence_m1[..., None].astype(ct)
    a = jnp.einsum(
        "epk,epl->ekl", gw, gm,
        preferred_element_type=jnp.float32, precision=prec,
    )
    b = jnp.einsum(
        "epk,ep->ek", gm, ((confidence_m1 + 1.0) * mask).astype(ct),
        preferred_element_type=jnp.float32, precision=prec,
    )
    return a, b


def global_gram(factors: jax.Array) -> jax.Array:
    """YᵀY over all rows (float32 accumulation) — [k, k]."""
    ct, prec = _gram_compute_dtype(factors)
    f = factors.astype(ct)
    return jnp.einsum(
        "fk,fl->kl", f, f, preferred_element_type=jnp.float32, precision=prec
    )


# Canonical block height for the blocked global-Gram reduction.  One value
# shared by the resident bucketed implicit paths and the out-of-core Gram
# pass (offload/windowed.py) — the summation ORDER is part of the bit
# contract between them, and the block height is what fixes it.
GRAM_BLOCK_ROWS = 4096


def global_gram_blocked(factors: jax.Array,
                        block_rows: int = GRAM_BLOCK_ROWS) -> jax.Array:
    """YᵀY by a pinned blocked reduction — [k, k], float32.

    Same math as ``global_gram`` with one canonical summation order: the
    table is cut into consecutive ``[block_rows, k]`` blocks (zero-padded
    tail — the pad contributes exact 0.0) and the per-block Grams
    accumulate in f32, block 0 first.  The out-of-core Gram pass replays
    this reduction block-for-block against staged ``HostFactorStore``
    rows, which is what keeps the resident and host_window implicit
    half-steps crc-identical: both run THIS program, never the
    whole-table einsum whose reassociation XLA owns.
    """
    f, k = factors.shape
    nb = max(-(-f // block_rows), 1)
    pad = nb * block_rows - f
    x = factors
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad, k), x.dtype)], axis=0
        )
    acc = jnp.zeros((k, k), jnp.float32)

    def body(acc, blk):
        return gram_block_add(acc, blk), None

    acc, _ = jax.lax.scan(body, acc, x.reshape(nb, block_rows, k))
    return acc


def gram_block_add(acc: jax.Array, blk: jax.Array) -> jax.Array:
    """One blocked-Gram step: ``acc + blkᵀblk`` (f32).  The single body
    both ``global_gram_blocked`` and the windowed store reduction run —
    per-block shapes and this op are the whole bit contract."""
    ct, prec = _gram_compute_dtype(blk)
    b = blk.astype(ct)
    return acc + jnp.einsum(
        "fk,fl->kl", b, b, preferred_element_type=jnp.float32, precision=prec
    )


def ials_half_step(
    fixed_factors: jax.Array,  # [F, k] (full fixed side)
    neighbor_idx: jax.Array,
    rating: jax.Array,  # raw ratings/counts; confidence = 1 + alpha·r
    mask: jax.Array,
    lam: float,
    alpha: float,
    *,
    gram: jax.Array | None = None,  # precomputed YᵀY (pass psum'd under SPMD)
    solver: str = "cholesky",
    reg_solve_algo: str | None = None,
) -> jax.Array:
    """Solve all entities of one side for implicit feedback.

    Regularization is plain λI (Hu et al.), not the ALS-WR λ·n·I of the
    explicit model.
    """
    k = fixed_factors.shape[-1]
    if gram is None:
        gram = global_gram(fixed_factors)
    a_obs, b = gather_gram_implicit(fixed_factors, neighbor_idx, alpha * rating, mask)
    reg = gram + lam * jnp.eye(k, dtype=jnp.float32)
    return regularized_solve_matrix(a_obs, b, reg, solver,
                                    algo=reg_solve_algo)


def walk_buckets(buckets, chunk_rows, arrays_of, piece, out, overlap=None):
    """The bucket scaffolding every width-bucketed half-step shares.

    For each bucket: extract its per-row arrays (``arrays_of(blk, out)`` —
    ``out`` is passed so warm-started optimizers can gather the bucket's
    current factors), run ``piece(*arrays) -> [rows, k]`` — streamed through
    HBM in [chunk, ...] pieces when ``chunk_rows`` bounds the bucket — and
    scatter the result into ``out`` at the bucket's entity rows (padding
    rows target the trash slot; real rows are unique across buckets).

    The chunk stream is double-buffered by default
    (``ops.pipeline.chunk_map``): chunk c+1's operand fetch is issued
    before ``piece`` runs on chunk c, so the HBM reads hide behind the
    solve; ``overlap=False`` is the serial ``lax.map`` reference schedule.
    """
    from cfk_tpu.ops.pipeline import chunk_map

    k = out.shape[-1]
    for blk, chunk in zip(buckets, chunk_rows):
        arrs = arrays_of(blk, out)
        rows = arrs[0].shape[0]
        if chunk is None or chunk >= rows:
            x = piece(*arrs)
        else:
            if rows % chunk != 0:
                raise ValueError(f"bucket rows {rows} not divisible by chunk {chunk}")
            n_chunks = rows // chunk
            reshaped = tuple(
                a.reshape((n_chunks, chunk) + a.shape[1:]) for a in arrs
            )
            x = chunk_map(
                piece, reshaped, n_chunks, overlap=overlap
            ).reshape(rows, k)
        out = out.at[blk["entity_local"]].set(x)
    return out


def ials_half_step_bucketed(
    fixed_factors: jax.Array,  # [F, k]
    buckets,  # sequence of dicts {neighbor, rating, mask, entity_local}
    chunk_rows,  # same-length sequence of static ints / None
    local_entities: int,
    lam: float,
    alpha: float,
    *,
    gram: jax.Array | None = None,
    solver: str = "cholesky",
    overlap: bool | None = None,
    reg_solve_algo: str | None = None,
    fused_epilogue: bool | None = None,
    in_kernel_gather: bool | None = None,
    table_dtype: str | None = None,
) -> jax.Array:
    """Implicit-feedback half-iteration over width-bucketed InBlocks.

    Same bucket walk as ``als_half_step_bucketed``; per entity the normal
    matrix is YᵀY + Σ_obs (c−1)·f fᵀ + λI.  Zero-interaction rows stay 0,
    identical to the padded path's (YᵀY + λI)x = 0 solve.

    Width classes that pass the port gates run the tiled gather kernels
    via ``ops.bucketed`` (in-kernel DMA gather + fused b-batch epilogue,
    sqrt-reparameterized single weighted stream — the tiled iALS trick);
    refused classes keep this legacy schedule.  ``table_dtype`` quantizes
    the gather table (``ops.quant``); the legacy fallback and the global
    Gram consume the dequantized view so every route sees the same values.
    """
    from cfk_tpu.ops import bucketed as bport, quant

    k = fixed_factors.shape[-1]
    data, scale = quant.quantize_table(fixed_factors, table_dtype)
    view = quant.dequantize_table(data, scale)
    if gram is None:
        # Blocked (not whole-einsum) so the out-of-core Gram pass can
        # replay the identical reduction — see global_gram_blocked.
        gram = global_gram_blocked(view)
    reg_m = gram + lam * jnp.eye(k, dtype=jnp.float32)

    def solve_piece(ni, rt, mk):
        rows, width = ni.shape
        modes = bport.resolve_bucket_modes(
            fused_epilogue, in_kernel_gather, solver, rows, width, k,
            None, reg_solve_algo, table_dtype=data.dtype,
        )
        if modes is None:
            a_obs, b = gather_gram_implicit(view, ni, alpha * rt, mk)
            return regularized_solve_matrix(a_obs, b, reg_m, solver,
                                            algo=reg_solve_algo)
        fused, gather = modes
        wt, rt_b = bport.ials_reparam(rt, mk, alpha)
        return bport.bucket_gram_solve(
            data, scale, ni, wt, rt_b, reg_m, lam=0.0, reg_mode="matrix",
            solver=solver, fused=fused, gather=gather, algo=reg_solve_algo,
        )

    out = walk_buckets(
        buckets, chunk_rows,
        lambda blk, _out: (blk["neighbor"], blk["rating"], blk["mask"]),
        solve_piece,
        jnp.zeros((local_entities + 1, k), jnp.float32),
        overlap=overlap,
    )
    return out[:local_entities]


def _blocked_spd_solve_pallas(a: jax.Array, b: jax.Array) -> jax.Array:
    """SPD solve for PALLAS_MAX_RANK < k ≤ 2·PALLAS_MAX_RANK via one level
    of block (Schur-complement) elimination.

    Split A = [[A₁₁ A₁₂],[A₂₁ A₂₂]] at k₁ = PALLAS_MAX_RANK.  One multi-RHS
    Gauss-Jordan computes Y = A₁₁⁻¹[A₁₂ | b₁]; the Schur complement
    S = A₂₂ − A₂₁·Y₁₂ (SPD) is solved by the single-RHS kernel; and
    x₁ = y₁ − Y₁₂·x₂ back-substitutes.  Everything else is batched k₁³
    matmuls — MXU work — so rank 128 costs two lane-vectorized solves plus
    GEMMs instead of XLA's latency-bound 128×128 cholesky custom calls
    (measured: full-Netflix rank-128 drops from 15.8 to well under the
    12 s/iter bar; pre-ledger record, PERF.md §8).
    """
    from cfk_tpu.ops.pallas import (
        PALLAS_MAX_RANK,
        gauss_solve_multi_pallas,
        gauss_solve_pallas,
    )

    k = a.shape[-1]
    k1 = PALLAS_MAX_RANK
    k2 = k - k1
    al = jnp.transpose(a, (1, 2, 0))  # [k, k, E]
    bl = b.T  # [k, E]
    a11, a12 = al[:k1, :k1], al[:k1, k1:]
    a21, a22 = al[k1:, :k1], al[k1:, k1:]
    b1, b2 = bl[:k1], bl[k1:]
    y = gauss_solve_multi_pallas(
        a11, jnp.concatenate([a12, b1[:, None, :]], axis=1)
    )  # [k1, k2+1, E]
    y12, y1 = y[:, :k2], y[:, k2]
    # Batch-last contractions: S = A₂₂ − A₂₁·Y₁₂ etc. (einsum over the k₁
    # axis with the batch as the trailing dim — XLA lowers these to batched
    # GEMMs; f32 operands keep full precision).
    s = a22 - jnp.einsum(
        "ije,jke->ike", a21, y12,
        preferred_element_type=jnp.float32, precision="highest",
    )
    rhs2 = b2 - jnp.einsum(
        "ije,je->ie", a21, y1,
        preferred_element_type=jnp.float32, precision="highest",
    )
    x2 = gauss_solve_pallas(s, rhs2)  # [k2, E]
    x1 = y1 - jnp.einsum(
        "ije,je->ie", y12, x2,
        preferred_element_type=jnp.float32, precision="highest",
    )
    return jnp.concatenate([x1, x2], axis=0).T  # [E, k]


def dispatch_spd_solve(a: jax.Array, b: jax.Array, solver: str) -> jax.Array:
    """Solve batched SPD systems with the selected backend.

    ``"cholesky"`` — ``batched_spd_solve``: the Cholesky factorisation and
                     its two substitutions, by the lane-batched Pallas
                     kernel on a TPU (float32, k <= 128, k % 8 == 0) and by
                     XLA's cholesky + triangular solves everywhere else.
    ``"pallas"``   — lane-vectorized Gauss-Jordan TPU kernel
                     (``cfk_tpu.ops.pallas``); interpret-mode off TPU.
    ``"auto"``     — pallas on a TPU backend (against XLA's batched
                     cholesky custom calls, latency-bound at small k, the
                     kernel was ~7× faster on 100k rank-64 systems and
                     ~1.7× on the end-to-end full-Netflix iteration:
                     pre-ledger), cholesky elsewhere.

    The pallas path pays an explicit [E,k,k] → [k,k,E] transpose to put the
    batch in the lane dimension.  Ranks in (PALLAS_MAX_RANK, 2·PALLAS_MAX_RANK]
    use one level of blocked Schur elimination on the same kernels; anything
    larger falls back to ``batched_spd_solve`` (XLA's calls: k > 128).
    """
    solver = _resolve_solver(solver)
    if solver == "cholesky":
        return batched_spd_solve(a, b)
    if solver == "pallas":
        from cfk_tpu.ops.pallas import PALLAS_MAX_RANK, gauss_solve_pallas

        k = a.shape[-1]
        if k > 2 * PALLAS_MAX_RANK:
            return batched_spd_solve(a, b)
        if k > PALLAS_MAX_RANK:
            return _blocked_spd_solve_pallas(a, b)
        x = gauss_solve_pallas(jnp.transpose(a, (1, 2, 0)), b.T)
        return x.T
    raise ValueError(f"unknown solver {solver!r}")


def _resolve_solver(solver: str) -> str:
    if solver == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "cholesky"
    return solver


def solve_route(solver: str, rank: int) -> str:
    """The route a half-step's float32 normal equations of ``rank`` take
    under ``solver``, named for a span: ``"lanes"`` or ``"xla"`` where it
    resolves to ``"cholesky"`` (``spd_solve_route``, asked with the shapes
    alone), else the solver's own name."""
    solver = _resolve_solver(solver)
    if solver != "cholesky":
        return solver
    return spd_solve_route(
        jax.ShapeDtypeStruct((1, rank, rank), jnp.float32),
        jax.ShapeDtypeStruct((1, rank), jnp.float32),
    )


def default_fused_epilogue() -> bool:
    """Process-wide default for the fused-epilogue family: the Gram
    kernels' in-VMEM ridge+solve (``ops.pallas.gram_kernel.
    gram_solve_tiles_pallas``) and the fused reg+solve dispatch below.
    True = fuse wherever the backend/rank gates allow — the production
    mode (the split path's per-chunk [Ec, k, k] A-batch write + readback
    is pure HBM traffic the fusion removes).  A patch point no tool
    patches any more (ROADMAP D13), like ``ops.pipeline.default_overlap``;
    per-call ``fused=`` and ``ALSConfig.fused_epilogue`` override it
    explicitly."""
    return True


def resolve_fused_epilogue(fused) -> bool:
    """Per-call override if given, else the process default."""
    return default_fused_epilogue() if fused is None else bool(fused)


def regularized_solve(
    a: jax.Array, b: jax.Array, count: jax.Array, lam: float,
    solver: str = "cholesky", fused: bool | None = None,
    algo: str | None = None,
) -> jax.Array:
    """Apply ALS-WR regularization λ·n_ratings·I and solve.

    The n floor at 1 keeps all-padding rows (n = 0) SPD; real rows always have
    n ≥ 1 so their math is exact reference semantics
    (``processors/MFeatureCalculator.java:91-95``).

    On the pallas backend at supported ranks the regularization, the
    batch-last transposes, and the elimination run as ONE kernel
    (``gauss_solve_reg_pallas``) — the separate diagonal-add pass re-wrote
    the whole Gram batch through HBM every chunk (round-3 profile).
    ``fused=False`` (or the process default off) pins the split
    ridge-add + dispatch schedule — the A/B baseline.
    ``algo`` threads the fused elimination
    choice ('lu'/'gj'; None/'auto' = the process default) — the knob the
    recovery ladder's GJ rung flips (``ALSConfig.reg_solve_algo``).
    """
    from cfk_tpu.ops.pallas import gauss_solve_reg_pallas
    from cfk_tpu.ops.pallas.solve_kernel import _fused_reg_rank_cap

    k = a.shape[-1]
    if (resolve_fused_epilogue(fused)
            and _resolve_solver(solver) == "pallas"
            and k <= _fused_reg_rank_cap(algo)):
        # The fused kernel bakes λ in as a compile-time constant; a traced
        # lam (e.g. a per-step tuned regularizer) cannot concretize, so it
        # takes the unfused path below — same math, one extra HBM pass —
        # instead of a ConcretizationTypeError only the pallas path raised.
        try:
            lam_static = float(lam)
        except jax.errors.ConcretizationTypeError:
            # Only the traced case falls through; genuinely invalid lam
            # (None, multi-element arrays) still raises at the call site.
            lam_static = None
        if lam_static is not None:
            return gauss_solve_reg_pallas(
                a, b, count, reg_mode="diag", lam=lam_static, algo=algo
            )
    reg = lam * jnp.maximum(count.astype(jnp.float32), 1.0)
    a = a + reg[:, None, None] * jnp.eye(k, dtype=a.dtype)
    return dispatch_spd_solve(a, b, solver)


def regularized_solve_matrix(
    a: jax.Array, b: jax.Array, reg: jax.Array, solver: str = "cholesky",
    fused: bool | None = None, algo: str | None = None,
) -> jax.Array:
    """Solve (A_e + R) x_e = b_e with one shared [k,k] SPD term R.

    The iALS half-steps' per-entity systems all add the same global
    YᵀY + λI (Hu et al. 2008); fusing the add into the pallas solve skips
    an [E,k,k] HBM rewrite per chunk, exactly like ``regularized_solve``
    (and like it, ``fused=False`` pins the split schedule for A/B runs
    and ``algo`` threads the elimination choice).
    """
    from cfk_tpu.ops.pallas import gauss_solve_reg_pallas
    from cfk_tpu.ops.pallas.solve_kernel import _fused_reg_rank_cap

    k = a.shape[-1]
    if (resolve_fused_epilogue(fused)
            and _resolve_solver(solver) == "pallas"
            and k <= _fused_reg_rank_cap(algo)):
        return gauss_solve_reg_pallas(a, b, reg, reg_mode="matrix", algo=algo)
    return dispatch_spd_solve(a + reg[None], b, solver)


def pad_rows_to_multiple(arrays, multiple: int):
    """Zero-pad every array's leading (entity) axis to a multiple.

    The shared prologue of entity-chunked scans whose chunk size comes
    from the HBM cell budget (an arbitrary integer): padded rows carry
    zero mask/count, so their solves/Grams are inert and callers slice
    the result back to the real count.  Returns (arrays, pad)."""
    e = arrays[0].shape[0]
    pad = (-e) % multiple
    if pad:
        rowpad = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        arrays = tuple(rowpad(x) for x in arrays)
    return arrays, pad


def _solve_chunk(
    fixed_factors: jax.Array,
    lam: float,
    neighbor_idx: jax.Array,
    rating: jax.Array,
    mask: jax.Array,
    count: jax.Array,
    solver: str = "cholesky",
    algo: str | None = None,
) -> jax.Array:
    a, b = gather_gram(fixed_factors, neighbor_idx, rating, mask)
    return regularized_solve(a, b, count, lam, solver, algo=algo)


def als_half_step(
    fixed_factors,  # [F, k], or an engine's (data, scale): ``gather_rows``
    neighbor_idx: jax.Array,  # [E, P]
    rating: jax.Array,  # [E, P]
    mask: jax.Array,  # [E, P]
    count: jax.Array,  # [E]
    lam: float,
    *,
    solve_chunk: Optional[int] = None,
    solver: str = "cholesky",
    overlap: bool | None = None,
    reg_solve_algo: str | None = None,
) -> jax.Array:
    """One ALS half-iteration: solve all [E] entities against fixed factors.

    ``solve_chunk`` bounds the [chunk, P, k] gather living in HBM at once
    by scanning over entity chunks.  An indivisible E is padded with
    zero-mask rows (their λ-floored solves are sliced off), so budget-
    derived chunk sizes (``ALSConfig.padded_solve_chunk``) always work.
    The chunk stream is double-buffered by default (``ops.pipeline``).
    """
    if solve_chunk is None or solve_chunk >= neighbor_idx.shape[0]:
        return _solve_chunk(
            fixed_factors, lam, neighbor_idx, rating, mask, count, solver,
            reg_solve_algo,
        )
    from cfk_tpu.ops.pipeline import chunk_map

    e = neighbor_idx.shape[0]
    (neighbor_idx, rating, mask, count), pad = pad_rows_to_multiple(
        (neighbor_idx, rating, mask, count), solve_chunk
    )
    n_chunks = (e + pad) // solve_chunk

    reshape = lambda x: x.reshape((n_chunks, solve_chunk) + x.shape[1:])
    out = chunk_map(
        lambda ni, r, m, c: _solve_chunk(fixed_factors, lam, ni, r, m, c,
                                         solver, reg_solve_algo),
        (reshape(neighbor_idx), reshape(rating), reshape(mask),
         reshape(count)),
        n_chunks, overlap=overlap,
    )
    return out.reshape(e + pad, table_parts(fixed_factors)[0].shape[-1])[:e]


def _ragged_gram_ddn():
    """Dimension numbers for the grouped-Gram ragged matmul: contract the
    (ragged, sorted-by-group) entry axis of both operands → [G, k, k]."""
    return lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0],
        rhs_group_dimensions=[],
    )


def default_segment_backend() -> str:
    """Gram backend for the segment layout: grouped ragged matmul (MXU; no
    [C, k, k] intermediate) when this JAX has it, else sorted segment_sum."""
    return "ragged" if hasattr(lax, "ragged_dot_general") else "segsum"


def _segment_gram_flat(
    fixed_factors, neighbor_idx, weight, rating, mask, num_segments,
    segment_ids, group_sizes, backend,
):
    """Gram/RHS contributions of a flat sorted run of ratings.

    A[e] += Σ w·f fᵀ and b[e] += Σ r·f over the run's entries owned by e
    (``weight`` is 1 for explicit ALS, the confidence excess c−1 for iALS;
    ``rating`` is r for explicit, c·preference = c for iALS).  Padding
    entries are masked to zero so their (trash) segment contributes nothing.

    ``backend="ragged"`` computes A and b together as ONE grouped matmul on
    the MXU (``lax.ragged_dot_general`` with the rating appended as lhs
    column k — out[:, :k, :] is A, out[:, k, :] is b), using the
    host-precomputed per-segment entry counts (``group_sizes``); no scatter
    ops anywhere, peak memory is the [C, k] gather.  ``"segsum"``
    materializes the [C, k, k] per-entry outer products and segment-sums
    them by ``segment_ids``.
    """
    ct, prec = _gram_compute_dtype(fixed_factors)
    f = fixed_factors[neighbor_idx].astype(ct) * mask[:, None].astype(ct)
    fw = f * weight[:, None].astype(ct)
    if backend == "ragged":
        lhs = jnp.concatenate([fw, rating[:, None].astype(ct)], axis=1)  # [C, k+1]
        out = lax.ragged_dot_general(
            lhs, f, group_sizes, _ragged_gram_ddn(),
            precision=(lax.Precision.HIGHEST if prec else None),
            preferred_element_type=jnp.float32,
        )  # [G, k+1, k]
        return out[:, :-1, :], out[:, -1, :]
    if backend != "segsum":
        raise ValueError(f"unknown segment gram backend {backend!r}")
    # segment_sum accumulates in the operand dtype — upcast so bf16-stored
    # factors still get float32 accumulation like the ragged path.
    f = f.astype(jnp.float32)
    fw = fw.astype(jnp.float32)
    a = jax.ops.segment_sum(
        fw[:, :, None] * f[:, None, :], segment_ids,
        num_segments=num_segments, indices_are_sorted=True,
    )
    b = jax.ops.segment_sum(
        rating[:, None] * f, segment_ids,
        num_segments=num_segments, indices_are_sorted=True,
    )
    return a, b


def _segment_scan(fixed_factors, per_chunk_gram, solve_rows, arrays, statics,
                  local_entities):
    """The chunk scan both segment half-steps share.

    ``arrays`` = (nb, rt, mk, seg, sizes, ent, cnt, cin, lseg) flat
    shard-local device arrays; ``per_chunk_gram(nb, rt, mk, seg, sizes) ->
    (A, b)`` builds one chunk's raw Gram/RHS [Ec+1, k, k]/[Ec+1, k];
    ``solve_rows(a, b, cnt) -> x`` solves the chunk's Ec rows.  The scan
    carries (partial A, partial b) of the entity straddling each chunk
    boundary — ``cin`` gates adding it to segment 0, ``lseg`` extracts the
    next carry — plus the output matrix, scattered per chunk (non-finalized
    rows target the trash slot).
    """
    nc, cap, e_c = statics
    k = fixed_factors.shape[-1]
    nb, rt, mk, seg, sizes, ent, cnt, cin, lseg = arrays
    chunks = (
        nb.reshape(nc, cap), rt.reshape(nc, cap), mk.reshape(nc, cap),
        seg.reshape(nc, cap), sizes.reshape(nc, e_c + 1),
        ent.reshape(nc, e_c), cnt.reshape(nc, e_c),
        cin.reshape(nc), lseg.reshape(nc),
    )

    def body(carry, chunk):
        a0, b0, out = carry
        nb_c, rt_c, mk_c, seg_c, sz_c, ent_c, cnt_c, cin_c, lseg_c = chunk
        a, b = per_chunk_gram(nb_c, rt_c, mk_c, seg_c, sz_c)
        a = a.at[0].add(cin_c * a0)
        b = b.at[0].add(cin_c * b0)
        x = solve_rows(a[:e_c], b[:e_c], cnt_c)
        out = out.at[ent_c].set(x)
        a1 = lax.dynamic_index_in_dim(a, lseg_c, 0, keepdims=False)
        b1 = lax.dynamic_index_in_dim(b, lseg_c, 0, keepdims=False)
        return (a1, b1, out), None

    init = jax.tree.map(
        lambda z: _match_varying(z, nb),
        (
            jnp.zeros((k, k), jnp.float32),
            jnp.zeros((k,), jnp.float32),
            jnp.zeros((local_entities + 1, k), jnp.float32),
        ),
    )
    (_, _, out), _ = lax.scan(body, init, chunks)
    # Rows never finalized by any chunk (zero-rating global-pad tail) stay
    # exactly 0 — matching the rectangular paths' λ-floored zero solve.
    return out[:local_entities]


def als_half_step_segment(
    fixed_factors: jax.Array,  # [F, k]
    neighbor_idx: jax.Array,  # [NC·C]
    rating: jax.Array,  # [NC·C]
    mask: jax.Array,  # [NC·C]
    seg_rel: jax.Array,  # [NC·C] chunk-relative entity rows, sorted per chunk
    chunk_entity: jax.Array,  # [NC·Ec] shard-local entity row (trash = E_local)
    chunk_count: jax.Array,  # [NC·Ec] full rating count of finalized rows
    group_sizes: jax.Array,  # [NC·(Ec+1)] physical entries per segment
    carry_in: jax.Array,  # [NC] 1.0 = seg 0 continues the previous chunk
    last_seg: jax.Array,  # [NC] chunk-relative index of the last real segment
    local_entities: int,
    lam: float,
    *,
    statics: tuple[int, int, int],
    solver: str = "cholesky",
    gram_backend: str | None = None,
    reg_solve_algo: str | None = None,
) -> jax.Array:
    """One explicit ALS-WR half-iteration over the packed segment layout.

    Semantics match ``als_half_step`` exactly (same normal equations, same
    λ·n·I regularization); only the Gram accumulation differs — a grouped
    ragged matmul over the flat sorted run, scanned over nnz chunks with the
    boundary-straddling entity's partial Gram carried across, so device
    memory is O(chunk) regardless of E or the degree distribution's head.
    """
    backend = gram_backend or default_segment_backend()
    e_c = statics[2]

    def chunk_gram(nb_c, rt_c, mk_c, seg_c, sz_c):
        return _segment_gram_flat(
            fixed_factors, nb_c, jnp.ones_like(rt_c), rt_c, mk_c,
            e_c + 1, seg_c, sz_c, backend,
        )

    def solve_rows(a, b, cnt_c):
        return regularized_solve(a, b, cnt_c, lam, solver,
                                 algo=reg_solve_algo)

    return _segment_scan(
        fixed_factors, chunk_gram, solve_rows,
        (neighbor_idx, rating, mask, seg_rel, group_sizes, chunk_entity,
         chunk_count, carry_in, last_seg),
        statics, local_entities,
    )


def ials_half_step_segment(
    fixed_factors: jax.Array,  # [F, k]
    neighbor_idx: jax.Array,  # [NC·C]
    rating: jax.Array,  # [NC·C] raw counts/ratings; confidence c = 1 + α·r
    mask: jax.Array,  # [NC·C]
    seg_rel: jax.Array,  # [NC·C]
    chunk_entity: jax.Array,  # [NC·Ec]
    group_sizes: jax.Array,  # [NC·(Ec+1)]
    carry_in: jax.Array,  # [NC]
    last_seg: jax.Array,  # [NC]
    local_entities: int,
    lam: float,
    alpha: float,
    *,
    statics: tuple[int, int, int],
    gram: jax.Array | None = None,  # precomputed YᵀY (pass psum'd under SPMD)
    solver: str = "cholesky",
    gram_backend: str | None = None,
    reg_solve_algo: str | None = None,
) -> jax.Array:
    """Implicit-feedback half-iteration over the packed segment layout.

    Per entity A = YᵀY + Σ_obs (c−1)·f fᵀ + λI, b = Σ_obs c·f (Hu et al.
    2008 with the global-Gram trick).  The scan carries the raw observed
    Gram of boundary-straddling entities; YᵀY + λI is added per chunk at
    solve time only.  Zero-interaction rows (chunk padding and rows outside
    every chunk) end up exactly 0: padding rows solve (YᵀY + λI)x = 0
    inside the chunk and scatter to the trash slot anyway.
    """
    k = fixed_factors.shape[-1]
    if gram is None:
        gram = global_gram(fixed_factors)
    reg = gram + lam * jnp.eye(k, dtype=jnp.float32)
    backend = gram_backend or default_segment_backend()
    e_c = statics[2]

    def chunk_gram(nb_c, rt_c, mk_c, seg_c, sz_c):
        return _segment_gram_flat(
            fixed_factors, nb_c, alpha * rt_c, (1.0 + alpha * rt_c) * mk_c,
            mk_c, e_c + 1, seg_c, sz_c, backend,
        )

    def solve_rows(a_obs, b, _cnt):
        return regularized_solve_matrix(a_obs, b, reg, solver,
                                        algo=reg_solve_algo)

    return _segment_scan(
        fixed_factors, chunk_gram, solve_rows,
        (neighbor_idx, rating, mask, seg_rel, group_sizes, chunk_entity,
         jnp.zeros(chunk_entity.shape, jnp.int32), carry_in, last_seg),
        statics, local_entities,
    )


def init_factors(
    key: jax.Array,
    rating: jax.Array,  # [E, P]
    mask: jax.Array,  # [E, P]
    count: jax.Array,  # [E]
    rank: int,
    *,
    num_entities: int | None = None,
) -> jax.Array:
    """Zhou et al. initialization, matching ``processors/UFeatureInitializer.java:50-56``:

    f[0] = entity's average rating, f[1:] ~ U(0, 1).
    """
    return init_factors_stats(key, jnp.sum(rating * mask, axis=1), count, rank,
                              num_entities=num_entities)


def init_factors_stats(
    key: jax.Array,
    rating_sum: jax.Array,  # [E] per-entity rating sum
    count: jax.Array,  # [E]
    rank: int,
    *,
    num_entities: int | None = None,
) -> jax.Array:
    """Zhou et al. init from per-entity stats (the bucketed-layout entry:
    bucketed blocks never materialize an [E, P] rectangle to sum over).

    ``num_entities`` (static) is the REAL entity count when the [E] arrays
    carry shard-count padding: threefry output DEPENDS on the draw shape
    (uniform(key, (2998, k)) and uniform(key, (3000, k)) share no values),
    so drawing at the padded length made an N-way run's init — hence its
    whole trajectory — a function of how E rounds against num_shards (the
    4-shard tiled SPMD mismatch).  Drawing at the real count and zero-
    padding keeps every shard count on the 1-way init exactly; pad rows
    were zeroed by the count mask anyway.
    """
    e = rating_sum.shape[0]
    n = e if num_entities is None else int(num_entities)
    avg = rating_sum / jnp.maximum(count.astype(jnp.float32), 1.0)
    rest = jax.random.uniform(key, (n, rank - 1), dtype=jnp.float32)
    if n != e:
        rest = jnp.pad(rest, ((0, e - n), (0, 0)))
    f = jnp.concatenate([avg[:, None], rest], axis=1)
    # Zero all-padding rows (n = 0): nothing references them in explicit ALS,
    # but the implicit model's global Gram YᵀY sums *every* row, so garbage
    # init there would silently poison iALS.
    return f * (count > 0).astype(jnp.float32)[:, None]


def als_half_step_bucketed(
    fixed_factors: jax.Array,  # [F, k]
    buckets,  # sequence of dicts {neighbor, rating, mask, count, entity_local}
    chunk_rows,  # same-length sequence of static ints / None
    local_entities: int,
    lam: float,
    *,
    solver: str = "cholesky",
    overlap: bool | None = None,
    reg_solve_algo: str | None = None,
    fused_epilogue: bool | None = None,
    in_kernel_gather: bool | None = None,
    table_dtype: str | None = None,
) -> jax.Array:
    """One ALS half-iteration over width-bucketed InBlocks.

    Width classes that pass the port gates (``ops.bucketed``) run the
    tiled gather kernels — in-kernel row DMA (``in_kernel_gather``) and
    the in-VMEM ridge+solve epilogue (``fused_epilogue``), one tile per
    entity: the same contraction as this legacy schedule, equal to float32
    round-off (``ops.bucketed``).  Refused classes (width < 16, SMEM
    overflow) keep the legacy gather + einsum + solve batch.  Rows absent
    from every bucket (zero ratings) stay exactly 0, matching the padded
    path's λ·I-floor solve of an all-zero system.  ``chunk_rows`` streams
    oversized buckets through HBM in [chunk, width, k] pieces.
    ``table_dtype`` quantizes the gather table (``ops.quant``).
    """
    from cfk_tpu.ops import bucketed as bport, quant

    k = fixed_factors.shape[-1]
    data, scale = quant.quantize_table(fixed_factors, table_dtype)
    view = quant.dequantize_table(data, scale)

    def solve_piece(ni, rt, mk, cnt):
        rows, width = ni.shape
        modes = bport.resolve_bucket_modes(
            fused_epilogue, in_kernel_gather, solver, rows, width, k,
            lam, reg_solve_algo, table_dtype=data.dtype,
        )
        if modes is None:
            return _solve_chunk(view, lam, ni, rt, mk, cnt, solver,
                                reg_solve_algo)
        fused, gather = modes
        return bport.bucket_gram_solve(
            data, scale, ni, mk, rt, cnt, lam=lam, reg_mode="diag",
            solver=solver, fused=fused, gather=gather, algo=reg_solve_algo,
        )

    out = walk_buckets(
        buckets, chunk_rows,
        lambda blk, _out: (
            blk["neighbor"], blk["rating"], blk["mask"], blk["count"]
        ),
        solve_piece,
        jnp.zeros((local_entities + 1, k), jnp.float32),
        overlap=overlap,
    )
    return out[:local_entities]
