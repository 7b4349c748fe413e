"""Subspace optimization — block coordinate descent for both ALS families.

Implements the optimizer of Rendle et al., "iALS++: Speeding up Matrix
Factorization with Subspace Optimization" (PAPERS.md), plus its direct
explicit-feedback analog for the flagship ALS-WR model (same block
coordinate descent on each entity's quadratic, with λ·n·I regularization
and no global-Gram term): instead of solving the full k×k normal equations
per entity per epoch (O(nnz·k² + E·k³)), sweep over coordinate blocks of
size b, solving a b×b subsystem per entity per block
(O(nnz·k + nnz·k·b + E·k·b²) per sweep).  At rank 128 with b=32 this is the
difference between a 2M-FLOP and a 130K-FLOP solve per entity, and the Gram
work drops by k/b — the big-k regime (MovieLens-25M at rank 128 and
beyond) is exactly where it pays.

Math (implicit objective, Hu et al. 2008, preferences 1, confidence
c = 1 + α·r, unobserved weight 1):

    A_u = G + Σ_obs (c−1)·f fᵀ + λI,   b_u = Σ_obs c·f,   G = YᵀY

Block update for coordinate block B with current iterate x:

    A_u[B,B] δ = −g_u[B],   g_u = A_u x − b_u,   x[B] += δ

using  g_u[B] = (x·G)[B] + λ·x[B] + Σ_obs f[B]·((c−1)·s − c),  s = fᵀx.
The per-interaction scores s are computed once per sweep (the O(nnz·k) term)
and updated incrementally after each block: s += f[B]ᵀ δ.

Exactness anchor: with block_size = k, one sweep from ANY iterate x0 gives
x0 + A⁻¹(b − A·x0) = A⁻¹b — bit-for-bit the full iALS solve path's answer
(same Gram assembly, same solver).  ``tests/test_ialspp.py`` pins this.

Each entity's update is independent given (fixed, G), so the sweep
vectorizes over entities exactly like the plain half-steps: one rectangle
for the padded layout, per-width-class rectangles (optionally chunked
through HBM) for the bucketed layout.  The reference has no implicit model
at all (SURVEY.md §2.6); this module is beyond-parity capability.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cfk_tpu.ops.solve import (
    regularized_solve,
    regularized_solve_matrix,
)


def _sweep_gather(fixed, scale, neighbor_idx, maskf, in_kernel_gather):
    """The sweep's gathered rectangle ``[E, P, k]`` — the ONE place the
    fixed-side rows enter the sweep, so the Gram blocks, the b-side, AND
    the per-interaction score stream all read the same values.

    With ``in_kernel_gather`` (default on) the rows are row-DMA'd by the
    Pallas stream producer (``gather_rows_pallas`` — scalar-prefetched
    indices, double-buffered VMEM scratch; interpret/old-jax routes run
    the bit-identical XLA twin), retiring the operand-size-cliffed XLA
    gather; off, the same canonical ops run as plain XLA.  For quantized
    tables (``ops.quant``) the per-row dequant scale is folded into the
    mask weight FIRST, so the single premultiply is also the dequantize —
    the score stream therefore sees exactly the dequantized values the
    kernels read (recomputing scores from the f32 master factors would
    make the fallback and kernel paths disagree bit-for-bit).
    """
    from cfk_tpu.ops import quant
    from cfk_tpu.ops.tiled import resolve_in_kernel_gather

    e, p = neighbor_idx.shape
    k = fixed.shape[-1]
    wt = quant.fold_scale(maskf, scale, neighbor_idx)
    if resolve_in_kernel_gather(in_kernel_gather):
        from cfk_tpu.ops.pallas.gram_kernel import gather_rows_pallas

        g = gather_rows_pallas(
            fixed, neighbor_idx.reshape(-1), wt.reshape(-1),
            out_dtype=jnp.float32,
        )
        return g.reshape(e, p, k)
    return fixed[neighbor_idx].astype(jnp.float32) * wt[..., None]


def _sweep_rect(
    fixed: jax.Array,  # [F, k] fixed-side gather table (f32/bf16/int8)
    x: jax.Array,  # [E, k] current own-side iterate (float32)
    neighbor_idx: jax.Array,  # [E, P]
    rating: jax.Array,  # [E, P] raw interaction strengths
    mask: jax.Array,  # [E, P] 1 = real
    lam: float,
    alpha: float,
    gram: jax.Array | None,  # [k, k] YᵀY over the FULL fixed side (implicit)
    block_size: int,
    solver: str,
    count: jax.Array | None = None,  # [E] rating counts (explicit: λ·n·I reg)
    scale: jax.Array | None = None,  # [F] int8 per-row dequant scales
    in_kernel_gather: bool | None = None,
    fused_epilogue: bool | None = None,
    reg_solve_algo: str | None = None,
) -> jax.Array:
    """One full sweep over all k/block_size coordinate blocks of a rectangle.

    Implicit mode (``gram`` given): per entity A = G + Σ(c−1)ffᵀ + λI,
    b = Σ c·f with c = 1 + α·r.  Explicit mode (``count`` given): ALS-WR's
    A = Σ ffᵀ + λ·n·I, b = Σ r·f — no global-Gram term (unobserved cells
    don't enter the explicit objective).  Either way the block update is
    A[B,B]δ = −g[B], g = A·x − b, with the per-interaction scores s = fᵀx
    computed once and rank-b updated after every block.

    The b×b subsystems route through the fused reg+solve dispatchers
    (``regularized_solve{,_matrix}``): the shared regularizer block
    (G[B,B]+λI, or λ·n·I diag) is applied INSIDE the lane-vectorized
    elimination kernel where the pallas solver is active — the b×b blocks
    sit far below the elimination's rank cap (LU 128 / GJ 64), which is
    what makes iALS++ an even better fit for the fused epilogue than the
    full-rank solves.  On the cholesky backend the dispatcher's split
    add + solve is the bit-identical pre-port computation (f32 adds
    commute), so the default CPU path is unchanged.
    """
    implicit = gram is not None
    if implicit == (count is not None):
        raise ValueError("exactly one of gram (implicit) / count (explicit)")
    k = x.shape[-1]
    if k % block_size != 0:
        raise ValueError(f"rank {k} not divisible by block_size {block_size}")
    f32 = jnp.float32
    x = x.astype(f32)
    maskf = mask.astype(f32)
    gathered = _sweep_gather(fixed, scale, neighbor_idx, maskf,
                             in_kernel_gather)
    if implicit:
        conf_m1 = (alpha * rating).astype(f32) * maskf  # c−1 obs, 0 pad
        c_obs = conf_m1 + maskf  # c at observed, 0 at pad
    else:
        # ALS-WR weighted ridge: λ·n per entity, floored at λ·1 for
        # all-padding rows (same floor as regularized_solve).
        reg_n = lam * jnp.maximum(count.astype(f32), 1.0)  # [E]
    # Scores s = fᵀx per interaction — once per sweep, then rank-b updates.
    s = jnp.einsum(
        "epk,ek->ep", gathered, x,
        preferred_element_type=f32, precision="highest",
    )
    eye_b = jnp.eye(block_size, dtype=f32)
    for j in range(k // block_size):
        cols = slice(j * block_size, (j + 1) * block_size)
        f_b = gathered[:, :, cols]  # [E, P, b]
        if implicit:
            w = conf_m1 * s - c_obs  # [E, P]; pad entries are exactly 0
            g_b = (
                jnp.einsum("ek,kb->eb", x, gram[:, cols],
                           preferred_element_type=f32, precision="highest")
                + lam * x[:, cols]
                + jnp.einsum("epb,ep->eb", f_b, w,
                             preferred_element_type=f32, precision="highest")
            )
            a_obs = jnp.einsum("ep,epb,epc->ebc", conf_m1, f_b, f_b,
                               preferred_element_type=f32,
                               precision="highest")
            delta = regularized_solve_matrix(
                a_obs, -g_b, gram[cols, cols] + lam * eye_b, solver,
                fused=fused_epilogue, algo=reg_solve_algo,
            )
        else:
            w = (s - rating.astype(f32)) * maskf  # residual at observed
            g_b = (
                reg_n[:, None] * x[:, cols]
                + jnp.einsum("epb,ep->eb", f_b, w,
                             preferred_element_type=f32, precision="highest")
            )
            a_obs = jnp.einsum("epb,epc->ebc", f_b, f_b,
                               preferred_element_type=f32,
                               precision="highest")
            delta = regularized_solve(
                a_obs, -g_b, count, lam, solver,
                fused=fused_epilogue, algo=reg_solve_algo,
            )
        x = x.at[:, cols].add(delta)
        s = s + jnp.einsum("epb,eb->ep", f_b, delta,
                           preferred_element_type=f32, precision="highest")
    return x


def als_pp_half_step(
    fixed: jax.Array,  # [F, k]
    x_prev: jax.Array,  # [E, k] previous own-side factors (warm start)
    neighbor_idx: jax.Array,
    rating: jax.Array,
    mask: jax.Array,
    count: jax.Array,  # [E] rating counts (ALS-WR λ·n·I)
    lam: float,
    *,
    block_size: int = 32,
    sweeps: int = 1,
    solver: str = "cholesky",
    in_kernel_gather: bool | None = None,
    fused_epilogue: bool | None = None,
    reg_solve_algo: str | None = None,
    table_dtype: str | None = None,
) -> jax.Array:
    """Explicit ALS-WR half-iteration by subspace sweeps (padded layout)."""
    from cfk_tpu.ops import quant

    data, scale = quant.quantize_table(fixed, table_dtype)
    for _ in range(sweeps):
        x_prev = _sweep_rect(
            data, x_prev, neighbor_idx, rating, mask, lam, 0.0, None,
            block_size, solver, count=count, scale=scale,
            in_kernel_gather=in_kernel_gather, fused_epilogue=fused_epilogue,
            reg_solve_algo=reg_solve_algo,
        )
    return x_prev


def _warm_bucket_walk(
    k, x_prev, buckets, chunk_rows, local_entities, bucket_keys, sweep_piece,
    overlap=None,
):
    """Warm-started bucket scatter shared by both families' bucketed sweeps.

    Seeds the output (with the trash row) from ``x_prev``, walks every
    bucket extracting the current factor rows plus ``bucket_keys`` arrays,
    runs ``sweep_piece`` on each piece, and scatters back.  Entities in no
    bucket (zero interactions) keep their previous value — the warm-started
    fixpoint for them is 0 and both trainers start them at 0.  ``overlap``
    double-buffers chunked buckets (chunk c+1's operand fetch under chunk
    c's sweep — ``ops.pipeline``), the default.
    """
    from cfk_tpu.ops.solve import walk_buckets

    out = jnp.zeros((local_entities + 1, k), jnp.float32)
    n = min(x_prev.shape[0], local_entities)
    out = out.at[:n].set(x_prev[:n].astype(jnp.float32))
    out = walk_buckets(
        buckets, chunk_rows,
        lambda blk, cur: (cur[blk["entity_local"]],)
        + tuple(blk[key] for key in bucket_keys),
        sweep_piece,
        out,
        overlap=overlap,
    )
    return out[:local_entities]


def als_pp_half_step_bucketed(
    fixed: jax.Array,  # [F, k]
    x_prev: jax.Array,  # [local_entities, k]
    buckets,  # sequence of dicts {neighbor, rating, mask, count, entity_local}
    chunk_rows,
    local_entities: int,
    lam: float,
    *,
    block_size: int = 32,
    sweeps: int = 1,
    solver: str = "cholesky",
    overlap: bool | None = None,
    in_kernel_gather: bool | None = None,
    fused_epilogue: bool | None = None,
    reg_solve_algo: str | None = None,
    table_dtype: str | None = None,
) -> jax.Array:
    """Explicit ALS-WR half-iteration by subspace sweeps over width buckets."""
    from cfk_tpu.ops import quant

    data, scale = quant.quantize_table(fixed, table_dtype)

    def sweep_piece(xb, ni, rt, mk, cnt):
        for _ in range(sweeps):
            xb = _sweep_rect(
                data, xb, ni, rt, mk, lam, 0.0, None, block_size, solver,
                count=cnt, scale=scale, in_kernel_gather=in_kernel_gather,
                fused_epilogue=fused_epilogue, reg_solve_algo=reg_solve_algo,
            )
        return xb

    return _warm_bucket_walk(
        fixed.shape[-1], x_prev, buckets, chunk_rows, local_entities,
        ("neighbor", "rating", "mask", "count"), sweep_piece,
        overlap=overlap,
    )


def ials_pp_half_step(
    fixed: jax.Array,  # [F, k]
    x_prev: jax.Array,  # [E, k] previous own-side factors (warm start)
    neighbor_idx: jax.Array,
    rating: jax.Array,
    mask: jax.Array,
    lam: float,
    alpha: float,
    *,
    gram: jax.Array | None = None,
    block_size: int = 32,
    sweeps: int = 1,
    solver: str = "cholesky",
    in_kernel_gather: bool | None = None,
    fused_epilogue: bool | None = None,
    reg_solve_algo: str | None = None,
    table_dtype: str | None = None,
) -> jax.Array:
    """iALS++ half-iteration over the padded rectangle layout."""
    from cfk_tpu.ops import quant
    from cfk_tpu.ops.solve import global_gram

    data, scale = quant.quantize_table(fixed, table_dtype)
    if gram is None:
        # YᵀY over the SAME dequantized rows the sweep gathers — see
        # quant.gather_operand_view.
        gram = global_gram(quant.dequantize_table(data, scale))
    for _ in range(sweeps):
        x_prev = _sweep_rect(
            data, x_prev, neighbor_idx, rating, mask, lam, alpha, gram,
            block_size, solver, scale=scale,
            in_kernel_gather=in_kernel_gather, fused_epilogue=fused_epilogue,
            reg_solve_algo=reg_solve_algo,
        )
    return x_prev


def ials_pp_half_step_bucketed(
    fixed: jax.Array,  # [F, k]
    x_prev: jax.Array,  # [local_entities(+pad rows ok), k]
    buckets,  # sequence of dicts {neighbor, rating, mask, entity_local}
    chunk_rows,  # same-length sequence of static ints / None
    local_entities: int,
    lam: float,
    alpha: float,
    *,
    gram: jax.Array | None = None,
    block_size: int = 32,
    sweeps: int = 1,
    solver: str = "cholesky",
    overlap: bool | None = None,
    in_kernel_gather: bool | None = None,
    fused_epilogue: bool | None = None,
    reg_solve_algo: str | None = None,
    table_dtype: str | None = None,
) -> jax.Array:
    """iALS++ half-iteration over width-bucketed InBlocks.

    Buckets partition the entities (each rated entity lives in exactly one
    bucket), so the sweep runs independently per bucket rectangle and
    scatters back; ``chunk_rows`` streams oversized buckets through HBM like
    the plain bucketed half-step does.  The per-width-class sweeps gather
    by in-kernel row DMA and solve their b×b subsystems through the fused
    reg+solve dispatchers (see ``_sweep_rect``); ``table_dtype`` quantizes
    the HBM gather table (``ops.quant``).
    """
    from cfk_tpu.ops import quant
    from cfk_tpu.ops.solve import global_gram_blocked

    data, scale = quant.quantize_table(fixed, table_dtype)
    if gram is None:
        # Blocked (not whole-einsum) so the out-of-core Gram pass can
        # replay the identical reduction — see global_gram_blocked.
        gram = global_gram_blocked(quant.dequantize_table(data, scale))

    def sweep_piece(xb, ni, rt, mk):
        for _ in range(sweeps):
            xb = _sweep_rect(
                data, xb, ni, rt, mk, lam, alpha, gram, block_size, solver,
                scale=scale, in_kernel_gather=in_kernel_gather,
                fused_epilogue=fused_epilogue, reg_solve_algo=reg_solve_algo,
            )
        return xb

    return _warm_bucket_walk(
        fixed.shape[-1], x_prev, buckets, chunk_rows, local_entities,
        ("neighbor", "rating", "mask"), sweep_piece,
        overlap=overlap,
    )
