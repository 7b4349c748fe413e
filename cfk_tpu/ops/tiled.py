"""Tile-padded Gram half-steps — the MXU-native segment layout.

Why this exists (measured on a v5e before the ledger, PERF.md §8):
the flat segment layout's grouped ragged matmul (``lax.ragged_dot_general``)
runs the per-entity Gram accumulation ~15× below what the MXU can do, and
XLA's row gather falls off a cliff (4×) once the fixed factor table exceeds
~34 MB.  This layout restructures the same math so both hot ops hit the
hardware's fast paths:

- Every entity's rating run is padded to a multiple of ``T`` rows (weight 0
  padding), so a chunk is an exact grid of [T, k] *tiles, each tile owned by
  one entity*.  The Gram contributions become ONE batched GEMM per chunk —
  ``einsum("ntk,ntl->nkl")`` on [NT, T, k] tiles, a shape XLA tiles straight
  onto the MXU — followed by a segment-sum of [NT, k, k] tile Grams by tile
  owner (≈3 tiles per entity), instead of a grouped matmul over 1M ragged
  segments.

- The side whose *fixed* table is large (solving movies gathers from the
  480k-row user table at full Netflix scale) additionally sorts its entries
  by (table slice, entity) and gathers each chunk from a
  ``lax.dynamic_slice`` of ≤ ``H`` rows — statically small, so XLA keeps the
  fast-gather strategy.  Entities then recur across slices, so this side
  accumulates per-entity Grams in a persistent [E+1, k, k] scan carry
  (``accum`` mode — only legal when the solve side has few entities, which
  is exactly the side whose fixed table is big) and solves once at the end.

- The side with many entities ("stream" mode) keeps the segment layout's
  chunk-scan structure: finalized rows are solved per chunk, an entity
  straddling a chunk boundary has its partial (A, b) carried across.

The reference computes the same normal equations one entity at a time in
EJML (``processors/MFeatureCalculator.java:85-99``); the λ·n_ratings
regularization and float32 accumulation semantics here are identical to
``cfk_tpu.ops.solve`` (the rectangular/segment paths), which the parity
tests assert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from cfk_tpu.ops.pipeline import prefetch_scan, resolve_overlap
from cfk_tpu.ops.solve import (
    _gram_compute_dtype,
    _match_varying,
    regularized_solve,
    regularized_solve_matrix,
)


_GZ_HOISTED_BUDGET_BYTES = 2 << 30  # accum-mode hoisted gather windows:
# past ~2 GB the duplicate table stops being a rounding error next to the
# [E+1, k, k] accumulator and the per-chunk dynamic_slice path takes over


def default_in_kernel_gather() -> bool:
    """Process-wide default for the in-kernel neighbor gather: fuse the
    per-chunk neighbor-factor gather into the Pallas Gram kernels (the
    ``*_gather_pallas`` variants DMA the indexed table rows straight into
    VMEM), retiring the materialized [C, k] gathered stream.  True =
    gather in-kernel wherever the gates allow (``resolve_gather_mode``).
    A patch point no tool patches any more (ROADMAP D13), like
    ``default_tiled_gram_backend``; per-call ``in_kernel_gather=`` and
    ``ALSConfig.in_kernel_gather`` override it explicitly."""
    return True


def resolve_in_kernel_gather(in_kernel_gather) -> bool:
    """Per-call override if given, else the process default."""
    if in_kernel_gather is None:
        return default_in_kernel_gather()
    return bool(in_kernel_gather)


def resolve_gather_mode(in_kernel_gather, backend, entries, meta_words,
                        tile_rows, num_segments, k, block_rows=None, *,
                        table_dtype) -> str:
    """Static gating of the in-kernel gather — ``"fused"`` or ``"xla"``.

    The logic lives in ``cfk_tpu.plan.registry`` now (ISSUE 9): ONE
    resolver shared by the tiled chunk bodies, the bucketed port, both
    SPMD ring half-steps, and the plan resolver's feasibility gates — and
    it consults the kernel registry's backend availability, so a forced
    ``mosaic_tpu`` outage reroutes the next trace to the emulation
    schedule (same math, bit-identical factors).  This alias keeps every
    existing call site and test import working."""
    from cfk_tpu.plan.registry import resolve_gather_mode as _resolve

    return _resolve(in_kernel_gather, backend, entries, meta_words,
                    tile_rows, num_segments, k, block_rows,
                    table_dtype=table_dtype)


def default_tiled_gram_backend() -> str:
    """Tile-Gram backend: the fused pallas grouped-Gram kernel.

    Measured on the real v5e at the full Netflix shape (rank 64, bf16,
    512k-entry chunks): the multi-tile kernel holds the whole per-chunk
    (A, b) output resident in VMEM, so the [NT, k, k] tile-Gram batch, its
    segment-sum read-back, the zero-fill, and the pre-GEMM layout copy all
    disappear — 1.285 s/iter (XLA backend) → 0.85 s/iter end-to-end.
    Round 2's one-tile-per-grid-step kernel lost this comparison (2.36 vs
    1.97 — overhead-bound); the multi-tile redesign (VERDICT r2 item #1)
    is what made pallas the measured default.  ``gram_backend="xla"``
    (batched GEMM + segment-sum) remains for A/B measurement."""
    return "pallas"


def _entity_gram_chunk(
    fixed_slice, nb, wt, rt, seg, tile_rows, num_segments, backend,
    unit_weights=False, zero_appended=False, carry=None,
    pregathered=None, gather="xla",
):
    """One chunk's per-entity Gram/RHS: (A [num_segments, k, k], b [.., k]).

    ``seg`` maps each [tile_rows]-entry tile to its owner (sorted;
    ``num_segments - 1`` = trash).  Rows of segments owning no tile are
    UNSPECIFIED under the pallas backend (never written) — callers must
    route them to trash (stream mode) or mask them (accum mode).

    A zero row is appended to the fixed slice and padding entries index it
    (format-3 blocks), so padding contributes exact zeros BEFORE any weight
    is applied.  ``zero_appended=True`` says the caller already placed that
    zero row (accum mode appends it per SLICE outside the chunk scan — the
    in-body concatenate re-copied the 17 MB slice every chunk, ~25 ms/iter
    in the round-3 profile).  ``unit_weights=True`` (explicit ALS: real
    weights are all 1.0) skips the w·f multiply entirely — measured 0.18
    s/iter of pure elementwise traffic at the full Netflix shape.

    The weighted path (iALS) takes ``wt`` as the **sqrt-reparameterized**
    per-entry weight √aw: the single stream gs = √aw·f (the multiply fuses
    into the producing gather) is used as BOTH Gram operands, so
    A = Σ aw·f fᵀ with the same kernel traffic as the unit path — round
    4's premultiplied second stream (gw = aw·f next to plain g) doubled
    the pipelined input for nothing (``ials_tiled_half_step`` rescales the
    b-coefficients by 1/√aw to compensate).

    ``pregathered`` (the overlap pipelines) hands in the chunk's gathered
    stream ``fz[nb].astype(ct)`` fetched one loop step early
    (``ops.pipeline.prefetch_scan``); the weight multiply and everything
    downstream run here unchanged, so the pipelined result is bit-equal to
    the in-body gather.

    ``gather="fused"`` (gated upstream by ``resolve_gather_mode``;
    pallas backend only) retires the materialized stream
    entirely: ``fixed_slice`` must then be the RAW table (no zero row)
    and ``nb`` indexes it with ``table_rows`` as the virtual zero row;
    the kernel DMAs the rows itself and applies ``wt`` in-register —
    which is also what realizes the padding zero row, so ``wt`` (the 0/1
    mask for the unit-weight path, √aw·mask for iALS) is consumed even
    when ``unit_weights=True``.
    """
    k = fixed_slice.shape[-1]
    g = _gathered_stream(fixed_slice, nb, wt, unit_weights, zero_appended,
                         pregathered, gather=gather)
    if g is None:  # gather == "fused": the kernel DMAs the rows itself
        from cfk_tpu.ops.pallas.gram_kernel import gram_tiles_gather_pallas

        return gram_tiles_gather_pallas(
            fixed_slice, nb, wt, rt, seg, num_segments=num_segments,
            tile_rows=tile_rows, carry=carry,
        )
    _, prec = _gram_compute_dtype(fixed_slice)
    if backend == "pallas" and 2 * num_segments * k * (k + 1) * 4 > (96 << 20):
        # The kernel keeps the whole (A, b) chunk output resident in VMEM
        # (double-buffered); past ~96 MB it cannot compile.  Dense shapes
        # never get here (full Netflix peaks at ~37 MB), but sparse ones
        # (many distinct entities per chunk) fall back to the XLA
        # segment-sum path instead of a Mosaic OOM.
        backend = "xla"
    if backend == "pallas":
        from cfk_tpu.ops.pallas.gram_kernel import gram_tiles_pallas

        return gram_tiles_pallas(
            g, rt, seg, num_segments=num_segments,
            tile_rows=tile_rows, carry=carry,
        )
    if backend != "xla":
        raise ValueError(f"unknown tiled gram backend {backend!r}")
    gt = g.reshape(-1, tile_rows, k)
    a_t = jnp.einsum(
        "ntk,ntl->nkl", gt, gt,
        preferred_element_type=jnp.float32, precision=prec,
    )
    # rt stays float32: the iALS sqrt-reparameterized b-coefficient
    # c/√(ε-clamped aw) reaches ~1e6·c at zero-strength entries, where a
    # bf16 cast costs ~0.5–1% relative b error (ADVICE r5); accumulation
    # is float32 anyway via preferred_element_type, so only this operand's
    # input rounding was at stake.
    b_t = jnp.einsum(
        "ntk,nt->nk", gt, rt.reshape(-1, tile_rows).astype(jnp.float32),
        preferred_element_type=jnp.float32, precision=prec,
    )
    a = jax.ops.segment_sum(
        a_t, seg, num_segments=num_segments, indices_are_sorted=True
    )
    b = jax.ops.segment_sum(
        b_t, seg, num_segments=num_segments, indices_are_sorted=True
    )
    if carry is not None:
        ca, cb, ci = carry
        a = a.at[0].add(ci * ca)
        b = b.at[0].add(ci * cb)
    return a, b


def _gathered_stream(fixed_slice, nb, wt, unit_weights, zero_appended,
                     pregathered, gather="xla"):
    """The gather prologue both chunk-Gram entries share: fetch the chunk's
    neighbor factors (or accept the pipeline-prefetched stream) and apply
    the sqrt-reparameterized weight — see ``_entity_gram_chunk``.

    ``gather="fused"`` returns None: there is no host-side stream to
    build — the gather-fused Pallas kernels DMA the indexed table rows
    into VMEM themselves (``ops.pallas.gram_kernel`` ``*_gather_pallas``)
    and apply the premultiply in-register; chunk bodies pass the index
    (and weight) chunks through instead of gathered rows."""
    if gather == "fused":
        return None
    k = fixed_slice.shape[-1]
    ct, _ = _gram_compute_dtype(fixed_slice)
    if pregathered is not None:
        g = pregathered  # [C, k], already in ct
    else:
        if zero_appended:
            fz = fixed_slice
        else:
            fz = jnp.concatenate([
                fixed_slice,
                _match_varying(
                    jnp.zeros((1, k), fixed_slice.dtype), fixed_slice
                ),
            ])
        g = fz[nb].astype(ct)  # [C, k]
    if not unit_weights:
        # Sqrt-weighted single stream (see _entity_gram_chunk): the
        # multiply fuses into the producing gather, and everything
        # downstream — kernel operands, both backends — sees one
        # stream, exactly like the unit path.
        g = g * wt.astype(ct)[:, None]
    return g


def _entity_gram_solve_chunk(
    fixed_slice, nb, wt, rt, seg, tile_rows, num_segments, lseg, reg,
    reg_mode, lam, unit_weights=False, zero_appended=False, carry=None,
    pregathered=None, gather="xla", algo=None,
):
    """Fused-epilogue twin of ``_entity_gram_chunk`` + the per-chunk solve.

    Returns (x [num_segments, k], carry_a [k, k], carry_b [k]): the
    chunk's (A, b) batch stays inside the Gram kernel's VMEM residency
    (``gram_solve_tiles_pallas``) where the ridge and the lane-vectorized
    elimination run in place — the split path's [Ec, k, k] HBM write +
    readback for the separate batched solve never happens.  The carry pair
    is the RAW (pre-ridge) partial of the boundary-straddling entity at
    ``lseg`` — exactly the ``a[lseg]``/``b[lseg]`` rows the split scan
    extracts.  Callers gate on ``resolve_fused_chunk_lam`` first (pallas
    backend + pallas solver + rank within the fused elimination cap).

    ``gather="fused"`` additionally keeps the [C, k] neighbor stream out
    of HBM (``gram_solve_tiles_gather_pallas`` — in-kernel DMA gather;
    see ``_entity_gram_chunk``); ``algo`` threads the elimination choice.
    """
    from cfk_tpu.ops.pallas.gram_kernel import (
        gram_solve_tiles_gather_pallas,
        gram_solve_tiles_pallas,
    )

    g = _gathered_stream(fixed_slice, nb, wt, unit_weights, zero_appended,
                         pregathered, gather=gather)
    if g is None:  # gather == "fused"
        return gram_solve_tiles_gather_pallas(
            fixed_slice, nb, wt, rt, seg, reg, lseg,
            num_segments=num_segments, tile_rows=tile_rows,
            reg_mode=reg_mode, lam=lam, carry=carry, algo=algo,
        )
    return gram_solve_tiles_pallas(
        g, rt, seg, reg, lseg, num_segments=num_segments,
        tile_rows=tile_rows, reg_mode=reg_mode, lam=lam, carry=carry,
        algo=algo,
    )


def _chunk_reg(cnt_c, implicit_reg):
    """The fused epilogue's regularizer operand: per-row counts (ALS-WR
    λ·n with the trash row floored at 1 — exactly the cnt_full the split
    path's ``regularized_solve`` sees) or the shared YᵀY+λI matrix
    (iALS).  One definition, so the stream and dense fused paths can
    never diverge on the trash-row floor."""
    if implicit_reg is None:
        return jnp.concatenate([cnt_c, jnp.ones((1,), cnt_c.dtype)])
    return implicit_reg


def resolve_fused_chunk_lam(fused_epilogue, solver, k, num_segments,
                            backend, lam, implicit, algo=None):
    """Static gating of the fused Gram+solve chunk path — the concretized
    λ when legal, None → the split Gram→HBM→solve schedule.

    Like ``resolve_gather_mode``, the logic lives in
    ``cfk_tpu.plan.registry`` (one resolver for the tiled bodies, the
    bucketed port, both ring half-steps, and the plan resolver's gates,
    with kernel-backend availability consulted); this alias keeps the
    existing import surface."""
    from cfk_tpu.plan.registry import resolve_fused_chunk_lam as _resolve

    return _resolve(fused_epilogue, solver, k, num_segments, backend, lam,
                    implicit, algo)


def resolve_tiled_route(mode, statics, k, lam, *, table_dtype, solver,
                        implicit=False, fused_epilogue=None,
                        in_kernel_gather=None, reg_solve_algo=None,
                        gram_backend=None):
    """(gather mode, fused-epilogue λ or None) of one tiled half-step: what
    the static gates resolve the knobs to for this mode's chunk statics.

    The ONE place a mode's statics are turned into the gates' arguments
    (scalar-prefetch words, segment count, block rows) — the three chunk
    bodies route by it, and ``chip_smoke.py`` prints and pins it.
    ``table_dtype`` is the dtype of the table the kernels would read
    (after quantization).  Accum mode has no per-chunk epilogue to fuse
    (its (A, b) lives in HBM across chunks), so its λ is always None."""
    backend = gram_backend or default_tiled_gram_backend()
    if mode == "dstream":
        nc, cap, e_c, t, nt, ng, bg = statics
        meta_words, block_rows = ng + 4 * nt + 1, bg
    elif mode == "stream":
        nc, cap, e_c, t = statics
        meta_words, block_rows = cap // t + 1, None
    else:
        nc, cap, t, h, e_c = statics
        meta_words, block_rows = cap // t, None
    gather = resolve_gather_mode(
        in_kernel_gather, backend, cap, meta_words, t, e_c + 1, k,
        block_rows, table_dtype=table_dtype,
    )
    fused_lam = None
    if mode != "accum":
        fused_lam = resolve_fused_chunk_lam(
            fused_epilogue, solver, k, e_c + 1, backend, lam, implicit,
            reg_solve_algo,
        )
    return gather, fused_lam


def quantize_tiled_operand(fixed_factors, blk, chunks, table_dtype):
    """Quantize a tiled half-step's gather operand (``ops.quant``).

    Returns (table, blk): the HBM-resident table the chunk bodies gather
    from (f32 identity / bf16 cast / int8 codes) and the block dict with
    the int8 per-row dequant scale FOLDED into the mode's per-entry weight
    stream — the canonical order (``quant.fold_scale`` first, then the one
    ``g = data[nb]·wt`` multiply) every gather path shares, which is what
    keeps the XLA gather, the Mosaic DMA gather, and the emulation twins
    bit-identical for any table dtype.  Mode specifics:

    - stream: the tile-aligned ``weight`` channel (0/1 mask, or √aw·mask
      for iALS) absorbs the scale; ``nb`` already indexes the table with
      F as the zero row.
    - dstream: the stream-aligned ``aweight_dense`` channel absorbs it —
      synthesized as the bare scale stream for explicit ALS, which has no
      weight channel of its own (dense padding indexes the zero row, whose
      appended scale is 0).
    - accum: slice-local indices are rebased to absolute table rows via
      the chunk's clamped window base (the same map ``abs_idx`` applies on
      the fused-gather route), so the fold indexes the true row's scale.
    """
    from cfk_tpu.ops import quant

    td = quant.resolve_table_dtype(table_dtype)
    if td == "float32":
        return fixed_factors, blk
    if td == "bfloat16":
        return fixed_factors.astype(jnp.bfloat16), blk
    data, scale = quant.quantize_table(fixed_factors, "int8")
    blk = dict(blk)
    mode = chunks[1]
    nb = blk["neighbor_idx"]
    if mode == "accum":
        nc, cap, t, h, e_c = tuple(chunks[2:])
        f_rows = fixed_factors.shape[0]
        base = jnp.repeat(blk["chunk_base"].reshape(nc), cap)
        abs_nb = jnp.where(nb < h, base + nb, f_rows)
        blk["weight"] = quant.fold_scale(blk["weight"], scale, abs_nb)
    elif mode == "dstream":
        wt = blk.get("aweight_dense")
        if wt is None:
            wt = jnp.ones(nb.shape, jnp.float32)
        blk["aweight_dense"] = quant.fold_scale(wt, scale, nb)
    else:
        blk["weight"] = quant.fold_scale(blk["weight"], scale, nb)
    return data, blk


def tiled_half_step(
    fixed_factors, blk, chunks, local_entities, lam, *,
    solver="cholesky", implicit_reg=None, overlap=None,
    fused_epilogue=None, in_kernel_gather=None, reg_solve_algo=None,
    table_dtype=None, return_chunk_rows=False,
):
    """Mode dispatch shared by the single-device and SPMD trainers.

    ``chunks`` is the static tuple ``("tiled", mode, *statics)`` the layout
    setup emits; ``blk`` the device-array dict of ``TiledBlocks`` fields.

    ``table_dtype`` quantizes the gather operand for this half-step
    (``ops.quant``; the solved factors keep the storage dtype): bf16
    halves the gather bytes, int8+per-row-scale quarters them, Gram/solve
    accumulation stays float32 either way.  ``None``/"float32" is
    bit-identical to the pre-quantization path.
    """
    mode = chunks[1]
    st = tuple(chunks[2:])
    fixed_factors, blk = quantize_tiled_operand(
        fixed_factors, blk, chunks, table_dtype
    )
    if return_chunk_rows and mode != "stream":
        # The windowed host-offload driver (cfk_tpu.offload) scatters on
        # the host; only the stream scan's per-chunk solve rows have that
        # shape — accum solves once at the end, dstream could support it
        # but no caller needs it yet.
        raise ValueError(
            f"return_chunk_rows is a stream-mode contract; mode={mode!r}"
        )
    if mode == "accum":
        return als_half_step_tiled_accum(
            fixed_factors, blk["neighbor_idx"], blk["rating"], blk["weight"],
            blk["tile_seg"], blk["chunk_base"], blk["chunk_entity"],
            blk["count"], local_entities, lam,
            statics=st, solver=solver, implicit_reg=implicit_reg,
            overlap=overlap, fused_epilogue=fused_epilogue,
            in_kernel_gather=in_kernel_gather, reg_solve_algo=reg_solve_algo,
        )
    if mode == "dstream":
        return als_half_step_tiled_dense(
            fixed_factors, blk["neighbor_idx"], blk["rating"],
            blk["tile_meta"], blk["chunk_entity"], blk["chunk_count"],
            blk["carry_in"], blk["last_seg"], local_entities, lam,
            statics=st, solver=solver, implicit_reg=implicit_reg,
            aweight_dense=blk.get("aweight_dense"),
            overlap=overlap, fused_epilogue=fused_epilogue,
            in_kernel_gather=in_kernel_gather, reg_solve_algo=reg_solve_algo,
        )
    return als_half_step_tiled(
        fixed_factors, blk["neighbor_idx"], blk["rating"], blk["weight"],
        blk["tile_seg"], blk["chunk_entity"], blk["chunk_count"],
        blk["carry_in"], blk["last_seg"], local_entities, lam,
        statics=st, solver=solver, implicit_reg=implicit_reg,
        overlap=overlap, fused_epilogue=fused_epilogue,
        in_kernel_gather=in_kernel_gather, reg_solve_algo=reg_solve_algo,
        return_chunk_rows=return_chunk_rows,
    )


_SQRT_WEIGHT_EPS = 1e-12  # clamp for α·r = 0 entries: their A-term becomes
# ε·f fᵀ (≪ the λ ≥ 0.01 ridge) while b stays exact — (c/√ε)·(√ε·f) = c·f.


def ials_tiled_half_step(
    fixed_factors, blk, chunks, local_entities, lam, alpha, *,
    gram=None, solver="cholesky", overlap=None,
    fused_epilogue=None, in_kernel_gather=None, reg_solve_algo=None,
    table_dtype=None,
):
    """Implicit-feedback (Hu et al. 2008) half-iteration on tiled blocks.

    Same global-Gram trick as ``ops.solve.ials_half_step``: per entity
    A = YᵀY + Σ_obs (c−1)·f fᵀ + λI with c = 1 + α·r.  The per-entry
    A-weight is carried as a **sqrt reparameterization** (round 5): the
    half-steps stream ONE weighted copy gs = √(α·r)·f and compute
    A = gsᵀgs = Σ α·r·f fᵀ exactly, with the b-coefficient rescaled to
    c/√(α·r) so b = Σ (c/√aw)·(√aw·f) = Σ c·f.  Round 4's premultiplied
    gw = α·r·f second stream DOUBLED the Gram kernels' pipelined input
    traffic and (at k = 128) squeezed VMEM — which is what made the dense
    layout measure slower for iALS (VERDICT r4 #3); the reparameterization
    makes the weighted path byte-identical in kernel traffic to the
    unit-weight path (no second stream, no kernel change).  Entries with
    α·r = 0 are kept exact in b by the ε clamp (``_SQRT_WEIGHT_EPS``);
    negative interaction strengths are invalid for iALS — the trainers
    reject them at entry (``models.ials._check_nonnegative_strengths``),
    so the clamp here never sees one on a supported path.  Both tile
    modes work unchanged with the YᵀY + λI term added at solve time via
    ``implicit_reg``.
    """
    k = fixed_factors.shape[-1]
    if gram is None:
        from cfk_tpu.ops import quant
        from cfk_tpu.ops.solve import global_gram

        # YᵀY must sum the SAME dequantized rows the Gram kernels gather
        # (ops.quant.gather_operand_view), or the shared implicit_reg term
        # and the per-entity observed Grams would disagree on what the
        # fixed factors ARE — the quantized-table analog of the subspace
        # score-stream consistency rule.
        gram = global_gram(
            quant.gather_operand_view(fixed_factors, table_dtype)
        )
    reg = gram + lam * jnp.eye(k, dtype=jnp.float32)
    blk = dict(blk)
    if chunks[1] == "dstream" and ("rating_dense" not in blk
                                   or "weight" not in blk):
        raise ValueError(
            "iALS on dense-stream blocks needs the weighted channels "
            "(rating_dense + tile-aligned weight); this dataset was "
            "staged without them — use the iALS device setup "
            "(weighted=True) or rebuild"
        )
    # b-coefficient c·mask, rescaled by 1/√aw from the TILE-ALIGNED
    # channels (rating carries r at valid slots, weight the 1.0 mask; both
    # zero at padding, so rt' is zero there too).
    aw_tile = jnp.sqrt(jnp.maximum(alpha * blk["rating"], _SQRT_WEIGHT_EPS))
    rt_scaled = (1.0 + alpha * blk["rating"]) * blk["weight"] / aw_tile
    if chunks[1] == "dstream":
        # Dense-stream weighted path: the √aw factor multiplies the
        # gathered stream (aweight_dense, STREAM-ALIGNED), fusing into the
        # gather; the kernel then runs its UNIT-weight path on gs.
        blk["rating"] = rt_scaled
        blk["aweight_dense"] = jnp.sqrt(jnp.maximum(
            alpha * blk["rating_dense"], _SQRT_WEIGHT_EPS))
        return tiled_half_step(
            fixed_factors, blk, chunks, local_entities, lam,
            solver=solver, implicit_reg=reg, overlap=overlap,
            fused_epilogue=fused_epilogue,
            in_kernel_gather=in_kernel_gather, reg_solve_algo=reg_solve_algo,
            table_dtype=table_dtype,
        )
    # The ε-clamped √aw is re-masked by the original 0/1 weight channel:
    # at valid entries ×1.0 is exact, and at padding the XLA path's
    # zero-row gather made ×√ε indistinguishable from ×0 anyway (0·√ε =
    # 0·0 = 0, bit-equal) — but the in-kernel gather path uses this
    # weight AS the padding mask (the DMA'd rows are clamped table rows,
    # not zeros), so the mask must survive the reparameterization.
    blk["rating"], blk["weight"] = rt_scaled, aw_tile * blk["weight"]
    return tiled_half_step(
        fixed_factors, blk, chunks, local_entities, lam,
        solver=solver, implicit_reg=reg, overlap=overlap,
        fused_epilogue=fused_epilogue,
        in_kernel_gather=in_kernel_gather, reg_solve_algo=reg_solve_algo,
        table_dtype=table_dtype,
    )


def als_half_step_tiled(
    fixed_factors: jax.Array,  # [F, k] full fixed side
    neighbor_idx: jax.Array,  # [NC·C] int32
    rating: jax.Array,  # [NC·C] f32 (b coefficient; 0 at padding)
    weight: jax.Array,  # [NC·C] f32 (A weight; 0 at padding)
    tile_seg: jax.Array,  # [NC·NT] int32 chunk-relative entity of each tile
    chunk_entity: jax.Array,  # [NC·Ec] shard-local entity row (trash = E_local)
    chunk_count: jax.Array,  # [NC·Ec] full rating count of finalized rows
    carry_in: jax.Array,  # [NC] 1.0 = seg 0 continues the previous chunk
    last_seg: jax.Array,  # [NC] chunk-relative index of the last real segment
    local_entities: int,
    lam: float,
    *,
    statics: tuple[int, int, int, int],  # (NC, C, Ec, T)
    solver: str = "cholesky",
    implicit_reg: jax.Array | None = None,  # [k,k] YᵀY+λI (iALS); None = ALS-WR
    gram_backend: str | None = None,
    overlap: bool | None = None,
    fused_epilogue: bool | None = None,
    in_kernel_gather: bool | None = None,
    reg_solve_algo: str | None = None,
    return_chunk_rows: bool = False,
) -> jax.Array:
    """Stream-mode tiled half-iteration (the many-entities side).

    Chunk-scan structure and carry semantics match
    ``ops.solve.als_half_step_segment`` exactly; only the Gram accumulation
    differs (fused pallas grouped-Gram kernel / batched tile GEMM +
    segment-sum).  Under the pallas backend, rows of segments owning no
    tile are unwritten garbage; their solves land in the trash row of
    ``out`` (``chunk_entity`` routes non-finalized rows there), so nothing
    real ever reads them.

    With ``overlap`` (the default) the chunk scan is double-buffered
    (``ops.pipeline.prefetch_scan``): chunk c+1's neighbor-factor gather —
    the row-slot-bound phase — is issued before chunk c's Gram+solve
    consume the other buffer, so the gather engine and the MXU run
    concurrently instead of strictly alternating.  Same gathers, same
    per-chunk op order, bit-identical factors (``tests/test_overlap.py``).

    ``fused_epilogue`` (default: on wherever legal — see
    ``resolve_fused_chunk_lam``) solves each chunk's normal equations
    INSIDE the Gram kernel's VMEM residency: the per-chunk [Ec, k, k]
    A-batch never round-trips through HBM, and the scan body consumes
    (x, carry) straight from the fused kernel.

    ``in_kernel_gather`` (default: on wherever legal — see
    ``resolve_gather_mode``) additionally retires the materialized [C, k]
    neighbor stream: the chunk bodies pass the index/weight chunks and
    the kernel DMAs the table rows itself; with overlap the pipelines
    then prefetch the INDEX chunk instead of the gathered one (the
    double-buffering moves inside the kernel).  Factors are bit-identical
    across the knob (tests/test_in_kernel_gather.py).
    """
    backend = gram_backend or default_tiled_gram_backend()
    overlap = resolve_overlap(overlap)
    nc, cap, e_c, t = statics
    k = fixed_factors.shape[-1]
    nt = cap // t
    # int8 tables (ops.quant) carry the per-row dequant scale folded into
    # the weight channel, so the single premultiply that realizes the
    # padding zero row is ALSO the dequantize — the unit-weight shortcut
    # (which skips that multiply on the XLA route) must not fire.
    unit = implicit_reg is None and fixed_factors.dtype != jnp.int8
    gather, fused_lam = resolve_tiled_route(
        "stream", statics, k, lam, table_dtype=fixed_factors.dtype,
        solver=solver, implicit=implicit_reg is not None,
        fused_epilogue=fused_epilogue, in_kernel_gather=in_kernel_gather,
        reg_solve_algo=reg_solve_algo, gram_backend=backend,
    )
    chunks = (
        neighbor_idx.reshape(nc, cap), rating.reshape(nc, cap),
        weight.reshape(nc, cap), tile_seg.reshape(nc, nt),
        chunk_entity.reshape(nc, e_c), chunk_count.reshape(nc, e_c),
        carry_in.reshape(nc), last_seg.reshape(nc),
    )

    def solve_chunk_rows(a, b, cnt_c):
        # The whole batch is solved including the trash row — solving it
        # beats slicing it away, which copied the batch again.  fused=True
        # pins the reg+solve FUSION (one kernel pass, the pre-existing
        # default): the fused_epilogue A/B toggles only the Gram→HBM→solve
        # round-trip, so split and fused chunk factors stay bit-exact and
        # a patched process default cannot swap the elimination algorithm
        # under the baseline.
        if implicit_reg is None:
            return regularized_solve(a, b, _chunk_reg(cnt_c, None), lam,
                                     solver, fused=True,
                                     algo=reg_solve_algo)
        return regularized_solve_matrix(a, b, implicit_reg, solver,
                                        fused=True, algo=reg_solve_algo)

    def body(carry, chunk):
        a0, b0 = carry
        nb_c, rt_c, wt_c, ts_c, ent_c, cnt_c, cin_c, lseg_c = chunk
        # Segment 0 may continue the previous chunk's last entity; the
        # carried partial is folded into segment 0 INSIDE the Gram kernel
        # (one fma pass over the resident accumulator) — folding it
        # outside either rewrote the whole [Ec,k,k] batch through HBM
        # (~0.17 ms/chunk) or cost a separate one-system solve per chunk
        # (~0.1 ms/chunk at rank 128).  The non-default gram_backend="xla"
        # A/B path DOES still pay the at[0].add batch rewrite (see
        # _entity_gram_chunk) — acceptable for a measurement-only branch.
        if fused_lam is not None:
            # Fused epilogue: ridge + solve run on the VMEM-resident
            # (A, b); only the solved rows and the raw carry row return.
            x, a1, b1 = _entity_gram_solve_chunk(
                fixed_factors, nb_c, wt_c, rt_c, ts_c, t, e_c + 1, lseg_c,
                _chunk_reg(cnt_c, implicit_reg),
                "diag" if implicit_reg is None else "matrix", fused_lam,
                unit_weights=unit, carry=(a0, b0, cin_c),
                gather=gather, algo=reg_solve_algo,
            )
            return (a1, b1), x[:e_c]
        a, b = _entity_gram_chunk(
            fixed_factors, nb_c, wt_c, rt_c, ts_c, t, e_c + 1, backend,
            unit_weights=unit, carry=(a0, b0, cin_c),
            gather=gather,
        )
        x = solve_chunk_rows(a, b, cnt_c)
        a1 = lax.dynamic_index_in_dim(a, lseg_c, 0, keepdims=False)
        b1 = lax.dynamic_index_in_dim(b, lseg_c, 0, keepdims=False)
        return (a1, b1), x[:e_c]

    init = jax.tree.map(
        lambda z: _match_varying(z, neighbor_idx),
        (
            jnp.zeros((k, k), jnp.float32),
            jnp.zeros((k,), jnp.float32),
        ),
    )
    # Solutions are emitted as stacked scan outputs and scattered ONCE
    # after the loop — carrying the [E+1, k] output buffer through the
    # scan rewrote it copy-on-write every chunk.  Trash-row collisions
    # (every non-finalized position routes to E_local) are harmless:
    # scatter-set keeps one of them and the trash row is dropped below.
    if overlap:
        # Double-buffered: the [cap, k] gather for chunk c+1 is issued
        # before chunk c's Gram/solve; the zero row is appended to the
        # fixed table ONCE (the serial body re-concatenates per chunk —
        # same values either way).  With the in-kernel gather the
        # pipeline prefetches the INDEX chunk instead — the gather itself
        # (and its double-buffering) now lives inside the kernel, so the
        # fetch is one cheap dynamic_slice and on/off stay bit-equal by
        # construction.
        ct, _ = _gram_compute_dtype(fixed_factors)
        if gather == "fused":
            from cfk_tpu.ops.pipeline import index_fetch

            fetch = index_fetch(neighbor_idx, cap)
        else:
            fz = jnp.concatenate([
                fixed_factors,
                _match_varying(
                    jnp.zeros((k,), fixed_factors.dtype)[None], fixed_factors
                ),
            ])

            def fetch(i):
                nb_c = lax.dynamic_slice(neighbor_idx, (i * cap,), (cap,))
                return fz[nb_c].astype(ct)

        def compute(carry, buf, x, _i):
            a0, b0 = carry
            rt_c, wt_c, ts_c, cnt_c, cin_c, lseg_c = x
            nb_c = buf if gather == "fused" else None
            g_cur = None if gather == "fused" else buf
            if fused_lam is not None:
                x_rows, a1, b1 = _entity_gram_solve_chunk(
                    fixed_factors, nb_c, wt_c, rt_c, ts_c, t, e_c + 1,
                    lseg_c, _chunk_reg(cnt_c, implicit_reg),
                    "diag" if implicit_reg is None else "matrix", fused_lam,
                    unit_weights=unit,
                    carry=(a0, b0, cin_c), pregathered=g_cur, gather=gather,
                    algo=reg_solve_algo,
                )
                return (a1, b1), x_rows[:e_c]
            a, b = _entity_gram_chunk(
                fixed_factors, nb_c, wt_c, rt_c, ts_c, t, e_c + 1, backend,
                unit_weights=unit, carry=(a0, b0, cin_c),
                pregathered=g_cur, gather=gather,
            )
            x_rows = solve_chunk_rows(a, b, cnt_c)
            a1 = lax.dynamic_index_in_dim(a, lseg_c, 0, keepdims=False)
            b1 = lax.dynamic_index_in_dim(b, lseg_c, 0, keepdims=False)
            return (a1, b1), x_rows[:e_c]

        _, xs = prefetch_scan(
            fetch, compute, nc, init,
            xs=(chunks[1], chunks[2], chunks[3], chunks[5], chunks[6],
                chunks[7]),
        )
    else:
        _, xs = lax.scan(body, init, chunks)
    if return_chunk_rows:
        # The windowed host-offload driver (cfk_tpu.offload.windowed)
        # scatters these by chunk_entity on the HOST — same values the
        # device scatter below would place, minus the [E, k] buffer.
        return xs.reshape(nc * e_c, k)
    out = _match_varying(
        jnp.zeros((local_entities + 1, k), jnp.float32), neighbor_idx
    )
    out = out.at[chunk_entity.reshape(nc * e_c)].set(xs.reshape(nc * e_c, k))
    return out[:local_entities]


def als_half_step_tiled_dense(
    fixed_factors: jax.Array,  # [F, k] full fixed side
    neighbor_idx: jax.Array,  # [NC·C] int32 DENSE stream (pad8 → zero row)
    rating: jax.Array,  # [NC·NT·T] f32 TILE-ALIGNED b coefficients
    tile_meta: jax.Array,  # [NC·(NG+4·NT)] int32 per-tile window metadata
    chunk_entity: jax.Array,  # [NC·Ec] finalization rows (trash = E_local)
    chunk_count: jax.Array,  # [NC·Ec]
    carry_in: jax.Array,  # [NC]
    last_seg: jax.Array,  # [NC]
    local_entities: int,
    lam: float,
    *,
    statics: tuple[int, int, int, int, int, int, int],  # (NC,C,Ec,T,NT,NG,BG)
    solver: str = "cholesky",
    implicit_reg: jax.Array | None = None,
    gram_backend: str | None = None,
    aweight_dense: jax.Array | None = None,  # [NC·C] per-entry A-weights
    overlap: bool | None = None,
    fused_epilogue: bool | None = None,
    in_kernel_gather: bool | None = None,
    reg_solve_algo: str | None = None,
) -> jax.Array:
    """Dense-stream tiled half-iteration (the many-entities side, unpadded).

    Identical scan/carry/finalization semantics to ``als_half_step_tiled``;
    the difference is the stream: entries are packed with only 16-row run
    alignment (the XLA gather that feeds each chunk fetches ~nnz rows, not
    ~1.26·nnz — the row-slot-bound gather engine is the iteration's
    binding resource), and the pallas kernel reconstructs [T]-row tiles as
    masked dynamic windows (``gram_tiles_dense_pallas``).  The weighted
    path (iALS: ``implicit_reg`` + ``aweight_dense`` carrying √aw)
    multiplies the single gathered stream (gs = √aw·g, fused into the
    gather) and runs the kernel's unit-weight path on it — see
    ``ials_tiled_half_step`` for the sqrt reparameterization.  ``overlap``
    double-buffers the chunk scan exactly as in ``als_half_step_tiled``
    (the dense gather for chunk c+1 runs under chunk c's Gram/solve)."""
    if implicit_reg is not None and aweight_dense is None:
        raise ValueError(
            "weighted dense-stream half-step needs aweight_dense (the "
            "per-entry A-weights aligned with the gather stream)"
        )
    backend = gram_backend or default_tiled_gram_backend()
    overlap = resolve_overlap(overlap)
    nc, cap, e_c, t, nt, ng, bg = statics
    k = fixed_factors.shape[-1]
    gather, fused_lam = resolve_tiled_route(
        "dstream", statics, k, lam, table_dtype=fixed_factors.dtype,
        solver=solver, implicit=implicit_reg is not None,
        fused_epilogue=fused_epilogue, in_kernel_gather=in_kernel_gather,
        reg_solve_algo=reg_solve_algo, gram_backend=backend,
    )
    ct, _ = _gram_compute_dtype(fixed_factors)
    if gather != "fused":
        # The zero-row-appended table only exists for the XLA-gather
        # schedule; the in-kernel gather realizes the zero row in-register
        # (clamp + window mask) and never builds this copy.
        fz = jnp.concatenate([
            fixed_factors,
            _match_varying(jnp.zeros((1, k), fixed_factors.dtype),
                           fixed_factors),
        ])
    chunks = (
        neighbor_idx.reshape(nc, cap), rating.reshape(nc, nt * t),
        tile_meta.reshape(nc, ng + 4 * nt), last_seg.reshape(nc),
        carry_in.reshape(nc), chunk_count.reshape(nc, e_c),
    )
    # The weighted stream channel exists whenever aweight_dense is staged —
    # iALS (√aw), or explicit ALS on an int8 table (the synthesized dequant
    # scale stream, quantize_tiled_operand) — not only under implicit_reg.
    if aweight_dense is not None:
        chunks = chunks + (aweight_dense.reshape(nc, cap),)

    def gram_solve(carry, g, x, nb_c=None):
        # ``g`` is the gathered stream on the XLA-gather schedule; with
        # the in-kernel gather it is None and ``nb_c`` carries the index
        # chunk instead — the kernel DMAs the rows and applies the √aw
        # premultiply (the stream-aligned weight channel) in-register.
        a0, b0 = carry
        rt_c, meta_c, lseg_c, cin_c, cnt_c = x[:5]
        wt_c = x[5] if aweight_dense is not None else None
        if gather != "fused" and wt_c is not None:
            g = g * wt_c.astype(ct)[:, None]  # sqrt-weighted single stream
        if fused_lam is not None:
            # Fused epilogue: the dense kernel solves its VMEM-resident
            # (A, b) in place — no [Ec, k, k] HBM round-trip per chunk.
            from cfk_tpu.ops.pallas.gram_kernel import (
                gram_solve_tiles_dense_gather_pallas,
                gram_solve_tiles_dense_pallas,
            )

            reg_kw = dict(
                num_segments=e_c + 1, tile_rows=t, num_tiles=nt,
                num_groups=ng, block_rows=bg,
                reg_mode="diag" if implicit_reg is None else "matrix",
                lam=fused_lam, carry=(a0, b0, cin_c), algo=reg_solve_algo,
            )
            if gather == "fused":
                x_rows, a1, b1 = gram_solve_tiles_dense_gather_pallas(
                    fixed_factors, nb_c, wt_c, rt_c, meta_c,
                    _chunk_reg(cnt_c, implicit_reg), lseg_c, **reg_kw,
                )
            else:
                x_rows, a1, b1 = gram_solve_tiles_dense_pallas(
                    g, rt_c, meta_c, _chunk_reg(cnt_c, implicit_reg),
                    lseg_c, **reg_kw,
                )
            return (a1, b1), x_rows[:e_c]
        if gather == "fused":
            from cfk_tpu.ops.pallas.gram_kernel import (
                gram_tiles_dense_gather_pallas,
            )

            a, b = gram_tiles_dense_gather_pallas(
                fixed_factors, nb_c, wt_c, rt_c, meta_c,
                num_segments=e_c + 1, tile_rows=t, num_tiles=nt,
                num_groups=ng, block_rows=bg, carry=(a0, b0, cin_c),
            )
        else:
            a, b = gram_tiles_dense_pallas_dispatch(
                g, rt_c, meta_c, num_segments=e_c + 1, tile_rows=t,
                num_tiles=nt, num_groups=ng, block_rows=bg,
                carry=(a0, b0, cin_c), backend=backend,
            )
        # fused=True: same rationale as the stream body's solve_chunk_rows
        # — the A/B axis is the round-trip, not the reg+solve fusion.
        if implicit_reg is None:
            x_rows = regularized_solve(a, b, _chunk_reg(cnt_c, None), lam,
                                       solver, fused=True,
                                       algo=reg_solve_algo)
        else:
            x_rows = regularized_solve_matrix(a, b, implicit_reg, solver,
                                              fused=True,
                                              algo=reg_solve_algo)
        a1 = lax.dynamic_index_in_dim(a, lseg_c, 0, keepdims=False)
        b1 = lax.dynamic_index_in_dim(b, lseg_c, 0, keepdims=False)
        return (a1, b1), x_rows[:e_c]

    init = jax.tree.map(
        lambda z: _match_varying(z, neighbor_idx),
        (
            jnp.zeros((k, k), jnp.float32),
            jnp.zeros((k,), jnp.float32),
        ),
    )
    if overlap:
        # Double-buffered: chunk c+1's dense gather (the iteration's
        # binding resource — see the layout rationale above) is issued
        # before chunk c's Gram/solve; the √aw premultiply stays at
        # compute time so the fetch is a pure gather.  With the in-kernel
        # gather the pipeline prefetches the index chunk instead — the
        # gather (and its double buffer) lives inside the kernel.
        if gather == "fused":
            from cfk_tpu.ops.pipeline import index_fetch

            fetch = index_fetch(neighbor_idx, cap)

            def compute(carry, buf, x, _i):
                return gram_solve(carry, None, x, nb_c=buf)
        else:
            def fetch(i):
                nb_c = lax.dynamic_slice(neighbor_idx, (i * cap,), (cap,))
                return fz[nb_c].astype(ct)

            def compute(carry, buf, x, _i):
                return gram_solve(carry, buf, x)

        _, xs = prefetch_scan(fetch, compute, nc, init, xs=chunks[1:])
    elif gather == "fused":
        _, xs = lax.scan(
            lambda carry, chunk: gram_solve(
                carry, None, chunk[1:], nb_c=chunk[0]
            ),
            init, chunks,
        )
    else:
        _, xs = lax.scan(
            lambda carry, chunk: gram_solve(
                carry, fz[chunk[0]].astype(ct), chunk[1:]
            ),
            init, chunks,
        )
    out = _match_varying(
        jnp.zeros((local_entities + 1, k), jnp.float32), neighbor_idx
    )
    out = out.at[chunk_entity.reshape(nc * e_c)].set(xs.reshape(nc * e_c, k))
    return out[:local_entities]


def gram_tiles_dense_pallas_dispatch(g, rt, meta, *, num_segments, tile_rows,
                                     num_tiles, num_groups, block_rows,
                                     carry, backend):
    """Route to the dense kernel (or its XLA emulation for A/B runs)."""
    from cfk_tpu.ops.pallas.gram_kernel import gram_tiles_dense_pallas

    return gram_tiles_dense_pallas(
        g, rt, meta, num_segments=num_segments, tile_rows=tile_rows,
        num_tiles=num_tiles, num_groups=num_groups, block_rows=block_rows,
        carry=carry, interpret=True if backend == "xla" else None,
    )


def als_half_step_tiled_accum(
    fixed_factors: jax.Array,  # [F, k] full fixed side
    neighbor_idx: jax.Array,  # [NC·C] int32 SLICE-LOCAL indices
    rating: jax.Array,  # [NC·C] f32
    weight: jax.Array,  # [NC·C] f32
    tile_seg: jax.Array,  # [NC·NT] int32 chunk-dense entity rank (trash = Ec)
    chunk_base: jax.Array,  # [NC] int32 table-slice row offset per chunk
    chunk_entity: jax.Array,  # [NC·Ec] shard-local entity of each rank (trash = E_local)
    count: jax.Array,  # [E_local] real rating count (regularizer)
    local_entities: int,
    lam: float,
    *,
    statics: tuple[int, int, int, int, int],  # (NC, C, T, H, Ec)
    solver: str = "cholesky",
    implicit_reg: jax.Array | None = None,
    gram_backend: str | None = None,
    overlap: bool | None = None,
    fused_epilogue: bool | None = None,
    in_kernel_gather: bool | None = None,
    reg_solve_algo: str | None = None,
) -> jax.Array:
    """Accumulator-mode tiled half-iteration (the few-entities side).

    Entries are sorted by (fixed-table slice, entity); each chunk gathers
    from a ``lax.dynamic_slice`` of H rows (statically small ⇒ the fast
    gather strategy).  Tile Grams first reduce *within the chunk* to its ≤
    Ec distinct entities (high-degree sides average ~90 tiles per entity,
    so 16k tiles collapse to a few hundred rows) and scatter-add into the
    persistent [E+1, k, k] accumulator via the chunk's entity list —
    touching megabytes per chunk instead of rewriting the whole accumulator
    (profiled at 3.6× the traffic).  ``tile_seg`` ranks are chunk-DENSE
    (slicing leaves gaps in the entity sequence, so ranks, not offsets);
    ranks owning no tile keep their unwritten-garbage Gram rows, and their
    ``chunk_entity`` slot routes them to the accumulator's trash row.
    Entities recur across slices, so per-chunk finalization is impossible
    and the solve happens once at the end.  Only legal when E_local·k² fits
    comfortably in HBM; the builder picks this mode exactly when the fixed
    side is the big one, which is also when the solve side is small
    (480k-user table ⇔ 17.7k movies).

    ``overlap`` double-buffers the chunk scan: chunk c+1's window select +
    gather is issued before chunk c's Gram + accumulator scatter-add.

    ``in_kernel_gather`` (default on where legal) retires accum mode's
    whole window machinery: slice-local indices
    are rebased to ABSOLUTE table rows (a cheap [C] int32 map — the
    clamped window base comes along as data) and the gather-fused kernel
    DMAs the rows straight from the full table, so neither the hoisted
    duplicate window stack (``gz``, a second resident copy of the fixed
    table) nor the per-chunk window copy is built — in-kernel DMA has no
    analog of XLA's operand-size gather cliff that forced them.
    """
    backend = gram_backend or default_tiled_gram_backend()
    overlap = resolve_overlap(overlap)
    nc, cap, t, h, e_c = statics
    k = fixed_factors.shape[-1]
    nt = cap // t
    # int8 tables: the dequant scale rides the (absolute-index-folded)
    # weight channel, so the weighted multiply must run (see the stream
    # body / quantize_tiled_operand).
    unit = implicit_reg is None and fixed_factors.dtype != jnp.int8
    gather, _ = resolve_tiled_route(
        "accum", statics, k, lam, table_dtype=fixed_factors.dtype,
        solver=solver, in_kernel_gather=in_kernel_gather,
        gram_backend=backend,
    )
    chunks = (
        neighbor_idx.reshape(nc, cap), rating.reshape(nc, cap),
        weight.reshape(nc, cap), tile_seg.reshape(nc, nt),
        chunk_base.reshape(nc), chunk_entity.reshape(nc, e_c),
    )

    # Build each slice's [h+1, k] gather window (zero row appended) ONCE,
    # outside the chunk scan — the in-body concatenate re-copied the whole
    # 17 MB slice every chunk (``pad.41``, ~25 ms/iter at full Netflix).
    # Cost of the win: ``gz`` is a second resident copy of the fixed-side
    # table (~61 MB bf16 for the full-Netflix user side) — accepted
    # because accum mode's dominant allocation is the [E+1,k,k]
    # accumulator (~290 MB there) and HBM is 16 GB; revisit before the
    # accumulator side ever grows past HBM/3.
    # Window bases replicate the builder's clamp (`min(s·h, F−h)`,
    # blocks.py) and are static, so the windows are static slices; a chunk
    # finds its window by comparing its base against the static base list
    # (the clamped last base is NOT a multiple of h, so `base // h` would
    # misroute it).
    f_rows = fixed_factors.shape[0]
    n_slices = max(1, -(-f_rows // h))
    bases = [min(s * h, max(f_rows - h, 0)) for s in range(n_slices)]
    # The hoisted window stack is a second resident copy of the fixed
    # table (~61 MB bf16 at full Netflix — fine next to the ~290 MB
    # accumulator).  On corpora where it would stop being a rounding
    # error (> _GZ_HOISTED_BUDGET_BYTES), degrade to the per-chunk
    # dynamic_slice + concat path instead of OOMing: same math, pays the
    # in-body slice copy the hoist was measured to save (~25 ms/iter).
    # The in-kernel gather (gather == "fused") never
    # builds the windows at all — absolute indices go straight to the
    # kernel's DMA, which has no operand-size gather cliff to dodge.
    gz_bytes = n_slices * (h + 1) * k * fixed_factors.dtype.itemsize
    hoist = gz_bytes <= _GZ_HOISTED_BUDGET_BYTES and gather != "fused"
    if gather != "fused":
        zrow = _match_varying(
            jnp.zeros((1, k), fixed_factors.dtype), fixed_factors
        )
    if hoist:
        gz = jnp.stack([
            jnp.concatenate([
                lax.slice_in_dim(fixed_factors, b, b + h), zrow
            ])
            for b in bases
        ])  # [n_slices, h+1, k]
    bases_arr = _match_varying(
        jnp.asarray(bases, jnp.int32), fixed_factors
    )

    def abs_idx(nb_c, base_c):
        # Slice-local → absolute (gather == "fused"): valid rows offset
        # by the chunk's clamped window base; the slice-local zero row
        # (index h) maps to the table-level virtual zero row (index F)
        # the gather kernels realize in-register.
        return jnp.where(nb_c < h, base_c + nb_c, f_rows)

    def select_window(base_c):
        if hoist:
            s_idx = jnp.sum((base_c >= bases_arr).astype(jnp.int32)) - 1
            # The per-chunk window COPY (dynamic_index of gz, ~9 ms/iter
            # at rank 64) is the cheap side of a measured trade: gathering
            # straight from the flattened [n_slices·(h+1), k] table with a
            # scalar row offset (no copy) regressed 0.71 → 1.67 s/iter —
            # XLA's gather strategy keys on OPERAND size, and the flat
            # table is past the ~34 MB fast-gather cliff even though each
            # chunk only touches one window of it.
            fixed_slice = lax.dynamic_index_in_dim(
                gz, s_idx, 0, keepdims=False
            )
        else:
            fixed_slice = jnp.concatenate([
                lax.dynamic_slice_in_dim(fixed_factors, base_c, h), zrow
            ])
        return fixed_slice

    def accumulate(carry, a, b, ent_c):
        # Rank rows owning no tile are unwritten garbage under the pallas
        # backend; ent_c routes them (and any NaN they hold) to the trash
        # row, which nothing reads.  The trash segment a[e_c] is dropped.
        acc_a, acc_b = carry
        acc_a = acc_a.at[ent_c].add(a[:e_c])
        acc_b = acc_b.at[ent_c].add(b[:e_c])
        return acc_a, acc_b

    def body(carry, chunk):
        nb_c, rt_c, wt_c, ts_c, base_c, ent_c = chunk
        if gather == "fused":
            a, b = _entity_gram_chunk(
                fixed_factors, abs_idx(nb_c, base_c), wt_c, rt_c, ts_c, t,
                e_c + 1, backend, unit_weights=unit,
                gather=gather,
            )
        else:
            fixed_slice = select_window(base_c)
            a, b = _entity_gram_chunk(
                fixed_slice, nb_c, wt_c, rt_c, ts_c, t, e_c + 1, backend,
                unit_weights=unit, zero_appended=True,
            )
        return accumulate(carry, a, b, ent_c), None

    init = jax.tree.map(
        lambda z: _match_varying(z, neighbor_idx),
        (
            jnp.zeros((local_entities + 1, k, k), jnp.float32),
            jnp.zeros((local_entities + 1, k), jnp.float32),
        ),
    )
    if overlap:
        # Double-buffered: chunk c+1's window select + slice-local gather
        # runs under chunk c's Gram + accumulator scatter-add.  The window
        # bases come from the raw [NC] chunk_base array so the fetch needs
        # no chunk tuple.  With the in-kernel gather the fetch is the
        # absolute-index rebase only (the DMA gather moved into the
        # kernel).
        ct, _ = _gram_compute_dtype(fixed_factors)
        base_flat = chunk_base.reshape(nc)

        def fetch(i):
            base_c = lax.dynamic_index_in_dim(
                base_flat, i, 0, keepdims=False
            )
            nb_c = lax.dynamic_slice(neighbor_idx, (i * cap,), (cap,))
            if gather == "fused":
                return abs_idx(nb_c, base_c)
            return select_window(base_c)[nb_c].astype(ct)

        def compute(carry, buf, x, _i):
            rt_c, wt_c, ts_c, ent_c = x
            if gather == "fused":
                a, b = _entity_gram_chunk(
                    fixed_factors, buf, wt_c, rt_c, ts_c, t, e_c + 1,
                    backend, unit_weights=unit,
                    gather=gather,
                )
            else:
                a, b = _entity_gram_chunk(
                    fixed_factors, None, wt_c, rt_c, ts_c, t, e_c + 1,
                    backend, unit_weights=unit,
                    zero_appended=True, pregathered=buf,
                )
            return accumulate(carry, a, b, ent_c), None

        (acc_a, acc_b), _ = prefetch_scan(
            fetch, compute, nc, init,
            xs=(chunks[1], chunks[2], chunks[3], chunks[5]),
        )
    else:
        (acc_a, acc_b), _ = lax.scan(body, init, chunks)
    # Accum mode's (A, b) lives in HBM ACROSS chunks by design (entities
    # recur across table slices), so there is no per-chunk VMEM residency
    # to solve inside; the fused knob here gates the one fused reg+solve
    # pass over the final accumulator vs the split ridge-add + dispatch
    # (the bench's fused/split A/B axis).
    a, b = acc_a[:local_entities], acc_b[:local_entities]
    if implicit_reg is None:
        return regularized_solve(a, b, count, lam, solver,
                                 fused=fused_epilogue, algo=reg_solve_algo)
    return regularized_solve_matrix(a, b, implicit_reg, solver,
                                    fused=fused_epilogue,
                                    algo=reg_solve_algo)
