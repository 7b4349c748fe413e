"""Pallas TPU kernel: fused grouped Gram accumulation over entity tiles.

The tiled layout (``cfk_tpu.ops.tiled``) computes per-entity normal-equation
terms A_e = Σ w·f fᵀ, b_e = Σ r·f from [T, k] tiles, each tile owned by one
entity.  The XLA formulation materializes the per-tile Gram batch [NT, k, k]
(128 MB per 1M-entry chunk), pays a layout copy of the gathered factors
before the batched GEMM, zero-fills a segment-sum accumulator, and reduces
tiles to entities through it — together ~60% of the measured chunk cost
(round-3 profile: gram GEMM 1.0 ms + segment-sum 1.5 ms + layout copy
0.6 ms + b-reduce 0.36 ms + zeros 0.2 ms per 1M-entry chunk, vs 1.7 ms for
the irreducible neighbor gather).  This kernel fuses all of it: the whole
per-chunk output (A [S, k, k], b [S, 1, k]; S = entities-per-chunk + trash)
stays resident in VMEM across the grid, each grid step computes
``group_tiles`` tile Grams on the MXU and accumulates them into their
owners' rows by dynamic index, and the result is written to HBM exactly
once.  Nothing intermediate ever touches HBM.

Round-2's one-tile-per-grid-step version (measured 2.36 vs 1.97 s/iter at
full Netflix — overhead-bound, parked in VERDICT r2) indexed the *output*
by the scalar-prefetched owner and relied on pallas' revisiting-output
pattern; the multi-tile redesign instead owns the whole output block, which
removes the per-tile grid overhead AND the one-entity-per-step write
pattern.  Requirements: each owner's tiles CONTIGUOUS in the stream (the
layout sorts by owner; a non-contiguous owner's later run would assign over
its earlier one) and the per-chunk segment count S small enough that
S·k·(k+1)·4 B fits VMEM alongside the streamed inputs (the builder's chunk
sizing keeps S ≲ 2.5k, ≤ ~37 MB).

Contract difference vs the XLA segment-sum path: rows of segments owning
no tile are NEVER WRITTEN (garbage — a row's first flush assigns, which is
what makes zero-initializing the 37 MB output block unnecessary).  The
tiled layout guarantees every real entity in a chunk owns ≥ 1 tile; callers
route absent rows to trash (stream mode) or mask them (accum mode), exactly
as they did for the round-2 kernel.

Reference semantics matched: per-entity normal equations of
``processors/MFeatureCalculator.java:85-99``; λ·n regularization and
float32 accumulation identical to ``cfk_tpu.ops.solve`` (asserted by
``tests/test_pallas_solve.py`` / ``tests/test_tiled.py`` parity tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from cfk_tpu.compat import typeof_vma
from cfk_tpu.ops.pallas.interpret import resolve_interpret
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SOLVE_LANES = 128  # lane width of the fused epilogue's solve tiles — the
# same 128-system batching the standalone solve kernels use


def _dense_window_bytes(block_rows: int, group_rows: int, k: int) -> int:
    """VMEM of the dense kernels' double-buffered input windows: the
    [BG, k] stream block, lane-padded to 128 and charged 4 B/element
    whatever its dtype (a ceiling for bf16), plus the [1, m·T] b row.
    A rank-64 float32 stream really occupies the padded 32 MiB at
    BG = 32k — budgeted at k lanes, the v5e compiler refused the fused
    kernel by 13 MiB (on the chip, PR 21)."""
    return 2 * (block_rows * max(k, 128) * 4 + group_rows * 4)


def _walk_stack_bytes(m: int, k: int) -> int:
    """VMEM the unrolled walk keeps on Mosaic's stack: a group's m tile Grams
    are all issued before the walk (``_tile_grams``), each a lane-padded
    [k, k] plus a [1, k] that occupies a whole (8, 128) tile, and the walk's
    running partials double them.  Negligible at the tiled layout's m = 64
    (~5 MB at k = 64); the bucketed port's m = 4096 // width reaches 256,
    where the v5e compiler allocated 12 MiB more than a budget without this
    term."""
    return 2 * m * (k * max(k, 128) + 8 * 128) * 4


def _tile_grams(g_ref, rt_ref, *, m, t, k, precision, row_off=None):
    """The m tile Grams of one grid step's [m·t, k] factor block.

    All m are issued before the accumulation walk (they have no dependence
    on it), so the MXU pipelines them back-to-back.  Tiles are sliced
    statically — a [m·t, k] → [m, t, k] shape cast is not supported by
    Mosaic's layout inference for every (t, k).  ``row_off`` (the
    gather-fused kernels) offsets every tile into the double-buffered
    VMEM gather scratch instead — a 16-aligned dynamic base (the gather
    support gate requires t % 16 == 0, so every tile keeps the
    alignment Mosaic's sublane slicing wants).
    """
    a_all, b_all = [], []
    for i in range(m):  # m is static → unrolled
        if row_off is None:
            g_i = g_ref[i * t:(i + 1) * t, :]  # [t, k]
        else:
            g_i = g_ref[pl.ds(pl.multiple_of(row_off + i * t, 16), t), :]
        r_i = rt_ref[:, i * t:(i + 1) * t]  # [1, t]
        a_all.append(jax.lax.dot_general(
            g_i, g_i, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ))  # [k, k]
        b_all.append(jax.lax.dot_general(
            r_i.astype(g_i.dtype), g_i, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ))  # [1, k]
    return a_all, b_all


def _tile_grams_dense(sc_ref, g_ref, rt_ref, *, m, t, k, base, ng, nt,
                      precision, row_off=None):
    """Dense-stream tile Grams: [t]-row WINDOWS into the gathered stream at
    16-aligned dynamic offsets (``pl.multiple_of`` — Mosaic rejects
    unhinted dynamic sublane slices of bf16 refs, and sub-(16,128)-tile
    offsets straddle two VMEM tiles per vreg load), with rows outside
    [lo, hi) masked out of ONE dot operand (zeroed rows contribute nothing
    to A; the tile-aligned rt carries zeros outside the window, so b needs
    no mask).  ``row_off`` (the gather-fused kernels) rebases the windows
    into the double-buffered VMEM gather scratch — 16-aligned because the
    gather gate requires block_rows % 16 == 0."""
    s_lb, s_lo, s_hi = ng, ng + nt, ng + 2 * nt
    # Row iota hoisted out of the unrolled loop; the window test
    # (rows >= lo) & (rows < hi) is ONE unsigned compare on (rows - lo)
    # — the mask chain is per-tile VPU work on the walk's critical path.
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, k), 0)
    a_all, b_all = [], []
    for i in range(m):
        ti = base + i
        lb_val = sc_ref[s_lb + ti]
        if row_off is not None:
            lb_val = row_off + lb_val
        lb = pl.multiple_of(lb_val, 16)
        lo = sc_ref[s_lo + ti]
        hi = sc_ref[s_hi + ti]
        keep = (rows - lo).astype(jnp.uint32) < (hi - lo).astype(jnp.uint32)
        gt = g_ref[pl.ds(lb, t), :]
        # One masked operand suffices: masked rows contribute zero rank-1
        # terms.
        gm = jnp.where(keep, gt, jnp.zeros_like(gt))
        r_i = rt_ref[:, i * t:(i + 1) * t]  # [1, t]
        a_all.append(jax.lax.dot_general(
            gm, gt, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ))
        b_all.append(jax.lax.dot_general(
            r_i.astype(gt.dtype), gt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ))
    return a_all, b_all


def _walk_tiles(seg_of, a_all, b_all, *, gi, base, m, a_ref, b_ref, carry):
    """The owner-run accumulation walk shared by every grouped-Gram kernel.

    Walks the group's m tiles holding the running owner's partial (A, b) in
    registers; (a_ref, b_ref) rows — output blocks in the split kernels,
    VMEM scratch in the fused ones — are touched only when the owner
    changes: ~one write per entity instead of one read-modify-write per
    tile.  ``began`` = the running owner's first tile is inside this group,
    so its flush ASSIGNS (first visit — which is what makes zero-init
    unnecessary); otherwise the row already holds earlier groups' partials
    and the flush accumulates.  Rows owning no tile are never written
    (garbage); callers route them to trash exactly as before.

    ``carry = (ca_ref, cb_ref, ci_ref)`` folds a previous chunk's partial
    (A, b) into segment 0 at grid step 0 (stream mode's boundary straddle
    — doing it here is ~free, while folding it outside either rewrote the
    whole Gram batch through HBM or cost a separate one-system solve per
    chunk, 97 ms/iter at rank 128).
    """
    def flush(row, began, acc_a, acc_b):
        @pl.when(began)
        def _assign():
            a_ref[pl.ds(row, 1)] = acc_a[None]
            b_ref[pl.ds(row, 1)] = acc_b[None]

        @pl.when(jnp.logical_not(began))
        def _accumulate():
            a_ref[pl.ds(row, 1)] += acc_a[None]
            b_ref[pl.ds(row, 1)] += acc_b[None]

    began = (gi == 0) | (seg_of(base) != seg_of(jnp.maximum(base - 1, 0)))
    acc_a, acc_b = a_all[0], b_all[0]
    if carry is not None:
        # Segment 0 owns the chunk's first tile whenever cin is 1 (the
        # continued entity has entries here by definition), so adding the
        # scaled carry into the running partial at grid step 0 lands it in
        # segment 0's flushed row; cin = 0 multiplies it away.
        ca_ref, cb_ref, ci_ref = carry
        fold = jnp.where(gi == 0, ci_ref[0, 0], 0.0)
        acc_a = acc_a + fold * ca_ref[...]
        acc_b = acc_b + fold * cb_ref[...]
    for i in range(1, m):  # m is static → unrolled
        change = seg_of(base + i) != seg_of(base + i - 1)
        prev_row = seg_of(base + i - 1)

        @pl.when(change)
        def _flush(row=prev_row, began=began, acc_a=acc_a, acc_b=acc_b):
            flush(row, began, acc_a, acc_b)

        # Arithmetic select: acc·keep + a is ONE fused multiply-add per
        # vreg where where(keep, acc+a, a) costs an add AND a select —
        # the accumulation chain is the kernel's VPU hot path (~60 ns/tile
        # over 1.8M tiles/iter at full Netflix).  Failure-mode caveat: a
        # non-finite acc (diverged factors) survives the ×0.0 reset as NaN
        # (inf·0 = NaN), so ONE bad tile Gram poisons every later segment
        # in the group, where a where-select would have discarded it at
        # the boundary.  Acceptable: non-finite factors are already a
        # broken run, and the trainers' outputs go NaN either way — this
        # only widens the blast radius within an already-lost iteration.
        keep_f = 1.0 - change.astype(jnp.float32)
        acc_a = acc_a * keep_f + a_all[i]
        acc_b = acc_b * keep_f + b_all[i]
        began = jnp.logical_or(began, change)
    flush(seg_of(base + m - 1), began, acc_a, acc_b)


def _solve_epilogue(a_scr, b_scr, reg_ref, lseg, x_ref, cao_ref, cbo_ref,
                    lu_scr, *, k, s_pad, reg_mode, lam, algo):
    """The fused Gram+solve epilogue: ridge + eliminate the VMEM-resident
    (A, b) in place, write back only the solved rows.

    Runs once, at the LAST grid step, after the walk's final flush: the
    chunk's whole (A [s_pad, k, k], b [s_pad, 1, k]) batch lives in VMEM
    *scratch* (never HBM — the split path's [Ec, k, k] write + readback is
    the round-trip this removes).  Per 128-lane tile it transposes to the
    solve kernels' batch-last layout, applies the regularizer in-register
    (``apply_reg_lanes`` — ``diag`` λ·max(n,1)·I from the padded count
    row, ``matrix`` one shared [k,k] Y'Y+λI), and runs the same
    lane-vectorized elimination the standalone reg+solve kernels use
    (``lu_solve_lanes``/``gj_solve_lanes``, ``solve_kernel.py``).  The
    chunk-boundary carry row (RAW, pre-ridge — the next chunk folds it
    into its own sums) is extracted at ``lseg`` before the solve.

    Rows of segments owning no tile hold scratch garbage; their "solves"
    produce garbage confined to their own lanes (every lane is an
    independent system) and callers route those rows to trash, exactly as
    they did for the unwritten rows of the split kernels.
    """
    cao_ref[...] = a_scr[pl.ds(lseg, 1)][0]
    cbo_ref[...] = b_scr[pl.ds(lseg, 1)][0]

    def tile_body(i, c):
        ts = pl.multiple_of(i * _SOLVE_LANES, _SOLVE_LANES)
        a_blt = jnp.transpose(
            a_scr[pl.ds(ts, _SOLVE_LANES)], (1, 2, 0)
        )  # [k, k, T] batch-last
        y = b_scr[pl.ds(ts, _SOLVE_LANES)][:, 0, :].T  # [k, T]
        reg = (reg_ref[0, pl.ds(ts, _SOLVE_LANES)] if reg_mode == "diag"
               else reg_ref[...])
        from cfk_tpu.ops.pallas.solve_kernel import (
            apply_reg_lanes,
            gj_solve_lanes,
            lu_solve_lanes,
        )

        tr = apply_reg_lanes(a_blt, reg, k=k, reg_mode=reg_mode, lam=lam)
        if algo == "lu":
            xt = lu_solve_lanes(tr, y, *lu_scr, k=k)
        else:
            xt = gj_solve_lanes(tr, y, k=k)
        x_ref[pl.ds(ts, _SOLVE_LANES)] = xt.T
        return c

    lax.fori_loop(0, s_pad // _SOLVE_LANES, tile_body, 0)


def _gram_groups_kernel(seg_ref, g_ref, *refs, m, t, k, precision,
                        with_carry):
    # refs = (rt_ref, [ca_ref, cb_ref, ci_ref], a_ref, b_ref): the carry
    # triple present iff the caller folds a previous chunk's partial
    # (A, b) into segment 0 (stream mode's boundary straddle — folded in
    # the walk, see ``_walk_tiles``).  Per-entry weights are expressed
    # upstream as the sqrt-reparameterized stream (g = √w·f — see
    # ``ops.tiled.ials_tiled_half_step``), so ONE stream serves both
    # weight modes; round 4's second premultiplied gw stream is gone.
    refs = list(refs)
    a_ref, b_ref = refs[-2:]
    del refs[-2:]
    carry = None
    if with_carry:
        carry = tuple(refs[-3:])
        del refs[-3:]
    rt_ref = refs[0]
    gi = pl.program_id(0)
    base = gi * m
    a_all, b_all = _tile_grams(g_ref, rt_ref, m=m, t=t, k=k,
                               precision=precision)
    _walk_tiles(lambda i: seg_ref[i], a_all, b_all, gi=gi, base=base, m=m,
                a_ref=a_ref, b_ref=b_ref, carry=carry)


def _gram_dense_kernel(sc_ref, g_ref, *refs, m, t, k, ng, nt,
                       precision, with_carry):
    # Dense-stream variant (see ``_tile_grams_dense`` for the windowing).
    # Walk/flush semantics are identical to ``_gram_groups_kernel``:
    # owners' tiles are contiguous (trash slots inherit the previous
    # owner's seg with an empty window), rows of absent segments are never
    # written.  Weighted (iALS) runs stream gs = √aw·f through this same
    # unit-weight form (sqrt reparameterization,
    # ``ops.tiled.ials_tiled_half_step``).
    refs = list(refs)
    a_ref, b_ref = refs[-2:]
    del refs[-2:]
    carry = None
    if with_carry:
        carry = tuple(refs[-3:])
        del refs[-3:]
    rt_ref = refs[0]
    gi = pl.program_id(0)
    base = gi * m
    s_seg = ng + 3 * nt
    a_all, b_all = _tile_grams_dense(
        sc_ref, g_ref, rt_ref, m=m, t=t, k=k, base=base, ng=ng, nt=nt,
        precision=precision,
    )
    _walk_tiles(lambda i: sc_ref[s_seg + i], a_all, b_all, gi=gi, base=base,
                m=m, a_ref=a_ref, b_ref=b_ref, carry=carry)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_segments", "tile_rows", "num_tiles", "num_groups",
        "block_rows", "interpret",
    ),
)
def gram_tiles_dense_pallas(
    g: jax.Array,  # [C, k] densely packed gathered factors (bf16/f32)
    rt: jax.Array,  # [NT·T] f32 TILE-ALIGNED b coefficients (0 off-window)
    meta: jax.Array,  # [NG + 4·NT] int32: g_blk ‖ lb ‖ lo ‖ hi ‖ seg
    *,
    num_segments: int,
    tile_rows: int,
    num_tiles: int,  # NT (tile slots)
    num_groups: int,  # NG (grid steps; group size m = NT // NG)
    block_rows: int,  # BG (stream rows per pipelined block)
    interpret: bool | None = None,
    carry: tuple[jax.Array, jax.Array, jax.Array] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Dense-stream grouped Gram: the unpadded-gather variant of
    ``gram_tiles_pallas``.

    The stream ``g`` carries only real entries (16-row run alignment,
    ~3.4% pad at Netflix shape vs 26% tile padding) — the win is on XLA's
    row-slot-bound gather engine, which produces ``g`` upstream.  The
    kernel pipelines ``g`` in [BG, k] blocks chosen by the per-group
    prefetched block index ``meta[:NG]`` (the builder keeps every group's
    tile windows inside one block), loads each tile as a [T]-row window
    at a dynamic 16-aligned offset, and masks rows outside [lo, hi).
    Same unwritten-absent-rows contract and chunk-boundary ``carry`` as
    ``gram_tiles_pallas``.  Weighted (iALS) callers pass the
    sqrt-reparameterized stream g = √aw·f with rescaled ``rt`` — one
    stream serves both weight modes (round 5; the former second ``gw``
    stream doubled pipelined traffic and squeezed VMEM at k = 128).
    See ``data.blocks._build_dense_stream`` for the metadata layout and
    contiguity guarantees.
    """
    c, k = g.shape
    t = tile_rows
    nt, ng, bg = num_tiles, num_groups, block_rows
    if nt % ng != 0:
        raise ValueError(f"num_tiles {nt} not divisible by num_groups {ng}")
    m = nt // ng
    if rt.shape != (nt * t,):
        raise ValueError(f"rt shape {rt.shape} != ({nt * t},)")
    if meta.shape != (ng + 4 * nt,):
        raise ValueError(f"meta shape {meta.shape} != ({ng + 4 * nt},)")
    if c % bg != 0 or bg < t:
        raise ValueError(f"stream length {c} not a multiple of block_rows "
                         f"{bg} >= tile_rows {t}")
    interpret = resolve_interpret(interpret)
    if interpret is True:
        # Vectorized emulation (CPU tests, shard_map interpret — same vma
        # rationale as gram_tiles_pallas): zeros for absent rows.
        return _emulate_gram_dense(
            g, rt, meta, num_segments=num_segments, tile_rows=t,
            num_tiles=nt, num_groups=ng, block_rows=bg, carry=carry,
        )

    vma = typeof_vma(g)
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d, vma=vma)) if vma else (
        lambda s, d: jax.ShapeDtypeStruct(s, d)
    )
    out_shape = (
        mk((num_segments, k, k), jnp.float32),
        mk((num_segments, 1, k), jnp.float32),
    )
    carry_specs = [] if carry is None else [
        pl.BlockSpec((k, k), lambda i, sc: (0, 0)),
        pl.BlockSpec((1, k), lambda i, sc: (0, 0)),
        pl.BlockSpec((1, 1), lambda i, sc: (0, 0)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ng,),
        in_specs=[
            pl.BlockSpec((bg, k), lambda i, sc: (sc[i], 0)),
            pl.BlockSpec((1, m * t), lambda i, sc: (0, i)),
        ] + carry_specs,
        out_specs=[
            pl.BlockSpec((num_segments, k, k), lambda i, sc: (0, 0, 0)),
            pl.BlockSpec((num_segments, 1, k), lambda i, sc: (0, 0, 0)),
        ],
    )
    precision = (
        jax.lax.Precision.HIGHEST if g.dtype == jnp.float32 else None
    )
    out_bytes = num_segments * k * (k + 1) * 4
    # The resident output is budgeted at 2× its bytes; the windows as
    # _dense_window_bytes counts them.
    in_bytes = _dense_window_bytes(bg, m * t, k)
    kwargs = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(2 * out_bytes + in_bytes + (10 << 20),
                             124 << 20)
    )}
    carry_ops = [] if carry is None else [
        carry[0].astype(jnp.float32),
        carry[1].reshape(1, k).astype(jnp.float32),
        carry[2].reshape(1, 1).astype(jnp.float32),
    ]
    a, b = pl.pallas_call(
        functools.partial(
            _gram_dense_kernel, m=m, t=t, k=k, ng=ng, nt=nt,
            precision=precision, with_carry=carry is not None,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=bool(interpret),
        **kwargs,
    )(meta, g, rt.reshape(1, nt * t), *carry_ops)
    return a, b[:, 0, :]


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "tile_rows", "group_tiles", "interpret"),
)
def gram_tiles_pallas(
    g: jax.Array,  # [C, k] gathered neighbor factors (bf16 or f32)
    rt: jax.Array,  # [C] f32 b-side coefficients (0 at padding)
    seg: jax.Array,  # [NT] int32 owner of each tile (sorted by the layout)
    *,
    num_segments: int,  # output rows (Ec + 1, trash last)
    tile_rows: int,
    group_tiles: int = 64,  # swept on-chip: 16→0.849, 32→0.830, 64→0.824,
    # 128→0.823 s/iter at full Netflix — 64 is the knee (128 only bloats
    # the unrolled walk and compile time)
    interpret: bool | None = None,
    carry: tuple[jax.Array, jax.Array, jax.Array] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(A [num_segments, k, k] f32, b [num_segments, k] f32).

    ONE stream serves both weight modes: weighted (iALS) callers pass the
    sqrt-reparameterized copy g = √w·f (which fuses into the producing
    gather for free and streams in the factors' natural layout) with
    b-coefficients rescaled by 1/√w — so A = gᵀg = Σ w·f fᵀ and
    b = Σ c·f exactly (``ops.tiled.ials_tiled_half_step``).  A raw
    [C, 1] weight column would relayout into one element per (8, 128)
    tile (measured 0.4 ms/chunk of pure copy), and round 4's second
    premultiplied gw stream doubled the pipelined input traffic — both
    are avoided by construction.  Padding entries gather the appended
    zero row, so they vanish from both sums.

    ``carry = (a0 [k,k] f32, b0 [k] f32, cin scalar f32)`` adds
    ``cin·(a0, b0)`` into segment 0's sums — the stream scan's
    chunk-boundary straddle, folded here where it costs one fma pass per
    group instead of an [Ec,k,k] HBM rewrite or a separate one-system
    solve outside.

    Rows of segments owning no tile are UNSPECIFIED (never written) —
    callers must route them to trash (stream mode) or mask them (accum
    mode).
    """
    c, k = g.shape
    t = tile_rows
    if c % t != 0:
        raise ValueError(f"entry count {c} not divisible by tile_rows {t}")
    nt = c // t
    if seg.shape != (nt,):
        raise ValueError(f"seg shape {seg.shape} != ({nt},)")
    interpret = resolve_interpret(interpret)
    if interpret is True and typeof_vma(g):
        # Under shard_map with vma checking, the pallas HLO interpreter's
        # grid loop slices varying operands with unvarying grid counters
        # and fails the vma match.  Mosaic compilation is unaffected (the
        # indexing lives inside the kernel binary), so only CPU-interpret
        # sharded runs (tests, dryrun_multichip) take this branch: the
        # same math via segment-sum, zeros for absent rows (a superset of
        # the kernel's unspecified-rows contract).  Outside shard_map this
        # kernel's interpret route is its own body.
        return _emulate_gram_tiles(
            g, rt, seg, num_segments=num_segments, tile_rows=tile_rows,
            carry=carry,
        )
    m = group_tiles
    while nt % m != 0:  # grid must tile exactly; m=1 always divides
        m //= 2

    vma = typeof_vma(g)
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d, vma=vma)) if vma else (
        lambda s, d: jax.ShapeDtypeStruct(s, d)
    )
    out_shape = (
        mk((num_segments, k, k), jnp.float32),
        mk((num_segments, 1, k), jnp.float32),
    )
    fac_spec = pl.BlockSpec((m * t, k), lambda i, seg: (i, 0))
    carry_specs = [] if carry is None else [
        pl.BlockSpec((k, k), lambda i, seg: (0, 0)),
        pl.BlockSpec((1, k), lambda i, seg: (0, 0)),
        pl.BlockSpec((1, 1), lambda i, seg: (0, 0)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt // m,),
        in_specs=[fac_spec,
                  pl.BlockSpec((1, m * t), lambda i, seg: (0, i))]
        + carry_specs,
        out_specs=[
            pl.BlockSpec((num_segments, k, k), lambda i, seg: (0, 0, 0)),
            pl.BlockSpec((num_segments, 1, k), lambda i, seg: (0, 0, 0)),
        ],
    )
    # f32 factors keep the solve path's full-precision convention (default
    # TPU matmul is bf16 — it would break reference parity ~1e-2 relative).
    precision = (
        jax.lax.Precision.HIGHEST if g.dtype == jnp.float32 else None
    )
    kwargs = {}
    if not interpret:
        # The resident output block dominates VMEM — and Mosaic double-
        # buffers output blocks even at a constant output index, so budget
        # 2× it plus the streamed input blocks with headroom (the default
        # 16 MB scoped allowance is far too small for S ≈ 2.5k segments).
        out_bytes = num_segments * k * (k + 1) * 4
        in_bytes = 2 * (m * t * (k + 1) * 4)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=min(2 * out_bytes + 4 * in_bytes
                                 + _walk_stack_bytes(m, k) + (12 << 20),
                                 110 << 20)
        )
    carry_ops = [] if carry is None else [
        carry[0].astype(jnp.float32),
        carry[1].reshape(1, k).astype(jnp.float32),
        carry[2].reshape(1, 1).astype(jnp.float32),
    ]
    a, b = pl.pallas_call(
        functools.partial(
            _gram_groups_kernel, m=m, t=t, k=k, precision=precision,
            with_carry=carry is not None,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=bool(interpret),
        **kwargs,
    )(seg, g, rt.reshape(1, c), *carry_ops)
    return a, b[:, 0, :]


def _emulate_gram_tiles(g, rt, seg, *, num_segments, tile_rows, carry):
    """XLA segment-sum emulation of the grouped-Gram kernel (interpret /
    shard_map-vma routes): zeros for absent rows — a superset of
    the kernel's unspecified-rows contract."""
    k = g.shape[-1]
    prec = (jax.lax.Precision.HIGHEST if g.dtype == jnp.float32 else None)
    gt = g.reshape(-1, tile_rows, k)
    a_t = jnp.einsum("ntk,ntl->nkl", gt, gt,
                     preferred_element_type=jnp.float32, precision=prec)
    # rt stays float32 (ADVICE r5): the iALS ε-clamped b-coefficient
    # loses ~0.5–1% relative accuracy under a bf16 cast, and the real
    # kernel consumes the f32 stream directly.
    b_t = jnp.einsum("ntk,nt->nk", gt,
                     rt.reshape(-1, tile_rows).astype(jnp.float32),
                     preferred_element_type=jnp.float32, precision=prec)
    a = jax.ops.segment_sum(a_t, seg, num_segments=num_segments,
                            indices_are_sorted=True)
    b = jax.ops.segment_sum(b_t, seg, num_segments=num_segments,
                            indices_are_sorted=True)
    if carry is not None:
        ca, cb, ci = carry
        a = a.at[0].add(ci * ca)
        b = b.at[0].add(ci * cb)
    return a, b


def _emulate_gram_dense(g, rt, meta, *, num_segments, tile_rows, num_tiles,
                        num_groups, block_rows, carry):
    """XLA emulation of the dense-stream grouped-Gram kernel: windowed
    gathers + masked einsums + segment-sum, zeros for absent rows."""
    k = g.shape[-1]
    t, nt, ng, bg = tile_rows, num_tiles, num_groups, block_rows
    m = nt // ng
    prec = (jax.lax.Precision.HIGHEST if g.dtype == jnp.float32 else None)
    gblk = meta[:ng]
    lb = meta[ng:ng + nt]
    lo = meta[ng + nt:ng + 2 * nt]
    hi = meta[ng + 2 * nt:ng + 3 * nt]
    seg = meta[ng + 3 * nt:ng + 4 * nt]
    absrow = jnp.repeat(gblk, m) * bg + lb  # [NT]
    win = absrow[:, None] + jnp.arange(t)[None, :]  # [NT, T]
    gt = g[win]  # [NT, T, k]
    rows = jnp.arange(t)[None, :]
    keep = (rows >= lo[:, None]) & (rows < hi[:, None])
    gm = jnp.where(keep[..., None], gt, jnp.zeros_like(gt))
    a_t = jnp.einsum("ntk,ntl->nkl", gm, gt,
                     preferred_element_type=jnp.float32, precision=prec)
    # rt stays float32 (ADVICE r5) — see _emulate_gram_tiles.
    b_t = jnp.einsum("ntk,nt->nk", gt,
                     rt.reshape(nt, t).astype(jnp.float32),
                     precision=prec, preferred_element_type=jnp.float32)
    a = jax.ops.segment_sum(a_t, seg, num_segments=num_segments,
                            indices_are_sorted=True)
    b = jax.ops.segment_sum(b_t, seg, num_segments=num_segments,
                            indices_are_sorted=True)
    if carry is not None:
        ca, cb, ci = carry
        a = a.at[0].add(ci * ca)
        b = b.at[0].add(ci * cb)
    return a, b


def _fused_scratch_bytes(s_pad: int, k: int) -> int:
    """VMEM bytes of the fused epilogue's resident state, as Mosaic lays it
    out: the A scratch; the b scratch, whose [1, k] rows each occupy a whole
    (8, 128) tile; the solved-rows output block, double-buffered and
    lane-padded; and EIGHT [k, k, 128] float32 temporaries for the
    elimination — LU's scratch buffers (one such block) and the ~seven the
    unrolled elimination keeps on Mosaic's stack.  Fitted to the v5e
    compiler's own accounting (its out-of-VMEM reports) at three shapes with
    32k-row dense blocks: k = 128, S = 384 allocated 104.19 MiB with bf16
    input windows and 120.59 MiB with f32 ones (this formula + windows:
    105.9 / 121.9); k = 64, S = 2688 allocated 87.41 MiB (87.1).  ONE
    formula, shared by the support gate below and the pallas_call budget
    (``_fused_call_pieces``), so the two can never drift into a gate that
    admits a shape the compiler then rejects."""
    tile = 8 * 128
    return 4 * (s_pad * k * k + s_pad * tile + 2 * s_pad * max(k, 128)
                + 8 * k * k * _SOLVE_LANES)


def fused_gram_solve_supported(num_segments: int, k: int,
                               algo: str | None = None) -> bool:
    """Can the fused Gram+solve epilogue handle this chunk shape?

    Two gates: the rank must fit the fused reg+solve elimination's cap
    (LU 128 / GJ 64 — past it the dispatcher's cholesky/Schur backends are
    needed, which only exist as separate passes; ``algo`` threads the
    caller's elimination choice, None/'auto' = the process default), and
    the lane-padded (A, b) scratch plus the elimination's temporaries
    (``_fused_scratch_bytes`` — same formula the compile budget uses) must
    leave VMEM headroom for the double-buffered input blocks under the
    ~124 MB scoped ceiling.  The 72 MB gate reserves ≥ 50 MB for inputs
    (the gate cannot see the chunk's block size, so it is conservative: a
    refused shape takes the split path — same math, one extra round-trip —
    never a Mosaic compile failure).  At rank 128 the temporaries alone
    are 64 MiB, so the gate refuses every rank-128 chunk: with 32k-row
    dense blocks the v5e compiler ran out of VMEM, and rank 128 takes the
    split Gram kernel + standalone LU-128 solve — the route the chip
    record was taken on.  At rank 64 it refuses chunks of more than ~2.4k
    segments (short rows: many entities per chunk).
    """
    from cfk_tpu.ops.pallas.solve_kernel import _fused_reg_rank_cap

    if k > _fused_reg_rank_cap(algo):
        return False
    s_pad = -(-num_segments // _SOLVE_LANES) * _SOLVE_LANES
    return _fused_scratch_bytes(s_pad, k) <= (72 << 20)


def _gram_solve_groups_kernel(seg_ref, g_ref, *refs, m, t, k, s_pad,
                              nt_total, precision, with_carry, reg_mode,
                              lam, algo):
    """Fused variant of ``_gram_groups_kernel``: the walk accumulates into
    VMEM *scratch* instead of output blocks, and the last grid step runs
    the ridge+solve epilogue in place (``_solve_epilogue``), writing back
    only the solved [s_pad, k] rows and the chunk-boundary carry row.
    ``seg_ref`` carries the chunk's lseg appended at index ``nt_total``.
    """
    refs = list(refs)
    if algo == "lu":
        lu_scr = tuple(refs[-3:])
        del refs[-3:]
    else:
        lu_scr = None
    a_scr, b_scr = refs[-2:]
    del refs[-2:]
    x_ref, cao_ref, cbo_ref = refs[-3:]
    del refs[-3:]
    carry = None
    if with_carry:
        carry = tuple(refs[-3:])
        del refs[-3:]
    rt_ref, reg_ref = refs[0], refs[1]
    gi = pl.program_id(0)
    base = gi * m
    a_all, b_all = _tile_grams(g_ref, rt_ref, m=m, t=t, k=k,
                               precision=precision)
    _walk_tiles(lambda i: seg_ref[i], a_all, b_all, gi=gi, base=base, m=m,
                a_ref=a_scr, b_ref=b_scr, carry=carry)

    @pl.when(gi == pl.num_programs(0) - 1)
    def _epilogue():
        _solve_epilogue(
            a_scr, b_scr, reg_ref, seg_ref[nt_total], x_ref, cao_ref,
            cbo_ref, lu_scr, k=k, s_pad=s_pad, reg_mode=reg_mode, lam=lam,
            algo=algo,
        )


def _gram_solve_dense_kernel(sc_ref, g_ref, *refs, m, t, k, ng, nt, s_pad,
                             precision, with_carry, reg_mode, lam, algo):
    """Fused variant of ``_gram_dense_kernel`` — same scratch-resident walk
    + last-step ridge+solve epilogue as ``_gram_solve_groups_kernel``.
    ``sc_ref`` carries the chunk's lseg appended at index ``ng + 4·nt``."""
    refs = list(refs)
    if algo == "lu":
        lu_scr = tuple(refs[-3:])
        del refs[-3:]
    else:
        lu_scr = None
    a_scr, b_scr = refs[-2:]
    del refs[-2:]
    x_ref, cao_ref, cbo_ref = refs[-3:]
    del refs[-3:]
    carry = None
    if with_carry:
        carry = tuple(refs[-3:])
        del refs[-3:]
    rt_ref, reg_ref = refs[0], refs[1]
    gi = pl.program_id(0)
    base = gi * m
    s_seg = ng + 3 * nt
    a_all, b_all = _tile_grams_dense(
        sc_ref, g_ref, rt_ref, m=m, t=t, k=k, base=base, ng=ng, nt=nt,
        precision=precision,
    )
    _walk_tiles(lambda i: sc_ref[s_seg + i], a_all, b_all, gi=gi, base=base,
                m=m, a_ref=a_scr, b_ref=b_scr, carry=carry)

    @pl.when(gi == pl.num_programs(0) - 1)
    def _epilogue():
        _solve_epilogue(
            a_scr, b_scr, reg_ref, sc_ref[ng + 4 * nt], x_ref, cao_ref,
            cbo_ref, lu_scr, k=k, s_pad=s_pad, reg_mode=reg_mode, lam=lam,
            algo=algo,
        )


def _fused_call_pieces(k, s_pad, num_segments, reg, reg_mode, carry, vma,
                       algo):
    """The plumbing every fused wrapper shares: reg/carry operands and
    specs, lane-padded output shapes, scratch shapes, and the VMEM budget.
    Returns (reg_op, reg_spec, carry_ops, carry_specs, out_shape,
    scratch_shapes, extra_vmem_bytes)."""
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d, vma=vma)) if vma else (
        lambda s, d: jax.ShapeDtypeStruct(s, d)
    )
    if reg_mode == "diag":
        reg_op = jnp.pad(
            reg.astype(jnp.float32), (0, s_pad - num_segments)
        ).reshape(1, s_pad)
        reg_spec = pl.BlockSpec((1, s_pad), lambda i, sc: (0, 0))
    else:
        reg_op = reg.astype(jnp.float32)
        reg_spec = pl.BlockSpec((k, k), lambda i, sc: (0, 0))
    carry_specs = [] if carry is None else [
        pl.BlockSpec((k, k), lambda i, sc: (0, 0)),
        pl.BlockSpec((1, k), lambda i, sc: (0, 0)),
        pl.BlockSpec((1, 1), lambda i, sc: (0, 0)),
    ]
    carry_ops = [] if carry is None else [
        carry[0].astype(jnp.float32),
        carry[1].reshape(1, k).astype(jnp.float32),
        carry[2].reshape(1, 1).astype(jnp.float32),
    ]
    out_shape = (
        mk((s_pad, k), jnp.float32),      # x
        mk((k, k), jnp.float32),          # carry A row (raw, pre-ridge)
        mk((1, k), jnp.float32),          # carry b row
    )
    out_specs = [
        pl.BlockSpec((s_pad, k), lambda i, sc: (0, 0)),
        pl.BlockSpec((k, k), lambda i, sc: (0, 0)),
        pl.BlockSpec((1, k), lambda i, sc: (0, 0)),
    ]
    scratch = [
        pltpu.VMEM((s_pad, k, k), jnp.float32),
        pltpu.VMEM((s_pad, 1, k), jnp.float32),
    ]
    if algo == "lu":
        scratch += [
            pltpu.VMEM((k, k, _SOLVE_LANES), jnp.float32),
            pltpu.VMEM((k, _SOLVE_LANES), jnp.float32),
            pltpu.VMEM((k, _SOLVE_LANES), jnp.float32),
        ]
    # Scratch is single-buffered (unlike the split kernels' resident
    # output, which Mosaic double-buffers even at a constant index) — the
    # fused path actually NEEDS LESS VMEM than split despite solving in
    # place.  Budget: scratch + elimination temporaries + headroom
    # (same formula the support gate applies — see _fused_scratch_bytes).
    scratch_bytes = _fused_scratch_bytes(s_pad, k)
    return (reg_op, reg_spec, carry_ops, carry_specs, out_shape, out_specs,
            scratch, scratch_bytes)


def gram_solve_tiles_pallas(
    g: jax.Array,  # [C, k] gathered neighbor factors (bf16 or f32)
    rt: jax.Array,  # [C] f32 b-side coefficients (0 at padding)
    seg: jax.Array,  # [NT] int32 owner of each tile (sorted by the layout)
    reg: jax.Array,  # diag: [num_segments] counts; matrix: [k, k] YᵀY+λI
    lseg: jax.Array,  # int32 scalar: the carry row to extract
    *,
    num_segments: int,
    tile_rows: int,
    group_tiles: int = 64,
    reg_mode: str = "diag",
    lam: float = 0.0,
    interpret: bool | None = None,
    carry: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    algo: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused Gram + ridge + solve over entity tiles: the chunk's normal
    equations never leave the kernel's VMEM residency.

    Same contract as ``gram_tiles_pallas`` for the Gram accumulation
    (sorted contiguous owners, unwritten absent rows, the chunk-boundary
    ``carry`` fold), but instead of writing (A [S, k, k], b [S, k]) to HBM
    for a separate batched solve, the last grid step applies the
    regularizer and runs the lane-vectorized elimination on the
    VMEM-resident batch (``_solve_epilogue``), returning

        (x [num_segments, k], carry_a [k, k], carry_b [k])

    where (carry_a, carry_b) is the RAW (pre-ridge) row at ``lseg`` — the
    partial sums of the entity straddling the next chunk boundary.  This
    removes the split path's per-chunk [Ec, k, k] A-batch write + readback
    (~2·Ec·k² f32 of pure HBM traffic per chunk) that PR 1's prefetch
    pipelines left as the exposed hot path.

    Off-TPU (interpret) this routes to the
    XLA-emulation twin (``cfk_tpu.compat.emulate_fused_gram_solve``): the
    same segment-sum Gram the split path emulates plus the interpret-mode
    fused reg+solve kernel — bit-identical to running split with
    ``gram_backend="xla"`` + the pallas solver, which is what the fused/
    split regression tests pin.  Rank cap and VMEM sizing are gated by
    ``fused_gram_solve_supported``; callers fall back to split past it.
    """
    from cfk_tpu.ops.pallas.solve_kernel import resolve_reg_solve_algo

    algo = resolve_reg_solve_algo(algo)
    return _gram_solve_tiles_pallas(
        g, rt, seg, reg, lseg, num_segments=num_segments,
        tile_rows=tile_rows, group_tiles=group_tiles, reg_mode=reg_mode,
        lam=lam, interpret=interpret, carry=carry, algo=algo,
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "tile_rows", "group_tiles", "reg_mode",
                     "lam", "interpret", "algo"),
)
def _gram_solve_tiles_pallas(
    g, rt, seg, reg, lseg, *, num_segments, tile_rows, group_tiles,
    reg_mode, lam, interpret, carry, algo,
):
    c, k = g.shape
    t = tile_rows
    if c % t != 0:
        raise ValueError(f"entry count {c} not divisible by tile_rows {t}")
    nt = c // t
    if seg.shape != (nt,):
        raise ValueError(f"seg shape {seg.shape} != ({nt},)")
    _check_reg_shape(reg, reg_mode, num_segments, k)
    interpret = resolve_interpret(interpret)
    if interpret is True:
        # The XLA-emulation twin (compat.py): CPU CI exercises the same
        # fused code shape without Mosaic.
        from cfk_tpu.compat import emulate_fused_gram_solve

        a, b = _emulate_gram_tiles(
            g, rt, seg, num_segments=num_segments, tile_rows=t, carry=carry,
        )
        return emulate_fused_gram_solve(
            a, b, reg, reg_mode=reg_mode, lam=lam, lseg=lseg,
        )
    m = group_tiles
    while nt % m != 0:  # grid must tile exactly; m=1 always divides
        m //= 2
    s_pad = -(-num_segments // _SOLVE_LANES) * _SOLVE_LANES
    vma = typeof_vma(g)
    (reg_op, reg_spec, carry_ops, carry_specs, out_shape, out_specs,
     scratch, scratch_bytes) = _fused_call_pieces(
        k, s_pad, num_segments, reg, reg_mode, carry, vma, algo)
    fac_spec = pl.BlockSpec((m * t, k), lambda i, sc: (i, 0))
    seg_plus = jnp.concatenate(
        [seg.astype(jnp.int32), jnp.asarray(lseg, jnp.int32).reshape(1)]
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt // m,),
        in_specs=[fac_spec,
                  pl.BlockSpec((1, m * t), lambda i, sc: (0, i)),
                  reg_spec] + carry_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    precision = (
        jax.lax.Precision.HIGHEST if g.dtype == jnp.float32 else None
    )
    in_bytes = 2 * (m * t * (k + 1) * 4)
    kwargs = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(scratch_bytes + 4 * in_bytes
                             + _walk_stack_bytes(m, k) + (12 << 20),
                             124 << 20)
    )}
    x, cao, cbo = pl.pallas_call(
        functools.partial(
            _gram_solve_groups_kernel, m=m, t=t, k=k, s_pad=s_pad,
            nt_total=nt, precision=precision,
            with_carry=carry is not None, reg_mode=reg_mode, lam=lam,
            algo=algo,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=bool(interpret),
        **kwargs,
    )(seg_plus, g, rt.reshape(1, c), reg_op, *carry_ops)
    return x[:num_segments], cao, cbo[0]


def gram_solve_tiles_dense_pallas(
    g: jax.Array,  # [C, k] densely packed gathered factors (bf16/f32)
    rt: jax.Array,  # [NT·T] f32 TILE-ALIGNED b coefficients (0 off-window)
    meta: jax.Array,  # [NG + 4·NT] int32: g_blk ‖ lb ‖ lo ‖ hi ‖ seg
    reg: jax.Array,  # diag: [num_segments] counts; matrix: [k, k]
    lseg: jax.Array,  # int32 scalar: the carry row to extract
    *,
    num_segments: int,
    tile_rows: int,
    num_tiles: int,
    num_groups: int,
    block_rows: int,
    reg_mode: str = "diag",
    lam: float = 0.0,
    interpret: bool | None = None,
    carry: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    algo: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused Gram+solve for the dense-stream layout — the unpadded-gather
    variant of ``gram_solve_tiles_pallas`` (same epilogue, dense windowed
    walk; see ``gram_tiles_dense_pallas`` for the stream/metadata
    contract)."""
    from cfk_tpu.ops.pallas.solve_kernel import resolve_reg_solve_algo

    algo = resolve_reg_solve_algo(algo)
    return _gram_solve_tiles_dense_pallas(
        g, rt, meta, reg, lseg, num_segments=num_segments,
        tile_rows=tile_rows, num_tiles=num_tiles, num_groups=num_groups,
        block_rows=block_rows, reg_mode=reg_mode, lam=lam,
        interpret=interpret, carry=carry, algo=algo,
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "tile_rows", "num_tiles", "num_groups",
                     "block_rows", "reg_mode", "lam", "interpret", "algo"),
)
def _gram_solve_tiles_dense_pallas(
    g, rt, meta, reg, lseg, *, num_segments, tile_rows, num_tiles,
    num_groups, block_rows, reg_mode, lam, interpret, carry, algo,
):
    c, k = g.shape
    t = tile_rows
    nt, ng, bg = num_tiles, num_groups, block_rows
    if nt % ng != 0:
        raise ValueError(f"num_tiles {nt} not divisible by num_groups {ng}")
    m = nt // ng
    if rt.shape != (nt * t,):
        raise ValueError(f"rt shape {rt.shape} != ({nt * t},)")
    if meta.shape != (ng + 4 * nt,):
        raise ValueError(f"meta shape {meta.shape} != ({ng + 4 * nt},)")
    if c % bg != 0 or bg < t:
        raise ValueError(f"stream length {c} not a multiple of block_rows "
                         f"{bg} >= tile_rows {t}")
    _check_reg_shape(reg, reg_mode, num_segments, k)
    interpret = resolve_interpret(interpret)
    if interpret is True:
        from cfk_tpu.compat import emulate_fused_gram_solve

        a, b = _emulate_gram_dense(
            g, rt, meta, num_segments=num_segments, tile_rows=t,
            num_tiles=nt, num_groups=ng, block_rows=bg, carry=carry,
        )
        return emulate_fused_gram_solve(
            a, b, reg, reg_mode=reg_mode, lam=lam, lseg=lseg,
        )
    s_pad = -(-num_segments // _SOLVE_LANES) * _SOLVE_LANES
    vma = typeof_vma(g)
    (reg_op, reg_spec, carry_ops, carry_specs, out_shape, out_specs,
     scratch, scratch_bytes) = _fused_call_pieces(
        k, s_pad, num_segments, reg, reg_mode, carry, vma, algo)
    meta_plus = jnp.concatenate(
        [meta.astype(jnp.int32), jnp.asarray(lseg, jnp.int32).reshape(1)]
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ng,),
        in_specs=[
            pl.BlockSpec((bg, k), lambda i, sc: (sc[i], 0)),
            pl.BlockSpec((1, m * t), lambda i, sc: (0, i)),
            reg_spec,
        ] + carry_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    precision = (
        jax.lax.Precision.HIGHEST if g.dtype == jnp.float32 else None
    )
    in_bytes = _dense_window_bytes(bg, m * t, k)
    kwargs = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(scratch_bytes + in_bytes + (10 << 20),
                             124 << 20)
    )}
    x, cao, cbo = pl.pallas_call(
        functools.partial(
            _gram_solve_dense_kernel, m=m, t=t, k=k, ng=ng, nt=nt,
            s_pad=s_pad, precision=precision, with_carry=carry is not None,
            reg_mode=reg_mode, lam=lam, algo=algo,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=bool(interpret),
        **kwargs,
    )(meta_plus, g, rt.reshape(1, nt * t), reg_op, *carry_ops)
    return x[:num_segments], cao, cbo[0]


# --------------------------------------------------------------------------
# In-kernel neighbor gather (gather-fused kernel variants)
#
# Every half-iteration above consumes a PRE-GATHERED [C, k] stream: XLA
# materializes fz[nb] in HBM and the kernel reads it straight back — the
# same write+readback shape the fused epilogue removed for the [Ec, k, k]
# A-batches, and the largest roofline gap of the pre-ledger record
# (PERF.md §8).  The ``*_gather_pallas`` variants retire
# that stream: the RAW fixed factor table stays in HBM/ANY memory, each
# tile's neighbor indices ride the scalar prefetch, and the kernel DMAs
# the indexed rows straight into a double-buffered VMEM block (group g+1's
# row DMAs are in flight while group g's Gram walk runs).  The zero-
# appended padding row is realized IN-REGISTER: indices are clamped to the
# last real row for the DMA and the per-entry premultiply ``wt`` (the 0/1
# validity mask for unit weights, √aw·mask for iALS) zeroes padding rows —
# the [F+1, k] zero-row copy of the table is never built.  Dense-stream
# padding needs the mask too: a run's 16-row alignment pads sit INSIDE its
# [lo, hi) window, so the dense wrappers always pass a weight stream — the
# caller's, times ``nb < F`` (``_dense_gather_weight``).
# Failure-mode caveat (same class the walk's arithmetic select accepts —
# see _walk_tiles): clamped-row × 0.0 is exactly 0 only for FINITE table
# rows; a diverged table (Inf/NaN rows) turns padding slots into NaN via
# 0·inf on the Mosaic route, where the XLA zero-row gather stayed 0.
# Acceptable: non-finite factors are already a broken run — the health
# sentinel (cfk_tpu.resilience) trips on the half-step's OUTPUT either
# way — this only widens the blast radius within an already-lost
# iteration, and only on real TPU (the emulation twin gathers true
# zeros).
#
# Index convention (all gather variants): ``nb == table.shape[0]`` is the
# virtual zero row; the clamp + wt/window masking makes its contribution
# exactly 0.  Off-TPU the wrappers route to
# ``compat.emulate_in_kernel_gather`` + the existing emulation twins,
# which run the numerically identical append-zero-row + gather + multiply
# the XLA-gather path runs — fused-gather vs XLA-gather factors are
# BIT-IDENTICAL on that route (tests/test_in_kernel_gather.py).  The
# Mosaic row-DMA path itself needs on-TPU validation (ROADMAP).
# --------------------------------------------------------------------------

# Scalar-prefetch budget for the gather variants: the whole index chunk
# (plus seg/meta words) lives in SMEM.  512 KiB admits the production 64k-
# entry chunks (64k indices + ~3k meta words ≈ 268 KiB, which the chip's
# compiler accepts — tests/test_chip_compile.py); past it the resolver keeps
# the XLA-gather path.
_GATHER_SMEM_BYTES_CAP = 512 << 10


def gather_prefetch_fits(entries: int, meta_words: int) -> bool:
    """Does the scalar prefetch (indices + seg/meta + lseg) fit SMEM?"""
    return (entries + meta_words + 1) * 4 <= _GATHER_SMEM_BYTES_CAP


def in_kernel_gather_supported(entries: int, meta_words: int, tile_rows: int,
                               block_rows: int | None = None, *,
                               k: int, table_dtype,
                               lowered: bool = True) -> bool:
    """Can the gather-fused kernels handle this chunk shape?

    Gates, each a refusal of the chip's compiler when crossed
    (tests/test_chip_compile.py compiles both sides for a v5e):

    - the row DMA (``_gather_dma``) copies ONE table row per descriptor,
      and Mosaic lowers a one-row slice only of a 32-bit table whose rows
      are whole 128-lane tiles: float32 at rank 64 fails with "Slice shape
      along dimension 1 must be aligned to tiling (128), but is 64",
      bfloat16 and int8 at any rank with "Slice shape along dimension 0
      must be aligned to tiling (8), but is 1";
    - the scalar prefetch must fit the SMEM budget;
    - tile/block row counts must be 16-aligned — the double-buffered
      gather scratch is addressed at ``slot·rows + i·t`` dynamic offsets,
      which Mosaic's sublane slicing only lowers at (16, 128)-tile
      alignment.

    A refused shape keeps the XLA-gather path (same math, the materialized
    stream feeding the non-gather Pallas kernels) — never a compile
    failure.  ``lowered=False`` asks for the wrappers' interpret route,
    where no DMA is lowered (the XLA twin runs, at any rank and dtype) and
    only the shape gates apply, as they always did off the chip.
    """
    if lowered and (jnp.dtype(table_dtype) != jnp.float32 or k % 128):
        return False
    if tile_rows % 16:
        return False
    if block_rows is not None and block_rows % 16:
        return False
    return gather_prefetch_fits(entries, meta_words)


def _gather_dma(table_ref, g_buf, sem, sc_ref, nb_base, row0, rows, slot,
                f_rows):
    """Descriptor factory for one group's per-row gather DMAs: scratch row
    ``slot·rows + r`` ← ``table[min(nb[row0 + r], F−1)]``.  Start and wait
    recreate identical descriptors (the pallas DMA idiom); all of a
    group's copies signal the slot's semaphore."""
    def copy(r):
        idx = sc_ref[nb_base + row0 + r]
        src = jnp.minimum(idx, f_rows - 1)
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(src, 1)],
            g_buf.at[pl.ds(slot * rows + r, 1)],
            sem.at[slot],
        )

    return copy


def _gather_double_buffer(g_buf, sem, table_ref, sc_ref, *, nb_base, rows,
                          gi, ng, f_rows, group_row0):
    """The gather variants' double buffer: issue group gi+1's row DMAs
    (and group 0's at the prologue step) BEFORE waiting on group gi's, so
    the next block's HBM row fetches run under this block's Gram walk —
    the in-kernel analog of ``ops.pipeline.prefetch_scan``.  Slot parity
    alternates; the slot being filled for gi+1 was last read at step
    gi−1, which the sequential grid has already retired.  Returns the
    VMEM row offset of group gi's ready block.  ``group_row0`` maps a
    group index to its first index-stream position (``g·rows`` for the
    tile stream, ``meta[g]·BG`` for the dense stream — dense groups may
    revisit a block, in which case its rows are simply re-fetched)."""
    def start(group):
        slot = lax.rem(group, 2)
        copy = _gather_dma(table_ref, g_buf, sem, sc_ref, nb_base,
                           group_row0(group), rows, slot, f_rows)

        def body(r, c):
            copy(r).start()
            return c

        lax.fori_loop(0, rows, body, 0)

    @pl.when(gi == 0)
    def _prologue():
        start(gi)

    @pl.when(gi + 1 < ng)
    def _prefetch():
        start(gi + 1)

    slot = lax.rem(gi, 2)
    copy = _gather_dma(table_ref, g_buf, sem, sc_ref, nb_base,
                       group_row0(gi), rows, slot, f_rows)

    def wait_body(r, c):
        copy(r).wait()
        return c

    lax.fori_loop(0, rows, wait_body, 0)
    return slot * rows


def _premultiply_rows(g_buf, off, rows, wt_ref, out_buf=None):
    """In-register per-entry premultiply on the gathered block: one
    (1, rows) → (rows, 1) relayout per grid step (VMEM-local — the XLA
    path's [C, 1] weight column relayout through HBM is what this
    replaces), then a fused broadcast multiply.  The weight is cast to
    the factor dtype first, matching the XLA path's ``wt.astype(ct)``
    bit-for-bit.  ``wt`` is the 0/1 validity mask for unit-weight callers
    — which is what zeroes the clamped padding rows in-register.

    ``out_buf`` (int8 quantized tables — ``ops.quant``) redirects the
    product into a separate f32 compute scratch instead of multiplying in
    place: the DMA'd int8 rows cannot hold the dequantized product, and
    the per-row dequant scale is already folded into ``wt`` upstream
    (``quant.fold_scale`` — the canonical order), so THIS multiply is
    also the dequantize.  One pass either way."""
    base = pl.ds(pl.multiple_of(off, 16), rows)
    blk = g_buf[base, :]
    if out_buf is None:
        w = jnp.transpose(wt_ref[...], (1, 0)).astype(blk.dtype)
        g_buf[base, :] = blk * w
    else:
        w = jnp.transpose(wt_ref[...], (1, 0)).astype(out_buf.dtype)
        out_buf[base, :] = blk.astype(out_buf.dtype) * w


def _pop_gather_scratch(refs, int8_table):
    """Pop the gather scratch tail (``… g_buf, sem[, dq_buf]``) off a
    kernel's ref list: returns (g_buf, sem, dq_buf-or-None).  ``dq_buf``
    (int8 tables only) is the f32 dequant compute buffer appended LAST in
    the scratch list."""
    dq_buf = None
    if int8_table:
        dq_buf = refs[-1]
        del refs[-1]
    g_buf, sem = refs[-2], refs[-1]
    del refs[-2:]
    return g_buf, sem, dq_buf


def _gram_gather_groups_kernel(sc_ref, table_ref, *refs, m, t, k, nt, f_rows,
                               precision, with_carry, int8_table=False):
    """Gather-fused twin of ``_gram_groups_kernel``: the [m·t, k] factor
    block is row-DMA'd from the ANY-memory table instead of streamed as a
    pipelined input.  Scalar layout: seg [NT] ‖ nb [NT·T]."""
    refs = list(refs)
    g_buf, sem, dq_buf = _pop_gather_scratch(refs, int8_table)
    a_ref, b_ref = refs[-2:]
    del refs[-2:]
    carry = None
    if with_carry:
        carry = tuple(refs[-3:])
        del refs[-3:]
    rt_ref, wt_ref = refs[0], refs[1]
    gi = pl.program_id(0)
    base = gi * m
    rows = m * t
    off = _gather_double_buffer(
        g_buf, sem, table_ref, sc_ref, nb_base=nt, rows=rows, gi=gi,
        ng=pl.num_programs(0), f_rows=f_rows,
        group_row0=lambda g: g * rows,
    )
    _premultiply_rows(g_buf, off, rows, wt_ref, out_buf=dq_buf)
    a_all, b_all = _tile_grams(dq_buf if int8_table else g_buf, rt_ref,
                               m=m, t=t, k=k,
                               precision=precision, row_off=off)
    _walk_tiles(lambda i: sc_ref[i], a_all, b_all, gi=gi, base=base, m=m,
                a_ref=a_ref, b_ref=b_ref, carry=carry)


def _gram_solve_gather_groups_kernel(sc_ref, table_ref, *refs, m, t, k, nt,
                                     s_pad, f_rows, precision, with_carry,
                                     reg_mode, lam, algo, int8_table=False):
    """Gather-fused twin of ``_gram_solve_groups_kernel`` (in-kernel
    gather + scratch-resident walk + last-step ridge+solve epilogue).
    Scalar layout: seg [NT] ‖ lseg ‖ nb [NT·T]."""
    refs = list(refs)
    g_buf, sem, dq_buf = _pop_gather_scratch(refs, int8_table)
    if algo == "lu":
        lu_scr = tuple(refs[-3:])
        del refs[-3:]
    else:
        lu_scr = None
    a_scr, b_scr = refs[-2:]
    del refs[-2:]
    x_ref, cao_ref, cbo_ref = refs[-3:]
    del refs[-3:]
    carry = None
    if with_carry:
        carry = tuple(refs[-3:])
        del refs[-3:]
    rt_ref, wt_ref, reg_ref = refs[0], refs[1], refs[2]
    gi = pl.program_id(0)
    base = gi * m
    rows = m * t
    off = _gather_double_buffer(
        g_buf, sem, table_ref, sc_ref, nb_base=nt + 1, rows=rows, gi=gi,
        ng=pl.num_programs(0), f_rows=f_rows,
        group_row0=lambda g: g * rows,
    )
    _premultiply_rows(g_buf, off, rows, wt_ref, out_buf=dq_buf)
    a_all, b_all = _tile_grams(dq_buf if int8_table else g_buf, rt_ref,
                               m=m, t=t, k=k,
                               precision=precision, row_off=off)
    _walk_tiles(lambda i: sc_ref[i], a_all, b_all, gi=gi, base=base, m=m,
                a_ref=a_scr, b_ref=b_scr, carry=carry)

    @pl.when(gi == pl.num_programs(0) - 1)
    def _epilogue():
        _solve_epilogue(
            a_scr, b_scr, reg_ref, sc_ref[nt], x_ref, cao_ref, cbo_ref,
            lu_scr, k=k, s_pad=s_pad, reg_mode=reg_mode, lam=lam, algo=algo,
        )


def _gram_gather_dense_kernel(sc_ref, table_ref, *refs, m, t, k, ng, nt, bg,
                              f_rows, precision, with_carry,
                              int8_table=False):
    """Gather-fused twin of ``_gram_dense_kernel``: the [BG, k] stream
    block is row-DMA'd by index instead of streamed, then premultiplied by
    the stream-aligned ``wt`` — which carries the padding mask: a run's
    16-row alignment pads sit INSIDE its [lo, hi) window, and the clamped
    DMA fetched a real row for them.  Scalar layout: meta [NG+4·NT] ‖
    nb [C]."""
    refs = list(refs)
    g_buf, sem, dq_buf = _pop_gather_scratch(refs, int8_table)
    a_ref, b_ref = refs[-2:]
    del refs[-2:]
    carry = None
    if with_carry:
        carry = tuple(refs[-3:])
        del refs[-3:]
    rt_ref, wt_ref = refs[0], refs[1]
    gi = pl.program_id(0)
    base = gi * m
    meta_words = ng + 4 * nt
    off = _gather_double_buffer(
        g_buf, sem, table_ref, sc_ref, nb_base=meta_words, rows=bg, gi=gi,
        ng=pl.num_programs(0), f_rows=f_rows,
        group_row0=lambda g: sc_ref[g] * bg,
    )
    _premultiply_rows(g_buf, off, bg, wt_ref, out_buf=dq_buf)
    a_all, b_all = _tile_grams_dense(
        sc_ref, dq_buf if int8_table else g_buf, rt_ref, m=m, t=t, k=k,
        base=base, ng=ng, nt=nt,
        precision=precision, row_off=off,
    )
    _walk_tiles(lambda i: sc_ref[ng + 3 * nt + i], a_all, b_all, gi=gi,
                base=base, m=m, a_ref=a_ref, b_ref=b_ref, carry=carry)


def _gram_solve_gather_dense_kernel(sc_ref, table_ref, *refs, m, t, k, ng,
                                    nt, bg, s_pad, f_rows, precision,
                                    with_carry, reg_mode, lam, algo,
                                    int8_table=False):
    """Gather-fused twin of ``_gram_solve_dense_kernel``.  Scalar layout:
    meta [NG+4·NT] ‖ lseg ‖ nb [C]."""
    refs = list(refs)
    g_buf, sem, dq_buf = _pop_gather_scratch(refs, int8_table)
    if algo == "lu":
        lu_scr = tuple(refs[-3:])
        del refs[-3:]
    else:
        lu_scr = None
    a_scr, b_scr = refs[-2:]
    del refs[-2:]
    x_ref, cao_ref, cbo_ref = refs[-3:]
    del refs[-3:]
    carry = None
    if with_carry:
        carry = tuple(refs[-3:])
        del refs[-3:]
    rt_ref, wt_ref, reg_ref = refs[0], refs[1], refs[2]
    gi = pl.program_id(0)
    base = gi * m
    meta_words = ng + 4 * nt
    off = _gather_double_buffer(
        g_buf, sem, table_ref, sc_ref, nb_base=meta_words + 1, rows=bg,
        gi=gi, ng=pl.num_programs(0), f_rows=f_rows,
        group_row0=lambda g: sc_ref[g] * bg,
    )
    _premultiply_rows(g_buf, off, bg, wt_ref, out_buf=dq_buf)
    a_all, b_all = _tile_grams_dense(
        sc_ref, dq_buf if int8_table else g_buf, rt_ref, m=m, t=t, k=k,
        base=base, ng=ng, nt=nt,
        precision=precision, row_off=off,
    )
    _walk_tiles(lambda i: sc_ref[ng + 3 * nt + i], a_all, b_all, gi=gi,
                base=base, m=m, a_ref=a_scr, b_ref=b_scr, carry=carry)

    @pl.when(gi == pl.num_programs(0) - 1)
    def _epilogue():
        _solve_epilogue(
            a_scr, b_scr, reg_ref, sc_ref[meta_words], x_ref, cao_ref,
            cbo_ref, lu_scr, k=k, s_pad=s_pad, reg_mode=reg_mode, lam=lam,
            algo=algo,
        )


def _int8_gather_pieces(table, rows, k):
    """int8-quantized-table extras for the gather wrappers (``ops.quant``):
    the f32 dequant compute scratch (appended LAST in the scratch list —
    the convention ``_pop_gather_scratch`` reverses) and its VMEM bytes.
    The per-row dequant scale rides the weight stream (folded upstream by
    ``quant.fold_scale``, which is also what makes the single premultiply
    the dequantize)."""
    if table.dtype != jnp.int8:
        return False, [], 0
    return True, [pltpu.VMEM((2 * rows, k), jnp.float32)], 2 * rows * k * 4


def _dense_gather_weight(table, nb, wt):
    """The dense gather kernels' stream-aligned premultiply: the caller's
    ``wt`` (√aw for iALS, the folded dequant scale for int8 tables; None =
    unit weights) times the padding mask.  The mask is what zeroes a run's
    alignment pads — they index the virtual zero row F, the row DMA clamps
    them onto a real row, and they sit inside the tile windows.  An int8
    table without ``wt`` is refused: its dequant scale travels only there,
    and raw codes would be accumulated as numbers."""
    if wt is None and table.dtype == jnp.int8:
        raise ValueError(
            "int8 gather tables need a weight stream (quant.fold_scale "
            "folds the per-row dequant scale into wt); got wt=None"
        )
    mask = (nb < table.shape[0]).astype(jnp.float32)
    return mask if wt is None else wt.astype(jnp.float32) * mask


def _gather_precision(table):
    """Einsum precision for the gather kernels' Gram walk: full-f32 MXU
    passes for f32 tables AND int8 tables (whose compute buffer is the f32
    dequant scratch); the bf16 stream keeps the fast default passes."""
    return (
        jax.lax.Precision.HIGHEST
        if table.dtype in (jnp.float32, jnp.int8) else None
    )


def _emulate_gather(table, nb, wt):
    """The wrappers' interpret-route gather: the XLA twin of the DMA
    fetch + in-register premultiply (``compat.emulate_in_kernel_gather``),
    at the factor compute dtype the materialized-stream path uses (f32
    for int8 tables — the dequant scratch dtype)."""
    from cfk_tpu.compat import emulate_in_kernel_gather
    from cfk_tpu.ops.solve import _gram_compute_dtype

    ct, _ = _gram_compute_dtype(table)
    return emulate_in_kernel_gather(table, nb, wt, ct)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "tile_rows", "group_tiles", "interpret"),
)
def gram_tiles_gather_pallas(
    table: jax.Array,  # [F, k] RAW fixed factor table (no zero row)
    nb: jax.Array,  # [C] int32 row indices; F = the virtual zero row
    wt: jax.Array,  # [C] f32 premultiply (0/1 mask, or √aw·mask for iALS)
    rt: jax.Array,  # [C] f32 b-side coefficients (0 at padding)
    seg: jax.Array,  # [NT] int32 owner of each tile (sorted by the layout)
    *,
    num_segments: int,
    tile_rows: int,
    group_tiles: int = 64,
    interpret: bool | None = None,
    carry: tuple[jax.Array, jax.Array, jax.Array] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Gather-fused ``gram_tiles_pallas``: same (A, b) contract, but the
    [C, k] neighbor stream is never materialized — the kernel DMAs the
    indexed table rows into VMEM itself (see the section comment above).
    ``wt`` is REQUIRED: it is both the weighted (√aw) premultiply and the
    in-register realization of the zero-appended padding row (unit-weight
    callers pass their 0/1 validity mask, e.g. the tiled layout's
    ``weight`` channel)."""
    c = nb.shape[0]
    k = table.shape[-1]
    t = tile_rows
    if c % t != 0:
        raise ValueError(f"entry count {c} not divisible by tile_rows {t}")
    nt = c // t
    if seg.shape != (nt,):
        raise ValueError(f"seg shape {seg.shape} != ({nt},)")
    interpret = resolve_interpret(interpret)
    if interpret is True:
        return _emulate_gram_tiles(
            _emulate_gather(table, nb, wt), rt, seg,
            num_segments=num_segments, tile_rows=t, carry=carry,
        )
    m = group_tiles
    while nt % m != 0:
        m //= 2
    rows = m * t
    f_rows = table.shape[0]
    int8_table, dq_scratch, dq_bytes = _int8_gather_pieces(table, rows, k)
    vma = typeof_vma(table)
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d, vma=vma)) if vma else (
        lambda s, d: jax.ShapeDtypeStruct(s, d)
    )
    out_shape = (
        mk((num_segments, k, k), jnp.float32),
        mk((num_segments, 1, k), jnp.float32),
    )
    carry_specs = [] if carry is None else [
        pl.BlockSpec((k, k), lambda i, sc: (0, 0)),
        pl.BlockSpec((1, k), lambda i, sc: (0, 0)),
        pl.BlockSpec((1, 1), lambda i, sc: (0, 0)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt // m,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # table
            pl.BlockSpec((1, rows), lambda i, sc: (0, i)),   # rt
            pl.BlockSpec((1, rows), lambda i, sc: (0, i)),   # wt
        ] + carry_specs,
        out_specs=[
            pl.BlockSpec((num_segments, k, k), lambda i, sc: (0, 0, 0)),
            pl.BlockSpec((num_segments, 1, k), lambda i, sc: (0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2 * rows, k), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ] + dq_scratch,
    )
    precision = _gather_precision(table)
    out_bytes = num_segments * k * (k + 1) * 4
    g_bytes = 2 * rows * k * table.dtype.itemsize + dq_bytes
    kwargs = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(2 * out_bytes + g_bytes + 4 * rows * 8
                             + _walk_stack_bytes(m, k) + (12 << 20),
                             124 << 20)
    )}
    carry_ops = [] if carry is None else [
        carry[0].astype(jnp.float32),
        carry[1].reshape(1, k).astype(jnp.float32),
        carry[2].reshape(1, 1).astype(jnp.float32),
    ]
    scalar = jnp.concatenate([seg.astype(jnp.int32), nb.astype(jnp.int32)])
    a, b = pl.pallas_call(
        functools.partial(
            _gram_gather_groups_kernel, m=m, t=t, k=k, nt=nt, f_rows=f_rows,
            precision=precision, with_carry=carry is not None,
            int8_table=int8_table,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=bool(interpret),
        **kwargs,
    )(scalar, table, rt.reshape(1, c).astype(jnp.float32),
      wt.reshape(1, c).astype(jnp.float32), *carry_ops)
    return a, b[:, 0, :]


def gram_solve_tiles_gather_pallas(
    table: jax.Array,  # [F, k] RAW fixed factor table (no zero row)
    nb: jax.Array,  # [C] int32 row indices; F = the virtual zero row
    wt: jax.Array,  # [C] f32 premultiply (0/1 mask, or √aw·mask for iALS)
    rt: jax.Array,  # [C] f32
    seg: jax.Array,  # [NT] int32
    reg: jax.Array,  # diag: [num_segments] counts; matrix: [k, k] YᵀY+λI
    lseg: jax.Array,  # int32 scalar: the carry row to extract
    *,
    num_segments: int,
    tile_rows: int,
    group_tiles: int = 64,
    reg_mode: str = "diag",
    lam: float = 0.0,
    interpret: bool | None = None,
    carry: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    algo: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Gather-fused ``gram_solve_tiles_pallas``: in-kernel neighbor gather
    AND the in-VMEM ridge+solve epilogue — per chunk, neither the [C, k]
    gathered stream nor the [Ec, k, k] A-batch ever touches HBM."""
    from cfk_tpu.ops.pallas.solve_kernel import resolve_reg_solve_algo

    algo = resolve_reg_solve_algo(algo)
    return _gram_solve_tiles_gather_pallas(
        table, nb, wt, rt, seg, reg, lseg, num_segments=num_segments,
        tile_rows=tile_rows, group_tiles=group_tiles, reg_mode=reg_mode,
        lam=lam, interpret=interpret, carry=carry, algo=algo,
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "tile_rows", "group_tiles", "reg_mode",
                     "lam", "interpret", "algo"),
)
def _gram_solve_tiles_gather_pallas(
    table, nb, wt, rt, seg, reg, lseg, *, num_segments, tile_rows,
    group_tiles, reg_mode, lam, interpret, carry, algo,
):
    c = nb.shape[0]
    k = table.shape[-1]
    t = tile_rows
    if c % t != 0:
        raise ValueError(f"entry count {c} not divisible by tile_rows {t}")
    nt = c // t
    if seg.shape != (nt,):
        raise ValueError(f"seg shape {seg.shape} != ({nt},)")
    _check_reg_shape(reg, reg_mode, num_segments, k)
    interpret = resolve_interpret(interpret)
    if interpret is True:
        from cfk_tpu.compat import emulate_fused_gram_solve

        a, b = _emulate_gram_tiles(
            _emulate_gather(table, nb, wt), rt, seg,
            num_segments=num_segments, tile_rows=t, carry=carry,
        )
        return emulate_fused_gram_solve(
            a, b, reg, reg_mode=reg_mode, lam=lam, lseg=lseg,
        )
    m = group_tiles
    while nt % m != 0:
        m //= 2
    rows = m * t
    f_rows = table.shape[0]
    int8_table, dq_scratch, dq_bytes = _int8_gather_pieces(table, rows, k)
    s_pad = -(-num_segments // _SOLVE_LANES) * _SOLVE_LANES
    vma = typeof_vma(table)
    (reg_op, reg_spec, carry_ops, carry_specs, out_shape, out_specs,
     scratch, scratch_bytes) = _fused_call_pieces(
        k, s_pad, num_segments, reg, reg_mode, carry, vma, algo)
    scratch = scratch + [
        pltpu.VMEM((2 * rows, k), table.dtype),
        pltpu.SemaphoreType.DMA((2,)),
    ] + dq_scratch
    scalar = jnp.concatenate([
        seg.astype(jnp.int32),
        jnp.asarray(lseg, jnp.int32).reshape(1),
        nb.astype(jnp.int32),
    ])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt // m,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # table
            pl.BlockSpec((1, rows), lambda i, sc: (0, i)),   # rt
            pl.BlockSpec((1, rows), lambda i, sc: (0, i)),   # wt
            reg_spec,
        ] + carry_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    precision = _gather_precision(table)
    g_bytes = 2 * rows * k * table.dtype.itemsize + dq_bytes
    kwargs = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(scratch_bytes + g_bytes + 4 * rows * 8
                             + _walk_stack_bytes(m, k) + (12 << 20),
                             124 << 20)
    )}
    x, cao, cbo = pl.pallas_call(
        functools.partial(
            _gram_solve_gather_groups_kernel, m=m, t=t, k=k, nt=nt,
            s_pad=s_pad, f_rows=f_rows, precision=precision,
            with_carry=carry is not None, reg_mode=reg_mode, lam=lam,
            algo=algo, int8_table=int8_table,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=bool(interpret),
        **kwargs,
    )(scalar, table, rt.reshape(1, c).astype(jnp.float32),
      wt.reshape(1, c).astype(jnp.float32), reg_op, *carry_ops)
    return x[:num_segments], cao, cbo[0]


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "tile_rows", "num_tiles", "num_groups",
                     "block_rows", "interpret"),
)
def gram_tiles_dense_gather_pallas(
    table: jax.Array,  # [F, k] RAW fixed factor table (no zero row)
    nb: jax.Array,  # [C] int32 dense-stream row indices (pad8 → F)
    wt: jax.Array | None,  # [C] f32 √aw stream (iALS) or None (unit)
    rt: jax.Array,  # [NT·T] f32 TILE-ALIGNED b coefficients
    meta: jax.Array,  # [NG + 4·NT] int32: g_blk ‖ lb ‖ lo ‖ hi ‖ seg
    *,
    num_segments: int,
    tile_rows: int,
    num_tiles: int,
    num_groups: int,
    block_rows: int,
    interpret: bool | None = None,
    carry: tuple[jax.Array, jax.Array, jax.Array] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Gather-fused ``gram_tiles_dense_pallas``: the dense [C, k] stream
    is never materialized — each grid step row-DMAs its [BG, k] block by
    index.  Unit-weight callers pass ``wt=None``; the kernel still
    premultiplies by the padding mask (``_dense_gather_weight``)."""
    c = nb.shape[0]
    k = table.shape[-1]
    t = tile_rows
    nt, ng, bg = num_tiles, num_groups, block_rows
    if nt % ng != 0:
        raise ValueError(f"num_tiles {nt} not divisible by num_groups {ng}")
    m = nt // ng
    if rt.shape != (nt * t,):
        raise ValueError(f"rt shape {rt.shape} != ({nt * t},)")
    if meta.shape != (ng + 4 * nt,):
        raise ValueError(f"meta shape {meta.shape} != ({ng + 4 * nt},)")
    if c % bg != 0 or bg < t:
        raise ValueError(f"stream length {c} not a multiple of block_rows "
                         f"{bg} >= tile_rows {t}")
    interpret = resolve_interpret(interpret)
    if interpret is True:
        return _emulate_gram_dense(
            _emulate_gather(table, nb, wt), rt, meta,
            num_segments=num_segments, tile_rows=t, num_tiles=nt,
            num_groups=ng, block_rows=bg, carry=carry,
        )
    f_rows = table.shape[0]
    wt = _dense_gather_weight(table, nb, wt)
    int8_table, dq_scratch, dq_bytes = _int8_gather_pieces(table, bg, k)
    vma = typeof_vma(table)
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d, vma=vma)) if vma else (
        lambda s, d: jax.ShapeDtypeStruct(s, d)
    )
    out_shape = (
        mk((num_segments, k, k), jnp.float32),
        mk((num_segments, 1, k), jnp.float32),
    )
    carry_specs = [] if carry is None else [
        pl.BlockSpec((k, k), lambda i, sc: (0, 0)),
        pl.BlockSpec((1, k), lambda i, sc: (0, 0)),
        pl.BlockSpec((1, 1), lambda i, sc: (0, 0)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ng,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # table
            pl.BlockSpec((1, m * t), lambda i, sc: (0, i)),  # rt
            pl.BlockSpec((1, bg), lambda i, sc: (0, sc[i])),  # wt
        ] + carry_specs,
        out_specs=[
            pl.BlockSpec((num_segments, k, k), lambda i, sc: (0, 0, 0)),
            pl.BlockSpec((num_segments, 1, k), lambda i, sc: (0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2 * bg, k), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ] + dq_scratch,
    )
    precision = _gather_precision(table)
    out_bytes = num_segments * k * (k + 1) * 4
    g_bytes = 2 * bg * k * table.dtype.itemsize + dq_bytes
    kwargs = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(2 * out_bytes + g_bytes + 4 * bg * 8
                             + (10 << 20), 124 << 20)
    )}
    carry_ops = [] if carry is None else [
        carry[0].astype(jnp.float32),
        carry[1].reshape(1, k).astype(jnp.float32),
        carry[2].reshape(1, 1).astype(jnp.float32),
    ]
    scalar = jnp.concatenate([meta.astype(jnp.int32), nb.astype(jnp.int32)])
    a, b = pl.pallas_call(
        functools.partial(
            _gram_gather_dense_kernel, m=m, t=t, k=k, ng=ng, nt=nt, bg=bg,
            f_rows=f_rows, precision=precision,
            with_carry=carry is not None, int8_table=int8_table,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=bool(interpret),
        **kwargs,
    )(scalar, table, rt.reshape(1, nt * t), wt.reshape(1, c), *carry_ops)
    return a, b[:, 0, :]


def gram_solve_tiles_dense_gather_pallas(
    table: jax.Array,  # [F, k] RAW fixed factor table (no zero row)
    nb: jax.Array,  # [C] int32 dense-stream row indices (pad8 → F)
    wt: jax.Array | None,  # [C] f32 √aw stream (iALS) or None (unit)
    rt: jax.Array,  # [NT·T] f32 TILE-ALIGNED b coefficients
    meta: jax.Array,  # [NG + 4·NT] int32
    reg: jax.Array,  # diag: [num_segments] counts; matrix: [k, k]
    lseg: jax.Array,  # int32 scalar
    *,
    num_segments: int,
    tile_rows: int,
    num_tiles: int,
    num_groups: int,
    block_rows: int,
    reg_mode: str = "diag",
    lam: float = 0.0,
    interpret: bool | None = None,
    carry: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    algo: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Gather-fused ``gram_solve_tiles_dense_pallas``: in-kernel dense
    gather AND the in-VMEM ridge+solve epilogue."""
    from cfk_tpu.ops.pallas.solve_kernel import resolve_reg_solve_algo

    algo = resolve_reg_solve_algo(algo)
    return _gram_solve_tiles_dense_gather_pallas(
        table, nb, wt, rt, meta, reg, lseg, num_segments=num_segments,
        tile_rows=tile_rows, num_tiles=num_tiles, num_groups=num_groups,
        block_rows=block_rows, reg_mode=reg_mode, lam=lam,
        interpret=interpret, carry=carry, algo=algo,
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "tile_rows", "num_tiles", "num_groups",
                     "block_rows", "reg_mode", "lam", "interpret", "algo"),
)
def _gram_solve_tiles_dense_gather_pallas(
    table, nb, wt, rt, meta, reg, lseg, *, num_segments, tile_rows,
    num_tiles, num_groups, block_rows, reg_mode, lam, interpret, carry,
    algo,
):
    c = nb.shape[0]
    k = table.shape[-1]
    t = tile_rows
    nt, ng, bg = num_tiles, num_groups, block_rows
    if nt % ng != 0:
        raise ValueError(f"num_tiles {nt} not divisible by num_groups {ng}")
    m = nt // ng
    if rt.shape != (nt * t,):
        raise ValueError(f"rt shape {rt.shape} != ({nt * t},)")
    if meta.shape != (ng + 4 * nt,):
        raise ValueError(f"meta shape {meta.shape} != ({ng + 4 * nt},)")
    if c % bg != 0 or bg < t:
        raise ValueError(f"stream length {c} not a multiple of block_rows "
                         f"{bg} >= tile_rows {t}")
    _check_reg_shape(reg, reg_mode, num_segments, k)
    interpret = resolve_interpret(interpret)
    if interpret is True:
        from cfk_tpu.compat import emulate_fused_gram_solve

        a, b = _emulate_gram_dense(
            _emulate_gather(table, nb, wt), rt, meta,
            num_segments=num_segments, tile_rows=t, num_tiles=nt,
            num_groups=ng, block_rows=bg, carry=carry,
        )
        return emulate_fused_gram_solve(
            a, b, reg, reg_mode=reg_mode, lam=lam, lseg=lseg,
        )
    f_rows = table.shape[0]
    wt = _dense_gather_weight(table, nb, wt)
    int8_table, dq_scratch, dq_bytes = _int8_gather_pieces(table, bg, k)
    s_pad = -(-num_segments // _SOLVE_LANES) * _SOLVE_LANES
    vma = typeof_vma(table)
    (reg_op, reg_spec, carry_ops, carry_specs, out_shape, out_specs,
     scratch, scratch_bytes) = _fused_call_pieces(
        k, s_pad, num_segments, reg, reg_mode, carry, vma, algo)
    scratch = scratch + [
        pltpu.VMEM((2 * bg, k), table.dtype),
        pltpu.SemaphoreType.DMA((2,)),
    ] + dq_scratch
    scalar = jnp.concatenate([
        meta.astype(jnp.int32),
        jnp.asarray(lseg, jnp.int32).reshape(1),
        nb.astype(jnp.int32),
    ])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ng,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # table
            pl.BlockSpec((1, m * t), lambda i, sc: (0, i)),  # rt
            pl.BlockSpec((1, bg), lambda i, sc: (0, sc[i])),  # wt
            reg_spec,
        ] + carry_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    precision = _gather_precision(table)
    g_bytes = 2 * bg * k * table.dtype.itemsize + dq_bytes
    kwargs = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(scratch_bytes + g_bytes + 4 * bg * 8
                             + (10 << 20), 124 << 20)
    )}
    x, cao, cbo = pl.pallas_call(
        functools.partial(
            _gram_solve_gather_dense_kernel, m=m, t=t, k=k, ng=ng, nt=nt,
            bg=bg, s_pad=s_pad, f_rows=f_rows, precision=precision,
            with_carry=carry is not None, reg_mode=reg_mode, lam=lam,
            algo=algo, int8_table=int8_table,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=bool(interpret),
        **kwargs,
    )(scalar, table, rt.reshape(1, nt * t), wt.reshape(1, c), reg_op,
      *carry_ops)
    return x[:num_segments], cao, cbo[0]


def _gather_rows_kernel(sc_ref, table_ref, *refs, bg, k, f_rows, weighted,
                        sep_buf):
    """Row-DMA stream producer: each grid step fetches its [BG] indexed
    rows into the double-buffered scratch (next group's copies in flight
    under this group's write-out), applies the premultiply (which is also
    the dequantize for quantized tables — scale folded into ``wt``
    upstream), and writes the [BG, k] block to the output stream.  The
    bucketed half-steps and the subspace sweeps use this where their
    consumer needs the whole gathered rectangle resident (the b×b sweeps
    rank-update a score stream across blocks, so the stream must exist) —
    it replaces XLA's operand-size-cliffed gather with per-row DMA, not
    the stream itself."""
    refs = list(refs)
    g_buf, sem, dq_buf = _pop_gather_scratch(refs, sep_buf)
    out_ref = refs[-1]
    wt_ref = refs[0] if weighted else None
    gi = pl.program_id(0)
    off = _gather_double_buffer(
        g_buf, sem, table_ref, sc_ref, nb_base=0, rows=bg, gi=gi,
        ng=pl.num_programs(0), f_rows=f_rows,
        group_row0=lambda g: g * bg,
    )
    base = pl.ds(pl.multiple_of(off, 16), bg)
    if weighted:
        _premultiply_rows(g_buf, off, bg, wt_ref, out_buf=dq_buf)
        src = dq_buf if sep_buf else g_buf
        out_ref[...] = src[base, :].astype(out_ref.dtype)
    else:
        out_ref[...] = g_buf[base, :].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("out_dtype", "block_rows", "interpret"),
)
def gather_rows_pallas(
    table: jax.Array,  # [F, k] RAW table (f32 / bf16 / int8 — no zero row)
    nb: jax.Array,  # [C] int32 row indices; F = the virtual zero row
    wt: jax.Array | None,  # [C] premultiply (mask / √aw·mask, scale folded)
    *,
    out_dtype=None,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Materialized gathered stream via in-kernel row DMA:
    ``out[i] = table[nb[i]].astype(out_dtype) · wt[i]`` with the virtual
    zero row realized by clamp + the ``wt`` mask (``wt=None`` skips the
    multiply — callers whose padding is annihilated downstream).

    Off-TPU and refused shapes route through the bit-identical
    XLA twin (``compat.emulate_in_kernel_gather``), so CPU CI pins the
    same numbers the Mosaic DMA path produces on hardware."""
    from cfk_tpu.ops.solve import _gram_compute_dtype

    c = nb.shape[0]
    k = table.shape[-1]
    if table.dtype == jnp.int8 and wt is None:
        # Same loud refusal as the gram kernels (_int8_gather_pieces):
        # the per-row dequant scale rides ONLY in wt (quant.fold_scale),
        # so a scale-less int8 gather would return raw codes as numbers.
        raise ValueError(
            "gather_rows_pallas: an int8 table needs the per-row dequant "
            "scale folded into wt (ops.quant.fold_scale); wt=None would "
            "return raw quantized codes"
        )
    if out_dtype is None:
        out_dtype, _ = _gram_compute_dtype(table)
    out_dtype = jnp.dtype(out_dtype)
    interpret = resolve_interpret(interpret)
    bg = block_rows or min(c, 1024)
    while bg > 16 and c % bg:
        bg //= 2
    supported = interpret is not True and c % bg == 0 and bg % 16 == 0
    if supported and interpret is False:
        supported = in_kernel_gather_supported(
            c, 0, 16, k=k, table_dtype=table.dtype)
    if not supported:
        from cfk_tpu.compat import emulate_in_kernel_gather

        return emulate_in_kernel_gather(table, nb, wt, out_dtype)
    f_rows = table.shape[0]
    weighted = wt is not None
    sep_buf = weighted and out_dtype != table.dtype
    vma = typeof_vma(table)
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d, vma=vma)) if vma else (
        lambda s, d: jax.ShapeDtypeStruct(s, d)
    )
    wt_specs = ([pl.BlockSpec((1, bg), lambda i, sc: (0, i))]
                if weighted else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(c // bg,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + wt_specs,
        out_specs=[pl.BlockSpec((bg, k), lambda i, sc: (i, 0))],
        scratch_shapes=[
            pltpu.VMEM((2 * bg, k), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ] + ([pltpu.VMEM((2 * bg, k), out_dtype)] if sep_buf else []),
    )
    g_bytes = 2 * bg * k * (table.dtype.itemsize
                            + (out_dtype.itemsize if sep_buf else 0))
    kwargs = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(g_bytes + 2 * bg * k * out_dtype.itemsize
                             + 4 * bg * 8 + (8 << 20), 124 << 20)
    )}
    wt_ops = ([wt.reshape(1, c).astype(jnp.float32)] if weighted else [])
    (out,) = pl.pallas_call(
        functools.partial(
            _gather_rows_kernel, bg=bg, k=k, f_rows=f_rows,
            weighted=weighted, sep_buf=sep_buf,
        ),
        grid_spec=grid_spec,
        out_shape=(mk((c, k), out_dtype),),
        interpret=bool(interpret),
        **kwargs,
    )(nb.astype(jnp.int32), table, *wt_ops)
    return out


def _check_reg_shape(reg, reg_mode, num_segments, k):
    if reg_mode == "diag":
        if reg.shape != (num_segments,):
            raise ValueError(
                f"diag reg shape {reg.shape} != ({num_segments},)"
            )
    elif reg_mode == "matrix":
        if reg.shape != (k, k):
            raise ValueError(f"matrix reg shape {reg.shape} != ({k},{k})")
    else:
        raise ValueError(f"unknown reg_mode {reg_mode!r}")
