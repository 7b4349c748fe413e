"""SPMD sharded ALS: the distributed half-iteration as explicit collectives.

The reference's per-iteration feature-exchange Kafka topics
(``apps/ALSApp.java:115-151``) become one collective per half-iteration:

- ``all_gather`` exchange — every shard receives the full fixed-side factor
  matrix over ICI, then solves its local entities.  This is the all-to-all
  join (reference's ``all-to-all-join`` branch, README.md:172) done right:
  the OutBlock send-once-per-partition dedup
  (``processors/MRatings2BlocksProcessor.java:63-65``) is exactly what
  all_gather gives for free.

- ``ring`` exchange — fixed-side factor *blocks* rotate around the shard ring
  via ``ppermute``; each shard accumulates the partial Gram matrix of the
  block it currently holds.  This is the block-to-block join
  (README.md:152-157) as a systolic ring — the ring-attention-style pattern:
  per-device memory stays O(F/S·k) instead of O(F·k), at the cost of S
  pipeline steps whose compute hides the permute latency.

The EOF barrier protocol of the reference (``processors/URatings2BlocksProcessor.java:56-63``)
has no runtime analog here: bulk-synchronous SPMD steps *are* the barrier
(SURVEY.md §2.6); the ingest-side protocol lives in ``cfk_tpu.transport``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cfk_tpu.compat import (
    shard_map as _compat_shard_map,
    to_varying as _to_varying,
)
from cfk_tpu.config import ALSConfig
from cfk_tpu.data.blocks import (
    BucketedBlocks,
    Dataset,
    PaddedBlocks,
    RingBlocks,
    SegmentBlocks,
    TiledBlocks,
    build_ring_blocks,
)
from cfk_tpu.models.als import ALSModel
from cfk_tpu.ops.solve import (
    _match_varying,
    als_half_step,
    als_half_step_bucketed,
    als_half_step_segment,
    gather_gram,
    global_gram,
    init_factors,
    init_factors_stats,
    regularized_solve,
)
from cfk_tpu.parallel.mesh import AXIS, shard_rows, to_host



def half_step_allgather(
    fixed_local, nb, rt, mk, cnt, *, lam, solve_chunk=None, solver="cholesky",
    table_dtype=None,
):
    """Per-shard half-iteration with all_gather'd fixed factors.

    Runs inside shard_map: all args are local shards (entity axis 0).
    ``table_dtype="bfloat16"`` quantizes the exchange payload BEFORE the
    all_gather (half the ICI bytes), which is also the gather-table cast
    downstream — per-row quantization commutes with row sharding.
    """
    from cfk_tpu.ops import quant

    fixed_full = lax.all_gather(
        quant.gather_operand_view(fixed_local, table_dtype),
        AXIS, axis=0, tiled=True,
    )
    return als_half_step(
        fixed_full, nb, rt, mk, cnt, lam, solve_chunk=solve_chunk, solver=solver
    )


def _gram_chunked(blk, nb_t, rt_t, mk_t, solve_chunk, overlap=None):
    """gather_gram over entity chunks: bounds the [chunk, P_ring, k] gather.

    An indivisible entity count is padded with zero-mask rows (their Grams
    are exact zeros, sliced off), so budget-derived chunk sizes always
    work.  The chunk stream is double-buffered (``ops.pipeline.chunk_map``):
    chunk c+1's operand fetch is issued while chunk c's Gram runs."""
    if solve_chunk is None or solve_chunk >= nb_t.shape[0]:
        return gather_gram(blk, nb_t, rt_t, mk_t)
    from cfk_tpu.ops.pipeline import chunk_map
    from cfk_tpu.ops.solve import pad_rows_to_multiple

    e = nb_t.shape[0]
    (nb_t, rt_t, mk_t), pad = pad_rows_to_multiple(
        (nb_t, rt_t, mk_t), solve_chunk
    )
    n_chunks = (e + pad) // solve_chunk
    reshape = lambda x: x.reshape((n_chunks, solve_chunk) + x.shape[1:])
    a, b = chunk_map(
        lambda ni, ri, mi: gather_gram(blk, ni, ri, mi),
        (reshape(nb_t), reshape(rt_t), reshape(mk_t)),
        n_chunks, overlap=overlap,
    )
    k = blk.shape[-1]
    return a.reshape(e + pad, k, k)[:e], b.reshape(e + pad, k)[:e]


def _ring_rotate(blk, perm, compute, *, overlap):
    """One double-buffered ring step: the next block's ``ppermute`` is
    issued BEFORE the Gram consumes the current one (two factor buffers
    alive — the classic double buffer), so XLA's async collective-permute
    scheduling can run the ICI transfer under the compute.  With
    ``overlap=False`` an ``optimization_barrier`` pins the serial reference
    schedule (compute fully drains, then the transfer starts) — the A/B
    baseline.  Returns (compute result, next
    block); both orders run identical ops on identical values, so factors
    are bit-equal either way (``tests/test_overlap.py``)."""
    permute = lambda b: jax.tree.map(
        lambda x: lax.ppermute(x, AXIS, perm), b
    )  # blk may be a (data, scale) tuple — quantized tables rotate both
    if overlap:
        nxt = permute(blk)
        out = compute(blk)
    else:
        out = compute(blk)
        out, blk = lax.optimization_barrier((out, blk))
        nxt = permute(blk)
    return out, nxt


def _nonfinite_flag(x):
    """int32 0/1: any NaN/Inf anywhere in ``x`` (ring-carry health probe)."""
    return jnp.where(
        jnp.all(jnp.isfinite(x.astype(jnp.float32))),
        jnp.int32(0), jnp.int32(1),
    )


def _payload_nonfinite_flag(tbl):
    """Ring-payload probe over the LAST leaf: the f32/bf16 factor block
    itself, or the int8 pair's f32 per-row scales.  The int8 codes are
    finite by construction, so probing them would miss every corruption;
    ``quant.quantize_table`` propagates a corrupt row's NaN/Inf into its
    scale, making the scales the one int8 leaf that can trip."""
    return _nonfinite_flag(tbl[-1])


def half_step_ring(
    fixed_local, nb, rt, mk, cnt, *, lam, num_shards, solve_chunk=None,
    solver="cholesky", overlap=None, probe=None, fused_epilogue=None,
    health=False, reg_solve_algo=None, table_dtype=None,
):
    """Per-shard half-iteration accumulating Gram blocks around a ppermute ring.

    ``nb/rt/mk`` are RingBlocks locals: [E_local, S, P_ring] with neighbor
    indices local to the fixed shard that owns them.  At ring step r this
    shard holds the factor block of fixed shard (my_index − r) mod S; the
    final step's block is consumed without a trailing ppermute (S−1 transfers
    per half-iteration, not S).

    The loop is a double-buffered pipeline (``_ring_rotate``): block r+1's
    transfer is in flight while block r's Gram accumulates.  ``probe``
    (timing-only, used by the bench's exchange/compute split) runs just the
    transfers ("exchange") or just the Gram/solve with no transfers
    ("compute") — same op counts as the respective phase of the real
    half-iteration, numerically meaningless factors.

    ``health=True`` (the resilience sentinel's ring-carry probe,
    ``cfk_tpu.resilience``) folds an ``isfinite`` check of each
    ring-rotated factor block into the loop carry and returns
    ``(factors, bad)`` — ``bad`` is a per-shard int32 flag that localizes
    in-flight exchange corruption to this half-iteration instead of
    waiting for it to surface in the solved factors.  Incompatible with
    the timing ``probe`` modes (which compute meaningless factors).
    """
    from cfk_tpu.ops import quant
    from cfk_tpu.ops.pipeline import resolve_overlap

    if health and probe is not None:
        raise ValueError("health probing and timing probes are exclusive")
    overlap = resolve_overlap(overlap)
    # Quantize the ROTATING payload once, before the ring: every ppermute
    # then moves the bf16 block (half the ICI bytes) and every Gram
    # consumes the same quantized rows — the padded layout's weight-free
    # Gram admits bf16 only (config validation refuses int8 here).
    fixed_local = quant.gather_operand_view(fixed_local, table_dtype)
    my = lax.axis_index(AXIS)
    e = nb.shape[0]
    k = fixed_local.shape[-1]
    perm = [(i, (i + 1) % num_shards) for i in range(num_shards)]

    def gram_at(blk, r):
        t = (my - r) % num_shards
        return _gram_chunked(
            blk,
            jnp.take(nb, t, axis=1),
            jnp.take(rt, t, axis=1),
            jnp.take(mk, t, axis=1),
            solve_chunk,
            overlap,
        )

    if probe == "exchange":  # transfers only; factors are a timing sink
        blk = lax.fori_loop(
            0, num_shards - 1,
            lambda r, blk: lax.ppermute(blk, AXIS, perm),
            fixed_local,
        )
        return jnp.zeros((e, k), jnp.float32) + jnp.sum(blk).astype(
            jnp.float32
        )

    def body(r, carry):
        a, b, blk, bad = carry
        if health:
            bad = bad | _nonfinite_flag(blk)
        if probe == "compute":  # Gram/solve only: never rotate the block
            ap, bp = gram_at(blk, r)
            return (a + ap, b + bp, blk, bad)
        (ap, bp), blk = _ring_rotate(
            blk, perm, lambda cur: gram_at(cur, r), overlap=overlap
        )
        return (a + ap, b + bp, blk, bad)

    # Mark the zero accumulators device-varying so the fori_loop carry type
    # matches the (varying) per-shard partial Gram sums.
    a0 = _to_varying(jnp.zeros((e, k, k), jnp.float32), AXIS)
    b0 = _to_varying(jnp.zeros((e, k), jnp.float32), AXIS)
    bad0 = _to_varying(jnp.zeros((), jnp.int32), AXIS)
    a, b, blk, bad = lax.fori_loop(
        0, num_shards - 1, body, (a0, b0, fixed_local, bad0)
    )
    if health:
        bad = bad | _nonfinite_flag(blk)
    ap, bp = gram_at(blk, num_shards - 1)
    # The ring's (A, b) accumulates ACROSS ring steps, so there is no
    # per-chunk VMEM residency to solve inside; ``fused_epilogue`` gates
    # the one fused reg+solve pass over the final sums (the fused/split
    # A/B axis).
    x = regularized_solve(a + ap, b + bp, cnt, lam, solver,
                          fused=fused_epilogue, algo=reg_solve_algo)
    return (x, bad) if health else x


def _segment_to_tree(blocks: SegmentBlocks) -> dict[str, np.ndarray]:
    """Flat per-shard packed chunks; every leaf rows-shards over P(AXIS)."""
    return {
        "neighbor": blocks.neighbor_idx,
        "rating": blocks.rating,
        "mask": blocks.mask,
        "seg": blocks.seg_rel,
        "entity": blocks.chunk_entity,
        "ecount": blocks.chunk_count,
        "gsizes": blocks.group_sizes,
        "cin": blocks.carry_in,
        "lseg": blocks.last_seg,
    }


# Both exchange layouts expose the same tree keys; "neighbor" holds dense
# global indices for all_gather blocks, shard-local indices for ring blocks.
def _padded_to_tree(blocks: PaddedBlocks) -> dict[str, np.ndarray]:
    return {
        "neighbor": blocks.neighbor_idx,
        "rating": blocks.rating,
        "mask": blocks.mask,
        "count": blocks.count,
    }


def _ring_to_tree(blocks: RingBlocks) -> dict[str, np.ndarray]:
    return {
        "neighbor": blocks.neighbor_local,
        "rating": blocks.rating,
        "mask": blocks.mask,
        "count": blocks.count,
    }


def _bucketed_to_tree(blocks: BucketedBlocks):
    """Tuple-of-dicts pytree (shard-major rows, P(AXIS) shardable) + static
    per-bucket chunk hints."""
    return blocks.to_tree()


def tree_specs(tree):
    return jax.tree.map(
        lambda v: P(AXIS, *([None] * (v.ndim - 1))), tree
    )


_tree_specs = tree_specs  # back-compat alias


def wrap_step(mesh, config: ALSConfig, half_m, half_u, mspecs, uspecs,
              *, carry_prev=False, ring_flags=False):
    """The one shard_map scaffold every training step shares.

    ``half_m``/``half_u`` map (fixed_local, local_block_tree) → new local
    factors for one side; the wrapper sequences the two half-iterations,
    casts factors to the storage/exchange dtype, and binds the row shardings.
    With ``carry_prev`` (warm-started optimizers like iALS++) the halves get
    the side's previous local factors too: (fixed_local, prev_local, blk).

    With ``ring_flags`` (the resilience sentinel's ring-carry probe) every
    half returns ``(factors, bad)`` and the step emits a third, replicated
    int32 output: the psum of both halves' per-shard exchange-corruption
    flags — 0 means every ring-rotated block stayed finite on every shard.
    """
    dtype = jnp.dtype(config.dtype)

    def solve_half(half, fixed, prev, blk):
        out = half(fixed, prev, blk) if carry_prev else half(fixed, blk)
        x, bad = out if ring_flags else (out, None)
        return x.astype(dtype), bad

    def iteration(u, m_prev, mblk, ublk):
        m, bad_m = solve_half(half_m, u, m_prev, mblk)
        u_new, bad_u = solve_half(half_u, m, u, ublk)
        if not ring_flags:
            return u_new, m
        return u_new, m, lax.psum(bad_m + bad_u, AXIS)

    out_specs = (P(AXIS, None), P(AXIS, None))
    if ring_flags:
        out_specs = out_specs + (P(),)
    return _compat_shard_map(
        iteration,
        mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS, None), mspecs, uspecs),
        out_specs=out_specs,
        check=use_check_vma(config),
    )


def gathered_half(solve, *, with_gram=False, with_prev=False,
                  table_dtype=None):
    """The all_gather exchange pattern every gathered layout shares.

    ``solve(fixed_full, blk, gram) -> factors`` gets the full fixed-side
    factor matrix (one all_gather over ICI per half-iteration) and, with
    ``with_gram`` (iALS), the mesh-wide YᵀY (local Gram psum'd — a [k,k]
    collective).  ``with_prev`` threads the side's previous local factors
    through as ``solve(fixed_full, prev_local, blk, gram)`` (iALS++ warm
    start; the sweep is per-entity so prev stays shard-local, no extra
    collective).  Used by the explicit and implicit SPMD steps so the
    exchange is written exactly once.

    ``table_dtype="bfloat16"`` casts the exchange payload BEFORE the
    all_gather (half the ICI bytes; per-row quantization commutes with
    row sharding, so the gathered table equals the single-device cast and
    the downstream half-step's own cast is idempotent).  int8 payloads
    are NOT pre-quantized here — the (codes, scales) pair would double
    the collective count for a path whose bytes win is in the HBM
    gathers; the downstream half-step quantizes the gathered table
    instead.  The iALS gram is computed over the DEQUANTIZED local view
    either way, so YᵀY matches what the kernels gather.
    """
    from cfk_tpu.ops import quant

    def _prep(fixed_local):
        gram = None
        if with_gram:
            gram = lax.psum(
                global_gram(
                    quant.gather_operand_view(fixed_local, table_dtype)
                ),
                AXIS,
            )
        payload = fixed_local
        if quant.resolve_table_dtype(table_dtype) == "bfloat16":
            payload = payload.astype(jnp.bfloat16)
        return lax.all_gather(payload, AXIS, axis=0, tiled=True), gram

    def half(fixed_local, blk):
        fixed_full, gram = _prep(fixed_local)
        return solve(fixed_full, blk, gram)

    def half_prev(fixed_local, prev_local, blk):
        fixed_full, gram = _prep(fixed_local)
        return solve(fixed_full, prev_local, blk, gram)

    return half_prev if with_prev else half


def _tiled_to_tree(blocks: TiledBlocks, weighted: bool = False
                   ) -> dict[str, np.ndarray]:
    """Flat per-shard tiled arrays; every leaf rows-shards over P(AXIS)."""
    if blocks.mode == "dstream":
        d = {
            "neighbor_idx": blocks.neighbor_idx,
            "rating": blocks.rating,
            "tile_meta": blocks.tile_meta,
            "chunk_entity": blocks.chunk_entity,
            "chunk_count": blocks.chunk_count,
            "carry_in": blocks.carry_in,
            "last_seg": blocks.last_seg,
            "count": blocks.count,
        }
        if weighted:
            if not blocks.weight.size or blocks.rating_dense is None:
                raise ValueError(
                    "these dense-stream blocks predate the weighted "
                    "channels — rebuild the dataset (delete its cache)"
                )
            d["weight"] = blocks.weight
            d["rating_dense"] = blocks.rating_dense
        return d
    return {
        "neighbor_idx": blocks.neighbor_idx,
        "rating": blocks.rating,
        "weight": blocks.weight,
        "tile_seg": blocks.tile_seg,
        "chunk_base": blocks.chunk_base,
        "chunk_entity": blocks.chunk_entity,
        "chunk_count": blocks.chunk_count,
        "carry_in": blocks.carry_in,
        "last_seg": blocks.last_seg,
        "slice_starts": blocks.slice_starts,
        "count": blocks.count,
    }


def _make_tiled_slice_grams(blk, *, cap, nt, e_c, t, k, backend, gather,
                            int8):
    """The per-slice chunk loop both tiled ring schedules share: scan the
    slice's chunks against whichever factor block this shard currently
    holds, scatter-adding chunk-dense per-entity Grams into the persistent
    accumulator.  Factored out of ``half_step_tiled_ring`` so the flat and
    hierarchical rings run the IDENTICAL per-slice ops (the hierarchy only
    reorders which block arrives when)."""
    from cfk_tpu.ops import quant
    from cfk_tpu.ops.tiled import _entity_gram_chunk

    nb, rt, wt = blk["neighbor_idx"], blk["rating"], blk["weight"]
    ts, ent = blk["tile_seg"], blk["chunk_entity"]
    starts = blk["slice_starts"]  # [S+1]

    def slice_grams(acc, tbl, t_idx):
        factors = tbl[0]
        scale_blk = tbl[1] if int8 else None
        # One zero-row append per ring step, not per chunk (the chunk-scan
        # body would otherwise re-copy the whole block every chunk); the
        # in-kernel gather skips even that — the kernel DMAs from the raw
        # rotated block and the weight channel masks the padding rows.
        if gather == "fused":
            fz = factors
        else:
            fz = jnp.concatenate([
                factors,
                _match_varying(
                    jnp.zeros((1, k), factors.dtype), factors
                ),
            ])

        def chunk_body(i, acc):
            acc_a, acc_b = acc
            nb_c = lax.dynamic_slice(nb, (i * cap,), (cap,))
            rt_c = lax.dynamic_slice(rt, (i * cap,), (cap,))
            wt_c = lax.dynamic_slice(wt, (i * cap,), (cap,))
            ts_c = lax.dynamic_slice(ts, (i * nt,), (nt,))
            ent_c = lax.dynamic_slice(ent, (i * e_c,), (e_c,))
            # int8: fold this block's per-row dequant scale into the 0/1
            # weight channel (nb is local to the rotated block; the
            # block-local virtual zero row gets the appended 0 scale).
            wt_c = quant.fold_scale(wt_c, scale_blk, nb_c)
            a, b = _entity_gram_chunk(
                fz, nb_c, wt_c, rt_c, ts_c, t, e_c + 1, backend,
                # the ring is explicit-ALS only; int8 must premultiply
                # (the fold above IS the dequantize)
                unit_weights=not int8,
                zero_appended=gather != "fused", gather=gather,
            )
            return (acc_a.at[ent_c].add(a[:e_c]), acc_b.at[ent_c].add(b[:e_c]))

        return lax.fori_loop(starts[t_idx], starts[t_idx + 1], chunk_body, acc)

    return slice_grams


def resolve_ici_group(config: ALSConfig) -> int:
    """Inner-ring size of the hierarchical exchange: the explicit
    ``config.ici_group`` when set, else devices-per-process when that
    divides the shard count (the physical ICI domain on a multi-host
    mesh), else one flat ring (bit-identical to ``exchange='ring'``)."""
    if config.ici_group is not None:
        return config.ici_group
    local = jax.local_device_count()
    if 0 < local <= config.num_shards and config.num_shards % local == 0:
        return local
    return config.num_shards


def hier_phase_count(num_shards: int, inner: int) -> int:
    """Outer (DCN) phase count of the hierarchical exchange: the number
    of cross-group hops ``half_step_tiled_ring_hier`` rotates, and
    therefore the number of collectives the distributed window exchange
    runs per half.  ``inner == num_shards`` (the flat path) degenerates
    to one phase."""
    if inner < 1 or num_shards % inner != 0:
        raise ValueError(
            f"inner ring size {inner} must divide num_shards={num_shards}"
        )
    return num_shards // inner


def hier_phase_of_visit(visit_index: int, inner: int) -> int:
    """Which outer (DCN) phase a position in ``hier_visit_order``
    belongs to: the visit order walks ``inner`` ICI steps per outer hop,
    so phase = ``visit_index // inner``.  This is the cross-process
    delivery contract — a window's fixed-table residual must be on its
    consuming host by the start of the phase its slice is visited in."""
    if inner < 1:
        raise ValueError(f"inner ring size {inner} must be >= 1")
    return visit_index // inner


def half_step_tiled_ring_hier(
    fixed_local, blk, chunks, local_entities, *, lam, num_shards, inner,
    solver="cholesky", gram_backend=None, overlap=None, probe=None,
    fused_epilogue=None, health=False, in_kernel_gather=None,
    reg_solve_algo=None, table_dtype=None,
):
    """Hierarchical ICI-ring-within-DCN-ring tiled half-iteration
    (ISSUE 11; the ALX-style exchange for meshes whose fabric is tiered).

    ``num_shards = outer · inner``: shards group into ``inner``-sized
    rings on the fast fabric.  Phase ``p`` rotates each group's blocks
    ``inner − 1`` times over the INNER permutation (pure ICI — shard
    (g, i) visits every block of group g − p), then ONE outer hop moves
    every held block to the same inner position of the next group (the
    only transfers that cross DCN).  O·(I−1) + (O−1) = S−1 transfers, all
    S blocks visited per shard — the flat ring's totals, with the slow
    fabric paid O−1 times instead of on every boundary edge every step.

    Numerics: the per-slice chunk math is IDENTICAL to the flat ring
    (``_make_tiled_slice_grams``); only the VISIT ORDER of slices differs,
    so the per-entity Gram sums associate differently (same additions,
    different order — within float tolerance of the flat ring, and
    deterministic for a fixed (num_shards, inner)).  ``inner ==
    num_shards`` degenerates to one inner ring whose schedule — and
    factors — are BIT-IDENTICAL to ``half_step_tiled_ring``
    (tests/test_offload.py pins both contracts).  Each transfer is
    double-buffered via ``_ring_rotate`` exactly like the flat ring;
    ``probe``/``health`` as in ``half_step_tiled_ring``.
    """
    from cfk_tpu.ops import quant
    from cfk_tpu.ops.pipeline import resolve_overlap
    from cfk_tpu.ops.tiled import (
        default_tiled_gram_backend,
        resolve_gather_mode,
    )

    if health and probe is not None:
        raise ValueError("health probing and timing probes are exclusive")
    s = num_shards
    if inner < 1 or s % inner != 0:
        raise ValueError(
            f"inner ring size {inner} must divide num_shards={s}"
        )
    outer = s // inner
    overlap = resolve_overlap(overlap)
    backend = gram_backend or default_tiled_gram_backend()
    _, _, nc, cap, t, h, e_c = chunks
    nt = cap // t
    k = fixed_local.shape[-1]
    data, scale = quant.quantize_table(fixed_local, table_dtype)
    gather = resolve_gather_mode(
        in_kernel_gather, backend, cap, nt, t, e_c + 1, k,
        table_dtype=data.dtype,
    )
    tbl0 = (data,) if scale is None else (data, scale)
    int8 = scale is not None
    my = lax.axis_index(AXIS)
    g, i_pos = my // inner, my % inner
    # Inner rotation: within-group shift by one; outer hop: same inner
    # position of the next group.  Both are full permutations of [0, S).
    inner_perm = [
        (q, (q // inner) * inner + (q % inner + 1) % inner)
        for q in range(s)
    ]
    outer_perm = [
        (q, ((q // inner + 1) % outer) * inner + q % inner)
        for q in range(s)
    ]
    slice_grams = _make_tiled_slice_grams(
        blk, cap=cap, nt=nt, e_c=e_c, t=t, k=k, backend=backend,
        gather=gather, int8=int8,
    )

    # Schedule: (phase p, inner step j) — this shard holds the block of
    # slice (g − p, i + p − j); see the derivation in the docstring.
    # Rolled as fori loops (the flat ring's discipline): trace size is
    # O(1) in both `outer` and `inner`, not O(S) — an unrolled schedule
    # would trace S copies of the chunk loop at 64–256-shard meshes.
    def held(p, j):
        return ((g - p) % outer) * inner + (i_pos + p - j) % inner

    if probe == "exchange":  # transfers only; factors are a timing sink
        def x_inner(t):
            return lax.fori_loop(
                0, inner - 1,
                lambda j, tt: jax.tree.map(
                    lambda x: lax.ppermute(x, AXIS, inner_perm), tt
                ),
                t,
            )

        tbl = lax.fori_loop(
            0, outer - 1,
            lambda p, t: jax.tree.map(
                lambda x: lax.ppermute(x, AXIS, outer_perm), x_inner(t)
            ),
            tbl0,
        )
        tbl = x_inner(tbl)
        return jnp.zeros((local_entities, k), jnp.float32) + jnp.sum(
            tbl[0].astype(jnp.float32)
        )

    acc0 = (
        _to_varying(jnp.zeros((local_entities + 1, k, k), jnp.float32),
                    AXIS),
        _to_varying(jnp.zeros((local_entities + 1, k), jnp.float32), AXIS),
    )
    bad0 = _to_varying(jnp.zeros((), jnp.int32), AXIS)

    if probe == "compute":  # chunk loops only: never rotate the block
        def body(r, acc):
            return slice_grams(acc, tbl0, held(r // inner, r % inner))

        acc_a, acc_b = lax.fori_loop(0, inner * outer, body, acc0)
        x = regularized_solve(
            acc_a[:local_entities], acc_b[:local_entities],
            blk["count"], lam, solver, fused=fused_epilogue,
            algo=reg_solve_algo,
        )
        return x

    def inner_rotations(p, carry):
        """Phase ``p``'s first inner − 1 visits, each ending in an
        inner-ring rotation (j = 0 .. inner−2)."""
        def step(j, c):
            a, b, tbl, bad = c
            if health:
                bad = bad | _payload_nonfinite_flag(tbl)
            (a, b), tbl = _ring_rotate(
                tbl, inner_perm,
                lambda cur: slice_grams((a, b), cur, held(p, j)),
                overlap=overlap,
            )
            return a, b, tbl, bad

        return lax.fori_loop(0, inner - 1, step, carry)

    def phase_body(p, c):
        # inner − 1 inner rotations, then the phase's LAST visit ends in
        # the one outer (DCN) hop — no lax.cond around the collectives:
        # the hop is peeled out of the rolled inner loop.
        a, b, tbl, bad = inner_rotations(p, c)
        if health:
            bad = bad | _payload_nonfinite_flag(tbl)
        (a, b), tbl = _ring_rotate(
            tbl, outer_perm,
            lambda cur: slice_grams((a, b), cur, held(p, inner - 1)),
            overlap=overlap,
        )
        return a, b, tbl, bad

    carry = (acc0[0], acc0[1], tbl0, bad0)
    carry = lax.fori_loop(0, outer - 1, phase_body, carry)
    # Final phase: inner − 1 inner rotations, then the last visit
    # consumes the block without a trailing transfer (S − 1 total).
    a, b, tbl, bad = inner_rotations(outer - 1, carry)
    if health:
        bad = bad | _payload_nonfinite_flag(tbl)
    a, b = slice_grams((a, b), tbl, held(outer - 1, inner - 1))
    x = regularized_solve(
        a[:local_entities], b[:local_entities],
        blk["count"], lam, solver, fused=fused_epilogue,
        algo=reg_solve_algo,
    )
    return (x, bad) if health else x


def half_step_tiled_ring(
    fixed_local, blk, chunks, local_entities, *, lam, num_shards,
    solver="cholesky", gram_backend=None, overlap=None, probe=None,
    fused_epilogue=None, health=False, in_kernel_gather=None,
    reg_solve_algo=None, table_dtype=None,
):
    """Tiled-layout half-iteration over the ppermute ring (block-to-block
    join) — the reference's headline join strategy at the at-scale layout.

    The ring-built tiled blocks sort each shard's entries by (owner shard
    of the neighbor, entity) with slices exactly the fixed-side factor
    shards, so at ring step r the device processes slice (my − r) mod S —
    whose neighbor indices are local to the factor block it currently
    holds — and scatter-adds chunk-dense per-entity Grams into a
    persistent [E_local+1, ...] accumulator; one batched solve at the end.
    S − 1 ppermutes per half-iteration; the full fixed-side matrix is
    never materialized per device (O(F/S·k) factor memory, the
    block-to-block property), traded against the O(E_local·k²)
    accumulator the join needs on TPU — PARITY.md discusses when that
    trade wins.

    Each ring step is double-buffered (``_ring_rotate``): the next block's
    ppermute is issued before the current block's chunk loop starts, so
    the ICI transfer hides behind the slice's Gram accumulation.
    ``probe``/``overlap``/``health`` as in ``half_step_ring``.

    ``in_kernel_gather`` (default on where legal) fuses each chunk's
    neighbor gather into the Gram kernel (``ops.tiled`` ``gather="fused"``
    — the rotated factor block is the kernel's DMA source), which also
    retires the per-ring-step zero-row append of the whole block.
    """
    from cfk_tpu.ops import quant
    from cfk_tpu.ops.pipeline import resolve_overlap
    from cfk_tpu.ops.tiled import (
        _entity_gram_chunk,
        default_tiled_gram_backend,
        resolve_gather_mode,
    )

    if health and probe is not None:
        raise ValueError("health probing and timing probes are exclusive")
    overlap = resolve_overlap(overlap)
    backend = gram_backend or default_tiled_gram_backend()
    _, _, nc, cap, t, h, e_c = chunks
    s = num_shards
    nt = cap // t
    k = fixed_local.shape[-1]
    # Quantize the ROTATING payload once, before the ring (ops.quant):
    # every ppermute then moves the bf16 block — or the (int8 codes,
    # f32 per-row scales) pair, a quarter of the bytes — and every Gram
    # consumes the quantized rows.  The int8 scale travels WITH its block
    # (indices are local to whichever block this shard currently holds),
    # folded into the weight channel per chunk — the canonical order.
    data, scale = quant.quantize_table(fixed_local, table_dtype)
    gather = resolve_gather_mode(
        in_kernel_gather, backend, cap, nt, t, e_c + 1, k,
        table_dtype=data.dtype,
    )
    tbl0 = (data,) if scale is None else (data, scale)
    int8 = scale is not None
    my = lax.axis_index(AXIS)
    perm = [(i, (i + 1) % s) for i in range(s)]
    slice_grams = _make_tiled_slice_grams(
        blk, cap=cap, nt=nt, e_c=e_c, t=t, k=k, backend=backend,
        gather=gather, int8=int8,
    )

    if probe == "exchange":  # transfers only; factors are a timing sink
        tbl = lax.fori_loop(
            0, s - 1,
            lambda r, f: jax.tree.map(
                lambda x: lax.ppermute(x, AXIS, perm), f
            ),
            tbl0,
        )
        return jnp.zeros((local_entities, k), jnp.float32) + jnp.sum(
            tbl[0].astype(jnp.float32)
        )

    def body(r, carry):
        acc_a, acc_b, tbl, bad = carry
        t_idx = (my - r) % s
        if health:
            bad = bad | _payload_nonfinite_flag(tbl)
        if probe == "compute":  # chunk loops only: never rotate the block
            acc_a, acc_b = slice_grams((acc_a, acc_b), tbl, t_idx)
            return acc_a, acc_b, tbl, bad
        (acc_a, acc_b), tbl = _ring_rotate(
            tbl, perm,
            lambda cur: slice_grams((acc_a, acc_b), cur, t_idx),
            overlap=overlap,
        )
        return acc_a, acc_b, tbl, bad

    a0 = _to_varying(
        jnp.zeros((local_entities + 1, k, k), jnp.float32), AXIS
    )
    b0 = _to_varying(jnp.zeros((local_entities + 1, k), jnp.float32), AXIS)
    bad0 = _to_varying(jnp.zeros((), jnp.int32), AXIS)
    acc_a, acc_b, tbl, bad = lax.fori_loop(
        0, s - 1, body, (a0, b0, tbl0, bad0)
    )
    if health:
        bad = bad | _payload_nonfinite_flag(tbl)
    acc_a, acc_b = slice_grams(
        (acc_a, acc_b), tbl, (my - (s - 1)) % s
    )
    # Like accum mode, the ring's accumulator lives across steps in HBM;
    # the fused knob gates the final fused reg+solve vs the split
    # ridge-add + dispatch.
    x = regularized_solve(
        acc_a[:local_entities], acc_b[:local_entities],
        blk["count"], lam, solver, fused=fused_epilogue,
        algo=reg_solve_algo,
    )
    return (x, bad) if health else x


def gathered_layout_trees(dataset: Dataset, config: ALSConfig,
                          weighted: bool = False):
    """Block trees + step kwargs for the all_gather-only layouts.

    Returns (mtree, utree, step_kw) for bucketed/segment/tiled datasets —
    the setup shared by the explicit and implicit sharded trainers — or
    None when the dataset uses padded rectangles (caller picks
    per-exchange).  ``weighted=True`` (the iALS trainer) ships the
    dense-stream weighted channels too; explicit ALS skips their ~1 GB
    dead upload at full Netflix.
    """
    bucketed = isinstance(dataset.movie_blocks, BucketedBlocks)
    segment = isinstance(dataset.movie_blocks, SegmentBlocks)
    tiled = isinstance(dataset.movie_blocks, TiledBlocks)
    if not (bucketed or segment or tiled):
        return None
    ring = config.exchange in ("ring", "hier_ring")
    if ring and not tiled:
        name = "bucketed" if bucketed else "segment"
        raise ValueError(
            f"{name} layout supports exchange='all_gather' only — the ring "
            "join needs the owner-shard-sorted entry stream the padded and "
            "tiled layouts have, and tiled strictly dominates "
            f"{name} at ring-relevant scales (PARITY.md 'Known intentional "
            "divergences' #5); build the tiled dataset with "
            "Dataset.from_coo(..., ring=True) or ring='auto'"
        )
    if tiled and config.exchange != "auto":
        # "auto" takes each half's ring flag as built (the builder chose
        # per side); the explicit exchanges require matching blocks.
        for name, blocks in (("movie", dataset.movie_blocks),
                             ("user", dataset.user_blocks)):
            if ring != blocks.ring:
                raise ValueError(
                    f"config.exchange={config.exchange!r} but the tiled "
                    f"{name}_blocks were built with ring={blocks.ring}; "
                    f"rebuild with Dataset.from_coo(..., layout='tiled', "
                    f"ring={ring})"
                )
    if bucketed:
        mtree, m_chunks = _bucketed_to_tree(dataset.movie_blocks)
        utree, u_chunks = _bucketed_to_tree(dataset.user_blocks)
    elif tiled:
        mtree = _tiled_to_tree(dataset.movie_blocks, weighted)
        utree = _tiled_to_tree(dataset.user_blocks, weighted)
        m_chunks = ("tiled", dataset.movie_blocks.mode) + dataset.movie_blocks.statics
        u_chunks = ("tiled", dataset.user_blocks.mode) + dataset.user_blocks.statics
    else:
        mtree = _segment_to_tree(dataset.movie_blocks)
        utree = _segment_to_tree(dataset.user_blocks)
        m_chunks = dataset.movie_blocks.statics
        u_chunks = dataset.user_blocks.statics
    step_kw = dict(
        m_chunks=m_chunks,
        u_chunks=u_chunks,
        m_local=dataset.movie_blocks.local_entities,
        u_local=dataset.user_blocks.local_entities,
        segment=segment,
        tiled=tiled,
    )
    if tiled:
        step_kw.update(
            m_ring=dataset.movie_blocks.ring,
            u_ring=dataset.user_blocks.ring,
        )
    return mtree, utree, step_kw


def use_check_vma(config: ALSConfig) -> bool:
    """shard_map's varying-mesh-axes checker guards collective placement
    (e.g. the ring path's pvary), so keep it on whenever possible.  The one
    case it must be off: interpret-mode pallas kernels (CPU tests), whose
    interpreted jaxprs mix invariant constants with varying operands.  On
    real TPU the compiled kernel carries an explicit vma tag and passes."""
    return config.solver != "pallas" or jax.default_backend() == "tpu"


def _zero_flag(half, prev=False):
    """Append an always-clean exchange flag to a non-ring half so every
    half has the ``(factors, bad)`` shape ``wrap_step(ring_flags=True)``
    expects (all_gather halves have no in-flight carry to corrupt; any
    non-finite output is caught by the step-level factor probe)."""
    if prev:
        return lambda fixed, prev_local, blk: (
            half(fixed, prev_local, blk),
            _to_varying(jnp.zeros((), jnp.int32), AXIS),
        )
    return lambda fixed, blk: (
        half(fixed, blk), _to_varying(jnp.zeros((), jnp.int32), AXIS)
    )


def make_training_step(
    mesh: Mesh,
    config: ALSConfig,
    mspecs,
    uspecs=None,
    *,
    m_chunks=None,
    u_chunks=None,
    m_local=None,
    u_local=None,
    segment=False,
    tiled=False,
    m_ring=False,
    u_ring=False,
    ring_probe=None,
    health_probe=False,
):
    """Build the jittable one-full-iteration SPMD step (solve M, then U).

    Returned ``step(u, m, mblocks, ublocks) -> (u, m)`` operates on
    row-sharded global arrays; collectives are explicit inside shard_map.
    The bucketed layout (``m_chunks`` given) all_gathers the fixed side and
    solves each width bucket of the local shard; the segment layout
    (``segment=True``; ``m_chunks`` is then the static scan-window hint)
    all_gathers the fixed side and segment-sums the local flat rating run.

    ``config.overlap`` selects the double-buffered (comm/compute overlapped)
    ring and chunk schedules — the default — or the serial reference
    schedule; ``ring_probe`` ("exchange"/"compute", timing-only) builds the
    split-measurement step the bench's overlap A/B uses.

    ``health_probe=True`` (the resilience sentinel) makes the step return
    ``(u, m, bad)``: ring halves fold per-rotation ``isfinite`` checks of
    the in-flight factor block into their carry, non-ring halves
    contribute an always-clean flag, and ``bad`` is the mesh-wide psum —
    the resilient loop fetches it on the health cadence.
    """
    dtype = jnp.dtype(config.dtype)
    if health_probe and ring_probe is not None:
        raise ValueError("health probing and timing probes are exclusive")
    if uspecs is None:
        uspecs = mspecs

    def flagged(half, prev=False):
        return _zero_flag(half, prev) if health_probe else half

    if config.algorithm == "als++":
        from cfk_tpu.ops.subspace import (
            als_pp_half_step,
            als_pp_half_step_bucketed,
        )

        alg = dict(block_size=config.block_size, sweeps=config.sweeps,
                   solver=config.solver,
                   in_kernel_gather=config.in_kernel_gather,
                   fused_epilogue=config.fused_epilogue,
                   reg_solve_algo=config.reg_solve_algo,
                   table_dtype=config.table_dtype)

        if m_chunks is not None:  # bucketed layout

            def pp_bkt(chunks, local):
                def solve(fixed_full, prev_local, blk, _gram):
                    return als_pp_half_step_bucketed(
                        fixed_full, prev_local, blk, chunks, local,
                        config.lam, **alg,
                    )

                return solve

            return wrap_step(
                mesh, config,
                flagged(gathered_half(pp_bkt(m_chunks, m_local),
                                      with_prev=True,
                                      table_dtype=config.table_dtype),
                        prev=True),
                flagged(gathered_half(pp_bkt(u_chunks, u_local),
                                      with_prev=True,
                                      table_dtype=config.table_dtype),
                        prev=True),
                mspecs, uspecs, carry_prev=True, ring_flags=health_probe,
            )

        def pp_padded(fixed_full, prev_local, blk, _gram):
            return als_pp_half_step(
                fixed_full, prev_local, blk["neighbor"], blk["rating"],
                blk["mask"], blk["count"], config.lam, **alg,
            )

        half = flagged(gathered_half(pp_padded, with_prev=True,
                                     table_dtype=config.table_dtype),
                       prev=True)
        return wrap_step(mesh, config, half, half, mspecs, uspecs,
                         carry_prev=True, ring_flags=health_probe)

    if tiled:  # tile-padded layout

        from cfk_tpu.ops.tiled import tiled_half_step

        def ring_half(chunks, local):
            ring_kw = dict(
                lam=config.lam, num_shards=config.num_shards,
                solver=config.solver, overlap=config.overlap,
                probe=ring_probe,
                fused_epilogue=config.fused_epilogue,
                health=health_probe,
                in_kernel_gather=config.in_kernel_gather,
                reg_solve_algo=config.reg_solve_algo,
                table_dtype=config.table_dtype,
            )

            def half(fixed_local, blk):
                if config.exchange == "hier_ring":
                    return half_step_tiled_ring_hier(
                        fixed_local, blk, chunks, local,
                        inner=resolve_ici_group(config), **ring_kw,
                    )
                return half_step_tiled_ring(
                    fixed_local, blk, chunks, local, **ring_kw,
                )

            return half

        def ag_half(chunks, local):
            def solve(fixed_full, blk, _gram):
                return tiled_half_step(
                    fixed_full, blk, chunks, local, config.lam,
                    solver=config.solver, overlap=config.overlap,
                    fused_epilogue=config.fused_epilogue,
                    in_kernel_gather=config.in_kernel_gather,
                    reg_solve_algo=config.reg_solve_algo,
                    table_dtype=config.table_dtype,
                )

            return flagged(gathered_half(
                solve, table_dtype=config.table_dtype))

        # Each half picks its exchange from how its blocks were built —
        # exchange="auto" mixes them (ring movie-half + all_gather
        # user-half at Netflix shape, the per-side memory optimum);
        # "ring"/"all_gather" build both sides the same way.
        return wrap_step(
            mesh, config,
            (ring_half if m_ring else ag_half)(m_chunks, m_local),
            (ring_half if u_ring else ag_half)(u_chunks, u_local),
            mspecs, uspecs, ring_flags=health_probe,
        )

    if segment:  # flat segment layout, all_gather exchange

        def seg_solve(statics, local):
            def solve(fixed_full, blk, _gram):
                return als_half_step_segment(
                    fixed_full, blk["neighbor"], blk["rating"], blk["mask"],
                    blk["seg"], blk["entity"], blk["ecount"], blk["gsizes"],
                    blk["cin"], blk["lseg"], local,
                    config.lam, statics=statics, solver=config.solver,
                    reg_solve_algo=config.reg_solve_algo,
                )

            return solve

        return wrap_step(
            mesh, config,
            flagged(gathered_half(seg_solve(m_chunks, m_local),
                                  table_dtype=config.table_dtype)),
            flagged(gathered_half(seg_solve(u_chunks, u_local),
                                  table_dtype=config.table_dtype)),
            mspecs, uspecs, ring_flags=health_probe,
        )

    if m_chunks is not None:  # bucketed layout, all_gather exchange

        def bkt_solve(chunks, local):
            def solve(fixed_full, blk, _gram):
                return als_half_step_bucketed(
                    fixed_full, blk, chunks, local, config.lam,
                    solver=config.solver, overlap=config.overlap,
                    reg_solve_algo=config.reg_solve_algo,
                    fused_epilogue=config.fused_epilogue,
                    in_kernel_gather=config.in_kernel_gather,
                    table_dtype=config.table_dtype,
                )

            return solve

        return wrap_step(
            mesh, config,
            flagged(gathered_half(bkt_solve(m_chunks, m_local),
                                  table_dtype=config.table_dtype)),
            flagged(gathered_half(bkt_solve(u_chunks, u_local),
                                  table_dtype=config.table_dtype)),
            mspecs, uspecs, ring_flags=health_probe,
        )

    if config.exchange == "all_gather":
        half_rect = functools.partial(
            half_step_allgather,
            lam=config.lam,
            solver=config.solver,
            table_dtype=config.table_dtype,
        )
    else:
        half_rect = functools.partial(
            half_step_ring,
            lam=config.lam,
            num_shards=config.num_shards,
            solver=config.solver,
            overlap=config.overlap,
            probe=ring_probe,
            fused_epilogue=config.fused_epilogue,
            health=health_probe,
            reg_solve_algo=config.reg_solve_algo,
            table_dtype=config.table_dtype,
        )

    # Factors are exchanged/stored in config.dtype (bfloat16 halves ICI bytes
    # and HBM); Gram contractions follow the storage dtype — native bf16 MXU
    # passes with float32 accumulation for bf16 factors, full-f32 "highest"
    # for float32 (see ops/solve.py _gram_compute_dtype).
    def half(fixed_local, blk):
        # Unified HBM budget → entities per chunk, derived from THIS
        # side's rectangle width (static inside the traced shard).
        return half_rect(
            fixed_local, blk["neighbor"], blk["rating"], blk["mask"],
            blk["count"],
            solve_chunk=config.padded_solve_chunk(blk["neighbor"].shape[-1]),
        )

    if config.exchange == "all_gather":
        half = flagged(half)
    return wrap_step(mesh, config, half, half, mspecs, uspecs,
                     ring_flags=health_probe)


def validate_sharded_dataset(dataset: Dataset, config: ALSConfig, mesh: Mesh) -> None:
    """Catch layout mistakes with actionable errors before XLA sees them."""
    s = config.num_shards
    if mesh.devices.size != s:
        raise ValueError(
            f"mesh has {mesh.devices.size} devices, config.num_shards={s}"
        )
    for name, blocks in (("movie", dataset.movie_blocks), ("user", dataset.user_blocks)):
        if blocks.padded_entities % s != 0:
            raise ValueError(
                f"{name}_blocks padded to {blocks.padded_entities} entities, not "
                f"divisible by num_shards={s}; rebuild the Dataset with "
                f"Dataset.from_coo(..., num_shards={s})"
            )
        if isinstance(blocks, (BucketedBlocks, SegmentBlocks, TiledBlocks)) and blocks.num_shards != s:
            layout = ("bucketed" if isinstance(blocks, BucketedBlocks)
                      else "segment" if isinstance(blocks, SegmentBlocks)
                      else "tiled")
            raise ValueError(
                f"{name}_blocks were built for num_shards={blocks.num_shards} "
                f"but config.num_shards={s}; their row/segment indices are "
                f"shard-local, so rebuild with Dataset.from_coo(..., "
                f"num_shards={s}, layout='{layout}')"
            )


def _config_under_plan(config, exec_plan):
    """The config the sharded step builders should execute: the plan's
    ``half_step_kwargs`` written back over the knob fields.  For
    pinned/default configs the sentinels round-trip to the exact same
    values (bit-identical routing); a cache-hit autotune plan's free-knob
    choices thread like the single-device trainers' seam."""
    import dataclasses as _dc

    kn = exec_plan.half_step_kwargs(config)
    return _dc.replace(
        config,
        overlap=(config.overlap if kn["overlap"] is None
                 else bool(kn["overlap"])),
        fused_epilogue=kn["fused_epilogue"],
        in_kernel_gather=kn["in_kernel_gather"],
        reg_solve_algo=kn["reg_solve_algo"],
        solver=kn["solver"],
        table_dtype=kn["table_dtype"],
    )


def _snapshot_to_host(u, m, **attrs):
    """Allgather-to-host under a ``train/host_gather`` span — the
    expensive host-side edge of a sharded save/snapshot cadence.  The
    resilient loop's ``snapshot_fn`` seam calls it bare; ``save_fn``
    passes ``what="save"``/``i=`` attrs."""
    from cfk_tpu.telemetry import span as _span

    attrs.setdefault("what", "snapshot")
    with _span("train/host_gather", **attrs):
        return to_host(u), to_host(m)


def _sharded_resilient_loop(
    manager, *, model, dataset, config, mesh, dtype, init_fn, make_raw_step,
    mtree, utree, metrics, checkpoint_every, health, fault_injector,
    resume_fn, save_meta, preemption_guard=None, watchdog=None,
    plan_provenance=None,
):
    """Bind the resilient loop's device↔host boundary to a 1-D mesh.

    Shared by the explicit and implicit sharded trainers: snapshots
    process_allgather to host, restores re-shard rows, saves are
    process-0-gated (the gather runs on every process — the collectives
    must pair up — but only rank 0 touches the store, async via the
    manager's writer thread), and escalation overrides rebuild the jitted
    step from a ``dataclasses.replace``d config (λ bump / split epilogue
    are jit-statics, so each rung re-traces).  ``preemption_guard`` /
    ``watchdog`` thread straight into the resilient loop: every process
    polls the guard at the same iteration boundary so the emergency save's
    gather collectives stay in lockstep, and rank 0 writes the manifest.
    """
    import dataclasses as _dc

    from cfk_tpu.resilience.loop import resilient_train_loop, save_checkpoint
    from cfk_tpu.resilience.policy import Overrides, policy_from_config

    def make_step(ov):
        cfg = config
        want = (ov.lam, ov.fused_epilogue,
                ov.reg_solve_algo or config.reg_solve_algo)
        if want != (config.lam, config.fused_epilogue,
                    config.reg_solve_algo):
            cfg = _dc.replace(
                config, lam=ov.lam, fused_epilogue=ov.fused_epilogue,
                reg_solve_algo=ov.reg_solve_algo or config.reg_solve_algo,
            )
        step = jax.jit(make_raw_step(cfg), donate_argnums=(0, 1))
        return lambda u, m: step(u, m, mtree, utree)

    def restore_fn(hu, hm):
        return (
            shard_rows(mesh, np.asarray(hu).astype(dtype)),
            shard_rows(mesh, np.asarray(hm).astype(dtype)),
        )

    def save_fn(done, u, m):
        # Multi-process: every host gathers (cheap, factors are [E, k])
        # but only process 0 writes the checkpoint dir — async, so the
        # step loop never waits for serialize+fsync+rename.  The gathered
        # pair doubles as the resilient loop's rollback anchor.
        uh, mh = _snapshot_to_host(u, m, i=done, what="save")
        if jax.process_index() == 0:
            meta = save_meta
            if plan_provenance is not None:
                # Re-read per save so mid-run plan transitions (rungs,
                # backend outages) appear in subsequent manifests.
                meta = dict(save_meta, **plan_provenance.as_meta())
            save_checkpoint(manager, done, uh, mh, meta=meta)
        return uh, mh

    # Eviction must be a fleet-wide agreement: SIGTERM delivery is racy
    # against iteration boundaries, so each boundary allgather-maxes the
    # per-process flags — any signalled process makes EVERY process run
    # the emergency save at that same boundary.  Only armed (and only a
    # collective) when a guard exists; symmetric across processes because
    # every worker passes the same arguments.
    evict_sync_fn = None
    if preemption_guard is not None and jax.process_count() > 1:
        from jax.experimental import multihost_utils as _mh

        def evict_sync_fn(local: bool) -> bool:
            flags = _mh.process_allgather(
                np.asarray(1 if local else 0, np.int32)
            )
            return bool(np.max(np.asarray(flags)) > 0)

    return resilient_train_loop(
        manager,
        model=model,
        rank=config.rank,
        num_iterations=config.num_iterations,
        u_shape=(dataset.user_blocks.padded_entities, config.rank),
        m_shape=(dataset.movie_blocks.padded_entities, config.rank),
        dtype=dtype,
        init_fn=init_fn,
        make_step=make_step,
        base_overrides=Overrides(
            lam=config.lam, fused_epilogue=config.fused_epilogue
        ),
        metrics=metrics,
        checkpoint_every=checkpoint_every,
        health=health,
        policy=policy_from_config(config),
        fault_injector=fault_injector,
        snapshot_fn=_snapshot_to_host,
        restore_fn=restore_fn,
        save_fn=save_fn,
        resume_fn=resume_fn,
        num_shards=config.num_shards,
        preemption_guard=preemption_guard,
        watchdog=watchdog,
        evict_sync_fn=evict_sync_fn,
        plan_provenance=plan_provenance,
    )


def train_als_sharded(
    dataset: Dataset,
    config: ALSConfig,
    mesh: Mesh,
    *,
    checkpoint_manager=None,
    checkpoint_every: int = 1,
    metrics=None,
    fault_injector=None,
    preemption_guard=None,
    watchdog=None,
) -> ALSModel:
    """Multi-device ALS-WR over a 1-D mesh; semantics match ``train_als``.

    With a ``CheckpointManager``, factors are saved every ``checkpoint_every``
    completed iterations and training resumes from the latest step on restart
    (the explicit form of the reference's never-read per-iteration topic
    journal — SURVEY.md §5 checkpoint/resume).  ``config.health_check_every``
    arms the sentinel: the factor probe is fetched on its cadence and the
    ring half-steps fold per-rotation exchange checks into their carries
    (``make_training_step(health_probe=True)``); a trip rolls back to the
    last good checkpoint and escalates (``cfk_tpu.resilience``).
    """
    from cfk_tpu.config import apply_overlap_xla_flags, enable_compile_cache
    from cfk_tpu.resilience.loop import validate_cadence
    from cfk_tpu.resilience.sentinel import health_from_config

    from cfk_tpu.plan import plan_for_config

    # Before the first compile (ISSUE 13): warm-start compile caching.
    enable_compile_cache(getattr(config, "compile_cache_dir", None))
    s = config.num_shards
    health = health_from_config(config)
    validate_cadence(checkpoint_every, health)
    apply_overlap_xla_flags(config)
    validate_sharded_dataset(dataset, config, mesh)
    exec_plan, plan_prov = plan_for_config(
        config,
        num_users=dataset.user_map.num_entities,
        num_movies=dataset.movie_map.num_entities,
        nnz=max(int(dataset.movie_blocks.count.sum()), 1),
    )
    # The sharded step builders read knobs off the config object, so the
    # plan seam is applied by rebuilding the config from the plan's
    # half_step_kwargs — identical for pinned/default configs (the
    # sentinels round-trip), and the manifest provenance can never attest
    # to a plan the execution ignored.
    config = _config_under_plan(config, exec_plan)

    if exec_plan.offload_tier == "host_window":
        # Out-of-core tier, sharded (ISSUE 12): the per-shard budget
        # predicate said resident tables cannot fit one device (or the
        # config pinned the tier) — training runs through the sharded
        # windowed host-offload driver, bit-exact vs THIS resident path
        # (per-shard staged windows under the all_gather scan or the
        # ring/hier_ring visit schedules; tests/test_offload_sharded.py).
        unsupported = [
            name for name, v in (
                ("checkpoint_manager", checkpoint_manager),
                ("fault_injector", fault_injector),
                ("preemption_guard", preemption_guard),
                ("watchdog", watchdog),
            ) if v is not None
        ]
        if unsupported:
            raise NotImplementedError(
                f"offload_tier='host_window' does not support "
                f"{unsupported} yet — the windowed driver keeps factors "
                "in host stores (see cfk_tpu/offload/windowed.py; "
                "window-level fault injection uses its window_faults=)"
            )
        from cfk_tpu.offload.windowed import train_als_host_window
        from cfk_tpu.utils.metrics import Metrics as _Metrics

        metrics = metrics if metrics is not None else _Metrics()
        metrics.note("plan", plan_prov.summary())
        # Config-threading ≡ half_step_kwargs for the windowed driver:
        # _config_under_plan already wrote the plan's knobs back over the
        # config fields, so execution cannot diverge from the provenance.
        return train_als_host_window(
            dataset, config, metrics=metrics, plan_provenance=plan_prov,
        )

    gathered = gathered_layout_trees(dataset, config)
    stats_init = gathered is not None  # bucketed/segment: init from stats
    step_kw = {}
    if gathered is not None:
        mtree, utree, step_kw = gathered
    elif config.exchange == "all_gather":
        mtree = _padded_to_tree(dataset.movie_blocks)
        utree = _padded_to_tree(dataset.user_blocks)
    else:
        coo = dataset.coo_dense
        mtree = _ring_to_tree(
            build_ring_blocks(
                coo.movie_raw, coo.user_raw, coo.rating,
                dataset.movie_map.num_entities, dataset.user_map.num_entities,
                num_shards=s, pad_multiple=config.pad_multiple,
            )
        )
        utree = _ring_to_tree(
            build_ring_blocks(
                coo.user_raw, coo.movie_raw, coo.rating,
                dataset.user_map.num_entities, dataset.movie_map.num_entities,
                num_shards=s, pad_multiple=config.pad_multiple,
            )
        )

    mtree = shard_rows(mesh, mtree)
    utree = shard_rows(mesh, utree)

    from cfk_tpu.transport.checkpoint import resume_state_synced

    dtype = jnp.dtype(config.dtype)

    def init_fn():
        # Init outside shard_map, drawn at the REAL entity count (threefry
        # output depends on the draw shape, so drawing at the shard-count-
        # padded length would make the init a function of num_shards — the
        # old 4-shard tiled mismatch); pad rows are zero either way.
        key = jax.random.PRNGKey(config.seed)
        init_kw = dict(
            rank=config.rank,
            num_entities=dataset.user_blocks.num_entities,
        )
        if stats_init:
            u = jax.jit(
                init_factors_stats, static_argnames=("rank", "num_entities")
            )(
                key,
                jnp.asarray(dataset.user_blocks.rating_sum),
                jnp.asarray(dataset.user_blocks.count),
                **init_kw,
            ).astype(dtype)
        else:
            u = jax.jit(
                init_factors, static_argnames=("rank", "num_entities")
            )(
                key,
                jnp.asarray(dataset.user_blocks.rating),
                jnp.asarray(dataset.user_blocks.mask),
                jnp.asarray(dataset.user_blocks.count),
                **init_kw,
            ).astype(dtype)
        u = shard_rows(mesh, u)
        m = shard_rows(
            mesh,
            np.zeros((dataset.movie_blocks.padded_entities, config.rank), dtype),
        )
        return u, m

    from cfk_tpu.utils.metrics import Metrics

    metrics = metrics if metrics is not None else Metrics()
    metrics.note("plan", plan_prov.summary())
    u, m = _sharded_resilient_loop(
        checkpoint_manager,
        model="als",
        dataset=dataset,
        config=config,
        mesh=mesh,
        dtype=dtype,
        init_fn=init_fn,
        make_raw_step=lambda cfg: make_training_step(
            mesh, cfg, _tree_specs(mtree), _tree_specs(utree),
            health_probe=health is not None, **step_kw
        ),
        mtree=mtree,
        utree=utree,
        metrics=metrics,
        checkpoint_every=checkpoint_every,
        health=health,
        fault_injector=fault_injector,
        preemption_guard=preemption_guard,
        watchdog=watchdog,
        resume_fn=lambda: resume_state_synced(
            checkpoint_manager,
            rank=config.rank,
            model="als",
            num_iterations=config.num_iterations,
            u_shape=(dataset.user_blocks.padded_entities, config.rank),
            m_shape=(dataset.movie_blocks.padded_entities, config.rank),
            num_shards=config.num_shards,
        ),
        save_meta={
            "rank": config.rank,
            "exchange": config.exchange,
            "model": "als",
            "num_shards": config.num_shards,
        },
        plan_provenance=plan_prov,
    )

    return ALSModel(
        user_factors=u,
        movie_factors=m,
        num_users=dataset.user_map.num_entities,
        num_movies=dataset.movie_map.num_entities,
    )


# -- item-axis sharded top-K serving (ISSUE 8) -------------------------------

def serve_topk_sharded(
    mesh: Mesh,
    u,  # [B, k] user-factor batch (replicated)
    table,  # [M_pad, k] item table, M_pad a multiple of shards·tile_m
    scale,  # [M_pad] f32 int8 per-row scales, or None
    seen_tiles,  # SeenTiles (serve_seen_tiles_sharded), or None
    *,
    k_top: int,
    num_movies: int,
    tile_m: int = 512,
):
    """Item-axis sharded score+top-K: (scores [B, K], movie rows [B, K],
    counts [shards, 5] — each shard's selection rounds and exclusion chunks,
    the tiles that ran any of either and the tiles it completed,
    ``topk_scores_counted``'s, left on their chips unsummed).

    The serving analog of the half-steps' exchange, with the direction
    reversed: the ITEM table is row-sharded over the mesh, the [B, k]
    request batch is replicated, each shard runs the streaming score+top-K
    kernel over its own table slice (its global row base rides the
    kernel's scalar-prefetched ``row_offset``), and ONE all_gather of the
    per-shard [B, K] selections — [B, shards·K] — feeds a final
    ``lax.top_k`` merge.  No dense score block ever crosses a shard
    boundary; the exchange is O(B·shards·K), independent of num_movies.
    The program takes the operands there are and no others: no scales
    without an int8 table, no rectangle without exclusion.

    Bit-equality with the single-shard kernel holds by construction:
    per-element score dots are identical (same k-order contraction), and
    the merge concatenates shards in ring order = ascending global tile
    order, which is exactly the order the single-shard carry folds tiles —
    so ties resolve identically (``tests/test_serving.py`` pins
    multi-shard == single-shard bit-exactly).
    """
    shards = mesh.devices.size
    m_pad = table.shape[0]
    if m_pad % (shards * tile_m) != 0:
        raise ValueError(
            f"table rows {m_pad} not divisible by shards×tile_m "
            f"({shards}×{tile_m}); pad with serving.engine.pad_table"
        )
    fn = _serve_topk_sharded_fn(
        mesh, m_pad // shards, scale is not None, seen_tiles is not None,
        k_top, num_movies, tile_m,
    )
    ops = [u, table]
    if scale is not None:
        ops.append(scale.astype(jnp.float32))
    if seen_tiles is not None:
        ops.append(seen_tiles)
    return fn(*ops)


@functools.lru_cache(maxsize=64)
def _serve_topk_sharded_fn(mesh, rows_per_shard, has_scale, has_seen,
                           k_top, num_movies, tile_m):
    """Jitted shard_map for one (mesh, shapes-class, K) serving config —
    cached so a live server's request stream reuses compiled programs
    instead of re-tracing the shard_map per call (the engine's pow2
    bucketing keeps the distinct key count small).  Operands: ``u``, the
    table, then the scales and the rectangle where the key says so."""
    from cfk_tpu.serving.engine import note_trace
    from cfk_tpu.serving.topk_kernel import topk_scores_counted

    def shard_fn(u_rep, tbl, *rest):
        rest = list(rest)
        sc = rest.pop(0) if has_scale else None
        seen = rest.pop(0) if has_seen else None
        off = lax.axis_index(AXIS).astype(jnp.int32) * rows_per_shard
        # the scope names the scorer's custom call in a device trace
        # (``_topk_shard_call.<n>``): without it the call would read
        # ``shard_map.<n>``, beside the one-device entry's ``_topk_call``
        with jax.named_scope("_topk_shard_call"):
            v, ids, counts = topk_scores_counted(
                u_rep, tbl, sc, seen,
                k_top=k_top, num_movies=num_movies, tile_m=tile_m,
                row_offset=off,
            )
        cat_v = lax.all_gather(v, AXIS, axis=1, tiled=True)
        cat_i = lax.all_gather(ids, AXIS, axis=1, tiled=True)
        mv, pos = lax.top_k(cat_v, k_top)
        return (mv[None], jnp.take_along_axis(cat_i, pos, axis=1)[None],
                counts[None])

    # Every shard merges the same all_gather'd candidates, so the merged
    # selections are equal on all of them — which jax's typing cannot say
    # (``lax.all_gather`` is varying → varying, and nothing public casts
    # varying → invariant).  So they come back stacked over the mesh axis,
    # typed as what they are to the checker, and the first is the answer.
    sharded = _compat_shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(AXIS)) + (P(AXIS),) * (has_scale + has_seen),
        out_specs=(P(AXIS), P(AXIS), P(AXIS)),
    )

    def _topk_shard_call(*ops):
        note_trace()
        mv, ids, counts = sharded(*ops)
        return mv[0], ids[0], counts

    return jax.jit(_topk_shard_call)


def serve_seen_tiles_sharded(mesh: Mesh, cells, seen_tiles, *, shape,
                             tile_m: int):
    """The [NT, B, W] exclusion rectangle of ``serve_topk_sharded`` and its
    [NT] hits (a ``SeenTiles``), each shard's NT / shards tiles built on
    the chip that scans them: ``cells`` (the batch's whole cell list as
    ``engine._seen_chunks`` pads it to a rung of ``SEEN_PIECE_RUNGS``,
    replicated) goes to every chip, each keeps its own tiles' cells in one
    run of one program, and none ever holds the other chips' slices.
    ``seen_tiles`` None starts the rectangle; one that an earlier run went
    into (a list past the top rung) is donated and takes this one on
    top."""
    fn = _serve_seen_tiles_sharded_fn(mesh, tuple(shape), tile_m,
                                      seen_tiles is None)
    return fn(cells) if seen_tiles is None else fn(cells, seen_tiles)


@functools.lru_cache(maxsize=64)
def _serve_seen_tiles_sharded_fn(mesh, shape, tile_m, fresh):
    from cfk_tpu.serving.engine import note_trace
    from cfk_tpu.serving.topk_kernel import SeenTiles, scatter_seen_cells

    nt, b, width = shape
    shards = mesh.devices.size
    if nt % shards != 0:
        raise ValueError(f"{nt} tiles not divisible by {shards} shards")
    per = nt // shards

    def shard_fn(cells, *rect):
        # the tile index rebased by the shard's first tile; another
        # shard's cell goes past the slice, where the scatter drops it —
        # never below zero, where ``.at[]`` would wrap round
        tile = cells[0] - lax.axis_index(AXIS).astype(jnp.int32) * per
        tile = jnp.where((tile >= 0) & (tile < per), tile, per)
        local = jnp.concatenate(
            [tile[None], _match_varying(cells[1:], tile)])
        start = rect[0] if rect else SeenTiles(
            _match_varying(jnp.full((per, b, width), tile_m, jnp.int32),
                           tile),
            _match_varying(jnp.zeros(per, jnp.int32), tile))
        return scatter_seen_cells(local, start, shape=(per, b, width),
                                  tile_m=tile_m)

    sharded = _compat_shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(),) if fresh else (P(), P(AXIS)),
        out_specs=P(AXIS),
    )

    def _seen_tiles_shard_call(*ops):
        note_trace()
        return sharded(*ops)

    return jax.jit(_seen_tiles_shard_call,
                   donate_argnums=() if fresh else (1,))
