"""Bucketed-layout kernel port — the PR 2/4 machinery on the width classes.

The pre-ledger record (PERF.md §8) had the bucketed layout as the worst
remaining roofline gap: its half-steps still ran the
original XLA schedule — materialized `fixed[nb]` gather, whole-rectangle
Gram einsum, separate batched solve — while the tiled layout got in-kernel
DMA gathers (PR 4) and the fused Gram+solve epilogue (PR 2).

The port is an ADAPTER, not a new kernel: a width bucket is a [rows, width]
rectangle of power-of-two width, and flattening it with ``tile_rows =
width`` makes it EXACTLY the tiled stream kernels' shape with one tile per
entity — ``seg = arange(rows)``, no chunk-straddling carry.  Per width
class (the ISSUE's "per-width-class grids") the bucket walk then calls

  - ``gram_solve_tiles_gather_pallas``  (gather=fused + fused epilogue:
    scalar-prefetched indices, double-buffered VMEM row DMA, in-VMEM
    ridge + lane-vectorized elimination — neither the gathered stream nor
    the [rows, k, k] A-batch touches HBM), or
  - ``gram_tiles_gather_pallas`` + the one-pass reg+solve kernel (split
    epilogue), or the same pair fed by an XLA-materialized stream
    (gather=xla) — the A/B axes toggle exactly what they toggle in tiled
    land, and every route runs the canonical ``g = table[nb]·wt`` +
    per-tile Gram ops.  On the CPU the routes that take a kernel's XLA
    twin (gather=fused under either epilogue) agree to the bit; gather=xla
    with the split epilogue feeds the stream to ``gram_tiles_pallas``,
    whose CPU route is the kernel body under the Pallas interpreter
    (``ops/pallas/interpret.py``), and differs from them by float32
    summation order (7.2e-7 absolute per half-step at toy size:
    ``tests/test_quant_table.py``).

One-tile-per-entity also means the emulation twin's per-tile einsum
``ntk,ntl->nkl`` is the legacy whole-rectangle ``epk,epl->ekl`` contraction
(equal to the bit on XLA:CPU where measured; two programs all the same, so
the tests hold them to round-off).  The implicit (iALS) port uses the
tiled layout's sqrt reparameterization (one gs = √aw·f stream instead of
the asymmetric (c−1)-premultiplied pair), which changes last-bit rounding
vs the legacy formulation — the same accepted trade the tiled iALS path
made in round 5.

Buckets whose width cannot tile (width < 16 — Mosaic's sublane alignment)
or whose flattened piece exceeds the scalar-prefetch SMEM budget keep the
legacy XLA schedule; they are the narrow tail of the byte distribution.

Quantized tables (``ops.quant``): the kernels read the bf16/int8 table
directly, with the int8 per-row dequant scale folded into the premultiply
weight (the canonical order); the legacy fallback consumes the
``gather_operand_view`` (whole-table dequant) so both routes see the same
values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from cfk_tpu.ops import quant

# VMEM row budget per kernel grid step: group_tiles·width rows double-
# buffered.  4096 rows × k=128 × 4 B × 2 buffers ≈ 4 MB — comfortable
# next to the fused epilogue's scratch.
_GROUP_ROWS = 4096


def bucket_port_supported(rows: int, width: int, k: int) -> bool:
    """Can this width class run the tiled-kernel adapter at all?

    Width must be 16-row-tileable (Mosaic sublane alignment — the same
    gate ``in_kernel_gather_supported`` applies to tile_rows) and one
    single-row piece must fit the scalar-prefetch SMEM budget.  Refused
    classes keep the legacy XLA schedule — same math, the measured-slow
    path — never a compile failure.
    """
    from cfk_tpu.ops.pallas.gram_kernel import gather_prefetch_fits

    if width < 16 or width % 16:
        return False
    return gather_prefetch_fits(width, 3)


def _sub_rows(rows: int, width: int, k: int, fused: bool,
              algo: str | None) -> int:
    """Rows per kernel call: the largest power-of-two piece whose
    flattened entry count passes the SMEM gate and whose segment count
    passes the fused epilogue's scratch gate (fused) or the split Gram
    kernels' resident-output cap (the same 96 MB ``ops.tiled`` falls back
    on).  The bucket is
    row-padded to a multiple and lax.map'd — each entity is wholly inside
    its own row, so pieces need no cross-piece accumulation."""
    from cfk_tpu.ops.pallas.gram_kernel import (
        fused_gram_solve_supported,
        gather_prefetch_fits,
    )

    sub = 1
    while True:
        nxt = sub * 2
        if nxt > rows:
            break
        if not gather_prefetch_fits(nxt * width, nxt + 2):
            break
        if fused and not fused_gram_solve_supported(nxt, k, algo):
            break
        if not fused and 2 * nxt * k * (k + 1) * 4 > (96 << 20):
            break  # the split kernels' resident (A, b) output cap
        sub = nxt
    return sub


def resolve_bucket_modes(fused_epilogue, in_kernel_gather, solver,
                         rows: int, width: int, k: int, lam,
                         algo: str | None, *,
                         table_dtype) -> tuple[bool, str] | None:
    """Static gating of the ported bucket piece.

    Returns (fused, gather) — ``None`` keeps the legacy XLA schedule.
    Delegates to the ONE shared mode resolver in ``cfk_tpu.plan.registry``
    (``resolve_gather_mode``/``resolve_fused_chunk_lam`` — the same gates
    the tiled chunk bodies and both ring half-steps run, including the
    kernel registry's backend-availability consult): the gather knob picks
    who fetches the rows (kernel DMA vs XLA stream), the fused knob
    whether the ridge+solve runs inside the Gram kernel's VMEM residency
    (pallas solver + a concretizable λ; ``lam=None`` is the iALS matrix
    mode, whose λ rides inside the shared reg matrix).  The duplicated
    copy of these gates this function used to carry is gone (ISSUE 9).
    """
    from cfk_tpu.plan.registry import (
        resolve_fused_chunk_lam,
        resolve_gather_mode,
    )

    if not bucket_port_supported(rows, width, k):
        return None
    gather = resolve_gather_mode(
        in_kernel_gather, "pallas", width, 3, width, 2, k,
        table_dtype=table_dtype,
    )
    lam_f = resolve_fused_chunk_lam(
        fused_epilogue, solver, k, 1, "pallas",
        0.0 if lam is None else lam, implicit=lam is None, algo=algo,
    )
    return lam_f is not None, gather


def _xla_stream(table, nb_flat, wt_flat):
    """The gather=xla route's materialized stream — the numerically
    identical ops the DMA gather's emulation twin runs (zero-row append,
    gather, cast, single premultiply), so the two gather modes stay
    bit-identical."""
    from cfk_tpu.compat import emulate_in_kernel_gather
    from cfk_tpu.ops.solve import _gram_compute_dtype

    ct, _ = _gram_compute_dtype(table)
    return emulate_in_kernel_gather(table, nb_flat, wt_flat, ct)


def bucket_gram_solve(
    table: jax.Array,  # [F, k] gather table (f32 / bf16 / int8 codes)
    scale: jax.Array | None,  # [F] int8 per-row dequant scales
    nb: jax.Array,  # [rows, width] int32 neighbor indices (< F)
    wt: jax.Array,  # [rows, width] premultiply (mask / √aw·mask)
    rt: jax.Array,  # [rows, width] b-side coefficients (0 at padding)
    reg,  # [rows] counts (diag) or [k, k] shared matrix (iALS)
    *,
    lam: float,
    reg_mode: str,
    solver: str,
    fused: bool,
    gather: str,
    algo: str | None,
) -> jax.Array:
    """One ported width-class piece: flatten to the tile stream, run the
    tiled kernels per sub-piece, return the solved [rows, k] factors."""
    from cfk_tpu.ops.pallas.gram_kernel import (
        gram_solve_tiles_gather_pallas,
        gram_solve_tiles_pallas,
        gram_tiles_gather_pallas,
        gram_tiles_pallas,
    )
    from cfk_tpu.ops.solve import (
        _match_varying,
        regularized_solve,
        regularized_solve_matrix,
    )

    rows, width = nb.shape
    k = table.shape[-1]
    wt = quant.fold_scale(wt, scale, nb)
    sub = _sub_rows(rows, width, k, fused, algo)
    pad = (-rows) % sub
    if pad:
        zrow = lambda x: jnp.pad(x, ((0, pad), (0, 0)))
        nb, wt, rt = zrow(nb), zrow(wt), zrow(rt)
        if reg_mode == "diag":
            reg = jnp.pad(reg, ((0, pad),))
    n_pieces = (rows + pad) // sub
    seg = _match_varying(jnp.arange(sub, dtype=jnp.int32), nb)
    lseg = _match_varying(jnp.asarray(sub - 1, jnp.int32), nb)
    gt = max(1, _GROUP_ROWS // width)
    kw = dict(num_segments=sub, tile_rows=width, group_tiles=gt)

    def piece(args):
        nb_p, wt_p, rt_p, reg_p = args
        nb_f = nb_p.reshape(-1)
        wt_f = wt_p.reshape(-1)
        rt_f = rt_p.reshape(-1)
        if fused:
            if gather == "fused":
                x, _, _ = gram_solve_tiles_gather_pallas(
                    table, nb_f, wt_f, rt_f, seg, reg_p, lseg,
                    reg_mode=reg_mode, lam=lam, algo=algo, **kw,
                )
            else:
                x, _, _ = gram_solve_tiles_pallas(
                    _xla_stream(table, nb_f, wt_f), rt_f, seg, reg_p, lseg,
                    reg_mode=reg_mode, lam=lam, algo=algo, **kw,
                )
            return x
        if gather == "fused":
            a, b = gram_tiles_gather_pallas(
                table, nb_f, wt_f, rt_f, seg, **kw,
            )
        else:
            a, b = gram_tiles_pallas(
                _xla_stream(table, nb_f, wt_f), rt_f, seg, **kw,
            )
        # fused=True pins the one-pass reg+solve kernel (where the solver
        # allows), exactly like the tiled chunk bodies' split path — the
        # fused A/B axis toggles only the Gram→HBM→solve round-trip.
        if reg_mode == "diag":
            return regularized_solve(a, b, reg_p, lam, solver, fused=True,
                                     algo=algo)
        return regularized_solve_matrix(a, b, reg_p, solver, fused=True,
                                        algo=algo)

    if n_pieces == 1:
        return piece((nb, wt, rt, reg))[:rows]
    nb_s = nb.reshape(n_pieces, sub, width)
    wt_s = wt.reshape(n_pieces, sub, width)
    rt_s = rt.reshape(n_pieces, sub, width)
    if reg_mode == "diag":
        reg_s = reg.reshape(n_pieces, sub)
    else:
        reg_s = jnp.broadcast_to(reg, (n_pieces,) + reg.shape)
    # The barrier keeps each piece's kernels out of XLA's output fusions:
    # fused with the loop's stacked-output update, a Mosaic call is held to
    # the default 16 MiB scoped-VMEM limit instead of its own
    # ``vmem_limit_bytes``, and the v5e compiler then refuses it.
    x = lax.map(lambda args: lax.optimization_barrier(piece(args)),
                (nb_s, wt_s, rt_s, reg_s))
    return x.reshape(n_pieces * sub, k)[:rows]


_SQRT_WEIGHT_EPS = 1e-12  # the tiled reparameterization's clamp — see
# ops.tiled.ials_tiled_half_step for the exactness argument at aw = 0


def ials_reparam(rt, mk, alpha):
    """The sqrt reparameterization for the implicit port: one weighted
    stream gs = √(α·r)·f (A = Σ α·r·f fᵀ exactly) with the b-coefficient
    rescaled to c/√aw, the ε-clamp keeping aw = 0 entries exact in b, and
    the 0/1 mask re-applied so padding survives the clamp (it is the DMA
    route's padding mask).  Returns (wt, rt_scaled)."""
    aw = jnp.sqrt(jnp.maximum(alpha * rt, _SQRT_WEIGHT_EPS))
    return aw * mk, (1.0 + alpha * rt) * mk / aw
