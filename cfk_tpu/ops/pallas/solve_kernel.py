"""Pallas TPU kernel: batched small-SPD solve via lane-vectorized Gauss-Jordan.

The framework's FLOP hot spot after the Gram matmuls is solving E independent
k×k SPD systems (k = rank, 5..128; E = entities per shard).  XLA lowers
``jnp.linalg.cholesky`` + two ``triangular_solve``s to sequential custom
calls that vectorize poorly for small k.  This kernel instead runs
Gauss-Jordan elimination with the *batch* dimension laid out along the TPU's
128-wide vector lanes: every scalar step of the textbook algorithm becomes a
[k, T] or [k, k, T] VPU op over T systems at once.  No pivoting — the
systems are SPD with a λ·n ≥ λ ridge (``regularized_solve``), so diagonal
pivots stay safely positive.

Layout contract: A is passed [k, k, E] and b [k, E] (batch LAST, so tiles
sit in the lane dimension).  The dispatcher (``ops.solve.dispatch_spd_solve``)
pays an explicit transpose from the batch-first Gram layout — measured at
0.024 s/iter of the 0.82 full-Netflix iteration (round-3 profile), i.e.
~3%: emitting batch-last from the Gram kernel would force its per-entity
flush onto dynamic LANE offsets (lane-shift ops per flush), a worse trade
than the one bulk transpose, so the transpose stays by choice now rather
than as a follow-up.

Cost: ≈ 2k³ FLOPs per system (vs k³/3 for Cholesky) — a 6× FLOP overhead
traded for full lane utilization, a win while the custom-call path is
latency-bound on small k.  The fully-unrolled k-loop holds [k, k, TILE]
temporaries in VMEM, which bounds the supported rank: k ≤ PALLAS_MAX_RANK
(= 64 → A tile 2 MiB); larger ranks must use the cholesky backend (the
dispatcher falls back automatically).  Falls back to interpret mode off-TPU
so tests run on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from cfk_tpu.compat import typeof_vma
from cfk_tpu.ops.pallas.interpret import resolve_interpret
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# VMEM budget cap: the kernel keeps [k, k, _LANES] float32 blocks live
# through an unrolled k-step elimination; k=64 → 2 MiB per buffer. k=128
# was measured (raising Mosaic's scoped-VMEM allowance to fit the 8 MiB
# A-block): it compiles but runs ~10× SLOWER than XLA's cholesky there —
# the fully-unrolled elimination is VPU-bound at O(k³) while cholesky's
# custom-call overhead amortizes at larger k. The crossover favors this
# kernel only up to k = 64, so the cap stays.
PALLAS_MAX_RANK = 64
# The LU variant does k³/3 VPU work (vs Gauss-Jordan's ~3k³ chain of
# fma+select over the full matrix), which moves its crossover past
# k = 128: one direct LU beats the blocked Schur composition of k=64 GJ
# kernels AND skips Schur's XLA-level [E,k,k] transposes.
LU_MAX_RANK = 128


def gj_solve_lanes(a, b, *, k: int):
    """In-register Gauss-Jordan over lanes: a [k,k,T], b [k,T] → x [k,T].

    The elimination core shared by the standalone solve kernels and the
    fused Gram+solve epilogue (``ops.pallas.gram_kernel``).  Row-index
    planes come from in-kernel iota (pallas kernels cannot capture array
    constants, and Mosaic needs multi-dim iota).
    """
    rows3 = jax.lax.broadcasted_iota(jnp.int32, (k, 1, 1), 0)
    rows2 = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)
    for j in range(k):  # k is static → fully unrolled
        inv = 1.0 / a[j, j, :]  # [T]
        row = a[j] * inv[None, :]  # [k,T] normalized pivot row
        bj = b[j] * inv  # [T]
        col = a[:, j, :]  # [k,T]
        # Eliminate column j from every row, keeping the normalized pivot
        # row via a select (Mosaic has no scatter, so no .at[j].set; the
        # select is also exact where subtract-then-restore would leave an
        # epsilon residue on row j).
        a = jnp.where(rows3 == j, row[None, :, :],
                      a - col[:, None, :] * row[None, :, :])
        b = jnp.where(rows2 == j, bj[None, :], b - col * bj[None, :])
    return b


def lu_solve_lanes(tr, y, u_scr, y_scr, x_scr, *, k: int):
    """In-register reverse-order no-pivot LU over lanes: tr [k,k,T],
    y [k,T] → x [k,T] (read back from ``x_scr``).

    The k³/3 elimination core of ``_lu_reg_kernel``, factored so the fused
    Gram+solve epilogue can run it on VMEM-resident Gram tiles.  Pivot rows
    go to the ``u_scr``/``y_scr`` VMEM scratch; forward substitution
    rebuilds x in increasing order through ``x_scr``.  See ``_lu_reg_kernel``
    for why the elimination runs in REVERSE variable order (offset-0
    slices are the only ones Mosaic's sublane broadcast lowers).
    """
    for n in range(k, 0, -1):  # static → unrolled; eliminate x_{n-1}
        inv = 1.0 / tr[n - 1, n - 1, :]
        yn = y[n - 1] * inv
        y_scr[n - 1, :] = yn
        if n > 1:
            row = tr[n - 1, :n - 1, :] * inv[None, :]
            col = tr[:n - 1, n - 1, :]
            u_scr[n - 1, :n - 1, :] = row
            tr = tr[:n - 1, :n - 1, :] - col[:, None, :] * row[None, :, :]
            y = y[:n - 1] - col * yn[None, :]
    x_scr[0, :] = y_scr[0, :]
    for j in range(1, k):
        corr = jnp.sum(u_scr[j, :j, :] * x_scr[:j, :], axis=0)
        x_scr[j, :] = y_scr[j, :] - corr
    return x_scr[...]


def _gauss_kernel(a_ref, b_ref, x_ref, *, k: int):
    """Solve T systems at once: a_ref [k,k,T], b_ref [k,T] → x_ref [k,T]."""
    x_ref[:] = gj_solve_lanes(a_ref[:], b_ref[:], k=k)


def _gauss_multi_kernel(a_ref, b_ref, x_ref, *, k: int):
    """Multi-RHS variant: a_ref [k,k,T], b_ref [k,m,T] → x_ref [k,m,T].

    The same unrolled Gauss-Jordan with the row operations applied to an
    [m]-wide RHS block — the building block of the blocked (Schur) solve
    for ranks above the single-kernel VMEM cap."""
    a = a_ref[:]
    b = b_ref[:]
    rows3 = jax.lax.broadcasted_iota(jnp.int32, (k, 1, 1), 0)
    for j in range(k):
        inv = 1.0 / a[j, j, :]  # [T]
        row = a[j] * inv[None, :]  # [k,T]
        bj = b[j] * inv[None, :]  # [m,T]
        col = a[:, j, :]  # [k,T]
        a = jnp.where(rows3 == j, row[None, :, :],
                      a - col[:, None, :] * row[None, :, :])
        b = jnp.where(rows3 == j, bj[None, :, :],
                      b - col[:, None, :] * bj[None, :, :])
    x_ref[:] = b


def apply_reg_lanes(a, reg, *, k: int, reg_mode: str, lam: float):
    """Add the regularizer to a batch-last [k,k,T] block in-register:
    ``diag`` = λ·max(n,1)·I from a [T] count lane vector (ALS-WR),
    ``matrix`` = one shared [k,k] SPD term (iALS's YᵀY+λI).  Shared by
    the standalone reg+solve kernels and the fused Gram+solve epilogue."""
    if reg_mode == "diag":
        regv = lam * jnp.maximum(reg.astype(jnp.float32), 1.0)  # [T]
        r3 = jax.lax.broadcasted_iota(jnp.int32, (k, k, 1), 0)
        c3 = jax.lax.broadcasted_iota(jnp.int32, (k, k, 1), 1)
        return a + jnp.where(r3 == c3, regv[None, None, :], 0.0)
    # matrix: one [k,k] SPD term shared across the batch (iALS)
    return a + reg[:, :, None]


def _apply_reg(a, r_ref, *, k: int, reg_mode: str, lam: float):
    """``apply_reg_lanes`` from the kernel's regularizer ref: the diag
    counts ride as a [1, T] block (1-D s32 operands draw an XLA T(1024)
    layout Mosaic rejects; 2-D rows use the standard tiling)."""
    reg = r_ref[0, :] if reg_mode == "diag" else r_ref[...]
    return apply_reg_lanes(a, reg, k=k, reg_mode=reg_mode, lam=lam)


def _lu_reg_kernel(a_ref, b_ref, r_ref, x_ref, u_scr, y_scr, x_scr, *,
                   k: int, reg_mode: str, lam: float):
    """Fused reg + LU solve, batch-first in/out — the k³/3 alternative to
    Gauss-Jordan's k³.

    No-pivot LU is stable here for the same reason GJ is (SPD + ridge).
    The elimination runs in REVERSE variable order with a shrinking
    trailing matrix: pure-functional shrink needs no in-register scatter
    (Mosaic has none), and eliminating the LAST variable keeps every slice
    offset-0 — Mosaic's sublane-broadcast lowering rejects offset slices
    (measured: offset-1 slices fail to lower, offset-0 of any length
    compile).  Pivot rows go to a VMEM scratch; forward substitution then
    rebuilds x in increasing order.  ~6× fewer VPU ops than the GJ kernel
    (Σ(n−1)² vs k·k² select+fma chains).
    """
    a = jnp.transpose(a_ref[...], (1, 2, 0))  # [k,k,T]
    y = b_ref[...].T  # [k,T]
    tr = _apply_reg(a, r_ref, k=k, reg_mode=reg_mode, lam=lam)
    x_ref[...] = lu_solve_lanes(tr, y, u_scr, y_scr, x_scr, k=k).T


def _gauss_reg_kernel(a_ref, b_ref, r_ref, x_ref, *, k: int, reg_mode: str,
                      lam: float):
    """Fused batch-first solve: a_ref [T,k,k], b_ref [T,k], r_ref the
    regularizer (``diag``: [T] rating counts; ``matrix``: [k,k] YᵀY+λI),
    x_ref [T,k].

    The round-3 profile showed the batch-last pallas solve paying three
    HBM round-trips outside the kernel: the λ·n·I diagonal add re-wrote the
    whole [E,k,k] Gram batch (~40 MB per chunk), and the [E,k,k]→[k,k,E]
    transpose plus the output transpose-back each copied it again
    (``copy.65``/``fusion.41``, ~66 ms of the 820 ms iteration).  Here the
    transposes happen in VMEM on the [T,k,k] block and the regularizer is
    added to the diagonal in-register, so HBM sees exactly one read of
    (A, b) and one write of x.  Padding systems (count 0 ⇒ reg λ·1) become
    λ·I — SPD — so no identity-fill prologue is needed either.
    """
    a = jnp.transpose(a_ref[...], (1, 2, 0))  # [k,k,T] batch-last
    b = b_ref[...].T  # [k,T]
    a = _apply_reg(a, r_ref, k=k, reg_mode=reg_mode, lam=lam)
    x_ref[...] = gj_solve_lanes(a, b, k=k).T


def default_reg_solve_algo() -> str:
    """PROCESS-DEFAULT elimination algorithm for the fused reg+solve
    kernel: ``"lu"`` (reverse-order no-pivot LU, k³/3 VPU work, rank cap
    128) vs ``"gj"`` (Gauss-Jordan, k³, cap 64).  At k=64 they measure
    identically in the production chunk scan (the kernel is
    issue-rate-bound, not FLOP-bound); LU is the default because it
    extends the fused path to k=128 — one direct solve instead of the
    blocked Schur composition.  gj is kept for the recovery ladder's
    rung and for A/B measurement.

    This is only the DEFAULT: callers that thread an explicit algorithm
    (``ALSConfig.reg_solve_algo`` → the half-step dispatchers → the
    ``algo=`` kwargs here) bypass it — which is how the recovery ladder's
    GJ rung works now (``resilience.policy``; it used to ride the env
    var).  ``gauss_solve_reg_pallas`` resolves this default BEFORE its
    jit boundary, so the concrete algorithm is part of the jit cache key
    and flipping the default (or monkeypatching this function) between
    calls compiles the right kernel instead of silently reusing the
    previous one.  Programs that jit a whole training step still bake the
    value in at THEIR trace time.

    The ``CFK_REG_SOLVE_ALGO`` env var is a DEPRECATED alias (ISSUE 9):
    the process default is a plan concern now — pin it with
    ``ALSConfig.reg_solve_algo`` / a ``PlanConstraints(reg_solve_algo=)``
    pin.  A set env var still wins (so old scripts keep working) but
    warns ONCE per process."""
    import os

    algo = os.environ.get("CFK_REG_SOLVE_ALGO")
    if algo is None:
        return "lu"
    if algo not in ("lu", "gj"):
        raise ValueError(
            f"CFK_REG_SOLVE_ALGO must be 'lu' or 'gj', got {algo!r}"
        )
    global _ENV_ALGO_WARNED
    if not _ENV_ALGO_WARNED:
        _ENV_ALGO_WARNED = True
        import warnings

        warnings.warn(
            "CFK_REG_SOLVE_ALGO is deprecated: pin the elimination "
            "algorithm through the execution planner instead "
            "(ALSConfig.reg_solve_algo or a PlanConstraints pin); the "
            "env var still wins this process but will be removed",
            DeprecationWarning,
            stacklevel=2,
        )
    return algo


_ENV_ALGO_WARNED = False


def resolve_reg_solve_algo(algo: str | None) -> str:
    """The threaded elimination algorithm if given, else the process
    default.  ``None`` and ``"auto"`` both defer (``"auto"`` is the
    ``ALSConfig.reg_solve_algo`` spelling of "no opinion", so configs
    stay env-var patchable by default)."""
    if algo is None or algo == "auto":
        return default_reg_solve_algo()
    if algo not in ("lu", "gj"):
        raise ValueError(f"reg_solve_algo must be 'lu' or 'gj', got {algo!r}")
    return algo


def _fused_reg_rank_cap(algo: str | None = None) -> int:
    """Largest rank the fused reg+solve path handles with the given (or
    default) algorithm — what the dispatchers in ``ops.solve`` route on."""
    return (LU_MAX_RANK if resolve_reg_solve_algo(algo) == "lu"
            else PALLAS_MAX_RANK)


def gauss_solve_reg_pallas(
    a: jax.Array,  # [E, k, k] float32 Gram batch (batch-FIRST)
    b: jax.Array,  # [E, k] float32
    reg: jax.Array,  # diag mode: [E] rating counts; matrix mode: [k,k]
    *,
    reg_mode: str = "diag",
    lam: float = 0.0,
    interpret: bool | None = None,
    algo: str | None = None,
) -> jax.Array:  # [E, k]
    """Regularize and solve a batch of SPD systems in one kernel pass.

    ``reg_mode="diag"`` applies ALS-WR's λ·max(n,1)·I (reference semantics,
    ``processors/MFeatureCalculator.java:91-95``); ``reg_mode="matrix"``
    adds a shared [k,k] SPD term (iALS's YᵀY+λI).  Batch-first layout in
    and out — the transposes the batch-last kernels need are done in VMEM,
    so callers no longer pay the [E,k,k] HBM transpose or a separate
    regularization pass.

    ``algo=None``/``"auto"`` is resolved HERE, outside the jit boundary,
    so the jit cache key always carries the concrete 'lu'/'gj' — flipping
    the default between calls (env var or monkeypatch) recompiles instead
    of silently reusing the previously traced kernel.
    """
    algo = resolve_reg_solve_algo(algo)
    return _gauss_solve_reg_pallas(
        a, b, reg, reg_mode=reg_mode, lam=lam, interpret=interpret,
        algo=algo,
    )


@functools.partial(
    jax.jit, static_argnames=("reg_mode", "lam", "interpret", "algo")
)
def _gauss_solve_reg_pallas(
    a: jax.Array,
    b: jax.Array,
    reg: jax.Array,
    *,
    reg_mode: str,
    lam: float,
    interpret: bool | None,
    algo: str,
) -> jax.Array:
    e, k, k2 = a.shape
    if k != k2 or b.shape != (e, k):
        raise ValueError(f"bad shapes a={a.shape} b={b.shape}")
    cap = LU_MAX_RANK if algo == "lu" else PALLAS_MAX_RANK
    if k > cap:
        raise ValueError(
            f"gauss_solve_reg_pallas[{algo}] supports rank <= {cap}, "
            f"got {k}; use the cholesky backend"
        )
    if reg_mode == "diag":
        if reg.shape != (e,):
            raise ValueError(f"diag reg shape {reg.shape} != ({e},)")
    elif reg_mode == "matrix":
        if reg.shape != (k, k):
            raise ValueError(f"matrix reg shape {reg.shape} != ({k},{k})")
    else:
        raise ValueError(f"unknown reg_mode {reg_mode!r}")
    interpret = resolve_interpret(interpret)
    tile = _LANES
    if interpret:
        # The HLO interpreter needs exact block tiling; compiled Mosaic
        # handles the ragged last block itself (out-of-bounds reads are
        # unspecified but stay in their own lanes — each lane is an
        # independent system — and out-of-bounds writes are dropped), so
        # on TPU no [E,k,k] pad/slice copy is paid (the pad alone was
        # ~28 ms/iter at full Netflix).
        e_pad = ((e + tile - 1) // tile) * tile
        a_p = _pad_to(a, e_pad, axis=0)
        b_p = _pad_to(b, e_pad, axis=0)
        r_p = (
            _pad_to(reg, e_pad, axis=0)[None, :]
            if reg_mode == "diag" else reg
        )
    else:
        e_pad = e
        a_p, b_p = a, b
        r_p = reg[None, :] if reg_mode == "diag" else reg
    mem = {} if interpret else {"memory_space": pltpu.VMEM}
    r_spec = (
        pl.BlockSpec((1, tile), lambda i: (0, i), **mem)
        if reg_mode == "diag"
        else pl.BlockSpec((k, k), lambda i: (0, 0), **mem)
    )
    vma = typeof_vma(a_p)
    out_shape = (
        jax.ShapeDtypeStruct((e_pad, k), jnp.float32, vma=vma)
        if vma
        else jax.ShapeDtypeStruct((e_pad, k), jnp.float32)
    )
    kwargs = {}
    if not interpret:
        # The batch-first input block + its in-kernel batch-last transpose
        # both sit in VMEM through the unrolled elimination (~20 MB at
        # k=64, ~4× that at k=128); the default 16 MB scoped allowance is
        # far short.
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=(40 if k <= 64 else 100) * 1024 * 1024
        )
    if algo == "lu":
        kern = functools.partial(
            _lu_reg_kernel, k=k, reg_mode=reg_mode, lam=lam
        )
        kwargs["scratch_shapes"] = [
            pltpu.VMEM((k, k, tile), jnp.float32),
            pltpu.VMEM((k, tile), jnp.float32),
            pltpu.VMEM((k, tile), jnp.float32),
        ]
    elif algo == "gj":
        kern = functools.partial(
            _gauss_reg_kernel, k=k, reg_mode=reg_mode, lam=lam
        )
    else:
        raise ValueError(f"unknown reg-solve algo {algo!r}")
    x = pl.pallas_call(
        kern,
        out_shape=out_shape,
        grid=((e_pad + tile - 1) // tile,),
        in_specs=[
            pl.BlockSpec((tile, k, k), lambda i: (i, 0, 0), **mem),
            pl.BlockSpec((tile, k), lambda i: (i, 0), **mem),
            r_spec,
        ],
        out_specs=pl.BlockSpec((tile, k), lambda i: (i, 0), **mem),
        interpret=bool(interpret),
        **kwargs,
    )(a_p, b_p, r_p)
    return x[:e]


def _pad_to(x: jax.Array, size: int, axis: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _lane_padded_inputs(a, b, b_pad_axis, interpret):
    """Shared wrapper prologue: lane-pad the batch, turn the all-zero padded
    systems into identity systems (the elimination would divide by zero),
    and resolve interpret mode.  Returns (a_p, b_p, e, e_pad, tile, interp).
    """
    k = a.shape[0]
    e = a.shape[2]
    interpret = resolve_interpret(interpret)
    tile = _LANES
    e_pad = ((e + tile - 1) // tile) * tile
    a_p = _pad_to(a, e_pad, axis=2)
    b_p = _pad_to(b, e_pad, axis=b_pad_axis)
    if e_pad != e:
        pad_lane = jnp.arange(e_pad) >= e
        a_p = a_p + jnp.eye(k, dtype=a.dtype)[:, :, None] * pad_lane[None, None, :]
    return a_p, b_p, e, e_pad, tile, interpret


def _solve_call(kernel, a_p, b_p, b_block, out_struct, tile, interpret,
                vmem_limit=None):
    """Shared pallas_call plumbing: VMEM block specs (skipped in interpret
    mode), vma tagging of the output aval (under shard_map the output must
    carry the inputs' varying-mesh-axes), and the optional scoped-VMEM
    raise."""
    k = a_p.shape[0]
    e_pad = a_p.shape[2]
    mem = {} if interpret else {"memory_space": pltpu.VMEM}
    nb = len(b_block)
    b_map = (lambda i: (0, 0, i)) if nb == 3 else (lambda i: (0, i))
    specs = dict(
        in_specs=[
            pl.BlockSpec((k, k, tile), lambda i: (0, 0, i), **mem),
            pl.BlockSpec(b_block, b_map, **mem),
        ],
        out_specs=pl.BlockSpec(b_block, b_map, **mem),
    )
    shape, dtype = out_struct
    vma = typeof_vma(a_p)
    if vma:
        out_shape = jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    else:
        out_shape = jax.ShapeDtypeStruct(shape, dtype)
    kwargs = {}
    if vmem_limit is not None and not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit)
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(e_pad // tile,),
        interpret=bool(interpret),
        **specs,
        **kwargs,
    )(a_p, b_p)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gauss_solve_multi_pallas(
    a: jax.Array,  # [k, k, E] float32, SPD per system
    b: jax.Array,  # [k, m, E] float32 — m right-hand sides per system
    *,
    interpret: bool | None = None,
) -> jax.Array:  # [k, m, E]
    """Solve A X = B with an [m]-wide RHS block per system (batch-last).

    Used by the blocked Schur solve for rank > PALLAS_MAX_RANK: one call
    computes A₁₁⁻¹[A₁₂ | b₁] in a single elimination.  VMEM holds
    [k, k, tile] + [k, m, tile] live through the unrolled elimination, so
    m is capped at PALLAS_MAX_RANK + 8 and the scoped-VMEM budget is raised
    (the default 16 MB is ~24 MB short at k = m = 64).
    """
    k, m, e = b.shape
    if a.shape != (k, k, e):
        raise ValueError(f"a shape {a.shape} != ({k},{k},{e})")
    if k > PALLAS_MAX_RANK or m > PALLAS_MAX_RANK + 8:
        raise ValueError(
            f"gauss_solve_multi_pallas supports k <= {PALLAS_MAX_RANK}, "
            f"m <= {PALLAS_MAX_RANK + 8} (VMEM budget), got k={k} m={m}"
        )
    a_p, b_p, e, e_pad, tile, interpret = _lane_padded_inputs(
        a, b, 2, interpret
    )
    x = _solve_call(
        functools.partial(_gauss_multi_kernel, k=k),
        a_p, b_p, (k, m, tile), ((k, m, e_pad), a.dtype), tile, interpret,
        vmem_limit=40 * 1024 * 1024,
    )
    return x[:, :, :e]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gauss_solve_pallas(
    a: jax.Array,  # [k, k, E] float32, SPD per system
    b: jax.Array,  # [k, E] float32
    *,
    interpret: bool | None = None,
) -> jax.Array:  # [k, E]
    """Solve A[:, :, e] x = b[:, e] for every e. Batch-last layout."""
    k, _, e = a.shape
    if k > PALLAS_MAX_RANK:
        raise ValueError(
            f"gauss_solve_pallas supports rank <= {PALLAS_MAX_RANK} (VMEM "
            f"budget), got {k}; use the cholesky backend"
        )
    a_p, b_p, e, e_pad, tile, interpret = _lane_padded_inputs(
        a, b, 1, interpret
    )
    x = _solve_call(
        functools.partial(_gauss_kernel, k=k),
        a_p, b_p, (k, tile), ((k, e_pad), a.dtype), tile, interpret,
    )
    return x[:, :e]
