"""Pallas TPU kernels: batched small-SPD solves with the batch along the lanes.

The framework's FLOP hot spot after the Gram matmuls is solving E independent
k×k SPD systems (k = rank, 5..128; E = entities per shard).  XLA lowers
``jnp.linalg.cholesky`` + two ``triangular_solve``s to sequential custom
calls that walk the columns with one system in the vector unit at a time.
The kernels here lay the *batch* dimension out along the TPU's 128-wide
vector lanes instead: every scalar step of the textbook algorithm becomes a
[k, T] or [k, k, T] VPU op over T systems at once.  No pivoting — the
systems are SPD with a λ·n ≥ λ ridge (``regularized_solve``), so diagonal
pivots stay safely positive.  Three eliminations:

- **Gauss-Jordan** (``gj_solve_lanes``; ``gauss_solve_pallas`` and the
  multi-RHS form): ≈ 2k³ FLOPs a system, fully unrolled over k with
  [k, k, TILE] temporaries in VMEM, so k ≤ PALLAS_MAX_RANK (= 64 → A tile
  2 MiB; at k = 128 the unrolled O(k³) chain measured ~10× slower than
  XLA's calls).  Ranks up to 128 compose two of them by one level of Schur
  elimination (``ops.solve._blocked_spd_solve_pallas``).
- **Reverse-order LU** (``lu_solve_lanes``; ``gauss_solve_reg_pallas`` with
  ``algo="lu"``, the fused ridge + solve the training half-steps take under
  ``solver="pallas"``): k³/3, unrolled over k with a shrinking trailing
  matrix, k ≤ LU_MAX_RANK (= 128).  ~1.1 µs a system at k = 128 in the
  pre-ledger record, but 128 steps of 128 different shapes: it compiles in
  ~3 min a shape there (173 s for the described v5e).
- **Cholesky** (``_chol_lanes_kernel``; ``cholesky_solve_lanes``, what
  ``ops.solve.batched_spd_solve`` — ``solver="cholesky"`` — runs on a TPU
  since PR 40): the same factorisation as a LOOP over the columns (a
  symmetric matrix's column j is its row j: a dynamic index on the leading
  axis of a VMEM scratch) with a loop over the rows below the pivot inside
  it, k³/2 register updates, each body traced once.  It compiles in well
  under a second at k = 128, which is what a caller with many shapes
  needs: the streaming fold-in's pow2 grid has 30.

Layout contract: the batch-last kernels take A as [k, k, E] and b as [k, E]
(tiles sit in the lane dimension); ``gauss_solve_reg_pallas`` and
``cholesky_solve_lanes`` take the batch-first Gram layout.  The first turns
its block in VMEM; the second leaves the turn to XLA, where it fuses with
the ridge's add.  (Emitting batch-last from the Gram kernel would force its
per-entity flush onto dynamic LANE offsets, a worse trade than one bulk
transpose: ~3 % of the full-Netflix iteration in the round-3 profile.)
All fall back to interpret mode off-TPU so tests run on CPU.
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
# The lane-batched Cholesky (``cholesky_solve_lanes``) is a loop over the
# columns, not an unrolled program: one tile keeps A, its factor and the
# double-buffered input block in VMEM (4 x 8 MiB at k = 128) and compiles
# in under a second at any rank up to the lane width.
CHOL_MAX_RANK = 128


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


def _chol_lanes_kernel(a_ref, b_ref, x_ref, a_scr, l_scr, y_scr, x_scr, *,
                       k: int):
    """Cholesky factor-and-solve of T systems, one a lane: a_ref [k,k,T],
    b_ref [k,T] -> x_ref [k,T].  ``A = L L^T`` by the right-looking
    (outer-product) form, no pivoting: SPD + ridge, as the LU kernel relies
    on.

    What keeps it a loop where ``lu_solve_lanes`` is an unrolled program:
    column j of a symmetric matrix is its row j, and ``a_scr[j]`` is a
    dynamic index on the leading, untiled axis of a VMEM scratch.  A step
    reads that row and the pivot (one sublane of it), scales by the pivot's
    inverse root, masks to rows >= j and subtracts ``l_i * l`` from row i of
    the trailing matrix, row by row from the pivot down in an inner loop
    whose body is ``k / 8`` registers wide: no offset slice of a value
    (Mosaic's sublane broadcast refuses those), no shrinking shape; the
    rows behind the pivot are the k^3 / 2 the dynamic bound gives back.
    The right-hand side rides along as one more row (forward substitution
    inside the column loop); the back substitution is a loop of ``[k, T]``
    products over the stored factor.  Lanes never mix: a system's bits do
    not depend on its tile's others.

    Kept this small on purpose: every equation of the body is lowered
    again, in Python, by each program that holds the call (this body:
    ~0.06 s a program on the chip machine's host, and the fold-in prewarms
    30 on every start).  Static column stages that skip the lower triangle
    (the other k^3 / 6) measured 0.227 ms (stages of 64) and 0.162 ms
    (stages of 32, rows unrolled by 4) for 256 systems of 128 on a v5e
    against this body's 0.278, and cost 1 s and ~3.5 s more of every warm
    start (PERF.md, section 6, PR 40).
    """
    a_scr[...] = a_ref[...]
    y_scr[...] = b_ref[...]
    x_scr[...] = jnp.zeros_like(x_scr)
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, _LANES), 0)
    zeros = jnp.zeros((k, _LANES), jnp.float32)

    def column(j, carry):
        inv = jax.lax.rsqrt(a_scr[j, pl.ds(j, 1), :])  # [1,T]
        col = jax.lax.select(rows >= j, a_scr[j] * inv, zeros)
        l_scr[j] = col  # column j of L, zero above the diagonal
        # Forward substitution: rows <= j of y_scr hold y, rows > j the
        # right-hand side less the columns done (col is 0 at rows < j, so
        # those stay; row j is set last).
        yj = y_scr[pl.ds(j, 1), :] * inv
        y_scr[...] = y_scr[...] - yj * col
        y_scr[pl.ds(j, 1), :] = yj

        def row(i, c):
            a_scr[i] = a_scr[i] - l_scr[j, pl.ds(i, 1), :] * col
            return c

        jax.lax.fori_loop(j + 1, k, row, 0)
        return carry

    jax.lax.fori_loop(0, k, column, 0)

    def back(n, carry):
        i = k - 1 - n
        # x is 0 at rows <= i still, L's column i at rows < i: the sum is
        # over the rows below the diagonal.
        dot = jnp.sum(l_scr[i] * x_scr[...], axis=0, keepdims=True)
        x_scr[pl.ds(i, 1), :] = (
            (y_scr[pl.ds(i, 1), :] - dot) / l_scr[i, pl.ds(i, 1), :])
        return carry

    jax.lax.fori_loop(0, k, back, 0)
    x_ref[...] = x_scr[...]


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
                vmem_limit=None, scratch=(), name=None):
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
    if scratch:
        kwargs["scratch_shapes"] = list(scratch)
    return pl.pallas_call(
        kernel,
        name=name,
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


def cholesky_solve_lanes(
    a: jax.Array,  # [E, k, k] float32, SPD per system (batch-FIRST)
    b: jax.Array,  # [E, k] float32
    *,
    interpret: bool | None = None,
) -> jax.Array:  # [E, k]
    """Cholesky-solve A[e] x = b[e] for every e with the batch along the
    lanes: what ``ops.solve.batched_spd_solve`` runs on a TPU.

    Batch-first in and out, like XLA's calls it stands in for; the turn to
    ``[k, k, E]`` and the pad to whole tiles of 128 systems are the
    caller's XLA, where they fuse with whatever made ``a`` (in the fold-in:
    the ridge's add).  A padded lane holds the zero system: its 0 / 0 stays
    in its lane and is sliced off again.  The kernel sits behind its own
    ``jit`` on the padded operands, so every program that agrees on
    ``(k, ceil(E / 128))`` shares one trace of it.
    """
    e, k, k2 = a.shape
    if k != k2 or b.shape != (e, k):
        raise ValueError(f"bad shapes a={a.shape} b={b.shape}")
    if k > CHOL_MAX_RANK or k % 8:
        raise ValueError(
            f"cholesky_solve_lanes supports rank <= {CHOL_MAX_RANK} in "
            f"multiples of 8, got {k}"
        )
    e_pad = -(-e // _LANES) * _LANES
    x = _cholesky_lanes_call(
        _pad_to(jnp.transpose(a, (1, 2, 0)), e_pad, axis=2),
        _pad_to(b.T, e_pad, axis=1),
        interpret=resolve_interpret(interpret),
    )
    return x[:, :e].T


@functools.partial(jax.jit, static_argnames=("interpret",))
def _cholesky_lanes_call(a_t, b_t, *, interpret):
    """a_t [k, k, E], b_t [k, E] -> x [k, E], E in whole tiles."""
    k, e_pad = b_t.shape
    block = (k, _LANES)
    return _solve_call(
        functools.partial(_chol_lanes_kernel, k=k),
        a_t, b_t, block, ((k, e_pad), a_t.dtype), _LANES, interpret,
        # the input block twice (the pipeline's two buffers), the working
        # copy, the factor, and room for the [k, T] rows
        vmem_limit=(4 * k * k + 64 * k) * _LANES * 4 + (4 << 20),
        scratch=(
            pltpu.VMEM((k, k, _LANES), jnp.float32),  # trailing matrix
            pltpu.VMEM((k, k, _LANES), jnp.float32),  # L, column j at [j]
            pltpu.VMEM(block, jnp.float32),  # y over the right-hand side
            pltpu.VMEM(block, jnp.float32),  # x
        ),
        name="cholesky_solve_lanes",
    )
