"""Incremental fold-in: solve ONLY the touched users against fixed movies.

Exactly one ALS half-iteration restricted to the touched rows — the math
the ROADMAP names: each touched user's normal equations

    (Σ m mᵀ + λ·n·I) u = Σ r·m        over that user's CURRENT ratings

solved against the fixed movie factors, so the existing chunked Gram+solve
machinery applies verbatim on a tiny entity set.  Two layouts:

- ``"padded"`` — one [T, P] rectangle built directly from the touched
  users' neighbor lists and solved by ``ops.solve.als_half_step`` (the
  single-rectangle reference path; the default for micro-batches, whose
  rectangles are tiny).
- ``"tiled"`` — ``data.blocks.build_tiled_blocks`` over the touched set,
  solved by ``ops.tiled.tiled_half_step`` — the same kernels the at-scale
  trainer runs, fused Gram+solve epilogue and in-kernel gather included
  (they engage under the identical gates; on CPU CI both route through
  their bit-exact XLA emulation twins).

Shapes are bucketed to powers of two (entity count and rectangle width) so
a long-running stream converges onto a handful of compiled programs
instead of re-tracing every batch.

Determinism contract: the solved rows are a deterministic function of
(neighbor lists, movie factors, solve configuration) — neighbor lists
arrive sorted by movie row (``StreamState.neighbors``), so the same batch
always produces bit-identical rows.  Rows ARE sensitive at the last-ulp
level to the batch's composition (co-members set the padded width and the
batch GEMM shapes), which is why the exactly-once pipeline pins batch
boundaries to log offsets: replayed and fault-injected deliveries re-cut
bit-identical batches (``cfk_tpu.streaming.consumer``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from cfk_tpu.ops.solve import als_half_step, solve_route
from cfk_tpu.ops.tiled import tiled_half_step
from cfk_tpu.resilience import sentinel as _sentinel
from cfk_tpu.telemetry import span


def _pow2_ceil(x: int, floor: int) -> int:
    out = floor
    while out < x:
        out *= 2
    return out


# Trace counter (ISSUE 13): bumped once per TRACE of a fold-in program
# (the bodies run only while jax traces a new shape bucket), so the
# session's prewarm() can pin its zero-new-traces contract and the bench
# fold-in row can report trace_count alongside updates/s.
_TRACES = [0]


def trace_count() -> int:
    """Fold-in program traces this process (both layouts)."""
    return _TRACES[0]


@functools.partial(
    jax.jit,
    static_argnames=("lam", "solver", "reg_solve_algo"),
)
def _padded_fold(fixed, neighbor_idx, rating, mask, count, touched,
                 norm_limit, *, lam, solver, reg_solve_algo):
    """(solved rows [E, k], the health sentinel's word over the first
    ``touched`` of them): one program, so the probe of a batch costs no
    second hand-over and no second wait."""
    _TRACES[0] += 1
    rows = als_half_step(
        fixed, neighbor_idx, rating, mask, count, lam,
        solver=solver, reg_solve_algo=reg_solve_algo,
    )
    word = _sentinel.side_word(rows, norm_limit, _sentinel.NONFINITE_U,
                               _sentinel.NORM_U, rows=touched)
    return rows, word


@functools.partial(
    jax.jit,
    static_argnames=("chunks", "entities", "lam", "solver", "fused_epilogue",
                     "in_kernel_gather", "reg_solve_algo"),
)
def _tiled_fold(fixed, blk, *, chunks, entities, lam, solver, fused_epilogue,
                in_kernel_gather, reg_solve_algo):
    _TRACES[0] += 1
    return tiled_half_step(
        fixed, blk, chunks, entities, lam, solver=solver,
        fused_epilogue=fused_epilogue, in_kernel_gather=in_kernel_gather,
        reg_solve_algo=reg_solve_algo,
    )


def fold_in_rows(
    movie_factors,
    neighbor_data,
    *,
    lam: float,
    solver: str = "auto",
    layout: str = "padded",
    pad_multiple: int = 8,
    fused_epilogue: bool | None = None,
    in_kernel_gather: bool | None = None,
    reg_solve_algo: str | None = None,
) -> np.ndarray:
    """Solve the touched users' rows against fixed ``movie_factors``.

    ``neighbor_data`` is a sequence of ``(movie_rows int32, ratings f32)``
    pairs, one per touched user, each sorted by movie row.  Returns the
    solved float32 rows ``[len(neighbor_data), k]`` in the same order.
    """
    t = len(neighbor_data)
    if t == 0:
        return np.zeros((0, movie_factors.shape[-1]), np.float32)
    if layout == "tiled":
        return _fold_tiled(
            movie_factors, neighbor_data, lam=lam, solver=solver,
            fused_epilogue=fused_epilogue, in_kernel_gather=in_kernel_gather,
            reg_solve_algo=reg_solve_algo,
        )
    if layout != "padded":
        raise ValueError(
            f"fold-in layout must be 'padded' or 'tiled', got {layout!r}"
        )
    return fold_in_dispatch(
        movie_factors, neighbor_data, lam=lam, solver=solver,
        pad_multiple=pad_multiple, reg_solve_algo=reg_solve_algo,
    ).fetch()[0]


def _rectangle(neighbor_data, pad_multiple: int, index=None):
    """The padded [E, P] operands of one fold-in, pow2 in both extents:
    (neighbor_idx, rating, mask, count); ``index`` maps item rows into a
    staged window."""
    width = max(int(mv.shape[0]) for mv, _ in neighbor_data)
    p = _pow2_ceil(max(width, 1), max(pad_multiple, 1))
    e = _pow2_ceil(len(neighbor_data), 8)
    neighbor_idx = np.zeros((e, p), np.int32)
    rating = np.zeros((e, p), np.float32)
    mask = np.zeros((e, p), np.float32)
    count = np.zeros((e,), np.float32)
    for i, (mv, rt) in enumerate(neighbor_data):
        n = mv.shape[0]
        neighbor_idx[i, :n] = mv if index is None else index(mv)
        rating[i, :n] = rt
        mask[i, :n] = 1.0
        count[i] = n
    return neighbor_idx, rating, mask, count


class FoldIn:
    """One padded fold-in between its hand-over to the device and its
    fetch.  ``fold_in_dispatch`` makes it: the operands are uploaded and
    ``_padded_fold`` is called, both asynchronous; ``fetch`` waits for the
    device and copies the solved rows and the sentinel's word to the host.
    A caller with other work on the device (a request server's scorer)
    fetches once that work has been answered (``StreamSession.pump``).
    ``entities`` x ``width`` is the padded rectangle, ``gather_bytes`` the
    item rows the program gathers for it, ``operand_bytes`` what went up."""

    def __init__(self, out, touched: int, entities: int, width: int,
                 rank: int, operand_bytes: int) -> None:
        self._out = out
        self.touched, self.entities, self.width = touched, entities, width
        self.rank = rank
        self.gather_bytes = entities * width * rank * 4
        self.operand_bytes = operand_bytes

    def fetch(self) -> tuple[np.ndarray, int]:
        """(rows [touched, k] float32, the user side's health word)."""
        rows, word = self._out
        with span("stream/batch/solve"), \
                span("stream/batch/solve/fetch") as sp:
            rows = np.asarray(rows, np.float32)
            word = int(np.asarray(word))
            sp.set(bytes=rows.nbytes + 4)
        return rows[:self.touched], word


def fold_in_dispatch(
    movie_factors,
    neighbor_data,
    *,
    lam: float,
    solver: str = "auto",
    pad_multiple: int = 8,
    reg_solve_algo: str | None = None,
    norm_limit: float = float("inf"),
    index=None,
) -> FoldIn:
    """Hand one padded fold-in (``neighbor_data`` not empty) to the device
    and return without waiting for it; ``FoldIn.fetch`` has the rows.
    ``norm_limit`` is the health sentinel's bound on a solved row's norm
    (the word's non-finite bit needs none)."""
    t = len(neighbor_data)
    with span("stream/batch/upload") as sp:
        host = _rectangle(neighbor_data, pad_multiple, index)
        operands = tuple(map(jnp.asarray, host))
        e, p = host[0].shape
        nbytes = sum(o.nbytes for o in host)
        sp.set(entities=e, width=p, bytes=nbytes)
    rank = int(movie_factors.shape[-1])
    with span("stream/batch/solve", touched=t, entities=e, width=p,
              gather_bytes=e * p * rank * 4,
              solve_route=solve_route(solver, rank)), \
            span("stream/batch/solve/dispatch"):
        out = _padded_fold(
            movie_factors, *operands, np.int32(t), np.float32(norm_limit),
            lam=float(lam), solver=solver, reg_solve_algo=reg_solve_algo,
        )
    return FoldIn(out, t, e, p, rank, nbytes)


def fold_in_rows_windowed(
    movie_store,
    neighbor_data,
    *,
    lam: float,
    solver: str = "auto",
    pad_multiple: int = 8,
    reg_solve_algo: str | None = None,
    stats: dict | None = None,
    return_staged: bool = False,
):
    """Restricted fold-in against an OUT-OF-CORE movie table (ISSUE 19).

    ``movie_store`` is a host-resident ``offload.store.HostFactorStore``;
    the batch's touched movie rows stage as ONE ad-hoc window (unique
    referenced rows gathered host-side, one ``device_put``), neighbor
    indices rebase into the window via ``searchsorted``, and the SAME
    ``_padded_fold`` program solves the identical pow2 rectangle — so the
    solved rows are BIT-IDENTICAL to ``fold_in_rows`` over the full
    device-resident table (the gather reads the same values; mask-0 cells
    contribute exact zeros; the rectangle shape is unchanged, so the
    batched solve bits are too).  The window row count buckets to pow2
    (min 8) so a long-running stream converges onto the same handful of
    compiled programs the resident path enjoys; pad slots replicate
    window row 0 (masked out — exact zero contribution).

    ``return_staged=True`` additionally returns the staged window (the
    device array the solve read), so the caller's health probe can run
    against the rows actually consumed — the out-of-core twin of probing
    the resident table.  ``stats`` (a dict) receives
    ``foldin_windows_staged`` / ``foldin_staged_bytes`` increments.
    """
    t = len(neighbor_data)
    k = movie_store.rank
    if t == 0:
        empty = np.zeros((0, k), np.float32)
        return (empty, None) if return_staged else empty
    touched = (np.unique(np.concatenate(
        [mv.astype(np.int64) for mv, _ in neighbor_data]))
        if any(mv.shape[0] for mv, _ in neighbor_data)
        else np.zeros((1,), np.int64))
    if touched.size == 0:
        touched = np.zeros((1,), np.int64)
    w = _pow2_ceil(int(touched.size), 8)
    rows = np.concatenate([
        touched, np.full(w - touched.size, touched[0], np.int64)
    ])
    window = movie_store.gather(rows)
    if stats is not None:
        stats["foldin_windows_staged"] = (
            stats.get("foldin_windows_staged", 0) + 1)
        stats["foldin_staged_bytes"] = (
            stats.get("foldin_staged_bytes", 0) + window.nbytes)
    staged = jnp.asarray(window)
    solved = fold_in_dispatch(
        staged, neighbor_data, lam=lam, solver=solver,
        pad_multiple=pad_multiple, reg_solve_algo=reg_solve_algo,
        index=lambda mv: np.searchsorted(
            touched, mv.astype(np.int64)).astype(np.int32),
    ).fetch()[0]
    return (solved, staged) if return_staged else solved


def _fold_tiled(movie_factors, neighbor_data, *, lam, solver, fused_epilogue,
                in_kernel_gather, reg_solve_algo):
    from cfk_tpu.data.blocks import build_tiled_blocks
    from cfk_tpu.models.als import _tiled_to_device

    t = len(neighbor_data)
    solve_dense = np.concatenate([
        np.full(mv.shape[0], i, np.int64)
        for i, (mv, _) in enumerate(neighbor_data)
    ])
    fixed_dense = np.concatenate(
        [mv.astype(np.int64) for mv, _ in neighbor_data]
    )
    rating = np.concatenate([rt for _, rt in neighbor_data])
    blocks = build_tiled_blocks(
        solve_dense, fixed_dense, rating, t,
        int(movie_factors.shape[0]),
    )
    blk = _tiled_to_device(blocks)
    out = _tiled_fold(
        movie_factors, blk,
        chunks=("tiled", blocks.mode) + blocks.statics,
        entities=blocks.padded_entities,
        lam=float(lam), solver=solver, fused_epilogue=fused_epilogue,
        in_kernel_gather=in_kernel_gather, reg_solve_algo=reg_solve_algo,
    )
    return np.asarray(out[:t], np.float32)
