"""Incremental fold-in: solve ONLY the touched users against fixed movies.

Exactly one ALS half-iteration restricted to the touched rows — the math
the ROADMAP names: each touched user's normal equations

    (Σ m mᵀ + λ·n·I) u = Σ r·m        over that user's CURRENT ratings

solved against the fixed movie factors.  Which way a micro-batch's systems
are assembled follows from its lists (``fold_route``), as
``ops.solve.spd_solve_route`` follows from the rank; no caller chooses:

- ``"padded"`` — the batch's longest list holds at most ``CHUNK`` (128)
  cells: one [E, P] rectangle built from the touched users' neighbor lists
  and solved by ``ops.solve.als_half_step`` in ONE program
  (``_padded_fold``: gather, Gram, solve and the sentinel's word), E and P
  powers of two, P at most ``CHUNK``.
- ``"cells"`` — a longer list is in the batch: every list is cut into chunk
  rows of ``CHUNK`` cells (the last one of a list filled up with masked
  cells), the chunk rows of the whole batch are laid end to end in (user
  row, item row) order and handed to the device in slabs of ``SLABS`` rows
  (``_cells_fold_gram``: gather, the chunk rows' Grams as one batched
  GEMM, summed by owner onto the batch's [E, k, k] systems), and one
  program solves them (``_cells_fold_solve``: ridge λ·n, the same solve,
  the sentinel's word).  What the device gathers and multiplies is the
  batch's cells plus under a chunk a user and under a slab a batch:
  ``cells + CHUNK * (touched + SLABS[-1])`` at most, never the heaviest
  list times the users.  A rectangle over a reviewer with 10,000 items is
  256 x 16,384 cells for ~234 k of real work (PERF.md section 6, PR 41).

The programs are a fixed set (``CHUNK``, ``SLABS`` and the entity buckets
bound every shape), whatever the longest list: ``StreamSession.prewarm``
runs each once and no list can outgrow them.

The fixed movie factors (``movie_factors`` / ``fixed`` below) are one
[M, k] device array, or the pair a serving engine holds its table as on
one device (``ServeEngine.fold_table``): ``(data, scale)``, float32 or
bfloat16 rows with ``scale`` None, int8 codes with a float32 scale a row.
Both routes gather from it where it lies (``ops.solve.gather_rows``: codes
and scales by the same indices, dequantized in float32 among the gathered
rows, code x scale: the view the engine's scorer scores against), so the
normal equations are float32 and ``HIGHEST`` whatever the table stores and
nothing the size of the table, or of a block of it, is ever made.  A
float32 pair lowers to the program the plain array lowers to.

Out-of-core sessions (``offload_tier='host_window'``) stage the batch's
touched item rows as one window and run the same two routes against it
(``fold_in_rows_windowed``: the one caller is ``StreamSession._dispatch``,
``tests/test_offload_ials.py`` and the offload session tests run it).  The
tiled fold-in (``build_tiled_blocks`` over the touched set, its statics cut
by the batch's lists and so never prewarmed) went with PR 41: the cells
route does its work with a fixed set of programs (ROADMAP D16).

Determinism contract: the solved rows are a deterministic function of
(neighbor lists, movie factors, solve configuration) — neighbor lists
arrive sorted by movie row (``StreamState.neighbors``) and users by row, so
the chunk rows, their slabs and the order their Grams are summed in depend
on the batch's lists alone, never on the order its records arrived in: the
same batch always produces bit-identical rows.  Rows ARE sensitive at the
last-ulp level to the batch's composition (co-members set the rectangle's
width, the slabs' cuts and the batch GEMM shapes), which is why the
exactly-once pipeline pins batch boundaries to log offsets: replayed and
fault-injected deliveries re-cut bit-identical batches
(``cfk_tpu.streaming.consumer``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cfk_tpu.ops.solve import (
    als_half_step, gather_gram, regularized_solve, solve_route, table_parts)
from cfk_tpu.resilience import sentinel as _sentinel
from cfk_tpu.telemetry import span

# Cells of one chunk row, and the widest rectangle: a batch whose longest
# list holds more takes the cells route.
CHUNK = 128
# Chunk rows one run of ``_cells_fold_gram`` takes, largest first: a batch's
# rows are covered by as many of each as fit, and the rest by the smallest.
SLABS = (4096, 1024, 256, 64)


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
    """Fold-in program traces this process (every route)."""
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


@jax.jit
def _cells_fold_gram(fixed, slab, acc_a, acc_b):
    """One slab of chunk rows onto the batch's systems: ``slab`` [S, 2 *
    CHUNK + 2] int32 holds, a chunk row, its item rows, its ratings (the
    float32 bits), how many of its cells are real and the entity that owns
    it (ascending down the slab); returns (``acc_a`` + the rows' Grams
    summed by owner, ``acc_b`` + their right-hand sides).  The same gather
    and ``HIGHEST`` einsum as the rectangle's (``ops.solve.gather_gram``)."""
    _TRACES[0] += 1
    neighbor_idx = slab[:, :CHUNK]
    rating = lax.bitcast_convert_type(slab[:, CHUNK:2 * CHUNK], jnp.float32)
    fill, owner = slab[:, 2 * CHUNK], slab[:, 2 * CHUNK + 1]
    mask = (lax.broadcasted_iota(jnp.int32, neighbor_idx.shape, 1)
            < fill[:, None]).astype(jnp.float32)
    a, b = gather_gram(fixed, neighbor_idx, rating, mask)
    entities = acc_a.shape[0]
    return (
        acc_a + jax.ops.segment_sum(a, owner, num_segments=entities,
                                    indices_are_sorted=True),
        acc_b + jax.ops.segment_sum(b, owner, num_segments=entities,
                                    indices_are_sorted=True),
    )


@functools.partial(
    jax.jit,
    static_argnames=("lam", "solver", "reg_solve_algo"),
)
def _cells_fold_solve(acc_a, acc_b, count, touched, norm_limit, *, lam,
                      solver, reg_solve_algo):
    """The systems ``_cells_fold_gram`` summed, solved as the rectangle's
    are (``ops.solve.regularized_solve``: ridge λ·n, the same route), with
    the sentinel's word over the first ``touched`` rows."""
    _TRACES[0] += 1
    rows = regularized_solve(acc_a, acc_b, count, lam, solver,
                             algo=reg_solve_algo)
    word = _sentinel.side_word(rows, norm_limit, _sentinel.NONFINITE_U,
                               _sentinel.NORM_U, rows=touched)
    return rows, word


@functools.lru_cache(maxsize=8)
def _zero_systems(entities: int, rank: int):
    """The all-zero (A [E, k, k], b [E, k]) a batch's first slab is summed
    onto: made once a shape and never written (no program donates it)."""
    return (jnp.zeros((entities, rank, rank), jnp.float32),
            jnp.zeros((entities, rank), jnp.float32))


def fold_route(neighbor_data) -> str:
    """``"padded"`` or ``"cells"``, from the batch's lists alone."""
    longest = max((int(mv.shape[0]) for mv, _ in neighbor_data), default=0)
    return "padded" if longest <= CHUNK else "cells"


def fold_in_rows(
    movie_factors,
    neighbor_data,
    *,
    lam: float,
    solver: str = "auto",
    pad_multiple: int = 8,
    reg_solve_algo: str | None = None,
) -> np.ndarray:
    """Solve the touched users' rows against fixed ``movie_factors``.

    ``neighbor_data`` is a sequence of ``(movie_rows int32, ratings f32)``
    pairs, one per touched user, each sorted by movie row.  Returns the
    solved float32 rows ``[len(neighbor_data), k]`` in the same order.
    """
    if not len(neighbor_data):
        return np.zeros((0, table_parts(movie_factors)[0].shape[-1]),
                        np.float32)
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


def slab_plan(chunk_rows: int) -> list[int]:
    """The slabs that cover ``chunk_rows`` chunk rows, largest first: as
    many of each size as fit, the rest in the smallest (under one of them
    is filled up with empty rows)."""
    plan, left = [], chunk_rows
    for size in SLABS[:-1]:
        plan += [size] * (left // size)
        left %= size
    return plan + [SLABS[-1]] * -(-left // SLABS[-1])


def _chunk_rows(neighbor_data, entities: int, index=None):
    """The batch's lists as chunk rows: (slabs, each [S, 2 * CHUNK + 2]
    int32 as ``_cells_fold_gram`` reads it; count [entities] float32; the
    cells).  Row after row in (user, item) order; a list's last row and a
    slab's last rows are filled up with cells that count for nothing."""
    lens = np.fromiter((mv.shape[0] for mv, _ in neighbor_data), np.int64,
                       len(neighbor_data))
    cells = int(lens.sum())
    rows_of = -(-lens // CHUNK)
    first_row = np.cumsum(rows_of) - rows_of
    c = int(rows_of.sum())
    plan = slab_plan(c)
    c_pad = sum(plan)
    packed = np.zeros((c_pad, 2 * CHUNK + 2), np.int32)
    bits = packed.view(np.float32)  # the ratings go in as they are
    for (mv, rt), row, n in zip(neighbor_data, first_row.tolist(),
                                lens.tolist()):
        if index is not None:
            mv = index(mv)
        full, cut = divmod(n, CHUNK)
        cut = n - cut
        if full:
            packed[row:row + full, :CHUNK] = mv[:cut].reshape(full, CHUNK)
            bits[row:row + full, CHUNK:2 * CHUNK] = rt[:cut].reshape(
                full, CHUNK)
        if n > cut:
            packed[row + full, :n - cut] = mv[cut:]
            bits[row + full, CHUNK:CHUNK + n - cut] = rt[cut:]
    if c:
        owner = np.repeat(np.arange(lens.shape[0]), rows_of)
        packed[:c, 2 * CHUNK] = np.minimum(
            lens[owner] - (np.arange(c) - first_row[owner]) * CHUNK, CHUNK)
        packed[:c, 2 * CHUNK + 1] = owner
        packed[c:, 2 * CHUNK + 1] = owner[-1]  # ascending to the end
    count = np.zeros((entities,), np.float32)
    count[:lens.shape[0]] = lens
    cuts = np.cumsum([0] + plan)
    return ([packed[lo:hi] for lo, hi in zip(cuts[:-1], cuts[1:])], count,
            cells)


class FoldIn:
    """One fold-in between its hand-over to the device and its fetch.
    ``fold_in_dispatch`` makes it: the operands are uploaded and the
    route's programs called, all asynchronous; ``fetch`` waits for the
    device and copies the solved rows and the sentinel's word to the host.
    A caller with other work on the device (a request server's scorer)
    fetches once that work has been answered (``StreamSession.pump``).

    ``route`` says which programs ran; ``cells`` is the sum of the touched
    users' lists and ``padded_cells`` what the route's layout really
    gathers and multiplies; ``chunks`` the chunk rows of the cells route (0
    on the padded one).  ``gather_bytes`` is what the route's programs
    gather from the table as it is stored (``table_dtype``): a row of
    ``rank`` elements a padded cell, and its float32 scale where the table
    has one.  ``entities`` x ``width`` is ``_padded_fold``'s rectangle and
    ``operand_bytes`` what went up for it: 0 where it did not run."""

    def __init__(self, out, touched: int, fixed, *, route: str,
                 cells: int, padded_cells: int, chunks: int = 0,
                 entities: int = 0, width: int = 0,
                 operand_bytes: int = 0) -> None:
        data, scale = table_parts(fixed)
        self._out = out
        self.touched, self.route = touched, route
        self.rank = int(data.shape[-1])
        self.cells, self.padded_cells, self.chunks = cells, padded_cells, chunks
        self.entities, self.width = entities, width
        self.table_dtype = str(data.dtype)
        self.gather_bytes = padded_cells * (
            self.rank * data.dtype.itemsize + (0 if scale is None else 4))
        self.operand_bytes = operand_bytes

    def counts(self) -> dict:
        """What a span says of the fold-in's work."""
        return dict(route=self.route, cells=self.cells,
                    padded_cells=self.padded_cells, chunks=self.chunks,
                    entities=self.entities, width=self.width,
                    gather_bytes=self.gather_bytes,
                    table_dtype=self.table_dtype)

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
    cells_entities: int = 8,
) -> FoldIn:
    """Hand one fold-in (``neighbor_data`` not empty) to the device, by the
    route its lists take (``fold_route``), and return without waiting for
    it; ``FoldIn.fetch`` has the rows.  ``norm_limit`` is the health
    sentinel's bound on a solved row's norm (the word's non-finite bit
    needs none).  ``cells_entities``: the least entity bucket of the cells
    route, which a session pins to the most users a micro-batch can touch
    so that the route's programs are one set."""
    t = len(neighbor_data)
    static = dict(lam=float(lam), solver=solver, reg_solve_algo=reg_solve_algo)
    if fold_route(neighbor_data) == "padded":
        with span("stream/batch/upload") as sp:
            host = _rectangle(neighbor_data, pad_multiple, index)
            operands = tuple(map(jnp.asarray, host))
            e, p = host[0].shape
            nbytes = sum(o.nbytes for o in host)
            sp.set(entities=e, width=p, bytes=nbytes)
        fold = FoldIn(None, t, movie_factors, route="padded",
                      cells=int(host[3].sum()), padded_cells=e * p,
                      entities=e, width=p, operand_bytes=nbytes)
        with span("stream/batch/solve", touched=t,
                  solve_route=solve_route(solver, fold.rank),
                  **fold.counts()), \
                span("stream/batch/solve/dispatch"):
            fold._out = _padded_fold(
                movie_factors, *operands, np.int32(t),
                np.float32(norm_limit), **static)
        return fold
    e = _pow2_ceil(t, max(cells_entities, 8))
    with span("stream/batch/upload") as sp:
        slabs, count, cells = _chunk_rows(neighbor_data, e, index)
        nbytes = sum(s.nbytes for s in slabs) + count.nbytes
        slabs = [jnp.asarray(s) for s in slabs]
        count = jnp.asarray(count)
        chunks = sum(s.shape[0] for s in slabs)
        sp.set(chunks=chunks, slabs=len(slabs), bytes=nbytes)
    fold = FoldIn(None, t, movie_factors, route="cells", cells=cells,
                  padded_cells=chunks * CHUNK, chunks=chunks)
    with span("stream/batch/solve", touched=t,
              solve_route=solve_route(solver, fold.rank), **fold.counts()), \
            span("stream/batch/solve/dispatch"):
        acc = _zero_systems(e, fold.rank)
        for slab in slabs:
            acc = _cells_fold_gram(movie_factors, slab, *acc)
        fold._out = _cells_fold_solve(
            *acc, count, np.int32(t), np.float32(norm_limit), **static)
    return fold


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
    programs solve the identical operands (``fold_in_dispatch``, either
    route) — so the solved rows are BIT-IDENTICAL to ``fold_in_rows`` over
    the full device-resident table (the gather reads the same values;
    masked cells contribute exact zeros; the shapes are unchanged, so the
    batched solve bits are too).  The window row count buckets to pow2
    (min 8) so a long-running stream converges onto the same handful of
    compiled programs the resident path enjoys; pad slots replicate
    window row 0 (masked out — exact zero contribution).

    Kept (ROADMAP D16, PR 41): ``StreamSession._dispatch`` calls it for
    every ``offload_tier='host_window'`` session, and the offload tests run
    that path; it is a staging step in front of the two routes, not a
    third program.

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
