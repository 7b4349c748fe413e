"""ServeEngine: live factors + seen lists behind the score+top-K kernel.

The stateful core of the request server — everything between "a batch of
user rows" and "[B, K] ids+scores":

- the item factor table, padded to the kernel's tile grid, quantized per
  ``ALSConfig.table_dtype`` (``ops.quant``) and kept device-resident (it
  is read every request; re-uploading 30 MB per query would dominate):
  on one device, or — ``shards=`` / ``mesh=`` — row-sharded over a mesh.
  It is taken as an array or as a row reader (``row_reader``) and goes up
  device by device and slice by slice (``_upload``), an int8 table's
  codes made on the host's cores by the written rule, so that a catalogue
  past one chip's memory — or past one chip's memory in float32 — is
  never whole in float32 on any device or in any host buffer of the
  engine's.  Over a mesh each chip then scans its
  slice and builds its own slice of the exclusion rectangle from the
  batch's replicated cell list; ``u`` and that list are all a batch sends
  to every chip, and one all_gather of the [B, K] selections merges them
  (``parallel.spmd.serve_topk_sharded``),
- the user factor source: a base snapshot taken at attach time plus a
  HOT-ROW OVERLAY — the factor rows most recently re-solved by streaming
  fold-in commits.  ``StreamSession`` publishes every commit through
  ``attach_session``'s listener; the event carries COPIES of the solved
  rows, applied under the engine lock, so a concurrently-scoring batch
  reads either the old or the new row, never a torn half-write (the
  serving side never reaches into the session's mutable arrays),
- the seen-list CSR for exclusion, with the same overlay treatment: a
  commit's (user, movie) cells append to the overlay so a just-rated
  movie disappears from that user's recommendations at the next request,
- pow2 request-batch bucketing: batches pad to a power of two (and the
  seen rectangle width is pow2 from ``group_seen_cells``), so live
  traffic converges onto a handful of compiled programs instead of
  re-tracing per batch — the same trick PR 6 used for fold-in shapes,
- the exclusion rectangle built on the device: the host groups the
  batch's seen cells at tile boundaries (a few thousand entries) and
  hands over that list whole, padded to a number of pieces from a short
  ladder (1, 2, 4, 8, 16: ``SEEN_PIECE_RUNGS``), a piece's size depending
  on the padded batch size alone; ONE run of one scatter program fills,
  scatters and lays out the [NT, B, W] rectangle where the scorer reads
  it, whatever the batch's cells (a list cut into pieces, each a run of
  its own, paid a pass or two over the 299 MB rectangle a piece: PERF.md
  section 6, PR 42).  A list past the top rung runs the top program
  again on its own result, so ``prewarm``'s batch-size ladder, walked
  over the rungs, closes the program set whatever the data,
- departments (``item_department=``, one int an item row): the table is
  laid out sorted by department, a stable permutation of the item rows
  (none where they come sorted), so that a department is one range of
  rows, ``department_range``; the seen lists are mapped into the layout
  once at load and every answer is given in the caller's item rows.  A
  batch that names a department (``stage(..., department=)``) is scored
  over that range alone: the exclusion rectangle is built over the range's
  tiles, padded to a rung of a short ladder of lengths (powers of two of
  the scorer's slabs), and the scorer's grid runs that rung
  (``topk_scores_counted(rows=, grid_tiles=)``); a batch that names none
  scans the whole table as before, in the same engine,
- a batch in two halves (``TopKBatch``): ``stage`` gathers, groups and
  uploads and captures the table; ``compute`` hands one batch to the
  device and fetches another's answer, the same one for ``topk``, the one
  a step older for a request server under a backlog, which so keeps the
  device busy under its own host work.
"""

from __future__ import annotations

import bisect
import functools
import threading

import numpy as np

from cfk_tpu.serving.topk_kernel import (
    NUM_COUNTS,
    SEEN_PIECE_RUNGS,
    _pow2_ceil,
    chunk_seen_cells,
    group_seen_cells,
    scatter_seen_cells,
    score_passes,
    seen_cell_capacity,
    seen_piece_rung,
    range_slabs,
    range_tiles,
    slab_tiles,
    topk_scores_counted,
)
from cfk_tpu.telemetry import span
from cfk_tpu.utils.search import csr_find


def pad_table(table: np.ndarray, tile_m: int, shards: int = 1) -> np.ndarray:
    """Zero-pad item rows to a multiple of ``shards × tile_m`` (the padding
    rows are masked by the kernel's global ``num_movies`` bound)."""
    quantum = tile_m * max(shards, 1)
    m_pad = -(-table.shape[0] // quantum) * quantum
    if m_pad == table.shape[0]:
        return table
    out = np.zeros((m_pad, table.shape[1]), table.dtype)
    out[: table.shape[0]] = table
    return out


# Float32 bytes of item rows that one slice of a sliced table upload reads,
# stages on the host and hands to the device (2,097,152 rows at rank 128).
_SLICE_BYTES = 1 << 30


def row_reader(movie_factors):
    """``read(lo, hi)`` → rows [lo, hi) of the item factors as a float32
    numpy array: ``movie_factors`` itself where it is such a callable (a
    table made, loaded or exported block by block, never whole on the
    host), else slices of the array it is."""
    if callable(movie_factors):
        return lambda lo, hi: np.asarray(movie_factors(lo, hi), np.float32)
    return lambda lo, hi: np.asarray(movie_factors[lo:hi], np.float32)


def _device_bytes_limit(device):
    """What the runtime lets one device hold, where it says (a TPU does,
    the CPU backend does not: None)."""
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("bytes_limit")


@functools.lru_cache(maxsize=1)
def _land_fn():
    """Jitted ``buf[lo:lo + len(piece)] = piece`` on the buffer's device,
    the buffer donated: a slice lands in place, no second table beside it."""
    import jax

    return jax.jit(
        lambda buf, piece, lo: jax.lax.dynamic_update_slice_in_dim(
            buf, piece, lo, 0),
        donate_argnums=0)


class ServeEngine:
    """Score top-K requests against live factors.

    ``seen_movies``/``seen_indptr`` (per-user-row CSR of rated movie rows,
    sorted ascending per user — ``Dataset.coo_dense`` order after a stable
    user sort) enables exclude-seen; None serves without exclusion.
    """

    def __init__(
        self,
        user_factors,  # [U, k] (np or jax; snapshot is taken)
        movie_factors,  # [M_pad0, k], or read(lo, hi) -> rows (row_reader)
        *,
        num_users: int,
        num_movies: int,
        seen_movies=None,
        seen_indptr=None,
        table_dtype: str | None = None,
        tile_m: int = 512,
        batch_quantum: int = 8,
        mesh=None,  # the caller's own; or
        shards: int | None = None,  # a mesh over the first `shards` devices
        item_department=None,  # [num_movies] ints: each item row's
    ) -> None:
        from cfk_tpu.config import enable_compile_cache
        from cfk_tpu.ops.quant import resolve_table_dtype

        # Before the first compile: a restarted server replays its serve
        # programs from the persistent cache.
        enable_compile_cache()
        self.num_movies = int(num_movies)
        self.num_users = int(num_users)
        self.table_dtype = resolve_table_dtype(table_dtype)
        self.tile_m = int(tile_m)
        self.batch_quantum = int(batch_quantum)
        if shards is not None:
            if mesh is not None:
                raise ValueError("pass one of mesh/shards, not both")
            from cfk_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(int(shards))
        self.mesh = mesh
        self._shards = 1 if mesh is None else int(mesh.devices.size)
        self._lock = threading.RLock()
        self._u_base = np.asarray(user_factors, np.float32)[:num_users]
        self._u_hot: dict[int, np.ndarray] = {}
        if (seen_movies is None) != (seen_indptr is None):
            raise ValueError(
                "pass both of seen_movies/seen_indptr or neither"
            )
        # The table's layout: row r of it is item row ``_to_item[r]``, item
        # row i lies at ``_to_layout[i]`` (both None: the item order), and
        # department d is the layout's rows ``_ranges[d]``.
        self._to_item = self._to_layout = None
        self._ranges: dict[int, tuple[int, int]] = {}
        if item_department is not None:
            self._lay_out(item_department)
        self._seen_indptr = (
            None if seen_indptr is None
            else np.asarray(seen_indptr, np.int64)
        )
        self._seen_movies = (
            None if seen_movies is None
            else self._seen_in_layout(np.asarray(seen_movies, np.int32))
        )
        # row -> the items the stream added to the user's list, ascending,
        # none of them in the base slice (``_extend_seen``)
        self._seen_hot: dict[int, list[int]] = {}
        self._set_table(movie_factors)
        self.invalidations = 0
        self.table_swaps = 0
        # Fleet state (ISSUE 18): the factor-table epoch every response is
        # stamped with (bumped on each full-table swap), and the readiness
        # flag behind /readyz — an engine is live from construction but
        # READY only once prewarm() has traced the batch-bucket set.
        self.epoch = 0
        # The last stream commit applied (``on_commit``): a batch is staged
        # against the user rows and seen lists as of this ordinal, captured
        # with them under the lock, and its answers name it.
        self.commit_ordinal = 0
        self.prewarmed = False

    @property
    def ready(self) -> bool:
        """Readiness (vs liveness): prewarmed AND an epoch table loaded —
        the /readyz signal and the fleet's rollover gate."""
        return bool(self.prewarmed and getattr(self, "_table", None)
                    is not None)

    def load_state(self, user_factors, movie_factors=None, *,
                   hot_rows=None, seen_cells=None, num_users=None,
                   epoch=None) -> None:
        """Atomically replace the live user-side state (and optionally the
        item table) from an epoch snapshot — the fleet replica's resync
        seam (ISSUE 18).  ``user_factors`` becomes the new base snapshot,
        ``hot_rows`` ({row: factor row}) the new overlay, ``seen_cells``
        ((user_row, movie_row) pairs) rebuild the seen overlay from
        scratch; ``movie_factors``/``epoch`` additionally swap the item
        table (a cross-epoch resync).  All under the engine lock, so a
        concurrently scoring batch reads entirely-old or entirely-new
        state, never a mixture."""
        with self._lock:
            self._u_base = np.asarray(user_factors, np.float32)
            self._u_hot = (
                {int(r): np.asarray(f, np.float32)
                 for r, f in hot_rows.items()} if hot_rows else {}
            )
            self._seen_hot = {}
            self._extend_seen(seen_cells or ())
            if num_users is not None:
                self.num_users = int(num_users)
            if movie_factors is not None:
                self._set_table(movie_factors)
                self.table_swaps += 1
            if epoch is not None:
                self.epoch = int(epoch)

    # -- departments ---------------------------------------------------------

    def _lay_out(self, item_department) -> None:
        """The layout by department: a stable sort of the item rows by their
        department, the identity (no permutation is kept, no row moves)
        where they come sorted, which is looked at, not assumed."""
        dept = np.asarray(item_department)
        if (dept.shape != (self.num_movies,)
                or not np.issubdtype(dept.dtype, np.integer)
                or (dept.size and dept.min() < 0)):
            raise ValueError(
                "item_department is one non-negative int an item row, "
                f"[{self.num_movies}]; got {dept.dtype} {dept.shape}")
        if self.mesh is not None:
            raise ValueError(
                "departments are ranges of one device's table: over a mesh "
                f"of {self._shards} devices a range crosses shards, each of "
                "which would scan its own piece of it (ROADMAP Queue 2: "
                "not built); serve departments from one chip, or the "
                "sharded table without them")
        if dept.size > 1 and np.any(dept[1:] < dept[:-1]):
            self._to_item = np.argsort(dept, kind="stable").astype(np.int32)
            self._to_layout = np.empty_like(self._to_item)
            self._to_layout[self._to_item] = np.arange(
                dept.size, dtype=np.int32)
            dept = dept[self._to_item]
        ids, starts = np.unique(dept, return_index=True)
        ends = np.append(starts[1:], dept.size)
        self._ranges = {int(d): (int(lo), int(hi))
                        for d, lo, hi in zip(ids, starts, ends)}

    def _seen_in_layout(self, seen_movies):
        """The seen lists' item rows as layout rows, ascending within each
        user again: once, at load."""
        if self._to_layout is None:
            return seen_movies
        rows = self._to_layout[seen_movies].astype(np.int64)
        owner = np.repeat(np.arange(self._seen_indptr.size - 1),
                          np.diff(self._seen_indptr))
        # one sort of (user, layout row) keys puts every list in order
        rows += owner * self.num_movies
        rows.sort()
        rows -= owner * self.num_movies
        return rows.astype(np.int32)

    @property
    def departments(self) -> tuple[int, ...]:
        """The departments this engine can restrict an answer to."""
        return tuple(self._ranges)

    def department_range(self, department: int) -> tuple[int, int]:
        """Rows ``[lo, hi)`` of the table's layout that hold the
        department's items."""
        try:
            return self._ranges[int(department)]
        except KeyError:
            raise ValueError(
                f"no department {department}: this engine "
                + (f"holds departments {sorted(self._ranges)}"
                   if self._ranges else "was given no item_department")
            ) from None

    # -- table ---------------------------------------------------------------

    def _set_table(self, movie_factors) -> None:
        """Upload ``movie_factors`` — a [num_movies, k] array, or a callable
        ``(lo, hi)`` → rows [lo, hi) as float32 (``row_reader``) — as the
        live item table, its rows in the layout's order."""
        read = row_reader(movie_factors)
        if self._to_item is not None:
            if callable(movie_factors):
                raise ValueError(
                    "a row reader hands the table over in item order, and "
                    "these departments are not sorted by item row: give "
                    "the factors as an array, or item_department sorted")
            read = lambda lo, hi: np.asarray(
                movie_factors[self._to_item[lo:hi]], np.float32)
        # one atomic reference swap: a batch in flight keeps the table
        # it captured; the next batch sees the new one
        self._table = self._upload(read, whole=not callable(movie_factors))

    def _upload(self, read, *, whole: bool):
        """(data, scale) on the device, or row-sharded over the mesh, from
        ``read(lo, hi)``: device by device and, within a device's rows,
        slice by slice.  A slice is read, zero-padded where it reaches past
        the catalogue, quantized where the rule is IEEE — int8 on the
        host's cores (``ops.quant.quantize_rows_host``), so codes go up, a
        quarter of the bytes; a bfloat16 table is cast on its device — and
        landed in the device's preallocated [rows, k] table by a donated
        update, with at most two slices in flight.  Neither the host nor a
        device ever holds the table whole in float32.

        ``whole`` (the caller handed an array) and a float32 table: what
        goes up is the table itself, so a device's rows go up in one piece,
        straight from the caller's array where they are a contiguous slice
        of it (all but a last, padded share): no staging on either side."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from cfk_tpu.ops.quant import (
            quantize_rows_host, quantize_table, table_itemsize)
        from cfk_tpu.parallel.mesh import AXIS

        shards, rank = self._shards, int(self._u_base.shape[1])
        int8 = self.table_dtype == "int8"
        per = -(-self.num_movies // (self.tile_m * shards)) * self.tile_m
        step = max(_SLICE_BYTES // (4 * rank), 1)
        if whole and self.table_dtype == "float32":
            step = per
        step = min(step, per)
        devices = ([None] if self.mesh is None
                   else list(self.mesh.devices.flat))
        held = per * (rank * table_itemsize(self.table_dtype) + 4 * int8)
        in_flight = 0 if step == per else 2 * step * rank * (1 if int8 else 4)
        limit = _device_bytes_limit(devices[0])
        if limit is not None and held + in_flight > limit:
            raise ValueError(
                f"the item table does not fit its device: {per:,} rows x "
                f"{rank} as {self.table_dtype} are {held:,} B"
                + (f" (+ {in_flight:,} B of slices in flight)"
                   if in_flight else "")
                + f" against {limit:,} B; shard it over more chips "
                "(shards=) or quantize it (table_dtype=)")
        dtype = jnp.dtype(self.table_dtype)
        datas, scales, slices = [], [], 0
        with span("serve/engine/table_upload", shards=shards,
                  rows_per_shard=per, rows_per_slice=step,
                  table_dtype=self.table_dtype,
                  quantized_on={"int8": "host", "bfloat16": "device"}.get(
                      self.table_dtype, "none")) as sp:
            for d, device in enumerate(devices):
                data = scale = None
                if step < per:
                    data = jnp.zeros((per, rank), dtype, device=device)
                    scale = (jnp.ones((per,), jnp.float32, device=device)
                             if int8 else None)
                for lo in range(d * per, (d + 1) * per, step):
                    n = min(step, (d + 1) * per - lo)
                    hi = min(lo + n, self.num_movies)
                    rows = (read(lo, hi) if hi > lo
                            else np.zeros((0, rank), np.float32))
                    staged = (rows.shape[0] < n
                              or not rows.flags.c_contiguous)
                    if staged:
                        stage = np.zeros((n, rank), np.float32)
                        stage[:rows.shape[0]] = rows
                        rows = stage
                    if int8:
                        q, s = quantize_rows_host(rows)
                        piece = (jax.device_put(q, device),
                                 jax.device_put(s, device))
                    else:
                        # committed to its chip, so the cast runs there too
                        piece = quantize_table(
                            jax.device_put(rows, device), self.table_dtype)
                    if step == per:
                        data, scale = piece
                        if staged:  # at most one staging copy at a time
                            jax.block_until_ready(data)
                    else:
                        # the slice before this one has landed (and its
                        # device buffer is free) before this one is: two
                        # in flight at most, one going up, one landing
                        jax.block_until_ready(data)
                        data = _land_fn()(data, piece[0], lo - d * per)
                        if int8:
                            scale = _land_fn()(scale, piece[1], lo - d * per)
                    slices += 1
                datas.append(data)
                scales.append(scale)
            jax.block_until_ready((datas, scales))
            if self.mesh is None:
                data, scale = datas[0], scales[0]
            else:
                sharding = NamedSharding(self.mesh, P(AXIS))
                data = jax.make_array_from_single_device_arrays(
                    (per * shards, rank), sharding, datas)
                scale = None if not int8 else (
                    jax.make_array_from_single_device_arrays(
                        (per * shards,), sharding, scales))
            sp.set(slices=slices,
                   bytes=data.nbytes + (0 if scale is None else scale.nbytes))
        return data, scale

    @property
    def table_rows(self) -> int:
        return int(self._table[0].shape[0])

    def fold_table(self):
        """The item table as a fold-in gathers from it: ``(data, scale)`` as
        this engine holds it on its one device, the very buffers the scorer
        scans (a ``StreamSession(engine=...)`` keeps no copy of its own and
        makes none): [M_pad, k] float32 or bfloat16 rows with ``scale``
        None, or int8 codes with the [M_pad] float32 scale of each row.
        The same tuple until the table is swapped.  A fold-in dequantizes
        the rows it gathers (``ops.solve.gather_rows``) and solves float32
        normal equations against them: the table a user's row is solved
        against is the table the user is scored against.  A table
        row-sharded over a mesh is refused: a fold-in gathers whole rows
        on one device."""
        if self.mesh is not None:
            raise ValueError(
                "a fold-in gathers whole item rows on one device; this "
                f"engine's table (table_dtype={self.table_dtype!r}) is "
                f"row-sharded over {self._shards} devices, and a gather "
                "that crosses shards is the half of ROADMAP R8 (a) still "
                "open: give the session a table of its own (no engine=)")
        if self._to_item is not None:
            raise ValueError(
                "a fold-in gathers item rows by their item number, and this "
                "engine's table is laid out by department (item_department "
                "came unsorted, so row r of it is not item r): give the "
                "departments sorted by item row, or the session a table of "
                "its own (no engine=)")
        with self._lock:
            return self._table

    def user_base(self) -> np.ndarray:
        """The base user table as the engine holds it (by reference, never
        written): what a ``StreamSession(engine=...)`` resumed from its
        store lays its solved rows over, so that neither its store nor its
        resume holds a second copy."""
        with self._lock:
            return self._u_base

    # -- live-update listener ------------------------------------------------

    def attach_session(self, session) -> None:
        """Subscribe to a ``StreamSession``'s commits: fold-in rows refresh
        the hot-row overlay, rated cells extend the seen overlay, retrains
        swap the whole table.  Fired AFTER each durable commit, so a
        request served after the commit returns reflects it."""
        session.add_commit_listener(self.on_commit)

    def on_commit(self, event: dict) -> None:
        """Apply one commit event (see ``StreamSession._fire_commit``)."""
        with self._lock:
            rows = event.get("rows")
            touched = event.get("touched_rows") or ()
            if rows is not None:
                for i, row in enumerate(touched):
                    self._u_hot[int(row)] = np.array(rows[i], np.float32)
                self.invalidations += len(touched)
            self._extend_seen(event.get("cells") or ())
            self.num_users = max(self.num_users,
                                 int(event.get("num_users", self.num_users)))
            self.commit_ordinal = max(
                self.commit_ordinal, int(event.get("stream_step", 0)))
            # Item-side per-row deltas: a commit that ships re-solved
            # MOVIE rows updates the table in place.
            mrows = event.get("movie_rows")
            if mrows is not None and not event.get("retrain"):
                self.apply_movie_deltas(mrows, event["movie_row_factors"])
            if event.get("retrain"):
                # a warm retrain re-solves EVERY row: drop the overlay and
                # re-snapshot both sides
                self._u_base = np.asarray(
                    event["user_factors"], np.float32
                )[: self.num_users]
                self._u_hot.clear()
                self._set_table(event["movie_factors"])
                self.table_swaps += 1
                self.epoch += 1

    def apply_movie_deltas(self, rows, factors) -> int:
        """Update item factor rows IN PLACE in the table.

        Quantization is per-row (``ops.quant``), so a delta row's
        codes+scale are bit-identical to what a full-table requantization
        would produce.  Returns the rows applied."""
        import jax.numpy as jnp

        from cfk_tpu.ops.quant import quantize_rows_host, quantize_table

        rows = np.asarray(rows, np.int64)
        f = np.asarray(factors, np.float32)
        keep = (rows >= 0) & (rows < self.num_movies)
        rows, f = rows[keep], f[keep]
        if rows.size == 0:
            return 0
        if self._to_layout is not None:
            rows = self._to_layout[rows]
        if self.table_dtype == "int8":
            # where the table's own codes were made (``_upload``)
            qd, qs = map(jnp.asarray, quantize_rows_host(f))
        else:
            qd, qs = quantize_table(jnp.asarray(f), self.table_dtype)
        with self._lock:
            data, scale = self._table
            set_rows = _set_rows_fn(data.sharding)
            data = set_rows(data, rows, qd.astype(data.dtype))
            if scale is not None:
                scale = set_rows(scale, rows, qs)
            self._table = (data, scale)
        return int(rows.size)

    # -- request path --------------------------------------------------------

    def _gather_users(self, user_rows: np.ndarray) -> np.ndarray:
        u = np.zeros((user_rows.shape[0], self._u_base.shape[1]), np.float32)
        base_n = self._u_base.shape[0]
        for i, row in enumerate(user_rows):
            hot = self._u_hot.get(int(row))
            if hot is not None:
                u[i] = hot
            elif row < base_n:
                u[i] = self._u_base[row]
            # else: streamed-in user with no commit yet → zero row
        return u

    def _extend_seen(self, cells) -> None:
        """A commit's rated (user row, item row) cells into the seen
        overlay: per user one ascending list of the items the stream added
        that the base list does not hold (one lookup for the whole commit),
        kept in order here, once a commit, so a batch reads it as it
        stands."""
        cells = np.asarray(list(cells), np.int64).reshape(-1, 2)
        if self._to_layout is not None and cells.shape[0]:
            # the lists are kept in the layout's rows; an item the table
            # does not hold stays past its end, where the scorer drops it
            known = cells[:, 1] < self.num_movies
            cells[known, 1] = self._to_layout[cells[known, 1]]
        if self._seen_movies is not None and cells.shape[0]:
            cells = cells[csr_find(self._seen_indptr, self._seen_movies,
                                   cells[:, 0], cells[:, 1]) < 0]
        for row, movie in cells.tolist():
            have = self._seen_hot.get(row)
            if have is None:
                self._seen_hot[row] = [movie]
                continue
            at = bisect.bisect_left(have, movie)
            if at == len(have) or have[at] != movie:
                have.insert(at, movie)

    def _batch_seen(self, user_rows: np.ndarray):
        """Per-batch CSR = base slice ⊕ hot overlay, sorted per user: the
        base slices in one gather; where users of the batch have an overlay
        (disjoint from its base and ascending: ``_extend_seen``) the runs
        between them are copied whole and each of theirs takes one sorted
        insert, so what runs in Python follows the hot users, not the
        lists."""
        if self._seen_movies is None and not self._seen_hot:
            return None
        rows = np.asarray(user_rows, np.int64)
        n = rows.shape[0]
        lo = np.zeros(n, np.int64)
        size = np.zeros(n, np.int64)
        if self._seen_movies is not None:
            based = rows < self._seen_indptr.shape[0] - 1
            lo[based] = self._seen_indptr[rows[based]]
            size[based] = self._seen_indptr[rows[based] + 1] - lo[based]
        base_ptr = np.zeros(n + 1, np.int64)
        np.cumsum(size, out=base_ptr[1:])
        total = int(base_ptr[-1])
        # cell j of slot i's base slice: seen_movies[lo[i] + j]
        base = (self._seen_movies[np.repeat(lo - base_ptr[:-1], size)
                                  + np.arange(total)]
                if total else np.zeros(0, np.int32))
        hot = ([(i, self._seen_hot[r]) for i, r in enumerate(rows.tolist())
                if r in self._seen_hot] if self._seen_hot else [])
        if not hot:
            return base, base_ptr
        grow = np.zeros(n + 1, np.int64)
        for i, extra in hot:
            grow[i + 1] = len(extra)
        indptr = base_ptr + np.cumsum(grow)
        movies = np.empty(int(indptr[-1]), np.int32)
        done = 0  # slots copied so far
        for i, extra in hot:
            movies[indptr[done]:indptr[i]] = base[base_ptr[done]:base_ptr[i]]
            mine = base[base_ptr[i]:base_ptr[i + 1]]
            movies[indptr[i]:indptr[i + 1]] = np.insert(
                mine, np.searchsorted(mine, extra), extra)
            done = i + 1
        movies[indptr[done]:] = base[base_ptr[done]:]
        return movies, indptr

    def topk(self, user_rows, k: int, *, exclude_seen: bool = True,
             stamp: dict | None = None, department: int | None = None):
        """(scores [n, k] f32, movie rows [n, k] int32) for the requested
        user rows, over the whole catalogue or, with ``department``, over
        that department's items alone.  The batch is padded to the pow2
        quantum (padding rows score with a zero factor vector and are
        sliced off), so request coalescing shares compiled programs across
        batch sizes.

        The two halves of one ``TopKBatch``, back to back: ``stage`` and
        the hand-over to the device, then the fetch.  The request server
        runs the same halves one step apart (``compute``).  ``stamp``, a
        dict, receives the ``epoch`` and the commit ``ordinal`` the batch
        was staged against."""
        batch = self.stage(user_rows, k, exclude_seen=exclude_seen,
                           department=department)
        if stamp is not None:
            stamp.update(epoch=batch.epoch, ordinal=batch.ordinal)
        return compute(batch, batch)

    def stage(self, user_rows, k: int, *, exclude_seen: bool = True,
              warm: bool = False,
              department: int | None = None) -> "TopKBatch":
        """The host's part of ``topk``'s front half: gather the user rows,
        group the seen cells, upload both.  The ``TopKBatch`` it returns
        owns the table it will be scored against (captured under the lock
        with the user rows and the epoch) and everything its answer needs,
        so a table swapped before the fetch changes nothing for it: no
        donation, a batch in flight keeps its table.  ``compute`` hands it
        to the device and fetches it.  ``warm`` is ``prewarm``'s: the
        batch's cell list is padded past the top rung, so that it runs
        both of that rung's programs, and the lower rungs' run beside it
        (``_warm_seen_rungs``): every program the rectangle of such a
        batch can take.

        ``department`` makes it a ranged batch: the one scan it shares is
        of the department's rows (``department_range``).  Only the cells of
        its users' lists that lie in the range are grouped, the rectangle
        covers the range's slabs padded to their rung (``_range_rung``),
        tile 0 the first tile of the range's first slab, and the scorer is
        handed the range's rows and the rung.  Such a rectangle is small,
        so its cell list goes up a piece a run of the scatter program (the
        program that starts a rectangle, then the one that adds to it): two
        programs a (batch size, rung) where the whole table's ladder has
        six."""
        user_rows = np.asarray(user_rows, dtype=np.int64)
        n = user_rows.shape[0]
        rows = None if department is None else self.department_range(
            department)
        if n == 0:
            return TopKBatch(self, n=0, k=k, epoch=self.epoch,
                             ordinal=self.commit_ordinal, result=(
                np.zeros((0, k), np.float32), np.zeros((0, k), np.int32)))
        if np.any((user_rows < 0) | (user_rows >= self.num_users)):
            bad = user_rows[(user_rows < 0)
                            | (user_rows >= self.num_users)][:5]
            raise ValueError(
                f"user rows out of range [0, {self.num_users}): {bad}"
            )
        if not 1 <= k <= self.num_movies:
            raise ValueError(f"k must be in [1, {self.num_movies}], got {k}")
        b = _pow2_ceil(n, self.batch_quantum)
        with span("serve/batch/assemble", n=n, b=b) as sp:
            with self._lock:
                table, scale = self._table
                epoch, ordinal = self.epoch, self.commit_ordinal
                u = np.zeros((b, self._u_base.shape[1]), np.float32)
                u[:n] = self._gather_users(user_rows)
                seen = self._batch_seen(user_rows) if exclude_seen else None
            movies = indptr_pad = None
            if seen is not None:
                movies, indptr = seen
                # padding slots carry EMPTY seen lists (repeat the last
                # indptr entry), not user 0's — aliasing the heaviest user
                # into every pad slot would inflate the seen-rectangle
                # width for rows whose output is sliced off anyway
                if rows is not None:
                    # the cells of the range alone: each list is ascending,
                    # so what is kept of it is too
                    keep = (movies >= rows[0]) & (movies < rows[1])
                    indptr = np.concatenate(
                        ([0], np.cumsum(keep)))[indptr]
                    movies = movies[keep]
                indptr_pad = np.concatenate(
                    [indptr, np.full(b - n, indptr[-1], np.int64)]
                )
                sp.set(seen_cells=len(movies))
        tiles = table.shape[0] // self.tile_m
        seen = shape = grid_tiles = None
        if movies is not None:
            with span("serve/batch/seen_tiles") as sp:
                cells, shape = group_seen_cells(
                    movies, indptr_pad, np.arange(b),
                    num_movies=self.num_movies,
                    tile_m=self.tile_m, num_tiles=tiles,
                )
                if rows is not None:
                    # the rectangle of the range's rung, from the first
                    # tile of its first slab
                    first, grid_tiles = self._range_grid(
                        rows, b, shape[2], table, k)
                    cells[0] -= first
                    shape = (grid_tiles,) + shape[1:]
                seen = _seen_chunks(sp, cells, shape, warm, rows is not None)
                if self.mesh is not None:
                    # how many of the cells each chip keeps for its slice
                    sp.set(shard_cells=np.bincount(
                        cells[0] // (shape[0] // self._shards),
                        minlength=self._shards).tolist())
        # the calls that hand the batch to the runtime; they may return
        # before the bytes have landed, and the fetch then waits for the
        # transfer as well as for the scorer
        with span("serve/batch/upload") as sp:
            nbytes = u.nbytes
            if seen is not None:
                nbytes += sum(c.nbytes for c in seen)
            if self.mesh is not None:
                sp.set(shards=self._shards,
                       replicated_bytes=nbytes * self._shards)
            if seen is not None:
                seen = [_put(c, self.mesh) for c in seen]
            u = _put(u, self.mesh)
            sp.set(bytes=nbytes)
        if warm and seen is not None and rows is None:
            self._warm_seen_rungs(shape)
        # what the fetch will say of the batch on ``serve/batch/compute``
        shard_tiles = tiles // self._shards
        slab = slab_tiles(shard_tiles, b, 0 if shape is None else shape[2],
                          u.shape[1], table.dtype, tile_m=self.tile_m,
                          k_top=k)
        counters = dict(
            n=n, b=b, k=k, tiles=tiles,
            # the tiles one grid step of the scorer streams and folds, and
            # the steps that makes of the table, all shards'
            slab_tiles=slab,
            grid_steps=-(-shard_tiles // slab) * self._shards,
            # what the scorer streams from HBM for the batch, all shards':
            # the table as it is held, and its scales
            table_dtype=self.table_dtype,
            scan_bytes=table.nbytes + (0 if scale is None else scale.nbytes),
            # the MXU passes the fold runs over a completed tile of such
            # a table (one on every other: ``completed_tiles``)
            score_passes=score_passes(table.dtype))
        if self.mesh is not None:
            counters.update(shards=self._shards,
                            merge_candidates=self._shards * k)
        if rows is not None:
            if grid_tiles is None:  # no exclusion: no rectangle
                _, grid_tiles = self._range_grid(rows, b, 0, table, k)
            # the tiles that hold a row of the range are those scanned;
            # the grid runs its rung's, the rest shut
            scanned = range_tiles(*rows, self.tile_m)
            counters.update(
                department=int(department), range_rows=rows[1] - rows[0],
                tiles=scanned, grid_tiles=grid_tiles,
                grid_steps=grid_tiles // slab,
                scan_bytes=scanned * (counters["scan_bytes"] // tiles))
        return TopKBatch(
            self, n=n, k=k, epoch=epoch, ordinal=ordinal, counters=counters,
            operands=(u, table, scale, seen, shape, rows, grid_tiles))

    def _range_grid(self, rows, b, seen_width, table, k):
        """(the table's tile that a ranged batch's rectangle starts at, the
        tiles its grid runs): the range's first slab, and its slabs padded
        to their rung, at the slab the scorer will choose for these shapes
        (``slab_tiles``)."""
        g = slab_tiles(table.shape[0] // self.tile_m, b, seen_width,
                       table.shape[1], table.dtype, tile_m=self.tile_m,
                       k_top=k)
        first, last = range_slabs(rows[0], rows[1], g, self.tile_m)
        return first * g, _range_rung(int(last) - first + 1) * g

    def _seen_tiles(self, chunks, shape):
        """The [NT, B, W] exclusion rectangle on the device and which of
        its tiles hold a cell (a ``SeenTiles``), from the batch's uploaded
        cell list (``_seen_chunks``; None = no exclusion): one run of the
        scatter program, which starts from a fresh all-padding rectangle;
        a list past the ladder's top rung comes as several arrays and each
        further one runs the top rung's program on the rectangle so far.
        Every caller, on one device or over a mesh, gets its rectangle
        here, from the one ``scatter_seen_cells``; over a mesh each chip
        builds the tiles it scans
        (``parallel.spmd.serve_seen_tiles_sharded``)."""
        if self.mesh is None:
            build = _seen_tiles_jit_fn()
        else:
            from cfk_tpu.parallel.spmd import serve_seen_tiles_sharded

            build = functools.partial(serve_seen_tiles_sharded, self.mesh)
        seen_tiles = None
        for cells in chunks or ():
            seen_tiles = build(cells, seen_tiles, shape=shape,
                               tile_m=self.tile_m)
        return seen_tiles

    def _warm_seen_rungs(self, shape) -> None:
        """``prewarm``'s: the rectangle's program of every rung under the
        top, run once over an all-padding list and waited for, so that no
        two of their rectangles are alive at once.  The top rung's two
        programs, the one that starts a rectangle and the one that adds to
        it, are the warm batch's own (``_seen_chunks``)."""
        import jax

        nt, b, _ = shape
        none = np.zeros((4, 0), np.int32)
        for rung in SEEN_PIECE_RUNGS[:-1]:
            (pad,) = chunk_seen_cells(none, rung * seen_cell_capacity(b), nt)
            jax.block_until_ready(
                self._seen_tiles([_put(pad, self.mesh)], shape))

    @property
    def trace_count(self) -> int:
        """Serve-program traces this PROCESS (engines share the jitted
        entry, so this is a process-wide counter — delta it around a
        call, as ``prewarm`` does)."""
        return trace_count()

    def prewarm(self, k: int, *, max_batch: int | None = None,
                user_rows=None, exclude_seen: bool = True,
                departments=None) -> dict:
        """Trace (and compile) the pow2 batch-bucket program set up
        front (ISSUE 13), so the first REAL request batch after attach
        pays zero traces — the cold-process counterpart of the pow2
        bucketing that already bounds steady-state re-traces (PR 6/8).

        Walks the batch-quantum ladder ``q, 2q, ... pow2_ceil(max_batch)``
        and scores a representative batch at each size (``user_rows``
        when given — pass a workload sample so the seen-rectangle widths
        it produces match live traffic — else the first users of the
        table; results are discarded, and the jit cache keys on shapes
        only, so bit-exactness is untouched).  The XLA compile behind
        each new trace is also served from the persistent cache
        (``config.enable_compile_cache``) — a warm restart pays neither.
        Returns
        ``{"programs", "new_traces", "prewarm_s"}``; a later batch whose
        (padded size, seen width) bucket was covered here traces
        nothing, which ``tests/test_staging.py`` pins.  An engine that
        was given departments also runs, at each size, a ranged batch of
        every rung that the ranges of ``departments`` take (all it holds
        where none are named; a deployment whose traffic names a few warms
        those)."""
        import time as _time

        with span("serve/prewarm", k=k, max_batch=max_batch):
            t0 = _time.time()
            top = _pow2_ceil(max(max_batch or self.batch_quantum, 1),
                             self.batch_quantum)
            if user_rows is None:
                rows = np.arange(min(top, self.num_users), dtype=np.int64)
            else:
                rows = np.asarray(user_rows, dtype=np.int64)
            if rows.size == 0:
                return {"programs": 0, "new_traces": 0, "prewarm_s": 0.0}
            before = trace_count()
            programs = 0

            b = self.batch_quantum
            while b <= top:
                take = rows[: min(b, rows.size)]
                # pad by REPEATING the sample rather than truncating the
                # bucket: topk pads to _pow2_ceil(n, quantum), so a short
                # sample still traces the intended batch size
                if take.size < b:
                    take = np.resize(take, b)
                batch = self.stage(take, k, exclude_seen=exclude_seen,
                                   warm=True)
                compute(batch, batch)
                programs += 1
                # an engine that was given departments: the ranged
                # programs of this batch size, one a rung that some
                # department's range takes (and a rectangle's width)
                warmed = set()
                for department in (self._ranges if departments is None
                                   else departments):
                    batch = self.stage(take, k, exclude_seen=exclude_seen,
                                       warm=True, department=department)
                    if batch.statics not in warmed:
                        warmed.add(batch.statics)
                        compute(batch, batch)
                        programs += 1
                b *= 2
            self.prewarmed = True  # the /readyz gate flips here
            return {
                "programs": programs,
                "new_traces": trace_count() - before,
                "prewarm_s": round(_time.time() - t0, 4),
            }


class TopKBatch:
    """One batch of ``ServeEngine.topk`` between its two halves.

    ``ServeEngine.stage`` makes it; ``dispatch`` hands it to the device
    (the jitted scatter over the batch's cell list, then the scorer:
    asynchronous calls that return at once) and ``fetch`` waits for the
    answer and copies it to the host.  The fetch reads the batch alone and
    nothing of the engine, so whatever the engine became in between (a
    commit, a delta, a table swap, another engine in its server's place)
    the answer is that of the table, the ``epoch`` and the commit
    ``ordinal`` the batch was staged against.  The empty batch (no rows)
    needs no device: it is made with its ``result`` and both halves pass
    it through."""

    def __init__(self, engine, *, n, k, epoch, ordinal=0, counters=None,
                 operands=None, result=None) -> None:
        self.engine = engine
        self.n, self.k = n, k
        self.epoch, self.ordinal = epoch, ordinal
        self.counters = counters
        self.result = result
        self._operands = operands
        self._out = None

    @property
    def statics(self):
        """What, beside the batch's padded size and K, chooses its programs:
        the rectangle's shape and a ranged batch's rung."""
        return self._operands[4], self._operands[6]

    @property
    def on_device(self) -> bool:
        """Handed to the device and not fetched yet."""
        return self._out is not None

    @property
    def failed(self) -> bool:
        """Its fetch raised: nothing is left to answer it from."""
        return (self._operands is None and self._out is None
                and self.result is None)

    def dispatch(self) -> None:
        eng = self.engine
        u, table, scale, seen, shape, rows, grid_tiles = self._operands
        seen_tiles = eng._seen_tiles(seen, shape)
        if rows is not None:
            # the same entry, the range's rows as two scalars and its rung
            # as a static: a family of programs beside the whole table's
            out = _topk_jit_fn()(
                u, table, scale, seen_tiles, np.asarray(rows, np.int32),
                k_top=self.k, num_movies=eng.num_movies, tile_m=eng.tile_m,
                grid_tiles=grid_tiles,
            )
        elif eng.mesh is not None:
            from cfk_tpu.parallel.spmd import serve_topk_sharded

            out = serve_topk_sharded(
                eng.mesh, u, table, scale, seen_tiles, k_top=self.k,
                num_movies=eng.num_movies, tile_m=eng.tile_m,
            )
        else:
            out = _topk_jit_fn()(
                u, table, scale, seen_tiles, k_top=self.k,
                num_movies=eng.num_movies, tile_m=eng.tile_m,
            )
        # the runtime keeps what the programs read
        self._operands, self._out = None, out

    def fetch(self, sp):
        """(scores [n, k], movie rows [n, k]) on the host; ``sp`` is the
        open ``serve/batch/compute`` span, which takes the batch's
        counters."""
        (vals, ids, counts), self._out = self._out, None
        with span("serve/batch/compute/fetch") as fetch:
            vals, ids = np.asarray(vals), np.asarray(ids)
            # a row a shard: the host adds them, no collective
            counts = np.asarray(counts).reshape(-1, NUM_COUNTS).sum(axis=0)
            fetch.set(bytes=vals.nbytes + ids.nbytes)
        # what the data made this batch cost, over every tile scanned
        # (all shards'): selection rounds run and tiles that ran any,
        # exclusion chunks run and tiles that ran any, tiles on which
        # every one of ``score_passes`` ran (an int8 or a float32 tile
        # behind a shut first gate runs one, and no mask)
        sp.set(select_rounds=int(counts[0]), select_tiles=int(counts[1]),
               seen_chunks=int(counts[2]), seen_hit_tiles=int(counts[3]),
               completed_tiles=int(counts[4]), **self.counters)
        vals, ids = vals[:self.n], ids[:self.n]
        to_item = self.engine._to_item
        if to_item is not None:
            # the layout's rows back to the caller's item rows (an empty
            # slot stays -1)
            ids = np.where(ids >= 0, to_item[np.maximum(ids, 0)], ids)
        self.result = vals, ids
        return self.result


def compute(dispatch: TopKBatch | None, fetch: TopKBatch | None):
    """One ``serve/batch/compute`` span: hand ``dispatch`` to the device,
    then fetch ``fetch``'s answer and return it (None without one).
    ``ServeEngine.topk`` passes one batch as both; the request server under
    a backlog passes the batch it has just polled and the one it handed
    over a step ago, whose scorer ran under the host's stages since.  The
    span's counters are the fetched batch's.  The empty batch, which
    ``stage`` returns already answered, opens no span."""
    to_device = dispatch is not None and dispatch.result is None
    from_device = fetch is not None and fetch.result is None
    if to_device or from_device:
        with span("serve/batch/compute") as sp:
            if to_device:
                with span("serve/batch/compute/dispatch"):
                    dispatch.dispatch()
            if from_device:
                fetch.fetch(sp)
    return None if fetch is None else fetch.result


def _seen_chunks(sp, cells: np.ndarray, shape, warm: bool = False,
                 ranged: bool = False):
    """One batch's cell list as ``ServeEngine._seen_tiles`` takes it once
    uploaded: one array, the list padded from its ``chunks`` pieces of
    ``capacity`` cells up to the next rung of ``SEEN_PIECE_RUNGS``, so the
    columns scattered stay under twice the cells past one piece; a list
    past the top rung, and ``prewarm``'s (``warm``: one piece past it), as
    several arrays of the top rung's size.  A ``ranged`` batch's list, the
    cells of one department, takes the ladder's first rung alone: a piece
    an array (``prewarm``'s: two).  Its ``serve/batch/seen_tiles``
    span says what the device will build ([tiles, b, width] int32), what
    the host built for it (``bytes``), the real ``cells`` among them and
    how many runs of the scatter program (``programs``) the batch will
    cost."""
    nt, b, width = shape
    capacity = seen_cell_capacity(b)
    top = SEEN_PIECE_RUNGS[0 if ranged else -1]
    pieces = max(-(-cells.shape[1] // capacity), top + 1 if warm else 1)
    rung = min(seen_piece_rung(pieces), top)
    runs = chunk_seen_cells(cells, rung * capacity, nt, -(-pieces // rung))
    sp.set(tiles=nt, b=b, width=width, cells=cells.shape[1],
           capacity=capacity, chunks=pieces, programs=len(runs),
           bytes=sum(c.nbytes for c in runs))
    return runs


# Trace counter (ISSUE 13): bumped once per TRACE of the serve program
# (the body below runs only while jax traces a new (B, W, K) variant), so
# prewarm() can prove its contract — zero new traces on the first real
# batch — and the bench rows can report trace_count next to
# time-to-first-batch.
_TRACES = [0]


def trace_count() -> int:
    """Traces of the serve programs this process: the exact scan and the
    seen-rectangle scatter, on one device or as shard programs over a
    mesh."""
    return _TRACES[0]


def note_trace() -> None:
    """One more trace of a serve program that lives outside this module
    (``parallel.spmd``'s shard programs)."""
    _TRACES[0] += 1


def _topk_call(u, table, scale, seen_tiles, rows=None, *, k_top, num_movies,
               tile_m, grid_tiles=None):
    _TRACES[0] += 1
    return topk_scores_counted(
        u, table, scale, seen_tiles, k_top=k_top, num_movies=num_movies,
        tile_m=tile_m, rows=rows, grid_tiles=grid_tiles,
    )


def _range_rung(slabs: int) -> int:
    """The slabs a ranged scan's grid runs for a range that lies in
    ``slabs`` of them: the next power of two.  Ranges of every length then
    share a dozen programs a batch size (a program costs ~0.1 s of every
    warm start, seconds of a cold one: PERF.md section 7, row 25), and what
    the padding costs is a step shut by one scalar compare and a rectangle
    under twice the range's (PERF.md section 6, PR 52)."""
    return _pow2_ceil(slabs)


def _seen_tiles_call(cells, seen_tiles, *, shape, tile_m):
    _TRACES[0] += 1
    return scatter_seen_cells(cells, seen_tiles, shape=shape, tile_m=tile_m)


@functools.lru_cache(maxsize=1)
def _seen_tiles_jit_fn():
    """Jitted seen-rectangle scatter: per (B, W) bucket one program a rung
    of ``SEEN_PIECE_RUNGS`` that starts a rectangle and, at the top rung,
    one that adds to the rectangle it is given (donated, so no second
    299 MB lives beside it)."""
    import jax

    return jax.jit(
        _seen_tiles_call, static_argnames=("shape", "tile_m"),
        donate_argnames=("seen_tiles",),
    )


@functools.lru_cache(maxsize=1)
def _topk_jit_fn():
    """Jitted single-device entry — with pow2 batch/width bucketing, live
    traffic converges onto a handful of (B, W, K) program variants."""
    import jax

    return jax.jit(
        _topk_call,
        static_argnames=("k_top", "num_movies", "tile_m", "grid_tiles"),
    )


@functools.lru_cache(maxsize=8)
def _set_rows_fn(sharding):
    """Jitted row update that leaves the table placed as it was: a
    row-sharded table stays row-sharded."""
    import jax

    return jax.jit(lambda data, rows, vals: data.at[rows].set(vals),
                   out_shardings=sharding)


def _put(x, mesh):
    """``x`` from the host onto the device, or (``u`` and the cell list are
    all a batch sends there) onto every chip of ``mesh``."""
    import jax
    import jax.numpy as jnp

    if mesh is None:
        return jnp.asarray(x)
    return jax.device_put(x, _replicated(mesh))


@functools.lru_cache(maxsize=8)
def _replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def engine_from_model(model, dataset=None, *, table_dtype=None, tile_m=512,
                      mesh=None, shards=None, batch_quantum=8,
                      item_department=None) -> ServeEngine:
    """Build an engine from an ``ALSModel`` (+ optional dataset/index whose
    ``coo_dense`` provides the exclude-seen lists; + optional
    ``item_department``, one int a dense item row)."""
    seen_movies = seen_indptr = None
    if dataset is not None:
        coo = dataset.coo_dense
        order = np.argsort(
            coo.user_raw * (dataset.movie_map.num_entities + 1)
            + coo.movie_raw, kind="stable",
        )
        seen_movies = coo.movie_raw[order].astype(np.int32)
        counts = np.bincount(
            coo.user_raw.astype(np.int64),
            minlength=dataset.user_map.num_entities,
        )
        seen_indptr = np.zeros(dataset.user_map.num_entities + 1, np.int64)
        np.cumsum(counts, out=seen_indptr[1:])
    u, m = model.user_factors, model.movie_factors
    if not getattr(u, "is_fully_addressable", True):
        from cfk_tpu.parallel.mesh import to_host

        u, m = to_host(u), to_host(m)
    return ServeEngine(
        np.asarray(u), np.asarray(m),
        num_users=model.num_users, num_movies=model.num_movies,
        seen_movies=seen_movies, seen_indptr=seen_indptr,
        table_dtype=table_dtype, tile_m=tile_m, mesh=mesh, shards=shards,
        batch_quantum=batch_quantum, item_department=item_department,
    )
