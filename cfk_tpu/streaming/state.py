"""Deduplicated per-user rating state: the idempotency layer of fold-in.

The fold-in solve is stateless per user — it re-derives a touched user's
factor row from that user's COMPLETE current ratings against the fixed
movie factors — so applying the same logical update twice, or applying two
updates to the same cell in either order, must converge to the same state.
``StreamState`` provides exactly that: the merge of the base dataset's
ratings and every applied ``(user, movie, rating, seq)`` upsert, with
last-seq-wins per (user, movie) cell (equal seq = a retried append,
dropped).  ``seq`` is the EVENT's sequence number, not its place in the log:
a producer that is handed the events' own numbers
(``StreamProducer.send_many(seqs=)``) may append them in any order, and a
record that arrives after a newer one of its cell is outranked by the
cell's applied ``seq``: counted ``stale``, consumed and committed with its
batch (its offset is under the unit's cursor), and changes nothing.

A list is read as ARRAYS: the base CSR slice merged with the cells the
stream changed (the user's delta, a staged overlay) by numpy, so what runs
in Python is proportional to the cells that changed, never to the list (a
reviewer with 10,000 items costs a slice, a ``searchsorted`` and an
insert); ``stage`` looks the one cell of each record up.

Nothing here is persisted by the state itself: it is a deterministic
function of (base dataset, the cells every commit unit of the store applied),
so crash recovery rebuilds it from the store's own cells (``CELL`` arrays, in
commit order: ``load_overlay``), never from the log below the cursor.

Application is TRANSACTIONAL: ``stage()`` computes the post-batch view
without mutating anything, the session solves and probes against it, and
only a healthy solve ``commit()``s — a poisoned micro-batch is discarded
wholesale, leaving both the served factors and the state they were solved
from untouched.

Base ratings carry seq −1 (every streamed update outranks the batch file);
new users grow the user table in first-appearance order within the
canonical batch order, which makes row assignment replay-deterministic.
Updates naming a movie the model has never seen have no factor column to
solve against — they are counted and dropped (``unknown_movie``), to be
picked up when the operator retrains from base + log.
"""

from __future__ import annotations

import dataclasses
import weakref

import numpy as np

from cfk_tpu.transport.serdes import RatingUpdate
from cfk_tpu.utils.search import bisect_slices, csr_find

_BASE_SEQ = -1
_NO_MOVIES = np.zeros(0, np.int32)
_NO_RATINGS = np.zeros(0, np.float32)

# One applied cell as the store holds it: a commit unit's, a snapshot's.
CELL = np.dtype([("row", "<i8"), ("movie", "<i4"), ("rating", "<f4"),
                 ("seq", "<i8")])


def cells_array(cell_writes: dict) -> np.ndarray:
    """A staged batch's writes (row -> {movie_row: (rating, seq)}) as a
    ``CELL`` array, in the order the dicts hold them."""
    flat = [(row, mv, rt, seq) for row, overlay in cell_writes.items()
            for mv, (rt, seq) in overlay.items()]
    return np.array(flat, CELL) if flat else np.zeros(0, CELL)


def last_per_cell(cells: np.ndarray) -> np.ndarray:
    """``cells`` (in the order they were applied) with one entry a (row,
    movie): the last applied, sorted by row then movie."""
    if not cells.shape[0]:
        return cells
    key = (cells["row"].astype(np.int64) << 32) | cells["movie"].astype(np.int64)
    order = np.argsort(key, kind="stable")
    ks = key[order]
    last = np.ones(ks.shape[0], bool)
    last[:-1] = ks[1:] != ks[:-1]
    return cells[order[last]]


def _ascending_lists(indptr: np.ndarray, movies: np.ndarray) -> bool:
    """Every list of the CSR strictly ascending (no item twice): one pass."""
    n = movies.shape[0]
    if n < 2:
        return True
    ok = movies[1:] > movies[:-1]
    starts = indptr[1:-1]
    ok[starts[(starts > 0) & (starts < n)] - 1] = True  # between two lists
    return bool(ok.all())


# The (indptr, item rows) pairs ``_ascending_lists`` has passed, held weakly:
# a state is built over the same arrays again by every successor of a killed
# stream task, inside the serving window, and the pass reads every cell (0.9 s
# for 144 M of them).  A base CSR is never written once a state holds it.
_PASSED: list[tuple[weakref.ref, weakref.ref]] = []


def _canonical_csr(indptr, movies, ratings):
    """The CSR with every list strictly ascending: itself (by reference)
    where it already is, else a copy sorted by (row, item) with the LAST
    cell of a repeated item kept (a ``Dataset``'s order, a repeated
    observation)."""
    _PASSED[:] = [(i, m) for i, m in _PASSED
                  if i() is not None and m() is not None]
    if any(i() is indptr and m() is movies for i, m in _PASSED):
        return indptr, movies, ratings
    if _ascending_lists(indptr, movies):
        _PASSED.append((weakref.ref(indptr), weakref.ref(movies)))
        return indptr, movies, ratings
    n = movies.shape[0]
    rows = np.repeat(np.arange(indptr.shape[0] - 1, dtype=np.int64),
                     np.diff(indptr))
    order = np.lexsort((np.arange(n), movies, rows))
    r, m = rows[order], movies[order]
    last = np.ones(n, bool)
    last[:-1] = (r[1:] != r[:-1]) | (m[1:] != m[:-1])
    keep = order[last]
    out = np.zeros(indptr.shape[0], np.int64)
    np.cumsum(np.bincount(r[last], minlength=indptr.shape[0] - 1),
              out=out[1:])
    return out, movies[keep], ratings[keep]


@dataclasses.dataclass
class ApplyStats:
    """What one batch application did — chaos tests assert these fired."""

    fresh: int = 0          # state-changing upserts applied
    stale: int = 0          # outranked by an already-applied seq (dup/reorder)
    rerated: int = 0        # of the fresh: the cell already held a value
    unknown_movie: int = 0  # no factor column for this movie — dropped
    new_users: int = 0      # rows grown for first-seen users


@dataclasses.dataclass(frozen=True)
class PendingApply:
    """A staged (not yet committed) batch application."""

    touched_rows: tuple[int, ...]          # sorted dense user rows to re-solve
    new_user_raw: tuple[int, ...]          # raw ids of rows grown, in order
    cell_writes: dict                      # row -> {movie_row: (rating, seq)}
    stats: ApplyStats


def overlay_of(row: int, pendings) -> dict | None:
    """What the given staged batches, in their order, write to ``row``
    (movie_row -> (rating, seq)), to be read only; None where none of them
    writes it."""
    merged = None
    for pending in pendings:
        cells = pending.cell_writes.get(row)
        if cells:
            merged = cells if merged is None else {**merged, **cells}
    return merged


class StreamState:
    """Merged base + streamed rating state, queryable per user row.

    Built from a ``Dataset`` (the CLI, the tests: raw ids through both id
    maps) or, ``from_csr``, from the per-user CSR a serving deployment
    already holds, with identity id maps: a corpus the block builder cannot
    hold never has to become a ``Dataset`` to take a stream."""

    def __init__(self, dataset) -> None:
        coo = dataset.coo_dense  # dense-index COO
        num_users = dataset.user_map.num_entities
        # Per-user CSR over the base ratings (built once, never mutated):
        # streamed deltas overlay it per touched user.
        order = np.argsort(coo.user_raw, kind="stable")
        counts = np.bincount(coo.user_raw.astype(np.int64),
                             minlength=num_users)
        indptr = np.zeros(num_users + 1, np.int64)
        np.cumsum(counts, out=indptr[1:])
        self._init(
            indptr, coo.movie_raw[order].astype(np.int32),
            coo.rating[order].astype(np.float32),
            num_movies=dataset.movie_map.num_entities,
            user_raw=dataset.user_map.raw_ids,
            movie_raw=dataset.movie_map.raw_ids,
        )

    @classmethod
    def from_csr(cls, indptr, movies, ratings, *, num_movies: int
                 ) -> "StreamState":
        """The state over a per-user CSR of base ratings (``indptr``
        [U + 1], item rows and ratings per cell, taken by reference where
        every list is ascending with no item twice, which one pass checks),
        raw ids = rows on both sides.  A list may hold an item twice or out
        of order; the state then keeps a sorted copy in which the later
        cell wins, as with a ``Dataset``."""
        indptr = np.asarray(indptr, np.int64)
        movies = np.asarray(movies, np.int32)
        ratings = np.asarray(ratings, np.float32)
        if (indptr.ndim != 1 or indptr.size < 1 or indptr[0] != 0
                or indptr[-1] != movies.shape[0]
                or movies.shape != ratings.shape):
            raise ValueError(
                f"not a CSR: indptr {indptr.shape} ends at "
                f"{indptr[-1] if indptr.size else None}, {movies.shape} item "
                f"rows, {ratings.shape} ratings")
        self = cls.__new__(cls)
        self._init(indptr, movies, ratings, num_movies=int(num_movies),
                   user_raw=None, movie_raw=None)
        return self

    def fresh(self) -> "StreamState":
        """A state nothing has been applied to, over this one's base (the
        same arrays, by reference): what a successor session starts from
        before it loads the store's overlay."""
        new = type(self).__new__(type(self))
        new._init(self._base_indptr, self._base_movies, self._base_ratings,
                  num_movies=self.num_movies, user_raw=self._base_user_raw,
                  movie_raw=self._movie_raw, canonical=True)
        return new

    def _init(self, indptr, movies, ratings, *, num_movies, user_raw,
              movie_raw, canonical: bool = False) -> None:
        # every base list strictly ascending, one cell an item: what every
        # read below relies on, established here, once
        if not canonical:
            indptr, movies, ratings = _canonical_csr(indptr, movies, ratings)
        self._base_indptr = indptr
        self._base_movies = movies
        self._base_ratings = ratings
        self.num_movies = int(num_movies)
        # None = identity: a raw id is its row
        self._base_user_raw = user_raw
        self._movie_raw = movie_raw
        self._num_base_users = int(indptr.shape[0] - 1)
        # Streamed overlay: row -> {movie_row: (rating, seq)}; rows past the
        # base user count are streamed-in new users.
        self._delta: dict[int, dict[int, tuple[float, int]]] = {}
        self._new_user_raw: list[int] = []
        self._new_user_rows: dict[int, int] = {}
        self.applied_seq_high = _BASE_SEQ

    # -- identity ------------------------------------------------------------

    @property
    def num_base_users(self) -> int:
        return self._num_base_users

    @property
    def num_users(self) -> int:
        return self.num_base_users + len(self._new_user_raw)

    def user_row(self, raw: int) -> int | None:
        """Dense row of a raw user id, or None if never seen."""
        got = self._new_user_rows.get(int(raw))
        if got is not None:
            return got
        if self._base_user_raw is None:
            return int(raw) if 0 <= raw < self._num_base_users else None
        i = int(np.searchsorted(self._base_user_raw, raw))
        if i < self.num_base_users and int(self._base_user_raw[i]) == int(raw):
            return i
        return None

    def user_raw_ids(self) -> np.ndarray:
        """Raw ids in row order (base ascending, then streamed new users)."""
        base = (np.arange(self._num_base_users, dtype=np.int64)
                if self._base_user_raw is None else self._base_user_raw)
        return np.concatenate([
            base, np.asarray(self._new_user_raw, np.int64),
        ]) if self._new_user_raw else base

    def _movie_raw_ids(self) -> np.ndarray:
        return (np.arange(self.num_movies, dtype=np.int64)
                if self._movie_raw is None else self._movie_raw)

    def movie_row(self, raw: int) -> int | None:
        if self._movie_raw is None:
            return int(raw) if 0 <= raw < self.num_movies else None
        i = int(np.searchsorted(self._movie_raw, raw))
        if i < self.num_movies and int(self._movie_raw[i]) == int(raw):
            return i
        return None

    # -- queries -------------------------------------------------------------

    def _written(self, row: int, movie: int, over=()) -> tuple | None:
        """(rating, seq) of one cell as the stream wrote it, or None: the
        newest of the staged batches ``over`` that writes it, else the
        delta."""
        for pending in reversed(over):
            got = pending.cell_writes.get(row)
            if got is not None and movie in got:
                return got[movie]
        got = self._delta.get(row)
        if got is not None and movie in got:
            return got[movie]
        return None

    def _held(self, row: int, movie: int, over=()) -> tuple | None:
        """(rating, seq) of one cell as the applied state holds it once
        the staged batches ``over`` are committed, or None: what the stream
        wrote (``_written``), else the base (seq -1)."""
        got = self._written(row, movie, over)
        if got is None:
            at = int(csr_find(self._base_indptr, self._base_movies,
                              np.array([row]), np.array([movie]))[0])
            if at >= 0:
                got = float(self._base_ratings[at]), _BASE_SEQ
        return got

    def neighbors(self, row: int, overlay: dict | None = None
                  ) -> tuple[np.ndarray, np.ndarray]:
        """(movie rows int32 ascending, ratings f32) for one user row:
        base + delta (+ an optional staged overlay for that row).

        Sorted by movie row — the canonical neighbor order, so the solve
        input (and therefore its bits) depends only on the state, never on
        arrival order.
        """
        return self.neighbors_many((row,), (overlay,))[0]

    def neighbors_many(self, rows, overlays
                       ) -> list[tuple[np.ndarray, np.ndarray]]:
        """``neighbors(row, overlay)`` of each row, as views of two flat
        arrays: the rows' base slices laid end to end by one concatenate,
        and the cells the stream changed (the delta, the overlays) merged in
        by ONE search over the whole batch: a changed cell the base holds
        replaces its rating in place, the others are inserted.  What runs in
        Python is proportional to the rows and their CHANGED cells (a
        micro-batch's touched users: one or two each), never to the lists,
        and the numpy calls are a dozen a batch, not a dozen a row."""
        n = len(rows)
        base_n, indptr = self._num_base_users, self._base_indptr
        cuts = [(int(indptr[row]), int(indptr[row + 1]))
                if row < base_n else (0, 0) for row in rows]
        ptr = np.zeros(n + 1, np.int64)
        np.cumsum([hi - lo for lo, hi in cuts], out=ptr[1:])
        if ptr[-1]:
            mv = np.concatenate([self._base_movies[lo:hi] for lo, hi in cuts])
            rt = np.concatenate([self._base_ratings[lo:hi] for lo, hi in cuts])
        else:
            mv, rt = _NO_MOVIES, _NO_RATINGS
        # the changed cells of the batch: (slot, movie row, rating)
        c_len, c_mv, c_rt = [0] * n, [], []
        for slot, (row, overlay) in enumerate(zip(rows, overlays)):
            changed = self._delta.get(row)
            if overlay:
                changed = {**changed, **overlay} if changed else overlay
            if changed:
                c_len[slot] = len(changed)
                c_mv.extend(changed)
                c_rt.extend([cell[0] for cell in changed.values()])
        if c_mv:
            c_slot = np.repeat(np.arange(n), c_len)
            c_mv = np.asarray(c_mv, np.int32)
            order = np.lexsort((c_mv, c_slot))
            c_slot, c_mv = c_slot[order], c_mv[order]
            c_rt = np.asarray(c_rt, np.float32)[order]
            end = ptr[c_slot + 1]
            at = bisect_slices(mv, ptr[c_slot], end, c_mv)
            held = at < end
            held[held] = mv[at[held]] == c_mv[held]
            rt[at[held]] = c_rt[held]  # rt is this call's own copy
            new = ~held
            mv = np.insert(mv, at[new], c_mv[new])
            rt = np.insert(rt, at[new], c_rt[new])
            ptr[1:] += np.cumsum(np.bincount(c_slot[new], minlength=n))
        ends = ptr.tolist()
        return [(mv[lo:hi], rt[lo:hi]) for lo, hi in zip(ends[:-1], ends[1:])]

    def to_coo(self):
        """The merged rating state as a raw-id COO (for warm full retrains:
        base + every committed upsert, exactly what the factors model).

        Rows the stream never touched pass through vectorized (the base
        CSR is one cell an item, ascending: ``_canonical_csr``); the delta
        rows are merged in one ``neighbors_many`` — O(touched) Python work,
        not O(all users), so ML-25M-scale exits and periodic retrains don't
        stall on an interpreter loop."""
        from cfk_tpu.data.blocks import RatingsCOO

        raw_users = self.user_raw_ids()
        counts = np.diff(self._base_indptr)
        base_rows = np.repeat(
            np.arange(self.num_base_users, dtype=np.int64), counts
        )
        sel = np.flatnonzero(~np.isin(
            base_rows, np.fromiter(self._delta, np.int64, len(self._delta))))
        movie_raw = self._movie_raw_ids()
        users = [raw_users[base_rows[sel]]]
        movies = [movie_raw[self._base_movies[sel]].astype(np.int64)]
        ratings = [self._base_ratings[sel]]
        touched = sorted(self._delta)
        for row, (mv, rt) in zip(touched, self.neighbors_many(
                touched, [None] * len(touched))):
            users.append(np.full(mv.shape[0], raw_users[row], np.int64))
            movies.append(movie_raw[mv].astype(np.int64))
            ratings.append(rt)
        return RatingsCOO(
            movie_raw=np.concatenate(movies),
            user_raw=np.concatenate(users),
            rating=np.concatenate(ratings).astype(np.float32),
        )

    # -- transactional application -------------------------------------------

    def stage(self, updates: tuple[RatingUpdate, ...] | list[RatingUpdate],
              over: tuple[PendingApply, ...] | list[PendingApply] = ()
              ) -> PendingApply:
        """Dedup a batch against the applied state WITHOUT mutating it.

        Updates must already be in canonical order (the consumer's
        (partition, offset) order).  Within the batch the same cell may be
        written repeatedly — the highest seq wins; against the applied
        state, only upserts whose seq outranks the cell's current seq are
        fresh.  A user whose batch records are ALL stale is not touched
        (no re-solve — the idempotent no-op for retried appends).

        ``over``: batches staged before this one and not committed yet, in
        their order.  The applied state is read as it will stand once they
        are committed (their cells, their new users' rows), so the result
        is what staging after those commits would have given.
        """
        stats = ApplyStats()
        writes: dict[int, dict[int, tuple[float, int]]] = {}
        new_raw: list[int] = []
        new_rows: dict[int, int] = {}
        next_row = self.num_users
        for earlier in over:
            for raw in earlier.new_user_raw:
                new_rows[raw] = next_row
                next_row += 1
        rows, movies, known = [], [], []
        for upd in updates:
            mv = self.movie_row(upd.movie)
            if mv is None:
                stats.unknown_movie += 1
                continue
            row = self.user_row(upd.user)
            if row is None:
                row = new_rows.get(int(upd.user))
            if row is None:
                row = next_row
                new_rows[int(upd.user)] = row
                new_raw.append(int(upd.user))
                next_row += 1
                stats.new_users += 1
            rows.append(row)
            movies.append(mv)
            known.append(upd)
        # the base's cells among them, in one lookup for the batch
        in_base = (csr_find(self._base_indptr, self._base_movies,
                            np.asarray(rows, np.int64),
                            np.asarray(movies, np.int64)) >= 0).tolist()
        for row, mv, upd, based in zip(rows, movies, known, in_base):
            mine = writes.get(row)
            current = mine.get(mv) if mine else None
            if current is None:
                current = self._written(row, mv, over)
            seq = (current[1] if current is not None
                   else _BASE_SEQ if based else None)
            if seq is not None:
                if upd.seq <= seq:
                    stats.stale += 1
                    continue
                stats.rerated += 1
            if mine is None:
                mine = writes[row] = {}
            mine[mv] = (float(upd.rating), int(upd.seq))
            stats.fresh += 1
        return PendingApply(
            touched_rows=tuple(sorted(writes)),
            new_user_raw=tuple(new_raw),
            cell_writes=writes,
            stats=stats,
        )

    def load_overlay(self, cells: np.ndarray, new_users) -> None:
        """Install what a store's commits applied over the base, on a state
        nothing has been applied to yet: ``cells`` (``CELL``, in the order
        they were applied, a later cell of a (row, movie) replacing an
        earlier one) and the raw ids of the streamed-in users in the order
        their rows were grown.  The cells are cut to one a (row, movie) by
        a sort, then entered in one pass: the state a live session would
        hold, so the batches after it cost what they cost that one."""
        if self._delta or self._new_user_raw:
            raise ValueError("load_overlay needs a state nothing was applied to")
        for raw in np.asarray(new_users, np.int64).tolist():
            self._new_user_rows[raw] = self.num_users
            self._new_user_raw.append(raw)
        cells = last_per_cell(np.asarray(cells, CELL))
        if not cells.shape[0]:
            return
        delta = self._delta
        for row, mv, rt, seq in zip(
                cells["row"].tolist(), cells["movie"].tolist(),
                cells["rating"].tolist(), cells["seq"].tolist()):
            mine = delta.get(row)
            if mine is None:
                mine = delta[row] = {}
            mine[mv] = (rt, seq)
        self.applied_seq_high = max(self.applied_seq_high,
                                    int(cells["seq"].max()))

    def overlay_cells(self) -> np.ndarray:
        """Every cell applied over the base, one a (row, movie), sorted by
        row then item, as a ``CELL`` array: what a snapshot of the store
        keeps of this state."""
        return last_per_cell(cells_array(self._delta))

    def commit(self, pending: PendingApply) -> None:
        """Fold a staged batch into the applied state."""
        for raw in pending.new_user_raw:
            self._new_user_rows[raw] = self.num_users
            self._new_user_raw.append(raw)
        for row, cells in pending.cell_writes.items():
            self._delta.setdefault(row, {}).update(cells)
            self.applied_seq_high = max(
                self.applied_seq_high, max(s for _, s in cells.values())
            )
