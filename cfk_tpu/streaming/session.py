"""The rate → fold-in loop: exactly-once streaming updates into live factors.

``StreamSession`` closes the loop the reference only sketched: ratings
arrive continuously on a durable updates topic, micro-batches of touched
users are folded into the live factor state by one restricted ALS
half-iteration, and every commit persists what the batch changed
ATOMICALLY WITH the consumer's offset cursor: the rows it solved, the cells
it applied and the cursor are one step of the store, written through
``CheckpointManager``'s atomic directory rename plus crc32 verification
(the PR 3/5 machinery), the cursor in the step's manifest.  A full snapshot
of the tables is written at bootstrap and at a retrain only (a session on
an engine's tables keeps neither table in it); every
``snapshot_every_units`` units the store's second writer thread folds the
units since the last one into an OVERLAY snapshot (the solved rows, the
applied cells, the streamed-in users, the cursor: never the base), so a
resume reads the newest intact overlay snapshot plus the unbroken run of
intact units after it, and costs what changed, not what exists: no byte of
the base tables, no record of the log below the cursor.  There is no
instant at which the factors and the cursor can disagree on disk; a crash
replays exactly the uncommitted log suffix.

READ COMMITTED: a unit is published to the listeners (the serving engine)
once its step is renamed into place, never when it is handed to the
writer, so an ordinal an answer names is a unit of the store, with the
cursor and the rows the engine was given under it; ``pump`` reads the
store's durable watermark and never waits on an fsync.  A successor on
the same store (``RecommendServer``'s supervisor, a restarted process)
publishes only what the engine lacks.

A batch is two halves (``_begin``: poll, stage, the touched users' lists,
the hand-over of the fold-in to the device; ``_finish``: fetch, probe,
apply, commit, publish).  ``step`` runs them back to back; ``pump``, which a
``RecommendServer`` calls between its polls, runs them a call apart, so the
fold-in executes between two scorer calls and the host never waits for it
behind a scorer it did not need.  A stream that has fallen behind its log
has several batches on the device at once, each staged over the ones before
it (``StreamState.stage(over=)``): what is committed, and in which order, is
what ``step`` after ``step`` would have committed, bit for bit.

Delivery semantics, layer by layer:

- **transport** may drop / duplicate / reorder (at-least-once):
  ``StreamConsumer`` heals all three by offset — a batch is a pure
  function of the log.
- **log** may hold retried appends and re-rates: ``StreamState`` dedups by
  (user, movie) seq, last-seq-wins — application is idempotent.
- **math** may be poisoned (singular systems at λ=0, NaN ratings): every
  fold-in is probed by the PR 3 health sentinel BEFORE commit; a tripped
  batch is rolled back (staged state discarded, factors untouched) and the
  recovery ladder escalates (λ bump → split epilogue → GJ) on retry;
  a batch that defeats the whole ladder is quarantined — its offsets are
  consumed (poison must not wedge the stream) but its writes never reach
  the served factors or the state.
- **process** may be evicted: the ``PreemptionGuard`` is polled at batch
  boundaries; eviction drains the async checkpoint writer so the last
  factor+cursor commit is durably on disk, then returns resumable.

Periodic warm-started full retrains (``retrain_every``) rebuild the full
dataset from the merged state and run the resilient stepped training loop
with the CURRENT factors as the starting checkpoint, folding the movie
side's staleness back in without ever serving a cold model.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import functools
import os
import time
import warnings
import zlib

import numpy as np

from cfk_tpu.ops.solve import table_parts
from cfk_tpu.resilience import sentinel as _sentinel
from cfk_tpu.resilience.loop import drain_checkpoints, save_checkpoint
from cfk_tpu.resilience.policy import Overrides, RecoveryPolicy, policy_from_config
from cfk_tpu.streaming.consumer import StreamConsumer
from cfk_tpu.streaming.foldin import (
    CHUNK, SLABS, _pow2_ceil, fold_in_dispatch, fold_in_rows_windowed,
    trace_count)
from cfk_tpu.streaming.producer import UPDATES_TOPIC
from cfk_tpu.streaming.state import (
    CELL, StreamState, cells_array, last_per_cell, overlay_of)
from cfk_tpu.telemetry import record_event, span
from cfk_tpu.telemetry.recorder import dump_flight
from cfk_tpu.transport.checkpoint import (
    ARRAYS, CheckpointCorruptError, CheckpointManager)

_STREAM_MODEL = "als-stream"
# The micro-batches one ``pump`` hands to the device while whole ones still
# wait in the log behind them (a stall, a burst): each costs the requests
# its fold-in's few milliseconds of the device, so a long backlog of ratings
# is drained at about twice their rate without starving the requests.
_PUMP_DEPTH = 3


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Knobs of the streaming loop (model/solver knobs stay on ALSConfig)."""

    topic: str = UPDATES_TOPIC
    # Log records consumed per partition per micro-batch.  Batch boundaries
    # are offsets, so this value is part of the replay contract: it is
    # recorded in every commit and the committed value wins on resume (a
    # changed setting applies only to batches past the committed cursor).
    batch_records: int = 256
    # Warm full retrain every N stream commits (None = never): rebuild the
    # dataset from the merged state and run the resilient training loop
    # warm-started from the current factors.
    retrain_every: int | None = None
    # Re-poll budget for delivery gaps (dropped records must be redelivered
    # by the at-least-once transport; after this many re-polls the session
    # fails loudly instead of hanging like the reference).
    gap_retries: int = 20
    gap_wait_s: float = 0.05
    # Sleep between polls while following an idle topic.
    poll_wait_s: float = 0.05
    # The whole user table (``user_factors``, a snapshot) is rounded up to
    # this many rows once streamed-in users outgrow the base table; the
    # rows themselves live in an appended segment that doubles.
    grow_multiple: int = 64
    # Every this many published units the units since the last overlay
    # snapshot are folded into a new one, on a writer thread of its own:
    # a resume then reads one overlay snapshot and at most about this many
    # units, however long the stream has run.
    snapshot_every_units: int = 256

    def __post_init__(self) -> None:
        if self.batch_records < 1:
            raise ValueError(
                f"batch_records must be >= 1, got {self.batch_records}"
            )
        if self.retrain_every is not None and self.retrain_every < 1:
            raise ValueError(
                f"retrain_every must be >= 1, got {self.retrain_every}"
            )
        if self.grow_multiple < 1:
            raise ValueError(
                f"grow_multiple must be >= 1, got {self.grow_multiple}"
            )
        if self.snapshot_every_units < 1:
            raise ValueError(
                f"snapshot_every_units must be >= 1, got "
                f"{self.snapshot_every_units}"
            )


def jnp_dtype(name):
    import jax.numpy as jnp

    return jnp.dtype(name)


class PoisonedBatchError(RuntimeError):
    """Raised when ``on_unrecoverable='raise'`` and a batch defeats the
    whole recovery ladder."""


class _UserRows:
    """A session's user factor table: the base array as it was handed over
    — never copied, never written, so a serving engine may hold the same
    array — and the rows solved since (re-solved base rows and streamed-in
    users alike) in an appended segment that doubles when it is full: a
    thousand new users allocate a few small segments, not a thousand
    copies of the base."""

    def __init__(self, base: np.ndarray) -> None:
        self.base = base
        self._slot: dict[int, int] = {}
        self._rows = np.zeros((0, base.shape[1]), base.dtype)
        self.allocations = 0  # of the segment; the base is allocated never

    def __len__(self) -> int:
        return len(self._slot)

    def set(self, rows, values) -> None:
        slots = []
        for row in rows:
            slot = self._slot.get(row)
            if slot is None:
                slot = self._slot[row] = len(self._slot)
            slots.append(slot)
        if len(self._slot) > self._rows.shape[0]:
            grown = np.zeros((max(2 * self._rows.shape[0], len(self._slot),
                                  64), self._rows.shape[1]),
                             self._rows.dtype)
            grown[:self._rows.shape[0]] = self._rows
            self._rows = grown
            self.allocations += 1
        self._rows[slots] = values

    def load(self, ids: np.ndarray, rows: np.ndarray) -> None:
        """Install the solved rows a store restored (``ids`` distinct), in
        one copy, on a table nothing has been solved into."""
        self._slot = dict(zip(ids.tolist(), range(ids.shape[0])))
        self._rows = np.array(rows, self.base.dtype)

    def get(self, rows) -> np.ndarray:
        """Copies of the given rows as they stand (zeros for a row nobody
        has solved yet)."""
        out = np.zeros((len(rows), self.base.shape[1]), self.base.dtype)
        for i, row in enumerate(rows):
            slot = self._slot.get(int(row))
            if slot is not None:
                out[i] = self._rows[slot]
            elif row < self.base.shape[0]:
                out[i] = self.base[row]
        return out

    def table(self, num_rows: int) -> np.ndarray:
        """The whole table as one new array of at least ``num_rows`` rows
        (a snapshot, the exit's model: never the per-batch path)."""
        out = np.zeros((max(num_rows, self.base.shape[0]),
                        self.base.shape[1]), self.base.dtype)
        out[:self.base.shape[0]] = self.base
        if self._slot:
            out[list(self._slot)] = self._rows[:len(self._slot)]
        return out


@dataclasses.dataclass
class _Batch:
    """One micro-batch between its poll and its commit."""

    batch: object  # the consumer's StreamBatch
    pending: object  # the state's PendingApply
    solve: tuple  # ``_dispatch``'s: (FoldIn on the device, None), or
    # (None, (rows, the fixed rows to probe)) of a route with no hand-over


@dataclasses.dataclass
class _Unit:
    """One commit unit as the store holds it: handed to the writer by
    ``_finish`` or restored by a resume, published once it is durable."""

    step: int
    meta: dict
    touched: np.ndarray  # int64 [T], the rows solved, ascending
    rows: np.ndarray  # [T, k], in the store's dtype
    cells: np.ndarray  # ``CELL`` [C], the cells applied, in their order
    new_users: np.ndarray  # int64, raw ids of the rows grown, in order
    cursors: dict
    appended: tuple = ()  # when the log took each record, where it says

    @classmethod
    def restored(cls, st, dtype) -> "_Unit":
        return cls(
            step=int(st.meta["stream_step"]), meta=dict(st.meta),
            touched=st.arrays["touched"],
            rows=np.asarray(st.user_factors).astype(dtype, copy=False),
            cells=st.arrays["cells"], new_users=st.arrays["new_users"],
            cursors={int(p): int(o)
                     for p, o in st.meta.get("offsets", {}).items()})

    @classmethod
    def snapshot(cls, st, dtype) -> "_Unit":
        """What a full snapshot holds of the overlay: no solved row (they
        are in its table), the cells applied so far, the users grown."""
        return cls(
            step=st.iteration, meta=dict(st.meta),
            touched=np.zeros(0, np.int64),
            rows=np.zeros((0, int(st.meta["rank"])), dtype),
            cells=st.arrays.get("cells", np.zeros(0, CELL)),
            new_users=st.arrays.get("new_users", np.zeros(0, np.int64)),
            cursors={})

    def arrays(self) -> dict:
        """The payloads of this unit's step beside its rows."""
        return {"touched": self.touched, "cells": self.cells,
                "new_users": self.new_users}

    def event(self) -> dict:
        """What a listener is given of this unit: copies, never views."""
        return {
            "touched_rows": self.touched.tolist(),
            "rows": np.array(self.rows, np.float32),
            "cells": list(zip(self.cells["row"].tolist(),
                              self.cells["movie"].tolist())),
            "cursors": dict(self.cursors),
            "retrain": False,
            "stream_step": self.step,
            "num_users": int(self.meta["users"]),
        }


def _fold(base: _Unit, units) -> _Unit:
    """``base`` with ``units`` (in commit order) applied, as one unit: each
    row's last solve, each cell's last write, the users in the order they
    came, the step, meta and cursor of the last."""
    ids = np.concatenate([base.touched] + [u.touched for u in units])
    rows = np.concatenate([base.rows] + [u.rows for u in units])
    _, at = np.unique(ids[::-1], return_index=True)
    keep = np.sort(ids.shape[0] - 1 - at)
    last = units[-1] if units else base
    return _Unit(
        step=last.step, meta=last.meta, touched=ids[keep], rows=rows[keep],
        cells=last_per_cell(
            np.concatenate([base.cells] + [u.cells for u in units])),
        new_users=np.concatenate(
            [base.new_users] + [u.new_users for u in units]),
        cursors=last.cursors)


def _table_digest(table, num_rows: int) -> dict:
    """What a snapshot records of a base table it does not hold: the rows
    the stream's base covers, the dtype, and a crc32 over 4,096 evenly
    spaced rows of them: enough to tell whether a store's units were solved
    over this table, for a few microseconds a gigabyte (the whole table's
    crc32 is what a resume used to pay: 9 s for 10.75 GB)."""
    n = min(int(num_rows), int(table.shape[0]))
    at = np.unique(
        np.linspace(0, max(n - 1, 0), min(n, 4096)).astype(np.int64))
    return {"rows": n, "dtype": str(table.dtype),
            "crc32": zlib.crc32(np.ascontiguousarray(table[at]).tobytes())
            if n else 0}


@functools.lru_cache(maxsize=None)
def _side_word_fn():
    """The fixed side's sentinel probe as ONE jitted function for the
    process: a successor session probes the table its predecessor probed
    and traces nothing."""
    import jax

    return jax.jit(functools.partial(
        _sentinel.side_word, nonfinite_bit=_sentinel.NONFINITE_M,
        norm_bit=_sentinel.NORM_M))


class StreamSession:
    """Consume rating updates and fold them into live ALS factors.

    ``manager`` (a ``CheckpointManager``-shaped store) is the session's
    system of record.  A full snapshot (user table, item table, cursor,
    stream metadata) is written at bootstrap and at a retrain; every
    micro-batch in between commits ONE UNIT — the solved rows, the cells
    applied and the cursor, through the same atomic rename and crc32 — so
    a commit costs what the batch changed, not the tables.  On construction
    the session either resumes from the store (newest intact overlay
    snapshot, or the full one, plus the unbroken run of intact units after
    it; the rating state rebuilt from their cells) or bootstraps from
    ``base_model`` (committing step 0 with a zero cursor).

    ``dataset`` is a ``Dataset`` or a ``StreamState`` (``from_csr``: a
    deployment that holds its ratings as a CSR and could never build
    blocks of them).  ``engine`` (a ``ServeEngine`` with its table on one
    device, float32, bfloat16 or int8 codes and scales) makes the session
    fold in against the table the engine serves, as the engine holds it
    (``ServeEngine.fold_table``: gathered rows are dequantized, the solve
    is float32) — one item table on the device, one user base on the host,
    both owned by the engine and absent from this store's snapshots — and
    subscribes the engine to the commits.  ``listeners`` are subscribed
    before a resume publishes what the engine lacks.
    """

    def __init__(
        self,
        dataset,
        config,
        transport,
        manager,
        *,
        stream: StreamConfig | None = None,
        base_model=None,
        metrics=None,
        preemption_guard=None,
        policy: RecoveryPolicy | None = None,
        engine=None,
        listeners=(),
    ) -> None:
        from cfk_tpu.config import enable_compile_cache
        from cfk_tpu.utils.metrics import Metrics

        if manager is None:
            raise ValueError(
                "StreamSession needs a checkpoint manager: the offset "
                "cursor commits atomically with the factors, so a durable "
                "store is not optional"
            )
        # Before the first compile (ISSUE 13): a warm persistent cache is
        # what makes a cold fold-in process skip the re-COMPILE half of
        # the per-batch trace bound; prewarm() covers the trace half.
        enable_compile_cache(getattr(config, "compile_cache_dir", None))
        if isinstance(dataset, StreamState):
            self.dataset, self.state = None, dataset
        else:
            self.dataset, self.state = dataset, StreamState(dataset)
        self.config = config
        self.transport = transport
        self.manager = manager
        self.stream = stream or StreamConfig()
        self.metrics = metrics if metrics is not None else Metrics()
        self.guard = preemption_guard
        self.policy = policy or policy_from_config(config)
        self.health = _sentinel.health_from_config(config)
        # Out-of-core sessions (ISSUE 19): with offload_tier='host_window'
        # the movie table lives in a host-resident ``HostFactorStore``
        # (the user table always was host numpy) and every fold-in stages
        # the batch's touched movie rows as one ad-hoc window
        # (``foldin.fold_in_rows_windowed`` — bit-identical rows).  The
        # commit protocol, sentinel ladder, and quarantine semantics below
        # are UNCHANGED: they only ever see the solved rows and the
        # factor arrays at commit time.
        self._offload = (
            getattr(config, "offload_tier", "device") == "host_window"
        )
        self._m_store = None
        self._foldin_stats: dict = {}
        self._engine = engine
        if engine is not None:
            if self._offload:
                raise ValueError(
                    "a session that folds in against a serving engine's "
                    "table gathers from it on the device: not an "
                    "offload_tier='host_window' session")
            if self.stream.retrain_every is not None:
                raise ValueError(
                    "retrain_every needs a Dataset and a table of the "
                    "session's own: retrain offline and start the engine "
                    "and the session from the new model")
            if jnp_dtype(config.dtype) != np.float32:
                raise ValueError(
                    "a fold-in solves float32 normal equations, whatever "
                    "the engine's table stores (its rows are dequantized "
                    f"as they are gathered): config.dtype is "
                    f"{config.dtype!r}")
            engine.fold_table()  # refuses, in words, a table no fold-in reads
        self._overrides = Overrides(
            lam=config.lam, fused_epilogue=config.fused_epilogue,
            reg_solve_algo=(None if config.reg_solve_algo == "auto"
                            else config.reg_solve_algo),
        )
        self.stream_step = 0
        self.quarantined: list[dict] = []
        self._m = None  # jnp [M_pad, k], fixed between retrains
        self._users: _UserRows | None = None
        self._fixed_word = (None, 0)  # (table probed, its sentinel bits)
        # polled and handed over, not committed yet: oldest first
        self._in_flight: collections.deque[_Batch] = collections.deque()
        # Serving-side subscribers (ISSUE 8): fired AFTER each durable
        # commit with copies of the solved rows, so a hot-user factor
        # cache (serving.ServeEngine.attach_session) re-serves fold-in
        # updates without ever reading this session's mutable arrays.
        self._commit_listeners: list = list(listeners)
        if engine is not None:
            engine.attach_session(self)
        # handed to the writer, not published yet: oldest first
        self._unpublished: collections.deque[_Unit] = collections.deque()
        self._durable_step = -1
        self.published_step = 0  # the last unit the listeners were given
        self._abandoned = False
        # Overlay snapshots: a second store under the first, with a writer
        # thread of its own (a 150 MB fold never stands in front of a unit).
        # ``_overlay``: what the newest restore point holds of the overlay
        # (an overlay snapshot's content, or a full snapshot's cells), as
        # one unit, held to fold the next from; ``_since``: the units
        # published after it; ``_overlay_job``: the snapshot being written
        # (its step, its units, where its content lands).
        self._overlay_store = None
        directory = getattr(manager, "directory", None)
        if directory is not None:
            self._overlay_store = CheckpointManager(
                os.path.join(directory, "overlay"), keep_last_n=2,
                max_pending=1)
        self._overlay: _Unit | None = None
        self._since: list[_Unit] = []
        self._overlay_job: tuple | None = None
        self._snapshot_step = 0  # the full snapshot every unit rests on
        # what the last resume read and fired (``RecommendServer`` reports it)
        self.resume_stats: dict = {}
        resumed = self._try_resume()
        if not resumed:
            self._bootstrap(base_model)

    # -- bootstrap / resume --------------------------------------------------

    def _factor_dtype(self):
        return jnp_dtype(self.config.dtype)

    def _set_movie(self, arr) -> None:
        """Install the fixed movie table: a device array normally, a
        host ``HostFactorStore`` in offload mode — SAME bytes either way
        (the store holds the config dtype verbatim), so the staged
        fold-in windows read exactly what the resident path would.  A
        session on an engine's table installs nothing: the engine owns
        the one table there is."""
        import jax.numpy as jnp

        if self._engine is not None:
            return
        if self._offload:
            from cfk_tpu.offload.store import HostFactorStore

            self._m_store = HostFactorStore.from_array(
                np.asarray(arr), dtype=self.config.dtype
            )
            self._m = None
        else:
            self._m = jnp.asarray(np.asarray(arr),
                                  dtype=self._factor_dtype())

    def _bootstrap(self, base_model) -> None:
        if base_model is None:
            raise ValueError(
                "no resumable stream state in the checkpoint store and no "
                "base_model given — train a base model first (train_als) "
                "or point the session at its existing stream directory"
            )
        # by reference where the dtype already fits: the base is never
        # written (``_UserRows``)
        self._users = _UserRows(np.asarray(base_model.user_factors).astype(
            self._factor_dtype(), copy=False))
        self._set_movie(getattr(base_model, "movie_factors", None))
        nparts = self.transport.num_partitions(self.stream.topic)
        self.consumer = StreamConsumer(
            self.transport, topic=self.stream.topic,
            cursors={p: 0 for p in range(nparts)},
            gap_retries=self.stream.gap_retries,
            gap_wait_s=self.stream.gap_wait_s,
        )
        # Step 0 pins the zero cursor atomically with the base factors, so
        # even a crash before the first batch resumes cleanly.
        self._commit_snapshot(note="bootstrap")

    def _restore_step(self, iteration: int, store=None):
        """One step of the store, or None (warned, flight-recorded) where
        it fails its checksums: resume falls back past it."""
        try:
            return (store or self.manager).restore(iteration, mmap=True)
        except CheckpointCorruptError as e:
            warnings.warn(f"skipping corrupt checkpoint: {e}")
            record_event("checkpoint", "corrupt_checkpoint_skipped",
                         iteration=iteration, error=str(e))
            dump_flight("corrupt_checkpoint")
            return None

    def _restorable(self):
        """(snapshot, overlay snapshot or None, [units after them]) — the
        full snapshot every unit rests on, the newest intact overlay
        snapshot over it, and the unbroken run of intact units that
        follows, each one stream step after the last; None on an empty
        store.  A torn unit ends the run: what came after it on disk is the
        uncommitted suffix, replayed from the log.  A torn overlay snapshot
        falls back to the one before it and the longer run after that."""
        steps = self.manager.iterations()
        if not steps:
            return None
        snapshot = overlay = None
        units: dict[int, object] = {}
        if self._overlay_store is not None:
            for it in reversed(self._overlay_store.iterations()):
                st = self._restore_step(it, self._overlay_store)
                if st is not None and int(st.meta["base_step"]) in steps:
                    base = self._restore_step(int(st.meta["base_step"]))
                    if base is not None and base.meta.get("kind") != "unit":
                        snapshot, overlay = base, st
                        break
        if snapshot is None:
            for it in reversed(steps):
                st = self._restore_step(it)
                if st is None:
                    continue
                if st.meta.get("kind") == "unit":
                    units[it] = st
                else:
                    snapshot = st
                    break
        if snapshot is None:
            return None
        run = []
        start = (overlay or snapshot).iteration
        expect = start + 1
        for it in steps[bisect.bisect_right(steps, start):]:
            st = units.get(it) or self._restore_step(it)
            if st is None:
                break
            if st.meta.get("kind") != "unit":
                # a later full snapshot (a retrain's) supersedes all before
                snapshot, overlay, run = st, None, []
                expect = st.iteration + 1
                continue
            if int(st.meta["stream_step"]) != expect:
                break
            run.append(st)
            expect += 1
        return snapshot, overlay, run

    @contextlib.contextmanager
    def _timed(self, stage: str):
        """A stage of the resume on the host's clock, for a caller with no
        tracer (``resume_stats[stage + "_s"]``)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.resume_stats[stage + "_s"] = time.perf_counter() - t0

    def _try_resume(self) -> bool:
        with self._timed("restore"), span("stream/recover/restore") as sp:
            found = self._restorable()
            if found is None:
                sp.drop()
                return False
            snapshot, overlay, restored = found
            dt = self._factor_dtype()
            units = [_Unit.restored(st, dt) for st in restored]

            def nbytes(st) -> int:
                return int(st.user_factors.nbytes + sum(
                    a.nbytes for a in st.arrays.values()))

            read = {"units": len(units),
                    "snapshot_bytes": nbytes(snapshot) + (
                        nbytes(overlay) if overlay is not None else 0),
                    "unit_bytes": sum(map(nbytes, restored))}
            self.resume_stats.update(read)
            sp.set(**read)
        self._pin(snapshot.iteration)
        self._snapshot_step = snapshot.iteration
        meta = dict((restored[-1] if restored else overlay or snapshot).meta)
        if meta.get("model") != _STREAM_MODEL:
            raise ValueError(
                f"checkpoint store holds model={meta.get('model')!r}, not a "
                f"{_STREAM_MODEL} session — point the stream at its own "
                "directory"
            )
        if int(meta.get("rank", -1)) != self.config.rank:
            raise ValueError(
                f"stream checkpoint has rank {meta.get('rank')}, config "
                f"wants {self.config.rank}"
            )
        solved_on = meta.get("table_dtype", "float32")  # older stores
        if self._engine is not None and solved_on != self._table_dtype():
            raise ValueError(
                f"this store's units were solved against a {solved_on} item "
                f"table; the engine serves a {self._table_dtype()} one: "
                "batches replayed onto it would re-solve to other bits "
                "(streaming/foldin.py's determinism contract): start the "
                "engine with the table the stream was started on, or give "
                "the stream a new directory")
        if int(meta.get("base_users", -1)) != self.state.num_base_users:
            raise ValueError(
                "stream checkpoint was committed against a base dataset "
                f"with {meta.get('base_users')} users; this dataset has "
                f"{self.state.num_base_users} — same --data required to "
                "resume (the rating state is the base plus the store's cells)"
            )
        with self._timed("state"), span("stream/recover/state") as sp:
            self._users = _UserRows(self._resumed_base(snapshot, dt))
            if snapshot.meta.get("item_table") == "engine":
                if self._engine is None:
                    # read-only: the store can be inspected, not folded into
                    self._m = None
            else:
                self._set_movie(snapshot.movie_factors)
            self._overlay = (_Unit.restored(overlay, dt)
                             if overlay is not None
                             else _Unit.snapshot(snapshot, dt))
            now = _fold(self._overlay, units) if units else self._overlay
            self._users.load(now.touched, now.rows)
            self.state.load_overlay(now.cells, now.new_users)
            if self._overlay_store is not None:
                self._since = list(units)
            sp.set(rows=int(now.touched.shape[0]),
                   cells=int(now.cells.shape[0]))
        if self.state.num_users != int(meta.get("users",
                                                self.state.num_users)):
            raise ValueError(
                f"restored state has {self.state.num_users} users, commit "
                f"recorded {meta.get('users')} — store and base disagree"
            )
        self.stream_step = int(meta.get("stream_step", snapshot.iteration))
        self._durable_step = self.published_step = self.stream_step
        self.quarantined = list(meta.get("quarantined", []))
        ov = meta.get("overrides")
        if ov is not None:
            # restore the sticky escalation ladder state committed with
            # the factors — resuming at the config's un-escalated knobs
            # would solve post-crash batches differently from the
            # uninterrupted run (bit-exact replay contract)
            self._overrides = Overrides(
                lam=float(ov["lam"]),
                fused_epilogue=ov.get("fused_epilogue"),
                reg_solve_algo=ov.get("reg_solve_algo"),
            )
        # Batch boundaries are part of the replay contract: the committed
        # batch_records wins over this session's setting, so post-cursor
        # batches are re-cut exactly as an uninterrupted run would have
        # cut them (batch composition moves the solved rows at the ulp
        # level — foldin.py's determinism contract).
        committed_br = int(meta.get("batch_records",
                                    self.stream.batch_records))
        if committed_br != self.stream.batch_records:
            self.metrics.note(
                "batch_records_override",
                f"resume uses the committed batch_records={committed_br} "
                f"(this session asked for {self.stream.batch_records}; the "
                "replay contract pins the committed value)",
            )
            self.stream = dataclasses.replace(
                self.stream, batch_records=committed_br
            )
        cursors = {int(p): int(o) for p, o in meta.get("offsets", {}).items()}
        self.consumer = StreamConsumer(
            self.transport, topic=self.stream.topic, cursors=cursors,
            gap_retries=self.stream.gap_retries,
            gap_wait_s=self.stream.gap_wait_s,
        )
        self.metrics.incr("replayed_units", len(units))
        self.metrics.incr("replayed_unit_cells",
                          sum(int(u.cells.shape[0]) for u in units))
        self.metrics.incr("restored_cells", int(now.cells.shape[0]))
        with self._timed("republish"), \
                span("stream/recover/republish") as sp:
            # the engine of a restarted server gets everything as the
            # commits it missed; the engine a predecessor in this process
            # published to gets only what it lacks
            have = (int(self._engine.commit_ordinal)
                    if self._engine is not None else 0)
            events = []
            if overlay is not None and have < overlay.iteration:
                # every unit up to the overlay snapshot, as one commit
                events.append(self._overlay.event())
            events += [u.event() for u in units
                       if u.step > have and u.touched.size]
            for event in events:
                self._fire_commit(event)
            self.resume_stats["republished"] = len(events)
            sp.set(units=len(events), engine_had=have)
        self.metrics.note(
            "stream_resumed",
            f"step {self.stream_step} (snapshot {snapshot.iteration}"
            + (f", overlay snapshot {overlay.iteration}" if overlay is not None
               else "")
            + f" + {len(units)} units), cursor {cursors}, "
            f"{len(self.state._new_user_raw)} streamed-in users",
        )
        record_event("stream", "stream_resumed", step=self.stream_step)
        return True

    def _resumed_base(self, snapshot, dt) -> np.ndarray:
        """The user base a resumed session's rows lie over: the snapshot's
        own table, or, where the snapshot names the engine's, the engine's
        (held to the digest taken at bootstrap); a store reopened without
        its engine has no base (its solved rows can be read)."""
        if snapshot.meta.get("user_base") != "engine":
            return np.asarray(snapshot.user_factors).astype(dt, copy=False)
        if self._engine is None:
            return np.zeros((0, self.config.rank), dt)
        base = self._engine.user_base()
        want = snapshot.meta.get("user_base_digest")
        got = _table_digest(base, self.state.num_base_users)
        if got != want:
            raise ValueError(
                "this store's units were solved over another user table "
                f"than the engine serves (recorded {want}, engine {got}): "
                "start the engine from the model the stream was started "
                "on, or give the stream a new directory")
        return base.astype(dt, copy=False)

    # -- the loop ------------------------------------------------------------

    @property
    def user_factors(self) -> np.ndarray:
        """The whole user table as one new array (the base is shared and
        never written; the solved rows live beside it)."""
        quantum = self.stream.grow_multiple
        return self._users.table(
            -(-self.state.num_users // quantum) * quantum
            if self.state.num_users > self._users.base.shape[0] else 0)

    def user_rows(self, rows) -> np.ndarray:
        """Copies of single user rows as committed, without the table."""
        return self._users.get(rows)

    @property
    def movie_factors(self):
        if self._engine is not None:
            data, scale = self._engine.fold_table()
            if scale is not None or data.dtype != np.float32:
                raise ValueError(
                    "this session folds in against its engine's table as "
                    f"the engine holds it ({self._engine.table_dtype}): "
                    "there is no float32 item table to hand out, and the "
                    "session makes none (dequantizing it is the caller's "
                    "choice, from ServeEngine.fold_table())")
            return data
        if self._offload:
            return self._m_store.as_array()
        return self._m

    def model(self):
        """Current live factors as an ``ALSModel`` (serving view).  An
        offload session returns host arrays (materializing the store is
        the caller's choice — the session itself never holds the full
        movie table on device).  An ``ALSModel`` is float32: on an engine
        whose table is quantized this refuses in words
        (``movie_factors``) and dequantizes nothing."""
        import jax.numpy as jnp

        from cfk_tpu.models.als import ALSModel

        if self._offload:
            return ALSModel(
                user_factors=self.user_factors,
                movie_factors=self._m_store.as_array(),
                num_users=self.state.num_users,
                num_movies=self.state.num_movies,
            )
        return ALSModel(
            user_factors=jnp.asarray(self.user_factors),
            movie_factors=self.movie_factors,
            num_users=self.state.num_users,
            num_movies=self.state.num_movies,
        )

    def backlog(self) -> int:
        return self.consumer.backlog()

    @property
    def in_flight(self) -> bool:
        """A micro-batch is polled and not published yet (on the device,
        or handed to the store's writer and not durable)."""
        return bool(self._in_flight or self._unpublished)

    def _fixed(self):
        """The item table a fold-in gathers from: the session's own array,
        or its engine's table as the engine holds it, ``(data, scale)``."""
        fixed = (self._engine.fold_table() if self._engine is not None
                 else self.movie_factors)
        if fixed is None:
            raise ValueError(
                "this session was resumed without the engine that owns its "
                "item table: it can be read, not folded into")
        return fixed

    def _table_word(self, fixed) -> int:
        """The sentinel's bits for the fixed side, probed once per table:
        nothing writes a table between two swaps."""
        if self._fixed_word[0] is not fixed:
            word = _side_word_fn()(fixed, self.health.norm_limit)
            self._fixed_word = (fixed, int(np.asarray(word)))
        return self._fixed_word[1]

    def _dispatch(self, pending, overrides: Overrides, over=()) -> tuple:
        """The front half of one staged batch's solve under the given
        overrides: the touched users' lists (``over``: the batches it was
        staged over, not committed yet), the rectangle, the hand-over.
        Returns (fold, solved): on a resident table the ``FoldIn`` comes
        back on the device (either route of ``foldin.fold_route``); an
        out-of-core session has solved by the time it returns, (rows, the
        fixed rows to probe)."""
        with span("stream/batch/neighbors") as sp:
            staged = (*over, pending)
            neighbor_data = self.state.neighbors_many(
                pending.touched_rows,
                [overlay_of(row, staged) for row in pending.touched_rows])
            lens = [mv.shape[0] for mv, _ in neighbor_data]
            sp.set(touched=len(neighbor_data), cells=sum(lens),
                   longest=max(lens, default=0))
        with self.metrics.phase("foldin_solve"):
            if self._offload:
                with span("stream/batch/solve", touched=len(neighbor_data),
                          offload=1):
                    rows, staged = fold_in_rows_windowed(
                        self._m_store, neighbor_data,
                        lam=overrides.lam,
                        solver=self.config.solver,
                        pad_multiple=self.config.pad_multiple,
                        reg_solve_algo=overrides.reg_solve_algo,
                        stats=self._foldin_stats,
                        return_staged=True,
                    )
                self.metrics.gauge(
                    "foldin_windows_staged",
                    self._foldin_stats.get("foldin_windows_staged", 0))
                self.metrics.gauge(
                    "foldin_staged_mb",
                    round(self._foldin_stats.get(
                        "foldin_staged_bytes", 0) / 1e6, 3))
                return None, (rows, staged)
            return fold_in_dispatch(
                self._fixed(), neighbor_data,
                lam=overrides.lam,
                solver=self.config.solver,
                pad_multiple=self.config.pad_multiple,
                reg_solve_algo=overrides.reg_solve_algo,
                norm_limit=(self.health.norm_limit
                            if self.health is not None else float("inf")),
                cells_entities=self._cells_entities(),
            ), None

    def _cells_entities(self) -> int:
        """The entity bucket of the cells route: the most users one
        micro-batch can touch (``batch_records`` a partition), so that the
        route runs one set of programs whatever the batch."""
        return _pow2_ceil(
            self.stream.batch_records * self.consumer.num_partitions, 8)

    def _fetch(self, fold, solved):
        """The back half of ``_dispatch``'s pair: (rows [T, k] f32, probe
        word int), the health sentinel's word taken BEFORE anything is
        applied."""
        import jax.numpy as jnp

        if fold is not None:
            with self.metrics.phase("foldin_solve"):
                rows, word = fold.fetch()
            if self.health is None or not rows.shape[0]:
                return rows, 0
            with self.metrics.phase("health_check"), \
                    span("stream/batch/probe"):
                # the user side's bits came with the rows, from the
                # program that solved them
                word |= self._table_word(self._fixed())
            self.metrics.incr("health_checks")
            return rows, word
        rows, m_probe = solved
        word = 0
        if self.health is not None and rows.shape[0]:
            with self.metrics.phase("health_check"), \
                    span("stream/batch/probe"):
                # Offload mode probes the STAGED window — the fixed rows
                # the solve actually read — instead of the full table the
                # session no longer holds on device; the sentinel bitmask
                # semantics (non-finite / norm) are unchanged.
                word = int(np.asarray(_sentinel.probe_word(
                    jnp.asarray(rows), m_probe, self.health.norm_limit
                )))
            self.metrics.incr("health_checks")
        return rows, word

    def prewarm(self, *, max_touched: int | None = None,
                max_width: int | None = None) -> dict:
        """Run every fold-in program once, up front (ISSUE 13).

        The programs a live stream runs are a FIXED set, whatever its
        lists (``foldin.fold_route``): the padded route's rectangles —
        touched users bucket to ``_pow2_ceil(t, 8)`` up to
        ``batch_records`` and widths to pow2 multiples of ``pad_multiple``
        up to ``foldin.CHUNK`` (6 x 5 = 30 at the defaults) — and the
        cells route's ``len(foldin.SLABS)`` Gram programs and one solve
        (5), which take every batch with a longer list in it, however long.
        Walking them once with synthetic zero batches compiles every
        program a cold process would otherwise trace mid-stream — the
        ROADMAP-measured fold-in bound ("per-batch jit re-trace dominates")
        paid at startup instead of against live updates (and not at all on
        a warm restart when ``ALSConfig.compile_cache_dir`` is wired — the
        persistent cache serves each compile).  No list can outgrow the
        set inside a window.  Results are discarded; the jit cache keys on
        shapes, so the stream's bits are untouched.  ``max_touched`` /
        ``max_width`` cut the padded route's grid short (a test's, a tool's
        smaller stream).

        Returns ``{"programs", "new_traces", "prewarm_s"}``; serving a
        first real batch afterwards traces nothing
        (``tests/test_staging.py``, ``tests/test_foldin_cells.py`` pin
        it)."""
        with span("stream/prewarm"):
            return self._prewarm_impl(max_touched=max_touched,
                                      max_width=max_width)

    def _prewarm_impl(self, *, max_touched: int | None = None,
                      max_width: int | None = None) -> dict:
        import time as _time

        t0 = _time.time()
        if self._offload:
            note = ("skipped: offload fold-in programs key on the staged "
                    "window's pow2 row bucket (data-dependent); rely on "
                    "compile_cache_dir")
            self.metrics.note("prewarm", note)
            return {"programs": 0, "new_traces": 0, "prewarm_s": 0.0,
                    "skipped": note}
        mt = max(int(max_touched or self.stream.batch_records), 1)
        pm = max(self.config.pad_multiple, 1)
        widths = []
        p = _pow2_ceil(1, pm)
        while p <= CHUNK:
            widths.append(p)
            if p >= (max_width or CHUNK):
                break
            p *= 2
        ents = []
        e = _pow2_ceil(1, 8)
        while True:
            ents.append(e)
            if e >= mt:
                break
            e *= 2
        before = trace_count()
        programs = 0
        fixed = self._fixed()
        num_m = int(table_parts(fixed)[0].shape[0])
        if self.health is not None:
            self._table_word(fixed)  # the fixed side's probe, once a table

        def run(lists) -> None:
            fold_in_dispatch(
                fixed, lists,
                lam=self._overrides.lam,
                solver=self.config.solver,
                pad_multiple=self.config.pad_multiple,
                reg_solve_algo=self._overrides.reg_solve_algo,
                cells_entities=self._cells_entities(),
            ).fetch()

        def zeros(n):
            # valid table rows, ratings zero: the solved values are
            # discarded
            return (np.minimum(np.arange(n), num_m - 1).astype(np.int32),
                    np.zeros(n, np.float32))

        for e in ents:
            for p in widths:
                # one user at the full width pins the rectangle to
                # exactly (e, p)
                run([zeros(p)] + [zeros(1)] * (e - 1))
                programs += 1
        for slab in SLABS:
            # one list of exactly ``slab`` chunk rows runs that slab's Gram
            # program (and the route's one solve, counted once below)
            run([zeros(slab * CHUNK)])
            programs += 1
        programs += 1
        out = {
            "programs": programs,
            "new_traces": trace_count() - before,
            "prewarm_s": round(_time.time() - t0, 4),
        }
        self.metrics.gauge("prewarm_programs", programs)
        self.metrics.gauge("prewarm_new_traces", out["new_traces"])
        self.metrics.gauge("prewarm_s", out["prewarm_s"])
        return out

    def _meta(self, cursors: dict, note: str | None) -> dict:
        meta = {
            "model": _STREAM_MODEL,
            "rank": int(self.config.rank),
            "num_shards": 1,
            "stream_step": self.stream_step,
            "offsets": {str(p): int(o) for p, o in cursors.items()},
            "batch_records": self.stream.batch_records,
            "seq_high": int(self.state.applied_seq_high),
            "base_users": self.state.num_base_users,
            "users": self.state.num_users,
            # what the fixed side stores: rows solved against another
            # table's rounding are other bits (``_try_resume`` refuses)
            "table_dtype": self._table_dtype(),
            # poison ranges whose offsets are consumed but whose writes
            # must never be re-applied — crash replay skips them
            "quarantined": list(self.quarantined),
            # the sticky escalation state: post-resume batches must solve
            # under the same overrides an uninterrupted run would have
            # used, or replay is no longer bit-identical (a stream that
            # needed λ·10 once needs it after the crash too)
            "overrides": {
                "lam": float(self._overrides.lam),
                "fused_epilogue": self._overrides.fused_epilogue,
                "reg_solve_algo": self._overrides.reg_solve_algo,
            },
        }
        if note:
            meta["note"] = note
        return meta

    def _table_dtype(self) -> str:
        """The dtype the fold-in's fixed side is stored in."""
        return str(self._engine.table_dtype if self._engine is not None
                   else jnp_dtype(self.config.dtype))

    def _save(self, users, movies, meta: dict, note: str | None, *,
              arrays: dict, wait: bool = False) -> int:
        """One step of the store.  ``wait``: written before this returns,
        straight from the arrays given (no host copy for a background
        writer to own: a snapshot's tables are the size of the host)."""
        with self.metrics.phase("commit"), \
                span("stream/batch/commit", step=self.stream_step,
                     kind=meta["kind"]) as sp:
            if wait:
                # a unit of the same step still queued would land on top
                drain_checkpoints(self.manager)
                self.manager.save(self.stream_step, users, movies,
                                  meta={**meta, ARRAYS: arrays})
            else:
                save_checkpoint(self.manager, self.stream_step, users,
                                movies, meta={**meta, ARRAYS: arrays})
            nbytes = int(users.nbytes + movies.nbytes
                         + sum(a.nbytes for a in arrays.values()))
            sp.set(bytes=nbytes)
        self.metrics.incr("stream_commits")
        record_event("stream", "commit", step=self.stream_step,
                     note=note or "")
        return nbytes

    def _commit_snapshot(self, note: str | None = None) -> None:
        """Both tables, the cells applied so far and the cursor as one
        step: at bootstrap and after a retrain, the two moments at which
        every row is new.  The tables of a session on an engine are the
        engine's to keep: the item table it scans, and the user base it
        holds by reference (recorded by shape, dtype and a sampled digest;
        no copy is written, none is read back)."""
        self._publish_durable(wait=True)
        meta = self._meta(self.consumer.cursors, note)
        meta["kind"] = "snapshot"
        arrays = {"cells": self.state.overlay_cells(),
                  "new_users": np.asarray(self.state._new_user_raw, np.int64)}
        base = self._users.base
        if self._engine is not None:
            meta["item_table"] = "engine"
            movies = np.zeros((0, self.config.rank), np.float32)
            meta["user_base"] = "engine"
            meta["user_base_digest"] = _table_digest(
                base, self.state.num_base_users)
            users = np.zeros((0, self.config.rank), base.dtype)
        else:
            movies = np.asarray(self.movie_factors)
            users = (base if not len(self._users)
                     and self.state.num_users <= base.shape[0]
                     else self.user_factors)
        self._save(users, movies, meta, note, arrays=arrays, wait=True)
        self._pin(self.stream_step)
        self._snapshot_step = self._durable_step = self.stream_step
        # overlay snapshots over an older full snapshot are void
        self._overlay = _Unit(
            step=self.stream_step, meta=meta, touched=np.zeros(0, np.int64),
            rows=users[:0], cursors={}, **arrays)
        self._since, self._overlay_job = [], None

    def _pin(self, step: int) -> None:
        """Keep the snapshot every later unit rests on out of the store's
        ``keep_last_n`` collection (units it collects only shorten the run
        a resume can take from disk: the log replays the rest)."""
        pin = getattr(self.manager, "pin", None)
        if pin is not None:
            pin(step)

    def _commit_unit(self, batch, pending, rows) -> int:
        """One micro-batch as one atomic unit of the store: the rows it
        solved, the cells it applied, the users it added (arrays of the
        step's payload) and the cursor after it (rename + crc32, as every
        step of the store).  ``pending`` None: a quarantined batch, whose
        unit moves the cursor alone.  Returns its bytes; the unit waits in
        ``_unpublished`` for its rename."""
        meta = self._meta(batch.cursors_after, None)
        meta["kind"] = "unit"
        unit = _Unit(
            step=self.stream_step, meta=meta,
            touched=np.asarray(
                () if pending is None else pending.touched_rows, np.int64),
            rows=rows,
            cells=cells_array({} if pending is None else pending.cell_writes),
            new_users=np.asarray(
                () if pending is None else pending.new_user_raw, np.int64),
            cursors=dict(batch.cursors_after), appended=batch.appended)
        nbytes = self._save(
            rows, np.zeros((0, rows.shape[1]), rows.dtype), meta, None,
            arrays=unit.arrays())
        self._unpublished.append(unit)
        return nbytes

    # -- read committed: publication follows the rename ----------------------

    def _publish_durable(self, *, wait: bool = False) -> int:
        """Publish, in order, every unit whose step is renamed into place;
        returns how many.  ``wait``: after the writer has drained (the
        callers that are no serving loop: ``step``, an exit, a retrain).
        ``pump`` never waits: it reads the store's watermark."""
        if wait and self._unpublished:
            drain_checkpoints(self.manager)
        take = getattr(self.manager, "take_durable", None)
        if take is None:
            # a store with no writer thread: durable when ``save`` returned
            self._durable_step = self.stream_step
        else:
            for step in take():
                self._durable_step = max(self._durable_step, step)
        published = 0
        while (self._unpublished
               and self._unpublished[0].step <= self._durable_step):
            self._publish(self._unpublished.popleft())
            published += 1
        return published

    def _publish(self, unit: _Unit) -> None:
        self.published_step = unit.step
        if unit.touched.size:
            # the COMMITTED representation — after the dtype cast, so a
            # bf16-dtype session's listeners cache exactly what a
            # post-crash engine would restore from the store (not the
            # pre-cast f32 solve)
            with span("stream/batch/publish", ordinal=unit.step) as pub:
                self._fire_commit(unit.event())
                stamps = [t for t in unit.appended if t]
                if stamps:
                    # from the log's taking of each rating to the
                    # listeners' return: what a reader of it waits
                    now = time.perf_counter()
                    waits = sorted((now - t) * 1e3 for t in stamps)
                    pub.set(visible_ms_p50=waits[len(waits) // 2],
                            visible_ms_max=waits[-1])
        if self._overlay_store is not None:
            self._since.append(unit)
            self._snapshot_overlay()

    def _snapshot_overlay(self) -> None:
        """Every ``snapshot_every_units`` published units, fold the units
        since the last overlay snapshot into the next one, on the overlay
        store's writer thread: from arrays this thread only holds by
        reference, so it pays neither the fold nor a copy.  Every unit
        folded is durable (it was published), so the snapshot never holds
        what the store's units do not."""
        store = self._overlay_store
        if self._overlay_job is not None:
            step, count, landed = self._overlay_job
            if "content" in landed and step in store.take_durable():
                self._overlay, self._since = landed["content"], self._since[count:]
                self._overlay_job = None
        if (self._overlay_job is not None
                or len(self._since) < self.stream.snapshot_every_units):
            return
        units, base = list(self._since), self._overlay
        meta = dict(units[-1].meta, kind="overlay",
                    base_step=self._snapshot_step)
        landed: dict = {}

        def build():
            with span("stream/snapshot/overlay", units=len(units)) as sp:
                now = landed["content"] = _fold(base, units)
                sp.set(bytes=int(now.rows.nbytes + now.cells.nbytes))
            return (now.rows, now.rows[:0], {**meta, ARRAYS: now.arrays()})

        self._overlay_job = (units[-1].step, len(units), landed)
        store.submit(units[-1].step, build)

    def abandon(self) -> dict:
        """What a kill of the stream task leaves of this session: nothing.
        The micro-batches polled or on the device are dropped, the units
        handed to the writer and not renamed yet are discarded
        (``CheckpointManager.abort_pending``), nothing more is published.
        What lives on is the log, the store's renamed steps and whatever
        the listeners were given; a successor on the same store replays the
        rest from the log.  Returns what was dropped."""
        dropped = {"in_flight_batches": len(self._in_flight),
                   "unpublished_units": len(self._unpublished),
                   "stream_step": self.stream_step,
                   "cursors": dict(self.consumer.cursors)}
        self._in_flight.clear()
        self._unpublished.clear()
        self._abandoned = True
        for store in (self.manager, self._overlay_store):
            abort = getattr(store, "abort_pending", None)
            if abort is not None:
                abort()
        record_event("stream", "session_abandoned", **{
            k: v for k, v in dropped.items() if k != "cursors"})
        return dropped

    def add_commit_listener(self, fn) -> None:
        """Subscribe ``fn(event: dict)`` to every durable commit.

        The event carries COPIES (never views of this session's mutable
        state): ``touched_rows`` + ``rows`` [T, k] f32 (the freshly solved
        factor rows), ``cells`` [(user_row, movie_row), ...] (the rated
        cells the batch applied), ``num_users``, ``stream_step``; a warm
        retrain instead fires ``retrain=True`` with full ``user_factors``/
        ``movie_factors`` snapshots.  Fired once the factor+cursor commit
        is renamed into place in the store (read committed), in commit
        order — a request served after the listener returns reflects the
        folded-in factors, and names an ordinal the store holds."""
        self._commit_listeners.append(fn)

    def _fire_commit(self, event: dict) -> None:
        event.setdefault("stream_step", self.stream_step)
        event.setdefault("num_users", self.state.num_users)
        for fn in self._commit_listeners:
            # A listener failure must not poison the commit that already
            # happened, nor starve the OTHER listeners (a broken serving
            # subscriber taking down the training stream would invert the
            # dependency) — record it loudly and keep going.
            try:
                fn(event)
            except Exception as e:
                self.metrics.incr("commit_listener_errors")
                record_event(
                    "stream", "commit_listener_error",
                    step=self.stream_step,
                    listener=getattr(fn, "__qualname__", repr(fn)),
                    error=f"{type(e).__name__}: {e}",
                )

    def step(self) -> dict | None:
        """Process ONE micro-batch, from its poll to its commit; returns
        its summary, or None when caught up with the log.  (Batches that
        ``pump`` left on the device are committed first.)"""
        self._alive()
        while self._in_flight:
            with span("stream/batch") as sp:
                self._finish(sp)
                self._publish_durable(wait=True)
        with span("stream/batch") as sp:
            if not self._begin():
                if not self._publish_durable(wait=True):
                    sp.drop()
                return None
            summary = self._finish(sp)
            # committed means durable: the unit is published before this
            # returns (``pump`` is the loop that does not wait)
            self._publish_durable(wait=True)
            return summary

    def pump(self, *, device_busy: bool = False) -> int:
        """Advance the stream from inside another loop — the request
        server's, between two of its polls; returns the micro-batches
        committed.

        A call commits the batches handed to the device by the call before
        and hands over the next.  With a scorer in flight (``device_busy``)
        what is handed over sits behind that scorer and is left there: by
        the next call the server has answered the scorer, the fold-in has
        run between two scorer calls, and its fetch waits for at most its
        own few milliseconds, so the host never waits for a fold-in behind
        a scorer it did not need.  One batch a call keeps up with a stream
        at the rate it was sized for; while a whole micro-batch still waits
        in the log behind the one just handed over (a stall, a burst) the
        call hands over another, ``_PUMP_DEPTH`` at most, each staged over
        the ones before it.  With nothing else on the device the call
        commits what it handed over before it returns.

        A unit is published once the store's writer has renamed it into
        place (read committed): each call publishes what has landed since
        the one before, and with a scorer in flight never waits for the
        writer; with nothing else on the device it sees its units into the
        store before it returns."""
        self._alive()
        done = begun = 0
        ready = len(self._in_flight)  # an earlier call's: run by now
        more = True
        while ready or more:
            # one span: the back half of a batch, the front half of another
            with span("stream/batch") as sp:
                published = self._publish_durable()
                finished = ready > 0
                if finished:
                    self._finish(sp)
                    ready -= 1
                    done += 1
                more = (begun < _PUMP_DEPTH
                        and (begun == 0 or self.consumer.backlog()
                             >= self.stream.batch_records)
                        and self._begin())
                begun += more
                if not (finished or more or published):
                    sp.drop()
        while self._in_flight and not device_busy:
            with span("stream/batch") as sp:
                self._publish_durable()
                self._finish(sp)
            done += 1
        if self._unpublished and not device_busy:
            # nothing else on the device, so no request batch is held up
            # behind this: see the units into the store and publish them,
            # as the call saw their fold-ins off the device (a lightly
            # loaded server polls its next requests over what it just
            # committed, a stalled one over what the stall held back)
            self._publish_durable(wait=True)
        elif self._unpublished and not (done or begun):
            # nothing to do but see the writer's rename: a caller that
            # spins on this call would hold the interpreter against the
            # very thread it waits for
            time.sleep(0.0005)
        return done

    def _begin(self) -> bool:
        """Poll one micro-batch, stage it against the applied state and
        the batches in flight, and hand its fold-in to the device; False
        when caught up."""
        batch = self.consumer.poll(self.stream.batch_records)
        if batch is None:
            return False
        step = self.stream_step + len(self._in_flight) + 1
        if batch.duplicates_dropped:
            self.metrics.incr("delivery_duplicates", batch.duplicates_dropped)
            record_event("stream", "delivery_duplicates_dropped", step=step,
                         duplicates=batch.duplicates_dropped)
        if batch.gap_repolls:
            self.metrics.incr("delivery_gap_repolls", batch.gap_repolls)
            record_event("stream", "delivery_gap_repolls", step=step,
                         repolls=batch.gap_repolls)
        self._in_flight.append(self._hand_over(batch))
        return True

    def _hand_over(self, batch) -> _Batch:
        over = [b.pending for b in self._in_flight]
        with self.metrics.phase("stage"), \
                span("stream/batch/stage", records=batch.num_records) as sp:
            pending = self.state.stage(batch.updates, over)
            sp.set(fresh=pending.stats.fresh, stale=pending.stats.stale,
                   rerated=pending.stats.rerated,
                   new_users=pending.stats.new_users)
        solve = (self._dispatch(pending, self._overrides, over)
                 if pending.touched_rows else (None, None))
        return _Batch(batch, pending, solve)

    def _hand_over_again(self) -> None:
        """What is in flight was staged over a batch that did not commit
        as staged (a trip: retried under sticky overrides, or quarantined)
        or solved against a table since retrained: stage and solve it
        anew, as ``step`` after ``step`` would have."""
        again, self._in_flight = self._in_flight, collections.deque()
        for b in again:
            self._in_flight.append(self._hand_over(b.batch))

    def _finish(self, sp) -> dict:
        """Fetch the batch in flight, probe it, and — healthy — apply,
        commit and publish it; ``sp`` is the open ``stream/batch`` span,
        which takes the committed batch's counts."""
        b = self._in_flight.popleft()
        batch, pending, (fold, solved) = b.batch, b.pending, b.solve
        self.metrics.incr("updates_fresh", pending.stats.fresh)
        self.metrics.incr("updates_stale", pending.stats.stale)
        self.metrics.incr("updates_unknown_movie", pending.stats.unknown_movie)
        summary = {
            "records": batch.num_records,
            "fresh": pending.stats.fresh,
            "stale": pending.stats.stale,
            "touched_users": len(pending.touched_rows),
            "new_users": pending.stats.new_users,
            "quarantined": False,
            "trips": 0,
        }
        rows = np.zeros((0, self.config.rank), np.float32)
        if pending.touched_rows:
            overrides = self._overrides
            trips = 0
            while True:
                rows, word = self._fetch(fold, solved)
                if not word:
                    break
                trips += 1
                summary["trips"] = trips
                self.metrics.incr("health_trips")
                report = _sentinel.HealthReport(
                    iteration=self.stream_step + 1, word=word, stats={}
                )
                self.metrics.note(
                    f"stream_trip_{self.stream_step + 1}_{trips}",
                    report.summary(),
                )
                record_event("fault", "stream_trip",
                             step=self.stream_step + 1, trip=trips,
                             reason=report.summary())
                dump_flight(f"stream_trip_{self.stream_step + 1}_{trips}")
                if trips > self.policy.max_recoveries:
                    # The whole ladder lost: quarantine the batch — its
                    # offsets are consumed (a poison pill must not wedge
                    # the stream) but neither the factors nor the rating
                    # state ever see its writes.
                    msg = (
                        f"stream batch at step {self.stream_step + 1} "
                        f"defeated the recovery ladder ({report.summary()}); "
                        f"offsets {batch.cursors_before} → "
                        f"{batch.cursors_after} quarantined"
                    )
                    record_event("fault", "quarantine",
                                 step=self.stream_step + 1,
                                 reasons=report.reasons, detail=msg)
                    dump_flight("quarantine")
                    if self.policy.on_unrecoverable == "raise":
                        raise PoisonedBatchError(msg)
                    self.quarantined.append({
                        "stream_step": self.stream_step + 1,
                        "offsets": {str(p): [batch.cursors_before[p],
                                             batch.cursors_after[p]]
                                    for p in batch.cursors_after},
                        "reasons": report.reasons,
                    })
                    self.metrics.incr("quarantined_batches")
                    self.metrics.note("quarantined", msg)
                    warnings.warn(msg)
                    summary["quarantined"] = True
                    pending = None
                    rows = rows[:0]
                    break
                # Rollback is free — nothing was committed — so a retry is
                # one escalation rung up (λ bump → split epilogue → GJ),
                # sticky for the rest of the session exactly like the
                # training ladder (a stream that needed λ·10 once will
                # need it again).
                new_overrides = self.policy.escalate(self._overrides,
                                                     trips + 1)
                if new_overrides != overrides:
                    overrides = new_overrides
                    self._overrides = new_overrides
                    self.metrics.gauge("stream_escalation_level", trips)
                    self.metrics.note(
                        f"stream_escalation_{trips}",
                        f"lam={overrides.lam:g} "
                        f"fused={overrides.fused_epilogue} "
                        f"algo={overrides.reg_solve_algo}",
                    )
                    record_event("fault", "stream_escalation", rung=trips,
                                 lam=overrides.lam)
                fold, solved = self._dispatch(pending, overrides)
            if pending is not None:
                with span("stream/batch/apply", touched=len(rows)):
                    self.state.commit(pending)
                    rows = rows.astype(self._factor_dtype())
                    self._users.set(pending.touched_rows, rows)
            if trips and self._in_flight:
                self._hand_over_again()
        self.stream_step += 1
        commit_bytes = self._commit_unit(batch, pending, rows)
        sp.set(step=self.stream_step, ordinal=self.stream_step,
               records=batch.num_records, fresh=summary["fresh"],
               touched=len(rows), new_users=summary["new_users"],
               commit_bytes=commit_bytes)
        if fold is not None:
            sp.set(rank=fold.rank, operand_bytes=fold.operand_bytes,
                   **fold.counts())
        summary["stream_step"] = self.stream_step
        if (self.stream.retrain_every is not None
                and self.stream_step % self.stream.retrain_every == 0):
            self.retrain()
            self._hand_over_again()
        return summary

    def _alive(self) -> None:
        if self._abandoned:
            raise RuntimeError(
                "this session was abandoned: a successor on its store "
                "carries the stream on")

    def run(self, *, max_batches: int | None = None, follow: bool = False,
            before_batch=None):
        """Drain (or follow) the updates topic; returns the live model.

        ``follow=True`` keeps polling an idle topic until ``max_batches``
        or eviction; the default drains until caught up.  ``before_batch``
        (chaos/testing hook) is called with the upcoming stream step before
        every poll — fault injectors deliver signals or kill the process
        there, the boundary at which a real eviction lands.
        """
        import time as _time

        batches = 0
        try:
            while True:
                if self.guard is not None and self.guard.triggered:
                    self._evict()
                    break
                if max_batches is not None and batches >= max_batches:
                    break
                if before_batch is not None:
                    before_batch(self.stream_step)
                    if self.guard is not None and self.guard.triggered:
                        self._evict()
                        break
                got = self.step()
                if got is None:
                    if not follow:
                        break
                    _time.sleep(self.stream.poll_wait_s)
                    continue
                batches += 1
        finally:
            # Same exit contract as the training loop: only committed
            # steps are left behind for the next reader.
            if not self._abandoned:
                self._publish_durable(wait=True)
            drain_checkpoints(self.manager)
            drain_checkpoints(self._overlay_store)
        return self.model()

    def _evict(self) -> None:
        """Eviction: the last commit already carries the cursor — drain
        the writer so it is durably on disk, then return resumable."""
        self._publish_durable(wait=True)
        drain_checkpoints(self.manager)
        record_event("signal", "stream_evicted", step=self.stream_step,
                     signal=self.guard.signal_name)
        dump_flight("stream_eviction")
        self.metrics.gauge("preempted", 1)
        self.metrics.note(
            "preempted",
            f"{self.guard.signal_name} at stream step {self.stream_step}; "
            "offset cursor committed and drained — re-run to resume",
        )

    # -- warm retrain --------------------------------------------------------

    def retrain(self, num_iterations: int | None = None) -> None:
        """Warm full retrain on the merged state, current factors as seed.

        Rebuilds the dataset from base + every committed upsert and runs
        the resilient stepped training loop (``train_als(warm_start=...)``)
        — the movie side finally sees the streamed ratings.  The retrained
        factors are permuted back into the session's row order (streamed-in
        users keep their appended rows, so crash replay still lines up)
        and committed with the unchanged cursor.
        """
        import dataclasses as _dc

        from cfk_tpu.data.blocks import Dataset
        from cfk_tpu.models.als import train_als

        if self.dataset is None or self._engine is not None:
            raise NotImplementedError(
                "a warm full retrain rebuilds the Dataset the session was "
                "given and installs a table of its own: a session on a CSR "
                "state or on an engine's table retrains offline and starts "
                "again from the new model"
            )
        if self._offload:
            raise NotImplementedError(
                "warm full retrain in an offload_tier='host_window' "
                "session needs warm_start threading through the windowed "
                "trainer (documented follow-up) — run the retrain "
                "offline and bootstrap a fresh session from its model"
            )
        with self.metrics.phase("retrain_build"):
            coo = self.state.to_coo()
            ds2 = Dataset.from_coo(
                coo,
                num_shards=1,
                pad_multiple=self.config.pad_multiple,
                layout=self.config.layout,
                chunk_elems=self.config.chunk_cells(),
                dense_stream=self.config.layout == "tiled",
            )
        if not np.array_equal(ds2.movie_map.raw_ids,
                              self.dataset.movie_map.raw_ids):
            raise RuntimeError(
                "merged state changed the movie universe — unknown movies "
                "are supposed to be rejected at apply time"
            )
        raw_users = self.state.user_raw_ids()
        perm = ds2.user_map.to_dense(raw_users)  # ds2 row per session row
        # Seed ds2's row order from the live factors.
        k = self.config.rank
        live = self.user_factors
        u_seed = np.zeros((ds2.user_blocks.padded_entities, k),
                          dtype=live.dtype)
        u_seed[perm] = live[: self.state.num_users]
        m_seed = np.asarray(self._m)[: ds2.movie_blocks.padded_entities]
        if m_seed.shape[0] < ds2.movie_blocks.padded_entities:
            m_seed = np.concatenate([
                m_seed,
                np.zeros((ds2.movie_blocks.padded_entities - m_seed.shape[0],
                          k), m_seed.dtype),
            ])
        cfg = self.config
        if num_iterations is not None:
            cfg = _dc.replace(cfg, num_iterations=num_iterations)
        with self.metrics.phase("retrain"):
            model = train_als(
                ds2, cfg, metrics=self.metrics,
                warm_start=(u_seed, m_seed),
                preemption_guard=self.guard,
            )
        # Back into session row order; new users keep their appended rows.
        u2 = np.asarray(model.user_factors)
        u_sess = np.zeros_like(live)
        u_sess[: self.state.num_users] = u2[perm]
        self._users = _UserRows(u_sess)  # every row is new: a new base
        self._set_movie(model.movie_factors)
        self.metrics.incr("stream_retrains")
        self._commit_snapshot(
            note=f"warm retrain at step {self.stream_step}")
        self._fire_commit({
            "retrain": True,
            "user_factors": np.array(u_sess, np.float32),
            "movie_factors": np.array(np.asarray(self._m), np.float32),
        })
