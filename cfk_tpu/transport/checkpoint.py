"""Per-iteration factor checkpointing + resume.

The reference's per-iteration Kafka topics (``user-features-i`` /
``movie-features-i``, provisioned by ``setup.sh:18-21``) are *incidentally* a
durable journal of every iteration's factors, but nothing ever reads them
back; any crash restarts from scratch (``streams.cleanUp()``,
``apps/BaseKafkaApp.java:36``; SURVEY.md §5).  This module makes that journal
an explicit API: factor matrices are written per iteration with an atomic
rename, and training resumes from the latest complete step.
"""

from __future__ import annotations

import atexit
import dataclasses
import io
import json
import os
import shutil
import tempfile
import threading
import time
import warnings
import weakref
import zlib
from collections import deque

import numpy as np

from cfk_tpu.telemetry.trace import span

_MANIFEST = "manifest.json"
_ARRAYS = "arrays.bin"
# A payload up to this size is read back with one read and parsed in memory
# (a stream's commit unit is four small files, and every reopening of one is
# a turn of the interpreter's lock given away to the thread beside); a larger
# one (a table) is checked through its file, then mapped or loaded from it.
_SMALL = 1 << 26
# A ``.npy`` payload up to this size is serialized in memory and written and
# synced through one descriptor.  Past it (an overlay snapshot's rows, a
# table) the array goes to its file by ``np.save``, which holds the
# interpreter's lock for no copy: a writer thread that serialized 50 MB in
# memory held the serving thread beside it for 100 ms (PERF.md section 6,
# PR 39).
_INLINE = 1 << 20
# A step's further payloads ride in its ``meta`` under this key, as a dict of
# arrays, and are written as one checksummed ``arrays.bin`` beside the
# factors (their raw bytes end to end; names, dtypes, shapes and offsets in
# the manifest under ``arrays``): every ``save`` override and wrapper
# that passes ``meta`` through carries them unchanged.
ARRAYS = "__arrays__"
_STEP_PREFIX = "step_"
# what a manifest holds of its own, beside the caller's ``meta``
_MANIFEST_OWN = ("iteration", "user_shape", "movie_shape", "dtype", "crc32",
                 "arrays")

# Managers with a live background writer, drained at interpreter exit so a
# process that finishes (or is SIGTERM'd into a clean shutdown) never leaves
# an enqueued checkpoint unwritten.  Weak references: a manager that is
# garbage-collected drains in __del__/wait_pending before it disappears from
# this set, and the atexit hook must not keep dead managers alive.
_LIVE_MANAGERS: "weakref.WeakSet[CheckpointManager]" = weakref.WeakSet()


def _drain_writers_at_exit() -> None:  # pragma: no cover - exit path
    for mgr in list(_LIVE_MANAGERS):
        try:
            mgr.wait_pending()
        except Exception as e:
            # Exit-time best effort: a failed background write must not turn
            # a clean shutdown into a crash loop; the warning names the loss.
            warnings.warn(f"checkpoint write pending at exit failed: {e}")


atexit.register(_drain_writers_at_exit)


class CheckpointCorruptError(ValueError):
    """An explicitly requested step failed integrity verification."""


def resume_state(
    manager: "CheckpointManager | None",
    *,
    rank: int,
    model: str,
    num_iterations: int,
    u_shape: tuple[int, int] | None = None,
    m_shape: tuple[int, int] | None = None,
    num_shards: int | None = None,
) -> "CheckpointState | None":
    """Shared resume validation for every trainer.

    Returns the latest state, or None when there is nothing to resume.
    Rejects checkpoints whose rank or model family differs from the config,
    runs already past ``num_iterations`` (silently returning over-trained
    factors as an N-iteration model would corrupt experiments), checkpoints
    whose recorded ``num_shards`` differs from this run's (shard-local
    block indices and padded row counts are shard-count-dependent, and the
    shapes can coincide by accident), and — when the expected
    ``u_shape``/``m_shape`` are given — stale checkpoints whose padded row
    counts don't match this run (different pad_multiple/num_shards), which
    would otherwise surface as an opaque shape error deep inside the jitted
    iteration.
    """
    if manager is None or manager.latest_iteration() is None:
        return None
    try:
        state = manager.restore()
    except FileNotFoundError as e:
        # Steps exist but none passed integrity verification (all torn/
        # corrupted): starting fresh beats crashing resume — the warning
        # from latest_valid_iteration() already named each bad step.
        warnings.warn(f"no intact checkpoint to resume from ({e}); "
                      "starting from scratch")
        return None
    if state.user_factors.shape[-1] != rank:
        raise ValueError(
            f"checkpoint at iteration {state.iteration} has rank "
            f"{state.user_factors.shape[-1]}, config rank={rank}; "
            "use a fresh checkpoint directory to change rank"
        )
    saved_model = state.meta.get("model", "als")
    if saved_model != model:
        raise ValueError(
            f"checkpoint was written by model family {saved_model!r}, "
            f"resuming as {model!r}; use a fresh checkpoint directory"
        )
    saved_shards = state.meta.get("num_shards")
    if (num_shards is not None and saved_shards is not None
            and int(saved_shards) != int(num_shards)):
        # The u_shape check below only catches this when the shard-count
        # padding happens to change the padded row counts; equal shapes
        # with different shard-local block layouts would train garbage.
        raise ValueError(
            f"checkpoint at iteration {state.iteration} was written by a "
            f"num_shards={int(saved_shards)} run, but this config has "
            f"num_shards={int(num_shards)}; shard-count padding and "
            "shard-local indices are not portable — use a fresh checkpoint "
            "directory (or restore() and re-shard the factors by hand)"
        )
    if state.iteration > num_iterations:
        raise ValueError(
            f"checkpoint is at iteration {state.iteration}, past the requested "
            f"num_iterations={num_iterations}; restore() an earlier step "
            "explicitly or use a fresh checkpoint directory"
        )
    if u_shape is not None:
        _check_shapes(state, u_shape, m_shape)
    return state


def checkpointed_train_loop(
    manager,
    *,
    model: str,
    rank: int,
    num_iterations: int,
    u_shape: tuple[int, int],
    m_shape: tuple[int, int],
    dtype,
    init_fn,
    step_fn,
    metrics,
    checkpoint_every: int = 1,
    num_shards: int = 1,
    preemption_guard=None,
    watchdog=None,
):
    """The single-process checkpointed training loop every trainer shares.

    Resumes from the manager's latest committed state (validated by
    ``resume_state``) or calls ``init_fn() -> (u, m)``; then steps
    ``step_fn(u, m) -> (u, m)`` from Python, journaling factors every
    ``checkpoint_every`` iterations under ``metrics`` phases.  Factoring
    this out keeps save cadence / resume validation / metrics accounting
    identical across model families by construction (ADVICE r3).

    This is the health-off special case of
    ``cfk_tpu.resilience.loop.resilient_train_loop`` (which adds sentinel
    probes, rollback and escalation); it delegates there so there is
    exactly one stepped loop.
    """
    from cfk_tpu.resilience.loop import resilient_train_loop

    return resilient_train_loop(
        manager,
        model=model,
        rank=rank,
        num_iterations=num_iterations,
        u_shape=u_shape,
        m_shape=m_shape,
        dtype=dtype,
        init_fn=init_fn,
        step_fn=step_fn,
        metrics=metrics,
        checkpoint_every=checkpoint_every,
        num_shards=num_shards,
        preemption_guard=preemption_guard,
        watchdog=watchdog,
    )


def resume_state_synced(
    manager: "CheckpointManager | None",
    *,
    rank: int,
    model: str,
    num_iterations: int,
    u_shape: tuple[int, int],
    m_shape: tuple[int, int],
    num_shards: int | None = None,
) -> "CheckpointState | None":
    """``resume_state`` with the decision broadcast from process 0.

    Under multi-process JAX, checkpoints are written by process 0 only; if
    hosts do not share a filesystem, the other processes would see no state
    (or a stale one) and start at a different iteration — their collectives
    would then no longer pair up across hosts (distributed deadlock).  This
    broadcasts process 0's (iteration, factors) so every process resumes in
    lockstep; single-process, it is exactly ``resume_state``.
    """
    import jax

    if jax.process_count() == 1:
        return resume_state(
            manager, rank=rank, model=model, num_iterations=num_iterations,
            u_shape=u_shape, m_shape=m_shape, num_shards=num_shards,
        )
    from jax.experimental import multihost_utils as mh

    # Only process 0's checkpoint is authoritative — other processes never
    # read their (possibly stale, possibly differently-shaped) local dirs;
    # they always contribute current-shape zeros to the factor broadcast.
    # Process 0 validates BEFORE any collective and broadcasts a status word,
    # so a bad checkpoint fails loudly on every process instead of leaving
    # the others hanging in a collective that process 0 never enters.
    state = None
    err: Exception | None = None
    if jax.process_index() == 0:
        try:
            state = resume_state(
                manager, rank=rank, model=model, num_iterations=num_iterations,
                u_shape=u_shape, m_shape=m_shape, num_shards=num_shards,
            )
        except Exception as e:
            err = e
        status = -2 if err is not None else (-1 if state is None else state.iteration)
    else:
        status = -1  # overwritten by the broadcast
    it = int(mh.broadcast_one_to_all(np.asarray(status, np.int64)))
    if it == -2:
        if err is not None:
            raise err
        raise RuntimeError(
            "process 0 failed to resume from its checkpoint directory "
            "(see its log for the underlying error)"
        )
    if it < 0:
        return None
    u = (
        state.user_factors.astype(np.float32)
        if state is not None
        else np.zeros(u_shape, np.float32)
    )
    m = (
        state.movie_factors.astype(np.float32)
        if state is not None
        else np.zeros(m_shape, np.float32)
    )
    return CheckpointState(
        iteration=it,
        user_factors=np.asarray(mh.broadcast_one_to_all(u)),
        movie_factors=np.asarray(mh.broadcast_one_to_all(m)),
        meta=state.meta if state is not None else {"model": model},
    )


def _check_shapes(state: "CheckpointState", u_shape, m_shape) -> None:
    got = (tuple(state.user_factors.shape), tuple(state.movie_factors.shape))
    if got != (tuple(u_shape), tuple(m_shape)):
        raise ValueError(
            f"checkpoint at iteration {state.iteration} has factor shapes "
            f"user={got[0]} movie={got[1]}, but this run needs "
            f"user={tuple(u_shape)} movie={tuple(m_shape)} (padded entity "
            "counts depend on pad_multiple/num_shards); use a fresh "
            "checkpoint directory"
        )


def _fsync_file(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:  # pragma: no cover - exotic filesystems
        return
    try:
        os.fsync(fd)
    except OSError:  # pragma: no cover - fsync on dirs unsupported
        pass
    finally:
        os.close(fd)


def _host_snapshot(x) -> np.ndarray:
    """Host copy of a factor array, issued non-blocking when possible.

    jax arrays get their device→host DMA started via ``copy_to_host_async``
    before the materializing ``np.asarray`` (which must block, but now only
    for the tail of an already-running transfer); numpy inputs are copied so
    the enqueued write can never observe caller-side mutation."""
    copy_async = getattr(x, "copy_to_host_async", None)
    if copy_async is not None:
        try:
            copy_async()
        except Exception:  # pragma: no cover - non-addressable shards
            pass
    return np.array(x, copy=True)


def _crc32_file(path: str) -> int:
    crc = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                return crc
            crc = zlib.crc32(chunk, crc)


def _write_synced(path: str, parts) -> int:
    """The buffers of ``parts`` end to end into a new file, synced before
    it is closed; the crc32 of the whole.  Nothing is copied on the way."""
    crc = 0
    with open(path, "wb") as f:
        for part in parts:
            f.write(part)
            crc = zlib.crc32(part, crc)
        f.flush()
        os.fsync(f.fileno())
    return crc


def _save_array(path: str, arr: np.ndarray) -> int:
    """One ``.npy`` payload, on disk and synced; its crc32."""
    if arr.nbytes > _INLINE:
        np.save(path, arr)
        _fsync_file(path)
        return _crc32_file(path)
    buf = io.BytesIO()
    np.save(buf, arr)
    return _write_synced(path, [buf.getbuffer()])


def _pack_arrays(arrays: dict) -> tuple[list, dict]:
    """(the arrays' raw bytes as views of them, to be written end to end;
    {name: dtype, shape, offset})."""
    index, parts, at = {}, [], 0
    for name, arr in arrays.items():
        arr = np.ascontiguousarray(arr)
        index[name] = {
            "dtype": arr.dtype.descr if arr.dtype.names else arr.dtype.str,
            "shape": list(arr.shape), "offset": at}
        parts.append(arr.reshape(-1).view(np.uint8))
        at += arr.nbytes
    return parts, index


def _unpack_arrays(data: bytes, index: dict) -> dict:
    out = {}
    for name, at in index.items():
        dtype = (np.dtype([tuple(f) for f in at["dtype"]])
                 if isinstance(at["dtype"], list) else np.dtype(at["dtype"]))
        count = int(np.prod(at["shape"]))
        out[name] = np.frombuffer(data, dtype, count, at["offset"]).reshape(
            at["shape"])
    return out


def should_save(done: int, every: int, total: int) -> bool:
    """Save cadence: every ``every`` completed iterations, and always at the end."""
    if every < 1:
        raise ValueError(f"checkpoint_every must be >= 1, got {every}")
    return done % every == 0 or done == total


@dataclasses.dataclass(frozen=True)
class CheckpointState:
    iteration: int  # iterations fully completed
    user_factors: np.ndarray
    movie_factors: np.ndarray
    meta: dict
    # a step's further payloads by name (``meta[ARRAYS]`` of its save): one
    # checksummed ``arrays.bin`` beside the factors, absent where none
    arrays: dict = dataclasses.field(default_factory=dict)


class _WriteAborted(Exception):
    """The write under way was discarded by ``abort_pending`` before its
    rename: its step never existed."""


class CheckpointManager:
    """Directory-of-steps checkpoint store with atomic per-step commits.

    Layout: ``<dir>/step_0000007/{manifest.json,user.npy,movie.npy}``.
    A step directory appears atomically (written to a temp dir, fsync'd, then
    renamed), so a crash mid-write can never yield a half checkpoint — the
    property the reference's in-memory, changelog-disabled stores lack
    (``apps/ALSApp.java:53-83``).

    ``save_async`` hands the serialize + fsync + atomic-rename to ONE
    background writer thread so the training loop never idles behind disk;
    ``wait_pending()`` is the barrier (the resilient loop drains before any
    rollback read and at loop exit, so the crc32/torn-step verification
    contract is unchanged — readers only ever see committed steps).  When
    more than ``max_pending`` saves are queued, ``save_async`` blocks (slow
    disk must throttle the producer, not grow an unbounded host-snapshot
    queue).  A writer error is sticky: it re-raises at the next
    ``save_async``/``wait_pending`` instead of vanishing on a daemon thread.

    ``keep_last_n`` garbage-collects old steps after each successful save,
    always keeping the newest N plus any ``pin()``ned step — the resilient
    loop pins its last verified-good rollback anchor, so the step the
    recovery ladder points at can never be collected out from under it.

    Under the tracer each job of the writer thread is a span
    ``checkpoint/write`` on that thread (``cfk-checkpoint-writer``), from
    its being taken up to the rename and the directory's fsync (``step``,
    ``kind`` where the job's meta has one, ``bytes``, ``fsyncs``,
    ``queued_ms`` = from ``save_async`` taking the job, before any wait
    for room, to the writer taking it up: ``queued_ms`` + the span is the
    caller's hand-over to durable).  A ``save_async`` that waits for room
    writes ``checkpoint/backpressure`` on the caller's thread (``pending``,
    ``max_pending``); one that does not wait writes nothing.

    What is on disk is told apart from what was handed over:
    ``take_durable()`` returns the steps renamed into place since it was
    last asked (a caller that must not show a step before it is durable
    polls it and never waits on an fsync); ``abort_pending()`` is what a
    kill does to the writer: queued jobs are dropped and the job being
    written is discarded unless its rename is already under way.
    """

    def __init__(
        self,
        directory: str,
        *,
        keep_last_n: int | None = None,
        async_write: bool = True,
        max_pending: int = 2,
    ) -> None:
        if keep_last_n is not None and keep_last_n < 1:
            raise ValueError(
                f"keep_last_n must be >= 1 (checkpoints retained after each "
                f"save), got {keep_last_n}; use keep_last_n=None to retain "
                "every step"
            )
        if max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        self.directory = directory
        self.keep_last_n = keep_last_n
        self.async_write = async_write
        self.max_pending = max_pending
        self._pinned: int | None = None
        self._lock = threading.Lock()
        self._queue_nonfull = threading.Condition(self._lock)
        self._queue_empty = threading.Condition(self._lock)
        self._jobs: deque = deque()
        self._inflight = 0
        self._writer_thread: threading.Thread | None = None
        self._writer_error: BaseException | None = None
        # what the last ``save`` of each thread wrote: (bytes, fsyncs)
        self._wrote = threading.local()
        # steps renamed into place and not yet reported (``take_durable``)
        self._durable: list[int] = []
        # bumped by ``abort_pending``: a writer job taken up under an older
        # value discards its step instead of renaming it
        self._abort_gen = 0
        # payload bytes ``verify`` has checksummed: what a restore read
        self.bytes_verified = 0
        os.makedirs(directory, exist_ok=True)

    def _step_dir(self, iteration: int) -> str:
        return os.path.join(self.directory, f"{_STEP_PREFIX}{iteration:07d}")

    # --- background writer -------------------------------------------------

    @property
    def pending_count(self) -> int:
        """Queued + in-flight async saves not yet committed to disk."""
        with self._lock:
            return len(self._jobs) + self._inflight

    def pin(self, iteration: int | None) -> None:
        """Protect one step from ``keep_last_n`` garbage collection — the
        resilient loop pins its last verified-good rollback anchor."""
        with self._lock:
            self._pinned = iteration

    def save_async(
        self,
        iteration: int,
        user_factors,
        movie_factors,
        meta: dict | None = None,
    ) -> None:
        """Snapshot the factors to host and enqueue the disk write.

        The snapshot happens here (device arrays are fetched via a
        non-blocking ``copy_to_host_async`` issue, then materialized) so
        the caller may mutate/donate its buffers immediately; only the
        serialize + fsync + atomic rename runs on the writer thread.
        Blocks while more than ``max_pending`` saves are queued
        (back-pressure) and re-raises any earlier writer failure.  With
        ``async_write=False`` (the A/B baseline) this is exactly ``save``.
        """
        hu, hm = _host_snapshot(user_factors), _host_snapshot(movie_factors)
        meta = dict(meta or {})
        if meta.get(ARRAYS):
            meta[ARRAYS] = {k: np.array(v, copy=True)
                            for k, v in meta[ARRAYS].items()}
        self._enqueue(iteration, lambda: (hu, hm, meta))

    def submit(self, iteration: int, build) -> None:
        """Enqueue a step whose payload is made on the writer thread:
        ``build()`` returns ``(user_factors, movie_factors, meta)``.
        For a step folded from what the caller already holds by reference
        (a stream's overlay snapshot): the caller's thread pays neither the
        fold nor a copy."""
        self._enqueue(iteration, build)

    def _enqueue(self, iteration: int, build) -> None:
        if not self.async_write:
            hu, hm, meta = build()
            self.save(iteration, hu, hm, meta=meta)
            return
        _LIVE_MANAGERS.add(self)
        queued = time.perf_counter()
        with self._lock:
            self._raise_writer_error_locked()
            pending = len(self._jobs) + self._inflight
            if pending >= self.max_pending:
                with span("checkpoint/backpressure", pending=pending,
                          max_pending=self.max_pending):
                    while len(self._jobs) + self._inflight >= self.max_pending:
                        self._queue_nonfull.wait()
                        self._raise_writer_error_locked()
            self._jobs.append((iteration, build, queued, self._abort_gen))
            if self._writer_thread is None or not self._writer_thread.is_alive():
                self._writer_thread = threading.Thread(
                    target=self._writer_loop,
                    name="cfk-checkpoint-writer",
                    daemon=True,
                )
                self._writer_thread.start()

    def wait_pending(self, timeout: float | None = None) -> bool:
        """Barrier: block until every queued async save is committed.

        Returns True when drained (False on timeout) and re-raises the
        first writer error.  Safe to call with no writer running."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            while self._jobs or self._inflight:
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    return False
                self._queue_empty.wait(remaining)
            self._raise_writer_error_locked()
        return True

    def take_durable(self) -> list[int]:
        """The steps renamed into place (``save`` past its directory
        fsync) since this was last asked, in the order they landed."""
        with self._lock:
            done, self._durable = self._durable, []
        return done

    def abort_pending(self) -> int:
        """What a kill of the process does to the writer, without the
        kill: every queued job is dropped, and the job being written is
        discarded before its rename (one whose rename is already under
        way lands whole: a step is on disk entirely or not at all, as
        ever).  Returns the queued jobs dropped.  Does not wait;
        ``wait_pending`` returns once the writer is idle."""
        with self._lock:
            dropped = len(self._jobs)
            self._jobs.clear()
            self._abort_gen += 1
            self._queue_nonfull.notify_all()
            if not self._inflight:
                self._queue_empty.notify_all()
        return dropped

    def _raise_writer_error_locked(self) -> None:
        if self._writer_error is not None:
            err, self._writer_error = self._writer_error, None
            raise err

    def _writer_loop(self) -> None:
        while True:
            with self._lock:
                if not self._jobs:
                    self._queue_empty.notify_all()
                    # Park the thread: it dies when idle and is respawned by
                    # the next save_async (no join-at-shutdown bookkeeping).
                    self._writer_thread = None
                    return
                iteration, build, queued, gen = self._jobs.popleft()
                self._inflight += 1
                self._queue_nonfull.notify_all()
            try:
                with span("checkpoint/write", step=iteration,
                          queued_ms=(time.perf_counter() - queued) * 1e3
                          ) as sp:
                    hu, hm, meta = build()
                    if "kind" in meta:
                        sp.set(kind=meta["kind"])
                    self._wrote.last = (0, 0)
                    self._wrote.abort_gen = gen
                    try:
                        self.save(iteration, hu, hm, meta=meta)
                    finally:
                        self._wrote.abort_gen = None
                    nbytes, fsyncs = self._wrote.last
                    sp.set(bytes=nbytes, fsyncs=fsyncs)
            except _WriteAborted:
                pass
            except BaseException as e:
                with self._lock:
                    if self._writer_error is None:
                        self._writer_error = e
            finally:
                with self._lock:
                    self._inflight -= 1
                    self._queue_nonfull.notify_all()
                    if not self._jobs and not self._inflight:
                        self._queue_empty.notify_all()

    def _retain(self, just_saved: int) -> None:
        """Apply the ``keep_last_n`` retention policy after a commit."""
        if self.keep_last_n is None:
            return
        steps = self.iterations()
        keep = set(steps[-self.keep_last_n:])
        keep.add(just_saved)
        with self._lock:
            if self._pinned is not None:
                keep.add(self._pinned)
        for it in steps:
            if it not in keep:
                shutil.rmtree(self._step_dir(it), ignore_errors=True)

    def save(
        self,
        iteration: int,
        user_factors,
        movie_factors,
        meta: dict | None = None,
    ) -> str:
        meta = dict(meta or {})
        arrays = meta.pop(ARRAYS, None)
        u = np.asarray(user_factors)
        m = np.asarray(movie_factors)
        stored_dtype = str(u.dtype)
        # npy can't round-trip ml_dtypes (bfloat16 loads back as raw void
        # bytes) — store float32 on disk and re-cast at restore.
        if u.dtype not in (np.float32, np.float64):
            u = u.astype(np.float32)
            m = m.astype(np.float32)
        tmp = tempfile.mkdtemp(dir=self.directory, prefix=".tmp_")
        try:
            # each payload synced as it is written, all of them before
            # the manifest that names their checksums
            crc = {"user.npy": _save_array(os.path.join(tmp, "user.npy"), u),
                   "movie.npy": _save_array(os.path.join(tmp, "movie.npy"), m)}
            extra_bytes, index = 0, None
            if arrays:
                # one file for all of them: one more fsync a step, not one
                # more a payload
                parts, index = _pack_arrays(arrays)
                crc[_ARRAYS] = _write_synced(os.path.join(tmp, _ARRAYS), parts)
                extra_bytes = sum(part.nbytes for part in parts)
            manifest = {
                "iteration": iteration,
                "user_shape": list(u.shape),
                "movie_shape": list(m.shape),
                "dtype": stored_dtype,
                # Content checksums of the payloads: the atomic rename
                # makes half-written step dirs impossible, but not silent
                # corruption *after* commit (torn page on power loss, bad
                # sector, an operator's stray truncate) — restore verifies
                # these and falls back to the previous complete step.
                "crc32": crc,
                **meta,
            }
            if index is not None:
                manifest["arrays"] = index
            text = json.dumps(manifest)
            with open(os.path.join(tmp, _MANIFEST), "w") as f:
                f.write(text)
                f.flush()
                os.fsync(f.fileno())
            # payloads and manifest are synced; now the directories on both
            # sides of the rename: the emergency (preemption) save path
            # relies on a committed step surviving an immediately-following
            # power-off/kill, not just an orderly process exit.
            _fsync_dir(tmp)
            gen = getattr(self._wrote, "abort_gen", None)
            if gen is not None and gen != self._abort_gen:
                raise _WriteAborted(iteration)
            final = self._step_dir(iteration)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)
            _fsync_dir(self.directory)
            # durable from here: the payloads' and the manifest's bytes, and
            # the fsyncs asked for (the manifest's, the two payloads', the
            # directories' on both sides of the rename)
            self._wrote.last = (u.nbytes + m.nbytes + extra_bytes + len(text),
                                1 + len(crc) + 2)
            with self._lock:
                self._durable.append(iteration)
            self._retain(iteration)
            # Flight-record the commit (post-rename — the event means "this
            # step is durably on disk", the fact an incident reader needs).
            from cfk_tpu.telemetry.recorder import record_event

            record_event("checkpoint", "checkpoint_committed",
                         iteration=iteration)
            return final
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise

    def iterations(self) -> list[int]:
        steps = []
        for name in os.listdir(self.directory):
            if not name.startswith(_STEP_PREFIX):
                continue
            full = os.path.join(self.directory, name, _MANIFEST)
            if os.path.exists(full):  # only complete (renamed) steps
                steps.append(int(name[len(_STEP_PREFIX):]))
        return sorted(steps)

    def latest_iteration(self) -> int | None:
        steps = self.iterations()
        return steps[-1] if steps else None

    def verify(self, iteration: int) -> None:
        """Integrity-check one committed step; raises
        ``CheckpointCorruptError`` on a torn/corrupted payload.

        The manifest must parse and, when it carries ``crc32`` checksums
        (every checkpoint written since they were introduced), each npy
        payload must match byte-for-byte.  Checksum-less legacy steps
        pass with only the parse check.
        """
        manifest = self._manifest(iteration)
        for name, want in (manifest.get("crc32") or {}).items():
            self._read_checked(iteration, name, want)

    def _read_checked(self, iteration: int, name: str,
                      want: int | None) -> bytes | None:
        """One payload held to its manifest checksum (``want`` None: a
        checksum-less legacy step passes).  A small payload is read once
        and its bytes returned; a large one is checked through its file and
        None returned: the caller maps or loads it from there."""
        path = os.path.join(self._step_dir(iteration), name)
        try:
            size = os.path.getsize(path)
            if size > _SMALL:
                got, data = _crc32_file(path), None
            else:
                with open(path, "rb") as f:
                    data = f.read()
                got = zlib.crc32(data)
        except OSError as e:
            raise CheckpointCorruptError(
                f"checkpoint step {iteration} is missing payload "
                f"{name!r} ({e})"
            ) from None
        self.bytes_verified += size
        if want is not None and got != want:
            raise CheckpointCorruptError(
                f"checkpoint step {iteration} payload {name!r} fails "
                f"its manifest checksum (crc32 {got:#010x} != recorded "
                f"{want:#010x}); the file is torn or corrupted — "
                f"delete {self._step_dir(iteration)} or restore an earlier "
                "step"
            )
        return data

    def _manifest(self, iteration: int) -> dict:
        step = self._step_dir(iteration)
        try:
            with open(os.path.join(step, _MANIFEST)) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            raise CheckpointCorruptError(
                f"checkpoint step {iteration} in {self.directory} has an "
                f"unreadable manifest ({e}); the write was torn — delete "
                f"{step} or restore an earlier step"
            ) from None

    def latest_valid_iteration(self) -> int | None:
        """Newest step that passes integrity verification; corrupt steps
        are skipped (with a warning) in favor of older complete ones."""
        for it in reversed(self.iterations()):
            try:
                self.verify(it)
            except CheckpointCorruptError as e:
                warnings.warn(f"skipping corrupt checkpoint: {e}")
                # Flight-record the torn step (and dump): resume silently
                # falling back past a corrupt checkpoint is exactly the
                # kind of incident that must leave a forensic trail.
                from cfk_tpu.telemetry.recorder import (
                    dump_flight,
                    record_event,
                )

                record_event("checkpoint", "corrupt_checkpoint_skipped",
                             iteration=it, error=str(e))
                dump_flight("corrupt_checkpoint")
                continue
            return it
        return None

    def manifest_meta(self, iteration: int) -> dict:
        """The caller-supplied ``meta`` of one committed step, without
        loading the factor payloads — the fleet's covering-step search
        reads many hosts' manifests and must not page in factor bytes
        to decide which step is jointly restorable.  Verifies the step
        first (same contract as ``restore``)."""
        self.verify(iteration)
        with open(os.path.join(self._step_dir(iteration), _MANIFEST)) as f:
            manifest = json.load(f)
        return {
            k: v
            for k, v in manifest.items()
            if k not in _MANIFEST_OWN
        }

    def restore(self, iteration: int | None = None, *,
                mmap: bool = False) -> CheckpointState:
        """``mmap``: payloads past 64 MiB come back memory-mapped and
        read-only (a table the size of the host's memory is then paged in
        as it is read, not loaded beside the live one)."""
        if iteration is None:
            iteration = self.latest_valid_iteration()
            if iteration is None:
                raise FileNotFoundError(
                    f"no intact checkpoints in {self.directory}"
                )
        step = self._step_dir(iteration)
        manifest = self._manifest(iteration)
        crc = manifest.get("crc32") or {}

        def load(name):
            data = self._read_checked(iteration, name, crc.get(name))
            if data is not None:
                return np.load(io.BytesIO(data))
            return np.load(os.path.join(step, name),
                           mmap_mode="r" if mmap else None)

        u, m = load("user.npy"), load("movie.npy")
        want_dtype = manifest.get("dtype", "float32")
        if str(u.dtype) != want_dtype:
            import ml_dtypes  # ships with jax

            u = u.astype(np.dtype(getattr(ml_dtypes, want_dtype, want_dtype)))
            m = m.astype(u.dtype)
        meta = {
            k: v
            for k, v in manifest.items()
            if k not in _MANIFEST_OWN
        }
        extra = {}
        if "arrays" in manifest:
            data = self._read_checked(iteration, _ARRAYS, crc.get(_ARRAYS))
            if data is None:  # past ``_SMALL``: checked above, read now
                with open(os.path.join(step, _ARRAYS), "rb") as f:
                    data = f.read()
            extra = _unpack_arrays(data, manifest["arrays"])
        return CheckpointState(
            iteration=manifest["iteration"], user_factors=u, movie_factors=m,
            meta=meta, arrays=extra,
        )
