"""Concurrent host staging engine (ISSUE 13).

PR 10/11 gave the out-of-core tier its SCHEDULE — per-shard windows under
the all_gather chunk scan or the ring/hier_ring visit orders — but not its
CONCURRENCY: ``train_als_host_window`` drove shards serially at the Python
level, so every shard's host-side window work (the ``stage_chunks`` view
assembly, the ``HostFactorStore`` gather, ``quantize_rows_host``, the
crc32 staging verify, and the ``device_put`` issue) sat on the one
consuming thread, and the sharded host_window wall-clock overstated the
tier (the explicit ROADMAP caveat).  ALX (arXiv 2112.02194) hides factor
streaming behind compute by pipelining transfers per shard concurrently;
this module is that pipeline's host half.

``WindowStager`` serves staged windows to the per-shard half-steps in the
EXACT consumption order each schedule commits — the driver flattens
(shard, window) tasks shard-major, each shard's windows in its own visit
order — while staging AHEAD of consumption on a bounded thread pool:

- ``mode="pool"``: up to ``depth`` tasks are in flight beyond the window
  being consumed (``depth + 1`` windows live on device — the staging
  arena ``offload/budget.py`` charges), executed by up to ``workers``
  threads.  Shard d+1's windows stage while shard d's compute runs, and a
  straggling fetch on one shard (``SlowHostFetch(only_shard=)``) blocks
  only its own future — the other workers keep staging and the consumer
  keeps draining until it actually needs the late window.
- ``mode="serial"``: the task runs on the CALLER'S thread inside
  ``take()`` — byte-for-byte the PR 10/11 double-buffer schedule (the
  half-steps call ``take()`` for window w+1 between dispatching window
  w's compute and joining it), kept as the A/B baseline arm.

Ordering/bit-exactness contract: staging is a PURE READ of the host store
(the stores are only written after a half-iteration completes), every
window is consumed in its schedule position regardless of which thread
staged it, and the compute order is untouched — so pooled and serial
staging are crc-identical to each other and to the resident shard_map
paths (``tests/test_offload_sharded.py`` pins the matrix).

Failure contract: a worker exception (a ``WindowIntegrityError`` from the
staging checksum, a chaos ``StagingCrash``, anything) propagates out of
``take()`` as the staging error — never a hang — and ``close()`` cancels
the not-yet-started tasks and drains the running ones, so a recovery
rollback never races a worker still reading the pre-rollback store.

Accounting (the windowed drivers' ``offload_*`` gauges):

- ``stage_busy_s``   — summed wall seconds workers (or the serial caller)
  spent inside staging tasks;
- ``stage_stall_s``  — seconds the CONSUMING thread waited in ``take()``
  for a window that was not ready: the staging time actually exposed to
  the critical path (serial mode exposes all of it by construction);
- ``pool_peak_inflight`` / ``pool_worker_stagings`` — proof the pool
  actually overlapped (the chaos straggler drill asserts on them).

``overlap_hidden_fraction = 1 - stall/busy`` is the headline column.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

from cfk_tpu.telemetry import record_event, span
from cfk_tpu.telemetry.recorder import dump_flight

# Staged-ahead windows beyond the one being consumed.  The driver clamps
# this by the window budget (depth + 1 windows must fit the staging
# share) and by the task count; 4 keeps four shards' first windows in
# flight at the default sharded shapes.
DEFAULT_POOL_DEPTH = 4
# Worker threads are capped at the depth (more could never run) and at a
# small constant — staging is memory-bound host work, and past a few
# threads the copies contend for the same bandwidth the jit compute uses.
MAX_POOL_WORKERS = 4

STAGING_MODES = ("pool", "serial")


class StagingStats(dict):
    """A stats dict with a lock: pooled staging increments shared
    counters from worker threads, and an unguarded read-modify-write
    would lose counts (``stats_add``/``stats_max`` take the lock)."""

    def __init__(self, *args, **kwargs) -> None:
        super().__init__(*args, **kwargs)
        self.lock = threading.Lock()


def stats_add(stats, key: str, val) -> None:
    """``stats[key] += val`` — under the lock when ``stats`` carries one
    (``StagingStats``); plain dicts (single-threaded callers, tests) are
    updated directly."""
    if stats is None:
        return
    lock = getattr(stats, "lock", None)
    if lock is not None:
        with lock:
            stats[key] = stats.get(key, 0) + val
    else:
        stats[key] = stats.get(key, 0) + val


def stats_max(stats, key: str, val) -> None:
    """``stats[key] = max(stats[key], val)`` with the same locking rule."""
    if stats is None:
        return
    lock = getattr(stats, "lock", None)
    if lock is not None:
        with lock:
            stats[key] = max(stats.get(key, 0), val)
    else:
        stats[key] = max(stats.get(key, 0), val)


def resolve_staging(staging: str | None) -> str:
    """The staging mode a driver runs: an explicit pin wins, ``None``/
    ``"auto"`` resolves to the pool (the concurrency is the default
    execution mode at ANY shard count — even one shard's windows stage
    ahead across windows — like PR 1's overlap; serial is the A/B
    baseline)."""
    if staging in (None, "auto"):
        return "pool"
    if staging not in STAGING_MODES:
        raise ValueError(
            f"staging must be one of {STAGING_MODES} (or 'auto'), "
            f"got {staging!r}"
        )
    return staging


def pool_workers_for(depth: int, workers: int | None = None) -> int:
    """Worker-thread count for a pool of ``depth``: never more threads
    than windows that can be in flight, never more than the cap."""
    if workers is not None:
        return max(1, min(int(workers), max(int(depth), 1)))
    return max(1, min(int(depth), MAX_POOL_WORKERS))


class WindowStager:
    """Stage (shard, window) tasks ahead of consumption, in order.

    ``tasks`` is the flattened consumption order — the driver lists every
    shard's schedule shard-major, each shard's windows in the exact visit
    order its half-step will request them — and ``stage_fn(shard, key)``
    performs one staging (gather + quantize + verify + ``device_put``).
    ``take()`` returns the next task's staged result; the caller calls it
    exactly ``len(tasks)`` times, in order, which is what lets the pooled
    and serial modes share one consumption seam.
    """

    def __init__(self, tasks, stage_fn, *, mode: str = "pool",
                 depth: int = DEFAULT_POOL_DEPTH, workers: int | None = None,
                 stats=None, span_attrs=None) -> None:
        if mode not in STAGING_MODES:
            raise ValueError(
                f"staging mode must be one of {STAGING_MODES}, got {mode!r}"
            )
        self._tasks = list(tasks)
        self._fn = stage_fn
        self.mode = mode
        self._stats = stats
        # Optional (shard, key) -> dict of extra window_stage span attrs
        # (ISSUE 15: rows_staged / rows_delta_skipped / rows_hot — plan-
        # time constants, so the provider must be a pure lookup; it runs
        # on worker threads).
        self._span_attrs = span_attrs
        self._next_submit = 0
        self._next_take = 0
        self._closed = False
        self._pool = None
        self._futures: dict[int, object] = {}
        self._inflight = 0
        self._lock = threading.Lock()
        if mode == "pool" and self._tasks:
            self.depth = max(int(depth), 1)
            self.workers = pool_workers_for(self.depth, workers)
            self._pool = ThreadPoolExecutor(
                max_workers=self.workers, thread_name_prefix="cfk-stage",
            )
            for _ in range(min(self.depth, len(self._tasks))):
                self._submit_next()
        else:
            self.depth = 0
            self.workers = 0

    # -- internals -----------------------------------------------------------

    def _run(self, idx: int):
        shard, key = self._tasks[idx]
        with self._lock:
            self._inflight += 1
            peak = self._inflight
        stats_max(self._stats, "pool_peak_inflight", peak)
        t0 = time.perf_counter()
        try:
            # The worker span carries thread (implicit) + (shard, window)
            # ids, so pool overlap against the consuming compute spans is
            # VISIBLE in the trace; its duration is exactly the interval
            # stage_busy_s meters, which is what lets the trace-recomputed
            # overlap fraction agree with the driver's gauge.  Extra attrs
            # (rows_staged / rows_delta_skipped) come from the driver's
            # provider so the trace shows the hot/delta reuse per window.
            extra = (self._span_attrs(shard, key)
                     if self._span_attrs is not None else {})
            with span("train/iter/half_step/window_stage",
                      shard=shard, window=key, mode=self.mode, **extra):
                out = self._fn(shard, key)
        finally:
            with self._lock:
                self._inflight -= 1
        stats_add(self._stats, "stage_busy_s",
                  time.perf_counter() - t0)
        if threading.current_thread().name.startswith("cfk-stage"):
            stats_add(self._stats, "pool_worker_stagings", 1)
        return out

    def _submit_next(self) -> None:
        i = self._next_submit
        if i < len(self._tasks):
            self._futures[i] = self._pool.submit(self._run, i)
            self._next_submit += 1

    # -- the consumption seam ------------------------------------------------

    @property
    def remaining(self) -> int:
        return len(self._tasks) - self._next_take

    def take(self):
        """The next task's staged result, in task order.

        Serial mode runs the staging HERE, on the consuming thread — the
        exact schedule position the PR 10 double buffer used (the caller
        dispatches window w's compute before asking for window w+1).
        Pool mode waits on the pre-submitted future; a worker exception
        re-raises here as the staging error (after cancelling the rest),
        and the wait time is metered as the exposed staging stall."""
        i = self._next_take
        if i >= len(self._tasks):
            raise IndexError("WindowStager exhausted: every task taken")
        self._next_take += 1
        shard, key = self._tasks[i]
        if self._pool is None:
            # Serial: the whole staging occupies the consuming thread —
            # stall == busy by construction, which is what makes the
            # overlap_hidden_fraction column read 0 for the baseline arm.
            t0 = time.perf_counter()
            try:
                with span("train/iter/half_step/window_wait",
                          shard=shard, window=key, mode=self.mode):
                    out = self._run(i)
            except BaseException as e:
                record_event("fault", "staging_error", shard=shard,
                             window=key, error=f"{type(e).__name__}: {e}")
                dump_flight("staging_error")
                raise
            stats_add(self._stats, "stage_stall_s",
                      time.perf_counter() - t0)
            return out
        fut = self._futures.pop(i)
        t0 = time.perf_counter()
        try:
            with span("train/iter/half_step/window_wait",
                      shard=shard, window=key, mode=self.mode):
                out = fut.result()
        except BaseException as e:
            # Propagate as the staging error — never leave workers
            # running against a store the caller is about to roll back.
            # Flight-record first: a staging-worker death is exactly the
            # incident the ring buffer exists to explain.
            record_event("fault", "staging_error", shard=shard, window=key,
                         error=f"{type(e).__name__}: {e}")
            dump_flight("staging_error")
            self.close()
            raise
        stats_add(self._stats, "stage_stall_s",
                  time.perf_counter() - t0)
        self._submit_next()
        return out

    def close(self) -> None:
        """Cancel not-yet-started tasks and drain running ones.
        Idempotent; the driver calls it in a ``finally`` around each
        half-iteration (rollback must not race a staging worker)."""
        if self._closed:
            return
        self._closed = True
        if self._pool is not None:
            for f in self._futures.values():
                f.cancel()
            self._pool.shutdown(wait=True)
            self._futures.clear()
            self._pool = None

    def __enter__(self) -> "WindowStager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
