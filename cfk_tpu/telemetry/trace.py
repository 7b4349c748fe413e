"""Hierarchical span tracing: a low-overhead, thread-aware host tracer.

The reference's entire timeline story was wall-clock ``println`` stamps at
phase edges; ``utils.metrics.Metrics.phase`` improved that to *accumulated*
seconds per phase name — good for bench rows, useless for questions of
SHAPE: does the staging pool's host gather actually run under the consuming
shard's compute?  Which ring visit straggles?  Where does a serve batch's
latency go between assembly, kernel, and respond?  Those are timeline
questions, and this module answers them the way ALX-style systems do: with
a per-thread span timeline exported as Chrome-trace/Perfetto JSON, written
next to the ``maybe_profile`` jax-profiler trace so the host and device
timelines can be read side by side (pass the same ``--trace-dir``).

Design constraints (the sentinel discipline, ISSUE 3's ≤2% budget):

- **Off is near-free and bit-identical.**  No tracer installed ⇒
  ``span()`` returns a module-level null context manager: one global read
  and one function call, no allocation.  Tracing never touches device
  values, so on/off results are identical by construction (held by
  ``tests/test_telemetry.py::test_tracer_on_trains_the_same_factors`` and
  ``tests/test_serve_spans.py::test_answers_are_bit_identical_traced_and_untraced``).
- **Thread-aware.**  Every event records its OS thread; staging-pool
  worker spans carry the (shard, window) ids their task staged, so pool
  overlap is *visible* in the trace instead of inferred from counters.
- **Attributes at the boundary.**  ``with span(...) as sp`` hands back
  the open span: ``sp.set(bytes=...)`` adds what is only known once the
  work is done, ``sp.drop()`` writes no event (an empty poll).  Both are
  no-ops on the null span.
- **One clock pair.**  A ``Tracer`` samples ``(perf_counter_ns,
  time_ns)`` once, at construction.  ``events()`` stays on
  ``perf_counter`` microseconds; the Chrome export is on the unix epoch
  through that pair (``Tracer.to_unix_ns``), the clock a ``jax.profiler``
  trace's ``profile_start_time`` is on, so the two files line up.
- **Runtime hooks live with the tracer.**  What pauses a stage without
  being a stage is put on the same clock by a hook that ``configure()``
  installs and ``shutdown()`` removes: with no tracer installed the
  runtime holds nothing of ours.  The one hook is on ``gc.callbacks``:
  every pass of Python's cyclic collector is a ``runtime/gc`` span
  (``GC_SPAN``) on the thread that ran it, nested in whatever stage was
  open.  ``Tracer.complete`` writes a span whose start was stamped
  earlier, for work that begins in one call and ends in another.

Span naming: callers pass the FULL span-name path (``train/iter/half_step/
window_stage``) — explicit at the call site, zero path-joining overhead
in the hot path.  The naming scheme is documented in ARCHITECTURE.md
("Telemetry").
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time

# Hard cap on buffered events: a runaway loop must degrade to dropped
# events (counted), never to unbounded memory.
MAX_EVENTS = 1_000_000

# One pass of Python's cyclic collector, on the thread that ran it.  A
# reader that finds this name here and no such span in a trace knows the
# hook was in and no pass ran; a program without the name has no hook.
GC_SPAN = "runtime/gc"


class _NullSpan:
    """The telemetry-off fast path: a reusable no-op context manager."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass

    def drop(self) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _SpanCM:
    """One with-block span; allocated per use (only when tracing is ON)."""

    __slots__ = ("_tracer", "_name", "_attrs", "_t0")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict) -> None:
        self._tracer = tracer
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> "_SpanCM":
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **attrs) -> None:
        """Attributes known only at the span's far boundary."""
        self._attrs.update(attrs)

    def drop(self) -> None:
        """Write no event for this span."""
        self._tracer = None

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter_ns()
        if self._tracer is None:
            return False
        if exc_type is not None:
            # annotate, never swallow — a span that died mid-fault is
            # exactly the event a flight-recorder reader wants labelled
            self._attrs = dict(self._attrs, error=exc_type.__name__)
        # ts and dur both derive from the μs-truncated endpoints (not
        # dur = (t1-t0)//1000): truncating the difference independently
        # can make a child span's end exceed its parent's by 1μs, which
        # would read as a malformed tree.
        ts = self._t0 // 1000
        self._tracer._emit(
            self._name, ts, t1 // 1000 - ts,
            threading.get_ident(), self._attrs,
        )
        return False


class Tracer:
    """Collect host spans; export Chrome-trace JSON.

    Events are appended to one shared list under a small lock (append is
    tens of nanoseconds; span granularity here is per-iteration /
    per-window / per-batch, so contention is negligible against the ≤2%
    budget).  Nesting needs no bookkeeping: with-block spans close in
    LIFO order per thread and ts/dur derive from shared µs-truncated
    endpoints, so the exported tree's well-formedness is checkable from
    the events alone (``validate_span_tree``)."""

    def __init__(self, trace_dir: str | None = None) -> None:
        self.trace_dir = trace_dir
        self._events: list[dict] = []
        # re-entrant: a collector pass can start on a thread that holds it
        # (between two bytecodes of ``_emit``), and its span is emitted
        # from inside the pass
        self._lock = threading.RLock()
        self._gc_t0 = 0  # perf_counter_ns at the start of the pass under way
        self._thread_names: dict[int, str] = {}
        self.dropped = 0
        # the one reading of both clocks: perf_counter (what every event is
        # stamped with) against the unix epoch (what the export is on)
        self.clock_pair_ns = (time.perf_counter_ns(), time.time_ns())

    def to_unix_ns(self, ts_us: int) -> int:
        """An event's ``ts`` (perf_counter microseconds) on the unix epoch."""
        perf_ns, unix_ns = self.clock_pair_ns
        return unix_ns + (int(ts_us) * 1000 - perf_ns)

    # -- recording -----------------------------------------------------------

    def _emit(self, name: str, ts_us: int, dur_us: int, tid: int,
              attrs: dict) -> None:
        """One locked append with the cap + thread-name bookkeeping."""
        event = {
            "name": name, "ph": "X", "ts": ts_us, "dur": dur_us,
            "pid": os.getpid(), "tid": tid, "args": attrs,
        }
        with self._lock:
            if len(self._events) >= MAX_EVENTS:
                self.dropped += 1
                return
            if tid not in self._thread_names:
                self._thread_names[tid] = threading.current_thread().name
            self._events.append(event)

    def span(self, name: str, **attrs) -> _SpanCM:
        return _SpanCM(self, name, attrs)

    def complete(self, name: str, t0_ns: int, **attrs) -> None:
        """A span on this thread from ``t0_ns``, a ``perf_counter_ns``
        reading taken when the work began, to now: for work that begins
        in one call and ends in another."""
        ts = t0_ns // 1000
        self._emit(name, ts, time.perf_counter_ns() // 1000 - ts,
                   threading.get_ident(), attrs)

    def _on_gc(self, phase: str, info: dict) -> None:
        """The ``gc.callbacks`` hook: a pass is one ``GC_SPAN``.  Passes
        never overlap in a process (the collector is not re-entrant), so
        one stamp serves; a pass already under way when the hook went in
        has no start and writes nothing."""
        if phase == "start":
            self._gc_t0 = time.perf_counter_ns()
        elif self._gc_t0:
            t0, self._gc_t0 = self._gc_t0, 0
            self.complete(GC_SPAN, t0, generation=info["generation"],
                          collected=info["collected"],
                          uncollectable=info["uncollectable"])

    # -- export --------------------------------------------------------------

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    def chrome_trace(self) -> dict:
        """The Chrome-trace JSON object (Perfetto / chrome://tracing), its
        ``ts`` in microseconds of the unix epoch; ``metadata`` carries the
        clock pair the conversion went through."""
        # a whole number of microseconds: every ts moves by the same
        # amount, so spans that nest in events() nest in the export
        shift_us = self.to_unix_ns(0) // 1000
        events = [dict(e, ts=e["ts"] + shift_us) for e in self.events()]
        with self._lock:
            names = dict(self._thread_names)
        meta = [
            {
                "name": "thread_name", "ph": "M", "pid": os.getpid(),
                "tid": tid, "args": {"name": tname},
            }
            for tid, tname in sorted(names.items())
        ]
        perf_ns, unix_ns = self.clock_pair_ns
        return {
            "traceEvents": meta + events, "displayTimeUnit": "ms",
            "metadata": {"ts_epoch": "unix", "clock_perf_counter_ns": perf_ns,
                         "clock_unix_ns": unix_ns},
        }

    def write(self, path: str | None = None) -> str | None:
        """Atomically write the Chrome trace; returns the path (None when
        no directory is configured and no path given)."""
        if path is None:
            if self.trace_dir is None:
                return None
            os.makedirs(self.trace_dir, exist_ok=True)
            path = os.path.join(
                self.trace_dir, f"cfk_host_trace_{os.getpid()}.json"
            )
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.chrome_trace(), f)
        os.replace(tmp, path)
        return path


# -- module-level singleton + fast-path API ----------------------------------

_TRACER: Tracer | None = None


def _unhook(tracer: Tracer | None) -> None:
    if tracer is not None and tracer._on_gc in gc.callbacks:
        gc.callbacks.remove(tracer._on_gc)


def configure(trace_dir: str | None = None) -> Tracer:
    """Install (and return) the process tracer and its runtime hook (one
    callback on ``gc.callbacks``); a tracer installed before gives both
    up.  Until this is called, every ``span()`` is the null fast path."""
    global _TRACER
    _unhook(_TRACER)
    _TRACER = Tracer(trace_dir=trace_dir)
    gc.callbacks.append(_TRACER._on_gc)
    return _TRACER


def get_tracer() -> Tracer | None:
    return _TRACER


def shutdown(write: bool = True) -> str | None:
    """Uninstall the tracer and its runtime hook; optionally write its
    trace first."""
    global _TRACER
    t = _TRACER
    _TRACER = None
    _unhook(t)
    if t is not None and write:
        return t.write()
    return None


def span(name: str, **attrs):
    """A span context manager — the null singleton when tracing is off."""
    t = _TRACER
    if t is None:
        return _NULL_SPAN
    return t.span(name, **attrs)


# -- analysis helpers --------------------------------------------------------

def validate_span_tree(events: list[dict]) -> dict[int, int]:
    """Check the exported complete-span events form a well-formed tree per
    thread: within one tid, spans either nest or are disjoint (the
    property the per-thread enter/exit stack guarantees — a torn pair
    shows up here as an overlap that is not containment).  Returns
    {tid: span_count}; raises ValueError naming the first violation."""
    by_tid: dict[int, list[dict]] = {}
    for e in events:
        if e.get("ph") == "X":
            by_tid.setdefault(e["tid"], []).append(e)
    counts: dict[int, int] = {}
    for tid, evs in by_tid.items():
        counts[tid] = len(evs)
        evs = sorted(evs, key=lambda e: (e["ts"], -e["dur"]))
        stack: list[tuple[int, int, str]] = []  # (start, end, name)
        for e in evs:
            s, d = e["ts"], e["ts"] + e["dur"]
            while stack and s >= stack[-1][1]:
                stack.pop()
            if stack and d > stack[-1][1]:
                raise ValueError(
                    f"tid {tid}: span {e['name']!r} [{s}, {d}] overlaps "
                    f"but does not nest inside {stack[-1][2]!r} "
                    f"[{stack[-1][0]}, {stack[-1][1]}]"
                )
            stack.append((s, d, e["name"]))
    return counts


def stage_overlap_from_events(events: list[dict]) -> float | None:
    """Recompute the staging engine's ``overlap_hidden_fraction`` from
    trace spans alone: 1 − (consumer wait)/(worker busy), where busy is
    the summed duration of ``window_stage`` spans and wait the summed
    duration of ``window_wait`` spans — the same two intervals
    ``offload/staging.py`` meters into ``stage_busy_s``/``stage_stall_s``,
    measured independently by the tracer.  The acceptance check: this
    number agrees with the driver's own ``offload_stage_hidden_frac``
    gauge within 5%.  Returns None when no staging spans are present."""
    busy = sum(e["dur"] for e in events
               if e.get("ph") == "X" and e["name"].endswith("window_stage"))
    stall = sum(e["dur"] for e in events
                if e.get("ph") == "X" and e["name"].endswith("window_wait"))
    if busy <= 0:
        return None
    return max(0.0, 1.0 - stall / busy)
