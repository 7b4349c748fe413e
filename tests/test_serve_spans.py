"""Spans of one serve step (ISSUE 24): every stretch of the batch period in
which the host works or waits has a span at the place the work happens, the
spans of one step share its ordinal, tracing changes no answer, and the
Chrome export is on the unix epoch through the tracer's one clock pair.
Under a backlog (ISSUE 33) a step's ``serve/batch`` holds the stages of the
batch it polled and, in its one ``serve/batch/compute``, that batch's
dispatch and the fetch and the counters of the batch it answers."""

import json
import time

import numpy as np
import pytest

from cfk_tpu import telemetry
from cfk_tpu.serving import engine as engine_mod
from cfk_tpu.serving.server import (
    RecommendServer,
    ServeClient,
    ensure_serve_topics,
)
from cfk_tpu.transport import InMemoryBroker

BATCH_SPANS = (
    "serve/poll",
    "serve/batch",
    "serve/batch/validate",
    "serve/batch/assemble",
    "serve/batch/seen_tiles",
    "serve/batch/upload",
    "serve/batch/compute",
    "serve/batch/compute/dispatch",
    "serve/batch/compute/fetch",
    "serve/batch/respond",
)
UPLOAD_SPAN = "serve/engine/table_upload"  # set-up, once per table
NUM_USERS, NUM_MOVIES, RANK = 12, 40, 4


@pytest.fixture
def tracer():
    t = telemetry.configure()
    yield t
    telemetry.shutdown(write=False)


def _engine(**kw):
    """A toy engine whose every user has rated three movies."""
    rng = np.random.default_rng(3)
    seen = np.concatenate([
        np.sort(rng.choice(NUM_MOVIES, 3, replace=False))
        for _ in range(NUM_USERS)
    ]).astype(np.int32)
    return engine_mod.ServeEngine(
        rng.standard_normal((NUM_USERS, RANK), dtype=np.float32),
        rng.standard_normal((NUM_MOVIES, RANK), dtype=np.float32),
        num_users=NUM_USERS, num_movies=NUM_MOVIES, seen_movies=seen,
        seen_indptr=np.arange(NUM_USERS + 1, dtype=np.int64) * 3,
        tile_m=16, batch_quantum=4, **kw,
    )


def _served(engine, users, *, max_batch=4, k=3):
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(engine, broker, max_batch=max_batch)
    client = ServeClient(broker)
    for u in users:
        client.request(u, k)
    return server, client


def _serve_events(tracer):
    """The server's and the engine's spans, by name: a pass of the
    collector (``runtime/gc``) may fall anywhere among them, and the
    client's spans (``serve/client/*``) have a test of their own."""
    return [e for e in tracer.events()
            if e["name"].startswith("serve/")
            and not e["name"].startswith("serve/client/")]


def _by_name(events):
    out = {}
    for e in events:
        out.setdefault(e["name"], []).append(e)
    return out


def test_one_step_emits_each_span_once(tracer, monkeypatch):
    grouped = []
    real = engine_mod.group_seen_cells

    def recording(*a, **kw):
        grouped.append(real(*a, **kw))
        return grouped[-1]

    monkeypatch.setattr(engine_mod, "group_seen_cells", recording)
    # four produced, four polled, nothing behind them: straight through
    server, client = _served(_engine(), range(4), max_batch=4)
    assert server.step() == 4
    events = tracer.events()
    spans = _by_name(_serve_events(tracer))
    # the engine was built under the tracer: its set-up span (PR 32: on the
    # one-device route too), then one of each of the batch's
    assert sorted(spans) == sorted(BATCH_SPANS + (UPLOAD_SPAN,))
    assert all(len(v) == 1 for v in spans.values())
    telemetry.validate_span_tree(events)
    args = {name: v[0]["args"] for name, v in spans.items()}
    assert args["serve/poll"]["batch"] == args["serve/batch"]["batch"] == 1
    assert args["serve/poll"]["requests"] == 4
    assert args["serve/poll"]["malformed"] == 0
    assert args["serve/poll"]["pending_after"] == 0
    assert args["serve/batch"] == {"requests": 4, "shed": 0, "batch": 1,
                                   "overlapped": False}
    assert args["serve/batch/assemble"]["seen_cells"] == 4 * 3
    # the rectangle the device builds, and what the host built for it: the
    # batch's twelve cells in one [4, capacity] int32 piece
    ((cells, shape),) = grouped
    assert shape == (3, 4, 16) and cells.shape == (4, 12)
    capacity = engine_mod.seen_cell_capacity(4)
    assert args["serve/batch/seen_tiles"] == {
        "tiles": 3, "b": 4, "width": 16, "cells": 12, "capacity": capacity,
        "chunks": 1, "programs": 1, "bytes": 4 * capacity * 4}
    # handed over: that piece and the [b, rank] float32 user batch
    assert args["serve/batch/upload"]["bytes"] == (4 * capacity * 4
                                                   + 4 * RANK * 4)
    # [b, k_pad] float32 scores and int32 ids come back
    assert args["serve/batch/compute/fetch"]["bytes"] == 2 * 4 * 8 * 4
    assert args["serve/batch/respond"]["responses"] == 4
    assert args["serve/batch/respond"]["bytes"] > 0
    # the next batch carries the next ordinal on both of its spans
    tracer.clear()
    for u in range(3):
        client.request(u, 3)
    assert server.step() == 3
    spans = _by_name(_serve_events(tracer))
    assert spans["serve/poll"][0]["args"]["batch"] == 2
    assert spans["serve/batch"][0]["args"]["batch"] == 2
    assert spans["serve/poll"][0]["args"]["pending_after"] == 0


@pytest.mark.parametrize("capacity, pieces, programs", [
    (24, 1, 1), (12, 2, 1), (8, 3, 1), (5, 5, 1), (2, 12, 1), (1, 24, 2)])
def test_seen_tiles_span_counts_the_runs_of_the_build_program(
        capacity, pieces, programs, tracer, monkeypatch):
    """``programs`` on ``serve/batch/seen_tiles`` is what the batch's
    rectangle costs in runs of the scatter program: 1 for any list up to
    the ladder's top rung of pieces (``chunks`` of ``capacity`` cells),
    one more for every top rung's worth past it; ``bytes`` is the padded
    list the host built and handed over."""
    # eight users' three cells each against pieces of `capacity` cells
    monkeypatch.setattr(engine_mod, "seen_cell_capacity", lambda b: capacity)
    server, _ = _served(_engine(), range(8), max_batch=8)
    tracer.clear()
    assert server.step() == 8
    (sp,) = _by_name(_serve_events(tracer))["serve/batch/seen_tiles"]
    rung = engine_mod.seen_piece_rung(pieces)
    assert sp["args"] == {
        "tiles": 3, "b": 8, "width": 16, "cells": 24, "capacity": capacity,
        "chunks": pieces, "programs": programs,
        "bytes": programs * 4 * rung * capacity * 4}
    assert programs == -(-pieces // engine_mod.SEEN_PIECE_RUNGS[-1])


def test_under_a_backlog_each_step_has_one_compute_of_two_batches(tracer):
    """Eleven requests in batches of four (4, 4, 3): the first step only
    hands its batch over, each later one hands over the batch it polled and
    fetches and answers the one before, the last (an empty poll) only
    answers.  One ``serve/poll``, one ``serve/batch`` and one
    ``serve/batch/compute`` a step, the ordinals agree, and the compute
    span's counters are the ANSWERED batch's."""
    server, _ = _served(_engine(), range(11), max_batch=4)
    tracer.clear()
    steps = []
    for want in (0, 4, 4, 3):
        assert server.step() == want
        steps.append(_by_name(_serve_events(tracer)))
        telemetry.validate_span_tree(tracer.events())
        tracer.clear()
    assert server.step() == 0 and _serve_events(tracer) == []
    staged = ("serve/batch/validate", "serve/batch/assemble",
              "serve/batch/seen_tiles", "serve/batch/upload",
              "serve/batch/compute/dispatch")
    answered = ("serve/batch/compute/fetch", "serve/batch/respond")
    always = ("serve/poll", "serve/batch", "serve/batch/compute")
    for ordinal, spans in enumerate(steps, start=1):
        want = always + (staged if ordinal < 4 else ()) \
            + (answered if ordinal > 1 else ())
        assert sorted(spans) == sorted(want), ordinal
        assert all(len(v) == 1 for v in spans.values())
        assert spans["serve/poll"][0]["args"]["batch"] == ordinal
        batch = spans["serve/batch"][0]["args"]
        assert batch["batch"] == ordinal
        assert batch["overlapped"] is (ordinal > 1)
    polled = [s["serve/batch"][0]["args"]["requests"] for s in steps]
    assert polled == [4, 4, 3, 0]
    assert [s["serve/poll"][0]["args"]["pending_after"] for s in steps] \
        == [7, 3, 0, 0]
    # the first step's compute holds a dispatch and no batch's counters
    assert steps[0]["serve/batch/compute"][0]["args"] == {}
    # the third polls three rows (padded to four) and answers batch two
    assert steps[2]["serve/batch/assemble"][0]["args"]["n"] == 3
    keys = {"n", "b", "k", "select_rounds", "select_tiles", "seen_chunks",
            "seen_hit_tiles", "completed_tiles", "tiles", "table_dtype",
            "scan_bytes",
            "score_passes", "slab_tiles", "grid_steps"}
    for spans, n in zip(steps[1:], (4, 4, 3)):
        compute = spans["serve/batch/compute"][0]["args"]
        assert set(compute) == keys
        assert (compute["n"], compute["b"], compute["k"]) == (n, 4, 8)
        assert spans["serve/batch/respond"][0]["args"]["responses"] == n
        assert spans["serve/batch/compute/fetch"][0]["args"]["bytes"] \
            == 2 * 4 * 8 * 4
    assert server.metrics.counters["serve_batches_overlapped"] == 3
    assert server.metrics.counters["serve_batches"] == 3


def test_children_nest_in_their_parents(tracer):
    server, _ = _served(_engine(), range(4))
    server.step()
    spans = {e["name"]: e for e in _serve_events(tracer)}

    def inside(child, parent):
        c, p = spans[child], spans[parent]
        return p["ts"] <= c["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"]

    for child in BATCH_SPANS[2:]:
        assert inside(child, "serve/batch"), child
    for child in BATCH_SPANS[7:9]:
        assert inside(child, "serve/batch/compute"), child
    poll, batch = spans["serve/poll"], spans["serve/batch"]
    assert poll["ts"] + poll["dur"] <= batch["ts"]
    order = [spans[n]["ts"] for n in BATCH_SPANS[2:]]
    assert order == sorted(order)


def test_an_empty_poll_emits_no_event(tracer):
    server, client = _served(_engine(), [])
    built = _serve_events(tracer)  # the engine's set-up span, nothing else
    assert [e["name"] for e in built] == [UPLOAD_SPAN]
    assert server.step() == 0
    assert _serve_events(tracer) == built
    # a frame that decodes to nothing still leaves a poll with no requests
    client.transport.produce(server.requests_topic, key=0, value=b"junk",
                             partition=0)
    assert server.step() == 0
    assert server.malformed_requests == 1
    assert _serve_events(tracer) == built


@pytest.mark.parametrize("exclude_seen", [True, False])
def test_answers_are_bit_identical_traced_and_untraced(exclude_seen):
    eng = _engine()
    if not exclude_seen:
        eng._seen_movies = eng._seen_indptr = None
    rows = np.array([5, 0, 11, 5, 2], np.int64)
    assert telemetry.get_tracer() is None
    off = eng.topk(rows, 8)
    assert telemetry.get_tracer() is None  # serving installs no tracer
    tracer = telemetry.configure()
    try:
        on = eng.topk(rows, 8)
        recorded = _serve_events(tracer)
    finally:
        telemetry.shutdown(write=False)
    names = {e["name"] for e in recorded}
    assert ("serve/batch/seen_tiles" in names) == exclude_seen
    assert "serve/batch/upload" in names
    np.testing.assert_array_equal(off[0], on[0])
    np.testing.assert_array_equal(off[1], on[1])
    # once more with it off again: no event exists for that batch
    again = eng.topk(rows, 8)
    np.testing.assert_array_equal(off[1], again[1])
    assert telemetry.get_tracer() is None and _serve_events(tracer) == recorded


def test_server_responses_identical_traced_and_untraced():
    def answers(traced):
        tracer = telemetry.configure() if traced else None
        try:
            server, client = _served(_engine(), [3, 1, 4, 1])
            server.step()
            got = sorted(client.poll_responses(), key=lambda r: r.req_id)
            events = _serve_events(tracer) if traced else []
        finally:
            telemetry.shutdown(write=False)
        return [(r.movie_rows.tobytes(), r.scores.tobytes()) for r in got], events

    off, none = answers(False)
    on, events = answers(True)
    assert off == on and len(off) == 4
    assert none == [] and sorted(e["name"] for e in events) \
        == sorted(BATCH_SPANS + (UPLOAD_SPAN,))


def test_export_is_on_the_unix_epoch_events_on_perf_counter(tmp_path, tracer):
    p0 = time.perf_counter_ns() // 1000
    with telemetry.span("serve/batch"):
        with telemetry.span("serve/batch/compute"):
            pass
    p1 = time.perf_counter_ns() // 1000
    now_ns = time.time_ns()
    events = _serve_events(tracer)
    assert all(p0 <= e["ts"] <= e["ts"] + e["dur"] <= p1 for e in events)
    with open(tracer.write(str(tmp_path / "trace.json"))) as f:
        doc = json.load(f)
    xs = [e for e in doc["traceEvents"]
          if e["ph"] == "X" and e["name"].startswith("serve/")]
    assert len(xs) == 2
    for e in xs:
        assert abs(e["ts"] * 1000 - now_ns) < 1e9
    meta = doc["metadata"]
    assert meta["ts_epoch"] == "unix"
    assert (meta["clock_perf_counter_ns"], meta["clock_unix_ns"]) \
        == tracer.clock_pair_ns
    # one conversion: the export is events() moved through to_unix_ns, by a
    # whole number of microseconds, so the exported spans nest as they did
    by_name = {e["name"]: e for e in events}
    for e in xs:
        assert e["ts"] == tracer.to_unix_ns(by_name[e["name"]]["ts"]) // 1000
        assert e["dur"] == by_name[e["name"]]["dur"]
    telemetry.validate_span_tree(xs)
    # events() itself was not moved by the export
    assert _serve_events(tracer) == events


def test_batch_and_compute_bracket_the_same_calls(tracer, monkeypatch):
    """Seen tiles and upload sit before ``serve/batch/compute`` opens, the
    call into the scorer and the fetch inside it: a scripted engine whose
    stages sleep known amounts."""
    import jax.numpy as jnp

    tiles_s, upload_s, dispatch_s = 0.05, 0.03, 0.02
    real_tiles, real_asarray = engine_mod.group_seen_cells, jnp.asarray
    real_jit = engine_mod._topk_jit_fn()

    def slow_tiles(*a, **kw):
        time.sleep(tiles_s)
        return real_tiles(*a, **kw)

    def slow_asarray(x, *a, **kw):
        if isinstance(x, np.ndarray) and x.dtype == np.int32:  # the cells
            time.sleep(upload_s)
        return real_asarray(x, *a, **kw)

    def slow_scorer(*a, **kw):
        time.sleep(dispatch_s)
        return real_jit(*a, **kw)

    eng = _engine()
    eng.topk(np.arange(4), 8)  # compile outside the spans under test
    tracer.clear()
    monkeypatch.setattr(engine_mod, "group_seen_cells", slow_tiles)
    monkeypatch.setattr(jnp, "asarray", slow_asarray)
    monkeypatch.setattr(engine_mod, "_topk_jit_fn", lambda: slow_scorer)
    server, _ = _served(eng, range(4))
    server.step()
    spans = {e["name"]: e for e in _serve_events(tracer)}
    ms = lambda name: spans[name]["dur"] * 1e-3
    end = lambda name: spans[name]["ts"] + spans[name]["dur"]
    assert ms("serve/batch/seen_tiles") >= tiles_s * 1e3
    assert ms("serve/batch/upload") >= upload_s * 1e3
    assert ms("serve/batch/compute/dispatch") >= dispatch_s * 1e3
    # what serve_host_ms reads (serve/batch - compute) holds both sleeps ...
    assert (ms("serve/batch") - ms("serve/batch/compute")
            >= (tiles_s + upload_s) * 1e3)
    # ... because compute opens only after the upload call has returned
    assert end("serve/batch/assemble") <= spans["serve/batch/seen_tiles"]["ts"]
    assert end("serve/batch/upload") <= spans["serve/batch/compute"]["ts"]
    assert ms("serve/batch/compute") >= dispatch_s * 1e3
    assert (ms("serve/batch/compute/dispatch")
            + ms("serve/batch/compute/fetch")) <= ms("serve/batch/compute")
    # and serve/batch closes after the responses were produced and flushed
    assert end("serve/batch/respond") <= end("serve/batch")
