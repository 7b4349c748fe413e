"""What pauses a stage without being one, on the tracer's clock (ISSUE 37):
the collector's passes (``runtime/gc``, a hook that lives and dies with the
tracer), the store's writer thread (``checkpoint/write``) and the wait for
room behind it (``checkpoint/backpressure``), and the client's send and
collect (``serve/client/flush``, ``serve/client/poll``)."""

import gc
import threading

import numpy as np
import pytest

from cfk_tpu import telemetry
from cfk_tpu.resilience.faults import SlowDiskCheckpointManager
from cfk_tpu.serving.server import (
    RESPONSES_TOPIC,
    RecommendServer,
    ServeClient,
    ensure_serve_topics,
)
from cfk_tpu.telemetry.trace import GC_SPAN, Tracer
from cfk_tpu.transport import InMemoryBroker


@pytest.fixture
def collector_off():
    """No pass but the ones a test forces."""
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


@pytest.fixture
def tracer():
    t = telemetry.configure()
    yield t
    telemetry.shutdown(write=False)


def _named(tracer, name):
    return [e for e in tracer.events() if e["name"] == name]


def _our_hooks():
    return [cb for cb in gc.callbacks
            if isinstance(getattr(cb, "__self__", None), Tracer)]


# -- runtime/gc ---------------------------------------------------------------


def test_a_forced_pass_is_one_span_nested_in_the_open_stage(
        collector_off, tracer):
    cycle = []
    cycle.append(cycle)
    del cycle
    with telemetry.span("stage/outer"):
        with telemetry.span("stage/outer/inner", n=1):
            gc.collect()
    (pass_,) = _named(tracer, GC_SPAN)
    (inner,) = _named(tracer, "stage/outer/inner")
    assert pass_["args"]["generation"] == 2
    assert pass_["args"]["collected"] >= 1
    assert pass_["args"]["uncollectable"] == 0
    assert pass_["tid"] == inner["tid"] == threading.get_ident()
    assert inner["ts"] <= pass_["ts"]
    assert pass_["ts"] + pass_["dur"] <= inner["ts"] + inner["dur"]
    telemetry.validate_span_tree(tracer.events())


def test_a_pass_lands_on_the_thread_that_ran_it(collector_off, tracer):
    def work():
        with telemetry.span("worker/stage"):
            gc.collect(0)

    t = threading.Thread(target=work, name="collecting-worker")
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    (pass_,) = _named(tracer, GC_SPAN)
    (stage,) = _named(tracer, "worker/stage")
    assert pass_["tid"] == stage["tid"] != threading.get_ident()
    assert pass_["args"]["generation"] == 0
    telemetry.validate_span_tree(tracer.events())


def test_the_hook_lives_and_dies_with_the_tracer(collector_off):
    assert telemetry.get_tracer() is None and _our_hooks() == []
    first = telemetry.configure()
    try:
        assert _our_hooks() == [first._on_gc]
        # a second configure gives the first tracer's hook up
        second = telemetry.configure()
        assert _our_hooks() == [second._on_gc]
        gc.collect()
        assert _named(first, GC_SPAN) == []
        assert len(_named(second, GC_SPAN)) == 1
    finally:
        telemetry.shutdown(write=False)
    assert _our_hooks() == []
    gc.collect()
    assert len(_named(second, GC_SPAN)) == 1
    # a tracer nobody installed hooks nothing
    loose = Tracer()
    gc.collect()
    assert _our_hooks() == [] and loose.events() == []
    telemetry.shutdown(write=False)  # with none installed: still nothing
    assert _our_hooks() == []


def test_a_pass_that_starts_inside_the_tracers_lock_does_not_deadlock(
        collector_off, tracer):
    """A pass can start on a thread between two bytecodes of ``_emit``,
    which holds the tracer's lock; its span is emitted from inside."""
    done = threading.Event()

    def work():
        with tracer._lock:
            gc.collect()
        done.set()

    t = threading.Thread(target=work, daemon=True)
    t.start()
    assert done.wait(timeout=30)
    assert len(_named(tracer, GC_SPAN)) == 1


# -- checkpoint/write, checkpoint/backpressure --------------------------------


def _save_three(tmp_path, delay_s):
    mgr = SlowDiskCheckpointManager(str(tmp_path), delay_s=delay_s,
                                    max_pending=1)
    u = np.arange(12, dtype=np.float32).reshape(4, 3)
    m = np.ones((2, 3), np.float32)
    for step in (1, 2, 3):
        mgr.save_async(step, u, m,
                       meta={"kind": "unit"} if step > 1 else None)
    assert mgr.wait_pending(timeout=60)
    assert mgr.iterations() == [1, 2, 3]
    return u, m


def test_the_writers_jobs_and_the_wait_for_room_are_spans(tmp_path, tracer):
    delay_s = 0.05
    u, m = _save_three(tmp_path, delay_s)
    writes = sorted(_named(tracer, "checkpoint/write"),
                    key=lambda e: e["args"]["step"])
    waits = _named(tracer, "checkpoint/backpressure")
    assert [e["args"]["step"] for e in writes] == [1, 2, 3]
    here = threading.get_ident()
    names = {e["tid"]: e["args"]["name"]
             for e in tracer.chrome_trace()["traceEvents"] if e["ph"] == "M"}
    for e in writes:
        assert e["tid"] != here
        assert names[e["tid"]] == "cfk-checkpoint-writer"
        # the slowed save runs inside the job's span
        assert e["dur"] >= delay_s * 1e6
        assert e["args"]["fsyncs"] == 5
        assert e["args"]["bytes"] > u.nbytes + m.nbytes
    assert "kind" not in writes[0]["args"]
    assert [e["args"]["kind"] for e in writes[1:]] == ["unit", "unit"]
    # one job may be pending: the second and third hand-overs wait for the
    # job before them, and that wait is what their jobs were queued for
    assert len(waits) == 2
    for e in waits:
        assert e["tid"] == here
        assert e["args"] == {"pending": 1, "max_pending": 1}
        assert e["dur"] >= 0.8 * delay_s * 1e6
    assert writes[0]["args"]["queued_ms"] < delay_s * 1e3
    for e in writes[1:]:
        assert e["args"]["queued_ms"] >= 0.8 * delay_s * 1e3
    telemetry.validate_span_tree(tracer.events())


def test_a_hand_over_that_finds_room_writes_no_backpressure(tmp_path, tracer):
    from cfk_tpu.transport import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), max_pending=4)
    u = np.zeros((2, 2), np.float32)
    mgr.save_async(1, u, u)
    assert mgr.wait_pending(timeout=60)
    assert len(_named(tracer, "checkpoint/write")) == 1
    assert _named(tracer, "checkpoint/backpressure") == []


def test_the_store_writes_no_event_with_the_tracer_off(tmp_path):
    assert telemetry.get_tracer() is None
    _save_three(tmp_path, 0.01)
    tracer = telemetry.configure()
    try:
        assert [e for e in tracer.events()
                if e["name"].startswith("checkpoint/")] == []
    finally:
        telemetry.shutdown(write=False)


# -- serve/client/flush, serve/client/poll ------------------------------------


class _OneRowEngine:
    """Answers every row with items 0..k-1: the client's spans need a
    server, not a scorer."""

    num_users, num_movies, epoch, ready = 8, 16, 0, True

    def topk(self, rows, k, stamp=None):
        ids = np.tile(np.arange(k, dtype=np.int32), (len(rows), 1))
        return np.zeros(ids.shape, np.float32), ids


def test_the_clients_send_and_collect_are_one_span_a_call(tracer):
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(_OneRowEngine(), broker, max_batch=8)
    client = ServeClient(broker)
    client.flush()  # nothing sent: no event
    assert client.poll_responses() == []  # nothing there: no event
    assert [e for e in tracer.events()
            if e["name"].startswith("serve/client/")] == []
    for u in range(5):
        client.request(u, 3)
    client.flush()
    client.flush()
    (send,) = _named(tracer, "serve/client/flush")
    assert send["args"] == {"requests": 5}
    assert server.step() == 5
    broker.produce(RESPONSES_TOPIC, key=0, value=b"junk", partition=0)
    assert len(client.poll_responses()) == 5
    (collect,) = _named(tracer, "serve/client/poll")
    assert collect["args"]["responses"] == 5
    assert collect["args"]["malformed"] == 1
    assert collect["args"]["bytes"] > len(b"junk")
    assert client.poll_responses() == []
    assert len(_named(tracer, "serve/client/poll")) == 1
    # the next send is a span of its own, from its first request
    client.request(1, 3)
    client.request(2, 3)
    client.flush()
    first, second = _named(tracer, "serve/client/flush")
    assert second["args"] == {"requests": 2}
    assert first["ts"] + first["dur"] <= second["ts"]
    telemetry.validate_span_tree(tracer.events())


def test_the_client_answers_the_same_with_the_tracer_off():
    assert telemetry.get_tracer() is None
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(_OneRowEngine(), broker, max_batch=8)
    client = ServeClient(broker)
    got = client.ask([0, 1, 2], 3, server=server)
    assert len(got) == 3 and client.malformed_responses == 0
