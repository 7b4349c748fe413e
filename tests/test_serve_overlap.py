"""The request server as a one-deep pipeline (ISSUE 33): under a backlog
``RecommendServer.step`` hands the batch it has polled to the device and
answers the batch it handed over a step before.  Against ``engine.topk`` on
the same batches to the bit, on the one-device float32 route, the int8 table
and a table sharded over the virtual CPU mesh; what each step returns; what
stays in flight; the straight-through case of a server with no backlog; and
what a table changed between a batch's two halves does to its answer."""

import threading

import numpy as np
import pytest

from cfk_tpu.serving import engine as engine_mod
from cfk_tpu.serving.fleet import AdmissionController
from cfk_tpu.serving.server import (
    RecommendServer,
    ServeClient,
    ensure_serve_topics,
)
from cfk_tpu.transport import InMemoryBroker

USERS, MOVIES, RANK, K = 40, 200, 8, 5
ROUTES = {
    "float32": {},
    "int8": {"table_dtype": "int8"},
    "shards2": {"shards": 2},
    "shards4": {"shards": 4},
}


def _factors(seed=5):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((USERS, RANK)).astype(np.float32),
            rng.standard_normal((MOVIES, RANK)).astype(np.float32))


def _engine(route="float32", seed=5, **kw):
    """A toy engine whose users have rated up to a dozen movies each."""
    uf, mf = _factors(seed)
    rng = np.random.default_rng(11)
    lists = [np.sort(rng.choice(MOVIES, int(rng.integers(0, 12)),
                                replace=False)) for _ in range(USERS)]
    indptr = np.zeros(USERS + 1, np.int64)
    indptr[1:] = np.cumsum([len(x) for x in lists])
    return engine_mod.ServeEngine(
        uf, mf, num_users=USERS, num_movies=MOVIES,
        seen_movies=np.concatenate(lists).astype(np.int32),
        seen_indptr=indptr, tile_m=16, batch_quantum=4,
        **ROUTES[route], **kw)


def _wired(engine, users, *, max_batch=4, k=K, **server_kw):
    """(server, client, req_ids): ``users`` sent before the first step."""
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(engine, broker, max_batch=max_batch, **server_kw)
    client = ServeClient(broker)
    ids = [client.request(int(u), k) for u in users]
    client.flush()
    return server, client, ids


def _by_id(client):
    got = {}
    for r in client.poll_responses():
        assert r.req_id not in got, "a request answered twice"
        got[r.req_id] = r
    return got


# -- the pipeline against the serial engine ------------------------------------


@pytest.mark.parametrize("route", list(ROUTES))
def test_a_backlog_is_answered_as_topk_answers_its_batches(route):
    """Eighteen requests in batches of four: the first step answers nothing,
    each later step answers the batch before its own, an empty poll answers
    the last; request for request the ids and scores are ``engine.topk``'s
    on the same batch, to the bit."""
    users = np.random.default_rng(2).integers(0, USERS, size=18)
    server, client, ids = _wired(_engine(route), users)
    oracle = _engine(route)
    returned, answers = [], {}
    for _ in range(5):
        returned.append(server.step())
        assert server._in_flight.on_device
        answers.update(_by_id(client))
        assert len(answers) == sum(returned)
    returned.append(server.step())  # an empty poll: the batch in flight
    answers.update(_by_id(client))
    # four full batches and one of two, each answered one step late
    assert returned == [0, 4, 4, 4, 4, 2]
    assert sum(returned) == len(ids) == len(answers) == server.requests_served
    assert server._in_flight is None and server.step() == 0
    assert server.batches == 5
    k_pad = 8
    for lo in range(0, len(ids), 4):
        rows = users[lo:lo + 4]
        want_scores, want_ids = oracle.topk(rows, k_pad)
        for i, rid in enumerate(ids[lo:lo + 4]):
            resp = answers[rid]
            assert not resp.error
            np.testing.assert_array_equal(resp.movie_rows, want_ids[i, :K])
            np.testing.assert_array_equal(resp.scores, want_scores[i, :K])


def test_a_single_request_is_answered_by_one_step():
    """No backlog, nothing in flight: straight through, both halves of the
    batch in the step that polled it."""
    server, client, (rid,) = _wired(_engine(), [7])
    assert server.step() == 1
    assert server._in_flight is None
    assert list(_by_id(client)) == [rid]
    assert server.metrics.counters.get("serve_batches_overlapped", 0) == 0
    # and again: a lightly loaded server never holds a batch back
    rid2 = client.request(9, K)
    assert server.step() == 1 and list(_by_id(client)) == [rid2]
    assert server.committed_cursors == server._cursors == {0: 2}


def test_a_poll_that_drains_the_log_still_answers_the_batch_in_flight():
    """Five requests, batches of four: the second poll takes the last one
    and finds the log empty behind it, with a batch in flight: that batch is
    answered and the one just polled stays in flight for the next step."""
    server, client, ids = _wired(_engine(), range(5))
    assert server.step() == 0
    assert server.step() == 4
    assert sorted(_by_id(client)) == sorted(ids[:4])
    assert server._in_flight is not None and server._in_flight.on_device
    assert server.step() == 1
    assert list(_by_id(client)) == ids[4:]
    assert server.metrics.counters["serve_batches_overlapped"] == 2


def test_ask_through_a_backlogged_server_terminates():
    server, client, _ = _wired(_engine(), [])
    got = client.ask(list(range(11)), K, server=server)
    assert len(got) == 11 and all(not r.error for r in got.values())
    assert server._in_flight is None


@pytest.mark.parametrize("how", ["max_requests", "stop", "idle_timeout"])
def test_serve_forever_leaves_nothing_in_flight(how):
    server, client, ids = _wired(_engine(), range(14))
    if how == "max_requests":
        served = server.serve_forever(max_requests=6)
        # two steps reach six answered at the earliest; the batch then in
        # flight is answered before the loop returns
        assert served == 12
    elif how == "stop":
        calls = iter([False, False, True])
        served = server.serve_forever(stop=lambda: next(calls))
        assert served == 8  # two steps, then the batch in flight
    else:
        served = server.serve_forever(idle_timeout_s=0.05)
        assert served == 14
    assert server._in_flight is None
    assert len(_by_id(client)) == served == server.requests_served
    assert server.committed_cursors == server._cursors == {0: served}


def test_serve_forever_in_a_thread_answers_all_and_stops_clean():
    server, client, _ = _wired(_engine(), [])
    stop = threading.Event()
    out = []
    t = threading.Thread(
        target=lambda: out.append(server.serve_forever(stop=stop.is_set)))
    t.start()
    try:
        got = client.ask(list(range(30)), K, timeout_s=60)
    finally:
        stop.set()
        t.join(timeout=30)
    assert not t.is_alive()
    assert len(got) == 30 and out == [30]
    assert server._in_flight is None


# -- the guarantees move with the batch ------------------------------------


def test_committed_cursors_follow_the_batch_answered_not_the_poll():
    server, client, ids = _wired(_engine(), range(10))
    assert server.step() == 0
    assert server._cursors == {0: 4} and server.committed_cursors == {0: 0}
    assert server.step() == 4
    # batch 2 is polled and in flight; only batch 1's answers are flushed
    assert server._cursors == {0: 8} and server.committed_cursors == {0: 4}
    assert server.step() == 4
    assert server._cursors == {0: 10} and server.committed_cursors == {0: 8}
    assert server.step() == 2
    assert server.committed_cursors == server._cursors == {0: 10}


def test_shed_and_out_of_range_requests_are_each_answered_once():
    """Admission keeps three of a poll's five; one of the three asks for a
    row the table lacks.  Every request has exactly one answer, with its
    batch, retriable flag and error text as before, and counts once."""
    server, client, _ = _wired(
        _engine(), [], max_batch=5,
        admission=AdmissionController(max_queue=3))
    users = [1, USERS + 3, 2, 3, 4, 5, 6, 7, 8, 9]
    ids = [client.request(u, K) for u in users]
    client.flush()
    returned, answers = [], {}
    while len(answers) < len(ids):
        returned.append(server.step())
        answers.update(_by_id(client))
    assert returned == [0, 5, 5] and server.step() == 0
    assert server.shed == 4 and server.requests_served == 6
    shed = [rid for rid in ids if answers[rid].retriable]
    assert shed == ids[3:5] + ids[8:10]
    assert all("overloaded" in answers[rid].error for rid in shed)
    bad = answers[ids[1]]
    assert not bad.retriable and bad.movie_rows.size == 0
    assert bad.error == (f"user row {USERS + 3} out of range [0, {USERS}) "
                         f"or k {K} outside [1, {MOVIES}]")
    for rid in (ids[0], ids[2], ids[5], ids[6], ids[7]):
        assert not answers[rid].error and answers[rid].movie_rows.size == K


def test_a_batch_of_errors_alone_needs_no_device():
    server, client, ids = _wired(_engine(), [USERS, USERS + 1])
    assert server.step() == 2
    assert all(r.error for r in _by_id(client).values())
    assert server._in_flight is None


@pytest.mark.parametrize("route", ["float32", "int8", "shards2"])
@pytest.mark.parametrize("change", ["movie_deltas", "retrain", "load_state"])
def test_a_table_changed_between_the_halves_leaves_the_batch_in_flight_alone(
        route, change):
    """Deltas, a retrain's new table and a resync, each after a batch was
    handed over and before it is fetched: the answers are the old table's
    and carry its epoch; the next batch sees the new one."""
    eng = _engine(route)
    server, client, ids = _wired(eng, range(8))
    before = _engine(route).topk(np.arange(4), 8)
    assert server.step() == 0 and server._in_flight.on_device
    uf2, mf2 = _factors(seed=77)
    if change == "movie_deltas":
        rows = np.arange(0, MOVIES, 3)
        eng.apply_movie_deltas(rows, mf2[rows])
        after_eng = _engine(route)
        after_eng.apply_movie_deltas(rows, mf2[rows])
        epoch = 0
    elif change == "retrain":
        eng.on_commit({"retrain": True, "user_factors": uf2,
                       "movie_factors": mf2, "num_users": USERS})
        after_eng = _engine(route, seed=77)
        epoch = 1
    else:
        eng.load_state(uf2, mf2, epoch=3)
        after_eng = _engine(route, seed=77)
        epoch = 3
    assert server.step() == 4
    first = _by_id(client)
    for i, rid in enumerate(ids[:4]):
        assert first[rid].epoch == 0
        np.testing.assert_array_equal(first[rid].movie_rows, before[1][i, :K])
        np.testing.assert_array_equal(first[rid].scores, before[0][i, :K])
    assert server.step() == 4
    second = _by_id(client)
    after = after_eng.topk(np.arange(4, 8), 8)
    for i, rid in enumerate(ids[4:]):
        assert second[rid].epoch == epoch
        np.testing.assert_array_equal(second[rid].movie_rows, after[1][i, :K])
        np.testing.assert_array_equal(second[rid].scores, after[0][i, :K])


def test_another_engine_in_the_servers_place_answers_only_the_next_batch():
    """A fleet replica's flip is one assignment, ``server.engine = new``:
    the batch in flight is fetched from the engine it was handed to."""
    old, new = _engine(), _engine(seed=31)
    new.epoch = 1
    server, client, ids = _wired(old, range(8))
    assert server.step() == 0
    server.engine = new
    assert server.step() == 4 and server.step() == 4
    got = _by_id(client)
    want_old = _engine().topk(np.arange(4), 8)
    want_new = _engine(seed=31).topk(np.arange(4, 8), 8)
    for i, rid in enumerate(ids[:4]):
        assert got[rid].epoch == 0
        np.testing.assert_array_equal(got[rid].movie_rows, want_old[1][i, :K])
    for i, rid in enumerate(ids[4:]):
        assert got[rid].epoch == 1
        np.testing.assert_array_equal(got[rid].movie_rows, want_new[1][i, :K])


# -- a failure in one half --------------------------------------------------


def test_a_failed_hand_over_leaves_the_batch_in_flight_answerable(monkeypatch):
    server, client, ids = _wired(_engine(), range(12))
    assert server.step() == 0
    real = engine_mod._topk_jit_fn()

    def broken(*a, **kw):
        raise RuntimeError("no room for the rectangle")

    monkeypatch.setattr(engine_mod, "_topk_jit_fn", lambda: broken)
    with pytest.raises(RuntimeError, match="no room"):
        server.step()
    # batch 1 is still in flight and uncommitted; batch 2 went unanswered
    assert server._in_flight.on_device
    assert server.committed_cursors == {0: 0} and _by_id(client) == {}
    monkeypatch.setattr(engine_mod, "_topk_jit_fn", lambda: real)
    assert server.step() == 4
    assert sorted(_by_id(client)) == sorted(ids[:4])
    assert server.committed_cursors == {0: 4}


def test_a_failed_fetch_leaves_the_batch_handed_over_in_flight(monkeypatch):
    server, client, ids = _wired(_engine(), range(12))
    assert server.step() == 0
    first = server._in_flight.handle
    monkeypatch.setattr(
        first, "fetch", lambda sp: (setattr(first, "_out", None), 1 / 0))
    with pytest.raises(ZeroDivisionError):
        server.step()
    # batch 1 is lost to this server and stays uncommitted, for an heir;
    # batch 2 was handed over before the fetch failed and is answered next
    assert first.failed and server._in_flight.handle is not first
    assert server.committed_cursors == {0: 0}
    assert server.step() == 4
    assert sorted(_by_id(client)) == sorted(ids[4:8])
    assert server.step() == 4 and sorted(_by_id(client)) == sorted(ids[8:])


# -- the engine's two halves -----------------------------------------------


@pytest.mark.parametrize("route", ["float32", "int8", "shards2"])
def test_two_batches_staged_before_either_is_fetched(route):
    """``topk`` is ``stage`` then ``compute`` on one batch; two batches
    handed over back to back and fetched in order give what two ``topk``
    calls give."""
    eng = _engine(route)
    a_rows, b_rows = np.array([3, 1, 4]), np.array([1, 5, 9, 2, 6])
    a, b = eng.stage(a_rows, 8), eng.stage(b_rows, 8)
    assert not a.on_device and a.result is None
    assert engine_mod.compute(a, None) is None and a.on_device
    assert engine_mod.compute(b, a) is a.result and not a.on_device
    got_b = engine_mod.compute(None, b)
    for got, rows in ((a.result, a_rows), (got_b, b_rows)):
        want = _engine(route).topk(rows, 8)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
    assert (a.n, a.counters["b"], b.n, b.counters["b"]) == (3, 4, 5, 8)
    assert a.epoch == b.epoch == 0
