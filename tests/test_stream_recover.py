"""The stream task dies and comes back (PR 39): the request server as the
session's supervisor, a resume that costs what changed, read-committed
publication, and the store's pieces under them.  Toy size, CPU; every loop
that waits has a deadline of its own (``_drive``)."""

from __future__ import annotations

import os
import time
import types

import numpy as np
import pytest

from cfk_tpu.config import ALSConfig
from cfk_tpu.serving import (
    RecommendServer, ServeClient, ServeEngine, ensure_serve_topics)
from cfk_tpu.streaming import (
    StreamConfig, StreamProducer, StreamSession, StreamState, foldin)
from cfk_tpu.streaming.state import CELL, last_per_cell
from cfk_tpu.transport import FileBroker, InMemoryBroker
from cfk_tpu.transport.checkpoint import ARRAYS, CheckpointManager

USERS, ITEMS, RANK, LAM = 300, 200, 8, 0.05


class KillSwitch:
    """A ratings transport whose ``consume`` raises once, when armed: the
    kill of the stream task, as the benchmark's harness delivers it."""

    def __init__(self, inner):
        self.inner, self.armed, self.fired = inner, False, 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def consume(self, topic, partition, start_offset=0):
        if self.armed and topic != "serve-requests" \
                and topic != "serve-responses":
            self.armed = False
            self.fired += 1
            raise RuntimeError("stream task killed")
        return self.inner.consume(topic, partition, start_offset)


def _stack(tmp_path, *, log=None, batch_records=8, snapshot_every=256,
           max_pending=8, seed=5, supervised=True):
    """A seeded catalogue served by an engine whose request server drives,
    and supervises, a stream session on the engine's tables; requests on an
    in-memory log, ratings on ``log`` (wrapped in a kill switch)."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, 9, USERS)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    items = np.concatenate([np.sort(rng.choice(ITEMS, n, replace=False))
                            for n in lens]).astype(np.int32)
    values = rng.integers(1, 6, items.size).astype(np.float32)
    u_tab = ((rng.random((USERS, RANK)) - 0.5) * 0.35).astype(np.float32)
    m_tab = ((rng.random((ITEMS, RANK)) - 0.5) * 0.35).astype(np.float32)
    engine = ServeEngine(u_tab, m_tab, num_users=USERS, num_movies=ITEMS,
                         seen_movies=items, seen_indptr=indptr, tile_m=64)
    requests = InMemoryBroker()
    ensure_serve_topics(requests)
    ratings = KillSwitch(log if log is not None else InMemoryBroker())
    s = types.SimpleNamespace(
        indptr=indptr, items=items, values=values, u_tab=u_tab, m_tab=m_tab,
        engine=engine, ratings=ratings, events=[], store=str(tmp_path / "store"),
        producer=StreamProducer(ratings), client=ServeClient(requests),
        sessions=[])
    cfg = ALSConfig(rank=RANK, lam=LAM, health_check_every=1)

    def make_session(**kw):
        sess = StreamSession(
            StreamState.from_csr(indptr, items, values, num_movies=ITEMS),
            cfg, ratings,
            CheckpointManager(s.store, max_pending=max_pending),
            stream=StreamConfig(batch_records=batch_records,
                                snapshot_every_units=snapshot_every),
            base_model=types.SimpleNamespace(user_factors=u_tab),
            engine=engine, listeners=[s.events.append], **kw)
        s.sessions.append(sess)
        return sess

    s.make_session = make_session
    s.server = RecommendServer(
        engine, requests, max_batch=8, session=make_session(),
        session_factory=make_session if supervised else None)
    return s


def _send(s, n, *, seed):
    """``n`` ratings of base users on items they have not rated, in one
    ``send_many``; returns them as (user, item, value) triples."""
    rng = np.random.default_rng(seed)
    users = rng.integers(0, USERS, n)
    sent = getattr(s, "sent", set())
    out = []
    for u in users.tolist():
        mine = set(s.items[s.indptr[u]:s.indptr[u + 1]].tolist())
        item = int(rng.integers(0, ITEMS))
        while item in mine or (u, item) in sent:
            item = int(rng.integers(0, ITEMS))
        sent.add((u, item))
        out.append((u, item, float(rng.integers(1, 6))))
    s.sent = sent
    s.producer.send_many(*map(np.asarray, zip(*out)))
    return out


def _drive(s, until, *, limit_s=60.0, what="", busy=False):
    """Step the server until ``until()``; the test's own time limit.
    ``busy``: under a backlog of requests, so that a scorer is in flight
    and the pump waits for nothing."""
    deadline = time.monotonic() + limit_s
    while not until():
        assert time.monotonic() < deadline, f"not within {limit_s} s: {what}"
        if busy:
            for u in range(16):
                s.client.request(u, 5)
            s.client.flush()
        s.server.step()
        if busy:
            s.client.poll_responses()


def _settled(s):
    sess = s.server.session
    return (sess is not None and s.server._recovering is None
            and not sess.backlog() and not sess.in_flight)


def _store_units(directory):
    """{step: (cursor, touched rows, solved rows, cells)} of a store's
    commit units, read with a manager of its own."""
    mgr = CheckpointManager(directory)
    out = {}
    for it in mgr.iterations():
        st = mgr.restore(it)
        if st.meta.get("kind") == "unit":
            out[it] = (st.meta["offsets"], st.arrays["touched"],
                       np.asarray(st.user_factors), st.arrays["cells"])
    return out


# --- the supervisor ----------------------------------------------------------


def test_a_pump_that_raises_is_survived_and_a_successor_commits_the_rest(
        tmp_path):
    s = _stack(tmp_path)
    first = _send(s, 40, seed=1)
    _drive(s, lambda: s.server.session.published_step >= 2, what="two units")
    dead = s.server.session
    s.ratings.armed = True
    first += _send(s, 8, seed=9)  # the poll of these is where it dies
    rids = [s.client.request(u, 5) for u in range(12)]
    s.client.flush()
    s.server.step()  # the pump raises inside: the step goes on
    assert s.ratings.fired == 1 and s.server.session is None
    assert dead._abandoned and s.server._recovering is not None
    with pytest.raises(RuntimeError, match="abandoned"):
        dead.pump()
    # answered while the task is away, by the last published ordinal
    got = {}
    deadline = time.monotonic() + 30
    while len(got) < len(rids) and time.monotonic() < deadline:
        s.server.step()
        got.update({r.req_id: r for r in s.client.poll_responses()})
    assert set(got) == set(rids) and not any(r.error for r in got.values())
    second = _send(s, 24, seed=2)
    _drive(s, lambda: _settled(s), what="the successor catching up")
    new = s.server.session
    assert new is not dead and len(s.sessions) == 2
    assert new.consumer.cursors == {0: len(first) + len(second)}
    (rec,) = s.server.recoveries
    assert rec["cause"] == "RuntimeError: stream task killed"
    assert rec["up_s"] <= rec["publishing_s"] <= rec["caught_up_s"]
    assert rec["units"] == new.resume_stats["units"] >= 2
    assert new.metrics.counters["session_restarts"] == 1
    assert new.metrics.counters["replayed_records"] == rec["replayed_records"]
    # a second kill is survived the same way
    s.ratings.armed = True
    _send(s, 16, seed=3)
    _drive(s, lambda: _settled(s) and len(s.server.recoveries) == 2,
           what="the second successor")
    assert len(s.sessions) == 3
    assert s.server.session.consumer.cursors == {0: 88}


def test_a_second_kill_while_the_successor_catches_up(tmp_path):
    s = _stack(tmp_path)
    sent = _send(s, 24, seed=1)
    _drive(s, lambda: s.server.session.published_step >= 2, what="two units")
    s.ratings.armed = True
    sent += _send(s, 96, seed=2)  # a dozen micro-batches to catch up with
    _drive(s, lambda: s.server.session is not None and s.ratings.fired == 1,
           what="the first successor adopted")
    assert s.server._recovering is not None and s.server.session.backlog()
    s.ratings.armed = True  # it dies at its next poll, still behind the log
    _drive(s, lambda: _settled(s) and s.ratings.fired == 2,
           what="the second successor")
    assert len(s.sessions) == 3 and len(s.server.recoveries) == 2
    assert s.server.recoveries[0]["caught_up_s"] is None  # it never did
    assert s.server.session.consumer.cursors == {0: len(sent)}
    # every rating once, no ordinal twice, across both kills
    steps = [e["stream_step"] for e in s.events]
    cells = [c for e in s.events for c in e["cells"]]
    assert len(steps) == len(set(steps))
    assert sorted(cells) == sorted((u, i) for u, i, _ in sent)


def test_without_a_factory_the_exception_ends_the_loop(tmp_path):
    s = _stack(tmp_path, supervised=False)
    _send(s, 8, seed=1)
    s.ratings.armed = True
    with pytest.raises(RuntimeError, match="stream task killed"):
        s.server.step()


def test_a_store_that_cannot_be_resumed_ends_the_loop_in_words(tmp_path):
    s = _stack(tmp_path)
    _send(s, 16, seed=1)
    _drive(s, lambda: _settled(s))
    # the engine now serves another user table than the units were solved on
    s.engine._u_base = s.engine._u_base + 1.0
    s.ratings.armed = True
    _send(s, 8, seed=2)
    with pytest.raises(ValueError, match="another user table"):
        _drive(s, lambda: False, limit_s=30)


# --- exactly once, read committed --------------------------------------------


def _twin_lists(s):
    """{user: {item: rating}} of the streamed cells, from the live state."""
    state = s.server.session.state
    return {int(r): {int(c["movie"]): float(c["rating"])
                     for c in state.overlay_cells() if c["row"] == r}
            for r in np.unique(state.overlay_cells()["row"])}


@pytest.mark.parametrize("kill_after", [1, 3, 6])
def test_exactly_once_against_an_uninterrupted_twin(tmp_path, kill_after):
    from benchmarks.harness import reference_foldin

    killed, twin = _stack(tmp_path / "k"), _stack(tmp_path / "t")
    sent = []
    for wave in range(4):
        for s in (killed, twin):
            got = _send(s, 20, seed=10 + wave)
        sent += got
        if wave == 1:
            _drive(killed, lambda: killed.server.session is not None and
                   killed.server.session.stream_step >= kill_after)
            killed.ratings.armed = True
        for s in (killed, twin):
            for _ in range(3):
                s.server.step()
    for s in (killed, twin):
        _drive(s, lambda: _settled(s), what="the drain")
    assert killed.ratings.fired == 1 and len(killed.server.recoveries) == 1
    a, b = killed.server.session, twin.server.session
    assert a.consumer.cursors == b.consumer.cursors == {0: 80}
    # every cell once: the lists equal the twin's, and hold 80 cells
    assert _twin_lists(killed) == _twin_lists(twin)
    assert sum(map(len, _twin_lists(killed).values())) == 80
    # published cells: each ordinal once, each cell once
    for s in (killed, twin):
        steps = [e["stream_step"] for e in s.events]
        assert steps == sorted(set(steps))
        cells = [c for e in s.events for c in e["cells"]]
        assert len(cells) == len(set(cells)) == 80
    # rows: the float64 solve over the list as of the end, for every user
    # whose last unit was committed after the kill
    after = {}
    for e in killed.events:
        if e["stream_step"] > killed.server.recoveries[0]["units"]:
            for row, vec in zip(e["touched_rows"], e["rows"]):
                after[row] = vec
    assert after
    for row, vec in after.items():
        lo, hi = killed.indptr[row], killed.indptr[row + 1]
        mine = [(i, r, 0) for u, i, r in sent if u == row]
        exact = reference_foldin.solve_row(
            killed.m_tab, *reference_foldin.list_as_of(
                killed.items[lo:hi], killed.values[lo:hi], mine, 0), LAM)
        assert reference_foldin.row_err(vec, exact) < 1e-4
        np.testing.assert_array_equal(a.user_rows([row])[0], vec)


class _SlowStore(CheckpointManager):
    """A store whose unit writes take their time: the units handed over
    just before a kill are still queued when it lands."""

    def save(self, iteration, u, m, meta=None):
        if (meta or {}).get("kind") == "unit":
            time.sleep(0.05)
        return super().save(iteration, u, m, meta=meta)


def test_read_committed_with_units_discarded_at_the_kill(tmp_path,
                                                         monkeypatch):
    import cfk_tpu.transport.checkpoint as checkpoint

    s = _stack(tmp_path, max_pending=8)
    # the first session's store is slow; the successor's is the plain one
    slow = _SlowStore(s.store, max_pending=8)
    dead = s.server.session
    dead.manager = slow
    _send(s, 64, seed=1)
    # hand units over faster than they land
    _drive(s, lambda: dead.stream_step >= 6, what="six units handed over",
           busy=True)
    assert dead.published_step < dead.stream_step  # never ahead of the store
    assert s.engine.commit_ordinal == dead.published_step
    s.ratings.armed = True
    _send(s, 8, seed=2)
    _drive(s, lambda: s.server.session is None, busy=True)
    at_kill = dead.stream_step
    _drive(s, lambda: _settled(s), what="the successor")
    (rec,) = s.server.recoveries
    assert rec["lost_units"] >= 1 and rec["replayed_records"] >= 8
    assert rec["units"] + rec["lost_units"] == at_kill
    # every ordinal the engine (and an answer) ever saw is a unit of the
    # store with the cursor and the rows the engine was given under it
    units = _store_units(s.store)
    seen = {}
    for e in s.events:
        assert e["stream_step"] not in seen, "an ordinal published twice"
        seen[e["stream_step"]] = e
    assert set(seen) <= set(units) and len(seen) >= 8
    for step, e in seen.items():
        offsets, touched, rows, cells = units[step]
        assert offsets == {str(p): o for p, o in e["cursors"].items()}
        assert touched.tolist() == e["touched_rows"]
        np.testing.assert_array_equal(rows, e["rows"])
        assert list(zip(cells["row"].tolist(), cells["movie"].tolist())) \
            == e["cells"]
    assert s.server.session.consumer.cursors == {0: 72}
    assert s.engine.commit_ordinal == s.server.session.stream_step
    assert checkpoint  # the module under test


# --- a resume that costs what changed ----------------------------------------


def test_a_resume_on_an_engine_reads_no_byte_of_the_user_base(tmp_path):
    s = _stack(tmp_path)
    _send(s, 40, seed=1)
    _drive(s, lambda: _settled(s))
    sess = s.server.session
    snap = CheckpointManager(s.store).restore(0)
    # the bootstrap snapshot holds neither table: both are the engine's
    assert snap.meta["user_base"] == snap.meta["item_table"] == "engine"
    assert snap.user_factors.shape[0] == snap.movie_factors.shape[0] == 0
    assert snap.meta["user_base_digest"]["rows"] == USERS
    on_disk = sum(os.path.getsize(os.path.join(d, f))
                  for d, _, fs in os.walk(s.store) for f in fs)
    # no copy of the user table was written: an array's header, no row
    assert os.path.getsize(os.path.join(
        s.store, "step_0000000", "user.npy")) == 128
    again = s.make_session()
    assert again._users.base is s.engine.user_base()
    assert np.shares_memory(again._users.base, s.u_tab)
    read = again.manager.bytes_verified + again._overlay_store.bytes_verified
    assert 0 < read <= on_disk
    assert again.stream_step == sess.stream_step == 5
    assert again.consumer.cursors == sess.consumer.cursors
    touched = sorted({r for e in s.events for r in e["touched_rows"]})
    np.testing.assert_array_equal(again.user_rows(touched),
                                  sess.user_rows(touched))
    np.testing.assert_array_equal(again.user_rows([0, 1, 299]),
                                  sess.user_rows([0, 1, 299]))
    # and no record of the log below the cursor: the state is the units'
    assert again.metrics.counters["restored_cells"] == 40
    # a store reopened without its engine can be read, not folded into
    bare = StreamSession(
        StreamState.from_csr(s.indptr, s.items, s.values, num_movies=ITEMS),
        ALSConfig(rank=RANK, lam=LAM), s.ratings, CheckpointManager(s.store),
        stream=StreamConfig(batch_records=8))
    np.testing.assert_array_equal(bare.user_rows(touched),
                                  sess.user_rows(touched))
    assert bare._users.base.shape[0] == 0


def _counting_consume(s):
    """Every log offset the ratings transport is asked to read from."""
    asked = []
    inner = s.ratings.inner.consume

    def consume(topic, partition, start_offset=0):
        asked.append(start_offset)
        return inner(topic, partition, start_offset)

    s.ratings.inner.consume = consume
    return asked


def test_a_resume_reads_one_overlay_snapshot_and_the_units_after_it(tmp_path):
    every = 4
    s = _stack(tmp_path, snapshot_every=every)
    n_units = 2 * every + 3
    sent = _send(s, 8 * n_units, seed=1)
    _drive(s, lambda: _settled(s))
    sess = s.server.session
    from cfk_tpu.resilience.loop import drain_checkpoints

    drain_checkpoints(sess._overlay_store)
    sess._publish_durable()
    assert sess.stream_step == n_units
    assert sess._overlay_store.iterations() == [every, 2 * every]
    asked = _counting_consume(s)
    again = s.make_session()
    assert again.resume_stats["units"] == 3
    assert again.metrics.counters["replayed_units"] == 3
    assert again.manager.bytes_verified < sess.manager.bytes_verified or \
        sess.manager.bytes_verified == 0
    assert asked == []  # no record of the log was read
    assert again.stream_step == n_units
    assert again.consumer.cursors == {0: len(sent)}
    touched = sorted({r for e in s.events for r in e["touched_rows"]})
    np.testing.assert_array_equal(again.user_rows(touched),
                                  sess.user_rows(touched))
    for row in touched:
        a, b = again.state.neighbors(row), sess.state.neighbors(row)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
    # the engine had it all: nothing is published twice
    assert again.resume_stats["republished"] == 0
    # an engine that has seen nothing gets the snapshot as one commit and
    # the units after it
    fresh = ServeEngine(s.u_tab, s.m_tab, num_users=USERS, num_movies=ITEMS,
                        seen_movies=s.items, seen_indptr=s.indptr, tile_m=64)
    s.engine, old = fresh, s.engine
    seen = []
    StreamSession(
        StreamState.from_csr(s.indptr, s.items, s.values, num_movies=ITEMS),
        ALSConfig(rank=RANK, lam=LAM, health_check_every=1), s.ratings,
        CheckpointManager(s.store), stream=StreamConfig(batch_records=8),
        engine=fresh, listeners=[seen.append])
    assert [e["stream_step"] for e in seen] == [2 * every, 9, 10, 11]
    assert fresh.commit_ordinal == old.commit_ordinal == n_units
    assert {r: v.tobytes() for r, v in fresh._u_hot.items()} == \
        {r: v.tobytes() for r, v in old._u_hot.items()}
    assert {r: sorted(v) for r, v in fresh._seen_hot.items()} == \
        {r: sorted(v) for r, v in old._seen_hot.items()}


@pytest.mark.parametrize("victim", ["user.npy", "arrays.bin", "manifest.json"])
def test_a_torn_overlay_snapshot_falls_back_to_the_one_before(tmp_path,
                                                              victim):
    every = 4
    s = _stack(tmp_path, snapshot_every=every)
    _send(s, 8 * (2 * every + 1), seed=1)
    _drive(s, lambda: _settled(s))
    sess = s.server.session
    from cfk_tpu.resilience.loop import drain_checkpoints

    drain_checkpoints(sess._overlay_store)
    newest = os.path.join(s.store, "overlay", f"step_{2 * every:07d}", victim)
    data = open(newest, "rb").read()
    with open(newest, "wb") as f:
        f.write(data[:len(data) // 2])
    with pytest.warns(UserWarning, match="corrupt checkpoint"):
        again = s.make_session()
    assert again.resume_stats["units"] == every + 1
    assert again.stream_step == sess.stream_step == 2 * every + 1
    touched = sorted({r for e in s.events for r in e["touched_rows"]})
    np.testing.assert_array_equal(again.user_rows(touched),
                                  sess.user_rows(touched))


def test_file_and_in_memory_logs_commit_the_same_units(tmp_path):
    logs = {"file": FileBroker(str(tmp_path / "log"), fsync=True),
            "memory": InMemoryBroker()}
    stacks = {name: _stack(tmp_path / name, log=log)
              for name, log in logs.items()}
    for wave in range(3):
        for s in stacks.values():
            _send(s, 30, seed=20 + wave)
            s.server.step()
    for s in stacks.values():
        _drive(s, lambda: _settled(s))
    a, b = (_store_units(s.store) for s in stacks.values())
    assert sorted(a) == sorted(b) and len(a) >= 10
    for step in a:
        assert a[step][0] == b[step][0]
        np.testing.assert_array_equal(a[step][1], b[step][1])
        np.testing.assert_array_equal(a[step][2], b[step][2])
        np.testing.assert_array_equal(a[step][3], b[step][3])
    # one append and one fsync a ``send_many``, read from the cursor on
    log = logs["file"]
    assert log.end_offset("rating-updates", 0) == 90
    assert log._left_at[("rating-updates", 0)][0] == 90
    logs["file"].close()


def test_no_new_trace_across_the_replacement(tmp_path):
    from cfk_tpu.serving.engine import trace_count

    s = _stack(tmp_path)
    s.server.session.prewarm(max_touched=8)
    _send(s, 24, seed=1)
    _drive(s, lambda: _settled(s))
    s.client.ask(list(range(8)), 5, server=s.server)
    before = trace_count() + foldin.trace_count()
    import jax

    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, dur, **kw: lowered.append(event)
        if event.endswith("jaxpr_to_mlir_module_duration") else None)
    s.ratings.armed = True
    _send(s, 24, seed=2)
    _drive(s, lambda: _settled(s) and s.server.recoveries)
    s.client.ask(list(range(8)), 5, server=s.server)
    assert len(s.sessions) == 2
    assert trace_count() + foldin.trace_count() == before
    assert lowered == []


# --- the pieces under them ---------------------------------------------------


def test_the_store_tells_durable_from_handed_over(tmp_path):
    mgr = _SlowStore(str(tmp_path), max_pending=8)
    rows = np.ones((2, 4), np.float32)
    cells = np.zeros(3, CELL)
    for step in (1, 2, 3):
        mgr.save_async(step, rows, rows[:0], meta={
            "kind": "unit", ARRAYS: {"cells": cells, "touched": np.arange(2)}})
    assert mgr.take_durable() == []  # handed over is not durable
    mgr.wait_pending()
    assert mgr.take_durable() == [1, 2, 3] and mgr.take_durable() == []
    back = mgr.restore(2)
    assert back.arrays["cells"].dtype == CELL and len(back.arrays["cells"]) == 3
    assert back.arrays["touched"].tolist() == [0, 1]
    # a kill: what is queued is dropped, what is being written is discarded
    for step in (4, 5, 6):
        mgr.save_async(step, rows, rows[:0],
                       meta={"kind": "unit", ARRAYS: {"cells": cells}})
    time.sleep(0.01)
    assert mgr.abort_pending() >= 2
    assert mgr.wait_pending(timeout=10)
    assert mgr.iterations() == [1, 2, 3] and mgr.take_durable() == []
    assert not [d for d in os.listdir(str(tmp_path)) if d.startswith(".tmp_")]
    # the store works on: an abort is no error of the writer
    mgr.save_async(4, rows, rows[:0], meta={"kind": "unit"})
    mgr.wait_pending()
    assert mgr.iterations() == [1, 2, 3, 4]


def test_a_step_built_on_the_writer_thread(tmp_path):
    import threading

    mgr = CheckpointManager(str(tmp_path))
    built_on = []

    def build():
        built_on.append(threading.current_thread().name)
        return (np.ones((3, 2), np.float32), np.zeros((0, 2), np.float32),
                {"kind": "overlay", ARRAYS: {"ids": np.arange(3)}})

    mgr.submit(7, build)
    mgr.wait_pending()
    assert built_on == ["cfk-checkpoint-writer"]
    back = mgr.restore(7)
    assert back.meta == {"kind": "overlay"}  # the arrays are no manifest's
    assert back.arrays["ids"].tolist() == [0, 1, 2]
    # a torn arrays payload fails the step's checksum
    from cfk_tpu.transport.checkpoint import CheckpointCorruptError

    path = os.path.join(str(tmp_path), "step_0000007", "arrays.bin")
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)
    with pytest.raises(CheckpointCorruptError, match="arrays.bin"):
        mgr.restore(7)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_loaded_overlay_reads_as_the_applied_one(seed):
    """``load_overlay`` (a sort of the store's cells) against applying the
    same cells one commit at a time."""
    from cfk_tpu.transport.serdes import RatingUpdate

    rng = np.random.default_rng(seed)
    lens = rng.integers(0, 5, 40)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    items = np.concatenate([np.sort(rng.choice(30, n, replace=False))
                            for n in lens] + [np.zeros(0, int)]).astype(np.int32)
    values = rng.integers(1, 6, items.size).astype(np.float32)

    def state():
        return StreamState.from_csr(indptr, items, values, num_movies=30)

    live, cells = state(), []
    for batch in range(6):
        ups = [RatingUpdate(seq=batch * 16 + i, user=int(rng.integers(0, 44)),
                            movie=int(rng.integers(0, 30)),
                            rating=float(rng.integers(1, 6)))
               for i in range(16)]
        pending = live.stage(ups)
        live.commit(pending)
        from cfk_tpu.streaming.state import cells_array

        cells.append((cells_array(pending.cell_writes),
                      list(pending.new_user_raw)))
    loaded = live.fresh()  # the same base by reference, nothing applied
    assert loaded._base_movies is live._base_movies and not loaded._delta
    loaded.load_overlay(np.concatenate([c for c, _ in cells]),
                        [r for _, new in cells for r in new])
    assert loaded.num_users == live.num_users
    assert loaded.applied_seq_high == live.applied_seq_high
    for row in range(live.num_users):
        a, b = loaded.neighbors(row), live.neighbors(row)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_array_equal(loaded.overlay_cells(), live.overlay_cells())
    assert len(last_per_cell(np.concatenate([c for c, _ in cells]))) \
        == len(live.overlay_cells())
    # a state something was applied to takes no overlay
    with pytest.raises(ValueError, match="nothing was applied"):
        live.load_overlay(np.zeros(0, CELL), [])


def test_a_log_on_disk_is_read_from_where_the_reader_left_it(tmp_path):
    log = FileBroker(str(tmp_path), fsync=True)
    prod = StreamProducer(log)
    prod.send_many(np.arange(3000), np.arange(3000) % 7, np.ones(3000))
    assert log.last_fsync_ms > 0
    first = []
    for rec in log.consume("rating-updates", 0, 0):
        if rec.offset >= 1500:
            break
        first.append(rec.offset)
    assert first == list(range(1500))
    assert log._left_at[("rating-updates", 0)][0] == 1500
    # the follower's next read seeks straight there; any other start goes
    # through the sparse index, and both read the same records
    a = [r.value for r in log.consume("rating-updates", 0, 1500)][:10]
    del log._left_at[("rating-updates", 0)]
    b = [r.value for r in log.consume("rating-updates", 0, 1500)][:10]
    assert a == b and len(a) == 10
    prod.send_many([5], [1], [2.0])
    assert [r.offset for r in log.consume("rating-updates", 0, 3000)] == [3000]
    log.close()


def test_the_new_spans_of_a_recovery(tmp_path):
    from cfk_tpu import telemetry

    tracer = telemetry.configure(None)
    try:
        s = _stack(tmp_path, snapshot_every=2,
                   log=FileBroker(str(tmp_path / "log"), fsync=True))
        _send(s, 40, seed=1)
        _drive(s, lambda: _settled(s))
        s.ratings.armed = True
        _send(s, 24, seed=2)
        _drive(s, lambda: _settled(s) and s.server.recoveries)
        from cfk_tpu.resilience.loop import drain_checkpoints

        drain_checkpoints(s.server.session._overlay_store)
        events = [e for e in tracer.events() if e.get("ph") == "X"]
    finally:
        telemetry.shutdown(write=False)
        s.ratings.inner.close()
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    (rec,) = by_name["stream/recover"]
    assert {"units", "snapshot_bytes", "unit_bytes", "replayed_records",
            "lost_units", "in_flight_batches"} <= set(rec["args"])
    for child in ("restore", "state", "republish"):
        (span,) = by_name[f"stream/recover/{child}"]
        assert rec["ts"] <= span["ts"] and \
            span["ts"] + span["dur"] <= rec["ts"] + rec["dur"] + 1
    (catchup,) = by_name["stream/recover/catchup"]
    assert {"records", "micro_batches"} <= set(catchup["args"])
    assert catchup["ts"] >= rec["ts"] + rec["dur"] - 2
    appends = by_name["stream/log/append"]
    assert len(appends) == 2 and appends[0]["args"]["records"] == 40
    assert appends[0]["args"]["bytes"] == 40 * 28
    assert appends[0]["args"]["fsync_ms"] > 0
    overlays = by_name["stream/snapshot/overlay"]
    assert overlays and {"units", "bytes"} <= set(overlays[0]["args"])
    assert overlays[0]["tid"] != rec["tid"]  # on a writer thread


def test_cli_serve_with_a_stream_dir_supervises_the_stream_task(
        tmp_path, monkeypatch, capsys):
    """``cfk_tpu serve --broker ... --stream-dir DIR``: the broker's ratings
    are folded into the served factors, and a stream task that dies is
    replaced from DIR (the broker is an in-memory log behind a kill switch;
    the serving loop is this test's, bounded)."""
    import warnings

    import cfk_tpu.transport.tcp as tcp
    from cfk_tpu.cli import main
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.models.als import train_als

    ds = Dataset.from_coo(synthetic_netflix_coo(60, 30, 900, seed=0))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        model = train_als(ds, ALSConfig(rank=4, num_iterations=3))
    csv = tmp_path / "ratings.csv"
    coo = ds.coo_dense
    raw_users = ds.user_map.raw_ids[coo.user_raw]
    raw_items = ds.movie_map.raw_ids[coo.movie_raw]
    with open(csv, "w") as f:
        f.write("userId,movieId,rating,timestamp\n")
        for u, m, r in zip(raw_users, raw_items, coo.rating):
            f.write(f"{u},{m},{r},0\n")
    ck = CheckpointManager(str(tmp_path / "ck"))
    ck.save(3, model.user_factors, model.movie_factors,
            meta={"model": "als", "rank": 4, "num_shards": 1})
    log = KillSwitch(InMemoryBroker())
    monkeypatch.setattr(tcp, "TcpBrokerClient", lambda host, port: log)
    rated = set(zip(raw_users.tolist(), raw_items.tolist()))
    new = [(int(u), int(m)) for u in ds.user_map.raw_ids[:12]
           for m in ds.movie_map.raw_ids[:30] if (int(u), int(m)) not in rated]
    seen = {}

    def serve(server):
        producer = StreamProducer(log)
        s = types.SimpleNamespace(server=server)

        def send(pairs):
            users, items = map(np.asarray, zip(*pairs))
            producer.send_many(users, items, np.full(len(pairs), 4.0))

        send(new[:40])
        _drive(s, lambda: _settled(s), what="the first ratings")
        log.armed = True
        send(new[40:64])
        _drive(s, lambda: _settled(s) and server.recoveries,
               what="the successor")
        seen.update(cursors=dict(server.session.consumer.cursors),
                    recoveries=len(server.recoveries),
                    ordinal=server.engine.commit_ordinal,
                    step=server.session.stream_step)
        return 0

    monkeypatch.setattr(RecommendServer, "serve_forever", serve)
    args = ["serve", "--data", str(csv), "--format", "movielens",
            "--checkpoint-dir", str(tmp_path / "ck"), "--tile-m", "16",
            "-k", "5", "--broker", "tcp://127.0.0.1:1",
            "--stream-dir", str(tmp_path / "stream"),
            "--stream-batch-records", "8"]
    assert main(args) == 0
    assert log.fired == 1 and seen["recoveries"] == 1
    assert seen["cursors"] == {0: 64} and seen["ordinal"] == seen["step"] == 8
    assert "folding in" in capsys.readouterr().err
    # a stream directory without a broker, or with replicas, is refused
    assert main(args[:-6] + args[-4:]) == 2
    assert main(args + ["--replicas", "2"]) == 2
