"""Replicated serving fleet (ISSUE 18): delta shipping, gap resync,
rollover, admission control, failover, client retry, /readyz.

Single-threaded where possible: ``FleetReplica.pump()`` runs one
supervised iteration (flip → apply deltas → pull lazy → serve) without
the replica thread, so the protocol assertions are deterministic; the
thread/kill paths run under chaos_lab as well (``serve_replica_kill``,
``serve_delta_gap``, ``serve_rollover``)."""

import threading
import time
import urllib.request
import warnings

import numpy as np
import pytest

from cfk_tpu.serving import (
    AdmissionController,
    DeltaPublisher,
    FleetReplica,
    RecommendServer,
    ServeClient,
    ServeEngine,
    ServeFleet,
    SnapshotStore,
    ensure_serve_topics,
    table_crc,
)
from cfk_tpu.transport import InMemoryBroker

U, M, K = 48, 64, 6


def _factors(seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((U, K)).astype(np.float32),
            rng.standard_normal((M, K)).astype(np.float32))


def _engine(u, m, **kw):
    return ServeEngine(u, m, num_users=U, num_movies=M, tile_m=16, **kw)


def _wired(replicas=1, seed=0, shards=None, **fleet_kw):
    """(fleet, publisher, broker, (u, m)) with the store seeded."""
    u, m = _factors(seed)
    broker = InMemoryBroker()
    fleet = ServeFleet(lambda i: _engine(u, m, shards=shards), broker,
                       replicas=replicas, **fleet_kw)
    fleet.seed_store(u, m, num_users=U)
    pub = DeltaPublisher(broker, fleet.store)
    return fleet, pub, broker, (u, m)


def _commit(rng, rows, *, num_users=U, cells=()):
    rows = np.asarray(rows, np.int64)
    return {
        "touched_rows": rows.tolist(),
        "rows": rng.standard_normal((rows.size, K)).astype(np.float32),
        "cells": list(cells), "retrain": False, "num_users": num_users,
    }


# -- publisher ---------------------------------------------------------------


def test_publisher_seq_monotonic_across_epochs():
    fleet, pub, broker, (u, m) = _wired()
    rng = np.random.default_rng(1)
    pub.on_commit(_commit(rng, [1, 2]))
    pub.on_commit(_commit(rng, [3]))
    u2, m2 = _factors(9)
    pub.on_commit({"retrain": True, "user_factors": u2,
                   "movie_factors": m2, "num_users": U})
    pub.on_commit(_commit(rng, [4]))
    from cfk_tpu.transport.serdes import decode_factor_delta

    frames = [decode_factor_delta(r.value)
              for r in broker.consume("factor-deltas", 0, 0)]
    assert [f.seq for f in frames] == [1, 2, 3, 4]
    assert [f.kind for f in frames] == ["rows", "rows", "epoch", "rows"]
    assert [f.epoch for f in frames] == [0, 0, 1, 1]
    # the epoch frame carries NO factors — the snapshot is in the store
    assert frames[2].user_rows.size == 0
    snap = fleet.store.state(1)
    np.testing.assert_array_equal(snap["user_factors"], u2)
    # store is written BEFORE the frame is produced: its seq covers the
    # newest frame, so a gap resync never lands behind the log
    assert fleet.store.state()["seq"] == 4


def test_publisher_hot_cold_split_ships_tail_lazy():
    fleet, pub, broker, _ = _wired()
    rng = np.random.default_rng(2)
    # a heavily skewed touch stream: rows 0-2 re-solved every commit,
    # the tail rows exactly once after their first touch
    for i in range(12):
        pub.on_commit(_commit(rng, [0, 1, 2, 10 + i]))
        pub.on_commit(_commit(rng, [0, 1, 2]))
    # later tail touches: by now the knee separates the 3 hot rows
    pub.on_commit(_commit(rng, [0, 10, 11, 12, 13, 14]))
    assert pub.lazy_rows > 0
    assert pub.eager_rows > pub.lazy_rows  # the head ships eagerly
    from cfk_tpu.transport.serdes import decode_factor_delta

    last = decode_factor_delta(
        list(broker.consume("factor-deltas", 0, 0))[-1].value
    )
    assert 0 in last.user_rows.tolist()  # hot row: factors in-frame
    assert last.lazy_user_rows.size > 0  # cold tail: ids only
    # every shipped row — eager AND lazy — is in the store overlay
    snap = fleet.store.state()
    for row in last.lazy_user_rows.tolist():
        assert row in snap["overlay"]


# -- replica apply / crc-exactness -------------------------------------------


def test_replica_apply_matches_direct_engine_crc():
    fleet, pub, broker, (u, m) = _wired()
    oracle = _engine(u, m)
    rng = np.random.default_rng(3)
    replica = fleet.replicas[0]
    for i in range(8):
        ev = _commit(rng, rng.integers(0, U, size=4),
                     cells=[(int(rng.integers(0, U)),
                             int(rng.integers(0, M)))])
        pub.on_commit(ev)
        oracle.on_commit(ev)
    replica.apply_deltas()
    replica.pull_lazy()  # cold rows arrive via the store, not the frame
    assert replica.applied_seq == 8
    assert replica.gaps_detected == 0
    assert table_crc(replica.engine) == table_crc(oracle)


def test_delta_gap_detected_and_resynced_crc_exact():
    from cfk_tpu.resilience.faults import DeltaStreamTamper

    u, m = _factors()
    broker = InMemoryBroker()
    tampered = DeltaStreamTamper(broker, topic="factor-deltas", hide=[3])
    fleet = ServeFleet(lambda i: _engine(u, m), tampered, replicas=1)
    fleet.seed_store(u, m, num_users=U)
    pub = DeltaPublisher(broker, fleet.store)  # publishes to the REAL log
    oracle = _engine(u, m)
    rng = np.random.default_rng(4)
    replica = fleet.replicas[0]
    for i in range(6):
        ev = _commit(rng, rng.integers(0, U, size=3))
        pub.on_commit(ev)
        oracle.on_commit(ev)
    replica.apply_deltas()
    replica.pull_lazy()
    # the hidden frame (offset 3 = seq 4) forced the gap path
    assert tampered.hidden >= 1
    assert replica.gaps_detected == 1
    assert replica.resyncs == 1
    # recovery contract: bit-exact vs an engine that saw EVERY commit
    assert replica.applied_seq == 6
    assert table_crc(replica.engine) == table_crc(oracle)


def test_undecodable_delta_frame_takes_gap_path():
    from cfk_tpu.resilience.faults import DeltaStreamTamper

    u, m = _factors()
    broker = InMemoryBroker()
    tampered = DeltaStreamTamper(broker, topic="factor-deltas", hide=[1],
                                 mode="truncate")
    fleet = ServeFleet(lambda i: _engine(u, m), tampered, replicas=1)
    fleet.seed_store(u, m, num_users=U)
    pub = DeltaPublisher(broker, fleet.store)
    oracle = _engine(u, m)
    rng = np.random.default_rng(5)
    replica = fleet.replicas[0]
    for i in range(4):
        ev = _commit(rng, [int(rng.integers(0, U))])
        pub.on_commit(ev)
        oracle.on_commit(ev)
    replica.apply_deltas()
    replica.pull_lazy()
    assert tampered.truncated >= 1
    assert replica.gaps_detected >= 1 and replica.resyncs >= 1
    assert table_crc(replica.engine) == table_crc(oracle)


def test_duplicate_delta_delivery_is_idempotent():
    # at-least-once delivery: the same frames consumed twice apply once
    fleet, pub, broker, (u, m) = _wired()
    oracle = _engine(u, m)
    rng = np.random.default_rng(6)
    replica = fleet.replicas[0]
    for i in range(3):
        ev = _commit(rng, [i, i + 10])
        pub.on_commit(ev)
        oracle.on_commit(ev)
    replica.apply_deltas()
    replica._delta_cursor = 0  # replay the whole log (rebalance replay)
    replica.apply_deltas()
    replica.pull_lazy()
    assert replica.applied_seq == 3
    assert replica.gaps_detected == 0
    assert table_crc(replica.engine) == table_crc(oracle)


# -- rollover ----------------------------------------------------------------


@pytest.mark.parametrize("shards", [None, 2], ids=["one_device", "shards2"])
def test_rollover_flips_epoch_and_applies_deferred_deltas(shards):
    fleet, pub, broker, (u, m) = _wired(shards=shards)
    rng = np.random.default_rng(7)
    replica = fleet.replicas[0]
    placed = replica.engine._table[0].sharding
    pub.on_commit(_commit(rng, [1]))
    replica.pump()
    assert replica.engine.epoch == 0
    u2, m2 = _factors(21)
    pub.on_commit({"retrain": True, "user_factors": u2,
                   "movie_factors": m2, "num_users": U})
    # rows for the NEW epoch arriving before this replica has flipped:
    # must be deferred, then applied post-flip
    late = _commit(rng, [5, 6])
    pub.on_commit(late)
    deadline = time.monotonic() + 30
    while replica.rollovers == 0 and time.monotonic() < deadline:
        replica.pump()
        time.sleep(0.01)
    assert replica.rollovers == 1
    assert replica.engine.epoch == 1
    # the new epoch's engine lies where the old one lay: a row-sharded
    # replica does not roll over onto one device
    assert replica.engine._table[0].sharding == placed
    assert len(placed.device_set) == (shards or 1)
    replica.pump()  # drain anything the flip left pending
    assert replica.applied_seq == 3
    # the deferred commit landed on the NEW engine
    oracle = _engine(u2, m2)
    oracle.epoch = 1
    oracle.on_commit(late)
    assert table_crc(replica.engine) == table_crc(oracle)
    # old epoch's overlay did NOT leak into the new table
    assert 1 not in replica.engine._u_hot


def test_rollover_serves_old_epoch_until_flip():
    fleet, pub, broker, (u, m) = _wired()
    ensure_serve_topics(broker)
    client = ServeClient(broker)
    replica = fleet.replicas[0]
    fleet.prewarm(3, max_batch=8)
    got = client.ask([1], 3, server=replica.server)
    assert next(iter(got.values())).epoch == 0
    u2, m2 = _factors(22)
    pub.on_commit({"retrain": True, "user_factors": u2,
                   "movie_factors": m2, "num_users": U})
    replica.apply_deltas()  # starts the background prewarm
    # until the new engine is ready, answers still come from epoch 0 —
    # zero downtime, and every response is stamped with ONE epoch
    got = client.ask([2], 3, server=replica.server)
    assert next(iter(got.values())).epoch in (0, 1)
    deadline = time.monotonic() + 30
    while replica.rollovers == 0 and time.monotonic() < deadline:
        replica.pump()
        time.sleep(0.01)
    got = client.ask([3], 3, server=replica.server)
    resp = next(iter(got.values()))
    assert resp.epoch == 1
    # post-flip answers score the NEW table exactly
    fresh = _engine(u2, m2)
    s, i = fresh.topk(np.asarray([3]), 3)
    np.testing.assert_array_equal(resp.movie_rows, i[0])
    np.testing.assert_array_equal(resp.scores, s[0])


# -- admission control -------------------------------------------------------


def test_admission_bounds_queue_with_retriable_rejections():
    u, m = _factors()
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(
        _engine(u, m), broker,
        admission=AdmissionController(max_queue=2),
    )
    client = ServeClient(broker)
    ids = [client.request(i, 3) for i in range(6)]
    client.flush()
    assert server.step() == 6  # every request ANSWERED (2 scored, 4 shed)
    by_id = {r.req_id: r for r in client.poll_responses()}
    assert len(by_id) == 6
    shed = [r for r in by_id.values() if r.retriable]
    ok = [r for r in by_id.values() if not r.error]
    assert len(ok) == 2 and len(shed) == 4
    assert all("overloaded" in r.error for r in shed)
    assert server.shed == 4
    # FIFO: the first two req_ids got real answers
    assert not by_id[ids[0]].error and not by_id[ids[1]].error


def test_admission_capacity_qps_sizing():
    a = AdmissionController(capacity_qps=1000.0, max_queue_s=0.05)
    assert a.max_queue == 50
    with pytest.raises(ValueError):
        AdmissionController()


def test_client_retries_through_shedding():
    # a shed request is re-sent after backoff and eventually answered —
    # injectable sleep so the test asserts the schedule without waiting
    u, m = _factors()
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(
        _engine(u, m), broker,
        admission=AdmissionController(max_queue=2),
    )
    client = ServeClient(broker)
    slept = []
    got = client.ask(list(range(6)), 3, server=server, retries=4,
                     rng=np.random.default_rng(0), sleep=slept.append)
    assert len(got) == 6
    assert all(not r.error for r in got.values())
    assert client.rejections >= 4  # the shed really happened
    assert client.retries >= 4  # and the re-sends really happened
    # backoff schedule: positive, and the base delays grow exponentially
    assert slept and all(s > 0 for s in slept)


def test_client_retry_exhaustion_raises_timeout():
    u, m = _factors()
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    client = ServeClient(broker)
    slept = []
    with pytest.raises(TimeoutError, match="attempts"):
        # no server at all: every attempt times out, then raises
        client.ask([1], 3, timeout_s=0.2, retries=2,
                   rng=np.random.default_rng(0), sleep=slept.append)
    assert client.retries == 2
    assert len(slept) >= 2  # one backoff per retry


# -- fleet: routing, failover, staleness -------------------------------------


def test_fleet_user_keyed_routing_partitions_traffic():
    fleet, pub, broker, _ = _wired(replicas=2)
    client = ServeClient(broker, route="user")
    for user in range(8):
        client.request(user, 3)
    client.flush()
    # user % 2 routing: each replica's partition holds exactly its users
    from cfk_tpu.transport.serdes import decode_score_request

    for part in (0, 1):
        users = [decode_score_request(r.value).user
                 for r in broker.consume("serve-requests", part, 0)]
        assert users == [u for u in range(8) if u % 2 == part]


def test_fleet_kill_failover_answers_every_accepted_request():
    fleet, pub, broker, _ = _wired(replicas=2)
    fleet.prewarm(3, max_batch=8)
    fleet.start()
    client = ServeClient(broker, route="user")
    try:
        got = client.ask(list(range(16)), 3, timeout_s=20)
        assert len(got) == 16
        fleet.kill_replica(0)
        assert not fleet.replicas[0].alive and fleet.replicas[1].alive
        # partition 0's users are now served by the survivor
        got = client.ask(list(range(16)), 3, timeout_s=20)
        assert len(got) == 16
        assert all(not r.error for r in got.values())
        assert fleet.counters()["failovers"] == 1
    finally:
        fleet.stop()


def test_failover_reserves_uncommitted_requests_at_least_once():
    # the victim polled (cursor advanced) but died before answering
    # (committed cursor did not): the survivor must re-serve from the
    # COMMITTED cursor, so the request is answered, not lost
    fleet, pub, broker, _ = _wired(replicas=2)
    client = ServeClient(broker, route="user")
    victim, heir = fleet.replicas
    rid = client.request(0, 3)  # user 0 -> partition 0 (victim)
    client.flush()
    victim.server._poll_requests()  # polled... then killed mid-batch
    assert victim.server._cursors[0] == 1
    assert victim.server.committed_cursors[0] == 0
    victim.kill()
    fleet.failover(0)
    heir.pump()
    by_id = {r.req_id: r for r in client.poll_responses()}
    assert rid in by_id and not by_id[rid].error


def test_a_replica_killed_with_a_batch_in_flight_is_reserved_by_the_heir():
    """The victim has answered one batch, handed the next to the device and
    polled a third (ISSUE 33) when it dies: its committed cursor stands
    exactly past the batch whose responses were flushed, and the heir
    serves everything after it, the batch in flight included."""
    fleet, pub, broker, _ = _wired(replicas=2, max_batch=4)
    client = ServeClient(broker, route="user")
    victim, heir = fleet.replicas
    ids = [client.request(2 * i, 3) for i in range(10)]  # all to partition 0
    client.flush()
    assert victim.pump() == 0  # batch 1 handed over, nothing answered
    assert victim.server.committed_cursors[0] == 0
    assert victim.pump() == 4  # batch 1 answered, batch 2 in flight
    assert victim.server._in_flight.on_device
    assert victim.server._cursors[0] == 8
    assert victim.server.committed_cursors[0] == 4
    answered = {r.req_id for r in client.poll_responses()}
    assert answered == set(ids[:4])
    victim.kill()
    fleet.failover(0)
    assert heir.server.committed_cursors[0] == heir.server._cursors[0] == 4
    served = 0
    while served < 6:
        served += heir.pump()
    rest = [r for r in client.poll_responses()]
    assert sorted(r.req_id for r in rest) == sorted(ids[4:])
    assert all(not r.error for r in rest)
    assert heir.server.committed_cursors[0] == 10
    assert heir.server._in_flight is None


def test_a_clean_stop_answers_the_batch_in_flight():
    fleet, pub, broker, _ = _wired(replicas=1, max_batch=4)
    fleet.prewarm(3, max_batch=4)
    client = ServeClient(broker)
    ids = [client.request(i, 3) for i in range(23)]
    client.flush()
    fleet.start()
    replica = fleet.replicas[0]
    deadline = time.monotonic() + 30
    while replica.server.batches < 2 and time.monotonic() < deadline:
        time.sleep(0.001)
    fleet.stop()
    # whatever it had polled when it was told to stop has its answer, and
    # its committed cursor says so: nothing is left in flight
    assert replica.server._in_flight is None
    got = {r.req_id for r in client.poll_responses()}
    done = replica.server.committed_cursors[0]
    assert done == replica.server._cursors[0] >= 8
    assert got == set(ids[:done])


def test_a_flip_between_dispatch_and_fetch_keeps_the_old_epochs_batch():
    """The new epoch's engine is swapped in while a batch handed to the old
    one is on the device: that batch is answered from the old table with
    the old epoch's stamp, the next from the new."""
    fleet, pub, broker, (u, m) = _wired(max_batch=4)
    client = ServeClient(broker)
    replica = fleet.replicas[0]
    fleet.prewarm(3, max_batch=4)
    ids = [client.request(i, 3) for i in range(8)]
    client.flush()
    assert replica.pump() == 0 and replica.server._in_flight.on_device
    u2, m2 = _factors(23)
    pub.on_commit({"retrain": True, "user_factors": u2,
                   "movie_factors": m2, "num_users": U})
    replica.apply_deltas()  # starts the background prewarm
    replica._pending_thread.join(timeout=60)
    assert replica._pending is not None
    assert replica.pump() == 4  # flips, polls batch 2, answers batch 1
    assert replica.rollovers == 1 and replica.server.engine.epoch == 1
    assert replica.pump() == 4
    got = {r.req_id: r for r in client.poll_responses()}
    old, new = _engine(u, m), _engine(u2, m2)
    for rows, want_epoch, eng in ((range(4), 0, old), (range(4, 8), 1, new)):
        s, i = eng.topk(np.asarray(list(rows)), 8)
        for j, row in enumerate(rows):
            resp = got[ids[row]]
            assert resp.epoch == want_epoch
            np.testing.assert_array_equal(resp.movie_rows, i[j, :3])
            np.testing.assert_array_equal(resp.scores, s[j, :3])


def test_responses_stamped_with_staleness_backlog():
    fleet, pub, broker, _ = _wired()
    ensure_serve_topics(broker)
    rng = np.random.default_rng(8)
    replica = fleet.replicas[0]
    client = ServeClient(broker)
    for _ in range(3):
        pub.on_commit(_commit(rng, [1]))
    # serve WITHOUT applying: the stamp must expose the 3-frame backlog
    client.request(2, 3)
    client.flush()
    replica.server.step()
    resp = client.poll_responses()[0]
    assert resp.staleness == 3
    replica.apply_deltas()
    client.request(2, 3)
    client.flush()
    replica.server.step()
    assert client.poll_responses()[0].staleness == 0


# -- readiness ---------------------------------------------------------------


def test_readyz_gated_on_prewarm():
    u, m = _factors()
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(_engine(u, m), broker, metrics_port=0,
                             labels={"replica": 3})
    try:
        base = f"http://127.0.0.1:{server.metrics_server.port}"
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{base}/readyz", timeout=5)
        assert exc.value.code == 503  # alive but NOT ready (no prewarm)
        with urllib.request.urlopen(f"{base}/healthz", timeout=5) as r:
            assert r.status == 200  # liveness is a different question
        server.engine.prewarm(3, max_batch=8)
        with urllib.request.urlopen(f"{base}/readyz", timeout=5) as r:
            assert r.status == 200
        # per-replica constant labels ride every sample (PR 16 seam)
        client = ServeClient(broker)
        client.ask([1], 3, server=server)
        with urllib.request.urlopen(f"{base}/metrics", timeout=5) as r:
            text = r.read().decode()
        assert 'replica="3"' in text
    finally:
        server.close()


def test_fleet_ready_property():
    fleet, pub, broker, _ = _wired(replicas=2)
    assert not fleet.ready
    fleet.prewarm(3, max_batch=8)
    assert fleet.ready


# -- commit-listener isolation -----------------------------------------------


def test_broken_commit_listener_does_not_poison_stream(tmp_path):
    # ISSUE 18 satellite: a serving subscriber that raises must not kill
    # the training stream or starve the OTHER listeners
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.models.als import train_als
    from cfk_tpu.streaming import StreamConfig, StreamProducer, StreamSession
    from cfk_tpu.transport.checkpoint import CheckpointManager

    ds = Dataset.from_coo(synthetic_netflix_coo(40, 20, 400, seed=1))
    cfg = ALSConfig(rank=4, num_iterations=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        model = train_als(ds, cfg)
    broker = InMemoryBroker()
    prod = StreamProducer(broker)
    prod.send(int(ds.user_map.raw_ids[0]), int(ds.movie_map.raw_ids[1]), 5.0)
    sess = StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=8), base_model=model,
    )

    def bomb(event):
        raise RuntimeError("replica fell over")

    seen = []
    sess.add_commit_listener(bomb)
    sess.add_commit_listener(seen.append)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        sess.run()  # must NOT raise
    assert len(seen) == 1  # the healthy listener still got the commit
    assert sess.metrics.counters.get("commit_listener_errors", 0) >= 1


def test_publisher_end_to_end_with_stream_session(tmp_path):
    # the full wire: StreamSession commit -> DeltaPublisher frame ->
    # FleetReplica apply -> served scores match an attached engine's
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.models.als import train_als
    from cfk_tpu.streaming import StreamConfig, StreamProducer, StreamSession
    from cfk_tpu.transport.checkpoint import CheckpointManager

    ds = Dataset.from_coo(synthetic_netflix_coo(40, 20, 400, seed=2))
    cfg = ALSConfig(rank=4, num_iterations=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        model = train_als(ds, cfg)
    nu = ds.user_map.num_entities
    nm = ds.movie_map.num_entities
    broker = InMemoryBroker()

    def factory(i):
        return ServeEngine(model.user_factors, model.movie_factors,
                           num_users=nu, num_movies=nm, tile_m=16)

    fleet = ServeFleet(factory, broker, replicas=1)
    fleet.seed_store(model.user_factors, model.movie_factors, num_users=nu)
    pub = DeltaPublisher(broker, fleet.store)
    prod = StreamProducer(broker)
    prod.send(int(ds.user_map.raw_ids[0]), int(ds.movie_map.raw_ids[1]), 5.0)
    sess = StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=8), base_model=model,
    )
    attached = ServeEngine(model.user_factors, model.movie_factors,
                           num_users=nu, num_movies=nm, tile_m=16)
    attached.attach_session(sess)
    pub.attach(sess)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        sess.run()
    replica = fleet.replicas[0]
    replica.pump()
    assert replica.applied_seq >= 1
    assert table_crc(replica.engine) == table_crc(attached)
