"""Exactly-once streaming fold-in: delivery faults, atomic commits, parity.

The contracts under test (ISSUE 6):

- offset-commit atomicity: factors and the consumer cursor commit as ONE
  atomic checkpoint step; a torn final commit falls back to the previous
  step and replaying the uncommitted log suffix converges to crc32-identical
  factors.
- delivery idempotency: duplicated / reordered / dropped-then-redelivered
  records produce factors bit-identical to clean delivery.
- fold-in math parity: the restricted half-iteration equals a direct batch
  solve of the same users' normal equations, on both the padded and tiled
  layouts.
- eviction drains the cursor: a preemption at a batch boundary leaves a
  committed factor+cursor step behind and the resumed session completes to
  the uninterrupted result.
"""

import os
import warnings
import zlib

import numpy as np
import pytest

from cfk_tpu.config import ALSConfig
from cfk_tpu.data.blocks import Dataset
from cfk_tpu.data.synthetic import synthetic_netflix_coo
from cfk_tpu.resilience.faults import FlakyPlan, FlakyTransport
from cfk_tpu.transport import CheckpointManager, FileBroker, InMemoryBroker
from cfk_tpu.streaming import (
    StreamConfig,
    StreamConsumer,
    StreamGapError,
    StreamProducer,
    StreamSession,
    StreamState,
)


def _crc(model) -> int:
    return zlib.crc32(np.asarray(model.user_factors).tobytes())


@pytest.fixture(scope="module")
def ds():
    return Dataset.from_coo(synthetic_netflix_coo(60, 30, 900, seed=0))


@pytest.fixture(scope="module")
def cfg():
    return ALSConfig(rank=4, num_iterations=4, health_check_every=1)


@pytest.fixture(scope="module")
def base(ds, cfg):
    from cfk_tpu.models.als import train_als

    return train_als(ds, cfg)


def _produce_stream(broker, ds, n=60, parts=2, seed=7, new_users=()):
    prod = StreamProducer(broker, num_partitions=parts)
    rng = np.random.default_rng(seed)
    prod.send_many(
        rng.choice(ds.user_map.raw_ids, n),
        rng.choice(ds.movie_map.raw_ids, n),
        rng.integers(1, 6, n).astype(np.float32),
    )
    for raw in new_users:
        prod.send(raw, int(ds.movie_map.raw_ids[0]), 4.0)
    return prod


def _run(ds, cfg, transport, mgr, base=None, batch_records=8, **kw):
    sess = StreamSession(
        ds, cfg, transport, mgr,
        stream=StreamConfig(batch_records=batch_records), base_model=base,
        **kw,
    )
    model = sess.run()
    return sess, model


# --- producer / consumer / state units --------------------------------------


def test_producer_seq_resumes_past_log(ds):
    broker = InMemoryBroker()
    p1 = StreamProducer(broker, num_partitions=3)
    first = p1.send(10, 20, 3.0)
    p1.send_many([11, 12, 13], [20, 21, 22], [1.0, 2.0, 3.0])
    assert first == 0 and p1.next_seq == 4
    # a fresh producer on the same topic resumes past the highest seq
    p2 = StreamProducer(broker)
    assert p2.num_partitions == 3  # existing partition count wins
    assert p2.next_seq == 4
    assert p2.send(14, 23, 5.0) == 4


def test_state_dedup_last_seq_wins(ds):
    from cfk_tpu.transport.serdes import RatingUpdate

    state = StreamState(ds)
    u = int(ds.user_map.raw_ids[0])
    mv_raw = int(ds.movie_map.raw_ids[5])
    mv_row = state.movie_row(mv_raw)
    row = state.user_row(u)
    # reordered within the batch: seq 2 arrives before seq 1
    pending = state.stage([
        RatingUpdate(seq=2, user=u, movie=mv_raw, rating=5.0),
        RatingUpdate(seq=1, user=u, movie=mv_raw, rating=1.0),
    ])
    assert pending.stats.fresh == 1 and pending.stats.stale == 1
    state.commit(pending)
    mv, rt = state.neighbors(row)
    assert rt[mv == mv_row] == [5.0]
    # a retried append (same seq again) is a no-op — the user is untouched
    pending = state.stage(
        [RatingUpdate(seq=2, user=u, movie=mv_raw, rating=5.0)]
    )
    assert pending.stats.stale == 1 and not pending.touched_rows
    # a genuinely newer seq overrides
    pending = state.stage(
        [RatingUpdate(seq=3, user=u, movie=mv_raw, rating=2.0)]
    )
    assert pending.touched_rows == (row,)
    state.commit(pending)
    mv, rt = state.neighbors(row)
    assert rt[mv == mv_row] == [2.0]


def test_state_unknown_movie_rejected_new_user_grown(ds):
    from cfk_tpu.transport.serdes import RatingUpdate

    state = StreamState(ds)
    known = int(ds.movie_map.raw_ids[0])
    pending = state.stage([
        RatingUpdate(seq=0, user=999_999, movie=10**7, rating=3.0),
        RatingUpdate(seq=1, user=999_999, movie=known, rating=3.0),
    ])
    assert pending.stats.unknown_movie == 1
    assert pending.stats.new_users == 1
    state.commit(pending)
    assert state.num_users == state.num_base_users + 1
    assert state.user_row(999_999) == state.num_base_users


def test_consumer_exactly_once_assembly(ds):
    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=40, parts=2)
    flaky = FlakyTransport(
        broker, FlakyPlan(duplicate=2, reorder=4, drop=5, seed=3)
    )
    clean = StreamConsumer(broker)
    faulty = StreamConsumer(flaky, gap_wait_s=0.001)
    while True:
        a, b = clean.poll(8), faulty.poll(8)
        assert (a is None) == (b is None)
        if a is None:
            break
        assert a.updates == b.updates  # identical batches, fault or not
        assert a.cursors_after == b.cursors_after
    assert flaky.duplicated and flaky.reordered and flaky.dropped


def test_consumer_gap_fails_loudly(ds):
    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=10, parts=1)
    # every delivery pass drops every record, forever: the log claims
    # records the transport never delivers — loud error, not a hang
    black_hole = FlakyTransport(
        broker, FlakyPlan(drop=1, drop_passes=1 << 30)
    )
    consumer = StreamConsumer(black_hole, gap_retries=2, gap_wait_s=0.001)
    with pytest.raises(StreamGapError, match="never delivered"):
        consumer.poll(4)


# --- fold-in math parity -----------------------------------------------------


def _expected_rows(state, rows, m_host, lam):
    k = m_host.shape[1]
    out = np.zeros((len(rows), k), np.float32)
    for i, row in enumerate(rows):
        mv, rt = state.neighbors(row)
        f = m_host[mv]
        a = f.T @ f + lam * max(len(mv), 1) * np.eye(k, dtype=np.float32)
        out[i] = np.linalg.solve(a, f.T @ rt)
    return out


@pytest.mark.parametrize("layout", ["padded", "tiled"])
def test_fold_in_matches_batch_half_solve(ds, layout):
    """The restricted half-iteration == a direct batch solve of the same
    rows' normal equations (the ISSUE's one-half-iteration parity)."""
    import jax.numpy as jnp

    from cfk_tpu.streaming.foldin import fold_in_rows

    state = StreamState(ds)
    rng = np.random.default_rng(0)
    m_host = rng.standard_normal(
        (ds.movie_blocks.padded_entities, 4)
    ).astype(np.float32)
    rows = [0, 3, 17]
    neighbor_data = [state.neighbors(r) for r in rows]
    got = fold_in_rows(
        jnp.asarray(m_host), neighbor_data, lam=0.05, solver="cholesky",
        layout=layout,
    )
    want = _expected_rows(state, rows, m_host, 0.05)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


def test_fold_in_tiled_padded_parity(ds):
    import jax.numpy as jnp

    from cfk_tpu.streaming.foldin import fold_in_rows

    state = StreamState(ds)
    rng = np.random.default_rng(1)
    m_host = rng.standard_normal(
        (ds.movie_blocks.padded_entities, 4)
    ).astype(np.float32)
    neighbor_data = [state.neighbors(r) for r in range(8)]
    a = fold_in_rows(jnp.asarray(m_host), neighbor_data, lam=0.05,
                     solver="cholesky", layout="padded")
    b = fold_in_rows(jnp.asarray(m_host), neighbor_data, lam=0.05,
                     solver="cholesky", layout="tiled")
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_session_foldin_rmse_parity_with_batch_solve(ds, cfg, base, tmp_path):
    """End-to-end: after draining the stream, every touched user's row
    equals the direct solve of their CURRENT normal equations against the
    fixed movie factors — fold-in is exactly one restricted half-iteration,
    never an approximation drifting with batch count."""
    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=60, parts=2)
    sess, model = _run(ds, cfg, broker, CheckpointManager(str(tmp_path)),
                       base=base, batch_records=8)
    # rows touched by ANY batch: recompute from the final state
    touched = sorted(sess.state._delta)
    assert touched
    m_host = np.asarray(model.movie_factors)
    want = _expected_rows(sess.state, touched, m_host, cfg.lam)
    got = np.asarray(model.user_factors)[touched]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # untouched rows ride through bit-identical to the base model
    untouched = sorted(
        set(range(sess.state.num_base_users)) - set(touched)
    )
    np.testing.assert_array_equal(
        np.asarray(model.user_factors)[untouched],
        np.asarray(base.user_factors)[untouched],
    )


def test_session_meters_every_stage_of_a_batch(ds, cfg, base, tmp_path):
    """A drained stream leaves the session's own accounting behind: the
    batches committed, the fresh updates absorbed, and a wall-clock phase
    for each stage of the batch loop (stage, solve, health probe, commit),
    through the asynchronous checkpoint writer."""
    from cfk_tpu.utils.metrics import Metrics

    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=48, parts=1)
    metrics = Metrics()
    sess, _ = _run(
        ds, cfg, broker, CheckpointManager(str(tmp_path), async_write=True),
        base=base, batch_records=16, metrics=metrics,
    )
    assert sess.stream_step == 3
    assert 0 < metrics.counters["updates_fresh"] <= 48
    for phase in ("stage", "foldin_solve", "health_check", "commit"):
        assert metrics.phases[phase] >= 0, phase


# --- delivery-fault / crash bit-exactness ------------------------------------


def test_duplicate_reorder_drop_delivery_bit_exact(ds, cfg, base, tmp_path):
    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=60, parts=2, new_users=(4242,))
    _, clean = _run(ds, cfg, broker, CheckpointManager(str(tmp_path / "a")),
                    base=base)
    flaky = FlakyTransport(
        broker, FlakyPlan(duplicate=3, reorder=5, drop=7, seed=1)
    )
    sess, faulty = _run(ds, cfg, flaky, CheckpointManager(str(tmp_path / "b")),
                        base=base)
    assert flaky.duplicated and flaky.reordered and flaky.dropped
    assert _crc(clean) == _crc(faulty)
    assert np.array_equal(np.asarray(clean.movie_factors),
                          np.asarray(faulty.movie_factors))
    assert sess.metrics.counters.get("delivery_duplicates", 0) > 0


def test_crash_replay_bit_exact_on_filebroker(ds, cfg, base, tmp_path):
    """Durable end to end: FileBroker log + checkpoint store on disk; a
    'crash' (session abandoned mid-stream) resumes from the committed
    cursor and converges to the uninterrupted run's exact factors."""
    with FileBroker(str(tmp_path / "log"), fsync=False) as broker:
        _produce_stream(broker, ds, n=60, parts=2, new_users=(4242, 4243))
        _, clean = _run(ds, cfg, broker,
                        CheckpointManager(str(tmp_path / "a")), base=base)
        # crashed run: only 3 batches processed, then the process dies
        s2 = StreamSession(
            ds, cfg, broker, CheckpointManager(str(tmp_path / "b")),
            stream=StreamConfig(batch_records=8), base_model=base,
        )
        s2.run(max_batches=3)
        del s2
        # a fresh process: resume from the store, finish the suffix
        s3 = StreamSession(
            ds, cfg, broker, CheckpointManager(str(tmp_path / "b")),
            stream=StreamConfig(batch_records=8),
        )
        replayed = s3.run()
        assert s3.metrics.counters.get("replayed_updates", 0) > 0
    assert _crc(clean) == _crc(replayed)


def test_torn_commit_falls_back_and_replay_converges(ds, cfg, base, tmp_path):
    """Offset-commit atomicity: the factors and the cursor live in ONE
    atomic step, so 'kill between factor write and cursor write' can only
    manifest as a torn step — which crc verification rejects wholesale;
    resume falls back to the previous (factor+cursor-consistent) step and
    replays the suffix to identical crc32."""
    from cfk_tpu.resilience.faults import TornCheckpointManager

    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=48, parts=2)
    s1, clean = _run(ds, cfg, broker, CheckpointManager(str(tmp_path / "a")),
                     base=base)
    final_step = s1.stream_step
    assert final_step >= 2
    # run with the FINAL stream commit torn (payload truncated after the
    # rename — the worst case: factors written, "cursor write" lost)
    inner = CheckpointManager(str(tmp_path / "b"))
    torn = TornCheckpointManager(inner, tear_at=final_step)
    s2 = StreamSession(
        ds, cfg, broker, torn, stream=StreamConfig(batch_records=8),
        base_model=base,
    )
    s2.run()
    assert torn.torn  # the fault fired
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # "skipping corrupt checkpoint"
        s3 = StreamSession(
            ds, cfg, broker, CheckpointManager(str(tmp_path / "b")),
            stream=StreamConfig(batch_records=8),
        )
        # the torn step was rejected: the session resumed one step earlier
        assert s3.stream_step == final_step - 1
        replayed = s3.run()
        assert s3.stream_step == final_step  # the suffix was re-processed
    assert _crc(clean) == _crc(replayed)


# --- eviction ----------------------------------------------------------------


def test_eviction_drains_and_commits_cursor(ds, cfg, base, tmp_path):
    from cfk_tpu.resilience.preempt import PreemptionGuard

    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=60, parts=2)
    _, clean = _run(ds, cfg, broker, CheckpointManager(str(tmp_path / "a")),
                    base=base)

    guard = PreemptionGuard()

    def evict_at(step):
        if step >= 3:
            guard.trigger()

    s2 = StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path / "b")),
        stream=StreamConfig(batch_records=8), base_model=base,
        preemption_guard=guard,
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        s2.run(before_batch=evict_at)
    assert "preempted" in s2.metrics.notes
    # the newest committed step carries exactly the consumer's cursor
    mgr = CheckpointManager(str(tmp_path / "b"))
    st = mgr.restore()
    assert {int(p): int(o) for p, o in st.meta["offsets"].items()} \
        == s2.consumer.cursors
    assert st.meta["stream_step"] == s2.stream_step == 3
    # resume finishes the stream to the uninterrupted result
    s3 = StreamSession(ds, cfg, broker, mgr,
                       stream=StreamConfig(batch_records=8))
    resumed = s3.run()
    assert _crc(clean) == _crc(resumed)


# --- poison batches ----------------------------------------------------------


def test_singular_batch_escalates_lambda(tmp_path):
    """λ=0 + a new user with one rating → exactly singular normal
    equations; the sentinel trips, the ladder's λ bump is the designed
    fix, and the stream continues with finite factors."""
    from cfk_tpu.models.als import train_als
    from cfk_tpu.resilience.faults import blockstructured_coo

    ds = Dataset.from_coo(blockstructured_coo(seed=0))
    cfg = ALSConfig(rank=4, num_iterations=4, lam=0.0, health_check_every=1)
    base = train_als(ds, cfg)
    broker = InMemoryBroker()
    prod = StreamProducer(broker)
    prod.send(777, int(ds.movie_map.raw_ids[0]), 5.0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        sess, model = _run(ds, cfg, broker,
                           CheckpointManager(str(tmp_path)), base=base)
    assert sess.metrics.counters.get("health_trips", 0) >= 1
    assert sess.metrics.gauges.get("stream_escalation_level", 0) >= 1
    assert not sess.quarantined
    assert sess._overrides.lam > 0  # the bump is sticky
    assert np.all(np.isfinite(np.asarray(model.user_factors)))


def test_escalated_overrides_survive_crash_resume(tmp_path):
    """Regression: the sticky escalation state (λ bump, epilogue/algo
    rungs) commits with every batch and is RESTORED on resume — a crash
    after an escalation must not revert post-resume solves to the
    config's un-escalated knobs, or replay is no longer bit-identical to
    an uninterrupted run (the singular batch escalates λ from 0; the
    good batches after it were solved at the bumped λ and must replay
    that way)."""
    from cfk_tpu.models.als import train_als
    from cfk_tpu.resilience.faults import blockstructured_coo

    ds = Dataset.from_coo(blockstructured_coo(seed=0))
    cfg = ALSConfig(rank=4, num_iterations=4, lam=0.0, health_check_every=1)
    base = train_als(ds, cfg)

    def produce(broker):
        prod = StreamProducer(broker)
        prod.send(777, int(ds.movie_map.raw_ids[0]), 5.0)  # singular
        for i in range(4):  # good batches solved under the bumped λ
            prod.send(int(ds.user_map.raw_ids[i]),
                      int(ds.movie_map.raw_ids[i + 1]), 4.0)

    clean = InMemoryBroker()
    produce(clean)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        s_clean, m_clean = _run(
            ds, cfg, clean, CheckpointManager(str(tmp_path / "clean")),
            base=base, batch_records=1,
        )
    assert s_clean._overrides.lam > 0  # the bump fired and stuck

    crash = InMemoryBroker()
    produce(crash)
    mgr = CheckpointManager(str(tmp_path / "crash"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        s1 = StreamSession(
            ds, cfg, crash, mgr,
            stream=StreamConfig(batch_records=1), base_model=base,
        )
        s1.run(max_batches=2)  # escalate + one good batch, then "crash"
    assert s1._overrides.lam > 0
    s2 = StreamSession(
        ds, cfg, crash, CheckpointManager(str(tmp_path / "crash")),
        stream=StreamConfig(batch_records=1),
    )
    # the committed ladder state is restored before any solving
    assert s2._overrides == s1._overrides
    m_resumed = s2.run()
    assert _crc(m_resumed) == _crc(m_clean)


def test_poison_batch_quarantined_factors_untouched(ds, cfg, base, tmp_path):
    """A NaN rating defeats every ladder rung → the batch is quarantined:
    its offsets are consumed (no wedge) but neither the factors nor the
    rating state see its writes, and later good batches still apply."""
    broker = InMemoryBroker()
    prod = StreamProducer(broker)
    victim = int(ds.user_map.raw_ids[0])
    other = int(ds.user_map.raw_ids[1])
    prod.send(victim, int(ds.movie_map.raw_ids[1]), float("nan"))
    prod.send(other, int(ds.movie_map.raw_ids[2]), 5.0)
    sess = StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=1), base_model=base,
    )
    u_before = np.array(sess.user_factors)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        model = sess.run()
    assert len(sess.quarantined) == 1
    assert sess.metrics.counters.get("quarantined_batches") == 1
    assert sess.backlog() == 0  # the poison pill did not wedge the stream
    # the victim's row is exactly the pre-poison value; the good batch
    # after the poison still applied
    vrow = sess.state.user_row(victim)
    orow = sess.state.user_row(other)
    u_after = np.asarray(model.user_factors)
    np.testing.assert_array_equal(u_after[vrow], u_before[vrow])
    assert not np.array_equal(u_after[orow], u_before[orow])
    assert np.all(np.isfinite(u_after))
    # the NaN never entered the rating state
    _, rt = sess.state.neighbors(vrow)
    assert np.all(np.isfinite(rt))


def test_poison_batch_raises_when_configured(ds, base, tmp_path):
    from cfk_tpu.streaming import PoisonedBatchError

    cfg = ALSConfig(rank=4, num_iterations=4, health_check_every=1,
                    on_unrecoverable="raise")
    broker = InMemoryBroker()
    StreamProducer(broker).send(
        int(ds.user_map.raw_ids[0]), int(ds.movie_map.raw_ids[0]),
        float("nan"),
    )
    sess = StreamSession(ds, cfg, broker, CheckpointManager(str(tmp_path)),
                         base_model=base)
    with pytest.raises(PoisonedBatchError, match="quarantined"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            sess.run()


def test_quarantined_batch_not_replayed_on_resume(ds, cfg, base, tmp_path):
    """Quarantined offsets are recorded in every commit and SKIPPED by the
    crash-replay state rebuild: resume must neither re-apply the poison
    writes the ladder rejected nor crash on a quarantined batch's
    never-committed new user (regression: replay used to re-apply every
    record below the cursor)."""
    broker = InMemoryBroker()
    prod = StreamProducer(broker)
    victim = int(ds.user_map.raw_ids[0])
    other = int(ds.user_map.raw_ids[1])
    # poison batch that also introduces a NEW user: its row is never
    # committed, so a replay that fails to skip it would hard-crash on
    # the new-user list check
    prod.send(888, int(ds.movie_map.raw_ids[1]), float("nan"))
    prod.send(victim, int(ds.movie_map.raw_ids[2]), float("nan"))
    prod.send(other, int(ds.movie_map.raw_ids[3]), 5.0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        sess1, model1 = _run(ds, cfg, broker,
                             CheckpointManager(str(tmp_path)), base=base,
                             batch_records=1)
    assert len(sess1.quarantined) == 2
    # fresh session on the same store + log: replays state below the
    # cursor minus the quarantined ranges
    sess2 = StreamSession(ds, cfg, broker, CheckpointManager(str(tmp_path)))
    assert sess2.quarantined == sess1.quarantined
    assert sess2.state.user_row(888) is None  # poison new user never existed
    assert sess2.state.num_users == sess1.state.num_users
    vrow = sess2.state.user_row(victim)
    _, rt = sess2.state.neighbors(vrow)
    assert np.all(np.isfinite(rt))  # the NaN write stayed quarantined
    assert _crc(sess2.model()) == _crc(model1)


def test_batch_records_committed_value_wins_on_resume(ds, cfg, base,
                                                      tmp_path):
    """Batch boundaries are part of the replay contract: a resume with a
    different --batch-records must keep cutting batches at the COMMITTED
    size, or the re-cut batches would drift from an uninterrupted run at
    the ulp level (regression: the committed value was written but never
    read back)."""
    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=60)
    clean_dir = str(tmp_path / "clean")
    crash_dir = str(tmp_path / "crash")
    _, model_clean = _run(ds, cfg, broker, CheckpointManager(clean_dir),
                          base=base, batch_records=8)
    sess1 = StreamSession(
        ds, cfg, broker, CheckpointManager(crash_dir),
        stream=StreamConfig(batch_records=8), base_model=base,
    )
    sess1.run(max_batches=2)  # "crash" with backlog remaining
    assert sess1.backlog() > 0
    sess2 = StreamSession(
        ds, cfg, broker, CheckpointManager(crash_dir),
        stream=StreamConfig(batch_records=3),  # operator changed the flag
    )
    assert sess2.stream.batch_records == 8  # the committed value won
    assert "batch_records_override" in sess2.metrics.notes
    model2 = sess2.run()
    assert _crc(model2) == _crc(model_clean)


def test_gap_repoll_not_counted_as_duplicates(ds):
    """Records re-seen because WE re-polled a gap are not transport
    duplicates; only a second copy within one delivery pass counts
    (regression: a single dropped record inflated duplicates_dropped by
    ~the batch size)."""
    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=30, parts=1)
    flaky = FlakyTransport(broker, FlakyPlan(drop=5, drop_passes=1))
    consumer = StreamConsumer(flaky, gap_wait_s=0.0)
    batch = consumer.poll(30)
    assert flaky.dropped > 0  # the fault fired
    assert batch.gap_repolls > 0  # and was healed by re-polling
    assert batch.duplicates_dropped == 0  # but is NOT a duplication fault
    assert batch.num_records == 30


# --- warm retrain / warm_start ----------------------------------------------


def test_warm_start_seeds_train_als(ds, cfg, base):
    from cfk_tpu.models.als import train_als

    u0 = np.asarray(base.user_factors)
    m0 = np.asarray(base.movie_factors)
    import dataclasses

    one = dataclasses.replace(cfg, num_iterations=1)
    warm = train_als(ds, one, warm_start=(u0, m0))
    # warm continuation ≠ cold iteration 1 (the seed was really used):
    cold = train_als(ds, one)
    assert not np.array_equal(np.asarray(warm.user_factors),
                              np.asarray(cold.user_factors))
    # and it equals stepping the base model exactly one more iteration —
    # for explicit ALS an iteration is (M | U_prev) then (U | M), and the
    # M half depends only on U_prev, so seeding (U_base, ·) reproduces it
    two = dataclasses.replace(cfg, num_iterations=cfg.num_iterations + 1)
    from cfk_tpu.models.als import train_als as t
    stepped = t(ds, two)
    np.testing.assert_allclose(
        np.asarray(warm.user_factors), np.asarray(stepped.user_factors),
        atol=1e-5, rtol=1e-5,
    )


def test_warm_start_shape_mismatch_refused(ds, cfg):
    from cfk_tpu.models.als import train_als

    bad = np.zeros((ds.user_blocks.padded_entities + 99, cfg.rank),
                   np.float32)
    m0 = np.zeros((ds.movie_blocks.padded_entities, cfg.rank), np.float32)
    with pytest.raises(ValueError, match="warm_start user factors"):
        train_als(ds, cfg, warm_start=(bad, m0))


def test_periodic_warm_retrain_and_resume(ds, cfg, base, tmp_path):
    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=40, parts=1, new_users=(5555,))
    sess = StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=16, retrain_every=2),
        base_model=base,
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        model = sess.run()
    assert sess.metrics.counters.get("stream_retrains", 0) >= 1
    # the retrain moved the MOVIE side too (fold-ins never do)
    assert not np.array_equal(np.asarray(model.movie_factors),
                              np.asarray(base.movie_factors))
    # resume after a retrain still lines rows up with the replayed state
    s2 = StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=16, retrain_every=2),
    )
    assert s2.state.num_users == sess.state.num_users
    assert _crc(s2.model()) == _crc(model)
