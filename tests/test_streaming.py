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
import dataclasses
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


def test_fold_in_matches_batch_half_solve(ds):
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
    )
    want = _expected_rows(state, rows, m_host, 0.05)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


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
        assert s3.metrics.counters.get("restored_cells", 0) > 0
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


# --- commit units, a CSR state, a base table never copied (PR 34) ------------


def _csr_of(ds):
    state = StreamState(ds)
    return state._base_indptr, state._base_movies, state._base_ratings


def test_session_from_csr_equals_session_from_dataset(ds, cfg, base,
                                                       tmp_path):
    """The state a serving deployment builds from the CSR it holds (identity
    id maps, no Dataset) folds the same ratings into the same bits."""
    rng = np.random.default_rng(7)
    users = rng.choice(ds.user_map.raw_ids, 60)
    movies = rng.choice(ds.movie_map.raw_ids, 60)
    ratings = rng.integers(1, 6, 60).astype(np.float32)
    # one user the base has never seen: the same raw id is past both row
    # spaces, so both sessions grow the same row for it
    users[17] = users[41] = 4242
    by_raw, by_row = InMemoryBroker(), InMemoryBroker()
    StreamProducer(by_raw, num_partitions=2).send_many(users, movies, ratings)
    known = users != 4242
    rows = np.where(known, ds.user_map.to_dense(np.where(known, users,
                                                         users[0])), 4242)
    StreamProducer(by_row, num_partitions=2).send_many(
        rows, ds.movie_map.to_dense(movies), ratings)
    a, _ = _run(ds, cfg, by_raw, CheckpointManager(str(tmp_path / "a")),
                base=base)
    indptr, items, values = _csr_of(ds)
    state = StreamState.from_csr(indptr, items, values,
                                 num_movies=ds.movie_map.num_entities)
    b, _ = _run(state, cfg, by_row, CheckpointManager(str(tmp_path / "b")),
                base=base)
    assert b.dataset is None and b.stream_step == a.stream_step >= 4
    assert b.state.num_users == a.state.num_users == 61
    np.testing.assert_array_equal(a.user_factors, b.user_factors)
    for row in sorted(a.state._delta):
        for x, y in zip(a.state.neighbors(row), b.state.neighbors(row)):
            np.testing.assert_array_equal(x, y)
    with pytest.raises(ValueError, match="not a CSR"):
        StreamState.from_csr(indptr[:-1], items, values, num_movies=30)


def test_kill_after_commit_units_resumes_bit_identical(ds, cfg, base,
                                                       tmp_path):
    """A commit is the rows the batch solved, the cells it applied and the
    cursor, one atomic unit: the store holds one snapshot and a unit a
    batch; a process killed after n of them resumes to the rows and the
    cursor of the uninterrupted run, bit for bit."""
    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=60, parts=1, new_users=(4242,))
    clean, _ = _run(ds, cfg, broker, CheckpointManager(str(tmp_path / "a")),
                    base=base)
    s2 = StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path / "b")),
        stream=StreamConfig(batch_records=8), base_model=base,
    )
    s2.run(max_batches=4)
    at_kill, cursor_at_kill = s2.user_factors, dict(s2.consumer.cursors)
    del s2
    mgr = CheckpointManager(str(tmp_path / "b"))
    assert mgr.iterations() == [0, 1, 2, 3, 4]
    snap = mgr.restore(0)
    assert snap.meta["kind"] == "snapshot"
    assert snap.user_factors.shape[0] >= 60 and snap.movie_factors.shape[0]
    applied = 0
    for it in range(1, 5):
        unit = mgr.restore(it)
        assert unit.meta["kind"] == "unit" and unit.meta["stream_step"] == it
        assert unit.meta["offsets"] == {"0": 8 * it}
        # the rows solved and the cells applied are arrays of the payload
        assert "touched_rows" not in unit.meta and "cells" not in unit.meta
        touched, cells = unit.arrays["touched"], unit.arrays["cells"]
        assert unit.user_factors.shape == (len(touched), cfg.rank)
        assert 1 <= len(touched) <= 8 and unit.movie_factors.shape[0] == 0
        assert set(cells["row"].tolist()) == set(touched.tolist())
        assert cells.dtype.names == ("row", "movie", "rating", "seq")
        assert len(cells) <= 8
        applied += len(cells)
    s3 = StreamSession(ds, cfg, broker, mgr,
                       stream=StreamConfig(batch_records=8))
    assert s3.stream_step == 4 and s3.consumer.cursors == cursor_at_kill
    assert s3.metrics.counters["replayed_units"] == 4
    assert s3.metrics.counters["replayed_unit_cells"] == applied
    np.testing.assert_array_equal(s3.user_factors, at_kill)
    s3.run()
    assert s3.stream_step == clean.stream_step
    assert s3.consumer.cursors == clean.consumer.cursors
    np.testing.assert_array_equal(s3.user_factors, clean.user_factors)


def test_a_thousand_new_users_never_copy_the_base_table(ds, cfg, base,
                                                        tmp_path):
    """Streamed-in users land in an appended segment that doubles: the base
    user table is the caller's array, by reference, never written and never
    copied, whatever the stream adds."""
    import types

    table = np.array(np.asarray(base.user_factors), np.float32)
    before = table.copy()
    model = types.SimpleNamespace(user_factors=table,
                                  movie_factors=base.movie_factors)
    broker = InMemoryBroker()
    prod = StreamProducer(broker)
    rng = np.random.default_rng(3)
    prod.send_many(10_000 + np.arange(1000),
                   rng.choice(ds.movie_map.raw_ids, 1000),
                   rng.integers(1, 6, 1000).astype(np.float32))
    sess = StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=16), base_model=model,
    )
    assert sess._users.base is table
    sess.run()
    assert sess.state.num_users == sess.state.num_base_users + 1000
    assert sess._users.base is table and np.array_equal(table, before)
    # 1,000 rows in a segment that doubles from 64: five allocations, not
    # the sixteen of a table regrown every 64 users
    assert len(sess._users) == 1000 and sess._users.allocations <= 5
    whole = sess.user_factors
    assert whole.shape[0] >= sess.state.num_users
    np.testing.assert_array_equal(whole[:sess.state.num_base_users],
                                  before[:sess.state.num_base_users])
    assert np.abs(whole[sess.state.num_base_users:sess.state.num_users]
                  ).sum(axis=1).min() > 0


def test_fold_in_rows_against_the_plain_reference(tmp_path):
    """Every row a stream commits is the float64 solve of the user's own
    ALS-WR normal equations over the float32 item table and the user's list
    as of that commit (``benchmarks/harness/reference_foldin.py``: numpy,
    nothing of the program)."""
    import types

    from benchmarks.harness import reference_foldin

    rng = np.random.default_rng(11)
    users_n, items_n, rank, lam = 80, 50, 8, 0.05
    lens = rng.integers(1, 9, users_n)
    indptr = np.concatenate([[0], np.cumsum(lens)])
    items = np.concatenate([np.sort(rng.choice(items_n, n, replace=False))
                            for n in lens]).astype(np.int32)
    values = rng.integers(1, 6, items.size).astype(np.float32)
    u_tab = ((rng.random((users_n, rank)) - 0.5) * 0.35).astype(np.float32)
    m_tab = ((rng.random((items_n, rank)) - 0.5) * 0.35).astype(np.float32)
    broker = InMemoryBroker()
    r_users = rng.integers(0, users_n, 64)
    r_items = rng.integers(0, items_n, 64)
    r_values = rng.integers(1, 6, 64).astype(np.float32)
    StreamProducer(broker).send_many(r_users, r_items, r_values)
    sess = StreamSession(
        StreamState.from_csr(indptr, items, values, num_movies=items_n),
        ALSConfig(rank=rank, lam=lam, health_check_every=1), broker,
        CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=8),
        base_model=types.SimpleNamespace(user_factors=u_tab,
                                         movie_factors=m_tab),
    )
    events = []
    sess.add_commit_listener(events.append)
    sess.run()
    assert len(events) == 8
    committed = np.repeat([e["stream_step"] for e in events], 8)
    worst = 0.0
    for e in events:
        assert e["cursors"] == {0: 8 * e["stream_step"]}
        for row, solved in zip(e["touched_rows"], e["rows"]):
            mine = [(r_items[j], r_values[j], committed[j])
                    for j in np.nonzero(r_users == row)[0]]
            lo, hi = indptr[row], indptr[row + 1]
            exact = reference_foldin.solve_row(
                m_tab, *reference_foldin.list_as_of(
                    items[lo:hi], values[lo:hi], mine, e["stream_step"]),
                lam)
            worst = max(worst, reference_foldin.row_err(solved, exact))
    # float32 normal equations against float64's: rounding, nothing else
    assert 0 < worst < 1e-5
    # the lower-precision control is three orders of magnitude off
    lo, hi = indptr[0], indptr[1]
    narrow = reference_foldin.solve_row(
        m_tab, items[lo:hi], values[lo:hi], lam, dtype=np.float16)
    exact = reference_foldin.solve_row(m_tab, items[lo:hi], values[lo:hi],
                                       lam)
    assert reference_foldin.row_err(narrow, exact) > 1e-4


def test_retention_keeps_the_snapshot_the_units_rest_on(ds, cfg, base,
                                                        tmp_path):
    """``keep_last_n`` collects old units, never the snapshot they rest on:
    a resume then takes the shorter run from disk and the rest from the
    log, to the same bits."""
    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=48, parts=1)
    clean, _ = _run(ds, cfg, broker, CheckpointManager(str(tmp_path / "a")),
                    base=base)
    mgr = CheckpointManager(str(tmp_path / "b"), keep_last_n=2)
    s2, _ = _run(ds, cfg, broker, mgr, base=base)
    assert mgr.iterations() == [0, 5, 6]  # the snapshot and the newest two
    s3 = StreamSession(ds, cfg, broker,
                       CheckpointManager(str(tmp_path / "b"), keep_last_n=2),
                       stream=StreamConfig(batch_records=8))
    # unit 1 is gone, so nothing after the snapshot can be taken from disk
    assert s3.stream_step == 0 and s3.backlog() == 48
    s3.run()
    np.testing.assert_array_equal(s3.user_factors, clean.user_factors)
    assert s3.consumer.cursors == clean.consumer.cursors


# --- several batches in flight (a stream behind its log) ---------------------


def _pendings_equal(a, b):
    return (a.touched_rows == b.touched_rows
            and a.new_user_raw == b.new_user_raw
            and a.cell_writes == b.cell_writes and a.stats == b.stats)


def test_staging_over_batches_in_flight_equals_staging_after_their_commits(
        ds):
    """``stage(over=)`` reads the state as it will stand once the batches
    before it are committed: their cells (a re-rate, a retried append),
    their new users' rows; the lists the solve reads are the same too."""
    from cfk_tpu.streaming.state import overlay_of
    from cfk_tpu.transport.serdes import RatingUpdate

    rng = np.random.default_rng(3)
    users = [int(u) for u in ds.user_map.raw_ids[:6]] + [9001, 9002, 9003]
    movies = [int(m) for m in ds.movie_map.raw_ids[:5]]
    ups = [RatingUpdate(user=int(rng.choice(users)),
                        movie=int(rng.choice(movies)),
                        rating=float(rng.integers(1, 6)), seq=seq)
           for seq in range(40)]
    ups[17] = dataclasses.replace(ups[3], rating=ups[3].rating)  # a retry
    batches = [ups[i:i + 8] for i in range(0, 40, 8)]
    one, many = StreamState(ds), StreamState(ds)
    in_flight = []
    for batch in batches:
        after = one.stage(batch)
        over = many.stage(batch, in_flight)
        assert _pendings_equal(after, over)
        for row in after.touched_rows:
            a = one.neighbors(row, after.cell_writes.get(row))
            b = many.neighbors(row, overlay_of(row, (*in_flight, over)))
            np.testing.assert_array_equal(a[0], b[0])
            np.testing.assert_array_equal(a[1], b[1])
        one.commit(after)
        in_flight.append(over)
    assert sum(p.stats.new_users for p in in_flight) == 3
    assert sum(p.stats.stale for p in in_flight) >= 1
    assert any(set(p.touched_rows) & set(q.touched_rows)
               for p, q in zip(in_flight, in_flight[1:]))


@pytest.mark.parametrize("fault", ["none", "poison", "singular"])
def test_a_backlog_pumped_several_batches_deep_commits_what_step_after_step_does(
        ds, cfg, base, tmp_path, fault):
    """``pump`` under a busy device hands over several micro-batches while
    whole ones wait in the log, each staged over the ones before it; what
    it commits — rows, cells, cursors, unit by unit — is what ``step`` after
    ``step`` commits, bit for bit, also where the batch at the head trips
    (retried under sticky overrides, or quarantined) with others behind."""
    from cfk_tpu.streaming import session as session_mod

    if fault == "singular":
        from cfk_tpu.models.als import train_als
        from cfk_tpu.resilience.faults import blockstructured_coo

        ds = Dataset.from_coo(blockstructured_coo(seed=0))
        cfg = ALSConfig(rank=4, num_iterations=4, lam=0.0,
                        health_check_every=1)
        base = train_als(ds, cfg)
    broker = InMemoryBroker()
    prod = _produce_stream(broker, ds, n=20, parts=1, new_users=(4242,))
    if fault == "poison":
        prod.send(int(ds.user_map.raw_ids[0]), int(ds.movie_map.raw_ids[1]),
                  float("nan"))
    if fault == "singular":
        prod.send(777, int(ds.movie_map.raw_ids[0]), 5.0)
    rng = np.random.default_rng(5)
    prod.send_many(rng.choice(ds.user_map.raw_ids[:8], 43),
                   rng.choice(ds.movie_map.raw_ids, 43),
                   rng.integers(1, 6, 43).astype(np.float32))
    prod.send(4242, int(ds.movie_map.raw_ids[3]), 2.0)

    def session(where):
        s = StreamSession(
            ds, cfg, broker, CheckpointManager(str(tmp_path / where)),
            stream=StreamConfig(batch_records=4), base_model=base)
        events = []
        s.add_commit_listener(events.append)
        return s, events

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        one, one_events = session("step")
        one.run()
        many, many_events = session("pump")
        deepest = pumps = 0
        while many.backlog() or many.in_flight:
            # a call that only waits for the writer's rename commits nothing
            pumps += bool(many.pump(device_busy=True)) or bool(many._in_flight)
            deepest = max(deepest, len(many._in_flight))
    assert deepest == session_mod._PUMP_DEPTH == 3
    assert pumps < one.stream_step  # several commits a call
    assert many.stream_step == one.stream_step >= 16
    assert many.consumer.cursors == one.consumer.cursors
    np.testing.assert_array_equal(many.user_factors, one.user_factors)
    assert many.quarantined == one.quarantined
    assert len(one.quarantined) == (fault == "poison")
    assert many._overrides == one._overrides
    # a trip's escalation is sticky: the batches behind it solved under it
    assert (one._overrides.lam > cfg.lam) == (fault != "none")
    assert one.metrics.counters.get("health_trips", 0) == \
        many.metrics.counters.get("health_trips", 0)
    assert many.metrics.counters["updates_fresh"] == \
        one.metrics.counters["updates_fresh"]
    assert len(many_events) == len(one_events)
    for a, b in zip(one_events, many_events):
        assert a["stream_step"] == b["stream_step"]
        assert a["touched_rows"] == b["touched_rows"]
        assert a["cells"] == b["cells"] and a["cursors"] == b["cursors"]
        np.testing.assert_array_equal(a["rows"], b["rows"])
    # the stores hold the same units, and either resumes to the same bits
    from cfk_tpu.resilience.loop import drain_checkpoints

    drain_checkpoints(one.manager)
    drain_checkpoints(many.manager)
    for it in many.manager.iterations()[1:]:
        a, b = one.manager.restore(it), many.manager.restore(it)
        assert a.meta == b.meta
        np.testing.assert_array_equal(a.user_factors, b.user_factors)
    again = StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path / "pump")),
        stream=StreamConfig(batch_records=4))
    assert again.stream_step == one.stream_step
    np.testing.assert_array_equal(again.user_factors, one.user_factors)


def test_a_pump_with_nothing_else_on_the_device_commits_what_it_handed_over(
        ds, cfg, base, tmp_path):
    broker = InMemoryBroker()
    _produce_stream(broker, ds, n=20, parts=1)
    s = StreamSession(ds, cfg, broker, CheckpointManager(str(tmp_path)),
                      stream=StreamConfig(batch_records=4), base_model=base)
    assert s.pump() == 3 and not s.in_flight and s.backlog() == 8
    assert s.published_step == 3  # seen into the store, and published
    # one batch a call where less than a whole one waits behind it
    assert s.pump(device_busy=True) == 0 and len(s._in_flight) == 2
    assert s.pump(device_busy=True) == 2 and not s._in_flight
    assert s.backlog() == 0 and s.stream_step == 5
    # with a scorer in flight committed is not yet published: that follows
    # the writer's rename, which such a call does not wait for
    while s.in_flight:
        s.pump(device_busy=True)
    assert s.published_step == 5
