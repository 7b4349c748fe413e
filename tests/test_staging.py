"""Host staging engine + donation budgets + warm-start caching (ISSUE 13).

Four groups:

- ``WindowStager`` units: in-order delivery under out-of-order worker
  completion, depth bounding, worker-exception propagation (the
  no-hang contract), serial-mode schedule equivalence, stats accounting;
- donation-aware budget arithmetic: the ring accumulator reservation
  ×2→×1, the staging-arena depth clamp, and the resident-tier
  solve-output credit (a shape refused only by the un-donated
  arithmetic fits with donation on — the default, because the trainers
  really donate);
- prewarm: ``ServeEngine.prewarm`` / ``StreamSession.prewarm`` trace the
  pow2 bucket set up front, pinned by ZERO new traces on the first real
  batch afterwards;
- ``enable_compile_cache``: the persistent-cache directory rules (the
  environment's directory wins, else one fixed directory), populated by a
  compile.
"""

import threading
import time

import numpy as np
import pytest

import jax

from cfk_tpu.offload import budget as _budget
from cfk_tpu.offload.staging import (
    StagingStats,
    WindowStager,
    pool_workers_for,
    resolve_staging,
    stats_add,
)


# --- WindowStager units ------------------------------------------------------


def test_pool_preserves_order_under_out_of_order_completion():
    # Workers finish out of order (earlier tasks sleep longer); take()
    # must still deliver task order — the consumption order IS the
    # bit-exactness contract.
    tasks = [(0, w) for w in range(6)] + [(1, w) for w in range(6)]
    delays = {0: 0.02, 1: 0.001, 2: 0.015, 3: 0.0, 4: 0.01, 5: 0.002}

    def stage(shard, w):
        time.sleep(delays[w])
        return (shard, w, threading.current_thread().name)

    stats = StagingStats()
    st = WindowStager(tasks, stage, mode="pool", depth=4, stats=stats)
    try:
        got = [st.take() for _ in range(len(tasks))]
    finally:
        st.close()
    assert [(s, w) for s, w, _ in got] == tasks
    # The staging really ran on pool workers, concurrently.
    assert all(name.startswith("cfk-stage") for _, _, name in got)
    assert stats["pool_peak_inflight"] >= 2
    assert stats["pool_worker_stagings"] == len(tasks)
    assert stats["stage_busy_s"] > 0


def test_pool_depth_bounds_lookahead():
    # With depth D, no more than D tasks may have STARTED beyond the
    # consumption cursor (the staging-arena bound the budget charges).
    started = []
    release = threading.Event()

    def stage(shard, w):
        started.append(w)
        release.wait(2.0)
        return w

    st = WindowStager([(0, w) for w in range(8)], stage, mode="pool",
                      depth=2, workers=2)
    try:
        time.sleep(0.1)
        assert len(started) <= 2  # nothing consumed yet: D in flight max
        release.set()
        out = [st.take() for _ in range(8)]
        assert out == list(range(8))
    finally:
        release.set()
        st.close()


def test_worker_exception_propagates_not_hangs():
    # The no-hang contract: an exception inside a worker re-raises from
    # take() (as the staging error), and the stager cancels the rest.
    def stage(shard, w):
        if w == 2:
            raise RuntimeError("boom in worker")
        return w

    st = WindowStager([(0, w) for w in range(6)], stage, mode="pool",
                      depth=4)
    assert st.take() == 0
    assert st.take() == 1
    with pytest.raises(RuntimeError, match="boom in worker"):
        st.take()
    st.close()  # idempotent after the error path already closed


def test_serial_mode_runs_on_caller_thread_in_order():
    seen = []

    def stage(shard, w):
        seen.append((shard, w, threading.current_thread().name))
        return w

    st = WindowStager([(0, 0), (0, 1)], stage, mode="serial")
    assert st.take() == 0
    # serial stages lazily, on demand, on the consuming thread — the
    # classic double-buffer position (stage w+1 after dispatching w)
    assert len(seen) == 1
    assert st.take() == 1
    assert all(t == threading.current_thread().name for _, _, t in seen)
    st.close()


def test_stats_add_is_thread_safe_on_staging_stats():
    stats = StagingStats()

    def bump():
        for _ in range(2000):
            stats_add(stats, "n", 1)

    ts = [threading.Thread(target=bump) for _ in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert stats["n"] == 8000


def test_resolve_staging_and_workers():
    assert resolve_staging(None) == "pool"
    assert resolve_staging("auto") == "pool"
    assert resolve_staging("serial") == "serial"
    with pytest.raises(ValueError, match="staging"):
        resolve_staging("turbo")
    assert pool_workers_for(1) == 1
    assert pool_workers_for(8) == 4  # MAX_POOL_WORKERS cap
    assert pool_workers_for(8, workers=2) == 2
    assert pool_workers_for(2, workers=9) == 2  # never more than depth


# --- donation-aware budgets --------------------------------------------------


def test_ring_accumulator_reservation_donation_credit():
    # Donated (the _ring_window_jit donate_argnums reality): ×1.
    # Un-donated (the PR 11 dispatch-boundary accounting): ×2.
    one = _budget.ring_accumulator_reservation(100, 8, donated=True)
    two = _budget.ring_accumulator_reservation(100, 8, donated=False)
    assert one == _budget.ring_accumulator_bytes(100, 8)
    assert two == 2 * one


def test_window_sizing_admitted_by_donation_credit():
    # A budget that fits the window next to the ×1 reservation but NOT
    # next to the ×2 one: the shape was refused before donation (PR 11
    # arithmetic), and is admitted now — the ISSUE 13 reclaim, in the
    # exact arithmetic the driver runs.
    acc = _budget.ring_accumulator_bytes(5000, 32)
    worst = acc  # a window as big as one accumulator copy
    hbm = (2 * worst + 1.5 * acc) / _budget.RESIDENT_FRACTION
    ok_donated = _budget.window_budget_bytes(
        hbm, reserved_bytes=_budget.ring_accumulator_reservation(
            5000, 32, donated=True)
    )
    ok_undonated = _budget.window_budget_bytes(
        hbm, reserved_bytes=_budget.ring_accumulator_reservation(
            5000, 32, donated=False)
    )
    assert worst <= ok_donated      # fits with donation on (today)
    assert worst > ok_undonated     # was refused at the ×2 reservation


def test_max_pool_depth_staging_arena():
    # depth+1 worst windows must fit the share; floor of 1 (the classic
    # double buffer's footprint).
    hbm = 100.0 / _budget.RESIDENT_FRACTION  # share == 100
    assert _budget.max_pool_depth(hbm, worst_window_bytes=20.0) == 4
    assert _budget.max_pool_depth(hbm, worst_window_bytes=40.0) == 1
    assert _budget.max_pool_depth(hbm, worst_window_bytes=1e9) == 1
    assert _budget.max_pool_depth(hbm, 20.0, reserved_bytes=60.0) == 1


def test_resident_solve_output_donation_credit():
    # donation=True (the default — the trainers donate their factor
    # args) reproduces the pre-ISSUE-13 totals exactly; donation=False
    # charges the un-donated solve-side output.
    kw = dict(dtype="float32", table_dtype="int8", num_shards=2)
    don = _budget.train_resident_bytes(10_000, 800, 100_000, 64, **kw)
    und = _budget.train_resident_bytes(10_000, 800, 100_000, 64,
                                       donation=False, **kw)
    assert don["solve_output_bytes"] == 0.0
    assert und["solve_output_bytes"] == 10_000 * 64 * 4 / 2
    assert und["total"] == don["total"] + und["solve_output_bytes"]
    # A budget in the band between the two totals: fits ONLY because of
    # the donation credit — the sweep rows record exactly this
    # (fits_device_without_donation=False on a tier=device point).
    hbm = (don["total"] + und["total"]) / 2 / _budget.RESIDENT_FRACTION
    assert _budget.fits_device(10_000, 800, 100_000, 64, hbm_bytes=hbm,
                               **kw)
    assert not _budget.fits_device(10_000, 800, 100_000, 64,
                                   hbm_bytes=hbm, donation=False, **kw)


# --- prewarm: zero traces on the first real batch ---------------------------


def test_serve_engine_prewarm_pins_zero_new_traces():
    from cfk_tpu.serving.engine import ServeEngine

    rng = np.random.default_rng(0)
    eng = ServeEngine(
        rng.standard_normal((50, 8)).astype(np.float32),
        rng.standard_normal((64, 8)).astype(np.float32),
        num_users=50, num_movies=60, tile_m=16, batch_quantum=4,
    )
    warm = eng.prewarm(3, max_batch=16)
    assert warm["programs"] == 3  # buckets 4, 8, 16
    assert warm["new_traces"] >= 1
    # First REAL batches inside the warmed buckets: zero new traces.
    before = eng.trace_count
    eng.topk(np.array([1, 2, 3]), 3)          # pads to 4
    eng.topk(np.arange(5), 3)                  # pads to 8
    eng.topk(np.arange(11), 3)                 # pads to 16
    assert eng.trace_count - before == 0
    # A bucket outside the warmed ladder still traces (the counter is
    # live, not a stub).
    eng.topk(np.arange(17), 3)                 # pads to 32
    assert eng.trace_count - before == 1


@pytest.mark.parametrize("pieces", [1, 3, 9, 17])
def test_serve_engine_prewarm_closes_the_seen_scatter_programs(
        pieces, monkeypatch):
    # the exclusion rectangle's programs are keyed by the padded batch size
    # (a piece of the cell list is a function of it alone) and by the rung
    # of pieces the list is padded to, so the ladder, walked over the rungs,
    # covers a batch of every size AND a batch of one, three, nine pieces
    # (rungs 1, 4, 16: one run of one program) or seventeen (the top rung's
    # program, and again on its own result), whatever seen lists the sample
    # happened to hold
    from cfk_tpu.serving import engine as engine_mod

    rng = np.random.default_rng(0)
    users, movies = 40, 400
    lists = [np.sort(rng.choice(movies, int(rng.integers(0, 5)),
                                replace=False)) for _ in range(users)]
    # a batch of b such rows holds b x (16 x pieces - 8) cells against
    # pieces of 16 x b: `pieces` of them at every batch size
    lists[7] = np.sort(rng.choice(movies, 16 * pieces - 8, replace=False))
    indptr = np.zeros(users + 1, np.int64)
    indptr[1:] = np.cumsum([x.size for x in lists])
    eng = engine_mod.ServeEngine(
        rng.standard_normal((users, 8)).astype(np.float32),
        rng.standard_normal((movies, 8)).astype(np.float32),
        num_users=users, num_movies=movies,
        seen_movies=np.concatenate(lists).astype(np.int32),
        seen_indptr=indptr, tile_m=16, batch_quantum=4,
    )
    # the sample holds none of the heavy user's rows
    warm = eng.prewarm(3, max_batch=16, user_rows=np.arange(20, 36))
    assert warm["programs"] == 3  # buckets 4, 8, 16
    # per bucket at most: the scorer, the scatter that starts a rectangle at
    # each of the five rungs and the top rung's that adds to one (fewer
    # where an earlier test of this process traced the same shapes: the
    # counter is process-wide)
    assert warm["new_traces"] <= 21
    before = eng.trace_count
    chunks = []
    real = engine_mod._seen_chunks
    monkeypatch.setattr(
        engine_mod, "_seen_chunks",
        lambda *a: chunks.append(real(*a)) or chunks[-1])
    for n in (3, 7, 13):  # a batch of every size
        eng.topk(np.arange(n), 3)
    for n in (4, 8, 16, 3):
        vals, ids = eng.topk(np.full(n, 7), 3)
        assert not set(ids.ravel().tolist()) & set(lists[7].tolist())
    # ... and the last, three such rows padded to four, of fewer
    held = [-(-n * lists[7].size // (16 * b))
            for n, b in ((4, 4), (8, 8), (16, 16), (3, 4))]
    assert held[:3] == [pieces] * 3
    assert [len(c) for c in chunks] == [1, 1, 1] + [-(-p // 16) for p in held]
    assert [c[0].shape[1] for c in chunks[3:]] == [
        engine_mod.seen_piece_rung(p) * 16 * b
        for p, b in zip(held, (4, 8, 16, 4))]
    assert eng.trace_count - before == 0


def test_stream_session_prewarm_pins_zero_new_traces(tmp_path):
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.models.als import train_als
    from cfk_tpu.streaming import (
        StreamConfig,
        StreamProducer,
        StreamSession,
    )
    from cfk_tpu.streaming.foldin import trace_count
    from cfk_tpu.transport import InMemoryBroker
    from cfk_tpu.transport.checkpoint import CheckpointManager

    ds = Dataset.from_coo(synthetic_netflix_coo(30, 12, 260, seed=0))
    cfg = ALSConfig(rank=4, num_iterations=2, health_check_every=1)
    base = train_als(ds, cfg)
    broker = InMemoryBroker()
    prod = StreamProducer(broker)
    rng = np.random.default_rng(1)
    n = 24
    prod.send_many(
        rng.choice(ds.user_map.raw_ids, n),
        rng.choice(ds.movie_map.raw_ids, n),
        rng.integers(1, 6, n).astype(np.float32),
    )
    sess = StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path)),
        stream=StreamConfig(batch_records=16), base_model=base,
    )
    warm = sess.prewarm(max_touched=16)
    assert warm["programs"] >= 1
    before = trace_count()
    got = sess.step()  # the first REAL micro-batch
    assert got is not None and got["records"] >= 1
    assert trace_count() - before == 0, \
        "first real fold-in batch re-traced after prewarm"


# --- compile cache -----------------------------------------------------------


def test_enable_compile_cache_directory_rules(tmp_path, monkeypatch):
    """The environment's directory wins and is never overridden in code;
    without it the flag/config directory, else ONE fixed in-checkout
    default — no per-device sub-directory, no temp name; an unusable path
    raises."""
    import os

    from cfk_tpu import config as cfg

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cfg, "DEFAULT_COMPILE_CACHE_DIR",
                        str(tmp_path / "default"))
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_compilation_cache", True)  # conftest: off
    try:
        assert cfg.enable_compile_cache() == str(tmp_path / "default")
        assert cfg.enable_compile_cache() == str(tmp_path / "default")
        # ... and it is in use, on whatever backend this is (the CPU here)
        assert jax.config.jax_compilation_cache_dir == str(
            tmp_path / "default")
        assert os.path.isdir(tmp_path / "default")
        sub = cfg.enable_compile_cache(str(tmp_path / "flag"))
        assert sub == str(tmp_path / "flag") and os.path.isdir(sub)
        assert jax.config.jax_compilation_cache_dir == sub

        # a fresh compile lands directly in that directory
        @jax.jit
        def f(x):
            return (x * 2.0 + 1.0).sum()

        f(jax.numpy.arange(1333.0)).block_until_ready()
        assert any("-cache" in name for name in os.listdir(sub))

        # with the variable set, nothing here names another directory
        env_dir = str(tmp_path / "env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert cfg.enable_compile_cache(str(tmp_path / "other")) == env_dir
        assert jax.config.jax_compilation_cache_dir == sub  # untouched
        assert not os.path.exists(tmp_path / "other")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")

        # a path that cannot be used is an error, not a warning
        blocker = tmp_path / "file"
        blocker.write_text("")
        with pytest.raises(OSError):
            cfg.enable_compile_cache(str(blocker / "sub"))
    finally:
        # restore: later tests must not inherit the cache dir
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_enable_compilation_cache", was[1])
