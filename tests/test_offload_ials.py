"""Out-of-core iALS/iALS++ at the host_window tier (ISSUE 19).

The contracts under test:

- bit-exactness: the windowed bucketed driver reproduces the resident
  trainer crc-for-crc across staging dtypes, hot-cache settings, window
  sizes, and shard counts — offload is a memory plan, never a math change.
- the global-Gram reservation is carved out of the device budget BEFORE
  the window split, and an infeasible budget refuses loudly, naming the
  Gram accumulator reserve.
- streaming fold-in against an out-of-core movie table is bit-identical
  to the device-resident fold and to a direct batch solve of the touched
  rows' normal equations; the session-level commit protocol (atomic
  cursor+factors, crash replay) is unchanged by the offload table.
- quality: quantized staging costs at most 2% held-out RMSE against the
  resident float32 model on a planted implicit split.
- plan layer: bucketed × host_window resolves for implicit configs (the
  pre-ISSUE-19 wart), stays refused for explicit ALS, and the autotune
  cache digest rotated so stale winners read as misses.
"""

import zlib

import numpy as np
import pytest

from cfk_tpu.data.blocks import Dataset, RatingsCOO
from cfk_tpu.data.synthetic import synthetic_netflix_coo
from cfk_tpu.models.ials import IALSConfig, train_ials
from cfk_tpu.offload.windowed import train_ials_host_window
from cfk_tpu.utils.metrics import Metrics


def _crc(model) -> tuple[int, int]:
    return (
        zlib.crc32(np.asarray(model.user_factors, np.float32).tobytes()),
        zlib.crc32(np.asarray(model.movie_factors, np.float32).tobytes()),
    )


def _cfg(**kw) -> IALSConfig:
    kw.setdefault("rank", 4)
    kw.setdefault("num_iterations", 2)
    kw.setdefault("lam", 0.1)
    kw.setdefault("alpha", 40.0)
    kw.setdefault("seed", 0)
    kw.setdefault("layout", "bucketed")
    kw.setdefault("algorithm", "ials++")
    kw.setdefault("block_size", 2)
    return IALSConfig(**kw)


@pytest.fixture(scope="module")
def coo():
    return synthetic_netflix_coo(60, 30, 900, seed=0)


@pytest.fixture(scope="module")
def ds(coo):
    return Dataset.from_coo(coo, layout="bucketed", chunk_elems=512)


@pytest.fixture(scope="module")
def resident(ds):
    """Resident reference models, cached per config override set."""
    cache = {}

    def get(**kw):
        key = tuple(sorted(kw.items()))
        if key not in cache:
            cache[key] = train_ials(ds, _cfg(**kw))
        return cache[key]

    return get


# --- crc-pinned parity matrix ------------------------------------------------


@pytest.mark.parametrize(
    "table_dtype,hot_rows",
    [
        ("float32", 0),
        ("float32", None),
        # each non-f32 staging dtype compiles its own jit family
        # (~10-15 s); tier-1 keeps the f32 pair under the suite's
        # wall-clock budget (int8 staging still runs in tier-1 through
        # the RMSE-contract test below) and the slow tier fills in the
        # quantized crc pins
        pytest.param("bfloat16", 0, marks=pytest.mark.slow),
        pytest.param("int8", None, marks=pytest.mark.slow),
    ],
)
def test_windowed_bit_exact_vs_resident(ds, resident, table_dtype, hot_rows):
    """resident × windowed parity across staging dtype and hot cache:
    the staged table view (quantized or not, hot partition or not) feeds
    the SAME subspace sweeps, so factors come out crc-identical."""
    cfg = _cfg(table_dtype=table_dtype, offload_tier="host_window")
    metrics = Metrics()
    model = train_ials_host_window(
        ds, cfg, metrics=metrics, chunks_per_window=2, hot_rows=hot_rows
    )
    assert _crc(model) == _crc(resident(table_dtype=table_dtype))
    # the Gram reduction ran device-side over staged blocks, and windows
    # actually streamed (this was not a degenerate single-window run)
    assert metrics.gauges.get("offload_gram_staged_mb", 0) > 0
    assert metrics.gauges.get("offload_gram_reserved_mb", 0) > 0
    assert metrics.gauges.get("offload_windows_m", 0) >= 1
    assert metrics.gauges.get("offload_windows_u", 0) >= 1
    if hot_rows == 0:
        assert metrics.gauges.get("offload_hot_rows", 0) == 0


def test_windowed_bit_exact_across_window_sizes(ds, resident):
    """Window cuts are a staging decision only: 1 chunk per window and 8
    chunks per window both reproduce the resident bits."""
    want = _crc(resident())
    for cpw in (1, 8):
        model = train_ials_host_window(
            ds, _cfg(offload_tier="host_window"), metrics=Metrics(),
            chunks_per_window=cpw,
        )
        assert _crc(model) == want, f"chunks_per_window={cpw}"


# Full-rank blocks (block_size == rank) are where windowed and resident part
# in the last bits.  The windowed driver compiles each width-class window as
# its own program, and so does the resident trainer's STEPPED loop (a
# checkpoint manager forces it): those agree to the bit.  The resident
# FUSED loop is one XLA:CPU program over both halves and every iteration,
# and there the compiler orders the sweep's float32 sums differently (the
# block's column slice is the whole row, so the slices fold away).  After
# this shape's two iterations, on factors up to 3.6 in magnitude: 4.8e-7
# absolute on the user side, 1.5e-8 on the movie side (measured, jax
# 0.9.0; 2.6e-6 after three iterations).  The bound is 8 x the larger.
_FULL_RANK_BLOCK_ATOL = 4e-6


@pytest.mark.parametrize("resident_loop", ["fused", "stepped"])
def test_windowed_vs_resident_at_full_rank_blocks(tmp_path, resident_loop):
    """iALS++ at 120 x 40 x 900, rank 4 = block_size, chunk_elems 512, two
    iterations, two chunks a window (where ROADMAP D0's fifth red test
    failed): the windowed driver is deterministic to the bit and
    matches either resident loop to ``_FULL_RANK_BLOCK_ATOL``."""
    from cfk_tpu.data.synth import synth_coo
    from cfk_tpu.transport.checkpoint import CheckpointManager

    ds = Dataset.from_coo(synth_coo(120, 40, 900, seed=0),
                          layout="bucketed", chunk_elems=512)
    cfg = _cfg(block_size=4, solver="cholesky")
    manager = (CheckpointManager(str(tmp_path))
               if resident_loop == "stepped" else None)
    want = train_ials(ds, cfg, checkpoint_manager=manager)
    win_cfg = _cfg(block_size=4, solver="cholesky",
                   offload_tier="host_window")
    metrics = Metrics()
    got = train_ials_host_window(ds, win_cfg, metrics=metrics,
                                 chunks_per_window=2)
    again = train_ials_host_window(ds, win_cfg, metrics=Metrics(),
                                   chunks_per_window=2)
    assert _crc(got) == _crc(again)  # one program twice
    for side in ("user_factors", "movie_factors"):
        np.testing.assert_allclose(
            np.asarray(getattr(got, side), np.float32),
            np.asarray(getattr(want, side), np.float32),
            rtol=0, atol=_FULL_RANK_BLOCK_ATOL,
        )
    assert metrics.gauges.get("offload_windows_m", 0) >= 1
    assert metrics.gauges.get("offload_windows_u", 0) >= 1
    assert metrics.gauges.get("offload_staged_mb", 0) > 0
    assert metrics.gauges.get("offload_gram_staged_mb", 0) > 0
    assert metrics.gauges.get("offload_gram_reserved_mb", 0) > 0


@pytest.mark.slow
def test_windowed_plain_ials_algorithm_bit_exact(ds, resident):
    """algorithm='als' (full-rank sweeps, no subspace blocks) rides the
    same windowed driver and stays bit-exact too.  slow: the full-rank
    bucketed half compiles its own jit family (~8 s) and shares all the
    driver seams the ials++ tier-1 pins already cover."""
    model = train_ials_host_window(
        ds, _cfg(algorithm="als", offload_tier="host_window"),
        metrics=Metrics(), chunks_per_window=2,
    )
    assert _crc(model) == _crc(resident(algorithm="als"))


def test_windowed_two_shard_matches_single_shard_resident(coo, resident):
    """2-shard bucketed windowed run: bit-deterministic across runs, and
    the prediction matrix matches the 1-shard resident model to float32
    round-off.  (Width classes cut per shard, so the in-kernel reduction
    order — and hence the exact bits — can shift with shard count; the
    bitwise contract holds at fixed shard count, the numerical one
    across shard counts.)"""
    ds2 = Dataset.from_coo(coo, num_shards=2, layout="bucketed",
                           chunk_elems=512)
    cfg = _cfg(num_shards=2, offload_tier="host_window")
    m_a = train_ials_host_window(ds2, cfg, metrics=Metrics(),
                                 chunks_per_window=2)
    m_b = train_ials_host_window(ds2, cfg, metrics=Metrics(),
                                 chunks_per_window=2)
    assert _crc(m_a) == _crc(m_b)
    np.testing.assert_allclose(
        m_a.predict_dense(), resident().predict_dense(),
        atol=1e-4, rtol=1e-3,
    )


# --- budget: the Gram reservation term ---------------------------------------


def test_gram_budget_refusal_names_the_reserve(ds):
    """An infeasible device budget refuses loudly BEFORE training and the
    message names the global-Gram accumulator reserve in MB."""
    with pytest.raises(ValueError, match="global-Gram accumulator") as ei:
        train_ials_host_window(
            ds, _cfg(offload_tier="host_window"), metrics=Metrics(),
            device_budget_bytes=64_000,
        )
    assert "MB global-Gram accumulator" in str(ei.value)


# --- streaming fold-in against the out-of-core table -------------------------


def _expected_rows(state, rows, m_host, lam):
    k = m_host.shape[1]
    out = np.zeros((len(rows), k), np.float32)
    for i, row in enumerate(rows):
        mv, rt = state.neighbors(row)
        f = m_host[mv]
        a = f.T @ f + lam * max(len(mv), 1) * np.eye(k, dtype=np.float32)
        out[i] = np.linalg.solve(a, f.T @ rt)
    return out


def test_fold_in_windowed_bit_exact_and_solve_parity(coo):
    """fold_in_rows_windowed stages the touched movie rows as ONE ad-hoc
    window from a HostFactorStore and reproduces the device-resident fold
    bit-for-bit — and both match the direct batch solve."""
    import jax.numpy as jnp

    from cfk_tpu.offload.store import HostFactorStore
    from cfk_tpu.streaming import StreamState
    from cfk_tpu.streaming.foldin import fold_in_rows, fold_in_rows_windowed

    ds_pad = Dataset.from_coo(coo)
    state = StreamState(ds_pad)
    rng = np.random.default_rng(0)
    m_host = rng.standard_normal(
        (ds_pad.movie_blocks.padded_entities, 4)
    ).astype(np.float32)
    rows = [0, 3, 17, 25]
    neighbor_data = [state.neighbors(r) for r in rows]
    res = fold_in_rows(jnp.asarray(m_host), neighbor_data, lam=0.05,
                       solver="cholesky")
    stats = {}
    win, staged = fold_in_rows_windowed(
        HostFactorStore.from_array(m_host), neighbor_data, lam=0.05,
        solver="cholesky", stats=stats, return_staged=True,
    )
    np.testing.assert_array_equal(np.asarray(res), np.asarray(win))
    np.testing.assert_allclose(
        np.asarray(win), _expected_rows(state, rows, m_host, 0.05),
        atol=2e-4, rtol=1e-4,
    )
    # the ad-hoc window covers the unique touched movie rows, pow2-padded
    touched = np.unique(np.concatenate([mv for mv, _ in neighbor_data]))
    n = int(np.asarray(staged).shape[0])
    assert n >= len(touched) and (n & (n - 1)) == 0
    assert stats["foldin_windows_staged"] == 1
    assert stats["foldin_staged_bytes"] > 0


def test_streaming_offload_session_parity_and_crash_replay(tmp_path):
    """StreamSession over an out-of-core table: same factors as the
    resident session (lam pinned — ALSConfig defaults 0.05, IALSConfig
    0.1), fold-in staging gauges recorded, and the atomic cursor+factors
    crash-replay contract reaches bit-equal crc on resume."""
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.models.als import train_als
    from cfk_tpu.streaming import StreamConfig, StreamProducer, StreamSession
    from cfk_tpu.transport import CheckpointManager, InMemoryBroker

    ds_pad = Dataset.from_coo(synthetic_netflix_coo(60, 30, 900, seed=0))
    cfg_res = ALSConfig(rank=4, num_iterations=4, health_check_every=1)
    base = train_als(ds_pad, cfg_res)
    cfg_off = IALSConfig(rank=4, num_iterations=4, health_check_every=1,
                         lam=0.05, layout="bucketed",
                         offload_tier="host_window")
    broker = InMemoryBroker()
    prod = StreamProducer(broker, num_partitions=2)
    rng = np.random.default_rng(7)
    prod.send_many(
        rng.choice(ds_pad.user_map.raw_ids, 60),
        rng.choice(ds_pad.movie_map.raw_ids, 60),
        rng.integers(1, 6, 60).astype(np.float32),
    )

    def run(cfg, name, base_model, max_batches=None):
        sess = StreamSession(
            ds_pad, cfg, broker, CheckpointManager(str(tmp_path / name)),
            stream=StreamConfig(batch_records=8), base_model=base_model,
        )
        return sess, sess.run(max_batches=max_batches)

    _, m_res = run(cfg_res, "res", base)
    s_off, m_off = run(cfg_off, "off", base)
    assert _crc(m_off) == _crc(m_res)
    assert s_off.metrics.gauges.get("foldin_windows_staged", 0) > 0
    assert s_off.metrics.gauges.get("foldin_staged_mb", 0) > 0
    # crash after 3 batches; a fresh process resumes from the committed
    # cursor+factors step (no base_model) and lands on the same bits
    s1, _ = run(cfg_off, "cr", base, max_batches=3)
    del s1
    s2 = StreamSession(
        ds_pad, cfg_off, broker, CheckpointManager(str(tmp_path / "cr")),
        stream=StreamConfig(batch_records=8),
    )
    m_rep = s2.run()
    assert s2.metrics.counters.get("restored_cells", 0) > 0
    assert _crc(m_rep) == _crc(m_off)


# --- quality: planted held-out RMSE contract ---------------------------------


def _planted_implicit(users=64, movies=32, nnz=1600, rank=4, held=400,
                      seed=0):
    """Planted NON-NEGATIVE factor model: iALS needs ratings that read as
    interaction strengths, so factors are folded positive and ratings
    clipped above zero (planted_factor_coo generates signed ratings)."""
    rng = np.random.default_rng(seed)
    u = np.abs(rng.standard_normal((users, rank))).astype(np.float32) + 0.1
    m = np.abs(rng.standard_normal((movies, rank))).astype(np.float32) + 0.1
    total = nnz + held
    ui = rng.integers(0, users, total)
    mi = rng.integers(0, movies, total)
    r = (np.einsum("nk,nk->n", u[ui], m[mi])
         + 0.05 * rng.standard_normal(total)).astype(np.float32)
    r = np.maximum(r, 0.05).astype(np.float32)
    key = ui.astype(np.int64) * movies + mi
    _, first = np.unique(key[:nnz], return_index=True)
    tr = np.sort(first)
    fresh = ~np.isin(key[nnz:], key[:nnz][tr])
    train = RatingsCOO(movie_raw=(mi[:nnz][tr] + 1).astype(np.int64),
                       user_raw=(ui[:nnz][tr] + 1).astype(np.int64),
                       rating=r[:nnz][tr])
    heldout = RatingsCOO(movie_raw=(mi[nnz:][fresh] + 1).astype(np.int64),
                         user_raw=(ui[nnz:][fresh] + 1).astype(np.int64),
                         rating=r[nnz:][fresh])
    return train, heldout


def test_quantized_offload_rmse_contract_on_planted_heldout():
    """int8 table staging may perturb bits (unlike f32, which is
    crc-identical) but must cost at most 2% held-out RMSE against the
    resident float32 model on a planted implicit split."""
    from cfk_tpu.eval.metrics import mse_rmse_heldout

    train, held = _planted_implicit()
    ds_p = Dataset.from_coo(train, layout="bucketed", chunk_elems=512)
    res = train_ials(ds_p, _cfg(num_iterations=5))
    off = train_ials_host_window(
        ds_p, _cfg(num_iterations=5, table_dtype="int8",
                   offload_tier="host_window"),
        metrics=Metrics(), chunks_per_window=2,
    )
    _, rmse_res, n_res = mse_rmse_heldout(res, ds_p, held)
    _, rmse_off, n_off = mse_rmse_heldout(off, ds_p, held)
    assert n_res == n_off and n_res > 0
    assert rmse_off <= 1.02 * rmse_res, (rmse_off, rmse_res)


# --- plan layer: the resolvability wart and the rotated cache digest ---------


def test_plan_bucketed_host_window_resolves_for_implicit():
    from cfk_tpu.plan import plan_for_config

    cfg = _cfg(offload_tier="host_window")
    plan, prov = plan_for_config(
        cfg, num_users=2_400, num_movies=240, nnz=48_000, implicit=True
    )
    assert plan.offload_tier == "host_window"
    assert plan.layout == "bucketed"


def test_config_gates_explicit_vs_implicit_host_window():
    from cfk_tpu.config import ALSConfig

    # implicit: bucketed × host_window is first-class now
    _cfg(offload_tier="host_window")
    # implicit host_window streams width classes, not padded rows
    with pytest.raises(ValueError, match="bucketed"):
        _cfg(layout="padded", offload_tier="host_window")
    # explicit ALS host_window remains tiled-only
    with pytest.raises(ValueError, match="tiled"):
        ALSConfig(rank=4, layout="bucketed", offload_tier="host_window")


def test_autotune_cache_digest_rotated_with_fieldset_version():
    """PLAN_FIELDSET_VERSION folded into the cache digest: winners tuned
    under the pre-ISSUE-19 feasible set (bucketed × host_window refused)
    must read as misses, so the unversioned legacy tag must NOT appear."""
    from cfk_tpu.plan import DeviceSpec
    from cfk_tpu.plan.autotune import cache_key
    from cfk_tpu.plan.resolver import shape_for_config
    from cfk_tpu.plan.spec import PLAN_FIELDS, PLAN_FIELDSET_VERSION

    assert PLAN_FIELDSET_VERSION >= 2
    shape = shape_for_config(
        _cfg(), num_users=2_400, num_movies=240, nnz=48_000, implicit=True
    )
    key = cache_key(shape, DeviceSpec.detect())
    joined = "|".join(sorted(PLAN_FIELDS))
    tag_now = zlib.crc32(f"v{PLAN_FIELDSET_VERSION}|{joined}".encode())
    tag_legacy = zlib.crc32(joined.encode())
    assert f"p{tag_now:08x}" in key
    assert f"p{tag_legacy:08x}" not in key
