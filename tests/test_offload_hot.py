"""Skew-aware hot-row device cache + delta staging (ISSUE 15).

The contracts: (1) plan-time classification is deterministic arithmetic
over the window plans' own row sets (reference counts, coverage curve,
knee — pinned on the counter-based synth generator, whose skew is
reproducible by construction); (2) every window's row set reconstructs
exactly from its hot / kept / delta split; (3) ``hot_rows=0`` is
PROVABLY the PR 12 engine (the delta staging path and the assembly jits
never run); (4) hot on ≡ hot off ≡ resident, crc-identical, across
dtype × shards × exchange; (5) the budget predicate refuses impossible
reservations loudly at BOTH the resolver and the executor, and the
resolver assigns a nonzero hot fraction only when the reservation fits.
"""

import dataclasses
import zlib

import numpy as np
import pytest

from cfk_tpu.config import ALSConfig
from cfk_tpu.data.blocks import Dataset
from cfk_tpu.data.synth import PowerLawSynth, SynthSpec, synth_coo
from cfk_tpu.models.als import train_als
from cfk_tpu.offload import budget as _budget
from cfk_tpu.offload import hot
from cfk_tpu.offload import windowed as _windowed
from cfk_tpu.offload.window import build_window_plan
from cfk_tpu.offload.windowed import train_als_host_window
from cfk_tpu.utils.metrics import Metrics


def _crc(model):
    return zlib.crc32(np.asarray(model.user_factors, np.float32).tobytes())


@pytest.fixture(scope="module")
def synth_plan():
    """The pinned classification workload: a counter-based power-law
    corpus cut into 6 movie-side windows (deterministic by construction
    — chunking and seeds fix every row set bit-for-bit)."""
    coo = PowerLawSynth(
        SynthSpec(num_users=300, num_movies=80, nnz=6000, seed=7)
    ).coo()
    ds = Dataset.from_coo(coo, layout="tiled", chunk_elems=256,
                          tile_rows=16, accum_max_entities=0)
    plan = build_window_plan(ds.movie_blocks,
                             ds.user_blocks.padded_entities,
                             chunks_per_window=1)
    return ds, plan


@pytest.fixture(scope="module")
def stream_ds():
    return Dataset.from_coo(
        synth_coo(60, 30, 900, seed=0), layout="tiled", chunk_elems=512,
        tile_rows=16, accum_max_entities=0,
    )


# --- plan-time classification ----------------------------------------------


def test_reference_counts_hand_built():
    # Two fake windows over a 10-row table: counts are per-window set
    # membership (repeats within a window count once — the row set is
    # already unique).
    class P:
        rows = np.array([[2, 5, 7, 0], [5, 7, 9, 0]])
        row_counts = np.array([3, 3])
        num_windows = 2

    counts = hot.reference_counts([P()], 10)
    assert counts.tolist() == [0, 0, 1, 0, 0, 2, 0, 2, 0, 1]
    order, cov = hot.coverage_curve(counts)
    # Hottest first, ties toward the lower row id.
    assert order.tolist() == [5, 7, 2, 9]
    np.testing.assert_allclose(cov, [2 / 6, 4 / 6, 5 / 6, 1.0])
    assert hot.select_hot_rows(counts, 2).tolist() == [5, 7]


def test_knee_is_zero_on_uniform_counts():
    # A flat curve IS the diagonal: residency buys nothing, knee = 0.
    counts = np.ones(32, dtype=np.int64)
    assert hot.knee_hot_rows(counts) == 0


def test_coverage_curve_pinned_on_synth(synth_plan):
    # The coverage-vs-f curve is deterministic by construction on the
    # counter-based generator — pin the knee and its coverage so a
    # change in classification arithmetic (or in the generator) is loud.
    _, plan = synth_plan
    counts = hot.reference_counts([plan], plan.table_rows)
    order, cov = hot.coverage_curve(counts)
    assert plan.num_windows == 6
    assert order.size == 299
    assert int(counts.sum()) == 1277
    knee = hot.knee_hot_rows(counts)
    assert knee == 126
    assert round(float(cov[knee - 1]), 6) == 0.523884
    # The head is genuinely hot: top rows appear in every window.
    assert counts[order[0]] == plan.num_windows


def test_delta_sets_reconstruct_every_window(synth_plan):
    # hot ∪ kept ∪ delta positions == the window's full row set, the
    # kept rows really are the predecessor's, and the delta is what's
    # left — per window, in schedule order.
    _, plan = synth_plan
    counts = hot.reference_counts([plan], plan.table_rows)
    hot_rows = hot.select_hot_rows(counts, hot.knee_hot_rows(counts))
    hmap = hot.build_hot_map(plan, plan.schedule(), hot_rows)
    assert (hmap.slots_hot, hmap.slots_kept, hmap.slots_delta) == (
        669, 281, 327
    )
    prev = -1
    for w in plan.schedule():
        c = int(plan.row_counts[w])
        rows_w = plan.rows[w, :c]
        dst_union = np.sort(np.concatenate([
            hmap.hot_dst[w], hmap.keep_dst[w], hmap.delta_dst[w],
        ]))
        assert dst_union.tolist() == list(range(c))  # exact disjoint cover
        # Hot positions hold hot rows, at the right partition index.
        np.testing.assert_array_equal(
            hot_rows[hmap.hot_src[w]], rows_w[hmap.hot_dst[w]]
        )
        if prev >= 0:
            pc = int(plan.row_counts[prev])
            prows = plan.rows[prev, :pc]
            # Kept rows exist in the predecessor at the recorded source.
            np.testing.assert_array_equal(
                prows[hmap.keep_src[w]], rows_w[hmap.keep_dst[w]]
            )
            # Delta rows are NOT in the predecessor (else they'd be kept).
            assert not np.isin(hmap.delta_rows[w], prows).any()
        else:
            assert hmap.keep_dst[w].size == 0  # chain head stages all cold
        np.testing.assert_array_equal(
            hmap.delta_rows[w], rows_w[hmap.delta_dst[w]]
        )
        prev = w
    assert (hmap.slots_total
            == hmap.slots_hot + hmap.slots_kept + hmap.slots_delta)


def test_scatter_back_maps_last_write_wins(synth_plan):
    # The stream scatter-back must pick each entity's LAST finalization
    # slot (the host scatter's winner) and only hot entities.
    _, plan = synth_plan
    local = plan.local_entities
    hot_rows = np.array([3, 7], dtype=np.int64)
    maps = hot.scatter_back_maps(plan, 0, local, hot_rows)
    for w, (src, dst) in maps.items():
        ent = np.asarray(plan.chunk_entity_of(w), dtype=np.int64)
        for s_i, d_i in zip(src, dst):
            assert ent[s_i] == hot_rows[d_i]
            assert (ent[s_i + 1:] != ent[s_i]).all()  # truly the last slot


# --- hot_rows=0 is the PR 12 engine ---------------------------------------


def test_hot_off_is_the_old_engine(stream_ds, monkeypatch):
    # With hot_rows=0 the delta staging path and the assembly jits must
    # NEVER run — the schedule, the staged payloads, and every jit are
    # byte-for-byte the PR 12 engine.
    calls = {"delta": 0, "assemble": 0}
    real_delta = _windowed._stage_window_delta
    real_assemble = _windowed._assemble_jit

    def spy_delta(*a, **k):
        calls["delta"] += 1
        return real_delta(*a, **k)

    def spy_assemble(*a, **k):
        calls["assemble"] += 1
        return real_assemble(*a, **k)

    monkeypatch.setattr(_windowed, "_stage_window_delta", spy_delta)
    monkeypatch.setattr(_windowed, "_assemble_jit", spy_assemble)
    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=0,
                    layout="tiled", solver="cholesky", hbm_chunk_elems=512,
                    hot_rows=0)
    m = Metrics()
    model = train_als_host_window(stream_ds, cfg, chunks_per_window=2,
                                  metrics=m)
    assert calls == {"delta": 0, "assemble": 0}
    assert m.notes.get("offload_hot") == "off"
    assert "offload_hot_resident_mb" not in m.gauges
    assert "offload_rows_delta_skipped" not in m.gauges
    # cold == the whole table share (the PR 12 quantity under its new
    # name), and the run is bit-identical to the resident trainer.
    assert m.gauges["offload_staged_cold_mb"] > 0
    assert _crc(model) == _crc(train_als(stream_ds, cfg))


# --- crc matrix -------------------------------------------------------------


@pytest.mark.parametrize("shards,exchange,table_dtype", [
    (1, "all_gather", "float32"),
    (1, "all_gather", "int8"),
    (2, "ring", "int8"),
])
def test_hot_on_off_resident_crc_identical(shards, exchange, table_dtype):
    coo = synth_coo(60, 30, 900, seed=0)
    build_kw = (dict(ring=True, ring_warn=False)
                if exchange in ("ring", "hier_ring")
                else dict(accum_max_entities=0))
    ds = Dataset.from_coo(coo, num_shards=shards, layout="tiled",
                          chunk_elems=512, tile_rows=16, **build_kw)
    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=3, seed=0,
                    layout="tiled", solver="cholesky", num_shards=shards,
                    exchange=exchange, table_dtype=table_dtype,
                    hbm_chunk_elems=512)
    off = _crc(train_als_host_window(ds, cfg, chunks_per_window=2,
                                     hot_rows=0))
    m = Metrics()
    auto = _crc(train_als_host_window(ds, cfg, chunks_per_window=2,
                                      metrics=m))
    m_pin = Metrics()
    pinned = _crc(train_als_host_window(ds, cfg, chunks_per_window=2,
                                        hot_rows=10, metrics=m_pin))
    assert off == auto == pinned
    assert m.gauges.get("offload_hot_rows", 0) > 0  # auto really cached
    assert 0 < m_pin.gauges["offload_hot_rows"] <= 10  # a pin is a cap
    if shards == 1 and exchange == "all_gather":
        assert off == _crc(train_als(ds, cfg))


def test_hot_cuts_staged_cold_bytes(stream_ds):
    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=0,
                    layout="tiled", solver="cholesky", hbm_chunk_elems=512)
    m_off, m_on = Metrics(), Metrics()
    train_als_host_window(stream_ds, cfg, chunks_per_window=2,
                          metrics=m_off, hot_rows=0)
    train_als_host_window(stream_ds, cfg, chunks_per_window=2,
                          metrics=m_on)
    assert (m_on.gauges["offload_staged_cold_mb"]
            < m_off.gauges["offload_staged_cold_mb"])
    assert m_on.gauges["offload_hot_resident_mb"] > 0
    assert 0 < m_on.gauges["offload_hot_coverage"] <= 1
    assert m_on.gauges["offload_rows_delta_skipped"] >= 0
    # Chunk arrays still cross PCIe either way: the TOTAL staged bytes
    # shrink by exactly the table-share saving, never below the chunks.
    assert (m_on.gauges["offload_staged_mb"]
            < m_off.gauges["offload_staged_mb"])


# --- budget predicate -------------------------------------------------------


def test_budget_hot_terms():
    assert _budget.stage_row_bytes(16, "float32") == 64.0
    assert _budget.stage_row_bytes(16, "bfloat16") == 32.0
    assert _budget.stage_row_bytes(16, "int8") == 20.0  # codes + f32 scale
    assert _budget.hot_reservation_bytes(100, 16, "float32") == 6400.0
    # The executor's exact form: headroom // row bytes.
    hbm = 1e6
    admit = _budget.max_hot_rows(hbm, 16, "float32",
                                 reserved_bytes=0.5e6)
    assert admit == int((hbm * _budget.RESIDENT_FRACTION - 0.5e6) // 64)
    assert _budget.hot_reservation_fits(admit, 16, "float32", hbm,
                                        reserved_bytes=0.5e6)
    assert not _budget.hot_reservation_fits(admit + 1, 16, "float32", hbm,
                                            reserved_bytes=0.5e6)
    # The planner's capped form leaves the window share.
    assert (_budget.max_hot_rows(hbm, 16, "float32")
            == int(hbm * _budget.RESIDENT_FRACTION
                   * _budget.HOT_BUDGET_FRACTION // 64))


def test_pinned_impossible_hot_raises_at_executor(stream_ds):
    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=1, seed=0,
                    layout="tiled", solver="cholesky", hbm_chunk_elems=512)
    with pytest.raises(ValueError, match="hot_rows=1000000 .* exceeds"):
        train_als_host_window(stream_ds, cfg, chunks_per_window=2,
                              hot_rows=1_000_000,
                              device_budget_bytes=2e6)


def test_auto_hot_resolves_off_when_budget_refuses(stream_ds, monkeypatch):
    # AUTO must degrade to the full-staging engine (not raise) when the
    # budget predicate admits zero hot rows — forced deterministically
    # by refusing every reservation (the razor-thin natural band where
    # windows fit but hot does not is shape-dependent; the CLAMP path is
    # what this pins, and the run must stay bit-identical to resident).
    monkeypatch.setattr(_budget, "max_hot_rows", lambda *a, **k: 0)
    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=0,
                    layout="tiled", solver="cholesky", hbm_chunk_elems=512)
    m = Metrics()
    model = train_als_host_window(stream_ds, cfg, chunks_per_window=2,
                                  metrics=m)
    assert m.notes.get("offload_hot") == "off"
    assert "headroom" in m.notes.get("offload_hot_decision", "")
    assert _crc(model) == _crc(train_als(stream_ds, cfg))


# --- resolver integration ---------------------------------------------------


def test_resolver_assigns_hot_only_when_budget_admits():
    from cfk_tpu.plan import DeviceSpec, PlanConstraints, ProblemShape
    from cfk_tpu.plan.resolver import plan

    big = ProblemShape(num_users=10_000_000, num_movies=1_000_000,
                       nnz=1_000_000_000, rank=128)
    v5e = DeviceSpec.nominal("tpu", name="v5e")
    ep, prov = plan(big, v5e)
    assert ep.offload_tier == "host_window"
    assert ep.hot_rows > 0
    assert any(f == "hot_rows" and "admits" in r
               for f, _, r in prov.explain)
    # Same shape, a device whose budget cannot hold even one hot row
    # at the capped share → the axis resolves 0 (refused, not raised).
    tiny = dataclasses.replace(v5e, hbm_bytes=1000.0)
    ep2, prov2 = plan(big, tiny)
    assert ep2.offload_tier == "host_window" and ep2.hot_rows == 0
    assert any(f == "hot_rows" and "refused" in r
               for f, _, r in prov2.explain)
    # A fitting shape stays resident with hot_rows=0.
    small = ProblemShape(num_users=1000, num_movies=500, nnz=20_000,
                         rank=16)
    ep3, _ = plan(small, v5e)
    assert ep3.offload_tier == "device" and ep3.hot_rows == 0


def test_resolver_pinned_impossible_hot_raises():
    from cfk_tpu.plan import DeviceSpec, PlanConstraints, ProblemShape
    from cfk_tpu.plan.resolver import plan
    from cfk_tpu.plan.spec import PlanConstraintError

    big = ProblemShape(num_users=10_000_000, num_movies=1_000_000,
                       nnz=1_000_000_000, rank=128)
    v5e = DeviceSpec.nominal("tpu", name="v5e")
    with pytest.raises(PlanConstraintError, match="hot_rows=.*exceeds"):
        plan(big, v5e, PlanConstraints(hot_rows=1_000_000_000))
    # Pinned 0 stays off on the host_window tier.
    ep, _ = plan(big, v5e, PlanConstraints(hot_rows=0))
    assert ep.offload_tier == "host_window" and ep.hot_rows == 0


def test_hot_update_jit_matches_host_roundtrip():
    # The in-place device scatter-back must produce bitwise the bytes a
    # host round-trip (store write → gather → quantize) would stage —
    # THE invariant that lets hot rows skip the host entirely.
    import jax

    from cfk_tpu.offload.store import HostFactorStore, quantize_rows_host
    from cfk_tpu.offload.windowed import _hot_update_jit

    rng = np.random.default_rng(0)
    xs = rng.standard_normal((12, 8)).astype(np.float32)
    src = np.array([3, 7, 11], dtype=np.int32)
    dst = np.array([0, 1, 2], dtype=np.int32)
    # int8: device-quantized pair == host-quantized pair, bit for bit.
    codes0 = np.zeros((3, 8), np.int8)
    scales0 = np.ones((3,), np.float32)
    codes, scales = _hot_update_jit()(
        jax.device_put(codes0), jax.device_put(scales0),
        jax.device_put(xs), jax.device_put(src), jax.device_put(dst),
        int8=True,
    )
    store = HostFactorStore(12, 8)
    store.write_range(0, xs)
    h_codes, h_scales = quantize_rows_host(store.gather(src))
    np.testing.assert_array_equal(np.asarray(codes), h_codes)
    np.testing.assert_array_equal(np.asarray(scales), h_scales)


def test_window_stage_span_attrs(stream_ds):
    # The trace must show the reuse: window_stage spans carry
    # rows_staged / rows_delta_skipped / rows_hot under the hot engine.
    from cfk_tpu import telemetry

    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=1, seed=0,
                    layout="tiled", solver="cholesky", hbm_chunk_elems=512)
    tracer = telemetry.configure()
    try:
        train_als_host_window(stream_ds, cfg, chunks_per_window=2)
        spans = [e for e in tracer.events()
                 if e["name"].endswith("window_stage")]
    finally:
        telemetry.shutdown(write=False)
    assert spans
    for e in spans:
        assert "rows_staged" in e["args"]
        assert "rows_delta_skipped" in e["args"]
    assert any(e["args"]["rows_delta_skipped"] >= 0 for e in spans)
    assert sum(e["args"]["rows_hot"] for e in spans) > 0
