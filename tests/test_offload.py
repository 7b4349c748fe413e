"""Out-of-core factor tables (cfk_tpu.offload, ISSUE 11).

The headline contract: windowed host-offload training is BIT-EXACT vs the
resident-table path at a small shape, on every supporting knob — table
dtype (f32/bf16/int8), gather mode, fused epilogue, overlap, storage
dtype, window size.  Plus: the host store and window-plan units, the
memory-budget predicate the planner and executor share, tier resolution
(oversized ⇒ host_window; pinned-but-impossible ⇒ loud error), the
staging-integrity ladder path, and the hierarchical ICI×DCN ring's
numeric contracts."""

import dataclasses
import zlib

import numpy as np
import pytest

import jax

from cfk_tpu.config import ALSConfig
from cfk_tpu.data.blocks import Dataset
from cfk_tpu.data.synth import synth_coo
from cfk_tpu.models.als import train_als
from cfk_tpu.offload.budget import (
    RESIDENT_FRACTION,
    fits_device,
    train_resident_bytes,
    window_budget_bytes,
)
from cfk_tpu.offload.store import HostFactorStore
from cfk_tpu.offload.window import build_window_plan
from cfk_tpu.offload.windowed import (
    train_als_host_window,
    windowed_half_step,
)


@pytest.fixture(scope="module")
def stream_ds():
    """Tiny power-law corpus as stream-forced tiled blocks (accum mode
    disabled — the out-of-core regime's mode on both sides)."""
    return Dataset.from_coo(
        synth_coo(60, 30, 900, seed=0), layout="tiled", chunk_elems=512,
        tile_rows=16, accum_max_entities=0,
    )


def _crc(model):
    return (
        zlib.crc32(np.asarray(model.user_factors, np.float32).tobytes()),
        zlib.crc32(np.asarray(model.movie_factors, np.float32).tobytes()),
    )


# --- HostFactorStore -------------------------------------------------------


def test_store_gather_and_write_across_shards():
    store = HostFactorStore(10, 3, num_shards=3)
    vals = np.arange(30, dtype=np.float32).reshape(10, 3)
    store.write_range(0, vals)
    np.testing.assert_array_equal(store.as_array(), vals)
    # Gather crossing shard boundaries, unordered with repeats.
    rows = np.array([9, 0, 4, 4, 7])
    np.testing.assert_array_equal(store.gather(rows), vals[rows])
    # Scatter-write at arbitrary rows.
    store.write_rows(np.array([2, 8]), np.zeros((2, 3), np.float32))
    assert store.as_array()[2].sum() == 0 and store.as_array()[8].sum() == 0
    # A copy is independent.
    snap = store.copy()
    store.write_range(0, vals)
    assert snap.as_array()[2].sum() == 0


def test_store_overshooting_ceil_split():
    # rows=10 / 7 shards: per=2 walks past 10 before the last shard —
    # bounds must clip (trailing shards empty), not go non-monotonic.
    store = HostFactorStore(10, 2, num_shards=7)
    vals = np.arange(20, dtype=np.float32).reshape(10, 2)
    store.write_range(0, vals)
    rows = np.array([9, 0, 5, 8])
    np.testing.assert_array_equal(store.gather(rows), vals[rows])
    store.write_rows(np.array([9]), np.full((1, 2), 7.0, np.float32))
    assert (store.as_array()[9] == 7.0).all()


def test_store_validation():
    with pytest.raises(ValueError):
        HostFactorStore(4, 2, num_shards=5)
    with pytest.raises(ValueError):
        HostFactorStore(4, 2, dtype="int8")
    store = HostFactorStore(4, 2)
    with pytest.raises(IndexError):
        store.gather(np.array([4]))
    with pytest.raises(IndexError):
        store.write_range(3, np.zeros((2, 2), np.float32))


def test_store_bf16_roundtrip():
    import ml_dtypes

    store = HostFactorStore(4, 2, dtype="bfloat16")
    store.write_range(0, np.full((4, 2), 1.00390625, np.float32))
    assert store.as_array().dtype == np.dtype(ml_dtypes.bfloat16)
    assert store.nbytes == 4 * 2 * 2


# --- WindowPlan ------------------------------------------------------------


def test_window_plan_invariants(stream_ds):
    mb, ub = stream_ds.movie_blocks, stream_ds.user_blocks
    wp = build_window_plan(mb, ub.padded_entities, chunks_per_window=1)
    nc = mb.statics[0]
    # Windows partition the real chunks; every window starts carry-free.
    assert wp.statics[0] >= 2  # the length-1-scan floor (bit-exactness)
    assert (wp.carry_in[:, 0] == 0.0).all()
    # Rebased indices stay inside the window (zero row == window_rows).
    assert wp.neighbor_idx.max() <= wp.window_rows
    assert wp.window_rows % 8 == 0
    # Staged rows reproduce the table rows the resident gather would read.
    table = np.arange(
        ub.padded_entities * 4, dtype=np.float32
    ).reshape(ub.padded_entities, 4)
    store = HostFactorStore.from_array(table)
    for w in range(wp.num_windows):
        tbl = store.gather(wp.rows[w])
        nbw = wp.neighbor_idx[w]
        real = nbw < wp.window_rows
        # window[rebased] == table[original] for every real entry
        np.testing.assert_array_equal(
            tbl[nbw[real]],
            table[wp.rows[w][nbw[real]]],
        )
    # The windows' real chunks tile the original chunk stream exactly:
    # concatenating each window's first chunk_counts[w] staged rating
    # chunks reproduces the blocks' flat rating stream.
    ncw, cap = wp.statics[0], wp.statics[1]
    assert wp.chunk_counts.sum() == nc
    real_rt = np.concatenate([
        wp.stage_chunks(w)[0].reshape(ncw, cap)[
            : wp.chunk_counts[w]
        ].reshape(-1)
        for w in range(wp.num_windows)
    ])
    np.testing.assert_array_equal(real_rt, mb.rating.reshape(-1))


def test_window_plan_refuses_wrong_modes(stream_ds):
    ds_accum = Dataset.from_coo(
        synth_coo(60, 30, 900, seed=0), layout="tiled", chunk_elems=512,
        tile_rows=16,  # default accum_max_entities: tiny sides go accum
    )
    with pytest.raises(ValueError, match="stream-mode"):
        build_window_plan(
            ds_accum.movie_blocks,
            ds_accum.user_blocks.padded_entities,
        )
    with pytest.raises(ValueError, match="chunks_per_window"):
        build_window_plan(
            stream_ds.movie_blocks,
            stream_ds.user_blocks.padded_entities, chunks_per_window=0,
        )


# --- windowed == resident bit-exactness ------------------------------------


def test_half_step_parity_bit_exact(stream_ds):
    from cfk_tpu.models import als as als_mod
    from cfk_tpu.ops.tiled import tiled_half_step

    mb, ub = stream_ds.movie_blocks, stream_ds.user_blocks
    k = 8
    rng = np.random.default_rng(0)
    u = rng.standard_normal((ub.padded_entities, k)).astype(np.float32)
    res = np.asarray(tiled_half_step(
        jax.numpy.asarray(u), als_mod._tiled_to_device(mb),
        ("tiled", mb.mode) + mb.statics, mb.padded_entities, 0.05,
        solver="pallas",
    ))
    store = HostFactorStore.from_array(u)
    for cpw in (1, 2, 4):
        wp = build_window_plan(mb, ub.padded_entities,
                               chunks_per_window=cpw)
        win = windowed_half_step(store, wp, lam=0.05, solver="pallas")
        np.testing.assert_array_equal(res, win)


@pytest.mark.parametrize("dtype,table_dtype,gather,fused,overlap,solver", [
    ("float32", "float32", None, None, True, "pallas"),
    ("float32", "bfloat16", None, None, True, "pallas"),
    ("float32", "int8", None, None, True, "pallas"),
    ("bfloat16", "bfloat16", None, None, False, "pallas"),
    ("float32", "float32", False, False, True, "cholesky"),
])
def test_train_parity_bit_exact(stream_ds, dtype, table_dtype, gather,
                                fused, overlap, solver):
    # The ISSUE 11 acceptance: windowed host-offload training crc-equals
    # the resident path on the same stream blocks, per supporting knob.
    cfg = ALSConfig(
        rank=8, lam=0.05, num_iterations=2, layout="tiled", solver=solver,
        dtype=dtype, table_dtype=table_dtype, in_kernel_gather=gather,
        fused_epilogue=fused, overlap=overlap,
    )
    base = _crc(train_als(stream_ds, cfg))
    for cpw in (1, 3):
        off = _crc(train_als_host_window(stream_ds, cfg,
                                         chunks_per_window=cpw))
        assert off == base, (dtype, table_dtype, gather, fused, overlap,
                             solver, cpw)


def test_train_parity_single_chunk_sides():
    # A side whose resident scan is LENGTH ONE: the window floor must not
    # pad it to two chunks (the resident program is itself a length-1
    # scan, so padding would introduce the very ~1 ulp program-shape
    # drift the floor exists to prevent on multi-chunk sides).  At this
    # degenerate shape the RESIDENT fused fori-loop itself drifts ~2e-5
    # from its own stepped twin (XLA fuses across the iteration body once
    # the inner scan is length-1 — pre-existing, measured here), so the
    # bit-exact reference is the resident STEPPED loop, the per-iteration
    # program the windowed driver mirrors.
    from cfk_tpu.resilience.faults import FaultInjector

    ds = Dataset.from_coo(
        synth_coo(40, 16, 300, seed=2), layout="tiled",
        chunk_elems=1 << 16, tile_rows=16, accum_max_entities=0,
    )
    assert ds.movie_blocks.statics[0] == 1  # the shape under test
    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, layout="tiled",
                    solver="pallas")
    stepped = _crc(train_als(ds, cfg, fault_injector=FaultInjector()))
    assert _crc(train_als_host_window(ds, cfg)) == stepped
    # The fused-loop comparison stays a tolerance check at this shape.
    fused = train_als(ds, cfg)
    win = train_als_host_window(ds, cfg)
    np.testing.assert_allclose(
        np.asarray(win.user_factors, np.float32),
        np.asarray(fused.user_factors, np.float32), rtol=2e-4, atol=2e-4,
    )


def test_train_als_routes_host_window_tier(stream_ds):
    # Pinning the tier on the config routes train_als itself through the
    # windowed driver — same factors, and the plan note records the tier.
    from cfk_tpu.utils.metrics import Metrics

    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, layout="tiled")
    base = _crc(train_als(stream_ds, cfg))
    metrics = Metrics()
    routed = train_als(
        stream_ds,
        dataclasses.replace(cfg, offload_tier="host_window"),
        metrics=metrics,
    )
    assert _crc(routed) == base
    assert "tier=host_window" in metrics.notes.get("plan", "")
    assert metrics.gauges.get("offload_windows_m", 0) >= 1
    with pytest.raises(NotImplementedError):
        train_als(
            stream_ds,
            dataclasses.replace(cfg, offload_tier="host_window"),
            warm_start=(np.zeros((60, 8)), np.zeros((30, 8))),
        )


def test_windowed_driver_exports_its_meters(stream_ds):
    # What the driver reports of a run, read off its own gauges: windows
    # on both sides, sublane-aligned window rows, the bytes staged (of
    # them the table's share, all cold with no hot partition), the plan's
    # pinned host bytes, and the time to the first full iteration.
    from cfk_tpu.utils.metrics import Metrics

    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, layout="tiled",
                    solver="cholesky")
    metrics = Metrics()
    train_als_host_window(stream_ds, cfg, metrics=metrics,
                          chunks_per_window=2, hot_rows=0)
    g = metrics.gauges
    assert g["offload_windows_m"] >= 1 and g["offload_windows_u"] >= 1
    assert g["offload_chunks_per_window"] == 2
    assert g["offload_window_rows_m"] >= 8
    assert g["offload_window_rows_m"] % 8 == 0
    assert g["offload_staged_mb"] > 0
    assert 0 < g["offload_staged_cold_mb"] <= g["offload_staged_mb"]
    assert g.get("offload_hot_rows", 0) == 0
    assert g["offload_plan_held_mb"] > 0
    assert g["time_to_first_step_s"] > 0


def test_window_integrity_trip_recovers_bit_exact(stream_ds):
    # A torn window (finite, WRONG bytes) is caught by the staging
    # checksum BEFORE any kernel consumes it; rollback + one-shot replay
    # is crc-identical to fault-free.
    from cfk_tpu.resilience.faults import (
        HostWindowCorruption,
        WindowFaultInjector,
    )
    from cfk_tpu.utils.metrics import Metrics

    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=3, layout="tiled",
                    health_check_every=1)
    base = _crc(train_als_host_window(stream_ds, cfg, chunks_per_window=2))
    inj = WindowFaultInjector(HostWindowCorruption(
        iteration=1, side="m", window=0, kind="torn",
    ))
    metrics = Metrics()
    rec = train_als_host_window(
        stream_ds, cfg, chunks_per_window=2, metrics=metrics,
        window_faults=inj,
    )
    assert inj.fired == 1
    assert metrics.counters.get("health_trips", 0) == 1
    assert metrics.counters.get("rollbacks", 0) == 1
    assert _crc(rec) == base


# --- memory budget + tier resolution ---------------------------------------


def test_budget_predicate_terms():
    r = train_resident_bytes(1000, 100, 10_000, 16)
    assert r["total"] == pytest.approx(
        r["factor_tables_bytes"] + r["gather_copy_bytes"]
        + r["block_arrays_bytes"]
    )
    assert fits_device(1000, 100, 10_000, 16, hbm_bytes=r["total"] * 2)
    assert not fits_device(1000, 100, 10_000, 16,
                           hbm_bytes=r["total"] / RESIDENT_FRACTION * 0.5)
    assert window_budget_bytes(100.0) == pytest.approx(
        100.0 * RESIDENT_FRACTION / 2
    )


def test_plan_resolves_oversized_to_host_window():
    from cfk_tpu.plan import (
        DeviceSpec,
        PlanConstraintError,
        PlanConstraints,
        ProblemShape,
        plan,
    )

    dev = DeviceSpec.nominal("tpu", name="v5e")
    big = ProblemShape(num_users=10_000_000, num_movies=1_000_000,
                       nnz=1_000_000_000, rank=128)
    ep, prov = plan(big, dev)
    assert ep.offload_tier == "host_window"
    assert ep.layout == "tiled"
    assert "tier=host_window" in prov.plan.summary()
    small = ProblemShape(num_users=1000, num_movies=100, nnz=10_000,
                         rank=16)
    assert plan(small, dev)[0].offload_tier == "device"
    # The guarantee: a pinned resident table that cannot fit is refused,
    # not promised.
    with pytest.raises(PlanConstraintError, match="cannot|exceeds"):
        plan(big, dev, PlanConstraints(offload_tier="device"))
    # Sharded shapes route through the SAME tier machinery now
    # (ISSUE 12), with PER-SHARD arithmetic: 4 shards of the 1B-rating
    # shape genuinely fit a v5e (tables and blocks divide), so the
    # resolver keeps them resident…
    import dataclasses as _dc

    assert plan(_dc.replace(big, num_shards=4), dev)[0].offload_tier \
        == "device"
    # …but a fixed side whose all_gather working copy ALONE overflows the
    # device stays oversized at ANY shard count (the copy replicates per
    # device — the term sharding cannot shrink), resolves host_window,
    # and refuses a pinned resident table per shard.
    big4 = _dc.replace(big, num_users=40_000_000, nnz=2_000_000_000,
                       num_shards=4)
    assert plan(big4, dev)[0].offload_tier == "host_window"
    with pytest.raises(PlanConstraintError, match="PER-SHARD|exceeds"):
        plan(big4, dev, PlanConstraints(offload_tier="device"))
    # Pinned host_window conflicts loudly with a non-tiled layout pin.
    with pytest.raises(PlanConstraintError, match="tiled"):
        plan(small, dev, PlanConstraints(offload_tier="host_window",
                                         layout="padded"))


def test_autotune_cache_key_records_plan_field_set(monkeypatch):
    # A cache entry tuned before a plan field existed must MISS: the key
    # carries a digest of the field set, so adding a field (as ISSUE 11
    # does with offload_tier) invalidates every older entry.
    import importlib

    from cfk_tpu.plan import DeviceSpec, ProblemShape, cache_key

    # the module, not the same-named function the package re-exports
    plan_autotune = importlib.import_module("cfk_tpu.plan.autotune")

    shape = ProblemShape(num_users=100, num_movies=10, nnz=1000, rank=8)
    dev = DeviceSpec.nominal("cpu")
    before = cache_key(shape, dev)
    monkeypatch.setattr(
        plan_autotune, "PLAN_FIELDS",
        {**plan_autotune.PLAN_FIELDS, "future_knob": ("a", "b")},
    )
    assert cache_key(shape, dev) != before


def test_config_offload_validation():
    with pytest.raises(ValueError, match="tiled"):
        ALSConfig(offload_tier="host_window", layout="padded")
    with pytest.raises(ValueError, match="offload_tier"):
        ALSConfig(offload_tier="resident")
    cfg = ALSConfig(offload_tier="host_window", layout="tiled")
    assert cfg.offload_tier == "host_window"
    # Sharded host_window is legal now (ISSUE 12) — including the ring
    # exchanges the sharded windowed driver replicates.
    cfg2 = ALSConfig(offload_tier="host_window", layout="tiled",
                     num_shards=2, exchange="hier_ring", ici_group=2)
    assert cfg2.offload_tier == "host_window"


def test_trainer_rejects_unsupported_configs(stream_ds):
    with pytest.raises(ValueError, match="tiled"):
        train_als_host_window(
            stream_ds, ALSConfig(rank=8, layout="padded"),
        )
    with pytest.raises(ValueError, match="explicit ALS"):
        train_als_host_window(
            stream_ds,
            ALSConfig(rank=8, layout="bucketed", algorithm="als++",
                      block_size=8),
        )


# --- hierarchical ICI×DCN ring ---------------------------------------------


needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 virtual devices"
)


@pytest.fixture(scope="module")
def ring_setup():
    from cfk_tpu.parallel.mesh import make_mesh

    coo = synth_coo(64, 32, 900, seed=1)
    ds1 = Dataset.from_coo(coo, num_shards=1, layout="tiled",
                           tile_rows=16, chunk_elems=512)
    ds4 = Dataset.from_coo(coo, num_shards=4, layout="tiled",
                           tile_rows=16, chunk_elems=512, ring=True,
                           ring_warn=False)
    return ds1, ds4, make_mesh(4)


def _hier_cfg(ici_group):
    return ALSConfig(rank=4, num_iterations=3, seed=3, num_shards=4,
                     layout="tiled", exchange="hier_ring",
                     ici_group=ici_group)


@needs_mesh
def test_hier_ring_one_inner_ring_bit_equals_flat_ring(ring_setup):
    from cfk_tpu.parallel.spmd import train_als_sharded

    _, ds4, mesh = ring_setup
    flat = train_als_sharded(
        ds4, dataclasses.replace(_hier_cfg(4), exchange="ring",
                                 ici_group=None), mesh,
    )
    hier = train_als_sharded(ds4, _hier_cfg(4), mesh)
    assert _crc(hier) == _crc(flat)


@needs_mesh
@pytest.mark.parametrize("inner", [1, 2])
def test_hier_ring_matches_single_device(ring_setup, inner):
    from cfk_tpu.parallel.spmd import train_als_sharded

    ds1, ds4, mesh = ring_setup
    ref = train_als(
        ds1, ALSConfig(rank=4, num_iterations=3, seed=3, layout="tiled"),
    ).predict_dense()
    got = train_als_sharded(ds4, _hier_cfg(inner), mesh)
    np.testing.assert_allclose(got.predict_dense(), ref,
                               rtol=2e-3, atol=2e-3)
    # Deterministic: a rerun is bit-identical.
    again = train_als_sharded(ds4, _hier_cfg(inner), mesh)
    assert _crc(got) == _crc(again)


def test_hier_config_validation():
    with pytest.raises(ValueError, match="tiled"):
        ALSConfig(exchange="hier_ring", layout="padded")
    with pytest.raises(ValueError, match="divide"):
        ALSConfig(exchange="hier_ring", layout="tiled", num_shards=4,
                  ici_group=3)
    with pytest.raises(ValueError, match="ici_group"):
        ALSConfig(ici_group=0)


def test_resolve_ici_group():
    from cfk_tpu.parallel.spmd import resolve_ici_group

    assert resolve_ici_group(
        ALSConfig(exchange="hier_ring", layout="tiled", num_shards=4,
                  ici_group=2)
    ) == 2
    # auto: local device count when it divides, else one flat ring
    auto = resolve_ici_group(
        ALSConfig(exchange="hier_ring", layout="tiled", num_shards=4)
    )
    assert auto in (1, 2, 4) and 4 % auto == 0
