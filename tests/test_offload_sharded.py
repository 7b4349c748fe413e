"""Sharded out-of-core training (cfk_tpu.offload, ISSUE 12).

The headline contract: SHARDED windowed host-offload training is BIT-EXACT
vs the sharded resident paths — the all_gather tiled scan and the
flat/hierarchical ring exchanges — across shard count × table dtype ×
window size × ici_group.  Plus: per-shard window-plan units, the
zero-copy plan-held-bytes contract, int8 (codes, scales) PCIe staging
(host quantizer bit-identical to the in-jit one), per-shard budget
arithmetic, resolver routing for sharded shapes, the ici_group plan
field's autotune-digest invalidation, and shard-targeted window faults."""

import dataclasses
import zlib

import numpy as np
import pytest

import jax

from cfk_tpu.config import ALSConfig
from cfk_tpu.data.blocks import Dataset
from cfk_tpu.data.synth import synth_coo
from cfk_tpu.offload import budget as _budget
from cfk_tpu.offload.store import HostFactorStore, quantize_rows_host
from cfk_tpu.offload.window import (
    build_ring_window_plan,
    build_window_plan,
)
from cfk_tpu.offload.windowed import (
    hier_visit_order,
    train_als_host_window,
)
from cfk_tpu.utils.metrics import Metrics

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 virtual devices"
)


def _crc(model):
    return (
        zlib.crc32(np.asarray(model.user_factors, np.float32).tobytes()),
        zlib.crc32(np.asarray(model.movie_factors, np.float32).tobytes()),
    )


@pytest.fixture(scope="module")
def corpus():
    return synth_coo(64, 32, 900, seed=1)


@pytest.fixture(scope="module")
def stream_ds2(corpus):
    """2-shard stream-forced tiled blocks (the all_gather windowed mode)."""
    return Dataset.from_coo(corpus, num_shards=2, layout="tiled",
                            tile_rows=16, chunk_elems=512,
                            accum_max_entities=0)


@pytest.fixture(scope="module")
def ring_ds4(corpus):
    """4-shard ring-built tiled blocks (the ring/hier windowed modes)."""
    return Dataset.from_coo(corpus, num_shards=4, layout="tiled",
                            tile_rows=16, chunk_elems=512, ring=True,
                            ring_warn=False)


@pytest.fixture(scope="module")
def mesh2():
    from cfk_tpu.parallel.mesh import make_mesh

    return make_mesh(2)


@pytest.fixture(scope="module")
def mesh4():
    from cfk_tpu.parallel.mesh import make_mesh

    return make_mesh(4)


# --- the sharded parity matrix ---------------------------------------------


@needs_mesh
@pytest.mark.parametrize("table_dtype,cpw", [
    ("float32", 1),
    ("float32", 3),
    ("bfloat16", 2),
    ("int8", 2),
])
def test_sharded_stream_parity_bit_exact(stream_ds2, mesh2, table_dtype,
                                         cpw):
    # All_gather-exchange sharded windowed training crc-equals the
    # resident shard_map path on the same sharded stream blocks.
    from cfk_tpu.parallel.spmd import train_als_sharded

    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=3,
                    num_shards=2, layout="tiled", table_dtype=table_dtype)
    ref = _crc(train_als_sharded(stream_ds2, cfg, mesh2))
    got = _crc(train_als_host_window(stream_ds2, cfg,
                                     chunks_per_window=cpw))
    assert got == ref, (table_dtype, cpw)


@needs_mesh
@pytest.mark.parametrize("exchange,ici,table_dtype", [
    ("ring", None, "float32"),
    ("hier_ring", 2, "float32"),
    ("hier_ring", 2, "bfloat16"),
    ("hier_ring", 2, "int8"),
    ("hier_ring", 4, "int8"),
])
def test_sharded_ring_parity_bit_exact(ring_ds4, mesh4, exchange, ici,
                                       table_dtype):
    # Ring/hier-ring windowed training replicates the resident exchange's
    # VISIT ORDER (hier_visit_order) against staged windows — crc-equal
    # per (exchange, ici_group, table dtype).
    from cfk_tpu.parallel.spmd import train_als_sharded

    cfg = ALSConfig(rank=4, lam=0.05, num_iterations=2, seed=3,
                    num_shards=4, layout="tiled", exchange=exchange,
                    ici_group=ici, table_dtype=table_dtype)
    ref = _crc(train_als_sharded(ring_ds4, cfg, mesh4))
    metrics = Metrics()
    got = _crc(train_als_host_window(ring_ds4, cfg, chunks_per_window=2,
                                     metrics=metrics))
    assert got == ref, (exchange, ici, table_dtype)
    # The fabric accounting fires: a 2-wide inner ring stages remote-
    # group rows (the DCN share); one inner ring stages none.
    if exchange == "hier_ring" and ici == 2:
        assert metrics.gauges.get("offload_rows_dcn", 0) > 0
    if ici == 4:
        assert metrics.gauges.get("offload_rows_dcn", 0) == 0


@needs_mesh
def test_sharded_auto_exchange_mixed_build_parity(corpus, mesh4):
    # exchange='auto' with a PER-SIDE mixed ring build (the resident
    # per-side memory optimum): the windowed driver must resolve each
    # half's execution shape from the blocks exactly as the resident
    # trainer does — ring movie half, stream user half — and stay
    # crc-identical.
    from cfk_tpu.parallel.spmd import train_als_sharded

    ds = Dataset.from_coo(corpus, num_shards=4, layout="tiled",
                          tile_rows=16, chunk_elems=512,
                          ring=(True, False), ring_warn=False)
    assert ds.movie_blocks.ring and not ds.user_blocks.ring
    cfg = ALSConfig(rank=4, lam=0.05, num_iterations=2, seed=3,
                    num_shards=4, layout="tiled", exchange="auto")
    ref = _crc(train_als_sharded(ds, cfg, mesh4))
    got = _crc(train_als_host_window(ds, cfg, chunks_per_window=2))
    assert got == ref


def test_stream_exchange_on_ring_blocks_raises(corpus):
    # A stream-shape half on ring-built blocks must raise with the
    # resident trainer's remedy, not silently rebuild a different
    # schedule.
    ds = Dataset.from_coo(corpus, num_shards=4, layout="tiled",
                          tile_rows=16, chunk_elems=512, ring=True,
                          ring_warn=False)
    cfg = ALSConfig(rank=4, lam=0.05, num_iterations=1, seed=3,
                    num_shards=4, layout="tiled", exchange="all_gather")
    with pytest.raises(ValueError, match="ring-built"):
        train_als_host_window(ds, cfg)


@needs_mesh
def test_sharded_route_through_train_als_sharded(stream_ds2, mesh2):
    # Pinning the tier routes the SHARDED trainer itself through the
    # windowed driver — same factors, tier in the plan note.
    from cfk_tpu.parallel.spmd import train_als_sharded

    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=3,
                    num_shards=2, layout="tiled")
    base = _crc(train_als_sharded(stream_ds2, cfg, mesh2))
    metrics = Metrics()
    routed = train_als_sharded(
        stream_ds2, dataclasses.replace(cfg, offload_tier="host_window"),
        mesh2, metrics=metrics,
    )
    assert _crc(routed) == base
    assert "tier=host_window" in metrics.notes.get("plan", "")
    assert metrics.gauges.get("offload_shards") == 2


def test_visit_order_matches_flat_ring():
    # inner == S and inner == 1 both degenerate to the flat ring's
    # (shard − r) mod S schedule; a 2-wide inner ring does not.
    for s in (2, 4, 8):
        flat = [[(q - r) % s for r in range(s)] for q in range(s)]
        assert [hier_visit_order(s, s, q) for q in range(s)] == flat
        assert [hier_visit_order(s, 1, q) for q in range(s)] == flat
    assert hier_visit_order(4, 2, 0) != [(0 - r) % 4 for r in range(4)]
    with pytest.raises(ValueError, match="divide"):
        hier_visit_order(4, 3, 0)


# --- per-shard window plans -------------------------------------------------


def test_shard_stream_plans_tile_the_shard_streams(stream_ds2):
    mb, ub = stream_ds2.movie_blocks, stream_ds2.user_blocks
    nc, cap = mb.statics[0], mb.statics[1]
    for d in range(2):
        wp = build_window_plan(mb, ub.padded_entities,
                               chunks_per_window=2, shard=d)
        ncw = wp.statics[0]
        assert wp.chunk_counts.sum() == nc
        got = np.concatenate([
            wp.stage_chunks(w)[0].reshape(ncw, cap)[
                : wp.chunk_counts[w]
            ].reshape(-1)
            for w in range(wp.num_windows)
        ])
        np.testing.assert_array_equal(
            got, mb.rating.reshape(2, -1)[d]
        )
    with pytest.raises(ValueError, match="shard"):
        build_window_plan(mb, ub.padded_entities, shard=2)


def test_ring_plan_windows_stage_the_referenced_rows(ring_ds4):
    mb, ub = ring_ds4.movie_blocks, ring_ds4.user_blocks
    nc, cap, t, h, e_c = mb.statics
    f_pad = ub.padded_entities
    table = np.arange(f_pad * 4, dtype=np.float32).reshape(f_pad, 4)
    store = HostFactorStore.from_array(table, num_shards=4)
    for d in range(4):
        rp = build_ring_window_plan(mb, shard=d, chunks_per_window=2)
        assert rp.num_slices == 4
        # Each slice's windows stay inside the slice's store shard, and
        # window[rebased] == block[original] for every real entry.
        nb_src = mb.neighbor_idx.reshape(4, nc, cap)[d]
        for w in range(rp.num_windows):
            sl = int(rp.slice_of[w])
            rows = rp.rows[w]
            assert (rows // h == sl).all()
            tbl = store.gather(rows)
            nbw = rp.neighbor_idx[w]
            real = nbw < rp.window_rows
            lo, n = int(rp.chunk_lo[w]), int(rp.chunk_counts[w])
            src = nb_src[lo:lo + n].reshape(-1)
            np.testing.assert_array_equal(
                tbl[nbw[: n * cap][real[: n * cap]]],
                table[sl * h + src[src < h]],
            )
    with pytest.raises(ValueError, match="ring-built"):
        # Stream blocks are the wrong shape class for ring plans.
        ds = Dataset.from_coo(synth_coo(32, 16, 200, seed=0),
                              layout="tiled", tile_rows=16,
                              chunk_elems=512, accum_max_entities=0)
        build_ring_window_plan(ds.movie_blocks, shard=0)


def test_window_plan_zero_copy_and_held_bytes(stream_ds2):
    # The zero-copy contract: full windows serve rating/weight/meta as
    # VIEWS of the block arrays (no new host memory), and the plan pins
    # only the rebased neighbor stream + row sets + metadata — strictly
    # less than the padded-copy footprint the old plan held (~2× the
    # interaction data).
    mb, ub = stream_ds2.movie_blocks, stream_ds2.user_blocks
    wp = build_window_plan(mb, ub.padded_entities, chunks_per_window=2,
                           shard=0)
    ncw, cap, e_c, t = wp.statics
    full = [w for w in range(wp.num_windows)
            if wp.chunk_counts[w] == ncw]
    assert full, "fixture must produce at least one full window"
    for w in full:
        rt, wt, ts, ent, cnt, cin, lseg = wp.stage_chunks(w)
        assert np.shares_memory(rt, mb.rating)
        assert np.shares_memory(wt, mb.weight)
        assert np.shares_memory(ts, mb.tile_seg)
        assert np.shares_memory(ent, mb.chunk_entity)
    # The RSS proxy: what the old plan materialized per window (padded
    # copies of every chunk array) vs what this plan holds.
    nt = cap // t
    old_copied = wp.num_windows * (
        ncw * cap * 12 + ncw * nt * 4 + 2 * ncw * e_c * 4 + 2 * ncw * 4
    ) + wp.rows.nbytes
    held = wp.plan_held_bytes()
    assert held < 0.55 * old_copied
    # And the held set is exactly the rebase + rows + tiny metadata.
    assert held <= (wp.neighbor_idx.nbytes + wp.rows.nbytes
                    + wp.carry_in.nbytes + wp.last_seg.nbytes + 4096)


# --- int8 PCIe staging ------------------------------------------------------


def test_host_quantizer_bit_matches_in_jit():
    # The staging quantizer must reproduce XLA's in-jit arithmetic —
    # including the algebraic-simplifier rewrite of /127 into *(1/127)
    # (a true numpy division drifts 1 ulp on some rows, which would break
    # the windowed==resident bit-exactness for int8 tables).
    from cfk_tpu.ops import quant

    rng = np.random.default_rng(0)
    x = (rng.standard_normal((512, 16))
         * rng.uniform(1e-3, 1e2, (512, 1))).astype(np.float32)
    x[7] = 0.0  # all-zero row keeps scale 1.0
    qj, sj = jax.jit(lambda v: quant.quantize_table(v, "int8"))(
        jax.numpy.asarray(x)
    )
    qh, sh = quantize_rows_host(x)
    np.testing.assert_array_equal(qh, np.asarray(qj))
    np.testing.assert_array_equal(sh, np.asarray(sj))
    assert sh[7] == 1.0
    # NaN rows poison their scale (no laundering into finite codes).
    x[3, 0] = np.nan
    _, sn = quantize_rows_host(x)
    assert np.isnan(sn[3])


def test_int8_staging_quarters_the_table_bytes(stream_ds2):
    # The honest staged-bytes contract: int8 windows ship (codes,
    # per-row scales) — (k + 4)/4k of the f32 table bytes — and the
    # recorded offload_staged_mb orders int8 < bf16 < f32 end-to-end.
    from cfk_tpu.offload.windowed import _stage_table

    k = 64
    rows = np.arange(40, dtype=np.int64)
    store = HostFactorStore.from_array(
        np.random.default_rng(0).standard_normal((64, k)).astype(
            np.float32
        )
    )
    common = dict(faults=None, iteration=0, side="m", window=0, shard=0,
                  verify_windows=False, stats=None, home_shard=0,
                  ici_group=1)
    f32, none = _stage_table(store, rows, stage_np=np.dtype(np.float32),
                             int8=False, **common)
    codes, scales = _stage_table(store, rows, stage_np=None, int8=True,
                                 **common)
    assert none is None
    assert (codes.nbytes + scales.nbytes) * 4 * k == pytest.approx(
        f32.nbytes * (k + 4), rel=0, abs=0
    )
    staged = {}
    for td in ("float32", "bfloat16", "int8"):
        cfg = ALSConfig(rank=8, lam=0.05, num_iterations=1, seed=3,
                        num_shards=2, layout="tiled", table_dtype=td)
        met = Metrics()
        train_als_host_window(stream_ds2, cfg, chunks_per_window=2,
                              metrics=met)
        staged[td] = met.gauges["offload_staged_mb"]
    assert staged["int8"] < staged["bfloat16"] < staged["float32"]


# --- per-shard budget arithmetic --------------------------------------------


def test_shard_entity_range_mirrors_store_bounds():
    # The clip/empty-trailing-shard edges mirror HostFactorStore exactly
    # (rows=10 / 7 shards: a ceil-split overshoots past shard 5).
    for rows, shards in ((10, 7), (10, 3), (64, 4), (5, 5), (1, 1)):
        store = HostFactorStore(rows, 2, num_shards=shards)
        for s in range(shards):
            lo, hi = _budget.shard_entity_range(rows, shards, s)
            assert (lo, hi) == (int(store.bounds[s]),
                                int(store.bounds[s + 1]))
    lo, hi = _budget.shard_entity_range(10, 7, 6)
    assert lo == hi == 10  # empty trailing shard, clipped not inverted
    with pytest.raises(ValueError):
        _budget.shard_entity_range(10, 7, 7)
    with pytest.raises(ValueError):
        _budget.shard_entity_range(10, 0, 0)


def test_per_shard_budget_terms():
    one = _budget.train_resident_bytes(1000, 100, 10_000, 16)
    four = _budget.train_resident_bytes(1000, 100, 10_000, 16,
                                        num_shards=4)
    # Tables and blocks divide; the all_gather working copy replicates.
    assert four["factor_tables_bytes"] == one["factor_tables_bytes"] / 4
    assert four["block_arrays_bytes"] == one["block_arrays_bytes"] / 4
    assert four["gather_copy_bytes"] == one["gather_copy_bytes"]
    assert four["total"] < one["total"]
    # fits_device charges per shard: a budget that refuses one shard can
    # accept four.
    hbm = one["total"] / _budget.RESIDENT_FRACTION * 0.6
    assert not _budget.fits_device(1000, 100, 10_000, 16, hbm_bytes=hbm)
    assert _budget.fits_device(1000, 100, 10_000, 16, hbm_bytes=hbm,
                               num_shards=4)
    # But no shard count shrinks the gather copy below the budget.
    tiny = one["gather_copy_bytes"] / _budget.RESIDENT_FRACTION * 0.9
    assert not _budget.fits_device(1000, 100, 10_000, 16, hbm_bytes=tiny,
                                   num_shards=64)
    # The ring modes' persistent accumulator is reserved BEFORE the
    # window double-buffer split (review finding: it is real device
    # state the window sizing must see).
    acc = _budget.ring_accumulator_bytes(100, 8)
    assert acc == (100 + 1) * 8 * 9 * 4
    assert _budget.window_budget_bytes(1000.0, reserved_bytes=0.0) \
        > _budget.window_budget_bytes(1000.0, reserved_bytes=100.0)
    assert _budget.window_budget_bytes(10.0, reserved_bytes=1e9) == 0.0


def test_shape_fits_device_threads_num_shards():
    from cfk_tpu.plan import DeviceSpec, ProblemShape

    shape1 = ProblemShape(num_users=10_000_000, num_movies=1_000_000,
                          nnz=1_000_000_000, rank=128)
    shape4 = dataclasses.replace(shape1, num_shards=4)
    dev = DeviceSpec.nominal("tpu", name="v5e")
    assert not _budget.shape_fits_device(shape1, dev)
    assert _budget.shape_fits_device(shape4, dev)


# --- resolver / plan field --------------------------------------------------


def test_sharded_oversized_resolves_host_window_with_exchange():
    from cfk_tpu.plan import (
        DeviceSpec,
        PlanConstraints,
        ProblemShape,
        plan,
    )

    dev = DeviceSpec.nominal("tpu", name="v5e")
    big = ProblemShape(num_users=40_000_000, num_movies=1_000_000,
                       nnz=2_000_000_000, rank=128, num_shards=4)
    ep, prov = plan(big, dev)
    assert ep.offload_tier == "host_window"
    # A pinned hier exchange + ici_group survives into the plan (and its
    # summary), so provenance records the hierarchy that runs.
    ep2, prov2 = plan(big, dev, PlanConstraints(
        offload_tier="host_window", exchange="hier_ring", ici_group=2,
    ))
    assert ep2.offload_tier == "host_window"
    assert ep2.exchange == "hier_ring"
    assert ep2.ici_group == 2
    assert "ici=2" in ep2.summary()
    # A non-dividing ici_group pin is refused AT RESOLUTION — the same
    # rule ALSConfig and hier_visit_order enforce ("no plan can promise
    # what execution refuses").
    from cfk_tpu.plan import PlanConstraintError

    with pytest.raises(PlanConstraintError, match="divide"):
        plan(big, dev, PlanConstraints(exchange="hier_ring", ici_group=3))


def test_pre_ici_group_autotune_cache_misses(tmp_path, monkeypatch):
    # The regression the plan-field-set digest exists for: a winner tuned
    # BEFORE ici_group was a plan field carries no decision for it, so
    # its cache entry must read as a MISS — not resolve the new knob to a
    # default behind the tuned label.
    import importlib
    import json

    from cfk_tpu.plan import DeviceSpec, PlanConstraints, ProblemShape
    from cfk_tpu.plan import autotune as _at_pkg  # noqa: F401

    plan_autotune = importlib.import_module("cfk_tpu.plan.autotune")
    shape = ProblemShape(num_users=100, num_movies=10, nnz=1000, rank=8)
    dev = DeviceSpec.nominal("cpu")
    cache = tmp_path / "plan_cache.json"

    old_fields = {f: v for f, v in plan_autotune.PLAN_FIELDS.items()
                  if f != "ici_group"}
    with monkeypatch.context() as m:
        m.setattr(plan_autotune, "PLAN_FIELDS", old_fields)
        stale_key = plan_autotune.cache_key(shape, dev)
    # Plant a pre-ici_group entry under the stale key.
    cache.write_text(json.dumps({
        "schema": 1,
        "entries": {stale_key: {"plan": {}, "measured_s": 1e-3}},
    }))
    ep, prov = plan_autotune.autotune(
        shape, dev, PlanConstraints(), cache_path=str(cache),
    )
    assert prov.cache == "miss"


# --- pooled vs serial staging (ISSUE 13) -------------------------------------


def _pool_serial_crc(ds, cfg, cpw, depth=None):
    a = _crc(train_als_host_window(ds, cfg, chunks_per_window=cpw,
                                   staging="serial"))
    b = _crc(train_als_host_window(ds, cfg, chunks_per_window=cpw,
                                   staging="pool", pool_depth=depth))
    return a, b


def test_pooled_staging_crc_identity_fast_representatives(corpus,
                                                          stream_ds2,
                                                          ring_ds4):
    # One fast representative per knob pair (the exhaustive matrix is
    # slow-marked below): staging order must never change consumption
    # order, so pooled == serial bit-for-bit.
    # (a) single shard, stream scan, int8 staging
    ds1 = Dataset.from_coo(corpus, layout="tiled", tile_rows=16,
                           chunk_elems=512, accum_max_entities=0)
    cfg1 = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=3,
                     layout="tiled", table_dtype="int8")
    a, b = _pool_serial_crc(ds1, cfg1, 2)
    assert a == b
    # (b) 2 shards, all_gather windows, bf16 tables, deep pool
    cfg2 = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=3,
                     num_shards=2, layout="tiled", table_dtype="bfloat16")
    a, b = _pool_serial_crc(stream_ds2, cfg2, 3, depth=8)
    assert a == b
    # (c) 4 shards, hier_ring visit schedule (ici_group=2), f32
    cfg3 = ALSConfig(rank=4, lam=0.05, num_iterations=2, seed=3,
                     num_shards=4, layout="tiled", exchange="hier_ring",
                     ici_group=2)
    a, b = _pool_serial_crc(ring_ds4, cfg3, 2)
    assert a == b


def test_staging_modes_share_programs_and_meter_the_engine():
    # Serial then pooled staging of one 2-shard workload (a shape of this
    # test's own, so the first arm is cold): the second arm traces no
    # window program (staging decides who copies, not what runs), serial
    # staging hides nothing (it stages on the consuming thread), and the
    # pool reports its depth and busy time.
    from cfk_tpu.data.synth import synth_coo
    from cfk_tpu.utils.metrics import Metrics

    ds = Dataset.from_coo(synth_coo(200, 60, 1500, seed=0), num_shards=2,
                          layout="tiled", tile_rows=16, chunk_elems=512,
                          accum_max_entities=0)
    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, num_shards=2,
                    layout="tiled", solver="cholesky")
    serial, pool = Metrics(), Metrics()
    a = train_als_host_window(ds, cfg, metrics=serial, chunks_per_window=2,
                              staging="serial")
    b = train_als_host_window(ds, cfg, metrics=pool, chunks_per_window=2,
                              staging="pool")
    assert _crc(a) == _crc(b)
    assert serial.notes["offload_staging"] == "serial"
    assert pool.notes["offload_staging"] == "pool"
    assert serial.gauges["offload_trace_count"] >= 1
    assert pool.gauges["offload_trace_count"] == 0
    assert serial.gauges["offload_stage_hidden_frac"] == 0.0
    assert serial.gauges.get("offload_pool_depth") is None
    assert pool.gauges["offload_pool_depth"] >= 1
    assert pool.gauges["offload_stage_busy_s"] >= 0
    assert pool.gauges["time_to_first_step_s"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("shards,exchange,ici", [
    (1, "all_gather", None),
    (2, "all_gather", None),
    (4, "all_gather", None),
    (4, "ring", None),
    (4, "hier_ring", 2),
    (4, "hier_ring", 4),
])
@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("cpw", [1, 3])
def test_pooled_staging_crc_identity_matrix(corpus, shards, exchange, ici,
                                            table_dtype, cpw):
    # The exhaustive pooled-vs-serial identity: shard count × exchange/
    # ici_group × table dtype × window size.  Combined with the
    # windowed==resident matrix above, this closes the chain
    # pool == serial == resident shard_map.
    ring = exchange in ("ring", "hier_ring")
    build_kw = dict(ring=True, ring_warn=False) if ring \
        else dict(accum_max_entities=0)
    ds = Dataset.from_coo(corpus, num_shards=shards, layout="tiled",
                          tile_rows=16, chunk_elems=512, **build_kw)
    cfg = ALSConfig(rank=4, lam=0.05, num_iterations=2, seed=3,
                    num_shards=shards, layout="tiled", exchange=exchange,
                    ici_group=ici, table_dtype=table_dtype)
    a, b = _pool_serial_crc(ds, cfg, cpw)
    assert a == b, (shards, exchange, ici, table_dtype, cpw)


# --- shard-targeted faults --------------------------------------------------


@needs_mesh
def test_one_shard_window_fault_recovers_fleet_bit_exact(stream_ds2):
    # A NaN-corrupted staged window on ONE shard trips the sentinel and
    # recovers crc-identical to fault-free — and the shard targeting is
    # real (the fault armed for shard 1 never fires on a shard-0-only
    # window stream).
    from cfk_tpu.resilience.faults import (
        HostWindowCorruption,
        WindowFaultInjector,
    )

    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=3, seed=3,
                    num_shards=2, layout="tiled", health_check_every=1)
    base = _crc(train_als_host_window(stream_ds2, cfg,
                                      chunks_per_window=2))
    inj = WindowFaultInjector(HostWindowCorruption(
        iteration=1, side="m", window=0, kind="nan", shard=1,
    ))
    metrics = Metrics()
    rec = train_als_host_window(stream_ds2, cfg, chunks_per_window=2,
                                metrics=metrics, window_faults=inj)
    assert inj.fired == 1
    assert metrics.counters.get("health_trips", 0) == 1
    assert _crc(rec) == base
    # Shard targeting: the same fault pinned to a shard that never
    # stages (side "m" windows exist on both shards here, so pin an
    # out-of-range shard id) stays cold.
    cold = WindowFaultInjector(HostWindowCorruption(
        iteration=1, side="m", window=0, kind="nan", shard=7,
    ))
    rec2 = train_als_host_window(stream_ds2, cfg, chunks_per_window=2,
                                 window_faults=cold)
    assert cold.fired == 0
    assert _crc(rec2) == base
