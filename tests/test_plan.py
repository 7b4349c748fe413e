"""Execution planner (ISSUE 9): resolution matrix, legacy-gate agreement,
bit-identical execution, autotune cache semantics, constraint conflicts,
kernel registry availability, and the env-var retirement.

The contract under test: every plan the resolver returns must satisfy the
SAME gates the half-steps execute under (no plan can promise a kernel the
execution would refuse), the default-config path must be bit-identical to
the pre-planner behavior, and a cost-model choice must execute bit-equal
to the knobs-off route for the knobs that are bit-exact by contract
(fused epilogue, in-kernel gather)."""

import dataclasses
import json
import warnings
import zlib

import numpy as np
import pytest

from cfk_tpu.config import ALSConfig
from cfk_tpu.plan import (
    DeviceSpec,
    ExecutionPlan,
    PlanCache,
    PlanConstraintError,
    PlanConstraints,
    ProblemShape,
    autotune,
    cache_key,
    constraints_from_config,
    plan,
    plan_cost,
    plan_for_config,
    rank_plans,
)
from cfk_tpu.plan.registry import (
    REGISTRY,
    resolve_fused_chunk_lam,
    resolve_gather_mode,
)

TPU = DeviceSpec.nominal("tpu", name="v5e")
CPU = DeviceSpec.nominal("cpu", name="test-cpu")


def _shape(rank=64, shards=1, **kw):
    base = dict(num_users=480_189, num_movies=17_770, nnz=100_480_507)
    base.update(kw)
    return ProblemShape(rank=rank, num_shards=shards, **base)


# -- resolution matrix: every cell satisfies the legacy gates ---------------

_LAYOUTS = ("padded", "bucketed", "segment", "tiled")
_DTYPES = ("float32", "bfloat16", "int8")
_RANKS = (8, 64, 160)
_SHARDS = (1, 2, 4)


@pytest.mark.parametrize("layout", _LAYOUTS)
@pytest.mark.parametrize("table_dtype", _DTYPES)
@pytest.mark.parametrize("rank", _RANKS)
@pytest.mark.parametrize("shards", _SHARDS)
def test_matrix_resolver_choice_satisfies_legacy_gates(
    layout, table_dtype, rank, shards
):
    cons = PlanConstraints(layout=layout, table_dtype=table_dtype)
    if table_dtype == "int8" and layout not in ("tiled", "bucketed"):
        # The cell ALSConfig itself refuses must be a loud conflict, not
        # a silently repaired plan.
        with pytest.raises(PlanConstraintError, match="int8"):
            plan(_shape(rank=rank, shards=shards), TPU, cons)
        return
    ep, prov = plan(_shape(rank=rank, shards=shards), TPU, cons)
    # Pins honored exactly.
    assert ep.layout == layout
    assert ep.table_dtype == table_dtype
    # Legacy gate agreement — the plan may only promise what the
    # execution-time gates would grant.
    from cfk_tpu.ops.pallas import PALLAS_MAX_RANK
    from cfk_tpu.ops.pallas.gram_kernel import fused_gram_solve_supported
    from cfk_tpu.ops.quant import validate_table_dtype_layout

    validate_table_dtype_layout(ep.table_dtype, ep.layout)  # no raise
    if ep.fused_epilogue:
        assert ep.solver == "pallas"
        assert ep.gram_backend == "pallas"
        assert fused_gram_solve_supported(1, rank, ep.reg_solve_algo)
    if ep.in_kernel_gather:
        assert ep.gram_backend == "pallas"
    if ep.solver == "pallas":
        assert rank <= 2 * PALLAS_MAX_RANK
    if ep.exchange == "ring":
        assert ep.layout in ("padded", "tiled")
    # Kernel slots name a registered backend for every slot.
    for slot, backend in ep.kernels:
        assert REGISTRY.get(slot, backend) is not None
    assert prov.est_cost_s > 0


def test_rank_past_lu_cap_resolves_split_epilogue():
    ep, _ = plan(_shape(rank=160), TPU, PlanConstraints(layout="tiled"))
    assert not ep.fused_epilogue  # LU cap 128 < 160: fused must be off
    assert dict(ep.kernels)["gram_solve"] == "xla_emulation"


def test_cost_model_orderings():
    """The monotonicities the ranking depends on (not absolute values)."""
    sh = _shape(rank=64)
    base, _ = plan(sh, TPU, PlanConstraints(layout="tiled"))
    c = lambda ep: plan_cost(sh, TPU, ep).seconds
    flip = lambda **kw: dataclasses.replace(base, **kw)
    assert c(flip(fused_epilogue=False)) > c(base)
    assert c(flip(reg_solve_algo="gj")) >= c(base)
    # The in-kernel gather is feasible only where Mosaic lowers its row
    # DMA: float32 tables at a multiple of 128 lanes (rank 128 here, where
    # the fused epilogue in turn is refused).
    assert not base.in_kernel_gather
    sh128 = _shape(rank=128)
    base128, _ = plan(sh128, TPU, PlanConstraints(layout="tiled"))
    assert base128.in_kernel_gather and not base128.fused_epilogue
    c128 = lambda ep: plan_cost(sh128, TPU, ep).seconds
    assert c128(dataclasses.replace(base128, in_kernel_gather=False)) \
        > c128(base128)
    # Quantized tables can only shrink the estimate.
    assert c(flip(table_dtype="int8")) <= c(base)
    # On the byte-bound CPU spec int8 is STRICTLY cheaper (resolve both
    # on the CPU so the solver choice matches what a host run would do).
    cpu_f32, _ = plan(sh, CPU, PlanConstraints(layout="tiled",
                                               table_dtype="float32"))
    cpu_int8, _ = plan(sh, CPU, PlanConstraints(layout="tiled",
                                                table_dtype="int8"))
    assert (plan_cost(sh, CPU, cpu_int8).seconds
            < plan_cost(sh, CPU, cpu_f32).seconds)


def test_serve_plan_prefers_quantized_table_and_big_quanta():
    sh = ProblemShape(num_users=1000, num_movies=59_000, nnz=59_000,
                      rank=128, kind="serve", serve_k=100)
    ep, _ = plan(sh, CPU)
    assert ep.table_dtype == "int8"  # the serve scan is byte-bound
    assert ep.serve_batch_quantum >= 64  # amortize the table scan
    pinned, _ = plan(sh, CPU, PlanConstraints(table_dtype="float32"))
    assert pinned.table_dtype == "float32"


# -- bit-identical execution ------------------------------------------------

def _tiny_ds(layout):
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo

    kw = {}
    if layout in ("tiled", "segment", "bucketed"):
        kw["chunk_elems"] = 512
    if layout == "tiled":
        kw["tile_rows"] = 16
    return Dataset.from_coo(
        synthetic_netflix_coo(60, 30, 900, seed=0), layout=layout, **kw
    )


def _crc(model):
    return (
        zlib.crc32(np.asarray(model.user_factors, np.float32).tobytes()),
        zlib.crc32(np.asarray(model.movie_factors, np.float32).tobytes()),
    )


_MATRIX = [
    ("padded", "float32"),
    ("padded", "bfloat16"),
    ("tiled", "float32"),
    ("tiled", "int8"),
    ("bucketed", "float32"),
    ("bucketed", "int8"),
]

# Chosen vs knobs-off on the bucketed layout runs two different XLA:CPU
# programs: the chosen route is the gather wrappers' XLA twin, knobs-off
# (gather=xla, split epilogue) feeds the materialized stream to
# ``gram_tiles_pallas``, whose CPU route is the kernel body under the
# Pallas interpreter (ops/pallas/interpret.py).  Same math, float32 sums
# in another order: 7.2e-7 absolute per half-step
# (test_quant_table.py::test_bucketed_port_f32_close_to_knobs_off), and
# after this test's three iterations, on factors up to 3.3 in magnitude,
# 3.6e-5 (float32) and 1.1e-5 (int8) on the user side, 1.0e-5 and 6.6e-6
# on the movie side (measured, jax 0.9.0).  The bound is 8 x the largest.
_BUCKETED_KNOBS_OFF_ATOL = 3e-4


def _matrix_cfg(layout, table_dtype):
    return ALSConfig(rank=8, num_iterations=3, layout=layout,
                     table_dtype=table_dtype, plan="model")


@pytest.mark.parametrize("layout,table_dtype", _MATRIX)
def test_matrix_plan_execution_bit_identical_to_chosen_knobs_pinned(
    layout, table_dtype
):
    """plan='model' with the fused-epilogue and gather knobs free must
    train bit-identically to plan='pinned' with those two knobs pinned to
    what the resolver chose: one program traced twice, so any drift is a
    planner bug (a deferred knob routed differently from its resolved
    value).  ``solver`` / ``reg_solve_algo`` stay deferred: the resolver
    prices them for the target chip while "auto" resolves per backend
    downstream (ROADMAP D13)."""
    from cfk_tpu.models.als import train_als

    ds = _tiny_ds(layout)
    cfg = _matrix_cfg(layout, table_dtype)
    ep, _ = plan_for_config(
        cfg, num_users=ds.user_map.num_entities,
        num_movies=ds.movie_map.num_entities,
        nnz=int(ds.movie_blocks.count.sum()),
    )
    assert {"fused_epilogue", "in_kernel_gather"}.isdisjoint(ep.pinned)
    pinned = dataclasses.replace(
        cfg, plan="pinned", fused_epilogue=ep.fused_epilogue,
        in_kernel_gather=ep.in_kernel_gather,
    )
    assert _crc(train_als(ds, pinned)) == _crc(train_als(ds, cfg))


@pytest.mark.parametrize("layout,table_dtype", _MATRIX)
def test_matrix_plan_execution_matches_knobs_off(layout, table_dtype):
    """The resolver's choice (plan='model', fused/gather free) against the
    pre-plan knobs-off route (both knobs pinned off).  Padded and tiled:
    bit-identical.  Bucketed: two XLA:CPU programs, equal to float32
    round-off (``_BUCKETED_KNOBS_OFF_ATOL`` has the measurement)."""
    from cfk_tpu.models.als import train_als

    ds = _tiny_ds(layout)
    cfg = _matrix_cfg(layout, table_dtype)
    chosen = train_als(ds, cfg)
    off = train_als(ds, dataclasses.replace(
        cfg, fused_epilogue=False, in_kernel_gather=False, plan="pinned",
    ))
    if layout != "bucketed":
        assert _crc(off) == _crc(chosen)
        return
    for side in ("user_factors", "movie_factors"):
        np.testing.assert_allclose(
            np.asarray(getattr(off, side), np.float32),
            np.asarray(getattr(chosen, side), np.float32),
            rtol=0, atol=_BUCKETED_KNOBS_OFF_ATOL,
        )


def test_default_config_modes_bit_identical():
    """plan='model' vs 'pinned' vs 'autotune' (cold cache) on the default
    config: the deferred-knob sentinels must route identically, so the
    three modes are the same execution bit-for-bit."""
    from cfk_tpu.models.als import train_als

    ds = _tiny_ds("padded")
    crcs = {
        mode: _crc(train_als(
            ds, ALSConfig(rank=6, num_iterations=3, plan=mode)
        ))
        for mode in ("pinned", "model", "autotune")
    }
    assert len(set(crcs.values())) == 1, crcs


def test_half_step_kwargs_preserves_deferred_sentinels():
    cfg = ALSConfig()
    ep, _ = plan_for_config(cfg, num_users=300, num_movies=80, nnz=2000)
    kw = ep.half_step_kwargs(cfg)
    # Deferred knobs stay deferred (process-default patch points intact).
    assert kw["fused_epilogue"] is None
    assert kw["in_kernel_gather"] is None
    assert kw["reg_solve_algo"] == "auto"
    assert kw["solver"] == "auto"
    # Concrete knobs thread concrete.
    assert kw["overlap"] is True
    assert kw["table_dtype"] == "float32"
    pinned_cfg = ALSConfig(fused_epilogue=False, in_kernel_gather=False,
                           reg_solve_algo="gj", solver="cholesky")
    ep2, _ = plan_for_config(pinned_cfg, num_users=300, num_movies=80,
                             nnz=2000)
    kw2 = ep2.half_step_kwargs(pinned_cfg)
    assert kw2["fused_epilogue"] is False
    assert kw2["in_kernel_gather"] is False
    assert kw2["reg_solve_algo"] == "gj"
    assert kw2["solver"] == "cholesky"


def test_trainer_records_plan_provenance_in_metrics_and_manifest(tmp_path):
    from cfk_tpu.models.als import train_als
    from cfk_tpu.transport.checkpoint import CheckpointManager
    from cfk_tpu.utils.metrics import Metrics

    ds = _tiny_ds("padded")
    metrics = Metrics()
    mgr = CheckpointManager(str(tmp_path))
    train_als(ds, ALSConfig(rank=6, num_iterations=2), metrics=metrics,
              checkpoint_manager=mgr)
    assert "plan" in metrics.notes and "source=" in metrics.notes["plan"]
    state = mgr.restore()
    assert state.meta["plan_source"] in ("model", "pinned")
    # The manifest's plan dict round-trips into a real ExecutionPlan.
    ep = ExecutionPlan.from_dict(state.meta["plan"])
    assert ep.layout == "padded"
    json.dumps(state.meta)  # manifest meta must stay JSON-serializable


# -- constraints ------------------------------------------------------------

def test_constraint_merge_conflict_names_both_values():
    a = PlanConstraints(table_dtype="int8")
    b = PlanConstraints(table_dtype="float32")
    with pytest.raises(PlanConstraintError) as e:
        a.merge(b)
    assert "table_dtype='int8'" in str(e.value).replace('"', "'")
    assert "float32" in str(e.value)


def test_hard_conflicts_raise():
    with pytest.raises(PlanConstraintError, match="ring"):
        plan(_shape(), TPU, PlanConstraints(layout="bucketed",
                                            exchange="ring"))
    with pytest.raises(PlanConstraintError, match="int8"):
        plan(_shape(), TPU, PlanConstraints(layout="segment",
                                            table_dtype="int8"))


def test_soft_pin_released_with_explanation():
    # fused pinned ON with the cholesky solver: today's execution silently
    # splits, so the plan must resolve to the effective split (not raise)
    # and say why.
    ep, prov = plan(_shape(rank=64), TPU, PlanConstraints(
        layout="tiled", fused_epilogue=True, solver="cholesky",
    ))
    assert not ep.fused_epilogue
    assert any(f == "fused_epilogue" and "released" in reason
               for f, _, reason in prov.explain)


def test_unknown_constraint_value_rejected():
    with pytest.raises(PlanConstraintError, match="not a known value"):
        PlanConstraints(table_dtype="float16")
    with pytest.raises(PlanConstraintError, match="positive int"):
        PlanConstraints(chunk_elems=-4)


def test_constraints_from_config_pins_concrete_knobs_only():
    cons = constraints_from_config(ALSConfig())
    pins = cons.pinned()
    assert pins["layout"] == "padded"
    assert pins["table_dtype"] == "float32"
    assert pins["overlap"] is True
    for free in ("fused_epilogue", "in_kernel_gather", "reg_solve_algo",
                 "solver", "chunk_elems"):
        assert free not in pins


# -- autotune cache ---------------------------------------------------------

def _fake_measure(costs):
    calls = []

    def measure(ep):
        calls.append(ep)
        return costs.get(ep.table_dtype, 1.0)

    measure.calls = calls
    return measure


def test_autotune_measures_caches_and_hits(tmp_path):
    path = str(tmp_path / "cache.json")
    sh = _shape(rank=32, num_users=4096, num_movies=512, nnz=65_536)
    cons = PlanConstraints(layout="tiled")
    # bf16 measures cheapest even though the model may rank f32 first.
    m = _fake_measure({"bfloat16": 0.1, "float32": 0.5, "int8": 0.4})
    ep, prov = autotune(sh, TPU, cons, cache_path=path, measure=m)
    assert ep.table_dtype == "bfloat16"
    assert prov.source == "autotune" and prov.cache == "miss"
    assert prov.measured_s == pytest.approx(0.1)
    assert len(m.calls) >= 2  # top candidates + the legacy default
    # Round-trip: same shape+device hits without measuring.
    m2 = _fake_measure({})
    ep2, prov2 = autotune(sh, TPU, cons, cache_path=path, measure=m2)
    assert (ep2, prov2.cache, prov2.source) == (
        ep, "hit", "autotune-cache")
    assert m2.calls == []


def test_autotune_stale_fingerprint_invalidates(tmp_path, monkeypatch):
    path = str(tmp_path / "cache.json")
    sh = _shape(rank=32)
    m = _fake_measure({"float32": 0.2})
    autotune(sh, TPU, PlanConstraints(layout="tiled"), cache_path=path,
             measure=m)
    # Different device fingerprint → miss, re-measures.
    other = dataclasses.replace(TPU, name="v6e")
    m2 = _fake_measure({"float32": 0.2})
    _, prov = autotune(sh, other, PlanConstraints(layout="tiled"),
                       cache_path=path, measure=m2)
    assert prov.cache == "miss" and m2.calls
    # Version bump → miss too (the cache key carries cfk_tpu.__version__).
    monkeypatch.setattr("cfk_tpu.__version__", "999.0")
    m3 = _fake_measure({"float32": 0.2})
    _, prov3 = autotune(sh, TPU, PlanConstraints(layout="tiled"),
                        cache_path=path, measure=m3)
    assert prov3.cache == "miss" and m3.calls
    # Shape-class bucketing: a nearby size shares the tuned entry.
    near = _shape(rank=32, num_users=480_000, nnz=100_000_000)
    assert cache_key(near, TPU) == cache_key(_shape(rank=32), TPU)


def test_corrupt_cache_reads_as_miss(tmp_path):
    path = tmp_path / "cache.json"
    path.write_text("{ not json")
    cache = PlanCache(str(path))
    assert cache.get("anything") is None
    # And a wrong-schema file too.
    path.write_text(json.dumps({"schema": 999, "entries": {"k": {}}}))
    assert PlanCache(str(path)).get("k") is None


def test_cache_hit_never_overrides_pins(tmp_path):
    """Code-review regression: a winner tuned with table_dtype FREE must
    not answer a query that PINS it — the cached plan would override an
    explicit config knob (the cache key carries the pin set, and a hit is
    double-checked against the current pins)."""
    path = str(tmp_path / "cache.json")
    sh = _shape(rank=32)
    free = PlanConstraints(layout="tiled")
    m = _fake_measure({"int8": 0.05, "float32": 0.5, "bfloat16": 0.4})
    ep, _ = autotune(sh, TPU, free, cache_path=path, measure=m)
    assert ep.table_dtype == "int8"
    # Same shape, dtype now pinned f32: must MISS and honor the pin.
    pinned = PlanConstraints(layout="tiled", table_dtype="float32")
    m2 = _fake_measure({"float32": 0.2})
    ep2, prov2 = autotune(sh, TPU, pinned, cache_path=path, measure=m2)
    assert prov2.cache == "miss" and m2.calls
    assert ep2.table_dtype == "float32"
    # Trainer-style consult-only with the pin: model fallback, never the
    # free-tuned int8 winner.
    ep3, prov3 = plan(sh, TPU, pinned, mode="autotune", cache_path=path)
    assert ep3.table_dtype == "float32"


def test_infeasible_solver_and_ring_pins_soft_release():
    """Code-review regression: pins today's execution silently falls back
    from must resolve (with an explain row), not raise — pre-planner,
    solver='pallas' past the blocked cap quietly took cholesky, and a
    single-device run never consults exchange='ring'."""
    from cfk_tpu.ops.pallas import PALLAS_MAX_RANK

    big = 4 * PALLAS_MAX_RANK  # past the 2× blocked-Schur cap
    ep, prov = plan(_shape(rank=big), TPU,
                    PlanConstraints(layout="tiled", solver="pallas"))
    assert ep.solver == "cholesky"
    assert any(f == "solver" and "released" in r
               for f, _, r in prov.explain)
    ep2, prov2 = plan(_shape(shards=1), TPU, PlanConstraints(
        layout="tiled", exchange="ring",
    ))
    assert ep2.exchange == "all_gather"
    assert any(f == "exchange" for f, _, r in prov2.explain)
    # End-to-end: the config trains instead of raising at entry.
    from cfk_tpu.models.als import train_als

    ds = _tiny_ds("tiled")
    train_als(ds, ALSConfig(rank=8, num_iterations=1, layout="tiled",
                            exchange="ring"))


def test_cache_consult_only_falls_back_to_model(tmp_path):
    sh = _shape(rank=32)
    ep, prov = plan(sh, TPU, PlanConstraints(layout="tiled"),
                    mode="autotune",
                    cache_path=str(tmp_path / "cold.json"))
    assert prov.cache == "miss"
    assert prov.source == "model"  # no measure fn → model fallback


# -- kernel registry --------------------------------------------------------

def test_registry_slots_resolve_loaders():
    for slot, backend in (("gram_solve", "mosaic_tpu"),
                          ("gram_gather", "xla_emulation"),
                          ("topk", "mosaic_tpu"),
                          ("reg_solve", "xla_emulation")):
        assert callable(REGISTRY.get(slot, backend).loader())
    with pytest.raises(KeyError, match="no kernel registered"):
        REGISTRY.get("gram", "mosaic_gpu")
    with pytest.raises(ValueError, match="unknown kernel slot"):
        REGISTRY.register("warp", "mosaic_tpu", lambda: None)


def test_forced_outage_reroutes_resolvers_and_bumps_generation():
    gen0 = REGISTRY.generation()
    args = (None, "pallas", 512, 34, 16, 33, 128)
    kw = dict(table_dtype="float32")
    assert resolve_gather_mode(*args, **kw) == "fused"
    assert resolve_fused_chunk_lam(None, "pallas", 8, 33, "pallas", 0.05,
                                   False) == 0.05
    with REGISTRY.unavailable("mosaic_tpu"):
        assert REGISTRY.generation() == gen0 + 1
        assert not REGISTRY.backend_available("mosaic_tpu")
        assert resolve_gather_mode(*args, **kw) == "xla"
        assert resolve_fused_chunk_lam(None, "pallas", 8, 33, "pallas",
                                       0.05, False) is None
        # The resolver lands every slot on the emulation floor.
        ep, _ = plan(_shape(rank=8), TPU, PlanConstraints(layout="tiled"))
        assert set(dict(ep.kernels).values()) == {"xla_emulation"}
        assert not ep.in_kernel_gather and not ep.fused_epilogue
    assert REGISTRY.backend_available("mosaic_tpu")
    assert REGISTRY.generation() == gen0 + 2


def test_emulation_floor_cannot_be_disabled():
    with pytest.raises(ValueError, match="degradation floor"):
        REGISTRY.force_unavailable("xla_emulation")


# -- env-var retirement -----------------------------------------------------

def test_reg_solve_algo_env_var_deprecated_warns_once(monkeypatch):
    import cfk_tpu.ops.pallas.solve_kernel as sk

    monkeypatch.delenv("CFK_REG_SOLVE_ALGO", raising=False)
    monkeypatch.setattr(sk, "_ENV_ALGO_WARNED", False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert sk.default_reg_solve_algo() == "lu"
    assert not w  # unset: the plan-level default, silently
    monkeypatch.setenv("CFK_REG_SOLVE_ALGO", "gj")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert sk.default_reg_solve_algo() == "gj"  # alias still wins
        assert sk.default_reg_solve_algo() == "gj"
    deprecations = [x for x in w if x.category is DeprecationWarning]
    assert len(deprecations) == 1  # warns ONCE per process
    assert "deprecated" in str(deprecations[0].message)


# -- provenance -------------------------------------------------------------

def test_provenance_row_and_transitions():
    ep, prov = plan(_shape(rank=8), TPU, PlanConstraints(layout="tiled"))
    row = prov.as_row()
    assert row["plan_source"] in ("model", "pinned")
    assert row["plan"].startswith("tiled/")
    assert "plan_transitions" not in row
    prov.record_transition("recovery_escalation", "lam=0.5")
    row2 = prov.as_row()
    assert "recovery_escalation" in row2["plan_transitions"]
    meta = prov.as_meta()
    assert meta["plan_transitions"][0]["reason"] == "recovery_escalation"
    assert ExecutionPlan.from_dict(meta["plan"]) == ep


def test_ranked_plans_are_cost_sorted_and_tie_break_to_legacy():
    ranked = rank_plans(_shape(rank=64), TPU,
                        PlanConstraints(layout="tiled"))
    costs = [s for s, _ in ranked]
    assert costs == sorted(costs)
    assert len({ep for _, ep in ranked}) == len(ranked)
