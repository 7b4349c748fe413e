"""Chaos lab: run every fault class end-to-end and report the outcome.

The pytest suite (``tests/test_resilience.py``, ``tests/test_tcp_broker.py``)
asserts the recovery contract; this runner is the operator-facing version —
one command that injects each fault class against a small deterministic
workload and prints a JSON row per scenario:

    python scripts/chaos_lab.py            # all scenarios
    python scripts/chaos_lab.py --scenario nan torn_checkpoint

Each row records whether the fault FIRED (a chaos run that injects nothing
proves nothing), whether the sentinel DETECTED it, whether the run
RECOVERED, and the recovered final RMSE against the fault-free run's.
Exit status is non-zero if any scenario misses its contract.

The infrastructure scenarios (ISSUE 5) extend the ladder past numerics:
``preemption`` (SIGTERM mid-iteration → emergency save → resume),
``slow_disk`` (async checkpoint writer absorbing 150 ms/save disk latency
with bit-exact factors), and ``worker_kill`` (SIGKILL one of two Gloo
processes → bounded survivor exit with intact store → full-fleet resume).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import warnings

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RMSE_RTOL = 0.15  # recovered final RMSE must be within this of fault-free


def _train(ds, cfg, **kw):
    from cfk_tpu.models.als import train_als

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return train_als(ds, cfg, **kw)


def _rmse(model, ds) -> float:
    from cfk_tpu.eval.metrics import mse_rmse_from_model

    return mse_rmse_from_model(model, ds)[1]


def _dataset():
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo

    return Dataset.from_coo(synthetic_netflix_coo(60, 30, 900, seed=0))


def _base_cfg(**kw):
    from cfk_tpu.config import ALSConfig

    return ALSConfig(rank=4, num_iterations=6, health_check_every=1, **kw)


def _row(name, *, fired, metrics, base_rmse, rec_rmse, ok_extra=True):
    detected = metrics.counters.get("health_trips", 0) >= 1
    recovered = (
        rec_rmse is not None
        and np.isfinite(rec_rmse)
        and abs(rec_rmse - base_rmse) <= RMSE_RTOL * max(base_rmse, 1e-9)
    )
    return {
        "scenario": name,
        "fault_fired": bool(fired),
        "detected": bool(detected),
        "recovered": bool(recovered),
        "rollbacks": metrics.counters.get("rollbacks", 0),
        "escalation_level": metrics.gauges.get("escalation_level", 0),
        "fault_free_rmse": round(float(base_rmse), 6),
        "recovered_rmse": (
            None if rec_rmse is None else round(float(rec_rmse), 6)
        ),
        "notes": metrics.notes,
        "ok": bool(fired and detected and recovered and ok_extra),
    }


def scenario_nan() -> dict:
    from cfk_tpu.resilience.faults import FactorCorruption, FaultInjector
    from cfk_tpu.utils.metrics import Metrics

    ds, cfg = _dataset(), _base_cfg()
    base_rmse = _rmse(_train(ds, cfg), ds)
    inj = FaultInjector(FactorCorruption(iteration=2, side="u"))
    metrics = Metrics()
    rec = _train(ds, cfg, metrics=metrics, fault_injector=inj)
    return _row("nan", fired=inj.fired, metrics=metrics,
                base_rmse=base_rmse, rec_rmse=_rmse(rec, ds))


def scenario_inf() -> dict:
    from cfk_tpu.resilience.faults import FactorCorruption, FaultInjector
    from cfk_tpu.utils.metrics import Metrics

    ds, cfg = _dataset(), _base_cfg()
    base_rmse = _rmse(_train(ds, cfg), ds)
    inj = FaultInjector(
        FactorCorruption(iteration=3, side="u", value=float("inf"))
    )
    metrics = Metrics()
    rec = _train(ds, cfg, metrics=metrics, fault_injector=inj)
    return _row("inf", fired=inj.fired, metrics=metrics,
                base_rmse=base_rmse, rec_rmse=_rmse(rec, ds))


def scenario_singular() -> dict:
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.resilience.faults import (
        FaultInjector,
        SingularChunk,
        blockstructured_coo,
    )
    from cfk_tpu.utils.metrics import Metrics

    ds = Dataset.from_coo(blockstructured_coo(seed=0))
    cfg = _base_cfg(lam=0.0)
    base_rmse = _rmse(_train(ds, cfg), ds)
    inj = FaultInjector(
        SingularChunk(iteration=2, side="u", rows=(0, 8), persistent=True)
    )
    metrics = Metrics()
    rec = _train(ds, cfg, metrics=metrics, fault_injector=inj)
    # the λ bump is THE designed fix for singular normal equations
    return _row("singular_chunk", fired=inj.fired, metrics=metrics,
                base_rmse=base_rmse, rec_rmse=_rmse(rec, ds),
                ok_extra=metrics.gauges.get("escalation_level", 0) >= 2)


def scenario_torn_checkpoint() -> dict:
    import tempfile

    from cfk_tpu.resilience.faults import TornCheckpointManager
    from cfk_tpu.transport.checkpoint import CheckpointManager
    from cfk_tpu.utils.metrics import Metrics

    ds, cfg = _dataset(), _base_cfg()
    base_rmse = _rmse(_train(ds, cfg), ds)
    with tempfile.TemporaryDirectory() as d:
        torn = TornCheckpointManager(
            CheckpointManager(d), tear_at=cfg.num_iterations
        )
        from cfk_tpu.models.als import train_als

        _train(ds, cfg, checkpoint_manager=torn)
        metrics = Metrics()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            rec = train_als(
                ds, cfg, checkpoint_manager=CheckpointManager(d),
                metrics=metrics,
            )
        skipped = any("skipping corrupt checkpoint" in str(w.message)
                      for w in caught)
    row = _row("torn_checkpoint", fired=bool(torn.torn), metrics=metrics,
               base_rmse=base_rmse, rec_rmse=_rmse(rec, ds),
               ok_extra=skipped)
    # detection here is the crc32 verification, not the sentinel
    row["detected"] = skipped
    row["ok"] = bool(row["fault_fired"] and skipped and row["recovered"])
    return row


def scenario_flaky_broker() -> dict:
    from cfk_tpu.resilience.faults import FlakyBrokerProxy, FlakyPlan
    from cfk_tpu.transport.tcp import BrokerProcess, TcpBrokerClient, build_broker

    if not build_broker():
        return {"scenario": "flaky_broker", "ok": False,
                "error": "cfk_broker binary unavailable"}
    payload = [bytes([i]) * 64 for i in range(32)]
    with BrokerProcess() as bp:
        with FlakyBrokerProxy(
            bp.port, FlakyPlan(drop_first_connects=2, delay_frames=2,
                               frame_delay=0.1)
        ) as proxy:
            with TcpBrokerClient(
                "127.0.0.1", proxy.port, connect_retries=5,
                retry_base=0.02, read_timeout=0.05, read_retries=20,
            ) as c:
                c.create_topic("chaos", 1)
                for i, v in enumerate(payload):
                    c.produce("chaos", key=i, value=v)
                got = [r.value for r in c.consume("chaos", 0)]
            dropped, delayed = proxy.dropped, proxy.delayed
    intact = got == payload
    return {
        "scenario": "flaky_broker",
        "fault_fired": bool(dropped and delayed),
        "connections_dropped": dropped,
        "frames_delayed": delayed,
        "detected": True,  # retries ARE the detection here
        "recovered": intact,
        "records_intact": intact,
        "ok": bool(dropped and delayed and intact),
    }


def scenario_preemption() -> dict:
    """Preemption mid-iteration: SIGTERM lands between iterations, the
    guard-armed loop drains the async writer, commits a final checkpoint,
    and exits resumable; a restart completes to the fault-free RMSE."""
    import tempfile

    from cfk_tpu.resilience.faults import FaultInjector, PreemptAt
    from cfk_tpu.resilience.preempt import PreemptionGuard
    from cfk_tpu.transport.checkpoint import CheckpointManager
    from cfk_tpu.utils.metrics import Metrics

    ds, cfg = _dataset(), _base_cfg()
    base_rmse = _rmse(_train(ds, cfg), ds)
    with tempfile.TemporaryDirectory() as d:
        inj = FaultInjector(PreemptAt(iteration=3))
        metrics = Metrics()
        with PreemptionGuard() as guard:
            _train(
                ds, cfg, checkpoint_manager=CheckpointManager(d),
                metrics=metrics, fault_injector=inj, preemption_guard=guard,
            )
        evicted = bool(guard.triggered and "preempted" in metrics.notes)
        mgr = CheckpointManager(d)
        committed = mgr.latest_valid_iteration()
        # every surviving step must pass crc verification (intact, not torn)
        for it in mgr.iterations():
            mgr.verify(it)
        rec = _train(ds, cfg, checkpoint_manager=CheckpointManager(d))
        rec_rmse = _rmse(rec, ds)
    recovered = (
        np.isfinite(rec_rmse)
        and abs(rec_rmse - base_rmse) <= RMSE_RTOL * max(base_rmse, 1e-9)
    )
    return {
        "scenario": "preemption",
        "fault_fired": bool(inj.fired),
        "detected": evicted,  # the guard + the loop's preempted note
        "recovered": bool(recovered),
        "committed_at_eviction": committed,
        "preempted_note": metrics.notes.get("preempted"),
        "fault_free_rmse": round(float(base_rmse), 6),
        "recovered_rmse": round(float(rec_rmse), 6),
        "ok": bool(inj.fired and evicted and committed == 4 and recovered),
    }


def scenario_slow_disk() -> dict:
    """Slow-disk async writer: checkpoint writes cost 150 ms each, but the
    step loop must not stall behind them — the async writer absorbs the
    latency (bounded by back-pressure), every step is intact after the
    drain, and factors are bit-identical to the sync-writer run."""
    import tempfile

    from cfk_tpu.resilience.faults import SlowDiskCheckpointManager
    from cfk_tpu.utils.metrics import Metrics

    ds, cfg = _dataset(), _base_cfg()
    delay = 0.15

    def run(async_write, d):
        # max_pending sized past the run's save count: the scenario
        # demonstrates the step loop NEVER stalling behind the slow disk
        # (the drain runs at loop exit); the tier-1 suite separately pins
        # the default cap's back-pressure behavior.
        mgr = SlowDiskCheckpointManager(
            d, delay_s=delay, async_write=async_write,
            max_pending=cfg.num_iterations + 2,
        )
        metrics = Metrics()
        model = _train(ds, cfg, checkpoint_manager=mgr, metrics=metrics)
        u, m = model.host_factors()
        return mgr, metrics, (u, m)

    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        sync_mgr, sync_metrics, sync_factors = run(False, d1)
        async_mgr, async_metrics, async_factors = run(True, d2)
        intact = (sorted(async_mgr.iterations())
                  == sorted(sync_mgr.iterations()))
        for it in async_mgr.iterations():
            async_mgr.verify(it)
    sync_stall = sync_metrics.phases.get("checkpoint", 0.0)
    async_stall = async_metrics.phases.get("checkpoint", 0.0)
    bit_exact = (
        np.array_equal(sync_factors[0], async_factors[0])
        and np.array_equal(sync_factors[1], async_factors[1])
    )
    return {
        "scenario": "slow_disk",
        "fault_fired": bool(async_mgr.writes >= cfg.num_iterations
                            and sync_stall >= delay * cfg.num_iterations),
        "detected": True,  # the async writer absorbing the delay IS the fix
        "recovered": bool(intact and bit_exact),
        "sync_ckpt_stall_s": round(sync_stall, 3),
        "async_ckpt_stall_s": round(async_stall, 3),
        "stall_removed_s": round(sync_stall - async_stall, 3),
        "slow_writes": async_mgr.writes,
        "factors_bit_exact": bool(bit_exact),
        "steps_intact": bool(intact),
        # with queue headroom the in-loop async stall is snapshot-only:
        # well under the injected per-save disk delay, let alone the sync
        # writer's full serialize+fsync total
        "ok": bool(intact and bit_exact
                   and async_stall < max(0.5 * sync_stall, 0.2)),
    }


def scenario_telemetry_overhead() -> dict:
    """Telemetry-overhead drill (ISSUE 14): the same tiny workload trained
    with the span tracer OFF and ON must produce crc-IDENTICAL factors —
    spans are host-side observation only and may never perturb the math.
    The wall factor is recorded informationally (min-of-N on this noisy
    shared container; the pinned ≤2% budget is measured at the bench's
    default shape, see ROADMAP)."""
    import json as _json
    import tempfile
    import time
    import zlib

    from cfk_tpu import telemetry

    ds, cfg = _dataset(), _base_cfg()

    def crc(model):
        return zlib.crc32(
            np.asarray(model.user_factors, np.float32).tobytes()
        ) & 0xFFFFFFFF

    _train(ds, cfg)  # warm the jit cache so both arms time steady-state
    t_off = []
    for _ in range(3):
        t0 = time.time()
        m_off = _train(ds, cfg)
        t_off.append(time.time() - t0)
    with tempfile.TemporaryDirectory() as td:
        tracer = telemetry.configure(trace_dir=td)
        try:
            t_on = []
            for _ in range(3):
                t0 = time.time()
                m_on = _train(ds, cfg)
                t_on.append(time.time() - t0)
            spans = len(tracer.events())
        finally:
            # never leak an active tracer into the remaining scenarios
            trace_path = telemetry.shutdown(write=True)
        with open(trace_path) as f:
            trace = _json.load(f)
        names = {e["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "X"}
        telemetry.validate_span_tree(trace["traceEvents"])
    crc_off, crc_on = crc(m_off), crc(m_on)
    telemetry.record_event("train", "telemetry_overhead_drill",
                           crc_off=crc_off, crc_on=crc_on, spans=spans)
    # This fault-free config runs the fused fori_loop: one span per train
    # call (per-iteration spans live on the stepped path — the nan/
    # offload scenarios exercise those).
    train_spans = bool({"train/fused_loop", "train/iter"} & names)
    factor = min(t_on) / max(min(t_off), 1e-9)
    return {
        "scenario": "telemetry_overhead",
        "fault_fired": True,  # the "fault" is the instrumentation itself
        "detected": spans > 0,
        "recovered": crc_on == crc_off,
        "crc_identical": crc_on == crc_off,
        "spans_recorded": spans,
        "train_spans": train_spans,
        "overhead_factor_wall": round(factor, 3),
        "ok": bool(crc_on == crc_off and spans > 0 and train_spans),
    }


def scenario_worker_kill() -> dict:
    """Worker-kill + restart: SIGKILL one of two Gloo processes mid-run;
    the survivor must exit bounded (watchdog or collective error) with an
    intact store, and restarting the fleet must resume to the same RMSE an
    uninterrupted 2-process run reaches (tests/multihost_worker.py
    drills — the same harness the slow pytest drills use)."""
    import importlib.util
    import re
    import signal
    import tempfile

    from cfk_tpu.resilience.preempt import STALL_EXIT_CODE
    from cfk_tpu.transport.checkpoint import CheckpointManager

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = 29700 + (os.getpid() % 200)

    # The ONE worker-launch harness (shared with the pytest drills in
    # tests/test_multihost.py) — loaded by path because tests/ is not a
    # package.
    spec = importlib.util.spec_from_file_location(
        "multihost_worker",
        os.path.join(root, "tests", "multihost_worker.py"),
    )
    mhw = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mhw)

    def spawn_pair(ckdir, drill, extra=(), port_off=0):
        procs = mhw.spawn_workers(
            port + port_off, 2, ckdir, "--drill", drill, *extra
        )
        return procs, mhw.communicate_all(procs, timeout=240)

    kill_iter = 4
    with tempfile.TemporaryDirectory() as ck, \
            tempfile.TemporaryDirectory() as ck_ref:
        procs, outs = spawn_pair(
            ck, "kill",
            ("--kill-iteration", str(kill_iter), "--stall-timeout", "6"),
        )
        victim_killed = procs[1].returncode == -signal.SIGKILL
        survivor_bounded = procs[0].returncode != 0
        survivor_graceful = procs[0].returncode == STALL_EXIT_CODE
        mgr = CheckpointManager(ck)
        steps = mgr.iterations()
        intact = bool(steps)
        try:
            for it in steps:
                mgr.verify(it)
        except Exception:
            intact = False
        rprocs, routs = spawn_pair(ck, "resume", port_off=2)
        m = re.search(r"DRILL_RESUME mse=([0-9.]+)", "".join(routs))
        resumed_mse = float(m.group(1)) if m else None
        # uninterrupted reference: the same drill config from a fresh dir
        uprocs, uouts = spawn_pair(ck_ref, "resume", port_off=4)
        mu = re.search(r"DRILL_RESUME mse=([0-9.]+)", "".join(uouts))
        uninterrupted_mse = float(mu.group(1)) if mu else None
    resumed_ok = (
        all(p.returncode == 0 for p in rprocs)
        and resumed_mse is not None
        and uninterrupted_mse is not None
        and abs(resumed_mse - uninterrupted_mse) < 1e-4
    )
    # The fault lives in subprocesses; the harness records the observed
    # outcome so the parent's flight dump names the kill (the workers'
    # own stall-watchdog dumps land in their cwd only if CFK_FLIGHT_DIR
    # is exported to them — the in-process record is the portable trail).
    from cfk_tpu.telemetry import record_event

    record_event("fault", "worker_kill_observed",
                 victim_exit=procs[1].returncode,
                 survivor_exit=procs[0].returncode,
                 steps_intact=bool(intact))
    return {
        "scenario": "worker_kill",
        "fault_fired": bool(victim_killed),
        "detected": bool(survivor_bounded),
        "recovered": bool(resumed_ok),
        "survivor_exit": procs[0].returncode,
        "survivor_graceful_stall_exit": bool(survivor_graceful),
        "steps_committed": steps,
        "checkpoints_intact": bool(intact),
        "resumed_mse": resumed_mse,
        "uninterrupted_mse": uninterrupted_mse,
        "ok": bool(victim_killed and survivor_bounded and intact
                   and resumed_ok),
    }


def scenario_offload_fleet() -> dict:
    """Distributed window exchange under a hard host loss: SIGKILL one of
    two offload-fleet processes AFTER it commits its per-host store-slice
    checkpoint; the survivor must exit bounded (Gloo collective error or
    the StallWatchdog — never a hang), every host's manifest must hold
    only intact committed steps, and restarting the full fleet must
    min-agree the resume step across the per-host manifests and land
    bit-identically (crc32) on the uninterrupted 2-process run — which
    itself bit-matches the one-process driver (the exchange contract)."""
    import importlib.util
    import re
    import signal
    import tempfile

    from cfk_tpu.resilience.preempt import STALL_EXIT_CODE
    from cfk_tpu.transport.checkpoint import CheckpointManager

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = 29700 + (os.getpid() % 200) + 20

    spec = importlib.util.spec_from_file_location(
        "multihost_worker",
        os.path.join(root, "tests", "multihost_worker.py"),
    )
    mhw = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mhw)

    def spawn_pair(ckdir, drill, extra=(), port_off=0):
        procs = mhw.spawn_workers(
            port + port_off, 2, ckdir, "--drill", drill, *extra
        )
        return procs, mhw.communicate_all(procs, timeout=240)

    def drill_rows(outs, tag):
        return {json.loads(line.split(" ", 1)[1])["pid"]:
                json.loads(line.split(" ", 1)[1])
                for out in outs for line in out.splitlines()
                if line.startswith(tag + " ")}

    kill_iter = 2
    with tempfile.TemporaryDirectory() as ck:
        # uninterrupted 2-process reference — the crc the resumed fleet
        # must land on bit-exactly
        uprocs, uouts = spawn_pair(None, "offload", port_off=4)
        urows = drill_rows(uouts, "DRILL_OFFLOAD")
        fleet_crc = urows.get(0, {}).get("crc")
        fleet_agrees = (len(urows) == 2
                        and urows[0]["crc"] == urows[1]["crc"])

        procs, outs = spawn_pair(
            ck, "offload-kill",
            ("--kill-iteration", str(kill_iter), "--stall-timeout", "6"),
        )
        victim_killed = procs[1].returncode == -signal.SIGKILL
        survivor_bounded = procs[0].returncode != 0
        survivor_graceful = procs[0].returncode == STALL_EXIT_CODE
        # BOTH hosts' manifests hold only intact committed steps (the
        # dead host's store slice recovers from ITS manifest, not a copy)
        intact = True
        steps_by_host = {}
        for pid in (0, 1):
            mgr = CheckpointManager(os.path.join(ck, f"host_{pid}"))
            steps = mgr.iterations()
            steps_by_host[pid] = steps
            try:
                for it in steps:
                    mgr.verify(it)
            except Exception:
                intact = False
            intact = intact and bool(steps)
        rprocs, routs = spawn_pair(ck, "offload-resume", port_off=2)
        rrows = drill_rows(routs, "DRILL_OFFLOAD_RESUME")
    resumed_ok = (
        all(p.returncode == 0 for p in rprocs)
        and len(rrows) == 2
        and rrows[0]["crc"] == rrows[1]["crc"] == fleet_crc
        and rrows[0]["resumed_from"] >= kill_iter
    )
    from cfk_tpu.telemetry import record_event

    record_event("fault", "offload_fleet_kill_observed",
                 victim_exit=procs[1].returncode,
                 survivor_exit=procs[0].returncode,
                 steps_intact=bool(intact),
                 resumed_from=rrows.get(0, {}).get("resumed_from"))
    return {
        "scenario": "offload_fleet",
        "fault_fired": bool(victim_killed),
        "detected": bool(survivor_bounded),
        "recovered": bool(resumed_ok),
        "survivor_exit": procs[0].returncode,
        "survivor_graceful_stall_exit": bool(survivor_graceful),
        "steps_committed": steps_by_host,
        "checkpoints_intact": bool(intact),
        "fleet_crc_agrees": bool(fleet_agrees),
        "uninterrupted_crc": fleet_crc,
        "resumed_crc": rrows.get(0, {}).get("crc"),
        "resumed_from": rrows.get(0, {}).get("resumed_from"),
        "ok": bool(victim_killed and survivor_bounded and intact
                   and fleet_agrees and resumed_ok),
    }


def scenario_fleet_shrink() -> dict:
    """Elastic fleet membership (ISSUE 20), the shrink half: SIGKILL one
    of two offload-fleet processes mid-iteration and the survivor must
    NOT exit — the elastic layer classifies the dead collective, the
    survivors min-agree the committed step from the per-host manifests,
    repartition ownership, reload the orphaned store slice, and finish
    training; the survivor's final crc32 must bit-match the
    uninterrupted 2-process run."""
    import importlib.util
    import signal
    import tempfile

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = 29700 + (os.getpid() % 200) + 120

    spec = importlib.util.spec_from_file_location(
        "multihost_worker",
        os.path.join(root, "tests", "multihost_worker.py"),
    )
    mhw = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mhw)

    def spawn_pair(ckdir, drill, extra=(), port_off=0):
        procs = mhw.spawn_workers(
            port + port_off, 2, ckdir, "--drill", drill, *extra
        )
        return procs, mhw.communicate_all(procs, timeout=240)

    def drill_rows(outs, tag):
        return {json.loads(line.split(" ", 1)[1])["pid"]:
                json.loads(line.split(" ", 1)[1])
                for out in outs for line in out.splitlines()
                if line.startswith(tag + " ")}

    kill_iter = 2
    with tempfile.TemporaryDirectory() as ck:
        # uninterrupted 2-process reference — the crc the shrunk
        # survivor must land on bit-exactly
        uprocs, uouts = spawn_pair(None, "offload", port_off=6)
        urows = drill_rows(uouts, "DRILL_OFFLOAD")
        fleet_crc = urows.get(0, {}).get("crc")
        fleet_agrees = (len(urows) == 2
                        and urows[0]["crc"] == urows[1]["crc"])

        procs, outs = spawn_pair(
            ck, "offload-elastic",
            ("--kill-iteration", str(kill_iter), "--stall-timeout", "10"),
        )
        rows = drill_rows(outs, "DRILL_OFFLOAD_ELASTIC")
    victim_killed = procs[1].returncode == -signal.SIGKILL
    survivor_row = rows.get(0, {})
    survivor_completed = (procs[0].returncode == 0
                          and survivor_row.get("crc") is not None)
    shrank = (survivor_row.get("shrinks", 0) >= 1
              and survivor_row.get("peers_lost", 0) >= 1
              and survivor_row.get("epoch", 0) >= 1)
    crc_exact = (fleet_crc is not None
                 and survivor_row.get("crc") == fleet_crc)
    from cfk_tpu.telemetry import record_event

    record_event("fault", "fleet_shrink_observed",
                 victim_exit=procs[1].returncode,
                 survivor_exit=procs[0].returncode,
                 shrinks=survivor_row.get("shrinks"),
                 epoch=survivor_row.get("epoch"),
                 crc_exact=bool(crc_exact))
    return {
        "scenario": "fleet_shrink",
        "fault_fired": bool(victim_killed),
        "detected": bool(shrank),
        "recovered": bool(survivor_completed and crc_exact),
        "survivor_exit": procs[0].returncode,
        "fleet_crc_agrees": bool(fleet_agrees),
        "uninterrupted_crc": fleet_crc,
        "survivor_crc": survivor_row.get("crc"),
        "shrinks": survivor_row.get("shrinks"),
        "fleet_epoch": survivor_row.get("epoch"),
        "ok": bool(victim_killed and survivor_completed and shrank
                   and fleet_agrees and crc_exact),
    }


def scenario_fleet_rejoin() -> dict:
    """Elastic fleet membership (ISSUE 20), the rejoin half, over the
    in-process threaded Rendezvous fabric running the REAL driver: kill
    one of two 'hosts' mid-half (survivor shrinks and keeps training),
    restart it as a joiner — it must readmit through the health-gated
    handshake at an iteration boundary, get its slice back, and finish
    as a full member; BOTH finals must bit-match the uninterrupted
    single-process run, and a frame from the dead host's previous life
    must be provably fenced (StaleEpochError, stale_rejected >= 1)."""
    import tempfile
    import zlib

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.offload.elastic import run_threaded_fleet
    from cfk_tpu.offload.windowed import train_als_host_window

    def crc(model):
        c = zlib.crc32(np.asarray(model.user_factors,
                                  np.float32).tobytes())
        return f"{zlib.crc32(np.asarray(model.movie_factors, np.float32).tobytes(), c):08x}"

    ds = Dataset.from_coo(
        synthetic_netflix_coo(64, 32, 900, seed=0), num_shards=4,
        layout="tiled", tile_rows=16, chunk_elems=512, ring=True,
        ring_warn=False,
    )
    cfg = ALSConfig(rank=4, lam=0.05, num_iterations=6, seed=3,
                    num_shards=4, layout="tiled", exchange="hier_ring",
                    ici_group=2, health_check_every=1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        ref = crc(train_als_host_window(ds, cfg))
        with tempfile.TemporaryDirectory() as ck:
            out = run_threaded_fleet(
                ds, cfg, ckdir=ck, num_processes=2, kill_pid=1,
                kill_iteration=2, rejoin=True, zombie_probe=True,
                thread_timeout_s=240.0,
            )
    res = out["results"]
    survivor = res.get(0)
    joiner = res.get("1:rejoin")
    survivor_crc = None if isinstance(survivor, BaseException) else (
        crc(survivor) if survivor is not None else None)
    joiner_crc = None if isinstance(joiner, BaseException) else (
        crc(joiner) if joiner is not None else None)
    met0 = out["metrics"].get(0)
    metj = out["metrics"].get("1:rejoin")
    shrank = bool(met0 and met0.counters.get("fleet_shrinks", 0) >= 1)
    rejoined = bool(
        met0 and met0.counters.get("fleet_rejoins", 0) >= 1
        and metj and metj.counters.get("fleet_rejoined", 0) >= 1
    )
    fenced = (out["stale_rejected"] >= 1
              and out["stale_error"] is not None)
    crc_exact = survivor_crc == joiner_crc == ref
    return {
        "scenario": "fleet_rejoin",
        "fault_fired": bool(shrank),
        "detected": bool(fenced),
        "recovered": bool(rejoined and crc_exact),
        "fleet_epoch": out["epoch"],
        "stale_rejected": out["stale_rejected"],
        "reference_crc": ref,
        "survivor_crc": survivor_crc,
        "joiner_crc": joiner_crc,
        "ok": bool(shrank and rejoined and fenced and crc_exact
                   and out["epoch"] >= 2),
    }


def _stream_fixture(parts=2, n=60, new_users=(4242,)):
    """(dataset, config, base model, broker-with-produced-stream)."""
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.models.als import train_als
    from cfk_tpu.streaming import StreamProducer
    from cfk_tpu.transport import InMemoryBroker

    ds = _dataset()
    cfg = ALSConfig(rank=4, num_iterations=4, health_check_every=1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        base = train_als(ds, cfg)
    broker = InMemoryBroker()
    prod = StreamProducer(broker, num_partitions=parts)
    rng = np.random.default_rng(11)
    prod.send_many(
        rng.choice(ds.user_map.raw_ids, n),
        rng.choice(ds.movie_map.raw_ids, n),
        rng.integers(1, 6, n).astype(np.float32),
    )
    for raw in new_users:
        prod.send(raw, int(ds.movie_map.raw_ids[0]), 4.0)
    return ds, cfg, base, broker


def _stream_run(ds, cfg, transport, mgr_dir, base=None, batch_records=8,
                max_batches=None):
    import zlib

    from cfk_tpu.streaming import StreamConfig, StreamSession
    from cfk_tpu.transport import CheckpointManager

    sess = StreamSession(
        ds, cfg, transport, CheckpointManager(mgr_dir),
        stream=StreamConfig(batch_records=batch_records), base_model=base,
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        model = sess.run(max_batches=max_batches)
    crc = zlib.crc32(np.asarray(model.user_factors).tobytes())
    return sess, crc


def scenario_stream_duplicates() -> dict:
    """Duplicated + reordered + dropped delivery of the SAME updates log
    must fold in to factors bit-identical (crc32) to clean delivery — the
    exactly-once assembly (dedup by offset, offset sort, gap re-poll) plus
    seq dedup make misdelivery invisible to the math."""
    import tempfile

    from cfk_tpu.resilience.faults import FlakyPlan, FlakyTransport

    ds, cfg, base, broker = _stream_fixture()
    with tempfile.TemporaryDirectory() as da, \
            tempfile.TemporaryDirectory() as db:
        _, crc_clean = _stream_run(ds, cfg, broker, da, base=base)
        flaky = FlakyTransport(
            broker, FlakyPlan(duplicate=3, reorder=5, drop=7, seed=1)
        )
        sess, crc_flaky = _stream_run(ds, cfg, flaky, db, base=base)
    fired = bool(flaky.duplicated and flaky.reordered and flaky.dropped)
    bit_exact = crc_clean == crc_flaky
    return {
        "scenario": "stream_duplicates",
        "fault_fired": fired,
        "duplicated": flaky.duplicated,
        "reordered": flaky.reordered,
        "dropped": flaky.dropped,
        # detection = the consumer's dedup/gap counters saw the faults
        "detected": bool(
            sess.metrics.counters.get("delivery_duplicates", 0) > 0
            and sess.metrics.counters.get("delivery_gap_repolls", 0) > 0
        ),
        "recovered": bit_exact,
        "factors_bit_exact": bit_exact,
        "clean_crc32": crc_clean,
        "faulty_crc32": crc_flaky,
        "ok": bool(fired and bit_exact),
    }


def scenario_stream_crash_replay() -> dict:
    """Crash mid-stream (process dies between commits): a fresh session
    resumes from the atomically-committed factor+cursor step, replays
    exactly the uncommitted log suffix, and converges to factors
    bit-identical to an uninterrupted run.  The final commit of the
    crashed run is ALSO torn (factors written, 'cursor write' lost —
    atomicity's worst case), which crc verification rejects wholesale."""
    import tempfile

    from cfk_tpu.resilience.faults import TornCheckpointManager
    from cfk_tpu.streaming import StreamConfig, StreamSession
    from cfk_tpu.transport import CheckpointManager

    ds, cfg, base, broker = _stream_fixture()
    with tempfile.TemporaryDirectory() as da, \
            tempfile.TemporaryDirectory() as db:
        _, crc_clean = _stream_run(ds, cfg, broker, da, base=base)
        # crashed run: 2 batches commit, then the 3rd commit is torn and
        # the process "dies" (session abandoned)
        torn = TornCheckpointManager(CheckpointManager(db), tear_at=3)
        s_crash = StreamSession(
            ds, cfg, broker, torn,
            stream=StreamConfig(batch_records=8), base_model=base,
        )
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            s_crash.run(max_batches=3)
        tear_fired = bool(torn.torn)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            s_resume = StreamSession(
                ds, cfg, broker, CheckpointManager(db),
                stream=StreamConfig(batch_records=8),
            )
            resumed_from = s_resume.stream_step
            import zlib

            model = s_resume.run()
            crc_replayed = zlib.crc32(
                np.asarray(model.user_factors).tobytes()
            )
    bit_exact = crc_clean == crc_replayed
    return {
        "scenario": "stream_crash_replay",
        "fault_fired": tear_fired,
        "detected": bool(resumed_from == 2),  # torn step 3 was rejected
        "recovered": bit_exact,
        "resumed_from_step": resumed_from,
        "restored_cells": s_resume.metrics.counters.get(
            "restored_cells", 0),
        "factors_bit_exact": bit_exact,
        "clean_crc32": crc_clean,
        "replayed_crc32": crc_replayed,
        "ok": bool(tear_fired and resumed_from == 2 and bit_exact),
    }


def scenario_stream_poison_batch() -> dict:
    """Two poison classes in one stream: a singular micro-batch (λ=0, a
    new one-rating user) that the ladder's λ bump FIXES, then a NaN-rating
    batch that defeats every rung and must be QUARANTINED — rolled back
    without corrupting the served factors, offsets consumed so the stream
    never wedges, and good batches after the poison still apply."""
    import tempfile

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.models.als import train_als
    from cfk_tpu.resilience.faults import blockstructured_coo
    from cfk_tpu.streaming import StreamConfig, StreamProducer, StreamSession
    from cfk_tpu.transport import CheckpointManager, InMemoryBroker

    ds = Dataset.from_coo(blockstructured_coo(seed=0))
    cfg = ALSConfig(rank=4, num_iterations=4, lam=0.0, health_check_every=1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        base = train_als(ds, cfg)
    broker = InMemoryBroker()
    prod = StreamProducer(broker)
    victim = int(ds.user_map.raw_ids[0])
    good_user = int(ds.user_map.raw_ids[1])
    prod.send(777, int(ds.movie_map.raw_ids[0]), 5.0)       # singular batch
    prod.send(victim, int(ds.movie_map.raw_ids[1]), float("nan"))  # poison
    prod.send(good_user, int(ds.movie_map.raw_ids[2]), 4.0)  # good after
    with tempfile.TemporaryDirectory() as d:
        sess = StreamSession(
            ds, cfg, broker, CheckpointManager(d),
            stream=StreamConfig(batch_records=1), base_model=base,
        )
        u_before = np.array(sess.user_factors)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            model = sess.run()
    u_after = np.asarray(model.user_factors)
    vrow = sess.state.user_row(victim)
    grow = sess.state.user_row(good_user)
    trips = sess.metrics.counters.get("health_trips", 0)
    escalated = sess.metrics.gauges.get("stream_escalation_level", 0) >= 1
    quarantined = len(sess.quarantined) == 1
    victim_intact = bool(np.array_equal(u_after[vrow], u_before[vrow]))
    good_applied = not np.array_equal(u_after[grow], u_before[grow])
    finite = bool(np.all(np.isfinite(u_after)))
    drained = sess.backlog() == 0
    return {
        "scenario": "stream_poison_batch",
        "fault_fired": True,  # both poisons are injected by construction
        "detected": bool(trips >= 2),  # sentinel tripped on both batches
        "recovered": bool(escalated and quarantined and victim_intact
                          and finite),
        "health_trips": int(trips),
        "lambda_escalated": bool(escalated),
        "quarantined_batches": sess.quarantined,
        "served_factors_intact": victim_intact,
        "good_batch_after_poison_applied": bool(good_applied),
        "stream_drained": bool(drained),
        "ok": bool(trips >= 2 and escalated and quarantined
                   and victim_intact and good_applied and finite
                   and drained),
    }



def scenario_quantized_table() -> dict:
    """ISSUE 7: the recovery ladder's split-epilogue and GJ rungs must
    work with a QUANTIZED gather table (table_dtype=bfloat16, the tiled
    pallas stack).  Four one-shot NaN corruptions on consecutive
    iterations force the ladder through every rung — retry, λ bump,
    split epilogue, GJ elimination — so the run finishes with the split
    schedule AND the GJ kernels pinned while every half-step gathers from
    the bf16 table; recovered RMSE parity proves those rungs solve
    correctly under quantization."""
    import dataclasses as _dc

    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.resilience.faults import FactorCorruption, FaultInjector
    from cfk_tpu.utils.metrics import Metrics

    ds = Dataset.from_coo(
        synthetic_netflix_coo(60, 30, 900, seed=0), layout="tiled",
        chunk_elems=512, tile_rows=16,
    )
    # lam_escalation=1.5 keeps the two λ bumps the full ladder applies
    # (rungs 2 and 4) inside the RMSE-parity budget — the scenario proves
    # the RUNGS execute under quantization, not λ×100 robustness.
    cfg = _dc.replace(
        _base_cfg(), layout="tiled", solver="pallas",
        table_dtype="bfloat16", max_recoveries=5, lam_escalation=1.5,
    )
    base_rmse = _rmse(_train(ds, cfg), ds)
    inj = FaultInjector(*[
        FactorCorruption(iteration=i, side="u") for i in (1, 2, 3, 4)
    ])
    metrics = Metrics()
    rec = _train(ds, cfg, metrics=metrics, fault_injector=inj)
    # level 4 = the GJ rung was reached (3 = split epilogue); both must
    # have executed for this scenario to prove anything
    return _row("quantized_table", fired=inj.fired, metrics=metrics,
                base_rmse=base_rmse, rec_rmse=_rmse(rec, ds),
                ok_extra=metrics.gauges.get("escalation_level", 0) >= 4)


def scenario_plan_fallback() -> dict:
    """ISSUE 9: a plan whose preferred kernel backend goes away MID-RUN
    degrades to the xla_emulation backend through the recovery ladder,
    with BIT-EXACT factors and the transition in provenance.

    The ``BackendOutage`` fault marks ``mosaic_tpu`` unavailable in the
    kernel registry at iteration 2 and NaNs a few factor rows (the
    symptom of kernels failing under a compiled program).  The sentinel
    trips; the resilient loop rolls back and — seeing the registry
    generation moved — rebuilds the step even at escalation rung 1, so
    the replay traces through ``resolve_gather_mode``/``resolve_fused_
    chunk_lam`` with mosaic down and lands on the emulation schedule.
    Escalation overrides are UNCHANGED (λ untouched), and the gather/
    fused knob routes are bit-identical by contract, so the recovered
    factors must equal the fault-free run's crc32 exactly — a far
    stronger check than RMSE parity.  The plan transition (reason
    ``backend_outage``) must appear in the metrics notes AND in the
    checkpoint-manifest provenance vocabulary."""
    import dataclasses as _dc
    import zlib

    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.resilience.faults import BackendOutage, FaultInjector
    from cfk_tpu.utils.metrics import Metrics

    ds = Dataset.from_coo(
        synthetic_netflix_coo(60, 30, 900, seed=0), layout="tiled",
        chunk_elems=512, tile_rows=16,
    )
    cfg = _dc.replace(_base_cfg(), layout="tiled", solver="pallas")

    def crc(model):
        return zlib.crc32(np.asarray(
            model.user_factors, np.float32
        ).tobytes())

    # Fault-free reference THROUGH THE SAME stepped loop (a no-op
    # injector), so loop structure cannot explain a crc difference.
    base = _train(ds, cfg, fault_injector=FaultInjector())
    base_rmse, base_crc = _rmse(base, ds), crc(base)
    outage = BackendOutage(iteration=2)
    metrics = Metrics()
    try:
        rec = _train(ds, cfg, metrics=metrics,
                     fault_injector=FaultInjector(outage))
    finally:
        outage.restore()
    rec_rmse, rec_crc = _rmse(rec, ds), crc(rec)
    transition = any(
        k.startswith("plan_transition") and "unavailable" in v
        for k, v in metrics.notes.items()
    )
    row = _row("plan_fallback", fired=outage.fired, metrics=metrics,
               base_rmse=base_rmse, rec_rmse=rec_rmse,
               ok_extra=transition and rec_crc == base_crc)
    row["bit_exact"] = bool(rec_crc == base_crc)
    row["transition_recorded"] = bool(transition)
    return row


def scenario_offload_window() -> dict:
    """ISSUE 11: the out-of-core windowed trainer detects and recovers
    from staged-window faults with BIT-EXACT factors.

    Two drills on the same stream-tiled dataset, both against a fault-free
    windowed run whose crc32 must equal the RESIDENT trainer's (the
    windowed==resident contract that makes bit-exact recovery meaningful):

    1. ``nan``: a seeded ``HostWindowCorruption`` NaNs rows of one staged
       movie-side window at iteration 1 (no integrity checking — the
       poison reaches the kernels).  The factor sentinel trips, the ladder
       rolls the host stores back to the last-good snapshot, and the
       replay (one-shot fault) lands crc-identical to fault-free.
    2. ``torn``: a torn window (second half stale zeros — finite and
       WRONG, invisible to isfinite) plus a ``SlowHostFetch`` delay plan.
       The staging checksum (``verify_windows``) catches the tear BEFORE
       any kernel consumes it; rollback + replay is crc-identical, and the
       delay plan fires throughout without perturbing a single bit.

    Both recoveries must be recorded as plan transitions in the
    provenance object riding the run."""
    import dataclasses as _dc
    import zlib

    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.offload.windowed import train_als_host_window
    from cfk_tpu.plan import plan_for_config
    from cfk_tpu.resilience.faults import (
        HostWindowCorruption,
        SlowHostFetch,
        WindowFaultInjector,
    )
    from cfk_tpu.utils.metrics import Metrics

    ds = Dataset.from_coo(
        synthetic_netflix_coo(60, 30, 900, seed=0), layout="tiled",
        chunk_elems=512, tile_rows=16, accum_max_entities=0,
    )
    cfg = _dc.replace(_base_cfg(), layout="tiled", solver="pallas")

    def crc(model):
        return zlib.crc32(np.asarray(
            model.user_factors, np.float32
        ).tobytes())

    base = train_als_host_window(ds, cfg, chunks_per_window=2)
    base_rmse, base_crc = _rmse(base, ds), crc(base)
    resident_crc = crc(_train(ds, cfg))

    nnz = int(ds.movie_blocks.count.sum())
    shape_kw = dict(num_users=ds.user_map.num_entities,
                    num_movies=ds.movie_map.num_entities, nnz=nnz)

    # Drill 1: NaN window, no integrity check — the factor sentinel path.
    nan_fault = WindowFaultInjector(
        HostWindowCorruption(iteration=1, side="m", window=0, kind="nan"),
    )
    m1 = Metrics()
    prov1 = plan_for_config(cfg, **shape_kw)[1]
    rec1 = train_als_host_window(
        ds, cfg, chunks_per_window=2, metrics=m1, window_faults=nan_fault,
        plan_provenance=prov1, verify_windows=False,
    )
    # Drill 2: torn window + slow-fetch delay — the staging-checksum path.
    torn_fault = WindowFaultInjector(
        HostWindowCorruption(iteration=2, side="u", window=0, kind="torn"),
        SlowHostFetch(delay_s=0.002, every=3),
    )
    m2 = Metrics()
    prov2 = plan_for_config(cfg, **shape_kw)[1]
    rec2 = train_als_host_window(
        ds, cfg, chunks_per_window=2, metrics=m2,
        window_faults=torn_fault, plan_provenance=prov2,
    )

    crc1, crc2 = crc(rec1), crc(rec2)
    transitions = bool(prov1.transitions) and bool(prov2.transitions)
    torn_detected = m2.counters.get("health_trips", 0) >= 1
    # Merge both drills' metrics into one row (the _row contract reads one
    # Metrics): counters/notes from drill 1, ok_extra covers drill 2.
    for k_, v in m2.counters.items():
        m1.counters[k_] = m1.counters.get(k_, 0) + v
    m1.notes.update({f"torn_{k_}": v for k_, v in m2.notes.items()})
    row = _row(
        "offload_window",
        fired=nan_fault.fired + torn_fault.fired,
        metrics=m1, base_rmse=base_rmse, rec_rmse=_rmse(rec1, ds),
        ok_extra=(
            base_crc == resident_crc
            and crc1 == base_crc and crc2 == base_crc
            and transitions and torn_detected
        ),
    )
    row["windowed_equals_resident"] = bool(base_crc == resident_crc)
    row["nan_bit_exact"] = bool(crc1 == base_crc)
    row["torn_bit_exact"] = bool(crc2 == base_crc)
    row["transitions_recorded"] = transitions
    row["slow_fetch_fired"] = int(torn_fault.faults[1].fired)
    return row


def scenario_offload_window_sharded() -> dict:
    """ISSUE 12: the SHARDED windowed trainer recovers fleet-wide from
    faults on ONE shard's staging pipeline with BIT-EXACT factors.

    Three fault classes on a 2-shard stream-tiled dataset, all against
    the fault-free sharded windowed run (itself crc-checked against the
    resident shard_map trainer when enough jax devices exist):

    1. ``nan`` on shard 1 only: the factor sentinel trips, the ladder
       rolls BOTH shards' host stores back to the last-good snapshot —
       one shard's poison must not leave the other shard's already-solved
       rows in the committed state — and the replay lands crc-identical.
    2. ``torn`` on shard 0 only: finite wrong bytes, invisible to
       isfinite; the PER-SHARD staging crc32 contract (``verify_windows``)
       catches it before any kernel consumes it, and rollback + replay is
       crc-identical fleet-wide.
    3. ``slow fetch`` on shard 1 only (a straggler host): fires
       throughout drill 2 without perturbing a single bit — the
       double-buffered per-shard staging absorbs it.
    """
    import dataclasses as _dc
    import zlib

    import jax as _jax

    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.offload.windowed import train_als_host_window
    from cfk_tpu.plan import plan_for_config
    from cfk_tpu.resilience.faults import (
        HostWindowCorruption,
        SlowHostFetch,
        WindowFaultInjector,
    )
    from cfk_tpu.utils.metrics import Metrics

    ds = Dataset.from_coo(
        synthetic_netflix_coo(60, 30, 900, seed=0), num_shards=2,
        layout="tiled", chunk_elems=512, tile_rows=16,
        accum_max_entities=0,
    )
    # hot_rows=0: this scenario drills the FULL-staging integrity path
    # (its window corruptions must land on staged table rows; under the
    # ISSUE 15 hot/delta engine a targeted window's delta can be EMPTY
    # on a tiny sharded shape and the fault would corrupt nothing).  The
    # hot engine's own fault paths — partition NaN + torn cold delta —
    # are the `hot_cache` scenario's job.
    cfg = _dc.replace(_base_cfg(num_shards=2), layout="tiled",
                      solver="pallas", hot_rows=0)

    def crc(model):
        return zlib.crc32(np.asarray(
            model.user_factors, np.float32
        ).tobytes())

    base = train_als_host_window(ds, cfg, chunks_per_window=2)
    base_rmse, base_crc = _rmse(base, ds), crc(base)
    resident_crc = None
    if len(_jax.devices()) >= 2:
        from cfk_tpu.parallel.mesh import make_mesh
        from cfk_tpu.parallel.spmd import train_als_sharded

        resident_crc = crc(train_als_sharded(ds, cfg, make_mesh(2)))

    nnz = int(ds.movie_blocks.count.sum())
    shape_kw = dict(num_users=ds.user_map.num_entities,
                    num_movies=ds.movie_map.num_entities, nnz=nnz)

    # Drill 1: NaN window on SHARD 1 only, no integrity check — the
    # factor sentinel path; recovery must restore the whole fleet.
    nan_fault = WindowFaultInjector(
        HostWindowCorruption(iteration=1, side="m", window=0, kind="nan",
                             shard=1),
    )
    m1 = Metrics()
    prov1 = plan_for_config(cfg, **shape_kw)[1]
    rec1 = train_als_host_window(
        ds, cfg, chunks_per_window=2, metrics=m1, window_faults=nan_fault,
        plan_provenance=prov1, verify_windows=False,
    )
    # Drill 2: torn window on SHARD 0 + a straggling shard-1 staging —
    # the per-shard staging-checksum path.
    torn_fault = WindowFaultInjector(
        HostWindowCorruption(iteration=2, side="u", window=0, kind="torn",
                             shard=0),
        SlowHostFetch(delay_s=0.002, every=2, only_shard=1),
    )
    m2 = Metrics()
    prov2 = plan_for_config(cfg, **shape_kw)[1]
    rec2 = train_als_host_window(
        ds, cfg, chunks_per_window=2, metrics=m2,
        window_faults=torn_fault, plan_provenance=prov2,
    )

    crc1, crc2 = crc(rec1), crc(rec2)
    transitions = bool(prov1.transitions) and bool(prov2.transitions)
    torn_detected = m2.counters.get("health_trips", 0) >= 1
    for k_, v in m2.counters.items():
        m1.counters[k_] = m1.counters.get(k_, 0) + v
    m1.notes.update({f"torn_{k_}": v for k_, v in m2.notes.items()})
    row = _row(
        "offload_window_sharded",
        fired=nan_fault.fired + torn_fault.fired,
        metrics=m1, base_rmse=base_rmse, rec_rmse=_rmse(rec1, ds),
        ok_extra=(
            (resident_crc is None or base_crc == resident_crc)
            and crc1 == base_crc and crc2 == base_crc
            and transitions and torn_detected
        ),
    )
    row["windowed_equals_resident"] = (
        None if resident_crc is None else bool(base_crc == resident_crc)
    )
    row["nan_on_one_shard_bit_exact"] = bool(crc1 == base_crc)
    row["torn_on_one_shard_bit_exact"] = bool(crc2 == base_crc)
    row["transitions_recorded"] = transitions
    row["slow_fetch_fired_on_straggler"] = int(torn_fault.faults[1].fired)
    return row


def scenario_hot_cache() -> dict:
    """ISSUE 15: faults in the skew-aware hot-row device cache.

    Two drills on the stream-tiled dataset, both with the hot/delta
    engine ON (auto resolution) against the hot-off AND resident crcs
    (the hot == full-staging == resident chain that makes bit-exact
    recovery meaningful):

    1. ``hot partition NaN``: ``HotCacheCorruption`` poisons rows of the
       DEVICE-RESIDENT user partition before the m half reads it (the
       host master is untouched).  The poison flows through assembled
       windows into solved factors, the sentinel trips, and rollback
       REBUILDS the partition from the host master — the replay
       (one-shot fault) lands crc-identical to fault-free.
    2. ``torn cold delta``: a ``HostWindowCorruption(kind='torn')`` on a
       staged COLD DELTA (with the hot engine on, the gathered rows the
       fault corrupts ARE the delta).  The existing staging crc32
       contract catches the tear BEFORE any kernel consumes it;
       rollback + replay is crc-identical — proving the integrity seam
       survived the staging-path change.

    Both recoveries are recorded as plan transitions; the flight dump's
    tail names the fault (``hot_cache_corruption`` / ``health_trip``)."""
    import dataclasses as _dc
    import zlib

    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.offload.windowed import train_als_host_window
    from cfk_tpu.plan import plan_for_config
    from cfk_tpu.resilience.faults import (
        HostWindowCorruption,
        HotCacheCorruption,
        WindowFaultInjector,
    )
    from cfk_tpu.utils.metrics import Metrics

    ds = Dataset.from_coo(
        synthetic_netflix_coo(60, 30, 900, seed=0), layout="tiled",
        chunk_elems=512, tile_rows=16, accum_max_entities=0,
    )
    cfg = _dc.replace(_base_cfg(), layout="tiled", solver="pallas")

    def crc(model):
        return zlib.crc32(np.asarray(
            model.user_factors, np.float32
        ).tobytes())

    m_base = Metrics()
    base = train_als_host_window(ds, cfg, chunks_per_window=2,
                                 metrics=m_base)
    base_rmse, base_crc = _rmse(base, ds), crc(base)
    hot_resolved = int(m_base.gauges.get("offload_hot_rows", 0))
    hot_off_crc = crc(train_als_host_window(ds, cfg, chunks_per_window=2,
                                            hot_rows=0))
    resident_crc = crc(_train(ds, cfg))

    nnz = int(ds.movie_blocks.count.sum())
    shape_kw = dict(num_users=ds.user_map.num_entities,
                    num_movies=ds.movie_map.num_entities, nnz=nnz)

    # Drill 1: NaN in the device-resident hot partition — the sentinel
    # path plus the rollback partition REBUILD.  Target the half whose
    # FIXED partition is non-empty (the auto knee may resolve one side
    # to 0 rows at this tiny shape): the m half reads the USER
    # partition, the u half the MOVIE one.
    nan_side = ("m" if m_base.gauges.get("offload_hot_rows_u", 0) > 0
                else "u")
    nan_fault = WindowFaultInjector(
        HotCacheCorruption(iteration=1, side=nan_side),
    )
    m1 = Metrics()
    prov1 = plan_for_config(cfg, **shape_kw)[1]
    rec1 = train_als_host_window(
        ds, cfg, chunks_per_window=2, metrics=m1, window_faults=nan_fault,
        plan_provenance=prov1, verify_windows=False,
    )
    # Drill 2: torn COLD-DELTA stage — the staging crc32 contract on the
    # hot engine's residual staging path.
    torn_fault = WindowFaultInjector(
        HostWindowCorruption(iteration=2, side="u", window=0,
                             kind="torn"),
    )
    m2 = Metrics()
    prov2 = plan_for_config(cfg, **shape_kw)[1]
    rec2 = train_als_host_window(
        ds, cfg, chunks_per_window=2, metrics=m2,
        window_faults=torn_fault, plan_provenance=prov2,
    )

    crc1, crc2 = crc(rec1), crc(rec2)
    transitions = bool(prov1.transitions) and bool(prov2.transitions)
    torn_detected = m2.counters.get("health_trips", 0) >= 1
    for k_, v in m2.counters.items():
        m1.counters[k_] = m1.counters.get(k_, 0) + v
    m1.notes.update({f"torn_{k_}": v for k_, v in m2.notes.items()})
    row = _row(
        "hot_cache",
        fired=nan_fault.fired + torn_fault.fired,
        metrics=m1, base_rmse=base_rmse, rec_rmse=_rmse(rec1, ds),
        ok_extra=(
            hot_resolved > 0
            and base_crc == hot_off_crc == resident_crc
            and crc1 == base_crc and crc2 == base_crc
            and transitions and torn_detected
        ),
    )
    row["hot_rows_resolved"] = hot_resolved
    row["hot_equals_off_equals_resident"] = bool(
        base_crc == hot_off_crc == resident_crc
    )
    row["hot_nan_rebuild_bit_exact"] = bool(crc1 == base_crc)
    row["torn_delta_bit_exact"] = bool(crc2 == base_crc)
    row["transitions_recorded"] = transitions
    return row


def scenario_offload_ials() -> dict:
    """ISSUE 19: the out-of-core iALS++ subspace driver detects and
    recovers from staged width-class-window faults with BIT-EXACT
    factors — and the rollback rebuilds BOTH device-resident carries,
    the hot partition (from the restored host masters) and the
    global-Gram accumulator (recomputed from those masters at the next
    half's reduction; it has no snapshot because it needs none).

    Two drills on a bucketed implicit dataset, both against a fault-free
    windowed run whose crc32 must equal the RESIDENT ``train_ials``
    run's (the windowed==resident contract for the subspace family):

    1. ``nan``: a seeded ``HostWindowCorruption`` NaNs rows of one
       staged width-class window mid-sweep at iteration 1 (no integrity
       checking — the poison reaches the b×b subspace kernels).  The
       factor sentinel trips, the ladder rolls the host stores back,
       the hot partition rebuilds, the Gram reduction recomputes, and
       the replay (one-shot fault) lands crc-identical to fault-free.
    2. ``torn``: finite-wrong bytes in a staged window — the staging
       checksum (``verify_windows``) catches the tear BEFORE any
       subspace kernel consumes it; rollback + replay is crc-identical.

    Both recoveries are recorded as plan transitions; the flight dump's
    tail names the fault (``health_trip``)."""
    import zlib

    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.models.ials import IALSConfig, train_ials
    from cfk_tpu.offload.windowed import train_ials_host_window
    from cfk_tpu.plan import plan_for_config
    from cfk_tpu.resilience.faults import (
        HostWindowCorruption,
        WindowFaultInjector,
    )
    from cfk_tpu.utils.metrics import Metrics

    ds = Dataset.from_coo(
        synthetic_netflix_coo(60, 30, 900, seed=0), layout="bucketed",
        chunk_elems=512,
    )
    cfg = IALSConfig(
        rank=4, num_iterations=6, health_check_every=1, lam=0.1,
        alpha=40.0, layout="bucketed", algorithm="ials++", block_size=2,
    )
    hot = 16  # pinned so the rollback's partition REBUILD is exercised

    def crc(model):
        return zlib.crc32(np.asarray(
            model.user_factors, np.float32
        ).tobytes())

    m_base = Metrics()
    base = train_ials_host_window(ds, cfg, chunks_per_window=2,
                                  hot_rows=hot, metrics=m_base)
    base_rmse, base_crc = _rmse(base, ds), crc(base)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        resident_crc = crc(train_ials(ds, cfg))
    gram_staged = float(m_base.gauges.get("offload_gram_staged_mb", 0))
    hot_resolved = int(m_base.gauges.get("offload_hot_rows", 0))

    nnz = int(ds.movie_blocks.count.sum())
    shape_kw = dict(num_users=ds.user_map.num_entities,
                    num_movies=ds.movie_map.num_entities, nnz=nnz,
                    implicit=True)

    # Drill 1: NaN width-class window mid-sweep — the sentinel path plus
    # the hot-partition + Gram-accumulator rebuild on rollback.
    nan_fault = WindowFaultInjector(
        HostWindowCorruption(iteration=1, side="m", window=0, kind="nan"),
    )
    m1 = Metrics()
    prov1 = plan_for_config(cfg, **shape_kw)[1]
    rec1 = train_ials_host_window(
        ds, cfg, chunks_per_window=2, hot_rows=hot, metrics=m1,
        window_faults=nan_fault, plan_provenance=prov1,
        verify_windows=False,
    )
    # Drill 2: torn window — the staging-checksum path.
    torn_fault = WindowFaultInjector(
        HostWindowCorruption(iteration=2, side="u", window=0,
                             kind="torn"),
    )
    m2 = Metrics()
    prov2 = plan_for_config(cfg, **shape_kw)[1]
    rec2 = train_ials_host_window(
        ds, cfg, chunks_per_window=2, hot_rows=hot, metrics=m2,
        window_faults=torn_fault, plan_provenance=prov2,
    )

    crc1, crc2 = crc(rec1), crc(rec2)
    transitions = bool(prov1.transitions) and bool(prov2.transitions)
    torn_detected = m2.counters.get("health_trips", 0) >= 1
    for k_, v in m2.counters.items():
        m1.counters[k_] = m1.counters.get(k_, 0) + v
    m1.notes.update({f"torn_{k_}": v for k_, v in m2.notes.items()})
    row = _row(
        "offload_ials",
        fired=nan_fault.fired + torn_fault.fired,
        metrics=m1, base_rmse=base_rmse, rec_rmse=_rmse(rec1, ds),
        ok_extra=(
            base_crc == resident_crc
            and crc1 == base_crc and crc2 == base_crc
            and transitions and torn_detected
            and gram_staged > 0 and hot_resolved > 0
        ),
    )
    row["windowed_equals_resident"] = bool(base_crc == resident_crc)
    row["nan_bit_exact"] = bool(crc1 == base_crc)
    row["torn_bit_exact"] = bool(crc2 == base_crc)
    row["transitions_recorded"] = transitions
    row["gram_staged_mb"] = gram_staged
    row["hot_rows_resolved"] = hot_resolved
    return row


def scenario_staging_pool() -> dict:
    """ISSUE 13: faults INSIDE the pooled host staging engine.

    Four drills on a 2-shard stream-tiled dataset, all with
    ``staging="pool"`` against the serial engine's fault-free crc (which
    itself must equal the pooled fault-free crc — the pooled == serial
    contract that makes the recoveries meaningful):

    1. ``straggler``: ``SlowHostFetch(only_shard=1)`` delays one shard's
       staging inside pool workers.  The other shard's windows keep
       staging (``pool_peak_inflight >= 2`` proves concurrent staging
       around the straggler), the half-iteration barrier holds, and the
       factors drift zero bits.
    2. ``nan``: a pool WORKER stages a NaN-poisoned window (the fault
       must fire on a ``cfk-stage-*`` thread — pinned via ``fired_in``).
       The factor sentinel trips and the ladder recovers crc-exact.
    3. ``torn``: finite-wrong bytes staged by a worker; the per-shard
       staging crc32 contract catches it BEFORE any kernel consumes it
       (the ``WindowIntegrityError`` propagates from the worker through
       ``WindowStager.take`` — not a hang), rollback + replay crc-exact.
    4. ``crash``: ``StagingCrash`` raises an arbitrary exception inside
       a worker; it must surface as the run's error, not hang the pool.
    """
    import dataclasses as _dc
    import zlib

    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.offload.windowed import train_als_host_window
    from cfk_tpu.resilience.faults import (
        HostWindowCorruption,
        SlowHostFetch,
        StagingCrash,
        WindowFaultInjector,
    )
    from cfk_tpu.utils.metrics import Metrics

    ds = Dataset.from_coo(
        synthetic_netflix_coo(60, 30, 900, seed=0), num_shards=2,
        layout="tiled", chunk_elems=512, tile_rows=16,
        accum_max_entities=0,
    )
    cfg = _dc.replace(_base_cfg(num_shards=2), layout="tiled",
                      solver="pallas")

    def crc(model):
        return zlib.crc32(np.asarray(
            model.user_factors, np.float32
        ).tobytes())

    serial_crc = crc(train_als_host_window(ds, cfg, chunks_per_window=2,
                                           staging="serial"))
    base = train_als_host_window(ds, cfg, chunks_per_window=2,
                                 staging="pool")
    base_rmse, base_crc = _rmse(base, ds), crc(base)

    # Drill 1: straggler shard — purely timing, zero drift, others
    # proceed (peak in-flight staging >= 2 while shard 1 sleeps).
    slow = WindowFaultInjector(
        SlowHostFetch(delay_s=0.004, every=1, only_shard=1),
    )
    m1 = Metrics()
    rec1 = train_als_host_window(ds, cfg, chunks_per_window=2,
                                 staging="pool", metrics=m1,
                                 window_faults=slow,
                                 verify_windows=False)
    crc1 = crc(rec1)
    peak = m1.gauges.get("offload_pool_peak_inflight", 0)

    # Drill 2: NaN window staged BY A POOL WORKER — sentinel path.
    nan_fault = HostWindowCorruption(iteration=1, side="m", window=0,
                                     kind="nan", shard=1)
    inj2 = WindowFaultInjector(nan_fault)
    m2 = Metrics()
    rec2 = train_als_host_window(ds, cfg, chunks_per_window=2,
                                 staging="pool", metrics=m2,
                                 window_faults=inj2, verify_windows=False)
    crc2 = crc(rec2)
    nan_in_worker = any(t.startswith("cfk-stage")
                        for t in nan_fault.fired_in)

    # Drill 3: torn window staged by a worker — the staging crc32
    # contract catches it pre-kernel; the WindowIntegrityError crosses
    # the pool boundary as the staging error.
    torn_fault = HostWindowCorruption(iteration=1, side="u", window=0,
                                      kind="torn", shard=0)
    inj3 = WindowFaultInjector(torn_fault)
    m3 = Metrics()
    rec3 = train_als_host_window(ds, cfg, chunks_per_window=2,
                                 staging="pool", metrics=m3,
                                 window_faults=inj3)
    crc3 = crc(rec3)
    torn_in_worker = any(t.startswith("cfk-stage")
                         for t in torn_fault.fired_in)
    torn_detected = m3.counters.get("health_trips", 0) >= 1

    # Drill 4: a worker exception propagates as the staging error.
    crash = StagingCrash(iteration=0, side="m", window=0,
                         message="chaos: staging crash drill")
    crashed = False
    try:
        train_als_host_window(ds, cfg, chunks_per_window=2,
                              staging="pool",
                              window_faults=WindowFaultInjector(crash))
    except RuntimeError as e:
        crashed = "staging crash drill" in str(e)
    crash_in_worker = any(t.startswith("cfk-stage")
                          for t in crash.fired_in)

    for extra in (m2, m3):
        for k_, v in extra.counters.items():
            m1.counters[k_] = m1.counters.get(k_, 0) + v
    row = _row(
        "staging_pool",
        fired=(slow.fired + nan_fault.fired + torn_fault.fired
               + crash.fired),
        metrics=m1, base_rmse=base_rmse, rec_rmse=_rmse(rec2, ds),
        ok_extra=(
            base_crc == serial_crc
            and crc1 == base_crc and crc2 == base_crc
            and crc3 == base_crc
            and peak >= 2 and nan_in_worker and torn_in_worker
            and torn_detected and crashed and crash_in_worker
        ),
    )
    row["pooled_equals_serial"] = bool(base_crc == serial_crc)
    row["straggler_bit_exact"] = bool(crc1 == base_crc)
    row["straggler_pool_peak_inflight"] = int(peak)
    row["nan_from_worker_bit_exact"] = bool(crc2 == base_crc)
    row["nan_fired_in_worker"] = nan_in_worker
    row["torn_from_worker_bit_exact"] = bool(crc3 == base_crc)
    row["torn_fired_in_worker"] = torn_in_worker
    row["worker_exception_propagated"] = crashed
    return row


def scenario_serve_under_foldin() -> dict:
    """ISSUE 8: serving stays correct while streaming fold-in commits land
    concurrently.  A RecommendServer thread answers a continuous request
    stream for a victim user while the main thread drains fold-in batches
    that re-solve that user's factor row; the serve engine's hot-row cache
    is invalidated through the session's commit listener.  Contract:
    (1) FRESHNESS — a request issued after a commit returns scores
    bit-identical to scoring the committed factors (and excludes the
    just-rated movie); (2) NO TORN READS — every response the hammering
    thread observed matches EXACTLY one committed snapshot of the victim's
    row (base or post-commit-N), never a mixture or a half-written row."""
    import tempfile
    import threading

    from cfk_tpu.serving import (
        RecommendServer,
        ServeClient,
        ServeEngine,
        engine_from_model,
        ensure_serve_topics,
    )
    from cfk_tpu.streaming import StreamConfig, StreamProducer, StreamSession
    from cfk_tpu.transport import CheckpointManager, InMemoryBroker

    ds, cfg, base, broker = _stream_fixture(parts=1, n=24, new_users=())
    victim = int(ds.user_map.raw_ids[0])
    prod = StreamProducer(broker)
    rated = [int(m) for m in ds.movie_map.raw_ids[3:6]]
    for mv in rated:  # three extra batches each re-solving the victim
        prod.send(victim, mv, 5.0)
    k = 5
    eng = engine_from_model(base, ds)
    vrow = int(ds.user_map.to_dense(np.asarray([victim]))[0])
    ensure_serve_topics(broker, response_partitions=2)
    server = RecommendServer(eng, broker, poll_wait_s=0.001)
    main_cli = ServeClient(broker, reply_partition=0)

    # committed snapshots of the victim's (factor row, seen set) — base
    # first, then one per commit event, captured through the SAME listener
    # channel the engine uses
    snapshots = [(np.array(eng._gather_users(np.asarray([vrow]))[0]),
                  tuple())]

    def snap_listener(event):
        if event.get("retrain") or vrow not in (event.get("touched_rows")
                                                or ()):
            return
        i = event["touched_rows"].index(vrow)
        extra = tuple(mv for row, mv in event["cells"] if row == vrow)
        prev = snapshots[-1][1]
        snapshots.append((np.array(event["rows"][i]), prev + extra))

    with tempfile.TemporaryDirectory() as d:
        sess = StreamSession(
            ds, cfg, broker, CheckpointManager(d),
            stream=StreamConfig(batch_records=1), base_model=base,
        )
        sess.add_commit_listener(snap_listener)
        eng.attach_session(sess)
        main_cli.ask([vrow], k, server=server)  # warm the serve path
        stop = threading.Event()
        hammered: list = []

        def hammer():
            import time as _t

            cli = ServeClient(broker, reply_partition=1)
            while not stop.is_set():
                rid = cli.request(vrow, k)
                deadline = _t.monotonic() + 5.0
                got = None
                while got is None:
                    for resp in cli.poll_responses():
                        if resp.req_id == rid:
                            got = resp
                    if _t.monotonic() > deadline:
                        return
                    _t.sleep(0.0005)
                hammered.append(got)

        srv_thread = threading.Thread(
            target=server.serve_forever, kwargs={"stop": stop.is_set},
            daemon=True,
        )
        ham_thread = threading.Thread(target=hammer, daemon=True)
        srv_thread.start()
        ham_thread.start()
        post = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            while sess.step() is not None:
                # a request issued strictly AFTER this commit returned
                post.append(next(iter(
                    main_cli.ask([vrow], k).values()
                )))
        stop.set()
        srv_thread.join(timeout=10)
        ham_thread.join(timeout=10)
        # same exit contract as sess.run(): drain the async checkpoint
        # writer before the directory goes away
        from cfk_tpu.resilience.loop import drain_checkpoints

        drain_checkpoints(sess.manager)
    commits = len(snapshots) - 1

    def expected_for(u_row, extra_seen):
        # a throwaway 1-row engine scoring exactly this committed snapshot
        # (same table, the victim's base CSR remapped onto row 0)
        lo, hi = int(eng._seen_indptr[vrow]), int(eng._seen_indptr[vrow + 1])
        e2 = ServeEngine(
            u_row[None, :], np.asarray(base.movie_factors),
            num_users=1, num_movies=eng.num_movies,
            seen_movies=eng._seen_movies[lo:hi],
            seen_indptr=np.asarray([0, hi - lo], np.int64),
        )
        if extra_seen:
            e2._extend_seen([(0, mv) for mv in extra_seen])
        sc, ids_ = e2.topk(np.asarray([0]), k)
        return sc[0], ids_[0]

    expected = [expected_for(u, seen) for u, seen in snapshots]
    final_scores, final_ids = expected[-1]
    fresh = bool(
        post
        and np.array_equal(np.asarray(post[-1].scores), final_scores)
        and np.array_equal(np.asarray(post[-1].movie_rows), final_ids)
    )
    rated_rows = set(int(ds.movie_map.to_dense(np.asarray([m]))[0])
                     for m in rated)
    excluded = bool(post) and not (
        set(int(x) for x in np.asarray(post[-1].movie_rows)) & rated_rows
    )
    torn = [
        resp.req_id for resp in hammered
        if not any(
            np.array_equal(np.asarray(resp.scores), ev)
            and np.array_equal(np.asarray(resp.movie_rows), ei)
            for ev, ei in expected
        )
    ]
    return {
        "scenario": "serve_under_foldin",
        "fault_fired": bool(commits >= 3 and hammered),
        "detected": bool(eng.invalidations >= 3),  # cache saw every commit
        "recovered": bool(fresh and excluded and not torn),
        "commits": commits,
        "cache_invalidations": int(eng.invalidations),
        "concurrent_responses": len(hammered),
        "post_commit_fresh": fresh,
        "just_rated_excluded": excluded,
        "torn_responses": torn,
        "ok": bool(commits >= 3 and hammered and eng.invalidations >= 3
                   and fresh and excluded and not torn),
    }


def _fleet_fixture(replicas, transport=None, seed=0, users=48, movies=64,
                   rank=6, **fleet_kw):
    """(fleet, publisher, broker, (u, m), oracle_engine) — a prewarmed
    serving fleet over synthetic factors with the store seeded; the
    oracle is a fresh engine over the same factors (the torn-read and
    crc witnesses)."""
    from cfk_tpu.serving import DeltaPublisher, ServeEngine, ServeFleet
    from cfk_tpu.transport import InMemoryBroker

    rng = np.random.default_rng(seed)
    u = rng.standard_normal((users, rank)).astype(np.float32)
    m = rng.standard_normal((movies, rank)).astype(np.float32)

    def engine(i=0):
        return ServeEngine(u, m, num_users=users, num_movies=movies,
                           tile_m=16)

    broker = InMemoryBroker()
    fleet = ServeFleet(engine, transport if transport is not None
                       else broker, replicas=replicas, **fleet_kw)
    fleet.seed_store(u, m, num_users=users)
    fleet.prewarm(5, max_batch=16)
    pub = DeltaPublisher(broker, fleet.store)
    return fleet, pub, broker, (u, m), engine()


def scenario_serve_replica_kill() -> dict:
    """ISSUE 18: killing a serving replica mid-traffic loses NOTHING.
    A 2-replica fleet answers a user-keyed request stream; replica 0 is
    killed abruptly (no cursor commit, no farewell) partway through.
    Contract: (1) NO LOST REQUESTS — every accepted request gets a
    response or an explicit retriable rejection (the client's bounded
    retry then re-sends; zero TimeoutErrors); (2) NO TORN READS — every
    response bit-matches the oracle engine over the same factors;
    (3) STALENESS RECORDED — every response carries a staleness stamp;
    (4) FAILOVER — the victim's partition moves to the survivor at the
    committed cursor and its users keep being answered."""
    from cfk_tpu.serving import ServeClient

    fleet, pub, broker, (u, m), oracle = _fleet_fixture(replicas=2)
    k = 5
    client = ServeClient(broker, route="user")
    answered = []
    timeouts = 0
    fleet.start()
    try:
        for wave in range(6):
            if wave == 3:
                fleet.kill_replica(0)  # abrupt, mid-stream
            for user in range(0, 16):
                try:
                    got = client.ask([user], k, timeout_s=20)
                    answered.append((user, next(iter(got.values()))))
                except TimeoutError:
                    timeouts += 1
    finally:
        fleet.stop()
    torn = []
    stamped = True
    for user, resp in answered:
        sc, ids = oracle.topk(np.asarray([user]), k)
        if not (np.array_equal(np.asarray(resp.scores), sc[0])
                and np.array_equal(np.asarray(resp.movie_rows), ids[0])):
            torn.append(user)
        stamped &= resp.staleness >= 0
    c = fleet.counters()
    return {
        "scenario": "serve_replica_kill",
        "fault_fired": bool(c["failovers"] == 1
                            and not fleet.replicas[0].alive),
        "detected": bool(c["failovers"] == 1),
        "recovered": bool(timeouts == 0 and len(answered) == 96
                          and not torn),
        "requests_answered": len(answered),
        "timeouts": timeouts,
        "torn_responses": torn,
        "staleness_stamped": bool(stamped),
        "client_retries": int(client.retries),
        "client_rejections": int(client.rejections),
        "survivor_served": int(
            fleet.replicas[1].server.requests_served
        ),
        "ok": bool(c["failovers"] == 1 and timeouts == 0
                   and len(answered) == 96 and not torn and stamped),
    }


def scenario_serve_delta_gap() -> dict:
    """ISSUE 18: a lost factor-delta frame must be detected LOUDLY and
    recovered bit-exactly.  A DeltaStreamTamper permanently hides one
    frame of the deltas topic from the replica; the publisher keeps
    shipping commits.  Contract: (1) DETECTED — the seq hole fires the
    gap path (flight event + dump, counter); (2) RECOVERED CRC-EXACT —
    the epoch-snapshot resync rebuilds user-side state bit-identical to
    a fresh engine that applied EVERY commit (table_crc); (3) SERVES
    FRESH — a post-resync request returns the re-solved factors' scores,
    including rows shipped only in the hidden frame."""
    from cfk_tpu.resilience.faults import DeltaStreamTamper
    from cfk_tpu.serving import ServeClient, ensure_serve_topics, table_crc
    from cfk_tpu.transport import InMemoryBroker

    broker = InMemoryBroker()
    tampered = DeltaStreamTamper(broker, topic="factor-deltas", hide=[2])
    fleet, pub, _, (u, m), oracle = _fleet_fixture(
        replicas=1, transport=tampered,
    )
    # _fleet_fixture built its own broker for the publisher — rewire the
    # publisher onto the REAL log underneath the tamper
    from cfk_tpu.serving import DeltaPublisher

    pub = DeltaPublisher(broker, fleet.store)
    ensure_serve_topics(broker)
    rng = np.random.default_rng(3)
    replica = fleet.replicas[0]
    victim_rows = None
    for i in range(6):
        rows = rng.integers(0, 48, size=3)
        ev = {
            "touched_rows": [int(r) for r in rows],
            "rows": rng.standard_normal((3, 6)).astype(np.float32),
            "cells": [], "retrain": False, "num_users": 48,
        }
        if i == 2:
            victim_rows = [int(r) for r in rows]  # only in hidden frame
        pub.on_commit(ev)
        oracle.on_commit(ev)
    replica.pump()
    crc_match = table_crc(replica.engine) == table_crc(oracle)
    # post-resync serving answers from the fully-recovered table
    client = ServeClient(broker)
    got = client.ask([victim_rows[0]], 5, server=replica.server)
    resp = next(iter(got.values()))
    sc, ids = oracle.topk(np.asarray([victim_rows[0]]), 5)
    fresh = bool(np.array_equal(np.asarray(resp.scores), sc[0])
                 and np.array_equal(np.asarray(resp.movie_rows), ids[0]))
    return {
        "scenario": "serve_delta_gap",
        "fault_fired": bool(tampered.hidden >= 1),
        "detected": bool(replica.gaps_detected >= 1),
        "recovered": bool(replica.resyncs >= 1 and crc_match and fresh),
        "frames_hidden": int(tampered.hidden),
        "gaps_detected": int(replica.gaps_detected),
        "resyncs": int(replica.resyncs),
        "applied_seq": int(replica.applied_seq),
        "crc_exact_vs_fresh_engine": bool(crc_match),
        "post_resync_fresh": fresh,
        "ok": bool(tampered.hidden >= 1 and replica.gaps_detected >= 1
                   and replica.resyncs >= 1 and crc_match and fresh),
    }


def scenario_serve_rollover() -> dict:
    """ISSUE 18: a warm-retrain epoch rollover under continuous traffic
    serves EVERY request and never shows a mixed-epoch table.  A hammer
    stream asks while the publisher announces epoch 1; the replica
    prewarms the new engine on a background thread and flips one pointer
    at a batch boundary.  Contract: (1) CONTINUOUS — zero timeouts
    through the swap; (2) NO MIXED-EPOCH READ — every response
    bit-matches the epoch-0 oracle or the epoch-1 oracle, never neither,
    and its epoch stamp agrees with the oracle it matched; (3) the swap
    COMPLETES — post-flip answers come from epoch 1."""
    import time as _t

    from cfk_tpu.serving import ServeClient, ServeEngine

    fleet, pub, broker, (u, m), oracle0 = _fleet_fixture(replicas=1)
    rng = np.random.default_rng(9)
    u2 = rng.standard_normal(u.shape).astype(np.float32)
    m2 = rng.standard_normal(m.shape).astype(np.float32)
    oracle1 = ServeEngine(u2, m2, num_users=u.shape[0],
                          num_movies=m.shape[0], tile_m=16)
    k = 5
    client = ServeClient(broker, route="user")
    answered = []
    timeouts = 0
    fleet.start()
    replica = fleet.replicas[0]
    try:
        deadline = _t.monotonic() + 60
        asks = post_flip = 0
        while _t.monotonic() < deadline:
            user = asks % 16
            try:
                got = client.ask([user], k, timeout_s=20)
                answered.append((user, next(iter(got.values()))))
            except TimeoutError:
                timeouts += 1
            asks += 1
            if asks == 10:
                pub.on_commit({"retrain": True, "user_factors": u2,
                               "movie_factors": m2, "num_users": 48})
            if replica.rollovers >= 1:
                # a few post-flip asks prove the new epoch serves, but
                # stop before their batch events push the rollover
                # events out of the flight dump's tail window
                post_flip += 1
                if post_flip >= 8:
                    break
    finally:
        fleet.stop()
    mixed = []
    stamp_wrong = []
    post_flip_new = False
    for user, resp in answered:
        s0, i0 = oracle0.topk(np.asarray([user]), k)
        s1, i1 = oracle1.topk(np.asarray([user]), k)
        is0 = bool(np.array_equal(np.asarray(resp.scores), s0[0])
                   and np.array_equal(np.asarray(resp.movie_rows), i0[0]))
        is1 = bool(np.array_equal(np.asarray(resp.scores), s1[0])
                   and np.array_equal(np.asarray(resp.movie_rows), i1[0]))
        if not (is0 or is1):
            mixed.append(user)
        elif is1 and not is0:
            post_flip_new = True
            if resp.epoch != 1:
                stamp_wrong.append(user)
        elif is0 and not is1 and resp.epoch != 0:
            stamp_wrong.append(user)
    return {
        "scenario": "serve_rollover",
        "fault_fired": bool(replica.rollovers >= 1),
        "detected": bool(replica.engine.epoch == 1),
        "recovered": bool(timeouts == 0 and not mixed and post_flip_new),
        "requests_answered": len(answered),
        "timeouts": timeouts,
        "rollovers": int(replica.rollovers),
        "mixed_epoch_responses": mixed,
        "epoch_stamp_mismatches": stamp_wrong,
        "served_from_new_epoch": post_flip_new,
        "ok": bool(replica.rollovers >= 1 and replica.engine.epoch == 1
                   and timeouts == 0 and not mixed and not stamp_wrong
                   and post_flip_new),
    }


SCENARIOS = {
    "nan": scenario_nan,
    "inf": scenario_inf,
    "singular_chunk": scenario_singular,
    "torn_checkpoint": scenario_torn_checkpoint,
    "flaky_broker": scenario_flaky_broker,
    "preemption": scenario_preemption,
    "slow_disk": scenario_slow_disk,
    "worker_kill": scenario_worker_kill,
    "offload_fleet": scenario_offload_fleet,
    "fleet_shrink": scenario_fleet_shrink,
    "fleet_rejoin": scenario_fleet_rejoin,
    "stream_duplicates": scenario_stream_duplicates,
    "stream_crash_replay": scenario_stream_crash_replay,
    "stream_poison_batch": scenario_stream_poison_batch,
    "quantized_table": scenario_quantized_table,
    "serve_under_foldin": scenario_serve_under_foldin,
    "serve_replica_kill": scenario_serve_replica_kill,
    "serve_delta_gap": scenario_serve_delta_gap,
    "serve_rollover": scenario_serve_rollover,
    "plan_fallback": scenario_plan_fallback,
    "offload_window": scenario_offload_window,
    "offload_window_sharded": scenario_offload_window_sharded,
    "staging_pool": scenario_staging_pool,
    "hot_cache": scenario_hot_cache,
    "offload_ials": scenario_offload_ials,
    "telemetry_overhead": scenario_telemetry_overhead,
}

# Flight-recorder contract (ISSUE 14): every scenario must leave a
# READABLE dump whose final events name the injected fault class — the
# any-of substrings below, searched over the last events of the
# scenario's newest dump.  Fault classes that dump at trip time
# (health_trip/quarantine/staging_error/preemption/...) leave their dump
# mid-scenario; classes whose fault is absorbed without a trip
# (flaky delivery, slow disk, duplicate delivery) are dumped by the
# harness at scenario end, with the fault's recorded events in the tail.
FLIGHT_EXPECT = {
    "nan": ("nonfinite",),
    "inf": ("nonfinite",),
    "singular_chunk": ("health_trip",),
    "torn_checkpoint": ("corrupt_checkpoint",),
    "flaky_broker": ("retryable_failure",),
    "preemption": ("preempt",),
    "slow_disk": ("checkpoint_committed",),
    "worker_kill": ("worker_kill",),
    "offload_fleet": ("offload_fleet_kill",),
    "fleet_shrink": ("fleet_shrink",),
    "fleet_rejoin": ("fleet_rejoin",),
    "stream_duplicates": ("delivery_duplicates",),
    "stream_crash_replay": ("stream_resumed", "corrupt_checkpoint"),
    "stream_poison_batch": ("quarantine",),
    "quantized_table": ("health_trip", "nonfinite"),
    "serve_under_foldin": ("commit", "serve"),
    "serve_replica_kill": ("replica_kill", "failover"),
    "serve_delta_gap": ("delta_gap", "resync"),
    "serve_rollover": ("rollover_begin", "rollover_flip"),
    "plan_fallback": ("health_trip", "nonfinite"),
    "offload_window": ("health_trip",),
    "offload_window_sharded": ("health_trip",),
    "staging_pool": ("health_trip", "staging_error"),
    "hot_cache": ("hot_cache_corruption", "health_trip"),
    "offload_ials": ("health_trip",),
    "telemetry_overhead": ("telemetry_overhead",),
}

# Events searched at the dump's tail: wide enough to cover a scenario's
# post-fault wind-down (commits, restores) without reaching back past the
# fault into unrelated history.
_FLIGHT_TAIL = 50


def _run_with_flight_recorder(name: str) -> dict:
    """Run one scenario with the flight recorder dumping into a scratch
    dir, then assert the dump contract and fold it into the row."""
    import glob
    import tempfile

    from cfk_tpu.telemetry import get_recorder

    rec = get_recorder()
    with tempfile.TemporaryDirectory() as td:
        rec.configure(dump_dir=td)
        rec.clear()
        try:
            row = SCENARIOS[name]()
        finally:
            rec.configure(dump_dir=None)
        dumps = sorted(
            glob.glob(os.path.join(td, "cfk_flight_*.json")),
            key=os.path.getmtime,
        )
        forced = False
        if not dumps:
            rec.configure(dump_dir=td)
            path = rec.dump(f"scenario_end_{name}")
            rec.configure(dump_dir=None)
            forced = True
            dumps = [path] if path else []
        named = False
        last_reason = None
        if dumps:
            with open(dumps[-1]) as f:
                payload = json.load(f)
            last_reason = payload.get("reason")
            tail = json.dumps(payload.get("events", [])[-_FLIGHT_TAIL:])
            named = any(s in tail for s in FLIGHT_EXPECT.get(name, ()))
    fr_ok = bool(dumps) and named
    row["flight_recorder"] = {
        "dumps": len(dumps),
        "forced_end_dump": forced,
        "last_reason": last_reason,
        "named_fault": named,
        "ok": fr_ok,
    }
    row["ok"] = bool(row.get("ok")) and fr_ok
    return row


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--scenario", nargs="*", default=list(SCENARIOS),
                   choices=list(SCENARIOS))
    args = p.parse_args()
    ok = True
    rows = []
    for name in args.scenario:
        row = _run_with_flight_recorder(name)
        rows.append(row)
        print(json.dumps(row), flush=True)
        ok &= bool(row.get("ok"))
    print(json.dumps({
        "chaos_lab": "pass" if ok else "FAIL",
        "scenarios": {r["scenario"]: r.get("ok") for r in rows},
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
