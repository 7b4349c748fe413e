"""scripts/perf_lab.py — the source of every headline perf number — gets the
same contract protection as bench.py: the JSON row shape, the min/median
timing math (against an injected deterministic clock), and the dataset
cache round-trip, all on CPU with tiny shapes."""

import importlib.util
import json
import os

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "perf_lab", os.path.join(_ROOT, "scripts", "perf_lab.py")
)
perf_lab = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(perf_lab)


def _args(**over):
    base = dict(
        users=300, movies=80, nnz=2000, seed=0, rank=8,
        layout="segment", chunk_elems=1024, tile_rows=16, slice_rows=None,
        solver="cholesky", dtype="float32", gram_backend=None,
        tiled_gram_backend=None, group_tiles=None, reg_solve_algo=None,
        ials=False, alpha=40.0, accum_chunk_elems=None, dense_stream=False,
        overlap="on", fused="on", gather="fused", table_dtype="float32",
        health="off",
        health_norm_limit=1e6, ckpt=None,
        foldin="off", foldin_updates=4096, foldin_batch_records=256,
        serve="off", serve_batch=64, serve_k=10, serve_requests=512,
        serve_tile_m=512, serve_mode="exact", serve_clusters=0,
        offload=None, offload_window_chunks=4, offload_budget_mb=None,
        offload_shards=1, optimizer="als",
        staging=None, staging_pool_depth=None, compile_cache_dir=None,
        hot_rows=None,
        plan=None, plan_cache=None,
        telemetry="off", trace_dir=None,
        iters=2, repeats=3, profile_dir=None,
    )
    base.update(over)
    import argparse

    return argparse.Namespace(**base)


def test_parser_matches_args_fixture():
    # The fixture above must cover exactly the parser's surface, so a new
    # flag cannot silently diverge from what run_lab is tested with.
    ns = perf_lab.make_parser().parse_args([])
    assert set(vars(ns)) == set(vars(_args()))


def test_json_row_contract(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    row = perf_lab.run_lab(_args())
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == row  # last stdout line IS the row
    for key in ("s_per_iter_min", "s_per_iter_median", "device_kind",
                "model_tflops_per_iter", "min_hbm_gb_per_iter",
                "layout", "rank", "iters_per_call"):
        assert key in row, key
    # this run is on the CPU backend: no efficiency against a TPU's peaks
    assert "not measured" in row["roofline"]
    assert "mfu" not in row and "vs_gather_roofline" not in row
    assert row["s_per_iter_min"] >= 0
    assert row["s_per_iter_min"] <= row["s_per_iter_median"]
    assert row["layout"] == "segment"


def test_tiled_dense_stream_row(tmp_path, monkeypatch):
    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    row = perf_lab.run_lab(_args(layout="tiled", dense_stream=True,
                                 chunk_elems=512, repeats=2))
    assert row["layout"] == "tiled"
    assert row["s_per_iter_min"] >= 0


def test_dataset_cache_round_trip(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    a = _args()
    ds1 = perf_lab.get_dataset(a)
    first = capsys.readouterr().out
    assert "cache hit" not in first
    ds2 = perf_lab.get_dataset(_args())
    second = capsys.readouterr().out
    assert "cache hit" in second
    np.testing.assert_array_equal(
        ds1.coo_dense.rating, ds2.coo_dense.rating
    )


def test_measure_steps_min_median_math(capsys):
    # Deterministic clock: each timed call brackets exactly one pair of
    # clock() reads; scripted durations 0.9, 0.3, 0.6 → min 0.3.
    durations = iter([0.9, 0.3, 0.6])
    now = [0.0]
    pending = [None]

    def clock():
        if pending[0] is None:
            pending[0] = next(durations)
            return now[0]
        now[0] += pending[0]
        pending[0] = None
        return now[0]

    calls = []

    def fake_steps(u, m):
        calls.append(1)
        return u, m

    u = np.zeros((2, 2), np.float32)
    times, *_ = perf_lab.measure_steps(
        fake_steps, u, u, repeats=3, iters=3, clock=clock,
    )
    assert len(calls) == 3
    np.testing.assert_allclose(times, [0.9, 0.3, 0.6])
    per_iter = [t / 3 for t in times]
    np.testing.assert_allclose(min(per_iter), 0.1)
    np.testing.assert_allclose(sorted(per_iter)[1], 0.2)  # the reported median


def test_health_axis_row(tmp_path, monkeypatch):
    import contextlib
    import io

    # the sentinel axis rides the same row contract (ISSUE 3: the
    # --health {on,off} pair is how its overhead is recorded)
    perf_lab.CACHE_ROOT, old = str(tmp_path), perf_lab.CACHE_ROOT
    try:
        with contextlib.redirect_stdout(io.StringIO()):
            on = perf_lab.run_lab(_args(health="on"))
            off = perf_lab.run_lab(_args(health="off"))
    finally:
        perf_lab.CACHE_ROOT = old
    assert on["health"] == "on" and off["health"] == "off"
    assert on["s_per_iter_min"] >= 0


def test_foldin_axis_row(tmp_path, monkeypatch, capsys):
    # the streaming fold-in axis (ISSUE 6): the tier-1 smoke path for the
    # whole streaming loop — in-memory broker, tiny synthetic stream,
    # through StreamSession's exactly-once batch/solve/probe/commit cycle
    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    row = perf_lab.run_lab(_args(
        foldin="on", foldin_updates=48, foldin_batch_records=16,
        layout="padded",
    ))
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == row  # scoreboard contract holds here too
    assert row["foldin"] == "on"
    assert row["updates"] == 48
    assert row["updates_per_s"] > 0
    assert row["batches"] >= 1
    for key in ("stage_s", "foldin_solve_s", "health_check_s", "commit_s"):
        assert row[key] >= 0, key


def test_ckpt_axis_row(tmp_path, monkeypatch):
    import contextlib
    import io

    # the checkpoint-writer axis (ISSUE 5): per-iteration saves ride the
    # timed call, and the row records the in-loop save stall + drain
    perf_lab.CACHE_ROOT, old = str(tmp_path), perf_lab.CACHE_ROOT
    try:
        with contextlib.redirect_stdout(io.StringIO()):
            a = perf_lab.run_lab(_args(ckpt="async"))
            s = perf_lab.run_lab(_args(ckpt="sync"))
    finally:
        perf_lab.CACHE_ROOT = old
    assert a["ckpt"] == "async" and s["ckpt"] == "sync"
    for row in (a, s):
        assert row["ckpt_save_stall_s_per_save"] >= 0
        assert row["ckpt_drain_s"] >= 0
        assert row["s_per_iter_min"] >= 0
    # NO relative sync-vs-async timing assert here: at this toy shape the
    # steps are ~ms while fsync dominates, so back-pressure makes the two
    # writers near-equal and noise flips the sign — the measured win lives
    # in bench.py --ckpt-ab at a real shape, where compute hides the disk.


def test_plan_axis_row(tmp_path, monkeypatch, capsys):
    # the execution-planner axis (ISSUE 9): the tier-1 smoke of the whole
    # resolve→thread-knobs→measure→provenance loop, mirroring
    # test_serve_axis_row's role for serving.  'model' resolves the free
    # knobs through the cost model and the row carries the provenance
    # columns; 'autotune' measures candidates with the lab's own step
    # timing and caches the winner (second run must hit).
    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    cache = str(tmp_path / "plan_cache.json")
    row = perf_lab.run_lab(_args(
        plan="model", layout="tiled", chunk_elems=512, tile_rows=16,
    ))
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == row  # scoreboard contract holds here too
    assert row["plan_axis"] == "model"
    assert row["plan_source"] in ("model", "pinned")
    assert row["plan_est_s"] >= 0
    assert "plan" in row and "table=" in row["plan"]
    # the roofline column charges the EXECUTED dtype, i.e. the plan's
    assert row["table_dtype"] in ("float32", "bfloat16", "int8")

    miss = perf_lab.run_lab(_args(
        plan="autotune", plan_cache=cache, layout="tiled",
        chunk_elems=512, tile_rows=16, repeats=2,
    ))
    assert miss["plan_cache"] == "miss"
    assert miss["plan_source"] == "autotune"
    assert miss["plan_measured_s"] > 0
    hit = perf_lab.run_lab(_args(
        plan="autotune", plan_cache=cache, layout="tiled",
        chunk_elems=512, tile_rows=16, repeats=2,
    ))
    assert hit["plan_cache"] == "hit"
    assert hit["plan_source"] == "autotune-cache"
    # the cached winner is the measured one
    assert hit["plan"] == miss["plan"]

    pinned = perf_lab.run_lab(_args(
        plan="pinned", layout="tiled", chunk_elems=512, tile_rows=16,
    ))
    assert pinned["plan_source"] == "pinned"
    assert pinned["table_dtype"] == "float32"  # legacy threading kept


def test_offload_axis_row(tmp_path, monkeypatch, capsys):
    # the out-of-core axis (ISSUE 11): the tier-1 in-memory smoke of the
    # whole store→window-plan→stage→windowed-half-step→host-scatter loop,
    # mirroring test_plan_axis_row's role for the planner.  Both tier
    # values run the SAME stream-forced tiled workload; crc equality IS
    # the windowed == resident bit-exactness contract.
    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    base = dict(layout="tiled", users=200, movies=60, nnz=1500,
                chunk_elems=512, tile_rows=16, rank=8, iters=2, repeats=2)
    dev = perf_lab.run_lab(_args(offload="device", **base))
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == dev  # scoreboard contract holds here too
    assert dev["offload"] == "device"
    assert dev["s_per_iter_min"] >= 0
    assert dev["factors_crc32"] > 0

    win = perf_lab.run_lab(_args(offload="host_window",
                                 offload_window_chunks=2, **base))
    assert win["offload"] == "host_window"
    assert win["windows_m"] >= 1 and win["windows_u"] >= 1
    assert win["window_rows_m"] >= 8
    assert win["staged_mb_per_run"] > 0
    assert win["staged_cold_mb_per_run"] > 0
    assert win["plan_held_mb"] > 0
    # windowed == resident, bit-exact — the ISSUE 11 acceptance contract
    assert win["factors_crc32"] == dev["factors_crc32"]


def test_offload_axis_optimizer_row(tmp_path, monkeypatch):
    # The --optimizer axis (ISSUE 19), mirroring test_offload_axis_row
    # for the implicit family: iALS++ on the bucketed width-class layout,
    # resident vs host_window through the out-of-core subspace driver
    # (width-class windows + global-Gram reduction) — crc equality is the
    # windowed == resident bit-exactness proof for the subspace sweeps,
    # and the windowed row carries the Gram reduction's own meters.
    # (iALS++ only, repeats=1: the plain-ials windowed == resident pair
    # lives in tests/test_offload_ials.py — duplicating it here pushed
    # the tier-1 suite past its wall-clock budget.)
    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    base = dict(layout="bucketed", users=120, movies=40, nnz=900,
                chunk_elems=512, rank=4, iters=2, repeats=1,
                optimizer="ialspp")
    dev = perf_lab.run_lab(_args(offload="device", **base))
    assert dev["offload"] == "device"
    assert dev["optimizer"] == "ialspp"
    assert dev["factors_crc32"] > 0

    win = perf_lab.run_lab(_args(offload="host_window",
                                 offload_window_chunks=2, **base))
    assert win["offload"] == "host_window"
    assert win["optimizer"] == "ialspp"
    assert win["windows_m"] >= 1 and win["windows_u"] >= 1
    assert win["staged_mb_per_run"] > 0
    assert win["gram_staged_mb_per_run"] > 0
    assert win["gram_reserved_mb"] > 0
    # windowed == resident, bit-exact — the ISSUE 19 acceptance contract
    assert win["factors_crc32"] == dev["factors_crc32"]


def test_offload_axis_hot_row(tmp_path, monkeypatch):
    # The hot-row cache axis (ISSUE 15): hot off (the PR 12 engine),
    # auto (coverage-knee resolution), and a pinned count all run the
    # SAME host_window workload — crc equality across the axis is the
    # hot/cold bit-exactness proof through the lab itself, and the hot
    # arms' rows carry the split metering (cold staged vs hot resident).
    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    base = dict(layout="tiled", users=200, movies=60, nnz=1500,
                chunk_elems=512, tile_rows=16, rank=8, iters=2, repeats=2,
                offload="host_window", offload_window_chunks=2)
    off = perf_lab.run_lab(_args(hot_rows=0, **base))
    auto = perf_lab.run_lab(_args(hot_rows=None, **base))
    pinned = perf_lab.run_lab(_args(hot_rows=12, **base))
    assert off["hot"] == "off" and off["hot_rows"] == 0
    assert off["hot_resident_mb"] in (None, 0, 0.0)
    assert auto["hot"] == "on" and auto["hot_rows"] > 0
    assert auto["hot_coverage"] > 0
    assert auto["hot_resident_mb"] > 0
    # The cache exists to cut staged table bytes — auto must not stage
    # MORE than full staging on the same schedule.
    assert auto["staged_cold_mb_per_run"] < off["staged_cold_mb_per_run"]
    assert pinned["hot_rows"] <= 12 and pinned["hot_rows"] > 0
    assert (off["factors_crc32"] == auto["factors_crc32"]
            == pinned["factors_crc32"])


def test_offload_axis_staging_row(tmp_path, monkeypatch):
    # The staging A/B axis (ISSUE 13): both engine modes run the SAME
    # 2-shard host_window workload — crc equality is the pooled==serial
    # bit-exactness proof through the lab itself, and the pool arm's row
    # carries the engine columns (depth, hidden fraction, trace count).
    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    base = dict(layout="tiled", users=200, movies=60, nnz=1500,
                chunk_elems=512, tile_rows=16, rank=8, iters=2, repeats=2,
                offload="host_window", offload_window_chunks=2,
                offload_shards=2)
    serial = perf_lab.run_lab(_args(staging="serial", **base))
    pool = perf_lab.run_lab(_args(staging="pool", **base))
    assert serial["staging"] == "serial" and pool["staging"] == "pool"
    assert pool["factors_crc32"] == serial["factors_crc32"]
    assert pool["pool_depth"] >= 1
    assert pool["stage_busy_s"] >= 0
    # the first (cold) arm traced the window programs; the second reuses
    # them — the process-wide jit cache IS the re-trace bound at work
    assert serial["trace_count"] >= 1
    assert pool["trace_count"] == 0
    assert pool["time_to_first_step_s"] > 0
    # serial stages on the consuming thread: stall == busy ⇒ hidden 0
    assert serial["overlap_hidden_fraction"] == 0.0
    assert serial["pool_depth"] is None


def test_offload_axis_sharded_row(tmp_path, monkeypatch):
    # The SHARDED arm (ISSUE 12): the host_window side runs the sharded
    # windowed driver; the device side the real shard_map trainer (this
    # test env forces 4 virtual devices) — crc equality between the arms
    # is the sharded windowed == resident bit-exactness proof, through
    # the lab's own two-point fit.
    import jax

    if len(jax.devices()) < 2:
        import pytest

        pytest.skip("needs 2 virtual devices")
    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    base = dict(layout="tiled", users=200, movies=60, nnz=1500,
                chunk_elems=512, tile_rows=16, rank=8, iters=2, repeats=2,
                offload_shards=2)
    dev = perf_lab.run_lab(_args(offload="device", **base))
    assert dev["offload_shards"] == 2
    win = perf_lab.run_lab(_args(offload="host_window",
                                 offload_window_chunks=2, **base))
    assert win["offload_shards"] == 2
    assert win["factors_crc32"] == dev["factors_crc32"]


def test_telemetry_axis_row(tmp_path, monkeypatch, capsys):
    # The --telemetry A/B axis (ISSUE 14), mirroring test_offload_axis_row:
    # both arms run the SAME trimmed host_window workload — crc equality is
    # the telemetry-on == telemetry-off bit-exactness contract (spans are
    # host-side observation only), and the on arm's row carries the span
    # count + the written Chrome trace.
    import cfk_tpu.telemetry as telemetry

    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    base = dict(layout="tiled", users=200, movies=60, nnz=1500,
                chunk_elems=512, tile_rows=16, rank=8, iters=2, repeats=2,
                offload="host_window", offload_window_chunks=2)
    off = perf_lab.run_lab(_args(telemetry="off", **base))
    assert "telemetry" not in off  # off arm is byte-for-byte pre-axis
    on = perf_lab.run_lab(_args(telemetry="on",
                                trace_dir=str(tmp_path / "trace"), **base))
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == on  # scoreboard contract incl. telemetry
    assert on["telemetry"] == "on"
    assert on["telemetry_spans"] > 0
    # spans are observation only: factors bit-identical across the arms
    assert on["factors_crc32"] == off["factors_crc32"]
    with open(on["telemetry_trace_path"]) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"] if e.get("ph") == "X"}
    assert "train/iter" in names
    assert any(n.endswith("window_stage") for n in names)
    # the axis tears the tracer down — later labs must not keep tracing
    assert telemetry.get_tracer() is None


def test_serve_axis_row(tmp_path, monkeypatch, capsys):
    # the top-K serving axis (ISSUE 8): the tier-1 smoke of the whole
    # request→score→top-K→respond loop — in-memory log, RecommendServer
    # coalescing, the score+top-K kernel with exclude-seen, open-loop
    # latency accounting — mirroring test_foldin_axis_row's role
    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    row = perf_lab.run_lab(_args(
        serve="on", serve_requests=24, serve_batch=8, serve_k=3,
        serve_tile_m=16, repeats=2,
    ))
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == row  # scoreboard contract holds here too
    assert row["serve"] == "on"
    assert row["answered"] == 24
    assert row["qps"] > 0
    assert row["serve_k"] == 3
    assert "not measured" in row["roofline"]  # CPU backend: no peaks
    assert row["batches"] >= 1
    for key in ("p50_ms", "p99_ms", "batch_s", "capacity_qps",
                "serve_batch_mb"):
        assert row[key] >= 0, key
    assert row["p50_ms"] <= row["p99_ms"]
    # every serve row now carries the ISSUE 16 A/B columns
    assert row["serve_mode"] == "exact"
    assert row["recall_at_k"] == 1.0
    assert row["bytes_scanned_per_batch"] > 0


def test_serve_axis_two_stage_row(tmp_path, monkeypatch, capsys):
    # the --serve-mode A/B axis (ISSUE 16), mirroring test_serve_axis_row:
    # the clustered candidate → exact-rescore path through the same full
    # request loop, with measured recall vs the bit-exact scan and the
    # executed mode's scan bytes in the row
    monkeypatch.setattr(perf_lab, "CACHE_ROOT", str(tmp_path))
    row = perf_lab.run_lab(_args(
        serve="on", serve_requests=24, serve_batch=8, serve_k=3,
        serve_tile_m=16, repeats=2, serve_mode="two_stage",
        serve_clusters=8,
    ))
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == row
    assert row["serve"] == "on"
    assert row["serve_mode"] == "two_stage"
    assert row["answered"] == 24
    assert row["qps"] > 0
    assert row["clusters"] == 8
    assert row["probe_clusters"] >= 1
    assert 0 < row["shortlist_rows"] <= row["movies"]
    assert 0.0 <= row["recall_at_k"] <= 1.0
    assert row["bytes_scanned_per_batch"] > 0
    assert "vs_roofline" not in row  # CPU backend: no peaks
