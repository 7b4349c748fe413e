"""The item-sharded runner end to end at a toy cell on four virtual CPU
devices (``toy_x4/``): it runs, a traced run reads the upload span, the
controls each make ``correct`` false, a program that cannot shard is refused
before any data is made; the blockwise seen lists and reference against the
whole-array ones; the device-trace readers on a made-up trace."""

import inspect
import json
import os
import shutil

# four virtual devices for the toy cell: the flag is read when the CPU
# backend starts, which no module does while pytest collects
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FLAG}=4").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.harness import (  # noqa: E402
    datagen, reference, reference_blocks, seen_blocks, xplane)

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_x4")
MANIFEST = os.path.join(TOY, "BENCHMARK.json")
CELL = "toy-serve-x4.serve-x4"


def drive(capsys, *, trace=0, seed=3_000_000_019, manifest=MANIFEST):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace), "--manifest", manifest],
                  require_tpu=False)
    out = capsys.readouterr().out
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), out


def test_sharded_toy_cell_runs_and_is_correct(capsys):
    res, out = drive(capsys)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_req_per_s", "setup_s"}
    assert res["metrics"]["serve_req_per_s"]["value"] == pytest.approx(150, rel=0.1)
    assert res["device"]["count"] == 4
    assert "in 4 shards of 768" in out  # 3,000 rows pad to 4 x 12 x 64
    assert out.count("against limit") >= 5


def test_traced_run_reads_the_upload_span_and_skips_absent_device_metrics(capsys):
    res, _ = drive(capsys, trace=1)
    # no TPU plane in a CPU trace: the three device-trace readers find
    # nothing and say nothing
    assert set(res["metrics"]) == {"setup_table_upload_s",
                                   "serve_batch_size.toy"}
    assert 0 < res["metrics"]["setup_table_upload_s"]["value"] < 30


def _with_config(tmp_path, edit):
    root = tmp_path / "toy_x4"
    shutil.copytree(TOY, root)
    path = root / "configs" / "toy-serve-x4.json"
    cfg = json.loads(path.read_text())
    edit(cfg)
    path.write_text(json.dumps(cfg))
    return str(root / "BENCHMARK.json")


def test_control_bf16_table_fails_the_topk_check(capsys, tmp_path):
    manifest = _with_config(tmp_path, lambda c: c.update(table_dtype="bfloat16"))
    res, out = drive(capsys, manifest=manifest)
    assert res["correct"] is False
    assert any("FAILED" in l for l in out.splitlines()
               if "check score_err" in l or "check rank_gap" in l)


def test_a_merge_that_ignores_one_shard_fails_the_run(capsys, monkeypatch):
    """The last shard's candidates never reach the merge (its rows are
    masked as if past the catalogue's end): every id served is valid and
    unseen, and only the comparison with the exact top-K can tell."""
    from cfk_tpu.parallel import spmd

    real = spmd.serve_topk_sharded

    def three_of_four(mesh, u, table, scale, seen, *, num_movies, **kw):
        return real(mesh, u, table, scale, seen,
                    num_movies=table.shape[0] * 3 // 4, **kw)

    monkeypatch.setattr(spmd, "serve_topk_sharded", three_of_four)
    res, out = drive(capsys)
    assert res["correct"] is False
    assert any("FAILED" in l for l in out.splitlines() if "check rank_gap" in l)
    assert any("-> ok" in l for l in out.splitlines()
               if "check invalid_id_sets" in l)


def test_served_answer_altered_where_it_is_produced(capsys, monkeypatch):
    from cfk_tpu.serving.engine import ServeEngine

    real = ServeEngine.topk

    def second_best(self, rows, k, **kw):
        vals, ids = real(self, rows, k, **kw)
        ids = ids.copy()
        ids[:, 0] = ids[:, 1]
        return vals, ids

    monkeypatch.setattr(ServeEngine, "topk", second_best)
    res, out = drive(capsys)
    assert res["correct"] is False
    assert "check invalid_id_sets" in out


def test_requests_dropped_by_the_server_fail_the_run(capsys, monkeypatch):
    from cfk_tpu.serving.server import RecommendServer

    real = RecommendServer._poll_requests
    monkeypatch.setattr(RecommendServer, "_poll_requests",
                        lambda self: [r for r in real(self) if r.req_id % 7])
    res, out = drive(capsys)
    assert res["correct"] is False and res["failed"] > 0
    assert "check failed_requests" in out and "FAILED" in out


def test_a_program_that_cannot_shard_is_refused_before_any_data(
        capsys, monkeypatch):
    """The parent commit's ``ServeEngine`` takes no ``shards``: the runner
    exits at once, with no result line."""
    from cfk_tpu.serving.engine import ServeEngine

    real = ServeEngine.__init__

    def parents(self, user_factors, movie_factors, *, num_users, num_movies,
                mesh=None, **kw):
        real(self, user_factors, movie_factors, num_users=num_users,
             num_movies=num_movies, mesh=mesh, **kw)

    assert "shards" in inspect.signature(real).parameters
    monkeypatch.setattr(ServeEngine, "__init__", parents)
    monkeypatch.setattr(datagen, "factor_table", lambda *a, **kw: 1 / 0)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "1",
                  "--manifest", MANIFEST], require_tpu=False)
    assert "takes no `shards`" in str(e.value)
    assert not any(l.startswith("{") for l in capsys.readouterr().out.splitlines())


@pytest.mark.parametrize("manifest, cell, sharded", [
    (MANIFEST, CELL, True),
    (os.path.join(os.path.dirname(TOY), "toy", "BENCHMARK.json"),
     "toy-serve.serve", False)], ids=["toy_x4", "toy"])
def test_the_sweep_tool_loads_the_runner_the_mix_names(
        capsys, manifest, cell, sharded):
    """One tool for every serve cell: a probe, then two shares of what it
    completed, each a window of the mix's own runner."""
    sweep = run.load_module(os.path.join(run.HERE, "tools", "sweep.py"),
                            "sweep_under_test")
    assert sweep.main(["--cpu", "--manifest", manifest, "--workload", cell,
                       "--seconds", "1", "--probe", "2000",
                       "--rates", "0.5,1.0"]) == 0
    out = capsys.readouterr().out
    assert ("in 4 shards of 768" in out) is sharded  # the sharded runner's line
    rows = [json.loads(l.split(" ", 1)[1]) for l in out.splitlines()
            if l.startswith("SWEEP_ROW ")]
    assert [r["pass"] for r in rows] == ["probe", 0, 0]
    capacity = rows[0]["answered_in_window_per_s"]
    assert 0 < capacity <= 2000
    assert [r["offered_req_per_s"] for r in rows[1:]] == [
        round(capacity * s / 10) * 10.0 for s in (0.5, 1.0)]
    assert all(r["new_traces"] == 0 for r in rows)


# -- the blockwise generator and reference against the whole-array ones -------

def test_seen_lists_in_blocks_past_24_bits_of_items():
    kw = dict(seed=20230001, users_per_block=1 << 12)
    items, indptr = seen_blocks.seen_lists_blocks(
        20_000, 40_000_000, 10.485, 64, threads=3, **kw)
    again = seen_blocks.seen_lists_blocks(
        20_000, 40_000_000, 10.485, 64, threads=1, **kw)
    assert (items == again[0]).all() and (indptr == again[1]).all()
    assert items.dtype == np.int32 and indptr.dtype == np.int64
    lens = np.diff(indptr)
    assert indptr[0] == 0 and indptr[-1] == items.size
    assert lens.min() >= 1 and lens.max() <= 64
    assert 9.5 < lens.mean() < 10.485  # the cut and the duplicates dropped
    assert items.min() >= 0 and items.max() < 40_000_000
    assert items.max() >= 1 << 24  # where datagen.seen_lists raises
    with pytest.raises(ValueError):
        datagen.seen_lists(10, 40_000_000, 10.485, 64, seed=1)
    # ascending within each user, so no duplicates
    inner = np.ones(items.size, bool)
    inner[indptr[1:-1]] = False
    assert (np.diff(items)[inner[1:]] > 0).all()
    # log-uniform popularity: each decade of ranks draws about as often
    other = seen_blocks.seen_lists_blocks(20_000, 40_000_000, 10.485, 64,
                                          seed=7, users_per_block=1 << 12)
    assert not np.array_equal(other[1], indptr)


@pytest.mark.parametrize("block", [1 << 20, 1000, 97])
def test_blockwise_reference_equals_the_whole_array_reference(block):
    rng = np.random.default_rng(block)
    n, m, rank, k = 9, 5000, 16, 10
    u = datagen.factor_table(n, rank, seed=1, scale=0.35, threads=1)
    table = datagen.factor_table(m, rank, seed=2, scale=0.35, threads=1)
    seen = [np.sort(rng.choice(m, int(rng.integers(0, 60)), replace=False))
            for _ in range(n)]
    best, scores = reference.exact_topk(u, table, seen, k)
    ids = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(scores, ids, axis=1)
    got_best, got_ids, at = reference_blocks.exact_topk_blocks(
        u, table, seen, k, ids, block=block)
    np.testing.assert_array_equal(got_ids, ids)
    np.testing.assert_allclose(got_best, best, rtol=0, atol=1e-6)
    assert reference_blocks.topk_gaps(vals, got_best, at) == pytest.approx(
        reference.topk_gaps(ids, vals, best, scores), abs=1e-6)
    # a missed best item, a seen item served, a score off by 1e-3
    worse = ids.copy()
    worse[:, 0] = np.argsort(-scores, axis=1)[:, k]
    _, _, at2 = reference_blocks.exact_topk_blocks(u, table, seen, k, worse,
                                                   block=block)
    assert reference_blocks.topk_gaps(vals, got_best, at2)[0] > 1e-5
    if len(seen[0]):
        served_seen = ids.copy()
        served_seen[0, 3] = seen[0][0]
        _, _, at3 = reference_blocks.exact_topk_blocks(
            u, table, seen, k, served_seen, block=block)
        assert reference_blocks.topk_gaps(vals, got_best, at3)[0] == np.inf
    assert reference_blocks.topk_gaps(vals + 1e-3, got_best, at)[1] > 5e-4


# -- the device-trace readers, on a trace made up for them --------------------

class _Ctx:
    def __init__(self, trace, **window):
        from benchmarks.harness import roofline

        self.trace_data, self.window = trace, window
        self.config = {"rank": 128, "table_dtype": "float32"}
        self.peaks = roofline.PEAKS["TPU v5 lite"]


def _reader(family):
    return run.load_module(
        os.path.join(run.HERE, "layer_metrics", family + ".py"), "r_" + family)


def test_shard_readers_on_a_made_up_trace():
    # per batch on device 0: a 2 ms build (twice for the second batch), a
    # 210 ms shard program of which 205 ms are the scorer's custom call
    modules, ops, t = [], [], 0.0
    for pieces in (1, 2):
        for _ in range(pieces):
            modules.append((t, t + 0.002, "jit__seen_tiles_shard_call(1)"))
            t += 0.002
        modules.append((t, t + 0.210, "jit__topk_shard_call(2)"))
        ops.append((t, t + 0.205, "_topk_shard_call.1", True))
        ops.append((t + 0.205, t + 0.209, "all-gather.8", False))
        t += 0.210
    trace = xplane.DeviceTrace(modules=[modules, []], ops=ops, mark=0.0,
                               profile_start_unix_ns=0)
    ctx = _Ctx(trace, batch_sizes=[256, 256], shards=4,
               table_rows=4 * 12_047_872, k_pad=16)
    assert _reader("serve_merge_ms").read(ctx, "serve_merge_ms.x4") == (
        pytest.approx(5.0))
    assert _reader("serve_seen_build_ms").read(ctx, "x") == pytest.approx(3.0)
    # 12,047,872 x 128 x 4 B + the batch in + the selection out at 819 GB/s:
    # a chip scans its slice, not the table
    floor = (12_047_872 * 512 + 256 * 512 + 256 * 16 * 8) / 819e9
    assert _reader("topk_roofline").read(ctx, "x") == pytest.approx(
        100 * floor / 0.205)
    assert 3.6 < 100 * floor / 0.205 < 3.7


def _one_device_ctx():
    trace = xplane.DeviceTrace(
        modules=[[(0.0, 0.17, "jit__topk_call(3)")]],
        ops=[(0.0, 0.16, "_topk_call.1", True)], mark=0.0,
        profile_start_unix_ns=0)
    return _Ctx(trace, batch_sizes=[256], table_rows=9_350_144, k_pad=16)


def test_the_roofline_reader_prices_the_whole_table_on_one_device():
    """The same reader, the one-device entry's name, no ``shards`` in the
    window: the share of a whole-table scan, not nothing."""
    floor = (9_350_144 * 512 + 256 * 512 + 256 * 16 * 8) / 819e9
    assert _reader("topk_roofline").read(_one_device_ctx(), "x") == (
        pytest.approx(100 * floor / 0.16))
    assert 3.6 < 100 * floor / 0.16 < 3.7
    # neither name in the trace: nothing, never 0
    none = xplane.DeviceTrace(modules=[[(0.0, 0.17, "jit_other(3)")]],
                              ops=[(0.0, 0.16, "fusion.1", False)], mark=0.0,
                              profile_start_unix_ns=0)
    ctx = _Ctx(none, batch_sizes=[256], table_rows=9_350_144, k_pad=16)
    assert _reader("topk_roofline").read(ctx, "x") is None


@pytest.mark.parametrize("shards, rows, period_ms", [
    (None, 9_350_144, 46.8), (4, 4 * 12_047_872, 57.7)])
def test_the_whole_steps_share_is_the_floor_over_the_batch_period(
        shards, rows, period_ms):
    starts = [1_000 + n * period_ms * 1e3 for n in range(4)]
    ctx = _Ctx(None, batch_sizes=[256] * 4, table_rows=rows, k_pad=16,
               **({"shards": shards} if shards else {}))
    ctx.program_spans = [
        {"name": "serve/batch", "ph": "X", "ts": t, "dur": 40_000.0,
         "args": {"batch": n}} for n, t in enumerate(starts)]
    floor = ((rows // (shards or 1)) * 512 + 256 * 512 + 256 * 16 * 8) / 819e9
    got = _reader("serve_step_mfu").read(ctx, "serve_step_mfu.saturate")
    assert got == pytest.approx(100 * floor / (period_ms * 1e-3))
    assert 12 < got < 14
    ctx.program_spans = ctx.program_spans[:1]  # one batch has no period
    assert _reader("serve_step_mfu").read(ctx, "x") is None


def test_shard_readers_say_nothing_of_a_one_device_program():
    ctx = _one_device_ctx()
    for family in ("serve_merge_ms", "serve_seen_build_ms",
                   "setup_table_upload_s"):
        assert _reader(family).read(ctx, family) is None
