"""The runner end to end at toy cells kept beside this file (a CPU rehearsal:
arguments, the last line's schema, ``correct`` false on planted faults and on
the lower-precision controls, refusal without a TPU on the real cells, and a
cell, configuration, mix and metric added by new files alone)."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import run

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
MANIFEST = os.path.join(TOY, "BENCHMARK.json")


def drive(capsys, cell, *, trace=0, seed=3_000_000_017, manifest=MANIFEST,
          seconds=1):
    """The rest of a run with the harness's look for a chip skipped."""
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), "--manifest", manifest],
                  require_tpu=False)
    out, err = capsys.readouterr()
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    # each number compared beside its limit: last in the result's line, and
    # the last lines on standard error, whatever the run found
    assert list(res)[-1] == "checks" and len(res["checks"]) >= 5
    last = err.strip().splitlines()[-len(res["checks"]) - 1:]
    assert last[-1] == f"correct: {res['correct']}"
    for line, (name, c) in zip(last, res["checks"].items()):
        assert set(c) == {"value", "limit"}
        assert line == (f"check {name}: {c['value']} against limit "
                        f"{c['limit']:g}")
    return res, out


def test_last_line_schema_and_correct(capsys):
    res, out = drive(capsys, "toy-serve.serve")
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device",
                        "checks"}
    assert list(res["checks"]) == ["compiles_in_window", "failed_requests",
                                   "invalid_id_sets", "rank_gap", "score_err"]
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_req_per_s", "setup_s"}
    # under its capacity the server answers what is offered (200 req/s)
    assert res["metrics"]["serve_req_per_s"]["value"] == pytest.approx(200, rel=0.1)
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # every number compared is printed beside its limit
    assert out.count("against limit") >= 4


def test_traced_run_reports_the_per_layer_metrics(capsys):
    res, _ = drive(capsys, "toy-serve.serve", trace=1)
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device",
                        "breakdown", "checks"}
    assert set(res["metrics"]) == {"setup_data_s", "serve_batch_size.toy"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_same_seed_same_inputs(capsys):
    from benchmarks.harness import datagen

    a = datagen.zipf_users(1000, 50, seed=3_000_000_001, a=1.2)
    b = datagen.zipf_users(1000, 50, seed=3_000_000_001, a=1.2)
    assert (a == b).all()
    t1 = datagen.factor_table(1000, 8, seed=2**31 + 5, scale=0.3, threads=3)
    t2 = datagen.factor_table(1000, 8, seed=2**31 + 5, scale=0.3, threads=1)
    assert (t1 == t2).all()


def _with_config(tmp_path, name, edit):
    """A copy of the toy benchmark whose configuration ``name`` is edited."""
    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    path = root / "configs" / f"{name}.json"
    cfg = json.loads(path.read_text())
    edit(cfg)
    path.write_text(json.dumps(cfg))
    return str(root / "BENCHMARK.json")


# -- the timed path broken underneath: ``correct`` must come out false --------

def test_served_answer_altered_where_it_is_produced(capsys, monkeypatch):
    from cfk_tpu.serving.engine import ServeEngine

    real = ServeEngine.topk

    def second_best(self, rows, k, **kw):
        vals, ids = real(self, rows, k, **kw)
        ids = ids.copy()
        ids[:, 0] = ids[:, 1]  # the best item replaced by a duplicate
        return vals, ids

    monkeypatch.setattr(ServeEngine, "topk", second_best)
    res, out = drive(capsys, "toy-serve.serve")
    assert res["correct"] is False
    assert "check invalid_id_sets" in out


def test_requests_dropped_by_the_server_fail_the_run(capsys, monkeypatch):
    """Every seventh request consumed and never answered: the backlog cannot
    be served out after the close, so the run has failed requests."""
    from cfk_tpu.serving.server import RecommendServer

    real = RecommendServer._poll_requests

    def lossy(self):
        return [r for r in real(self) if r.req_id % 7]

    monkeypatch.setattr(RecommendServer, "_poll_requests", lossy)
    res, out = drive(capsys, "toy-serve.serve")
    assert res["correct"] is False and res["failed"] > 0
    assert "check failed_requests" in out and "FAILED" in out


def test_requests_shed_by_the_server_fail_the_run(capsys, monkeypatch):
    """Backlog shed with an explicit refusal is an answer, but not the one
    the deployment guarantees."""
    from cfk_tpu.serving.server import RecommendServer

    class ShedEveryFifth:
        def admit(self, reqs):
            return ([r for r in reqs if r.req_id % 5],
                    [r for r in reqs if not r.req_id % 5])

    real = RecommendServer.__init__

    def init(self, *a, **kw):
        real(self, *a, **kw)
        self.admission = ShedEveryFifth()

    monkeypatch.setattr(RecommendServer, "__init__", init)
    res, out = drive(capsys, "toy-serve.serve")
    assert res["correct"] is False and res["failed"] > 0
    assert "check failed_requests" in out and "FAILED" in out


def test_the_window_counts_every_answer_over_all_its_time():
    """A scripted server: the rate is all the answers seen by the close over
    all the time to the close, and the close waits for the batch in flight."""
    from benchmarks.harness import loadgen

    now = [0.0]

    class Resp:
        def __init__(self, rid):
            self.req_id, self.error = rid, None

    class Client:
        def __init__(self):
            self.sent, self.out = [], []

        def request(self, user, k):
            self.sent.append(len(self.sent))
            return self.sent[-1]

        def flush(self):
            pass

        def poll_responses(self):
            got, self.out = self.out, []
            return got

    class Server:
        """Takes all that is pending (at most 30) and answers it 0.35 s later."""

        def __init__(self, client):
            self.c, self.done = client, 0

        def step(self):
            take = self.c.sent[self.done:self.done + 30]
            if not take:
                return 0
            now[0] += 0.35
            self.done += len(take)
            self.c.out += [Resp(r) for r in take]
            return len(take)

    client = Client()

    def sleep(s):
        now[0] += max(s, 1e-4)

    res = loadgen.run_open_loop(
        client, Server(client), users=np.zeros(1000, np.int64), rate=100.0,
        seconds=2.0, k=1, drain_s=10.0, clock=lambda: now[0], sleep=sleep)
    # batches end at 0.35, 0.7, ...; the one in flight at 2.0 ends at 2.1
    assert res.window_s == pytest.approx(2.1)
    assert res.batch_sizes == [1, 30, 30, 30, 30, 30]
    assert res.batch_ends_s == pytest.approx([0.35, 0.7, 1.05, 1.4, 1.75, 2.1])
    assert res.answered_in_window == 151 == sum(res.batch_sizes)
    # what fell due while the last batch ran was never sent: one thread
    assert res.offered == 176 and res.backlog_at_close == 25
    assert res.unanswered == 0 and len(res.responses) == 176
    assert res.drain_s == pytest.approx(0.35)


def test_compile_inside_the_window_fails_the_run(capsys, monkeypatch, tmp_path):
    from cfk_tpu.serving.engine import ServeEngine

    real = ServeEngine.prewarm

    def half_warm(self, k, *, max_batch=None, **kw):
        return real(self, k, max_batch=8, **kw)  # the wider buckets left cold

    monkeypatch.setattr(ServeEngine, "prewarm", half_warm)
    toy = json.load(open(os.path.join(TOY, "traffic", "toy-serve.json")))
    assert toy["max_batch"] > 8
    # a burst makes the first batch wider than the one warmed bucket
    from benchmarks.harness import loadgen

    real_loop = loadgen.run_open_loop

    def bursty(client, server, **kw):
        for u in kw["users"][:40]:
            client.request(int(u), kw["k"])
        return real_loop(client, server, **kw)

    monkeypatch.setattr(loadgen, "run_open_loop", bursty)
    # a catalogue size no other test has compiled for in this process
    manifest = _with_config(tmp_path, "toy-serve", lambda c: c.update(items=2777))
    res, out = drive(capsys, "toy-serve.serve", manifest=manifest)
    assert res["correct"] is False
    assert "check compiles_in_window" in out and "FAILED" in out


# -- the controls: the nearest precision below the stated one must fail -------

def test_control_bf16_table_fails_the_topk_check(capsys, tmp_path):
    manifest = _with_config(tmp_path, "toy-serve",
                            lambda c: c.update(table_dtype="bfloat16"))
    res, out = drive(capsys, "toy-serve.serve", manifest=manifest)
    assert res["correct"] is False
    assert any("FAILED" in l for l in out.splitlines()
               if "check score_err" in l or "check rank_gap" in l)


# -- refusal ------------------------------------------------------------------

def test_real_cells_refuse_to_run_without_a_tpu():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cell in cells:
        p = subprocess.run(
            [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
             "1", "--seconds", "1", "--trace", "0"], cwd=run.ROOT, env=env,
            capture_output=True, text=True, timeout=300)
        assert p.returncode != 0, cell
        assert "no accelerator" in p.stderr
        assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_refuses_where_the_program_is_absent(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under paths."""
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    cell = json.load(open(tmp_path / "BENCHMARK.json"))["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


# -- a later PR adds by new files and manifest entries alone ------------------

def test_cell_config_mix_and_metric_added_without_editing_a_file(capsys, tmp_path):
    root = tmp_path / "added"
    (root / "configs").mkdir(parents=True)
    (root / "traffic").mkdir()
    (root / "layer_metrics").mkdir()
    cfg = json.load(open(os.path.join(TOY, "configs", "toy-serve.json")))
    cfg.update(name="new-serve", items=2000)
    (root / "configs" / "new-serve.json").write_text(json.dumps(cfg))
    mix = json.load(open(os.path.join(TOY, "traffic", "toy-serve.json")))
    mix.update(rate=120)
    (root / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (root / "layer_metrics" / "offered_rate.py").write_text(
        "def read(ctx, name):\n"
        "    return ctx.window['attempted'] / ctx.window['window_s']\n")
    manifest = json.load(open(MANIFEST))
    manifest["paths"] = ["."]
    manifest["configs"] = [{"name": "new-serve", "source": "none",
                            "file": "configs/new-serve.json", "reduced": [],
                            "why": "added by files alone"}]
    manifest["workloads"] = [{"name": "new-serve.new-mix", "config": "new-serve",
                              "traffic": "new-mix", "chips": 1, "why": "added"}]
    for m in manifest["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["new-serve.new-mix"]
    manifest["per_layer"] = [
        {"name": "offered_rate.new", "unit": "req/s", "better": "higher",
         "source": "program_counter", "layer": "load generator",
         "moves": "serve_req_per_s"},
        # a reader that is already there serves the new cell unchanged
        {"name": "serve_batch_size.new", "unit": "req", "better": "higher",
         "source": "program_counter", "layer": "serving",
         "moves": "serve_req_per_s"}]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    res, _ = drive(capsys, "new-serve.new-mix", trace=1,
                   manifest=str(root / "BENCHMARK.json"))
    assert res["correct"] is True
    assert set(res["metrics"]) == {"offered_rate.new", "serve_batch_size.new"}
    assert res["metrics"]["offered_rate.new"]["value"] == pytest.approx(120, rel=0.05)
