"""The department-page runner end to end at a toy cell kept beside this file
(a CPU rehearsal of ``runners/serve_dept.py``): the last line's schema, the
three per-layer metrics of a traced run, and every planted fault failing
exactly its check."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import reference_dept

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_dept")
MANIFEST = os.path.join(TOY, "BENCHMARK.json")
CELL = "toy-serve-dept.serve-dept"
CHECKS = ["compiles_in_window", "failed_requests", "invalid_id_sets",
          "wrong_department_batches", "rank_gap", "score_err"]


def drive(capsys, *, trace=0, seed=3_000_000_017, manifest=MANIFEST, seconds=1):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), "--manifest", manifest],
                  require_tpu=False)
    out, _ = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), out


def failed(res) -> set:
    return {name for name, c in res["checks"].items()
            if not (isinstance(c["value"], float) and c["value"] <= c["limit"])}


def _with_config(tmp_path, edit):
    root = tmp_path / "toy_dept"
    shutil.copytree(TOY, root)
    path = root / "configs" / "toy-serve-dept.json"
    cfg = json.loads(path.read_text())
    edit(cfg)
    path.write_text(json.dumps(cfg))
    return str(root / "BENCHMARK.json")


def test_last_line_schema_and_correct(capsys):
    res, out = drive(capsys)
    assert list(res["checks"]) == CHECKS and not failed(res)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_req_per_s", "setup_s"}
    assert res["metrics"]["serve_req_per_s"]["value"] == pytest.approx(200, rel=0.1)
    assert "batches by department" in out


def test_traced_run_reports_the_department_metrics(capsys):
    res, _ = drive(capsys, trace=1)
    assert res["correct"] is True
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(m) == {"setup_data_s", "serve_dept_scan_share.toy",
                      "serve_dept_batch_rows.toy", "serve_dept_grid_pad.toy"}
    # departments of 900, 650 and 350 rows of 3,000 (24 tiles of 128), asked
    # in proportion to 9 : 6 : 5; each range straddles tiles
    assert 0.2 < m["serve_dept_scan_share.toy"] < 0.35
    assert m["serve_dept_batch_rows.toy"] >= 1.0
    assert m["serve_dept_grid_pad.toy"] >= 1.0


def test_the_readers_report_nothing_on_a_program_without_the_spans():
    """The parent's traced runs: no ``department`` on any span, no
    ``table_rows_whole`` in the window."""
    import types

    from benchmarks.layer_metrics import (
        serve_dept_batch_rows, serve_dept_grid_pad, serve_dept_scan_share)

    spans = [{"name": "serve/batch", "args": {"requests": 256, "batch": 1}},
             {"name": "serve/batch/compute", "args": {"tiles": 18262}}]
    ctx = types.SimpleNamespace(
        program_spans=spans, window={"table_rows": 9350144},
        config={"engine": {"tile_m": 512}})
    for reader in (serve_dept_batch_rows, serve_dept_grid_pad,
                   serve_dept_scan_share):
        assert reader.read(ctx, "x.y") is None


def test_the_reference_reads_departments_from_the_list_alone():
    depts = [{"name": "a", "items": 3}, {"name": "b", "items": 2}]
    assert reference_dept.ranges(depts) == [(0, 3), (3, 5)]
    assert reference_dept.department_of_rows(depts).tolist() == [0, 0, 0, 1, 1]
    table = np.asarray([[1.0], [5.0], [3.0], [9.0], [7.0]], np.float32)
    best, scores = reference_dept.exact_topk(
        np.ones((1, 1), np.float32), table, [np.asarray([1, 4])], 2, 0, 3)
    assert best.tolist() == [[3.0, 1.0]] and scores.shape == (1, 3)
    ids = np.asarray([[2, 0]])
    assert reference_dept.topk_gaps(ids, best, best, scores, 0) == (0.0, 0.0)
    # the best missed for a worse one
    gap, err = reference_dept.topk_gaps(
        np.asarray([[0]]), best[:, 1:], best[:, :1], scores, 0)
    assert gap == pytest.approx(2 / 3) and err == 0.0
    # an id of the neighbouring department: invalid, and left out of the gaps
    assert reference_dept.invalid_id_sets(
        [np.asarray([2, 3])], [np.asarray([1, 4])], [(0, 3)], 2) == 1
    assert reference_dept.topk_gaps(
        np.asarray([[2, 3]]), best, best, scores, 0) == (0.0, 0.0)
    assert reference_dept.invalid_id_sets(
        [np.asarray([2, 1])], [np.asarray([1, 4])], [(0, 3)], 2) == 1  # seen
    assert reference_dept.invalid_id_sets(
        [np.asarray([2, 0])], [np.asarray([1, 4])], [(0, 3)], 2) == 0


# -- planted faults: each fails exactly its check ------------------------------

def test_a_scan_cut_one_tile_short_fails_rank_gap(capsys, monkeypatch):
    from cfk_tpu.serving.engine import ServeEngine

    real = ServeEngine.department_range

    def short(self, department):
        lo, hi = real(self, department)
        return lo, hi - self.tile_m  # the department's last tile never scanned

    monkeypatch.setattr(ServeEngine, "department_range", short)
    res, _ = drive(capsys)
    assert res["correct"] is False and failed(res) == {"rank_gap"}


def test_an_id_of_the_neighbouring_department_fails_invalid_id_sets(
        capsys, monkeypatch):
    from cfk_tpu.serving.engine import ServeEngine

    real = ServeEngine.topk

    def neighbour(self, rows, k, department=None, **kw):
        vals, ids = real(self, rows, k, department=department, **kw)
        ids = ids.copy()
        ids[:, 0] = self.department_range(department)[1]  # the next one's first
        return vals, ids

    monkeypatch.setattr(ServeEngine, "topk", neighbour)
    res, _ = drive(capsys)
    assert res["correct"] is False and failed(res) == {"invalid_id_sets"}


def test_two_batches_answered_as_one_fail_wrong_department_batches(
        capsys, monkeypatch):
    """Every answer right, but a step hands back the answers of two batches
    together, and they name two departments: the batch as the client sees
    it was mixed."""
    from cfk_tpu.serving.server import RecommendServer

    real = RecommendServer.step
    held, idle = [], [0]

    def fused(self):
        n = real(self)
        idle[0] = 0 if n else idle[0] + 1
        if held and idle[0] > 200:
            return held.pop()  # the odd one out, once the traffic has ended
        if not n:
            return 0
        if held:
            return n + held.pop()
        held.append(n)  # answered, and not owned up to until the next
        return 0

    monkeypatch.setattr(RecommendServer, "step", fused)
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert failed(res) == {"wrong_department_batches"}


def test_the_bfloat16_control_fails_score_err(capsys, tmp_path):
    manifest = _with_config(tmp_path,
                            lambda c: c.update(table_dtype="bfloat16"))
    res, _ = drive(capsys, manifest=manifest)
    assert res["correct"] is False
    assert "score_err" in failed(res) and failed(res) <= {"score_err",
                                                          "rank_gap"}


def test_a_rung_left_cold_fails_compiles_in_window(capsys, monkeypatch, tmp_path):
    from cfk_tpu.serving.engine import ServeEngine

    real = ServeEngine.prewarm

    def whole_table_only(self, k, **kw):
        return real(self, k, **{**kw, "departments": []})

    monkeypatch.setattr(ServeEngine, "prewarm", whole_table_only)
    # a catalogue size no other test has compiled for in this process

    def resize(c):
        c.update(items=2777)
        c["departments"][3]["items"] -= 223

    # a window long enough to outlast the compiles it has to wait for, so
    # that every department's requests are still sent and sampled
    res, out = drive(capsys, manifest=_with_config(tmp_path, resize),
                     seconds=4)
    assert res["correct"] is False
    assert failed(res) == {"compiles_in_window"}, out[-3000:]


def test_requests_of_one_department_never_answered_fail_failed_requests(
        capsys, monkeypatch):
    """Department 1's requests past its first dozen are consumed and never
    answered: the sample still holds eight of them, the backlog cannot be
    served out."""
    from cfk_tpu.serving.server import RecommendServer

    real = RecommendServer._poll_requests
    kept = []

    def lossy(self):
        out = []
        for r in real(self):
            if r.department == 1:
                kept.append(r.req_id)
                if len(kept) > 12:
                    continue
            out.append(r)
        return out

    monkeypatch.setattr(RecommendServer, "_poll_requests", lossy)
    res, _ = drive(capsys)
    assert res["correct"] is False and res["failed"] > 0
    assert failed(res) == {"failed_requests"}


def test_a_department_left_short_of_its_sample_reads_inf(capsys, monkeypatch):
    from cfk_tpu.serving.server import RecommendServer

    real = RecommendServer._poll_requests
    monkeypatch.setattr(
        RecommendServer, "_poll_requests",
        lambda self: [r for r in real(self) if r.department != 2])
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert failed(res) == {"failed_requests", "rank_gap", "score_err"}
    assert res["checks"]["rank_gap"]["value"] == "inf"


def test_a_share_of_whole_catalogue_requests_is_held_to_both_references(
        capsys, tmp_path):
    root = tmp_path / "toy_dept"
    shutil.copytree(TOY, root)
    path = root / "traffic" / "toy-serve-dept.json"
    mix = json.loads(path.read_text())
    mix["whole_catalogue_share"] = 0.25
    path.write_text(json.dumps(mix))
    res, out = drive(capsys, manifest=str(root / "BENCHMARK.json"))
    assert res["correct"] is True and not failed(res)
    assert "-1: " in out  # batches that named none
