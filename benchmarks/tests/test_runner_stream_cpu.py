"""The serve-while-folding-in runner end to end at the toy cell beside this
file (a CPU rehearsal): the last line's schema, the per-layer metrics of a
traced run, a program without the entry points refused before any data, and
planted faults that each make ``correct`` false by the check that is theirs."""

import json
import os

import numpy as np
import pytest

from benchmarks import run

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_stream")
MANIFEST = os.path.join(TOY, "BENCHMARK.json")
CELL = "toy-stream.foldin"
CHECKS = ["compiles_in_window", "failed_requests", "invalid_id_sets",
          "rank_gap", "score_err", "lost_ratings", "stale_reads",
          "foldin_row_err", "reopened_store"]


def drive(capsys, *, trace=0, seed=3_000_000_017, seconds=2):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), "--manifest",
                   MANIFEST], require_tpu=False)
    out, err = capsys.readouterr()
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert list(res)[-1] == "checks" and list(res["checks"]) == CHECKS
    assert err.strip().splitlines()[-1] == f"correct: {res['correct']}"
    return res, out


def failed_checks(res):
    return {n for n, c in res["checks"].items()
            if not (isinstance(c["value"], float)
                    and c["value"] <= c["limit"])}


def test_last_line_schema_and_correct(capsys):
    res, out = drive(capsys)
    assert res["correct"] is True and res["failed"] == 0, out
    assert not failed_checks(res)
    assert set(res["metrics"]) == {"serve_req_per_s", "setup_s"}
    # requests and ratings are both operations: 200 req/s and 150 ratings/s
    assert res["attempted"] == pytest.approx(2 * (200 + 150), rel=0.02)
    assert res["metrics"]["serve_req_per_s"]["value"] == pytest.approx(
        200, rel=0.1)
    assert "0 late, 0 never committed" in out and "store reopened" in out


def test_traced_run_reports_the_stream_metrics(capsys):
    res, out = drive(capsys, trace=1)
    assert res["correct"] is True, out[-3000:]
    got = set(res["metrics"])
    # the device's numbers need a device: nothing on the CPU, and no error
    assert not {"foldin_device_ms.foldin", "foldin_roofline.foldin"} & got
    assert got == {
        "setup_data_s", "stream_ratings_per_s.foldin",
        "stream_visible_ms.p50", "stream_visible_ms.p95",
        "stream_touched_users.foldin", "serve_span_ms.period",
    } | {f"stream_span_ms.{s}" for s in (
        "stage", "neighbors", "solve", "apply", "commit", "publish", "batch")}
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["stream_ratings_per_s.foldin"] == pytest.approx(150, rel=0.25)
    assert 1 <= m["stream_touched_users.foldin"] <= 32
    assert 0 < m["stream_visible_ms.p50"] <= m["stream_visible_ms.p95"] < 500
    # a batch's stages lie inside it
    assert m["stream_span_ms.batch"] >= m["stream_span_ms.solve"] > 0


def test_a_program_without_the_entry_points_is_refused_before_any_data(
        capsys, monkeypatch):
    from cfk_tpu.streaming import StreamState

    monkeypatch.delattr(StreamState, "from_csr")
    with pytest.raises(SystemExit) as stop:
        drive(capsys)
    assert "lacks StreamState.from_csr" in str(stop.value)
    assert "seen lists" not in capsys.readouterr().out


def test_the_roofline_counts_are_the_spans(capsys):
    from benchmarks.harness import roofline, roofline_foldin

    pk = roofline.PEAKS["TPU v5 lite"]
    args = dict(entities=256, width=128, rank=128,
                gather_bytes=256 * 128 * 128 * 4,
                operand_bytes=3 * 256 * 128 * 4 + 256 * 4)
    cost = roofline_foldin.foldin_cost(**args)
    # the issue's reckoning: a Gram of 1.07 GFLOP, 0.36 GFLOP of LU solves,
    # 16.8 MB gathered
    assert cost.flops == pytest.approx(1.07e9 + 0.36e9, rel=0.02)
    assert cost.bytes == pytest.approx(16.8e6 + 0.39e6 + 0.13e6, rel=0.02)
    assert roofline_foldin.batch_floor_s(args, pk) == pytest.approx(
        cost.bytes / 819e9)
    assert roofline_foldin.batch_floor_s({"touched": 0}, pk) is None


# -- planted faults: each makes ``correct`` false by its own check -----------

def test_a_rating_made_visible_late(capsys, monkeypatch):
    import time

    from cfk_tpu.streaming import StreamSession

    real, t0 = StreamSession.pump, []

    def sluggish(self, **kw):
        t0.append(time.perf_counter())
        # nothing is folded in for the first 0.8 s: visible_within_s is 0.5
        return real(self, **kw) if time.perf_counter() - t0[0] > 0.8 else 0

    monkeypatch.setattr(StreamSession, "pump", sluggish)
    res, _ = drive(capsys)
    # the server went on answering: the follow-ups read what was not there
    assert res["correct"] is False and res["failed"] > 0
    assert failed_checks(res) == {"stale_reads"}


def test_a_stall_of_the_whole_loop_fails_operations_and_breaks_no_read(
        capsys, monkeypatch):
    """The machine stops for longer than visible_within_s: the ratings sent
    just before are visible late, failed operations each, and no request
    was answered meanwhile, so none read a stale list: the run is slower,
    not wrong."""
    import time

    from cfk_tpu.serving import RecommendServer

    real, t0 = RecommendServer.step, []

    def stalls_once(self):
        t0.append(time.perf_counter())
        if len(t0) > 1 and t0[-1] - t0[0] > 1.0 and "done" not in t0:
            t0.append("done")
            time.sleep(0.8)  # visible_within_s is 0.5
        return real(self)

    monkeypatch.setattr(RecommendServer, "step", stalls_once)
    res, out = drive(capsys)
    assert "done" in t0
    assert res["correct"] is True and not failed_checks(res), out[-3000:]
    # what was in the log and on the device when it stopped: a batch or two
    assert 0 < res["failed"] <= 2 * 32 + 8


def test_a_rating_never_committed(capsys, monkeypatch):
    from cfk_tpu.streaming import StreamProducer

    real, calls = StreamProducer.send_many, []

    def lossy(self, users, items, values):
        calls.append(len(users))
        if len(calls) == 40:  # one sending never reaches the log
            return None
        return real(self, users, items, values)

    monkeypatch.setattr(StreamProducer, "send_many", lossy)
    res, _ = drive(capsys)
    assert res["correct"] is False and res["failed"] > 0
    assert "lost_ratings" in failed_checks(res)


def test_an_answer_scored_with_the_vector_of_the_ordinal_before(
        capsys, monkeypatch):
    from cfk_tpu.serving.engine import ServeEngine

    real = ServeEngine.on_commit

    def one_behind(self, event):
        # the ordinal and the cells of this commit, but every touched user
        # keeps the vector it had as of the ordinal before
        rows = np.asarray(event["touched_rows"], np.int64)
        known = rows < self.num_users
        stale = np.array(event["rows"], np.float32)
        stale[known] = self._gather_users(rows[known])
        return real(self, dict(event, rows=stale))

    monkeypatch.setattr(ServeEngine, "on_commit", one_behind)
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert "score_err" in failed_checks(res)


def test_a_newly_rated_item_served(capsys, monkeypatch):
    from cfk_tpu.serving.engine import ServeEngine

    real = ServeEngine.on_commit
    monkeypatch.setattr(
        ServeEngine, "on_commit",
        lambda self, event: real(self, dict(event, cells=())))
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert "invalid_id_sets" in failed_checks(res)


def test_a_fold_in_through_bfloat16(capsys, monkeypatch):
    """The lower-precision control: the gathered item rows and their Gram
    pass through bfloat16 (no knob of the program does that: the patch is
    what ``PERF.md`` section 2's control applies on the chip)."""
    import jax.numpy as jnp

    from cfk_tpu.ops import solve
    from cfk_tpu.streaming import foldin

    # jax keeps traces by function and shapes: start and end with none
    foldin._padded_fold.clear_cache()
    monkeypatch.setattr(solve, "_gram_compute_dtype",
                        lambda fixed: (jnp.bfloat16, None))
    try:
        res, _ = drive(capsys)
    finally:
        foldin._padded_fold.clear_cache()
    assert res["correct"] is False
    assert "foldin_row_err" in failed_checks(res)
    assert res["checks"]["foldin_row_err"]["value"] > 10 * 3e-5


def test_a_commit_unit_dropped_before_the_reopen(capsys, monkeypatch):
    from cfk_tpu.transport import CheckpointManager

    real = CheckpointManager.save

    def forgetful(self, iteration, users, movies, meta=None):
        if iteration == 7 and (meta or {}).get("kind") == "unit":
            return ""
        return real(self, iteration, users, movies, meta=meta)

    monkeypatch.setattr(CheckpointManager, "save", forgetful)
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert "reopened_store" in failed_checks(res)


def test_a_fold_in_program_traced_inside_the_window(capsys, monkeypatch):
    from cfk_tpu.streaming import StreamSession, foldin

    monkeypatch.setattr(
        StreamSession, "prewarm",
        lambda self, **kw: {"programs": 0, "new_traces": 0, "prewarm_s": 0.0})
    foldin._padded_fold.clear_cache()
    res, _ = drive(capsys)
    assert res["correct"] is False
    assert "compiles_in_window" in failed_checks(res)
