"""The serve-while-folding-in runner with the stream task killed in the
window, end to end at the toy cell beside this file (a CPU rehearsal): the
last line's schema, the recovery's line and metrics, a program without the
supervisor refused before any data, planted faults that each make ``correct``
false by the check that is theirs, and a slow recovery that fails more
operations and stays correct."""

import functools
import json
import os
import time

import numpy as np
import pytest

from benchmarks import run

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "toy_stream_kill")
MANIFEST = os.path.join(TOY, "BENCHMARK.json")
CELL = "toy-stream-kill.foldin-kill"
CHECKS = ["compiles_in_window", "failed_requests", "invalid_id_sets",
          "rank_gap", "score_err", "lost_ratings", "duplicate_cells",
          "uncommitted_reads", "ordinal_rewritten", "stale_reads",
          "foldin_row_err", "reopened_store"]
REAL = "amazon14-stream-r128-durable.serve-foldin-kill"


def drive(capsys, *, trace=0, seed=3_000_000_017, seconds=3):
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


def recover_line(out):
    (line,) = [ln for ln in out.splitlines() if "] recover: killed at" in ln]
    return line


def test_last_line_schema_and_correct_across_the_kill(capsys):
    res, out = drive(capsys)
    assert res["correct"] is True and not failed_checks(res), out[-4000:]
    assert set(res["metrics"]) == {"serve_req_per_s", "setup_s"}
    assert res["attempted"] == pytest.approx(3 * (200 + 150), rel=0.02)
    assert res["metrics"]["serve_req_per_s"]["value"] == pytest.approx(
        200, rel=0.1)
    line = recover_line(out)
    assert "killed at 1.0" in line and "TaskKilled" in line
    for part in ("successor up after", "publishing after", "restore", "state",
                 "republish", "caught up after", "units lost",
                 "records replayed", "snapshot bytes read",
                 "longest batch period in it"):
        assert part in line
    assert "failed operations:" in out and "late inside the outage" in out
    assert "0 never committed, 0 outstanding" in out
    assert "sent before the kill" in out and "store reopened" in out
    # the store and the log are removed after the check
    assert not os.listdir(os.path.join(run.HERE, ".cache", "stream"))


def test_traced_run_reports_the_recovery(capsys):
    res, out = drive(capsys, trace=1, seconds=2)
    assert res["correct"] is True, out[-3000:]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    new = {"stream_recover_s.total", "stream_recover_s.restore",
           "stream_recover_s.state", "stream_recover_s.catchup",
           "stream_recover_units.kill", "stream_lost_units.kill",
           "stream_log_append_ms.p50", "stream_log_append_ms.p95",
           "stream_outage_failed_share.kill"}
    assert new <= set(m)
    assert m["stream_recover_s.total"] >= (m["stream_recover_s.restore"]
                                           + m["stream_recover_s.state"]) > 0
    assert m["stream_recover_units.kill"] >= 1
    assert m["stream_lost_units.kill"] >= 0
    assert 0 < m["stream_log_append_ms.p50"] <= m["stream_log_append_ms.p95"]
    assert 0 <= m["stream_outage_failed_share.kill"] < 0.5
    assert not {"foldin_device_ms.foldin", "foldin_roofline.foldin"} & set(m)


def test_the_readers_report_nothing_without_their_spans():
    import types

    from benchmarks.layer_metrics import (
        stream_log_append_ms, stream_lost_units, stream_outage_failed_share,
        stream_recover_s, stream_recover_units)

    ctx = types.SimpleNamespace(
        program_spans=[{"name": "stream/recover/restore", "dur": 5.0,
                        "args": {}}],  # a set-up's resume, no kill
        window={"attempted": 10}, span_durations_ms=lambda name: [])
    for reader, name in ((stream_recover_s, "stream_recover_s.total"),
                         (stream_recover_s, "stream_recover_s.restore"),
                         (stream_recover_units, "stream_recover_units.kill"),
                         (stream_lost_units, "stream_lost_units.kill"),
                         (stream_log_append_ms, "stream_log_append_ms.p50"),
                         (stream_outage_failed_share,
                          "stream_outage_failed_share.kill")):
        assert reader.read(ctx, name) is None


def test_a_program_without_the_supervisor_is_refused_before_any_data(
        capsys, monkeypatch):
    from cfk_tpu.streaming import StreamSession

    monkeypatch.delattr(StreamSession, "abandon")
    with pytest.raises(SystemExit) as stop:
        drive(capsys)
    assert "lacks StreamSession.abandon" in str(stop.value)
    assert "outlives its stream task" in str(stop.value)
    assert "seen lists" not in capsys.readouterr().out


def test_the_real_manifest_lists_the_cell_where_it_must():
    """By >=: whatever else a later PR lists, these hold the new cell."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    control = "amazon14-stream-r128.serve-foldin"
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[REAL]["chips"] == 1
    assert cells[REAL]["traffic"] == "serve-foldin-kill"
    (e2e,) = [m for m in manifest["end_to_end"]
              if m["name"] == "serve_req_per_s"]
    assert REAL in e2e["workloads"]
    listing = {m["name"] for m in manifest["per_layer"]
               if REAL in m.get("workloads", ())}
    # every metric that lists the control lists the cell, the shares of a
    # peak among them
    assert listing >= {m["name"] for m in manifest["per_layer"]
                       if control in m.get("workloads", ())}
    assert listing >= {"foldin_roofline.foldin", "topk_roofline.saturate",
                       "serve_step_mfu.saturate",
                       "stream_recover_s.total", "stream_recover_s.restore",
                       "stream_recover_s.state", "stream_recover_s.catchup",
                       "stream_recover_units.kill", "stream_lost_units.kill",
                       "stream_log_append_ms.p50", "stream_log_append_ms.p95",
                       "stream_outage_failed_share.kill"}
    _, _, cell, config, traffic = run.load_cell(
        os.path.join(run.ROOT, "BENCHMARK.json"), REAL)
    assert traffic["runner"] == "serve_stream_kill" and traffic["kills"] == 1
    control_mix = run.load_cell(os.path.join(run.ROOT, "BENCHMARK.json"),
                                control)[4]
    for key in ("rate", "rating_rate", "k", "zipf_a", "max_batch",
                "drain_seconds", "trace_seconds", "followup_share",
                "followup_delay_s"):
        assert traffic[key] == control_mix[key]
    assert config["stream"]["log"] == "file" and config["reduced"] == []
    assert config["stream"]["snapshot_every_units"] >= 1


# -- planted faults: each makes ``correct`` false by its own check -----------

def test_a_successor_that_rewinds_the_cursor_without_dedup(
        capsys, monkeypatch):
    """The successor starts one batch before the store's cursor and applies
    those ratings as if it had never seen them."""
    from cfk_tpu.streaming import StreamSession, StreamState

    real_init, real_stage, rewound = StreamSession.__init__, \
        StreamState.stage, []

    @functools.wraps(real_init)  # the runner reads the signature
    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        if self.stream_step and kw.get("engine") is not None:  # a successor
            back = self._since[-1]  # the last unit the store holds
            self.consumer.cursors[0] = (int(back.meta["offsets"]["0"])
                                        - int(back.cells.shape[0]))
            rewound.append(self.state)

    def stage(self, updates, over=()):
        if rewound and self is rewound[0]:
            rewound.pop()
            held, self._delta = self._delta, {}
            try:
                return real_stage(self, updates, over)
            finally:
                self._delta = held
        return real_stage(self, updates, over)

    monkeypatch.setattr(StreamSession, "__init__", init)
    monkeypatch.setattr(StreamState, "stage", stage)
    res, out = drive(capsys)
    assert res["correct"] is False, out[-3000:]
    assert failed_checks(res) == {"duplicate_cells"}


def test_publication_before_durability_with_the_queue_discarded(
        capsys, monkeypatch):
    """The parent's order: a unit is shown to the engine when it is handed
    to the writer.  What the kill discards had been read."""
    from cfk_tpu.streaming import StreamSession

    real = StreamSession._publish_durable

    def eager(self, *, wait=False):
        self._durable_step = self.stream_step
        if hasattr(self.manager, "take_durable"):
            self.manager.take_durable()
            monkeypatch.setattr(type(self.manager), "take_durable",
                                lambda m: [], raising=False)
        return real(self, wait=False)

    monkeypatch.setattr(StreamSession, "_publish_durable", eager)
    res, out = drive(capsys)
    assert res["correct"] is False, out[-3000:]
    failed = failed_checks(res)
    assert failed & {"uncommitted_reads", "ordinal_rewritten"}, failed
    assert "units lost" in recover_line(out)


def test_a_successor_that_never_comes(capsys, monkeypatch):
    from cfk_tpu.serving import server

    monkeypatch.setattr(server._Recovery, "bring_up",
                        lambda self, factory: time.sleep(30))
    res, out = drive(capsys)
    assert res["correct"] is False and res["failed"] > 0
    assert failed_checks(res) == {"lost_ratings"}, out[-3000:]
    # the server went on answering all the while
    assert res["checks"]["failed_requests"]["value"] == 0


def test_a_stale_read_outside_the_outage(capsys, monkeypatch):
    from cfk_tpu.streaming import StreamSession

    real, t0 = StreamSession.pump, []

    def sluggish(self, **kw):
        t0.append(time.perf_counter())
        # nothing is folded in for the first 0.8 s (visible_within_s is 0.5,
        # the kill comes at 1.0 s): the follow-ups of the first 0.2 s read
        # what was not there, long before the outage
        return real(self, **kw) if time.perf_counter() - t0[0] > 0.8 else 0

    monkeypatch.setattr(StreamSession, "pump", sluggish)
    res, out = drive(capsys)
    assert res["correct"] is False and res["failed"] > 0
    assert failed_checks(res) == {"stale_reads"}, out[-3000:]


def test_a_recovery_slowed_threefold_fails_more_and_stays_correct(
        capsys, monkeypatch):
    from cfk_tpu.serving import server

    sound, _ = drive(capsys)
    real = server._Recovery.bring_up

    def slow(self, factory):
        time.sleep(0.9)  # visible_within_s is 0.5
        return real(self, factory)

    monkeypatch.setattr(server._Recovery, "bring_up", slow)
    res, out = drive(capsys)
    assert res["correct"] is True and not failed_checks(res), out[-3000:]
    assert res["failed"] > sound["failed"]
    assert res["failed"] >= 0.3 * 150  # the ratings of the outage, late
    (kinds,) = [ln for ln in out.splitlines() if "failed operations:" in ln]
    late_inside = int(kinds.split(" ratings late inside the outage")[0]
                      .split()[-1].replace(",", ""))
    assert late_inside == res["failed"] or "stale reads inside" in kinds
    assert late_inside > 0
