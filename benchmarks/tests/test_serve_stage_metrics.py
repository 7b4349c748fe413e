"""The readers of the serve batch's stages (``serve_span_ms.<stage>``,
``serve_upload_mb``) against a hand-written span list, and a traced toy run
that reports every one of them."""

import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.tests.test_runner_cpu import TOY, drive

LAYER = os.path.join(run.HERE, "layer_metrics")
STAGES = ("poll", "assemble", "seen_tiles", "upload", "dispatch", "fetch",
          "respond", "outside", "period")


def _reader(family):
    return run.load_module(os.path.join(LAYER, family + ".py"),
                           "reader_under_test_" + family)


def _span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1, "tid": 1,
            "args": args}


def _ctx(spans):
    ctx = run.Ctx.__new__(run.Ctx)
    ctx.program_spans = spans
    return ctx


def _three_batches():
    """Batches 7, 8, 9 start at 1,000, 11,000 and 26,000 us."""
    spans = []
    for n, t0, poll, batch, upload, mb in ((7, 1_000, 200, 7_000, 1_000, 300),
                                           (8, 11_000, 400, 9_000, 3_000, 100),
                                           (9, 26_000, 300, 8_000, 2_000, 200)):
        spans += [
            _span("serve/poll", t0 - poll - 50, poll, batch=n, requests=4),
            _span("serve/batch", t0, batch, batch=n, requests=4),
            _span("serve/batch/upload", t0 + 500, upload, bytes=mb * 10**6),
        ]
    return spans


def test_a_stage_with_a_span_reads_the_median_of_its_durations():
    read = _reader("serve_span_ms").read
    ctx = _ctx(_three_batches())
    assert read(ctx, "serve_span_ms.poll") == pytest.approx(0.3)
    assert read(ctx, "serve_span_ms.upload") == pytest.approx(2.0)
    assert _reader("serve_upload_mb").read(ctx, "serve_upload_mb.saturate") \
        == pytest.approx(200.0)


def test_outside_and_period_come_from_starts_and_ends():
    read = _reader("serve_span_ms").read
    ctx = _ctx(_three_batches())
    # starts 1,000 -> 11,000 -> 26,000 us
    assert read(ctx, "serve_span_ms.period") == pytest.approx(12.5)
    # batch 7 ends at 8,000, poll 8 starts at 11,000 - 450 = 10,550: 2.55 ms;
    # batch 8 ends at 20,000, poll 9 starts at 26,000 - 350 = 25,650: 5.65 ms
    assert read(ctx, "serve_span_ms.outside") == pytest.approx((2.55 + 5.65) / 2)
    # a batch whose next poll belongs to another ordinal gives no reading
    spans = [e for e in _three_batches()
             if not (e["name"] == "serve/poll" and e["args"]["batch"] == 8)]
    assert read(_ctx(spans), "serve_span_ms.outside") == pytest.approx(5.65)


@pytest.mark.parametrize("stage", STAGES)
def test_an_absent_span_reads_nothing(stage):
    """What the parent commit's program gives: ``serve/batch`` with no
    ordinal and none of the new spans (``period`` needs only its starts)."""
    read = _reader("serve_span_ms").read
    assert read(_ctx([]), "serve_span_ms." + stage) is None
    parent = [_span("serve/batch", 0, 5_000, requests=4),
              _span("serve/batch/assemble", 10, 100, n=4, b=4),
              _span("serve/batch/respond", 4_000, 900, responses=4),
              _span("serve/batch", 9_000, 5_000, requests=4)]
    got = read(_ctx(parent), "serve_span_ms." + stage)
    if stage in ("assemble", "respond", "period"):
        assert got == pytest.approx({"assemble": 0.1, "respond": 0.9,
                                     "period": 9.0}[stage])
    else:
        assert got is None
    assert _reader("serve_upload_mb").read(_ctx(parent), "x.saturate") is None


def test_an_unknown_stage_is_an_error():
    with pytest.raises(ValueError, match="no stage"):
        _reader("serve_span_ms").read(_ctx([]), "serve_span_ms.nonsense")


def test_traced_toy_run_reports_every_stage_metric(capsys, tmp_path):
    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    new = [m for m in real["per_layer"]
           if m["name"].split(".")[0] in ("serve_span_ms", "serve_upload_mb")]
    assert len(new) == 10
    manifest["per_layer"] += [dict(m, workloads=["toy-serve.serve"])
                              for m in new]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    res, _ = drive(capsys, "toy-serve.serve", trace=1,
                   manifest=str(root / "BENCHMARK.json"))
    assert res["correct"] is True
    got = res["metrics"]
    for m in new:
        assert got[m["name"]]["unit"] == m["unit"]
        assert got[m["name"]]["value"] > 0, m["name"]
    ms = lambda stage: got["serve_span_ms." + stage]["value"]
    # the stages lie inside the period they are the parts of
    assert sum(ms(s) for s in STAGES[:-1]) <= ms("period")
    # float32 [b, 16] users and a [tiles, b, width] int32 rectangle
    assert got["serve_upload_mb.saturate"]["value"] < 1.0
    # the gaps of the device are named by the program's spans
    names = {name for name, _ in res["breakdown"]["idle_gaps"]}
    assert names <= {"no host span", "serve/poll", "serve/batch"} | {
        "serve/batch/" + c for c in ("validate", "assemble", "seen_tiles",
                                     "upload", "compute", "compute/dispatch",
                                     "compute/fetch", "respond")}


def test_the_stages_tool_cuts_the_spans_batch_by_batch():
    stages = run.load_module(os.path.join(run.HERE, "tools", "stages.py"),
                             "stages_under_test")
    rows = stages.per_batch(_three_batches(), _reader("serve_span_ms").by_batch)
    assert [r["batch"] for r in rows] == [7, 8, 9]
    first, _, last = rows
    assert first["inside_ms"] == {"serve/batch/upload": pytest.approx(1.0)}
    assert first["poll_ms"] == pytest.approx(0.2)
    assert first["outside_ms"] == pytest.approx(2.55)
    assert first["period_ms"] == pytest.approx(10.0)
    # the parts of a period: the batch, the stretch to the next poll, that
    # poll, and the step from the poll to the next batch (50 us here)
    assert (first["batch_ms"] + first["outside_ms"] + first["next_poll_ms"]
            == pytest.approx(first["period_ms"] - 0.05))
    assert "period_ms" not in last and "outside_ms" not in last
