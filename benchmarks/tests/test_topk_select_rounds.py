"""The reader of the scorer's selection rounds per tile
(``topk_select_rounds.saturate``) against a hand-written span list, a program
without the counts, and a traced toy run."""

import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.tests.test_runner_cpu import TOY, drive
from benchmarks.tests.test_serve_stage_metrics import _ctx, _reader, _span

NAME = "topk_select_rounds.saturate"


def _entry():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return next(m for m in json.load(f)["per_layer"] if m["name"] == NAME)


def test_reads_the_median_ratio_over_the_batches():
    read = _reader("topk_select_rounds").read
    spans = [
        _span("serve/batch/compute", 0, 900, n=4, b=8, k=16,
              select_rounds=30, select_tiles=20, tiles=100),
        _span("serve/batch/compute", 2_000, 900, n=4, b=8, k=16,
              select_rounds=50, select_tiles=40, tiles=100),
        _span("serve/batch/compute", 4_000, 900, n=4, b=8, k=16,
              select_rounds=1_600, select_tiles=100, tiles=100),
        _span("serve/batch/upload", 5_000, 10, bytes=64),
    ]
    assert read(_ctx(spans), NAME) == pytest.approx(0.5)


def test_a_program_without_the_counts_reads_nothing():
    """The parent commit's span has ``n``, ``b`` and ``k`` alone."""
    read = _reader("topk_select_rounds").read
    assert read(_ctx([]), NAME) is None
    parent = [_span("serve/batch/compute", 0, 900, n=4, b=8, k=16)]
    assert read(_ctx(parent), NAME) is None


def test_the_manifest_entry():
    entry = _entry()
    assert entry == {
        "name": NAME, "unit": "rounds/tile", "better": "lower",
        "source": "program_counter", "layer": "serving kernel",
        "moves": "serve_req_per_s",
        "workloads": ["amazon14-serve-r128.serve-saturate",
                      "amazon23-serve-r128.serve-saturate-x4"]}


def test_traced_toy_run_reports_rounds_per_tile(capsys, tmp_path):
    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["per_layer"].append(dict(_entry(), workloads=["toy-serve.serve"]))
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    res, _ = drive(capsys, "toy-serve.serve", trace=1,
                   manifest=str(root / "BENCHMARK.json"))
    assert res["correct"] is True
    got = res["metrics"][NAME]
    assert got["unit"] == "rounds/tile"
    # the server pads K = 10 to 16: the first of the six tiles fills the
    # carry in 16 rounds, a later one needs fewer
    assert 16 / 6 <= got["value"] < 16
