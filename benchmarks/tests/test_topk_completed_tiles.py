"""The reader of the share of the tiles the scorer completed
(``topk_completed_tiles.saturate``) against hand-written span lists (the count
there, a program that completes every tile and says so by carrying no count, a
span without ``tiles``), the manifest entry wherever it stands, and traced toy
runs over an int8 and a float32 table."""

import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.tests import test_runner_cpu, test_runner_q8_cpu
from benchmarks.tests.test_serve_stage_metrics import _ctx, _reader, _span

NAME = "topk_completed_tiles.saturate"
INT8_CELLS = ["amazon23-serve-r128-int8.serve-saturate-int8",
              "amazon23-stream-r128-int8.serve-foldin-skew-int8"]


def _entry():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return next(m for m in json.load(f)["per_layer"] if m["name"] == NAME)


def test_reads_the_median_share_over_the_batches():
    read = _reader("topk_completed_tiles").read
    common = dict(n=4, b=8, k=16, score_passes=3, tiles=200)
    spans = [
        _span("serve/batch/compute", 0, 900, completed_tiles=20, **common),
        _span("serve/batch/compute", 2_000, 900, completed_tiles=30, **common),
        _span("serve/batch/compute", 4_000, 900, completed_tiles=200,
              **common),
        _span("serve/batch/upload", 5_000, 10, bytes=64),
    ]
    assert read(_ctx(spans), NAME) == pytest.approx(0.15)


def test_a_program_that_completes_every_tile_reads_one():
    """The parent commit's span says how many passes a tile takes and how
    many tiles were scanned, and has no ``completed_tiles``: every tile ran
    every pass."""
    read = _reader("topk_completed_tiles").read
    parent = [_span("serve/batch/compute", t, 900, n=4, b=8, k=16,
                    select_rounds=30, select_tiles=20, seen_chunks=9,
                    seen_hit_tiles=9, tiles=100, score_passes=3)
              for t in (0, 2_000)]
    assert read(_ctx(parent), NAME) == 1.0


@pytest.mark.parametrize("args", [
    None,  # no span at all
    dict(n=4, b=8, k=16),  # before the gated selection
    dict(n=4, b=8, k=16, score_passes=3),  # no tiles: nothing to divide by
    dict(n=4, b=8, k=16, completed_tiles=5, tiles=0, score_passes=3),
    dict(n=4, b=8, k=16, select_rounds=30, tiles=100),  # before PR 35
], ids=["no_span", "n_b_k_alone", "no_tiles", "zero_tiles", "no_passes"])
def test_a_span_without_tiles_or_passes_reads_nothing(args):
    read = _reader("topk_completed_tiles").read
    spans = [] if args is None else [
        _span("serve/batch/compute", 0, 900, **args)]
    assert read(_ctx(spans), NAME) is None


def test_the_manifest_entry():
    assert _entry() == {
        "name": NAME, "unit": "share", "better": "lower",
        "source": "program_counter", "layer": "serving kernel",
        "moves": "serve_req_per_s", "workloads": INT8_CELLS}


@pytest.mark.parametrize("toy", ["int8", "float32"])
def test_traced_toy_run_reports_the_share(capsys, tmp_path, toy):
    mod, cell = ((test_runner_q8_cpu, test_runner_q8_cpu.CELL)
                 if toy == "int8" else (test_runner_cpu, "toy-serve.serve"))
    root = tmp_path / "toy"
    shutil.copytree(mod.TOY, root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["per_layer"].append(dict(_entry(), workloads=[cell]))
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    path = str(root / "BENCHMARK.json")
    if toy == "int8":
        res, _ = mod.drive(capsys, trace=1, manifest=path)
    else:
        res, _ = mod.drive(capsys, cell, trace=1, manifest=path)
    assert res["correct"] is True
    got = res["metrics"][NAME]
    assert got["unit"] == "share"
    if toy == "int8":
        # 47 tiles of 64 rows: the first fill the carry, and the gate of a
        # later one opens or not by what pass 0 finds there
        assert 1 / 47 <= got["value"] <= 1
    else:
        assert got["value"] == 1.0
