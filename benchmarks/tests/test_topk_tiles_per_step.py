"""The reader of the scorer's tiles per grid step
(``topk_tiles_per_step.saturate``) against a hand-written span list, a program
whose span lacks the two attributes, the manifest entry, and a traced toy
run."""

import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.tests.test_runner_cpu import TOY, drive
from benchmarks.tests.test_serve_stage_metrics import _ctx, _reader, _span

NAME = "topk_tiles_per_step.saturate"
CELLS = ["amazon14-serve-r128.serve-saturate",
         "amazon23-serve-r128.serve-saturate-x4",
         "amazon23-serve-r128-int8.serve-saturate-int8",
         "amazon14-stream-r128.serve-foldin"]


def _entry():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return next(m for m in json.load(f)["per_layer"] if m["name"] == NAME)


def test_reads_the_median_ratio_over_the_batches():
    read = _reader("topk_tiles_per_step").read
    spans = [
        _span("serve/batch/compute", 0, 900, n=4, b=8, k=16, tiles=100,
              slab_tiles=16, grid_steps=7),
        _span("serve/batch/compute", 2_000, 900, n=4, b=8, k=16, tiles=100,
              slab_tiles=8, grid_steps=13),
        _span("serve/batch/compute", 4_000, 900, n=4, b=8, k=16, tiles=100,
              slab_tiles=1, grid_steps=100),
        # a step under a backlog whose compute holds a dispatch and no fetch
        _span("serve/batch/compute", 6_000, 900),
        _span("serve/batch/upload", 7_000, 10, bytes=64),
    ]
    assert read(_ctx(spans), NAME) == pytest.approx(100 / 13)
    # the one-chip cell's call: 18,262 tiles in 1,142 steps of 16
    cell = [_span("serve/batch/compute", 0, 900, tiles=18_262, slab_tiles=16,
                  grid_steps=1_142)]
    assert read(_ctx(cell), NAME) == pytest.approx(15.991, abs=1e-3)


@pytest.mark.parametrize("args", [
    None,  # no span at all
    dict(n=4, b=8, k=16),  # before the gated selection
    dict(n=4, b=8, k=16, select_rounds=30, select_tiles=20, seen_chunks=9,
         seen_hit_tiles=9, tiles=100, table_dtype="float32",
         scan_bytes=1 << 20, score_passes=6),
], ids=["no_span", "n_b_k_alone", "the_parent"])
def test_a_program_without_the_attributes_reads_nothing(args):
    """The parent commit's span has ``tiles`` and neither ``slab_tiles``
    nor ``grid_steps``: nothing to read, and nothing raised."""
    read = _reader("topk_tiles_per_step").read
    spans = [] if args is None else [
        _span("serve/batch/compute", 0, 900, **args)]
    assert read(_ctx(spans), NAME) is None


def test_the_manifest_entry():
    assert _entry() == {
        "name": NAME, "unit": "tiles/step", "better": "higher",
        "source": "program_counter", "layer": "serving kernel",
        "moves": "serve_req_per_s", "workloads": CELLS}


def test_traced_toy_run_reports_tiles_per_step(capsys, tmp_path):
    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["per_layer"].append(dict(_entry(), workloads=["toy-serve.serve"]))
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    res, _ = drive(capsys, "toy-serve.serve", trace=1,
                   manifest=str(root / "BENCHMARK.json"))
    assert res["correct"] is True
    got = res["metrics"][NAME]
    assert got["unit"] == "tiles/step"
    # six tiles: one ragged step of a slab of four and one of two
    assert got["value"] == pytest.approx(3.0)
