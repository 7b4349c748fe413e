"""The reader of the scorer's exclusion chunks per tile
(``topk_seen_chunks.saturate``) against a hand-written span list, a program
without the count, the manifest entry, and a traced toy run."""

import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.tests.test_runner_cpu import TOY, drive
from benchmarks.tests.test_serve_stage_metrics import _ctx, _reader, _span

NAME = "topk_seen_chunks.saturate"


def _entry():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return next(m for m in json.load(f)["per_layer"] if m["name"] == NAME)


def test_reads_the_median_ratio_over_the_batches():
    read = _reader("topk_seen_chunks").read
    spans = [
        _span("serve/batch/compute", 0, 900, n=4, b=8, k=16, select_rounds=30,
              select_tiles=20, seen_chunks=9, seen_hit_tiles=9, tiles=100),
        _span("serve/batch/compute", 2_000, 900, n=4, b=8, k=16,
              select_rounds=50, select_tiles=40, seen_chunks=0,
              seen_hit_tiles=0, tiles=100),
        _span("serve/batch/compute", 4_000, 900, n=4, b=8, k=16,
              select_rounds=16, select_tiles=1, seen_chunks=104,
              seen_hit_tiles=100, tiles=100),
        _span("serve/batch/upload", 5_000, 10, bytes=64),
    ]
    assert read(_ctx(spans), NAME) == pytest.approx(0.09)


@pytest.mark.parametrize("args", [
    None,  # no span at all
    dict(n=4, b=8, k=16),  # before the gated selection
    dict(n=4, b=8, k=16, select_rounds=30, select_tiles=20, tiles=100),
], ids=["no_span", "n_b_k_alone", "the_parent"])
def test_a_program_without_the_count_reads_nothing(args):
    """The parent commit's span has the selection's counts and ``tiles``
    and no ``seen_chunks``: nothing to read, and nothing raised."""
    read = _reader("topk_seen_chunks").read
    spans = [] if args is None else [
        _span("serve/batch/compute", 0, 900, **args)]
    assert read(_ctx(spans), NAME) is None


def test_the_manifest_entry():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["per_layer"][-1] == _entry() == {
        "name": NAME, "unit": "chunks/tile", "better": "lower",
        "source": "program_counter", "layer": "serving kernel",
        "moves": "serve_req_per_s",
        "workloads": ["amazon14-serve-r128.serve-saturate",
                      "amazon23-serve-r128.serve-saturate-x4"]}


def test_traced_toy_run_reports_chunks_per_tile(capsys, tmp_path):
    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["per_layer"].append(dict(_entry(), workloads=["toy-serve.serve"]))
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    res, _ = drive(capsys, "toy-serve.serve", trace=1,
                   manifest=str(root / "BENCHMARK.json"))
    assert res["correct"] is True
    got = res["metrics"][NAME]
    assert got["unit"] == "chunks/tile"
    # six tiles and seen lists that reach most of them: a tile a batch row
    # has rated into runs its one chunk of 16 slots, the others none
    assert 0 < got["value"] <= 1
