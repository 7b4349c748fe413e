"""The reader of the share of serve steps that ran with a batch in flight
(``serve_overlap_share.saturate``) against a hand-written span list, a
program without the attribute, the manifest entry, and a traced toy run."""

import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.tests.test_runner_cpu import TOY, drive
from benchmarks.tests.test_serve_stage_metrics import _ctx, _reader, _span

NAME = "serve_overlap_share.saturate"


def _entry():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return next(m for m in json.load(f)["per_layer"] if m["name"] == NAME)


def test_reads_the_share_of_steps_with_a_batch_in_flight():
    read = _reader("serve_overlap_share").read
    spans = [
        _span("serve/batch", 0, 900, batch=1, requests=4, overlapped=False),
        _span("serve/batch", 2_000, 900, batch=2, requests=4, overlapped=True),
        _span("serve/batch", 4_000, 900, batch=3, requests=4, overlapped=True),
        _span("serve/batch", 6_000, 900, batch=4, requests=0, overlapped=True),
        _span("serve/batch/compute", 6_100, 500, n=4, b=8, k=16),
    ]
    assert read(_ctx(spans), NAME) == pytest.approx(0.75)
    assert read(_ctx(spans[:1]), NAME) == 0.0


@pytest.mark.parametrize("args", [None, dict(batch=3, requests=4, shed=0)],
                         ids=["no_span", "the_parent"])
def test_a_program_without_the_attribute_reads_nothing(args):
    """The parent commit's ``serve/batch`` has its ordinal and no
    ``overlapped``: nothing to read, and nothing raised."""
    read = _reader("serve_overlap_share").read
    spans = [] if args is None else [_span("serve/batch", 0, 900, **args)]
    assert read(_ctx(spans), NAME) is None


def test_the_manifest_entry():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]
                 if w["traffic"].startswith("serve-saturate")]
    entry = _entry()
    assert len(cells) >= 3 and set(cells[:3]) <= set(entry.pop("workloads"))
    assert entry == {
        "name": NAME, "unit": "share", "better": "higher",
        "source": "program_span", "layer": "serving",
        "moves": "serve_req_per_s"}


def test_traced_toy_run_overlaps_nearly_every_step(capsys, tmp_path):
    """A toy window offered more than the CPU answers: after the first
    step every step finds a batch in flight."""
    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["per_layer"].append(dict(_entry(), workloads=["toy-serve.serve"]))
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    mix = root / "traffic" / "toy-serve.json"
    traffic = json.loads(mix.read_text())
    traffic["rate"] = 20_000  # far over what the interpreter's scorer answers
    mix.write_text(json.dumps(traffic))
    res, _ = drive(capsys, "toy-serve.serve", trace=1,
                   manifest=str(root / "BENCHMARK.json"))
    assert res["correct"] is True
    got = res["metrics"][NAME]
    assert got["unit"] == "share"
    assert 0.9 <= got["value"] <= 1.0
