"""The two readers of the exclusion rectangle's build
(``serve_seen_programs.saturate``, ``serve_seen_device_ms.saturate``) against
hand-written spans and traces: a span of a program that cut the cell list
into pieces (``chunks`` only), one of a program that builds the rectangle in
one run (``programs``), a window or a trace without them (nothing reported,
nothing raised), the manifest entries, and a traced toy run."""

import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.harness import xplane
from benchmarks.tests.test_runner_cpu import TOY, drive
from benchmarks.tests.test_serve_stage_metrics import _ctx, _reader, _span

PROGRAMS = "serve_seen_programs.saturate"
DEVICE_MS = "serve_seen_device_ms.saturate"
ONE_CHIP = ["amazon14-serve-r128.serve-saturate",
            "amazon23-serve-r128-int8.serve-saturate-int8",
            "amazon14-stream-r128.serve-foldin",
            "amazon14-stream-r128-durable.serve-foldin-kill",
            "amazon14-stream-r128-skew.serve-foldin-skew"]
X4 = "amazon23-serve-r128.serve-saturate-x4"


def _entry(name):
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return next(m for m in json.load(f)["per_layer"] if m["name"] == name)


def _seen(ts, **args):
    return _span("serve/batch/seen_tiles", ts, 500, tiles=18_262, b=256,
                 width=16, capacity=4_096, **args)


@pytest.mark.parametrize("spans, want", [
    # the parent's span: the pieces were its runs (the skew cell's batches)
    ([_seen(0, cells=23_000, chunks=6), _seen(9_000, cells=1_800, chunks=1),
      _seen(18_000, cells=17_000, chunks=5)], 4.0),
    # one run however many pieces; two past the top rung of sixteen
    ([_seen(0, cells=23_000, chunks=6, programs=1),
      _seen(9_000, cells=1_800, chunks=1, programs=1),
      _seen(18_000, cells=70_000, chunks=18, programs=2),
      _seen(27_000, cells=9_000, chunks=3, programs=1)], 1.25),
    # the control's batches, either program
    ([_seen(0, cells=1_800, chunks=1), _seen(9_000, cells=1_700, chunks=1,
                                             programs=1)], 1.0),
], ids=["chunks_only", "programs", "one_piece_either_way"])
def test_programs_a_batch_is_the_mean_over_the_spans(spans, want):
    read = _reader("serve_seen_programs").read
    other = [_span("serve/batch/upload", 600, 10, bytes=64),
             _span("serve/batch/compute", 700, 900, seen_chunks=9, tiles=100)]
    assert read(_ctx(spans + other), PROGRAMS) == pytest.approx(want)


@pytest.mark.parametrize("spans", [
    [],
    [_span("serve/batch/compute", 0, 900, seen_chunks=9, tiles=100)],
    # before the device built the rectangle the span had neither count
    [_span("serve/batch/seen_tiles", 0, 500, tiles=3, b=4, width=16)],
], ids=["no_span", "no_seen_tiles_span", "neither_count"])
def test_a_window_without_the_counts_reads_nothing(spans):
    assert _reader("serve_seen_programs").read(_ctx(spans), PROGRAMS) is None


def _trace_ctx(modules):
    ctx = _ctx([])
    ctx.trace_data = xplane.DeviceTrace(
        modules=[modules] if modules is not None else [], ops=[], mark=0.0,
        profile_start_unix_ns=0)
    return ctx


def _batches(builds_a_batch, build_s=0.002, fold=False):
    """Device 0's modules over three batches: the build ``builds_a_batch``
    times, the scorer once, and (a stream cell) a fold-in run between."""
    modules, t = [], 0.0
    for n in range(3):
        for _ in range(builds_a_batch):
            modules.append((t, t + build_s, f"jit__seen_tiles_call({7 + n})"))
            t += build_s
        modules.append((t, t + 0.025, "jit__topk_call(3)"))
        t += 0.025
        if fold:
            modules.append((t, t + 0.001, "jit__cells_fold_gram(9)"))
            t += 0.001
    return modules


@pytest.mark.parametrize("modules, want", [
    (_batches(1), 2.0),  # one run of 2 ms a batch
    (_batches(5), 10.0),  # the parent under long lists: five runs a batch
    (_batches(1, build_s=0.0045, fold=True), 4.5),  # the fold-in is not its
], ids=["one_run", "a_run_a_piece", "beside_the_foldin"])
def test_device_ms_is_the_build_modules_time_per_scorer_run(modules, want):
    read = _reader("serve_seen_device_ms").read
    assert read(_trace_ctx(modules), DEVICE_MS) == pytest.approx(want)


@pytest.mark.parametrize("modules", [
    None,  # no device plane at all (a CPU run)
    [],
    [(0.0, 0.025, "jit__topk_call(3)")],  # no exclusion: no build
    [(0.0, 0.002, "jit__seen_tiles_call(7)")],  # no scorer run to divide by
    # the shard programs are the x4 cell's, read by serve_seen_build_ms.x4
    [(0.0, 0.002, "jit__seen_tiles_shard_call(1)"),
     (0.002, 0.2, "jit__topk_shard_call(2)")],
], ids=["no_plane", "no_module", "no_build", "no_scorer", "shard_programs"])
def test_a_trace_without_the_modules_reads_nothing(modules):
    read = _reader("serve_seen_device_ms").read
    assert read(_trace_ctx(modules), DEVICE_MS) is None


def test_the_manifest_entries():
    assert _entry(PROGRAMS) == {
        "name": PROGRAMS, "unit": "runs/batch", "better": "lower",
        "source": "program_counter", "layer": "serving",
        "moves": "serve_req_per_s",
        "workloads": ONE_CHIP[:1] + [X4] + ONE_CHIP[1:]}
    assert _entry(DEVICE_MS) == {
        "name": DEVICE_MS, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "serving",
        "moves": "serve_req_per_s", "workloads": ONE_CHIP}


def test_traced_toy_run_reports_one_program_a_batch(capsys, tmp_path):
    """The toy serve cell under both entries: every batch's rectangle is one
    run of the program; the CPU's trace has no device plane, so the device
    metric is left out of the line and nothing raises."""
    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for name in (PROGRAMS, DEVICE_MS):
        manifest["per_layer"].append(
            dict(_entry(name), workloads=["toy-serve.serve"]))
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    res, _ = drive(capsys, "toy-serve.serve", trace=1,
                   manifest=str(root / "BENCHMARK.json"))
    assert res["correct"] is True
    assert res["metrics"][PROGRAMS] == {"value": 1.0, "unit": "runs/batch"}
    assert DEVICE_MS not in res["metrics"]
