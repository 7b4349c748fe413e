"""The readers of what pauses a serve step (``host_gc_ms``, ``serve_pause_ms``),
of the client's half of ``outside`` (``serve_client_ms``) and of the store's
writer thread (``stream_commit_durable_ms``, ``stream_commit_backpressure_ms``)
against hand-written span lists, a program without the spans, the manifest
entries, and traced toy runs."""

import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.tests.test_serve_stage_metrics import _ctx, _reader, _span

HERE = os.path.dirname(os.path.abspath(__file__))
GC, WAIT, WRITE = "runtime/gc", "checkpoint/backpressure", "checkpoint/write"
SERVING, WRITER = 1, 2
NEW = {
    "host_gc_ms.saturate": "ms", "host_gc_ms.longest": "ms",
    "serve_pause_ms.named": "ms/s", "serve_pause_ms.unnamed": "ms/s",
    "serve_client_ms.send": "ms", "serve_client_ms.collect": "ms",
    "stream_commit_durable_ms.p50": "ms", "stream_commit_durable_ms.p95": "ms",
    "stream_commit_backpressure_ms.foldin": "ms",
}
STREAM_ONLY = {n for n in NEW if n.startswith("stream_")}


def _on(tid, span):
    return dict(span, tid=tid)


def _steps(starts_ms, dur_ms=6):
    """One ``serve/poll`` and one ``serve/batch`` a step on the serving
    thread, the batch starting at each of ``starts_ms``."""
    spans = []
    for n, t in enumerate(starts_ms, start=1):
        spans += [
            _span("serve/poll", t * 1000 - 500, 400, batch=n, requests=4),
            _span("serve/batch", t * 1000, dur_ms * 1000, batch=n, requests=4),
        ]
    return spans


@pytest.fixture
def no_hook(monkeypatch):
    """The program before PR 37: its tracer exports no name for a pass."""
    from cfk_tpu.telemetry import trace

    monkeypatch.delattr(trace, "GC_SPAN")


# -- host_gc_ms ---------------------------------------------------------------


def test_gc_time_is_summed_over_the_serving_threads_batches():
    read = _reader("host_gc_ms").read
    spans = _steps([0, 10, 20, 30]) + [
        _span(GC, 1_000, 2_000, generation=0, collected=3, uncollectable=0),
        _span(GC, 21_000, 6_000, generation=2, collected=9, uncollectable=0),
        # a pass on the store's writer thread is not inside a serve step
        _on(WRITER, _span(GC, 5_000, 30_000, generation=2, collected=0,
                          uncollectable=0)),
        # nor one while the profiler starts, or while the run prints
        _span(GC, -90_000, 40_000, generation=2, collected=0, uncollectable=0),
        _span(GC, 37_000, 50_000, generation=2, collected=0, uncollectable=0),
    ]
    assert read(_ctx(spans), "host_gc_ms.saturate") == pytest.approx(8 / 4)
    assert read(_ctx(spans), "host_gc_ms.longest") == pytest.approx(6.0)


def test_the_hook_in_and_no_pass_reads_zero():
    read = _reader("host_gc_ms").read
    ctx = _ctx(_steps([0, 10, 20]))
    assert read(ctx, "host_gc_ms.saturate") == 0.0
    assert read(ctx, "host_gc_ms.longest") == 0.0
    # no serve step in the window: nothing to divide by
    assert read(_ctx([]), "host_gc_ms.saturate") is None


def test_a_program_without_the_hook_reads_nothing(no_hook):
    spans = _steps([0, 10, 20, 60, 70])
    for family, name in (("host_gc_ms", "host_gc_ms.saturate"),
                         ("host_gc_ms", "host_gc_ms.longest"),
                         ("serve_pause_ms", "serve_pause_ms.named"),
                         ("serve_pause_ms", "serve_pause_ms.unnamed")):
        assert _reader(family).read(_ctx(spans), name) is None


# -- serve_pause_ms -----------------------------------------------------------


def test_a_long_period_is_split_into_named_and_unnamed():
    read = _reader("serve_pause_ms").read
    # periods 10, 10, 10, 40, 10 ms: median 10, one step 30 ms over it
    spans = _steps([0, 10, 20, 30, 70, 80]) + [
        _span(GC, 38_000, 12_000, generation=2, collected=0, uncollectable=0),
        _span(WAIT, 52_000, 8_000, pending=8, max_pending=8),
        # a short pass in a step that was not long names nothing
        _span(GC, 12_000, 1_000, generation=0, collected=0, uncollectable=0),
        # the writer thread's spans pause no serve step
        _on(WRITER, _span(WRITE, 31_000, 30_000, step=7, kind="unit")),
        _on(WRITER, _span(GC, 31_000, 25_000, generation=1, collected=0,
                          uncollectable=0)),
    ]
    ctx = _ctx(spans)
    # 20 of the 30 ms named, over the 0.08 s the periods span
    assert read(ctx, "serve_pause_ms.named") == pytest.approx(20 / 0.08)
    assert read(ctx, "serve_pause_ms.unnamed") == pytest.approx(10 / 0.08)


def test_pauses_name_no_more_than_the_excess_and_overlaps_count_once():
    read = _reader("serve_pause_ms").read
    spans = _steps([0, 10, 20, 30, 70, 80]) + [
        _span(WAIT, 31_000, 30_000, pending=8, max_pending=8),
        _span(GC, 40_000, 5_000, generation=0, collected=0, uncollectable=0),
        _span(GC, 62_000, 7_000, generation=2, collected=0, uncollectable=0),
    ]
    ctx = _ctx(spans)
    # 37 ms seen inside a step 30 ms over the median: all of it named
    assert read(ctx, "serve_pause_ms.named") == pytest.approx(30 / 0.08)
    assert read(ctx, "serve_pause_ms.unnamed") == 0.0


def test_even_periods_pause_nothing():
    read = _reader("serve_pause_ms").read
    ctx = _ctx(_steps([0, 10, 21, 30, 42]))
    assert read(ctx, "serve_pause_ms.named") == 0.0
    assert read(ctx, "serve_pause_ms.unnamed") == 0.0
    assert read(_ctx(_steps([0, 10])), "serve_pause_ms.named") is None


# -- serve_client_ms ----------------------------------------------------------


def test_send_and_collect_are_summed_inside_each_outside_stretch():
    read = _reader("serve_client_ms").read
    # batch n ends at t + 6 ms, poll n+1 starts at t + 9.5 ms
    spans = _steps([0, 10, 20, 30]) + [
        _span("serve/client/poll", 6_200, 900, responses=4, bytes=400,
              malformed=0),
        _span("serve/client/flush", 7_300, 1_500, requests=4),
        _span("serve/client/poll", 16_100, 1_100, responses=4, bytes=400,
              malformed=0),
        _span("serve/client/flush", 17_400, 700, requests=2),
        _span("serve/client/flush", 18_200, 600, requests=2),
        _span("serve/client/poll", 26_100, 1_000, responses=4, bytes=400,
              malformed=0),
        _span("serve/client/flush", 27_400, 1_000, requests=4),
        # another client, on a thread of its own
        _on(WRITER, _span("serve/client/flush", 7_000, 2_000, requests=9)),
    ]
    ctx = _ctx(spans)
    assert read(ctx, "serve_client_ms.send") == pytest.approx(1.3)
    assert read(ctx, "serve_client_ms.collect") == pytest.approx(1.0)


def test_a_program_without_the_clients_spans_reads_nothing():
    read = _reader("serve_client_ms").read
    ctx = _ctx(_steps([0, 10, 20]))
    assert read(ctx, "serve_client_ms.send") is None
    assert read(ctx, "serve_client_ms.collect") is None
    assert read(_ctx([]), "serve_client_ms.send") is None


# -- stream_commit_* ----------------------------------------------------------


def _micro_batches(n):
    return [_span("stream/batch", 10_000 * i, 5_000, ordinal=i + 1,
                  records=256) for i in range(n)]


def test_durable_is_queued_plus_the_writers_span_of_each_unit():
    read = _reader("stream_commit_durable_ms").read
    writes = [_on(WRITER, _span(WRITE, 10_000 * i, dur, step=i + 1,
                                kind="unit", bytes=131_000, fsyncs=5,
                                queued_ms=queued))
              for i, (dur, queued) in enumerate(
                  [(4_000, 0.5), (5_000, 1.0), (4_500, 0.5), (9_000, 31.0)])]
    # 4.5, 6.0, 5.0, 40.0 ms
    snapshot = _span(WRITE, 0, 15_000_000, step=0, kind="snapshot",
                     queued_ms=0.0)
    ctx = _ctx(_micro_batches(4) + writes + [snapshot])
    assert read(ctx, "stream_commit_durable_ms.p50") == pytest.approx(5.5)
    assert read(ctx, "stream_commit_durable_ms.p95") == pytest.approx(
        6.0 + 0.85 * 34.0)
    assert read(_ctx(_micro_batches(4) + [snapshot]),
                "stream_commit_durable_ms.p50") is None


def test_backpressure_is_the_waits_over_the_micro_batches_committed():
    read = _reader("stream_commit_backpressure_ms").read
    name = "stream_commit_backpressure_ms.foldin"
    writes = [_on(WRITER, _span(WRITE, 10_000 * i, 4_000, step=i + 1,
                                kind="unit", queued_ms=0.1)) for i in range(4)]
    waits = [_span(WAIT, 11_000, 74_000, pending=2, max_pending=2),
             _span(WAIT, 95_000, 6_000, pending=2, max_pending=2)]
    # a stream/batch with no ordinal committed nothing
    idle = [_span("stream/batch", 50_000, 10)]
    assert read(_ctx(_micro_batches(4) + idle + writes + waits), name) \
        == pytest.approx(80 / 4)
    assert read(_ctx(_micro_batches(4) + writes), name) == 0.0
    # the parent: commits, and a writer thread the tracer cannot see
    assert read(_ctx(_micro_batches(4)), name) is None
    assert read(_ctx(writes), name) is None


# -- the manifest, and traced toy runs ----------------------------------------


def _entries():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return manifest, {m["name"]: m for m in manifest["per_layer"]
                      if m["name"] in NEW}


def test_the_manifest_entries():
    manifest, entries = _entries()
    assert set(entries) == set(NEW)
    serving = {w["name"] for w in manifest["workloads"]
               if w["traffic"].startswith("serve-")}
    streams = {w["name"] for w in manifest["workloads"]
               if w["traffic"] == "serve-foldin"}
    assert streams and streams < serving
    for name, m in entries.items():
        assert m["unit"] == NEW[name] and m["better"] == "lower"
        assert m["source"] == "program_span"
        assert m["moves"] == "serve_req_per_s"
        want = streams if name in STREAM_ONLY else serving
        assert set(m["workloads"]) >= want
        if name in STREAM_ONLY:
            assert set(m["workloads"]) == streams


def _toy_with(tmp_path, toy, cell, names):
    """A copy of a toy benchmark whose cell reports ``names`` too."""
    root = tmp_path / toy
    shutil.copytree(os.path.join(HERE, toy), root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    _, entries = _entries()
    manifest["per_layer"] += [dict(entries[n], workloads=[cell])
                              for n in names]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def _traced(capsys, root, cell, seconds):
    rc = run.main(["--workload", cell, "--seed", "3000000017", "--seconds",
                   str(seconds), "--trace", "1", "--manifest",
                   str(root / "BENCHMARK.json")], require_tpu=False)
    out, _ = capsys.readouterr()
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True, out[-3000:]
    return {k: v for k, v in res["metrics"].items() if k in NEW}


def test_traced_toy_serve_run_reports_the_pause_and_client_metrics(
        capsys, tmp_path):
    names = sorted(set(NEW) - STREAM_ONLY)
    root = _toy_with(tmp_path, "toy", "toy-serve.serve", names)
    got = _traced(capsys, root, "toy-serve.serve", 1)
    assert sorted(got) == names
    assert {n: m["unit"] for n, m in got.items()} \
        == {n: NEW[n] for n in names}
    m = {n: v["value"] for n, v in got.items()}
    assert m["serve_client_ms.send"] > 0 and m["serve_client_ms.collect"] > 0
    assert 0 <= m["host_gc_ms.saturate"] <= m["host_gc_ms.longest"] * 1e3
    assert m["serve_pause_ms.named"] >= 0 and m["serve_pause_ms.unnamed"] >= 0


def test_traced_toy_stream_run_reports_the_stores_metrics(capsys, tmp_path):
    """The stream runner keeps the collector off from its set-up to its
    check: the hook is in, and no pass runs on the serving thread."""
    names = sorted(STREAM_ONLY | {"host_gc_ms.saturate", "host_gc_ms.longest"})
    root = _toy_with(tmp_path, "toy_stream", "toy-stream.foldin", names)
    got = _traced(capsys, root, "toy-stream.foldin", 2)
    assert sorted(got) == names
    m = {n: v["value"] for n, v in got.items()}
    assert m["host_gc_ms.saturate"] == 0.0 and m["host_gc_ms.longest"] == 0.0
    assert 0 < m["stream_commit_durable_ms.p50"] \
        <= m["stream_commit_durable_ms.p95"]
    assert m["stream_commit_backpressure_ms.foldin"] >= 0.0
