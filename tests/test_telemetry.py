"""Unified telemetry subsystem (ISSUE 14): span tracing, flight recorder,
thread-safe metrics registry, Prometheus export, and the instrumentation
contracts — span trees well-formed across threads, Chrome-trace JSON
round-trips, ring-buffer eviction order, text-format conformance,
prewarm/serve spans present, and the acceptance check that the staging
overlap fraction recomputed from spans agrees with the driver's gauge."""

import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from cfk_tpu import telemetry
from cfk_tpu.telemetry.metrics import Histogram, Metrics


@pytest.fixture
def tracer():
    t = telemetry.configure()
    yield t
    telemetry.shutdown(write=False)


@pytest.fixture
def recorder(tmp_path):
    rec = telemetry.get_recorder()
    rec.clear()
    rec.configure(dump_dir=str(tmp_path), capacity=512)
    yield rec
    rec.configure(dump_dir=None, capacity=512)
    rec.clear()


# -- tracer ------------------------------------------------------------------


def test_null_span_when_unconfigured():
    assert telemetry.get_tracer() is None
    with telemetry.span("train/iter", i=0) as sp:  # no-op, no error
        sp.set(rows=3)  # boundary attributes and drop are no-ops too
        sp.drop()


def test_span_tree_balanced_across_threads(tracer):
    # Nested spans on several threads concurrently: the exported events
    # must form a well-formed per-thread tree (every enter matched by its
    # own exit — overlap within a tid is always containment).
    barrier = threading.Barrier(4)  # hold all four threads alive together

    def worker(tid):
        barrier.wait()
        for i in range(20):
            with telemetry.span("outer", tid=tid, i=i):
                with telemetry.span("outer/mid"):
                    with telemetry.span("outer/mid/leaf"):
                        pass
                with telemetry.span("outer/mid2"):
                    pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # the workers' spans by name: a collector pass may fall among them
    events = [e for e in tracer.events() if e["name"].startswith("outer")]
    telemetry.validate_span_tree(tracer.events())
    counts = telemetry.validate_span_tree(events)
    assert sum(counts.values()) == 4 * 20 * 4
    # the barrier held all four threads alive together: distinct tids
    assert len(counts) == 4


def test_span_records_exception_and_stays_balanced(tracer):
    with pytest.raises(ValueError):
        with telemetry.span("boom"):
            raise ValueError("x")
    (e,) = [e for e in tracer.events() if e["name"] == "boom"]
    assert e["args"]["error"] == "ValueError"
    telemetry.validate_span_tree([e])


def test_chrome_trace_json_round_trips(tmp_path, tracer):
    with telemetry.span("train/iter", i=0):
        pass
    path = tracer.write(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    # thread-name metadata + the X span (by name: a collector pass that
    # fell inside it is an X of its own)
    assert [e["ph"] for e in events if e["ph"] != "X"] == ["M"]
    (x,) = [e for e in events if e["name"] == "train/iter"]
    assert x["ph"] == "X"
    assert {"ts", "dur", "pid", "tid", "args"} <= set(x)
    # round-trip: re-serialize parses identically
    assert json.loads(json.dumps(doc)) == doc


def test_tracer_write_to_trace_dir(tmp_path):
    t = telemetry.configure(trace_dir=str(tmp_path / "td"))
    try:
        with telemetry.span("a"):
            pass
    finally:
        path = telemetry.shutdown(write=True)
    assert path is not None and path.endswith(".json")
    with open(path) as f:
        assert json.load(f)["traceEvents"]


# -- flight recorder ---------------------------------------------------------


def test_ring_buffer_eviction_order(recorder):
    recorder.configure(capacity=8)
    for i in range(20):
        recorder.record("test", "evt", i=i)
    evs = recorder.events()
    assert len(evs) == 8
    assert [e["i"] for e in evs] == list(range(12, 20))  # oldest evicted
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs)


def test_dump_atomic_and_readable(recorder, tmp_path):
    recorder.record("fault", "health_trip", reason="nonfinite_user_factors")
    path = recorder.dump("health_trip: test")
    assert path is not None
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "health_trip: test"
    assert doc["num_events"] == 1
    assert doc["events"][-1]["name"] == "health_trip"
    assert not [p for p in (tmp_path.iterdir())
                if ".tmp." in p.name]  # atomic: no temp litter


def test_dump_without_dir_is_memory_only(monkeypatch):
    monkeypatch.delenv("CFK_FLIGHT_DIR", raising=False)
    rec = telemetry.FlightRecorder()
    rec.record("x", "y")
    assert rec.dump("nowhere") is None  # no dir configured -> no disk
    assert rec.events()  # but the ring still holds the events


def test_resilient_loop_dumps_on_trip(recorder, tmp_path):
    # End-to-end: a NaN fault mid-training must leave a dump whose final
    # events name the trip, with the preceding iterations in the tail.
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.models.als import train_als
    from cfk_tpu.resilience.faults import FactorCorruption, FaultInjector

    ds = Dataset.from_coo(synthetic_netflix_coo(40, 20, 300, seed=0))
    cfg = ALSConfig(rank=4, num_iterations=4, health_check_every=1)
    train_als(ds, cfg,
              fault_injector=FaultInjector(
                  FactorCorruption(iteration=2, side="u")))
    dumps = [p for p in tmp_path.iterdir()
             if p.name.startswith("cfk_flight_")]
    assert dumps, "health trip left no flight dump"
    with open(sorted(dumps)[-1]) as f:
        doc = json.load(f)
    names = [e["name"] for e in doc["events"]]
    assert "health_trip" in names
    assert "iter" in names  # the timeline of the steps before the fault
    trip = next(e for e in doc["events"] if e["name"] == "health_trip")
    assert "nonfinite" in trip["reason"]


# -- metrics registry --------------------------------------------------------


def test_metrics_thread_safety_hammer():
    # The ISSUE 14 satellite pin: concurrent incr/phase/observe from many
    # threads must not lose a single count (the old defaultdict registry
    # did — read-modify-write without a lock).
    m = Metrics()
    threads_n, per = 8, 2000

    def worker():
        for _ in range(per):
            m.incr("hits")
            m.incr("weighted", 0.5)
            m.observe("lat_ms", 1.0)
            with m.phase("work"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(threads_n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert m.counters["hits"] == threads_n * per
    assert m.counters["weighted"] == pytest.approx(threads_n * per * 0.5)
    assert m.histograms["lat_ms"].count == threads_n * per
    assert m.phases["work"] > 0


def test_histogram_quantile_contract():
    h = Histogram("t", reservoir=1024)
    vals = np.arange(1000, dtype=np.float64)
    for v in vals:
        h.observe(v)
    # below the reservoir bound the quantiles are EXACT np.percentile
    assert h.quantile(0.5) == pytest.approx(np.percentile(vals, 50))
    assert h.quantile(0.99) == pytest.approx(np.percentile(vals, 99))
    assert h.min == 0.0 and h.max == 999.0 and h.count == 1000
    s = h.summary()
    assert s["count"] == 1000 and s["p50"] == pytest.approx(499.5)


def test_histogram_reservoir_bounded_and_deterministic():
    def fill(name):
        h = Histogram(name, reservoir=64)
        for v in range(10_000):
            h.observe(float(v))
        return h

    a, b = fill("same"), fill("same")
    assert a.count == 10_000 and len(a.reservoir()) == 64  # O(1) memory
    assert a.reservoir() == b.reservoir()  # per-name seeded RNG
    # the reservoir is a uniform sample: its median sits near the true one
    assert 2000 < a.quantile(0.5) < 8000


def test_loadgen_latency_memory_is_bounded():
    # The loadgen satellite: per-request latency state must be O(1) in
    # request count (reservoir + outstanding-only dict), same quantile
    # estimator as the old np.percentile lists.
    from cfk_tpu.serving import loadgen

    assert loadgen.LATENCY_RESERVOIR == 4096
    h = Histogram("serve_request_latency_ms",
                  reservoir=loadgen.LATENCY_RESERVOIR)
    lat = np.random.default_rng(0).exponential(10.0, size=3000)
    for v in lat:
        h.observe(v)
    assert h.quantile(0.5) == pytest.approx(np.percentile(lat, 50))
    assert h.quantile(0.99) == pytest.approx(np.percentile(lat, 99))


# -- prometheus export -------------------------------------------------------


def _full_registry():
    m = Metrics()
    m.incr("serve_requests", 42)
    m.gauge("offload_stage_hidden_frac", 0.93)
    m.gauge("plan", "not-a-number")  # non-numeric gauges must be skipped
    m.note("health_trip_1", "nonfinite")  # notes never exported
    with m.phase("train"):
        pass
    for v in (1.0, 2.0, 3.0):
        m.observe("serve_batch_ms", v)
    return m


def test_prometheus_text_conformance():
    text = telemetry.prometheus_text(_full_registry())
    assert text.endswith("\n")
    sample_re = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? "
        r"(NaN|[-+0-9.eE]+)$"
    )
    typed = set()
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("# TYPE "):
            name, kind = line[len("# TYPE "):].rsplit(" ", 1)
            assert kind in ("counter", "gauge", "summary")
            assert name not in typed  # one TYPE line per family
            typed.add(name)
            continue
        assert sample_re.match(line), line
        metric = re.split(r"[{ ]", line, 1)[0]
        base = re.sub(r"_(sum|count|total)$", "", metric)
        assert any(t in (metric, base, metric[:-len("_total")]
                         if metric.endswith("_total") else metric)
                   for t in typed), f"sample before TYPE: {line}"
    assert "cfk_serve_requests_total 42" in text
    assert 'cfk_phase_seconds{phase="train"}' in text
    assert 'cfk_serve_batch_ms{quantile="0.5"} 2' in text
    assert "cfk_serve_batch_ms_count 3" in text
    assert "cfk_plan" not in text  # the non-numeric gauge was skipped
    assert "nonfinite" not in text  # notes stay out of the scrape


def test_prometheus_text_survives_inf_values():
    # Review regression: one inf gauge/observation must not break the
    # scrape forever (OverflowError from int(inf)); Prometheus spells
    # them +Inf/-Inf.
    m = Metrics()
    m.gauge("up_inf", float("inf"))
    m.gauge("down_inf", float("-inf"))
    m.observe("h", float("inf"))
    m.observe("h", 1.0)
    text = telemetry.prometheus_text(m)
    assert "cfk_up_inf +Inf" in text
    assert "cfk_down_inf -Inf" in text
    assert "cfk_h_sum +Inf" in text


def test_dump_never_raises_on_non_jsonable_fields(tmp_path):
    # Review regression: record() takes free-form fields; a numpy scalar
    # (or anything json can't encode) must degrade to its repr — never
    # raise TypeError out of a fault handler ("never raises" contract).
    rec = telemetry.FlightRecorder(dump_dir=str(tmp_path))
    rec.record("fault", "x", window=np.int64(3), arr=np.zeros(2))
    path = rec.dump("np-fields")
    assert path is not None
    with open(path) as f:
        doc = json.load(f)  # readable despite the numpy fields
    assert "3" in str(doc["events"][0]["window"])  # repr-degraded value
    assert not [p for p in tmp_path.iterdir() if ".tmp." in p.name]


def test_emitter_creates_parent_directory(tmp_path):
    # Review regression: a JSONL path in a not-yet-existing directory
    # must fail fast (or be created) at construction — not crash stop()
    # inside the CLI's exit finally after a successful run.
    m = Metrics()
    m.incr("x")
    path = tmp_path / "sub" / "dir" / "m.jsonl"
    em = telemetry.MetricsEmitter(m, str(path), interval_s=5)
    em.start()
    em.stop()
    assert json.loads(path.read_text().splitlines()[-1])["counters"]["x"] == 1


def test_recorder_capacity_reconfigure_keeps_dump_dir(tmp_path):
    # Review regression: a capacity-only configure() must not silently
    # disable disk dumps (None stays the explicit off switch).
    rec = telemetry.FlightRecorder(dump_dir=str(tmp_path))
    rec.configure(capacity=16)
    rec.record("fault", "x")
    assert rec.dump("still-dumps") is not None
    rec.configure(dump_dir=None)
    assert rec.dump("now-disabled") is None


def test_metrics_http_endpoint_under_load():
    m = _full_registry()
    stop = threading.Event()

    def mutate():
        while not stop.is_set():
            m.incr("serve_requests")
            m.observe("serve_batch_ms", 1.0)

    t = threading.Thread(target=mutate)
    with telemetry.MetricsHTTPServer(m, port=0) as srv:
        t.start()
        try:
            for _ in range(5):
                with urllib.request.urlopen(srv.url, timeout=5) as r:
                    assert r.status == 200
                    assert r.headers["Content-Type"].startswith(
                        "text/plain; version=0.0.4"
                    )
                    body = r.read().decode()
                assert "cfk_serve_requests_total" in body
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5
            ) as r:
                assert r.read() == b"ok\n"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=5
                )
        finally:
            stop.set()
            t.join()
    assert srv.scrapes >= 5


def test_jsonl_emitter(tmp_path):
    m = Metrics()
    m.incr("iterations", 3)
    path = tmp_path / "metrics.jsonl"
    em = telemetry.MetricsEmitter(m, str(path), interval_s=0.05)
    em.start()
    import time

    time.sleep(0.18)
    m.incr("iterations", 4)
    em.stop()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) >= 2  # periodic lines + the final stop() flush
    assert lines[0]["counters"]["iterations"] == 3.0
    assert lines[-1]["counters"]["iterations"] == 7.0
    assert all("ts" in ln for ln in lines)


# -- instrumentation contracts ----------------------------------------------


def _tiny_serve_engine(num_users=24, num_movies=16, rank=4):
    from cfk_tpu.serving.engine import ServeEngine

    rng = np.random.default_rng(0)
    return ServeEngine(
        rng.standard_normal((num_users, rank), dtype=np.float32),
        rng.standard_normal((num_movies, rank), dtype=np.float32),
        num_users=num_users, num_movies=num_movies,
        tile_m=16, batch_quantum=4,
    )


def test_serve_prewarm_and_first_batch_spans(tracer):
    from cfk_tpu.serving.server import (
        RecommendServer,
        ServeClient,
        ensure_serve_topics,
    )
    from cfk_tpu.transport import InMemoryBroker

    eng = _tiny_serve_engine()
    warm = eng.prewarm(3, max_batch=8)
    assert warm["programs"] >= 1
    names = [e["name"] for e in tracer.events()]
    assert "serve/prewarm" in names
    assert "serve/batch/compute" in names  # prewarm scores real batches
    tracer.clear()
    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    server = RecommendServer(eng, broker, max_batch=8)
    client = ServeClient(broker)
    got = client.ask([0, 1, 2], 3, server=server)
    assert len(got) == 3
    names = [e["name"] for e in tracer.events()]
    for want in ("serve/batch", "serve/batch/validate",
                 "serve/batch/assemble", "serve/batch/compute",
                 "serve/batch/respond"):
        assert want in names, want
    telemetry.validate_span_tree(tracer.events())
    assert server.metrics.histograms["serve_batch_ms"].count == 1
    assert server.metrics.histograms["serve_batch_size"].count == 1


def test_recommend_server_metrics_port_serves_scrape():
    from cfk_tpu.serving.server import (
        RecommendServer,
        ServeClient,
        ensure_serve_topics,
    )
    from cfk_tpu.transport import InMemoryBroker

    broker = InMemoryBroker()
    ensure_serve_topics(broker)
    with RecommendServer(_tiny_serve_engine(), broker, max_batch=8,
                         metrics_port=0) as server:
        client = ServeClient(broker)
        client.ask([0, 1], 2, server=server)
        url = server.metrics_server.url
        with urllib.request.urlopen(url, timeout=5) as r:
            body = r.read().decode()
        assert "cfk_serve_requests_total 2" in body
        assert 'cfk_serve_batch_ms{quantile="0.5"}' in body
    assert server.metrics_server is None  # close() released the port


def _stream_session(tmp_path, n_updates=24):
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synthetic import synthetic_netflix_coo
    from cfk_tpu.models.als import train_als
    from cfk_tpu.streaming import StreamConfig, StreamProducer, StreamSession
    from cfk_tpu.transport import CheckpointManager, InMemoryBroker

    ds = Dataset.from_coo(synthetic_netflix_coo(30, 15, 220, seed=0))
    cfg = ALSConfig(rank=4, num_iterations=2, health_check_every=1)
    base = train_als(ds, cfg)
    broker = InMemoryBroker()
    prod = StreamProducer(broker, num_partitions=1)
    rng = np.random.default_rng(5)
    prod.send_many(
        rng.choice(ds.user_map.raw_ids, n_updates),
        rng.choice(ds.movie_map.raw_ids, n_updates),
        rng.integers(1, 6, n_updates).astype(np.float32),
    )
    return StreamSession(
        ds, cfg, broker, CheckpointManager(str(tmp_path / "stream")),
        stream=StreamConfig(batch_records=8), base_model=base,
    )


def test_stream_batch_and_prewarm_spans(tmp_path, tracer):
    sess = _stream_session(tmp_path)
    warm = sess.prewarm()
    assert "stream/prewarm" in [e["name"] for e in tracer.events()]
    assert warm["programs"] >= 1
    tracer.clear()
    sess.run()
    names = [e["name"] for e in tracer.events()]
    for want in ("stream/batch", "stream/batch/stage",
                 "stream/batch/solve", "stream/batch/probe",
                 "stream/batch/commit"):
        assert want in names, want
    # every commit handed over is a job of the store's writer thread
    assert sess.manager.wait_pending(timeout=60)
    commits = [e for e in tracer.events() if e["name"] == "stream/batch/commit"]
    writes = [e for e in tracer.events() if e["name"] == "checkpoint/write"]
    assert sorted(e["args"]["step"] for e in writes) \
        == sorted(e["args"]["step"] for e in commits)
    assert {e["args"]["kind"] for e in writes} == {"unit"}
    assert {e["tid"] for e in writes}.isdisjoint(e["tid"] for e in commits)
    telemetry.validate_span_tree(tracer.events())


def test_windowed_overlap_gauge_agrees_with_spans(tracer):
    # THE acceptance check: a sharded host_window run's staging-worker
    # spans must demonstrably overlap the consuming compute spans, and the
    # overlap_hidden_fraction recomputed from the trace must agree with
    # the driver's own gauge within 5% — two independent measurements of
    # the same two intervals.
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synth import synth_coo
    from cfk_tpu.offload.windowed import train_als_host_window
    from cfk_tpu.utils.metrics import Metrics

    shards = 2
    ds = Dataset.from_coo(
        synth_coo(200, 60, 1500, seed=0), num_shards=shards,
        layout="tiled", chunk_elems=512, tile_rows=16,
        accum_max_entities=0,
    )
    # hot_rows=0: measure the FULL-staging engine this agreement check
    # was calibrated on — the ISSUE 15 hot/delta engine shrinks staging
    # tasks to tiny deltas at this shape, where scheduler noise swamps
    # the 5% window (the hot path's span attrs have their own test in
    # tests/test_offload_hot.py).
    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=0,
                    layout="tiled", num_shards=shards,
                    offload_tier="host_window", hot_rows=0)
    metrics = Metrics()
    train_als_host_window(ds, cfg, metrics=metrics, chunks_per_window=2,
                          staging="pool")
    events = tracer.events()
    stage_spans = [e for e in events if e["name"].endswith("window_stage")]
    compute_spans = [e for e in events
                     if e["name"].endswith("window_compute")
                     or e["name"].endswith("ring_visit")]
    assert stage_spans and compute_spans
    # pool workers staged on their own threads (thread-aware spans)
    worker_tids = {e["tid"] for e in stage_spans}
    consumer_tids = {e["tid"] for e in compute_spans}
    assert worker_tids - consumer_tids, (
        "no staging span ran on a worker thread"
    )
    # demonstrable overlap: some worker stage span overlaps in wall time
    # with some consumer compute span
    overlaps = any(
        s["ts"] < c["ts"] + c["dur"] and c["ts"] < s["ts"] + s["dur"]
        for s in stage_spans if s["tid"] not in consumer_tids
        for c in compute_spans
    )
    assert overlaps, "staging-worker spans never overlapped compute spans"
    from_spans = telemetry.stage_overlap_from_events(events)
    gauge = metrics.gauges.get("offload_stage_hidden_frac")
    assert from_spans is not None and gauge is not None
    assert abs(from_spans - gauge) <= 0.05, (from_spans, gauge)


def test_tracer_on_trains_the_same_factors(tmp_path):
    # Spans observe from the host only: a windowed run under the tracer
    # ends on the factors of the same run without it, the written Chrome
    # trace holds the iteration and staging spans, and shutdown leaves no
    # tracer behind for whoever trains next.
    import zlib

    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synth import synth_coo
    from cfk_tpu.offload.windowed import train_als_host_window

    ds = Dataset.from_coo(
        synth_coo(200, 60, 1500, seed=0), layout="tiled", chunk_elems=512,
        tile_rows=16, accum_max_entities=0,
    )
    cfg = ALSConfig(rank=8, lam=0.05, num_iterations=2, seed=0,
                    layout="tiled", solver="cholesky",
                    offload_tier="host_window")

    def crc():
        model = train_als_host_window(ds, cfg, chunks_per_window=2)
        return zlib.crc32(
            np.asarray(model.user_factors, np.float32).tobytes())

    assert telemetry.get_tracer() is None
    off = crc()
    tracer = telemetry.configure(trace_dir=str(tmp_path / "trace"))
    try:
        on = crc()
        assert len(tracer.events()) > 0
    finally:
        path = telemetry.shutdown(write=True)
    assert on == off
    with open(path) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]
                 if e.get("ph") == "X"}
    assert "train/iter" in names
    assert any(n.endswith("window_stage") for n in names)
    assert telemetry.get_tracer() is None


def test_staging_error_leaves_flight_dump(recorder, tmp_path):
    from cfk_tpu.offload.staging import WindowStager

    def boom(shard, key):
        if key == 1:
            raise RuntimeError("worker crashed staging window 1")
        return key

    stager = WindowStager([(0, 0), (0, 1), (0, 2)], boom, mode="pool",
                          depth=2)
    assert stager.take() == 0
    with pytest.raises(RuntimeError):
        stager.take()
        stager.take()
    dumps = [p for p in tmp_path.iterdir()
             if p.name.startswith("cfk_flight_")]
    assert dumps
    with open(sorted(dumps)[-1]) as f:
        doc = json.load(f)
    last = doc["events"][-1]
    assert last["name"] == "staging_error"
    assert "worker crashed" in last["error"]


def test_prometheus_text_constant_labels():
    # Fleet attribution (distributed window exchange): per-host exporters
    # attach {process="N"} to every counter/gauge sample so one scrape
    # target per host aggregates without name collisions.
    m = Metrics()
    m.incr("exchange_payloads", 7)
    m.gauge("offload_exchange_rows_dcn", 192)
    m.gauge("offload_fleet_process", 1)
    with m.phase("train"):
        pass
    text = telemetry.prometheus_text(m, labels={"process": 1})
    assert 'cfk_exchange_payloads_total{process="1"} 7' in text
    assert 'cfk_offload_exchange_rows_dcn{process="1"} 192' in text
    # phase samples keep their own label set (constant labels are a
    # per-target concern; merging them into multi-label samples is the
    # scraper's job)
    assert 'cfk_phase_seconds{phase="train"}' in text
    # TYPE lines never carry labels
    assert "# TYPE cfk_offload_exchange_rows_dcn gauge" in text
    # unlabeled rendering is unchanged
    plain = telemetry.prometheus_text(m)
    assert "cfk_offload_exchange_rows_dcn 192" in plain
    # label values are escaped, names sanitized
    odd = telemetry.prometheus_text(m, labels={"host name": 'a"b'})
    assert 'host_name="a\\"b"' in odd


def test_metrics_http_server_labels_passthrough():
    import urllib.request

    m = Metrics()
    m.gauge("offload_exchange_rows_dcn", 44)
    with telemetry.MetricsHTTPServer(m, port=0,
                                     labels={"process": 0}) as srv:
        body = urllib.request.urlopen(srv.url, timeout=5).read().decode()
    assert 'cfk_offload_exchange_rows_dcn{process="0"} 44' in body


def test_windowed_spans_carry_host_attribution(tracer):
    # Every fabric-attributed span of the windowed driver (window_stage,
    # window_compute / ring_visit, half_step) must carry the host attr —
    # 0 under one process; the fleet drills assert per-process values.
    from cfk_tpu.config import ALSConfig
    from cfk_tpu.data.blocks import Dataset
    from cfk_tpu.data.synth import synth_coo
    from cfk_tpu.offload.windowed import train_als_host_window

    ds = Dataset.from_coo(
        synth_coo(120, 50, 1200, seed=0), num_shards=2, layout="tiled",
        chunk_elems=512, tile_rows=16, accum_max_entities=0,
    )
    cfg = ALSConfig(rank=4, lam=0.05, num_iterations=1, seed=0,
                    layout="tiled", num_shards=2,
                    offload_tier="host_window")
    train_als_host_window(ds, cfg, chunks_per_window=2)
    events = tracer.events()
    for suffix in ("window_stage", "window_compute", "half_step"):
        spans = [e for e in events if e["name"].endswith(suffix)]
        assert spans, f"no {suffix} spans"
        for e in spans:
            assert e["args"].get("host") == 0, (suffix, e["args"])
