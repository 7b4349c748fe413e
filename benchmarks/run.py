#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  This
file finds ``configs/<config>.json``, ``traffic/<mix>.json``, the mix's runner
``runners/<runner>.py`` and each per-layer metric's reader
``layer_metrics/<family>.py`` by those names under the manifest's ``paths``;
it holds nothing that belongs to one cell, so a later PR adds a cell, a
configuration, a mix or a metric with new files and manifest entries alone.

It runs on a TPU or not at all, prints progress lines, and ends with the one
JSON line of the contract.  ``--trace 0`` measures the end-to-end metrics with
tracing off; ``--trace 1`` measures a short window under the JAX profiler and
the program's span tracer and reports the per-layer metrics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up runs from here to the end of warm-up

import argparse
import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Ctx:
    """What a runner and the metric readers are handed."""

    def __init__(self, args, cell, config, traffic, cache_dir):
        self.seed, self.seconds, self.trace = args.seed, args.seconds, args.trace
        self.cell, self.config, self.traffic = cell, config, traffic
        self.cache_dir = cache_dir
        self.phases: dict[str, float] = {}
        self.program_spans: list = []  # the program's tracer, traced runs

    def say(self, msg: str) -> None:
        print(f"[{time.perf_counter() - T_START:8.2f}s] {msg}", flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = (self.phases.get(name, 0.0)
                                 + time.perf_counter() - t0)

    def span_durations_ms(self, name: str) -> list:
        """Durations of the program's complete spans called ``name`` inside
        the traced window, in the order they were recorded."""
        return [e["dur"] * 1e-3 for e in self.program_spans
                if e["name"] == name]


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find(search_dirs, *relative) -> str:
    for d in search_dirs:
        path = os.path.join(d, *relative)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(
        f"{os.path.join(*relative)} under none of {search_dirs}")


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in the manifest")


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def layer_metrics_of(manifest: dict, cell: str) -> list:
    """The per-layer metrics this cell reports: those that list it, and those
    with no list whose ``moves`` metric this cell reports."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    return [m for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else reports(e2e[m["moves"]], cell))]


def load_cell(manifest_path: str, workload: str, config_overrides=None):
    """(manifest, search dirs, cell, configuration, traffic mix) of a cell,
    each file found by the name the manifest gives."""
    with open(manifest_path) as f:
        manifest = json.load(f)
    base = os.path.dirname(os.path.abspath(manifest_path))
    search = [os.path.join(base, p) for p in manifest["paths"]]
    if HERE not in search:
        search.append(HERE)
    cell = by_name(manifest["workloads"], workload, "workload")
    cfg_entry = by_name(manifest["configs"], cell["config"], "configuration")
    with open(os.path.join(base, cfg_entry["file"])) as f:
        config = json.load(f)
    config.update(config_overrides or {})  # the tools' controls, never the CLI
    with open(find(search, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return manifest, search, cell, config, traffic


def prepare_cache() -> str:
    """All the benchmark leaves behind lives under ``benchmarks/.cache/`` in
    this checkout — data sets, traces, and JAX's persistent compile cache at a
    fixed path (the path is part of the cache's key).  Where the machine came
    with ``JAX_COMPILATION_CACHE_DIR`` set, the program keeps to that."""
    cache_dir = os.path.join(HERE, ".cache")
    os.makedirs(cache_dir, exist_ok=True)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(cache_dir, "jax"))
    return cache_dir


def device_or_exit(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        sys.exit(f"FAILED: JAX found no accelerator (platform {platform!r}); "
                 "the benchmark runs on a TPU or not at all")
    if len(devices) < chips:
        sys.exit(f"FAILED: the cell asks for {chips} chip(s), JAX found "
                 f"{len(devices)}")
    return devices[:chips]


class LoweringCounter:
    """Counts programs lowered (each is a compile or a compile-cache load):
    inside the measured window there may be none."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self.EVENT:
            self.count += 1


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


@contextlib.contextmanager
def traced(ctx, trace_dir: str):
    """The traced window: the JAX profiler (device ops; no Python tracer, it
    slows the host) and the program's own span tracer."""
    import jax

    from benchmarks.harness.xplane import WINDOW_MARK
    from cfk_tpu import telemetry

    shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = telemetry.configure(None)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    ctx.window_t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_MARK):
        pass
    try:
        yield
    finally:
        ctx.window_t1 = time.perf_counter()
        jax.profiler.stop_trace()
        ctx.program_spans = [e for e in tracer.events() if e.get("ph") == "X"]
        telemetry.shutdown(write=False)


def breakdown(ctx, trace) -> dict:
    from benchmarks.harness import xplane

    ops = sorted(trace.self_times().items(), key=lambda kv: -kv[1])[:10]
    # the program's spans are on perf_counter (in microseconds); the trace's
    # clock starts at the mark
    shift = (trace.mark if trace.mark is not None else 0.0) - ctx.window_t0
    spans = [(e["ts"] * 1e-6 + shift, (e["ts"] + e["dur"]) * 1e-6 + shift,
              e["name"]) for e in ctx.program_spans]
    lo, hi = ctx.window_t0 + shift, ctx.window_t1 + shift
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": xplane.name_gaps(trace, lo, hi, spans, top=5)}


def main(argv=None, *, require_tpu: bool = True,
         config_overrides: dict | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another manifest (the tests' toy cells)")
    args = ap.parse_args(argv)

    manifest, search, cell, config, traffic = load_cell(
        args.manifest, args.workload, config_overrides)
    runner_mod = load_module(
        find(search, "runners", traffic["runner"] + ".py"),
        "bench_runner_" + traffic["runner"])

    cache_dir = prepare_cache()
    try:
        import cfk_tpu  # noqa: F401  the system under test
    except ImportError as e:
        sys.exit(f"FAILED: the program is not in this checkout ({e})")
    devices = device_or_exit(cell["chips"], require_tpu)
    import jax

    from benchmarks.harness import roofline, xplane
    from cfk_tpu.config import enable_compile_cache

    ctx = Ctx(args, cell, config, traffic, cache_dir)
    kind = devices[0].device_kind
    ctx.peaks = roofline.peaks_for(kind) if require_tpu else None
    ctx.say(f"cell {cell['name']}: config {cell['config']}, traffic "
            f"{cell['traffic']}, seed {args.seed}, {args.seconds:g} s, trace "
            f"{args.trace}, on {len(devices)}x {kind} ({devices[0].platform})")
    ctx.say(f"compile cache: {enable_compile_cache()}")

    lowered = LoweringCounter()
    run = runner_mod.make(ctx)
    run.setup()
    setup_s = time.perf_counter() - T_START
    ctx.say(f"set-up done in {setup_s:.2f} s: "
            + ", ".join(f"{k} {v:.2f}" for k, v in ctx.phases.items()))

    trace, lowered_before = None, lowered.count
    if args.trace:
        trace_dir = os.path.join(cache_dir, "trace", cell["name"])
        seconds = min(args.seconds, float(traffic["trace_seconds"]))
        with traced(ctx, trace_dir):
            window = run.window(seconds)
        trace = xplane.reduce_xplane(xplane.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        window = run.window(args.seconds)
    lowered_in_window = lowered.count - lowered_before
    peak = memory_peak_bytes(devices)  # before the reference touches JAX

    checks = [("compiles_in_window", lowered_in_window, 0,
               "exact: every program the window runs was compiled or loaded "
               "during warm-up")] + run.check(window)
    correct = True
    for name, value, limit, why in checks:
        ok = bool(value <= limit)
        correct &= ok
        ctx.say(f"check {name}: {value:.6g} against limit {limit:g} -> "
                f"{'ok' if ok else 'FAILED'}  [{why}]")
    # each number compared beside its limit, last in the result's line and
    # last on standard error: all the driver keeps of a run that is not correct
    compared = {name: {"value": float(value) if math.isfinite(value)
                       else str(value), "limit": float(limit)}
                for name, value, limit, _ in checks}

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics: dict = {}
    result = {"correct": correct, "attempted": int(window["attempted"]),
              "failed": int(window["failed"]),
              "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = ctx.window_t1 - ctx.window_t0
        ctx.trace_data, ctx.window, ctx.device = trace, window, device
        for m in layer_metrics_of(manifest, cell["name"]):
            family = m["name"].split(".")[0]
            reader = load_module(find(search, "layer_metrics", family + ".py"),
                                 "bench_metric_" + family)
            value = reader.read(ctx, m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["breakdown"] = breakdown(ctx, trace)
    else:
        values = dict(window["end_to_end"], setup_s=setup_s)
        for m in manifest["end_to_end"]:
            if reports(m, cell["name"]):
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    result["checks"] = compared
    print(json.dumps(result), flush=True)
    for name, c in compared.items():
        print(f"check {name}: {c['value']} against limit {c['limit']:g}",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
