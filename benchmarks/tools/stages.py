#!/usr/bin/env python3
"""Where a serve batch's period goes with the JAX profiler off: one process,
one set-up, then open-loop windows that alternate the program's span tracer
off and on.

    python3 benchmarks/tools/stages.py --workload <serve cell> --seed 7 \\
        --seconds 45 --pairs 4 [--soak 30] [--lineup-seconds 8]

Prints one ``STAGES_WINDOW`` JSON line per window (``serve_req_per_s`` and the
load generator's batch periods, tracer off and on: what the tracer costs),
then over the tracer-on windows one ``STAGES_SPAN`` line per span (median,
p95, longest), ``STAGES_SLOWEST`` (the stage breakdown of the slowest batch
seen: the instrument for a stall of whole seconds) and ``STAGES_COVER`` (the
share of ``serve/batch`` its children cover, of ``serve/batch/compute`` its
two, and the stage medians summed against the period's).  ``--soak`` adds
that many windows with the tracer on and no twin, for a stall too rare to fall
into four windows.  ``--lineup-seconds``
adds one window under the profiler whose Chrome trace the tracer writes beside
the profiler's own: ``STAGES_LINEUP`` says, through the two files' unix clocks
alone, where the scorer's device events fall inside ``serve/batch/compute``.
Not part of a benchmark run: PERF.md's section 5 is written from what this
prints on the chip.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run  # noqa: E402
from benchmarks.harness.stats import median, percentile  # noqa: E402

CHILDREN = ("validate", "assemble", "seen_tiles", "upload", "compute", "respond")
# what serve_span_ms reads, in the order a batch passes them, then the period
STAGES = ("poll", "assemble", "seen_tiles", "upload", "dispatch", "fetch",
          "respond", "outside", "period")
# spans no metric of their own reads
OTHER = {"validate": "serve/batch/validate", "batch": "serve/batch",
         "compute": "serve/batch/compute"}
LEAVES = STAGES[:-1] + ("validate",)


def say(tag: str, row: dict) -> None:
    print(f"{tag} " + json.dumps(row), flush=True)


def spread_row(values) -> dict:
    return {"n": len(values), "median_ms": median(values),
            "p95_ms": percentile(values, 95), "longest_ms": max(values)}


def per_batch(spans, by_batch) -> list:
    """One record per ``serve/batch`` span: its ordinal, the spans it
    contains on its thread by name, its poll, the stretch after it to the
    next poll, that poll, and the period to the next batch's start."""
    polls, whole = by_batch(spans, "serve/poll"), by_batch(spans, "serve/batch")
    out = []
    for n, b in whole.items():
        lo, hi = b["ts"], b["ts"] + b["dur"]
        row = {"batch": n, "requests": b["args"].get("requests"),
               "batch_ms": b["dur"] * 1e-3,
               "inside_ms": {e["name"]: e["dur"] * 1e-3 for e in spans
                             if e is not b and e["tid"] == b["tid"]
                             and lo <= e["ts"] and e["ts"] + e["dur"] <= hi}}
        if n in polls:
            row["poll_ms"] = polls[n]["dur"] * 1e-3
        if n + 1 in polls and n + 1 in whole:
            row["outside_ms"] = (polls[n + 1]["ts"] - hi) * 1e-3
            row["next_poll_ms"] = polls[n + 1]["dur"] * 1e-3
            row["period_ms"] = (whole[n + 1]["ts"] - lo) * 1e-3
        out.append(row)
    return out


def lineup(ctx, runner, seconds: float) -> dict:
    """One window under the profiler, the tracer's Chrome trace written into
    the profiler's directory, the two lined up by their unix clocks alone."""
    import re

    from benchmarks.harness import shard_trace, xplane
    from cfk_tpu import telemetry

    scorer = re.compile(shard_trace.SCORER)
    trace_dir = os.path.join(ctx.cache_dir, "trace", "stages-lineup")
    with run.traced(ctx, trace_dir):
        runner.window(seconds)
        tracer = telemetry.get_tracer()
        os.makedirs(trace_dir, exist_ok=True)
        host_path = tracer.write(os.path.join(trace_dir, "cfk_host_trace.json"))
        window_t0_unix_ns = tracer.to_unix_ns(ctx.window_t0 * 1e6)
    trace = xplane.reduce_xplane(xplane.find_xplane(trace_dir))
    with open(host_path) as f:
        doc = json.load(f)
    compute = sorted((e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3)
                     for e in doc["traceEvents"]
                     if e.get("name") == "serve/batch/compute")
    start_ns = trace.profile_start_unix_ns
    kernels = [(start_ns + a * 1e9, start_ns + b * 1e9)
               for a, b, name, custom in trace.ops
               if custom and scorer.search(name)]
    inside, lead_ms, tail_ms = 0, [], []
    for a, b in kernels:
        for lo, hi in compute:
            if lo <= a and b <= hi:
                inside += 1
                lead_ms.append((a - lo) * 1e-6)
                tail_ms.append((hi - b) * 1e-6)
                break
    row = {"host_trace": os.path.relpath(host_path, run.ROOT),
           "compute_spans": len(compute), "topk_device_events": len(kernels),
           "device_events_inside_a_compute_span": inside,
           "clock_pair": doc["metadata"]}
    if lead_ms:
        row.update(compute_start_to_kernel_start_ms=median(lead_ms),
                   kernel_end_to_compute_end_ms=median(tail_ms),
                   kernel_ms=median([(b - a) * 1e-6 for a, b in kernels]))
    if trace.mark is not None:
        # the benchmark lines the same two clocks up by its own mark
        row["unix_minus_mark_alignment_ms"] = (
            start_ns + trace.mark * 1e9 - window_t0_unix_ns) * 1e-6
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--pairs", type=int, default=4,
                    help="windows with the tracer off, and as many with it on")
    ap.add_argument("--soak", type=int, default=0,
                    help="further windows with the tracer on, after the pairs")
    ap.add_argument("--lineup-seconds", type=float, default=0.0)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--manifest", default=os.path.join(run.ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    _, search, cell, config, traffic = run.load_cell(args.manifest,
                                                     args.workload)
    cache_dir = run.prepare_cache()
    run.device_or_exit(cell["chips"], not args.cpu)
    from cfk_tpu import telemetry

    ns = argparse.Namespace(seed=args.seed, trace=0,
                            seconds=max(args.seconds, args.lineup_seconds))
    ctx = run.Ctx(ns, cell, config, traffic, cache_dir)
    runner = run.load_module(
        run.find(search, "runners", traffic["runner"] + ".py"),
        "bench_runner_" + traffic["runner"]).make(ctx)
    reader = run.load_module(
        run.find(search, "layer_metrics", "serve_span_ms.py"),
        "bench_metric_serve_span_ms")
    runner.setup()

    rates = {"off": [], "on": []}
    readings = {stage: [] for stage in STAGES + tuple(OTHER)}
    batches = []
    for i in range(2 * args.pairs + args.soak):
        mode = "on" if i % 2 or i >= 2 * args.pairs else "off"
        tracer = telemetry.configure(None) if mode == "on" else None
        try:
            w = runner.window(args.seconds)
        finally:
            telemetry.shutdown(write=False)
        rate = w["end_to_end"]["serve_req_per_s"]
        if i < 2 * args.pairs:  # the summary compares the paired windows
            rates[mode].append(rate)
        ends = runner.result.batch_ends_s
        gaps = [(b - a) * 1e3 for a, b in zip(ends, ends[1:])]
        say("STAGES_WINDOW", {
            "window": i, "tracer": mode, "serve_req_per_s": rate,
            "batches": len(w["batch_sizes"]), "failed": w["failed"],
            "new_traces": w["new_traces"],
            "loadgen_period_ms": spread_row(gaps) if gaps else None})
        if tracer is not None:
            # window by window: no period or outside runs from one window
            # into the next
            spans = [e for e in tracer.events() if e.get("ph") == "X"]
            for stage in STAGES:
                readings[stage] += reader.durations_ms(spans, stage)
            for stage, name in OTHER.items():
                readings[stage] += [e["dur"] * 1e-3 for e in spans
                                    if e["name"] == name]
            batches += [dict(r, window=i)
                        for r in per_batch(spans, reader.by_batch)]

    for stage, d in readings.items():
        if d:
            say("STAGES_SPAN", dict(stage=stage, **spread_row(d)))
    if batches:
        # by the period where the batch has one: a stall between two
        # batches counts against the first
        say("STAGES_SLOWEST", max(
            batches, key=lambda r: r.get("period_ms", r["batch_ms"])))
        mid = {stage: median(d) for stage, d in readings.items() if d}
        leaves = sum(mid.get(stage, 0.0) for stage in LEAVES)
        child = lambda r: sum(r["inside_ms"].get("serve/batch/" + c, 0.0)
                              for c in CHILDREN)
        sub = lambda r: (r["inside_ms"]["serve/batch/compute/dispatch"]
                         + r["inside_ms"]["serve/batch/compute/fetch"])
        say("STAGES_COVER", {
            "batches": len(batches),
            "children_share_of_batch": median(
                [child(r) / r["batch_ms"] for r in batches]),
            "dispatch_fetch_share_of_compute": median(
                [sub(r) / r["inside_ms"]["serve/batch/compute"]
                 for r in batches if "serve/batch/compute" in r["inside_ms"]]),
            # batch by batch (the parts are skewed: their medians do not
            # add); what is missing is the step from the poll to the batch
            "poll_batch_outside_over_period": median(
                [(r["batch_ms"] + r["outside_ms"] + r["next_poll_ms"])
                 / r["period_ms"] for r in batches if "period_ms" in r]),
            "sum_of_leaf_medians_ms": leaves,
            "period_median_ms": mid["period"],
            "leaves_over_period": leaves / mid["period"]})
    if rates["off"] and rates["on"]:
        off, on = median(rates["off"]), median(rates["on"])
        say("STAGES_SUMMARY", {
            "serve_req_per_s_tracer_off": rates["off"],
            "serve_req_per_s_tracer_on": rates["on"],
            "median_off": off, "median_on": on, "on_over_off": on / off})
    if args.lineup_seconds > 0:
        say("STAGES_LINEUP", lineup(ctx, runner, args.lineup_seconds))
    return 0


if __name__ == "__main__":
    sys.exit(main())
