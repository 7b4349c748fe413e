#!/usr/bin/env python3
"""Find a serve cell's knee: one process, one set-up by the mix's own runner
(the one its traffic file names), then the open-loop window at each offered
rate in turn, ``--passes`` times over.

    python3 benchmarks/tools/sweep.py --workload <serve cell> --seed 7 \\
        --seconds 20 --rates 1000,1100,1200 [--passes 2]
    ... --probe 9000 --rates 0.90,0.95,1.0    # shares of what it completes

``--probe`` first runs one window at a rate far above the knee; the answers
per second that window completes are the capacity, and ``--rates`` are then
shares of it (rounded to 10 req/s), so one call on the chip finds the knee of
a system whose capacity is not known yet.  Prints one ``SWEEP_ROW`` JSON line
per window: answers per second inside it, the backlog at its close and how
long it took to serve, the latency percentiles from the scheduled send, and
how the batches fell into the engine's power-of-two buckets.  The knee is the
highest rate whose backlog at the close is under one batch.  Not part of a
benchmark run: the rates in ``traffic/serve-*.json`` are set from what this
prints on the chip (PERF.md, section 4).
"""

import argparse
import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run  # noqa: E402
from benchmarks.harness.stats import percentile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--passes", type=int, default=1)
    ap.add_argument("--probe", type=float)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--manifest", default=os.path.join(run.ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    _, search, cell, config, traffic = run.load_cell(args.manifest,
                                                     args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    traffic.update(rate=args.probe or max(rates), drain_seconds=60)
    cache_dir = run.prepare_cache()
    run.device_or_exit(cell["chips"], not args.cpu)
    ns = argparse.Namespace(seed=args.seed, seconds=args.seconds, trace=0)
    ctx = run.Ctx(ns, cell, config, traffic, cache_dir)
    runner = run.load_module(
        run.find(search, "runners", traffic["runner"] + ".py"),
        "bench_runner_" + traffic["runner"]).make(ctx)
    runner.setup()

    def window(rep, rate) -> float:
        traffic["rate"] = rate
        w = runner.window(args.seconds)
        sizes = w["batch_sizes"]
        buckets = collections.Counter(
            max(8, 1 << (s - 1).bit_length()) for s in sizes)
        print("SWEEP_ROW " + json.dumps({
            "offered_req_per_s": rate, "pass": rep,
            "answered_in_window_per_s": w["end_to_end"]["serve_req_per_s"],
            "backlog_at_close": runner.result.backlog_at_close,
            "drain_s": runner.result.drain_s,
            "p50_ms": percentile(w["latency_ms"], 50),
            "p95_ms": percentile(w["latency_ms"], 95),
            "late_p95_ms": percentile(w["late_ms"], 95),
            "batches": len(sizes),
            "mean_batch": sum(sizes) / max(len(sizes), 1),
            "buckets": dict(sorted(buckets.items())),
            "new_traces": w["new_traces"]}), flush=True)
        return w["end_to_end"]["serve_req_per_s"]

    if args.probe:
        capacity = window("probe", args.probe)
        rates = [round(capacity * share / 10) * 10.0 for share in rates]
    for rep in range(args.passes):
        for rate in rates:
            window(rep, rate)
    return 0


if __name__ == "__main__":
    sys.exit(main())
