#!/usr/bin/env python3
"""Find a serve configuration's knee: one process, one set-up, then the
open-loop window at each offered rate in turn.

    python3 benchmarks/tools/sweep.py --workload <serve cell> --seed 7 \\
        --seconds 10 --rates 300,350,400 [--repeat 3]

Prints one ``SWEEP_ROW`` JSON line per window: answers per second inside it,
the backlog at its close, the latency percentiles from the scheduled send,
and how the batches fell into the engine's power-of-two buckets.  Not part
of a benchmark run: the rates in ``traffic/serve-*.json`` are set from what
this prints on the chip (PERF.md, section 4).
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--manifest", default=os.path.join(run.ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    _, search, cell, config, traffic = run.load_cell(args.manifest,
                                                     args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    traffic.update(rate=max(rates), drain_seconds=60)
    cache_dir = run.prepare_cache()
    run.device_or_exit(cell["chips"], not args.cpu)
    ns = argparse.Namespace(seed=args.seed, seconds=args.seconds, trace=0)
    ctx = run.Ctx(ns, cell, config, traffic, cache_dir)
    runner = run.load_module(run.find(search, "runners", "serve.py"),
                             "bench_runner_serve").make(ctx)
    runner.setup()
    for rate in rates:
        for rep in range(args.repeat):
            traffic["rate"] = rate
            w = runner.window(args.seconds)
            sizes = w["batch_sizes"]
            buckets = collections.Counter(
                max(8, 1 << (s - 1).bit_length()) for s in sizes)
            print("SWEEP_ROW " + json.dumps({
                "offered_req_per_s": rate, "rep": rep,
                "answered_in_window_per_s": w["end_to_end"]["serve_req_per_s"],
                "backlog_at_close": runner.result.backlog_at_close,
                "p50_ms": percentile(w["latency_ms"], 50),
                "p95_ms": percentile(w["latency_ms"], 95),
                "late_p95_ms": percentile(w["late_ms"], 95),
                "batches": len(sizes),
                "mean_batch": sum(sizes) / max(len(sizes), 1),
                "buckets": dict(sorted(buckets.items())),
                "new_traces": w["new_traces"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
