#!/usr/bin/env python3
"""Read a cell's check numbers over many seeds in one process (the data set
loaded once, one compile), sound and under a lower-precision control.

    python3 benchmarks/tools/limits.py --workload <cell> --seeds 11,12,13 \\
        --seconds 10 [--set table_dtype=bfloat16]

Each run prints its progress lines (every number compared beside its limit)
and its result line; ``--set`` overrides keys of the configuration file, which
is how the control switches on the program's own lower-precision path.  Not
part of a benchmark run: the limits in the configuration files are set from
what this prints on the chip (PERF.md, section 2).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse on whatever backend JAX finds")
    ap.add_argument("--manifest")
    args = ap.parse_args()
    overrides: dict = {}
    for item in args.set:
        key, value = item.split("=", 1)
        try:
            value = json.loads(value)
        except ValueError:
            pass
        head, _, tail = key.partition(".")
        if tail:
            overrides.setdefault(head, {})[tail] = value
        else:
            overrides[head] = value
    for seed in args.seeds.split(","):
        print(f"LIMITS seed {seed} overrides {overrides}", flush=True)
        argv = ["--workload", args.workload, "--seed", seed, "--seconds",
                str(args.seconds), "--trace", "0"]
        if args.manifest:
            argv += ["--manifest", args.manifest]
        run.main(argv, require_tpu=not args.cpu, config_overrides=overrides)
    return 0


if __name__ == "__main__":
    sys.exit(main())
