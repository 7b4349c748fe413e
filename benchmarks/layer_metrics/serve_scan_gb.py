"""Median over the traced window's batches of the gigabytes the scorer streams
from HBM for one batch: ``scan_bytes / 1e9`` from the program's
``serve/batch/compute`` span (the item table as the devices hold it, codes and
scales, added up over the shards of a mesh).  What a narrower table buys: 4.79
for 9.35 M float32 rows, 24.7 for 48.19 M over four chips, ~6.4 for the same
48.19 M as int8 codes and float32 scales on one.  A program whose span carries
no such count reports nothing."""

from benchmarks.harness import stats


def read(ctx, name):
    scans = [e["args"]["scan_bytes"] / 1e9 for e in ctx.program_spans
             if e["name"] == "serve/batch/compute"
             and "scan_bytes" in e.get("args", {})]
    return stats.median(scans) if scans else None
