"""Median over the traced window's micro-batches of one stage of the stream
session's batch, the stage taken from the metric's suffix.

A ``stream/batch`` span that carries ``ordinal`` committed a micro-batch.
Under the request server it holds two halves, as ``serve/batch`` does: the
fetch, probe, apply, commit and publish of the batch committed, then the
stage, neighbours, upload and hand-over of the next; so a stage's reading is
the sum of the spans of its name inside one ``stream/batch`` (``solve`` is
there twice: around the fetch and around the hand-over).  ``batch`` is the
whole span.  A program without the span reports nothing.
"""

from benchmarks.harness import stats

STAGES = ("stage", "neighbors", "upload", "solve", "probe", "apply",
          "commit", "publish")


def batches(spans):
    """The ``stream/batch`` spans that committed a micro-batch."""
    return [e for e in spans if e["name"] == "stream/batch"
            and "ordinal" in e.get("args", {})]


def durations_ms(spans, stage):
    outer = batches(spans)
    if stage == "batch":
        return [e["dur"] * 1e-3 for e in outer]
    if stage not in STAGES:
        raise ValueError(f"no stage {stage!r} of the stream batch")
    inner = sorted((e["ts"], e["dur"]) for e in spans
                   if e["name"] == "stream/batch/" + stage)
    out, i = [], 0
    for b in sorted(outer, key=lambda e: e["ts"]):
        lo, hi, total = b["ts"], b["ts"] + b["dur"], 0.0
        while i < len(inner) and inner[i][0] < lo:
            i += 1
        while i < len(inner) and inner[i][0] + inner[i][1] <= hi:
            total += inner[i][1]
            i += 1
        out.append(total * 1e-3)
    return out


def read(ctx, name):
    d = durations_ms(ctx.program_spans, name.split(".", 1)[1])
    return stats.median(d) if d else None
