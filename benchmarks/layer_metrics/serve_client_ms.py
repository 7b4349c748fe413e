"""The two parts of ``serve_span_ms.outside`` that are the program's: the
client's send (``serve/client/flush``: from the first request produced since
the last flush to the flush's return) and its collect
(``serve/client/poll``), each summed inside one stretch from the end of a
``serve/batch`` to the start of the next step's ``serve/poll``, median over
the traced window's stretches.  What is left of ``outside`` is the
generator's own bookkeeping and, under a server with a session, the
session's pump.  A program without the spans reports nothing."""

from benchmarks.harness import host_spans, stats

SPAN_OF = {"send": "serve/client/flush", "collect": "serve/client/poll"}


def by_batch(spans, name):
    return {e["args"]["batch"]: e for e in spans
            if e["name"] == name and "batch" in e.get("args", {})}


def durations_ms(spans, part):
    mine = host_spans.on_serving_thread(spans)
    if not any(e["name"] in SPAN_OF.values() for e in mine):
        return []
    batches, polls = by_batch(mine, "serve/batch"), by_batch(mine, "serve/poll")
    inner = sorted((e["ts"], e["dur"]) for e in mine
                   if e["name"] == SPAN_OF[part])
    out, i = [], 0
    for n, b in sorted(batches.items()):
        if n + 1 not in polls:
            continue
        lo, hi, total = b["ts"] + b["dur"], polls[n + 1]["ts"], 0
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
