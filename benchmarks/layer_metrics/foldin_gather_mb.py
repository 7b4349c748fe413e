"""Median over the traced window's fold-ins of what the program says it
gathered from the item table, in MB: ``gather_bytes`` on the
``stream/batch/solve`` span of each hand-over, true to the table since PR 47
(a padded cell's row as it is stored, and its scale where there is one) on
both routes.  Only spans that name their table (``table_dtype``) are read:
before PR 47 the count was float32's on the rectangle and 0 on the cells
route, and such a program reports nothing."""

from benchmarks.harness import stats


def read(ctx, name):
    mb = [e["args"]["gather_bytes"] / 1e6 for e in ctx.program_spans
          if e["name"] == "stream/batch/solve"
          and "table_dtype" in e.get("args", {})
          and "gather_bytes" in e["args"]]
    return stats.median(mb) if mb else None
