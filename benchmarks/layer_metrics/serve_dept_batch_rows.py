"""Mean requests in a batch of the department that the traced window asked
for least: over the program's ``serve/batch`` spans that carry a
``department``, the department whose batches hold the fewest requests in
all, and the mean of ``requests`` over its batches.  Under a backlog every
department's batches are full (256) if the server hands each department the
share of the steps its arrivals ask for; well under that, the small
departments run padded.  A program whose span carries no department reports
nothing."""


def read(ctx, name):
    by_dept: dict = {}
    for e in ctx.program_spans:
        args = e.get("args", {})
        if (e["name"] == "serve/batch" and "department" in args
                and args.get("requests")):
            by_dept.setdefault(args["department"], []).append(args["requests"])
    if not by_dept:
        return None
    least = min(by_dept.values(), key=sum)
    return sum(least) / len(least)
