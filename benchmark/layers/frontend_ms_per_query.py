"""Front end (`sql/`, `plan/`): the program's own tracer spans `parse`,
`analyze_plan` and `optimize`, summed over the window, per query.  A text
whose plan is cached by its SQL pays only `parse`."""

SPANS = ("parse", "analyze_plan", "optimize")


def read(ctx):
    queries = ctx["spans"].get("query", [0])[0]
    if not queries:
        return None
    return sum(ctx["spans"].get(s, [0, 0.0])[1] for s in SPANS) / queries
