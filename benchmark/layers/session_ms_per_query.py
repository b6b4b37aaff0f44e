"""Entry (`session.py`): what a query spends in the session outside the
front end and the executor.  The program's tracer spans `query_admit` +
`query_finish` + `query`, less `parse`, `analyze_plan`, `optimize` (the front
end's) and `execute` (the executor's), per query."""

OUTER = ("query_admit", "query", "query_finish")
INNER = ("parse", "analyze_plan", "optimize", "execute")


def read(ctx):
    spans = ctx["spans"]
    queries = spans.get("query", [0])[0]
    if not queries or "query_admit" not in spans:
        return None
    ms = sum(spans.get(s, [0, 0.0])[1] for s in OUTER)
    ms -= sum(spans.get(s, [0, 0.0])[1] for s in INNER)
    return ms / queries
