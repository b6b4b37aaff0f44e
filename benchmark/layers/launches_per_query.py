"""Fragment program (`exec/local`, `exec/streaming`): supervised launches of
a fragment executable per query, the count of the program's tracer span
`launch`.  Beside `dispatches_per_query` (what the device saw) it says how
many device programs start outside any supervised launch."""


def read(ctx):
    spans = ctx["spans"]
    queries = spans.get("query", [0])[0]
    if not queries or "launch" not in spans:
        return None
    return spans["launch"][0] / queries
