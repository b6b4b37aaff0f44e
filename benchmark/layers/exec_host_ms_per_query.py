"""Fragment program (`exec/local`, `exec/streaming`): the query thread
working in the executor: the program's tracer span `execute` less the spans in
which that thread only waits, `device_get` (on the device) and `tile_wait`
(on the prefetch pool), per query."""

WAITS = ("device_get", "tile_wait")


def read(ctx):
    spans = ctx["spans"]
    queries = spans.get("query", [0])[0]
    if not queries or "device_get" not in spans:
        return None
    ms = spans.get("execute", [0, 0.0])[1]
    ms -= sum(spans.get(s, [0, 0.0])[1] for s in WAITS)
    return ms / queries
