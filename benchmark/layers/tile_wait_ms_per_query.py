"""Fragment program (`exec/local`, `exec/streaming`): the query thread blocked
on the prefetch pool for the next tile, the program's tracer span `tile_wait`,
per query.  Only a streamed query has tiles."""


def read(ctx):
    spans = ctx["spans"]
    queries = spans.get("query", [0])[0]
    if not queries or "tile_wait" not in spans:
        return None
    return spans["tile_wait"][1] / queries
