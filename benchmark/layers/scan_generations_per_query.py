"""Scan (`connectors/tpch_device`): runs of the device generator per query,
the count of the program's tracer span `devgen` over the count of `query`
in the window.  0 where the scan's lanes stay resident in HBM between
queries, one per tile where a streamed scan generates them again for every
query.  A program with no such span reads 0 as well."""


def read(ctx):
    spans = ctx["spans"]
    queries = spans.get("query", [0])[0]
    if not queries:
        return None
    return spans.get("devgen", [0])[0] / queries
