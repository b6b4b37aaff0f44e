"""Scan (`connectors/tpch_device`): staging of the tiles on the prefetch pool
(host side of the scan, generator dispatch, upload), the program's tracer span
`tile_stage`, per query.  It runs beside the query thread, so it is no part of
any sum against `execute`."""


def read(ctx):
    spans = ctx["spans"]
    queries = spans.get("query", [0])[0]
    if not queries or "tile_stage" not in spans:
        return None
    return spans["tile_stage"][1] / queries
