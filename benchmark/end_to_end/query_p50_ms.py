"""Median of all the window's query latencies, `execute` + `to_pylist`."""
import statistics


def read(ctx):
    if not ctx["latencies_s"]:
        return None
    return statistics.median(ctx["latencies_s"]) * 1e3
