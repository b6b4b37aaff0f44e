"""Base-table rows read by every query completed in the window, over the
whole window's seconds: all the work over all the time, a stall included."""


def read(ctx):
    if not ctx["latencies_s"]:
        return None
    return ctx["rows_per_query"] * len(ctx["latencies_s"]) / ctx["window_s"]
