"""95th percentile of all the window's query latencies.  Listed only for
cells whose window holds some hundreds of queries."""
import numpy as np


def read(ctx):
    if not ctx["latencies_s"]:
        return None
    return float(np.percentile(ctx["latencies_s"], 95)) * 1e3
