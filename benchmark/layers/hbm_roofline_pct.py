"""Kernels (`ops/`): the least time the chip could take for a query over the
device time it took.  The least time is the bytes of the referenced columns
at their narrowest width (`min_bytes` in the configuration) over the chip's
HBM peak: memory-bound, the same work whatever implements it."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["busy_s"] or not ctx["peaks"]:
        return None
    least_s = ctx["least_bytes_per_query"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (trace["busy_s"] / trace["queries"])
