"""Kernels (`ops/`) on a mesh: the least time the chips of the cell could
take for a query over the device time they took.  The least time is the
bytes of the referenced columns at their narrowest width (`min_bytes` in the
configuration) over the HBM peak of all the chips that worked
(`trace["chips"]` device planes with operations; each holds its shard);
the device time is the mean busy time of those chips.  Same bytes whatever
implements the query, so the share cannot pass 100; on one chip it equals
`hbm_roofline_pct`."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["busy_s"] or not trace["chips"] or not ctx["peaks"]:
        return None
    least_s = ctx["least_bytes_per_query"] / (
        trace["chips"] * ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (trace["busy_s"] / trace["queries"])
