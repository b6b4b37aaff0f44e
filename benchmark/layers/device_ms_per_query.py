"""Kernels (`ops/`): the union of the device-operation intervals of the
traced slice, per query in it."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["busy_s"]:
        return None
    return trace["busy_s"] / trace["queries"] * 1e3
