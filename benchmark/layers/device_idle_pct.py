"""Device: the share of the traced slice in which no operation ran."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
