"""Fragment program (`exec/local`, `exec/streaming`): device programs
launched per query, the `XLA Modules` events of the traced slice."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["modules"]:
        return None
    return trace["modules"] / trace["queries"]
