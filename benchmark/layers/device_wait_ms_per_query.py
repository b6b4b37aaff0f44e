"""Fragment program (`exec/local`): the query thread waiting in the program's
tracer span `device_get`, per query.  A host-clock wait: the device's run, the
wake-up after it, the copy back and the watchdog thread's join, so it reads
above `device_ms_per_query`."""


def read(ctx):
    spans = ctx["spans"]
    queries = spans.get("query", [0])[0]
    if not queries or "device_get" not in spans:
        return None
    return spans["device_get"][1] / queries
