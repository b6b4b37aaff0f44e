"""Process start to window start: imports, session, device generation,
compile or cache load of the run's parameter sets, two executions of each."""


def read(ctx):
    return ctx["setup_seconds"]
