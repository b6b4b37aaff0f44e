"""Operators (`exec/local`): the slots the query's mark joins that carry a
residual work over — semi and anti joins whose correlation is more than a
key match (`EXISTS (... and f.c <> s.c)`), summed over the fragment (the
program's counter `semiWorkSlots`, counted in `exec/local._TraceCtx.
_semi_residual`: the pair buffer where the join expands (source,
filtering) pairs, the build's slots plus the probe's where it reads each
key's extremes).  A buffer sized from slots, not from the pairs the data
holds, reads higher.  The program writes it when it traces the fragment,
so it is read from the last set-up execution that carries it.  A program
without the counter reads nothing."""


def read(ctx):
    for profile in reversed(ctx["setup_profiles"]):
        if profile.get("semiWorkSlots") is not None:
            return profile["semiWorkSlots"]
    return None
