"""Kernels (`ops/`): the update elements of every `scatter` of the fragment's
optimized program, `programCensus.scatterUpdates`: the work count behind the
11-15 M updates/s that scatters run at.  Read as `gather_elements_per_query`
reads its counter; a program without the census reads nothing."""


def read(ctx):
    for profile in reversed(ctx["setup_profiles"]):
        census = profile.get("programCensus")
        if census and census.get("instructions"):
            return census.get("scatterUpdates")
    return None
