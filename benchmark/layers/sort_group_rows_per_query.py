"""Kernels (`ops/`): the input slots of the query's group-bys that were
lowered through the sort path (`ops/aggregation.sort_group_ids` ->
`permute_lanes` -> `SortedSegments`), the program's counter `sortGroupRows`.
The program writes it when it traces the fragment, so it is read from the
last set-up execution that carries it (the query that compiled the program
the window runs; a capacity retrace replaces the counts of the rung before
it, so this is one trace's).  A program without the counter reads nothing."""


def read(ctx):
    for profile in reversed(ctx["setup_profiles"]):
        if profile.get("sortGroupRows") is not None:
            return profile["sortGroupRows"]
    return None
