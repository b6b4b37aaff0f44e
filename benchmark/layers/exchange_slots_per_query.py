"""Mesh (`parallel/mesh_executor`): the slots a chip receives through the
mesh's exchanges in one query, the sum of the program's counters
`broadcastExchangeSlots` (a join's or semi join's build side replicated by
`all_gather`: chips x the shard's slots, live or not, since the mesh does not
compact), `partitionedExchangeSlots` (`shuffle.repartition`'s `all_to_all`:
chips x chunk; 0 where the planner chose no partitioned join, and then the
counter is absent) and `groupStateExchangeSlots` (the partial group state a
sort group-by gathers before its final step).  One shard's, as every counter
of a mesh trace.  The program writes them when it traces the fragment, so
they are read from the last set-up execution that carries one of them (as
`sort_group_rows_per_query` reads its counter).  A program without the
counters reads nothing."""

COUNTERS = ("broadcastExchangeSlots", "partitionedExchangeSlots",
            "groupStateExchangeSlots")


def read(ctx):
    for profile in reversed(ctx["setup_profiles"]):
        if any(profile.get(c) is not None for c in COUNTERS):
            return sum(profile.get(c) or 0 for c in COUNTERS)
    return None
