"""Kernels (`ops/`): the output elements of every `gather` of the fragment's
optimized program, `programCensus.gatherElements`: the work count behind the
45-100 M elements/s that gathers run at.  The program takes the census when
it compiles the fragment (`trino_tpu/obs/program_census.py`: from the opcodes
of the executable's HLO text, so it stands for a stale executable too) and
puts the same object into every warm query's profile; it is read from the
last set-up execution that carries it.  A `while` body counts once.  One
shard's on a mesh.  A program without the census reads nothing."""


def read(ctx):
    for profile in reversed(ctx["setup_profiles"]):
        census = profile.get("programCensus")
        if census and census.get("instructions"):
            return census.get("gatherElements")
    return None
