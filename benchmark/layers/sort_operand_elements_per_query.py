"""Kernels (`ops/`): rows x operands of every `sort` of the fragment's optimized
program, `programCensus.sortOperandElements`.  Read as
`gather_elements_per_query` reads its counter; a program without the census
reads nothing."""


def read(ctx):
    for profile in reversed(ctx["setup_profiles"]):
        census = profile.get("programCensus")
        if census and census.get("instructions"):
            return census.get("sortOperandElements")
    return None
