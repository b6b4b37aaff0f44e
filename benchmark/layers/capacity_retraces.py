"""Fragment program (`exec/local`): compiles of set-up that the capacity
ladder caused: a buffer the plan-time estimate sized was too small for the
data, and the fragment was traced and compiled again one rung up (cause
`ladder_rung` in the kernel profile's `compilesByCause`).  Each is one more
whole compile in `setup_s`.  A profile without causes reads nothing."""


def read(ctx):
    causes = [(p.get("summary") or {}).get("compilesByCause")
              for p in ctx["setup_profiles"]]
    if all(c is None for c in causes):
        return None
    return sum((c or {}).get("ladder_rung", 0) for c in causes)
