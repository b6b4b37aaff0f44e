"""Fragment program, in set-up: trace + XLA compile (or persistent-cache
load) of the run's fragments and of the device generator's programs."""


def read(ctx):
    return sum(
        sum(k.get("compileWallS", 0.0) for k in p.get("kernels") or [])
        + (p.get("devgenCompileS") or 0.0)
        for p in ctx["setup_profiles"])
