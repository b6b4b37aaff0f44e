"""Scan (`connectors/tpch_device`): running the generator's programs in HBM
during set-up (blocking dispatches); their compile is in `compile_s`."""


def read(ctx):
    total = sum(p.get("devgenWallS") or 0.0 for p in ctx["setup_profiles"])
    return total or None
