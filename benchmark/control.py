"""The control of `correct`: the plain reference put in the program's place,
computed with float32 products and sums where the configuration guarantees
exact decimal arithmetic (what a TPU does natively, and the step a later
change would be tempted by).  It has to come out as NOT correct.

    python benchmark/control.py --workload <cell> --seeds 1,2,3 [--sf 0.01]

Host numpy only (no JAX).  Exit code 0 where the comparison refused the
control's answer for every parameter set of every seed.
"""
import argparse
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (HERE, os.path.join(HERE, "queries")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import datagen  # noqa: E402
import run  # noqa: E402
import yardstick  # noqa: E402


def control_passes(query, sf, params):
    """Per parameter set: does the comparison accept the float32 answer?"""
    exact, _ = query.reference(datagen, sf, params)
    lower, _ = query.reference(datagen, sf, params, acc=np.float32)
    limit = getattr(query, "LIMIT", None)
    return [query.check(low[:limit], ref) for low, ref in zip(lower, exact)]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--sf", type=float, default=0.0)
    args = p.parse_args(argv)
    workload = yardstick.load_json("workloads", args.workload + ".json")
    cfg = yardstick.load_json("configs", workload["config"] + ".json")
    query = run.load_module("queries", workload["query"])
    accepted = 0
    for seed in args.seeds.split(","):
        params = run.draw_sets(query, int(seed), workload)
        passes = control_passes(query, args.sf or cfg["sf"], params)
        accepted += sum(passes)
        print("control %s seed %s: wrong answers %d of %d (limit 0): %s" % (
            args.workload, seed, len(passes) - sum(passes), len(passes),
            "not correct, as it has to be" if not any(passes)
            else "ACCEPTED: the comparison cannot see float32"), flush=True)
    return 1 if accepted else 0


if __name__ == "__main__":
    sys.exit(main())
