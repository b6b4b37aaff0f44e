"""Run one cell several times and print how widely each metric spreads.

    python benchmark/sets.py --workload <cell> --seeds 11,12,13 --sets 2 \\
        --seconds 30 --trace 0 --out chiprun_out/<dir>

Each run is a process of its own (this one never touches JAX, so the chip
is free for it); every set uses the same seeds.  A spread is the distance
between the first and third quartile (`statistics.quantiles(values, n=4)`)
as a share of the median: what BENCHMARK.json's bounds are set from.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--seconds", default="30")
    p.add_argument("--trace", default="0")
    p.add_argument("--out", required=True)
    args = p.parse_args()
    os.makedirs(args.out, exist_ok=True)
    seeds = args.seeds.split(",")
    values = {}    # metric -> [set][run]
    for k in range(args.sets):
        for seed in seeds:
            tag = "%s.t%s.set%d.seed%s" % (args.workload, args.trace, k, seed)
            with open(os.path.join(args.out, tag + ".err"), "w") as err:
                proc = subprocess.run(
                    [sys.executable, os.path.join(HERE, "run.py"),
                     "--workload", args.workload, "--seed", seed,
                     "--seconds", args.seconds, "--trace", args.trace],
                    stdout=subprocess.PIPE, stderr=err, text=True)
            line = (proc.stdout.strip().splitlines() or ["{}"])[-1]
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                f.write(line + "\n")
            res = json.loads(line) if proc.returncode == 0 else {}
            print("%s rc=%d correct=%s %s" % (
                tag, proc.returncode, res.get("correct"),
                {m: v["value"] for m, v in res.get("metrics", {}).items()}),
                flush=True)
            for m, v in res.get("metrics", {}).items():
                values.setdefault(m, [[] for _ in range(args.sets)])[k].append(v["value"])
    for m, per_set in values.items():
        print("%s %s: medians %s spreads %s" % (
            args.workload, m,
            [statistics.median(v) for v in per_set if v],
            [spread(v) for v in per_set if len(v) >= 2]), flush=True)


if __name__ == "__main__":
    main()
