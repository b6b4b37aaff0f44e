#!/usr/bin/env python
"""bucket_ladder: recommend a geometric padding ladder from a shape census.

The compile observatory's shape census records, per kernel family, the
row-count distribution real traffic presented (a bounded power-of-two
sketch, persisted as ``census-*.json`` snapshots next to the ``co-*``
ledger segments by a session with ``compile_observatory_dir`` set).
This tool turns that census into the direct input ROADMAP item 3
needs: an equi-height padding ladder (Ioannidis, *The History of
Histograms*, VLDB 2003 — applied to row counts instead of values)
whose rungs sit at equal-mass quantiles
of the observed distribution, with the predicted waste ratio
(padded/actual rows) the ladder would have produced against the same
traffic.

    python scripts/bucket_ladder.py --dir /tmp/obs          # census dir
    python scripts/bucket_ladder.py --census-file c.json    # one snapshot
    python scripts/bucket_ladder.py --dir /tmp/obs --json   # machine form

A ladder with few rungs wastes padding (every shape rounds far up); a
rung per shape retraces on every new shape.  The waste ratio printed
here is the knob: pick the smallest rung count whose predicted waste is
acceptable, and every censused shape compiles at most once per rung.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from trino_tpu.obs.compile_observatory import (  # noqa: E402
    ShapeCensus,
    read_census_dir,
    recommend_ladder,
)


def load_census(args) -> ShapeCensus:
    if args.census_file:
        census = ShapeCensus(max_families=1 << 16)
        with open(args.census_file) as f:
            census.merge(json.load(f))
        return census
    return read_census_dir(args.dir)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument(
        "--dir",
        help="compile_observatory_dir: merges every census-*.json writer",
    )
    src.add_argument(
        "--census-file", help="a single census snapshot JSON"
    )
    ap.add_argument(
        "--rungs", type=int, default=8,
        help="maximum ladder rungs (default 8)",
    )
    ap.add_argument(
        "--lane", type=int, default=128,
        help="rung alignment, the TPU lane width (default 128)",
    )
    ap.add_argument(
        "--json", action="store_true", help="machine-readable output"
    )
    ap.add_argument(
        "--emit", metavar="PATH", default=None,
        help="write the census-tuned ladder as a JSON file the engine "
        "loads at startup (the padding_ladder_file session property)",
    )
    args = ap.parse_args()

    census = load_census(args)
    rec = recommend_ladder(census, max_rungs=args.rungs, lane=args.lane)
    if args.emit:
        if not rec["observations"]:
            print("refusing to emit an empty ladder (no census "
                  "observations)", file=sys.stderr)
            return 1
        doc = {
            "ladder": rec["ladder"],
            "lane": args.lane,
            "wasteRatio": rec["wasteRatio"],
            "observations": rec["observations"],
            "source": "census",
        }
        # atomic write: a worker booting mid-emit must read the old
        # ladder or the new one, never a torn file
        tmp = args.emit + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        os.replace(tmp, args.emit)
        print(f"wrote {args.emit}: {len(rec['ladder'])} rungs, "
              f"predicted waste {rec['wasteRatio']:.3f}x")
        if not args.json:
            return 0
    if args.json:
        print(json.dumps(rec, indent=2, sort_keys=True))
        return 0 if rec["observations"] else 1
    if not rec["observations"]:
        print("no census observations found (is the directory right, "
              "and was compile_observatory_dir set on the run?)")
        return 1
    print(f"census: {rec['observations']} observations across "
          f"{len(census.families)} kernel families")
    print("recommended padding ladder (rows, lane-aligned):")
    for pr in rec["perRung"]:
        if not pr["count"]:
            continue
        waste = (
            pr["rung"] * pr["count"] / pr["actualRows"]
            if pr["actualRows"] else 1.0
        )
        print(f"  {pr['rung']:>12,}  covers {pr['count']:>8,} "
              f"observation(s)  (rung waste {waste:.2f}x)")
    print(f"ladder: {rec['ladder']}")
    print(f"predicted waste ratio: {rec['wasteRatio']:.3f}x "
          "(padded rows / actual rows over the censused traffic)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
