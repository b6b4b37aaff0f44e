#!/usr/bin/env python
"""Lint: all shape padding goes through trino_tpu/exec/shapes.py.

The bucketed-batch ABI only bounds compiled-program counts if EVERY
padded capacity quantizes through the one PaddingLadder — a single
ad-hoc ``((n + 127) // 128) * 128`` re-introduces an unbounded shape
per split size and silently re-opens the p99 retrace hole the ladder
closed.  This linter forbids the next-multiple-of-lane idiom (and
direct re-implementations of it) everywhere except the canonical home,
``trino_tpu/exec/shapes.py``.

Suppression: append ``# pad-discipline: ok`` with a reason when a match
is genuinely not a shape capacity (none exist today).
"""
from __future__ import annotations

import os
import re
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

SCAN_DIRS = ("trino_tpu", "scripts", "tests")

# the canonical home of the idiom; everything else must quantize
# through exec.shapes (lane_align / PaddingLadder.quantize)
ALLOWED = (os.path.join("trino_tpu", "exec", "shapes.py"),)

PATTERNS = (
    # ((n + 127) // 128) * 128 and spacing variants
    re.compile(r"\+\s*127\s*\)\s*//\s*128"),
    re.compile(r"//\s*128\s*\)\s*\*\s*128"),
    # the generalized form: ((n + lane - 1) // lane) * lane
    re.compile(r"\+\s*lane\s*-\s*1\s*\)\s*//\s*lane"),
    re.compile(r"//\s*lane\s*\)\s*\*\s*lane"),
)

SUPPRESS = "# pad-discipline: ok"


def _py_files():
    for d in SCAN_DIRS:
        for dirpath, _dirnames, filenames in os.walk(os.path.join(ROOT, d)):
            for fn in filenames:
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def main() -> int:
    me = os.path.abspath(__file__)
    violations = []
    for path in _py_files():
        rel = os.path.relpath(path, ROOT)
        if rel in ALLOWED or os.path.abspath(path) == me:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                lines = f.readlines()
        except OSError:
            continue
        for i, line in enumerate(lines, 1):
            if SUPPRESS in line:
                continue
            for pat in PATTERNS:
                if pat.search(line):
                    violations.append(f"{rel}:{i}: {line.strip()}")
                    break
    if violations:
        print("pad discipline: ad-hoc lane padding outside "
              "trino_tpu/exec/shapes.py — quantize through the "
              "PaddingLadder (or shapes.lane_align) instead:")
        for v in violations:
            print("  " + v)
        return 1
    print("pad discipline: ok (all padding via exec/shapes.py)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
