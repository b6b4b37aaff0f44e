#!/usr/bin/env python
"""Lint: HBM residency hygiene for hot-path device programs.

Two checks:

  1. **Donation is a decision, not an accident.**  Every ``jax.jit(``
     call under ``trino_tpu/exec/``, ``trino_tpu/ops/``, and
     ``trino_tpu/connectors/`` must either pass ``donate_argnums`` (the
     compiled program may reuse the argument's HBM in place) or carry a
     ``# no-donate: <reason>`` comment on the call or just above it.  A
     bare jit on the hot path silently doubles page residency: the input
     buffers AND the program's working set live simultaneously.

  2. **No unregistered pallas kernels.**  Every ``def *_kernel(`` in
     ``trino_tpu/ops/pallas_kernels.py`` must appear as a key in its
     ``KERNEL_REGISTRY`` — the registry is what the kernel profile
     uses to attribute dispatches, so an unregistered kernel is
     invisible to regression triage.

Run standalone (``python scripts/check_donation.py``, exit 1 on
violations) or via ``scripts/lint.py`` / the tier-1 lint test.
"""
from __future__ import annotations

import os
import re
import sys

JIT_RE = re.compile(r"\bjax\s*\.\s*jit\s*\(")
KERNEL_DEF_RE = re.compile(r"^def\s+(_?[A-Za-z0-9_]*_kernel)\s*\(")

SCAN_DIRS = (
    os.path.join("trino_tpu", "exec"),
    os.path.join("trino_tpu", "ops"),
    os.path.join("trino_tpu", "connectors"),
)
PALLAS = os.path.join("trino_tpu", "ops", "pallas_kernels.py")

# the no-donate waiver may ride the preceding comment block
WAIVER_LOOKBACK = 2


def _call_text(text: str, start: int) -> str:
    """The balanced ``jax.jit(...)`` call starting at ``start`` (offset
    of the opening paren) — donate_argnums must be INSIDE this call, not
    merely on a nearby line (which would let an adjacent donated jit
    vouch for a bare one)."""
    depth = 0
    for i in range(start, len(text)):
        c = text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return text[start:i + 1]
    return text[start:]


def _iter_py(root: str):
    for d in SCAN_DIRS:
        base = os.path.join(root, d)
        for dirpath, _dirnames, filenames in os.walk(base):
            if "__pycache__" in dirpath:
                continue
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def check_tree(root: str):
    """Returns (checked_count, violations: [(relpath, lineno, message)])."""
    checked = 0
    violations = []
    for path in _iter_py(root):
        rel = os.path.relpath(path, root)
        with open(path, "r", encoding="utf-8") as f:
            text = f.read()
        lines = text.splitlines()
        for m in JIT_RE.finditer(text):
            checked += 1
            lineno = text.count("\n", 0, m.start()) + 1
            if "donate_argnums" in _call_text(text, m.end() - 1):
                continue
            back = "\n".join(
                lines[max(0, lineno - 1 - WAIVER_LOOKBACK): lineno]
            )
            if "# no-donate:" in back:
                continue
            violations.append((
                rel, lineno,
                "jax.jit without donate_argnums — donate the per-dispatch "
                "buffers or waive with '# no-donate: <reason>'",
            ))

    pallas_path = os.path.join(root, PALLAS)
    with open(pallas_path, "r", encoding="utf-8") as f:
        text = f.read()
    rel = os.path.relpath(pallas_path, root)
    m = re.search(r"KERNEL_REGISTRY\s*=\s*\{(.*?)\n\}", text, re.S)
    registry = m.group(1) if m else ""
    for i, line in enumerate(text.splitlines()):
        dm = KERNEL_DEF_RE.match(line)
        if not dm:
            continue
        checked += 1
        name = dm.group(1)
        if '"%s"' % name not in registry and "'%s'" % name not in registry:
            violations.append((
                rel, i + 1,
                "kernel %s not in KERNEL_REGISTRY — unregistered kernels "
                "are invisible to dispatch attribution" % name,
            ))
    if m is None:
        violations.append((rel, 1, "KERNEL_REGISTRY not found"))
    return checked, violations


def main() -> int:
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    checked, violations = check_tree(root)
    for rel, lineno, msg in violations:
        print("%s:%d: %s" % (rel, lineno, msg))
    print(
        "check_donation: %d site(s) checked, %d violation(s)"
        % (checked, len(violations))
    )
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
