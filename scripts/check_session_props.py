#!/usr/bin/env python
"""Lint: session properties are well-formed and actually consumed.

Checks, over ``SESSION_PROPERTIES`` in ``trino_tpu/config.py``:

  1. every property name is snake_case (``^[a-z][a-z0-9_]*$``) — the
     SET SESSION surface is one naming regime with the metric stems;
  2. no duplicate ``PropertyMetadata`` registrations (the dict build
     would silently keep only the last one);
  3. every property carries a non-empty description (SHOW SESSION's
     third column must never be blank);
  4. every property name is referenced somewhere in the tree OUTSIDE
     its registration — a property nothing reads is dead config.

Run standalone (``python scripts/check_session_props.py``, exit 1 on
violations) or via ``scripts/lint.py`` / the tier-1 lint test.
"""
from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
REGISTRATION_RE = re.compile(
    r'PropertyMetadata\(\s*["\']([a-z0-9_.]+)["\']'
)

SCAN_DIRS = ("trino_tpu", "tests", "scripts")


def iter_source_files(root: str):
    for d in SCAN_DIRS:
        base = os.path.join(root, d)
        for dirpath, _dirnames, filenames in os.walk(base):
            if "__pycache__" in dirpath:
                continue
            for fn in filenames:
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def check_tree(root: str):
    """Returns (checked_count, violations: [(where, message)])."""
    violations = []
    config_path = os.path.join(root, "trino_tpu", "config.py")
    with open(config_path, "r", encoding="utf-8") as f:
        config_text = f.read()

    names = REGISTRATION_RE.findall(config_text)
    rel = os.path.relpath(config_path, root)
    seen = set()
    for n in names:
        if not NAME_RE.match(n):
            violations.append(
                (rel, f"property {n!r} violates snake_case "
                      "^[a-z][a-z0-9_]*$")
            )
        if n in seen:
            violations.append(
                (rel, f"property {n!r} registered twice (the dict build "
                      "silently keeps only the last)")
            )
        seen.add(n)

    from trino_tpu.config import SESSION_PROPERTIES

    for name, meta in SESSION_PROPERTIES.items():
        if not str(getattr(meta, "description", "") or "").strip():
            violations.append(
                (rel, f"property {name!r} has an empty description")
            )

    # dead-property check: the quoted name must appear in some file
    # other than its registration (properties.get / props dict keys /
    # SET SESSION text in tests all count as consumption)
    referenced = set()
    for path in iter_source_files(root):
        if os.path.abspath(path) == os.path.abspath(config_path):
            continue
        with open(path, "r", encoding="utf-8") as f:
            text = f.read()
        for n in names:
            if n in referenced:
                continue
            if f'"{n}"' in text or f"'{n}'" in text or f" {n} " in text:
                referenced.add(n)
    for n in names:
        if n not in referenced:
            violations.append(
                (rel, f"property {n!r} is never referenced outside its "
                      "registration (dead config)")
            )
    return len(names), violations


def main() -> int:
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    checked, violations = check_tree(root)
    if violations:
        for where, msg in violations:
            print(f"{where}: {msg}")
        return 1
    print(f"ok: {checked} session properties conform and are consumed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
