"""sqlite3-based correctness oracle.

The reference validates SQL semantics against an H2 in-memory DB loaded with
TPC-H (testing/trino-testing/.../H2QueryRunner.java:91).  Here sqlite (stdlib)
plays the H2 role: identical generated data is loaded host-side and the same
(or dialect-adjusted) SQL runs on both engines; results are diffed with
decimal tolerance.
"""
from __future__ import annotations

import importlib.util
import os
import sqlite3
import sys
from typing import Iterable, Sequence

import numpy as np

from trino_tpu.connectors import tpch
from trino_tpu.page import Column, Page


def load_tpch(conn: sqlite3.Connection, sf: float, tables: Iterable[str]):
    # SQL-spec (and Trino) LIKE is case-sensitive; sqlite defaults to
    # case-insensitive ASCII matching, which diverges on patterns like
    # Q16's '%Customer%Complaints%'
    conn.execute("PRAGMA case_sensitive_like = ON")
    for table in tables:
        schema = tpch.SCHEMAS[table]
        cols = ", ".join(c for c, _ in schema)
        conn.execute(f"CREATE TABLE {table} ({cols})")
        values, dicts, count = tpch.generate(table, sf)
        page = Page(
            [Column(t, values[c], None, dicts.get(c)) for c, t in schema],
            count,
            [c for c, _ in schema],
        )
        rows = page.to_pylist()
        ph = ", ".join(["?"] * len(schema))
        conn.executemany(f"INSERT INTO {table} VALUES ({ph})", rows)
    conn.commit()


def normalize(rows: Sequence[tuple]) -> list:
    from decimal import Decimal

    out = []
    for r in rows:
        norm = []
        for v in r:
            if isinstance(v, Decimal):
                # wide decimals come back exact; oracle sides are floats
                norm.append(round(float(v), 4))
            elif isinstance(v, float):
                norm.append(round(v, 4))
            elif isinstance(v, np.generic):
                norm.append(v.item())
            else:
                norm.append(v)
        out.append(tuple(norm))
    return out


def assert_rows_match(actual, expected, tol=1e-2, ordered=True):
    assert len(actual) == len(expected), (
        f"row count {len(actual)} != {len(expected)}\n"
        f"actual[:5]={actual[:5]}\nexpected[:5]={expected[:5]}"
    )
    a = actual if ordered else sorted(map(repr, actual))
    b = expected if ordered else sorted(map(repr, expected))
    if not ordered:
        # fall back to repr-sort only for fully-hashable rows
        a = sorted(normalize(actual), key=repr)
        b = sorted(normalize(expected), key=repr)
    else:
        a = normalize(actual)
        b = normalize(expected)
    for i, (ra, rb) in enumerate(zip(a, b)):
        assert len(ra) == len(rb), f"row {i}: arity {len(ra)} != {len(rb)}"
        for j, (va, vb) in enumerate(zip(ra, rb)):
            if isinstance(va, float) or isinstance(vb, float):
                assert va is not None and vb is not None, (
                    f"row {i} col {j}: {va!r} != {vb!r}"
                )
                denom = max(1.0, abs(vb))
                assert abs(float(va) - float(vb)) / denom <= tol, (
                    f"row {i} col {j}: {va!r} != {vb!r}"
                )
            else:
                assert va == vb, f"row {i} col {j}: {va!r} != {vb!r}"


def assert_q1_fuses_past_the_int64_gate(monkeypatch, make_session, sf=0.01):
    """TPC-H Q1 with ``megakernel.SUM_GATE`` patched so low that the
    table-wide int64 proof fails for ``sum_charge``: its wide
    accumulators must fuse all the same (chunk recombination), in a
    fresh session so that the fragment is traced, and answer as the
    unfused path and sqlite do.  ``make_session(sf, **props)`` builds
    the session under test; returns its outer kernel profile."""
    from tpch_sql import QUERIES, oracle_dialect
    from trino_tpu.ops import megakernel
    from trino_tpu.session import tpch_session

    q1 = QUERIES[1][0]
    monkeypatch.setattr(megakernel, "SUM_GATE", 2 ** 40)
    # detached from the process-wide jit cache, both sessions: this one
    # has to trace, and neither may leave a program of TPC-H's own Q1
    # text for a test that expects to compile it (test_tpu_compile)
    props = {"result_cache": False, "compile_cache": False}
    on = make_session(sf, megakernels="on", **props)
    rows = on.execute(q1).to_pylist()
    prof = on.last_kernel_profile
    assert prof.get("fusedAggregates", 0) >= 1, prof
    assert prof.get("fusedSumsPastInt64", 0) >= 1, prof
    assert not prof.get("fusionRejects"), prof
    off = tpch_session(sf, megakernels="off", **props)
    assert rows == off.execute(q1).to_pylist()
    conn = sqlite3.connect(":memory:")
    load_tpch(conn, sf, ["lineitem"])
    expected = conn.execute(oracle_dialect(q1)).fetchall()
    assert_rows_match(rows, expected, tol=2e-2, ordered=True)
    return prof


BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def bench_module(*parts):
    """A file of benchmark/ under a name of its own (tests/ and benchmark/
    both have short module names)."""
    qdir = os.path.join(BENCH, "queries")
    if qdir not in sys.path:
        sys.path.append(qdir)   # the queries import their `_rows`
    name = "bench_" + "_".join(parts)
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, *parts) + ".py")
        sys.modules[name] = mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    return sys.modules[name]
