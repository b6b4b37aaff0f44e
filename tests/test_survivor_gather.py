"""An inner join that compacts gathers its build side for the survivors
only (`exec/local._TraceCtx._compact_then_gather`): where the join's output
selection is exactly the probe's `matched` (no residual key, no residual
filter) and its compaction engages, the probe lanes and the build row are
compacted first and the build columns gathered over `cap` slots, not over
every probe slot and then again by the compaction.  Every case answers as
the sqlite oracle does and as the same text with compaction off, and
`survivorGatherJoins` counts exactly the joins that qualify."""
import dataclasses as dc
import sqlite3

import pytest

from oracle import assert_rows_match, load_tpch
from tpch_sql import oracle_dialect
from trino_tpu.exec.local import _TraceCtx
from trino_tpu.plan import optimizer
from trino_tpu.session import tpch_session

SF = 0.01
QUARTER = ("o_orderdate >= date '1995-01-01' "
           "and o_orderdate < date '1995-04-01'")
DIRECT = f"""select o_orderpriority, count(*) as n, sum(l_extendedprice) as s
  from lineitem join orders on l_orderkey = o_orderkey
  where {QUARTER}
  group by o_orderpriority order by o_orderpriority"""

# name -> (text, joins that take the new path, direct tables allowed,
#          the counter that names the join's lowering)
CASES = {
    # one gather into a direct table
    "direct": (DIRECT, 1, True, "directJoins"),
    # the sorted unique build and `probe`
    "unique_key": (DIRECT, 1, False, "sortJoins"),
    # two columns: the hashed locator, `verify_rows` inside `matched`; the
    # estimate (2 rows) is far under the survivors: it also climbs a rung
    "unique_key_verified": (
        f"""select o1.o_orderpriority, count(*) as n, sum(o1.o_totalprice) as s
          from orders o1 join (select o_orderkey as k, o_custkey as c
            from orders where {QUARTER}) o2
          on o1.o_orderkey = o2.k and o1.o_custkey = o2.c
          group by o1.o_orderpriority order by o1.o_orderpriority""",
        1, False, "hashedKeyJoins"),
    # direct on `s_suppkey`, the nation compared on the gathered row: the
    # survivors of the first key outnumber the estimate, so it keeps the
    # gather-then-compact order
    "residual_keys": (
        """select s_nationkey, count(*) as n, sum(l_extendedprice) as s
          from lineitem join supplier
          on l_suppkey = s_suppkey and l_linenumber = s_nationkey
          group by s_nationkey order by s_nationkey""",
        0, True, "residualKeyJoins"),
    # a residual filter reads the build's columns on every probe slot
    "residual_filter": (
        DIRECT.replace(
            "on l_orderkey = o_orderkey",
            "on l_orderkey = o_orderkey "
            "and l_extendedprice * 4 > o_totalprice"),
        0, True, "directJoins"),
    # a left join keeps every probe row: nothing compacts
    "left_join": (
        f"""select l_linenumber, count(o_orderkey) as n, sum(o_totalprice) as s
          from lineitem left join (select * from orders where {QUARTER}) o
          on l_orderkey = o_orderkey
          group by l_linenumber order by l_linenumber""",
        0, True, "directJoins"),
}


@pytest.fixture(scope="module")
def oracle_db():
    conn = sqlite3.connect(":memory:")
    load_tpch(conn, SF, ["orders", "lineitem", "supplier"])
    return conn


@pytest.fixture()
def toy_scale_compacts(monkeypatch):
    """The optimizer compacts tables of 2^20 rows and more; SF 0.01 has
    60,000."""
    monkeypatch.setattr(optimizer, "_COMPACT_MIN_ROWS", 1 << 10)


def _run(sql):
    s = tpch_session(SF, result_cache=False, compile_cache=False,
                     device_cpu_fallback=False)
    return s.execute(sql).to_pylist(), s.last_kernel_profile


@pytest.mark.parametrize("case", list(CASES))
def test_join_answers_as_the_oracle_and_counts_the_joins_that_qualify(
        case, toy_scale_compacts, oracle_db, monkeypatch):
    sql, survivors, direct, lowering = CASES[case]
    if not direct:
        monkeypatch.setattr(optimizer, "_DIRECT_MAX_DOMAIN", 0)
    rows, prof = _run(sql)
    assert rows
    assert_rows_match(rows, oracle_db.execute(oracle_dialect(sql)).fetchall(),
                      tol=1e-6, ordered=True)
    assert prof.get("survivorGatherJoins", 0) == survivors
    assert prof.get(lowering, 0) == 1
    if survivors:
        assert prof["compactions"] >= 1
    # without compaction: the same rows in the same order
    monkeypatch.setattr(optimizer, "_COMPACT_MIN_ROWS", 1 << 40)
    off_rows, off = _run(sql)
    assert off_rows == rows
    assert "compactions" not in off and "survivorGatherJoins" not in off
    # an inner join counts its probe width, whichever order it took
    assert off.get("joinSlots") == prof.get("joinSlots")


def test_survivors_past_the_estimates_rung_rerun_wider(
        toy_scale_compacts, oracle_db, monkeypatch):
    """A twentieth of the optimizer's estimate: the join compacts its first
    `cap` survivors, the capacity check sees the true count, the ladder
    re-runs the fragment wider, and the answer is exact."""
    seen = []
    orig = _TraceCtx._compaction_cap

    def starved(self, node, n):
        est = getattr(node, "compact_rows", None)
        if est is not None:
            seen.append(est)
            node = dc.replace(node, compact_rows=max(1, est // 20))
        return orig(self, node, n)

    monkeypatch.setattr(_TraceCtx, "_compaction_cap", starved)
    rows, prof = _run(DIRECT)
    assert seen
    assert prof["summary"]["compilesByCause"].get("ladder_rung", 0) >= 1
    assert prof["survivorGatherJoins"] == 1
    assert_rows_match(rows, oracle_db.execute(oracle_dialect(DIRECT))
                      .fetchall(), tol=1e-6, ordered=True)
