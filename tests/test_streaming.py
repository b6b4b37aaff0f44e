"""Streaming (bounded-working-set) execution tests.

Reference parity: operator/Driver.java:372 bounded-page streaming,
ScanFilterAndProjectOperator.java:190 split-at-a-time pull — here the
streaming unit is an HBM-sized tile of splits through the regular
fragment DAG (see exec/streaming.py docstring).
"""
import pytest

from trino_tpu.exec import streaming
from trino_tpu.session import tpch_session

Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING'
  and c_custkey = o_custkey and l_orderkey = o_orderkey
  and o_orderdate < date '1995-03-15' and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
"""

Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) q,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) c,
       avg(l_quantity) a, count(*) n
from lineitem where l_shipdate <= date '1998-09-02'
group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus
"""

Q6 = """
select sum(l_extendedprice * l_discount) from lineitem
where l_shipdate >= date '1994-01-01' and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07 and l_quantity < 24
"""


@pytest.fixture(scope="module")
def free():
    return tpch_session(0.05)


def _streamed(q, sf=0.05, limit=3_000_000):
    """Run under a tight limit, asserting the streaming path engaged."""
    calls = []
    orig = streaming.execute_streaming

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    streaming.execute_streaming = spy
    try:
        s = tpch_session(sf, query_max_memory_bytes=limit)
        rows = s.execute(q).to_pylist()
    finally:
        streaming.execute_streaming = orig
    assert calls, "streaming path did not engage"
    return rows


def test_q6_streams_exact(free):
    assert _streamed(Q6) == free.execute(Q6).to_pylist()


def test_q1_streams_exact(free):
    # grouped aggregation incl. wide decimal sums and avg across tiles
    assert _streamed(Q1) == free.execute(Q1).to_pylist()


def test_q3_streams_exact(free):
    # joins (broadcast builds) + group-by + topN across tiles
    assert _streamed(Q3) == free.execute(Q3).to_pylist()


def test_count_distinct_streams_under_memory_limit(free):
    """Round 3 refused this (raw rows gathered to one task); the
    decomposed plan (count over hash-partitioned Distinct) tiles, and
    with the rewrite disabled the distinct SPILL path (host-array
    distinct state) still answers exactly.  Only with spill disabled
    too does the limit surface LOUDLY rather than silently wrong."""
    from trino_tpu.utils.memory import ExceededMemoryLimitError

    q = "select count(distinct l_suppkey) from lineitem"
    ref = tpch_session(0.05).execute(q).to_pylist()
    s = tpch_session(0.05, query_max_memory_bytes=1_000_000)
    assert s.execute(q).to_pylist() == ref
    raw = tpch_session(
        0.05, query_max_memory_bytes=1_000_000,
        distinct_agg_rewrite=False,
    )
    assert raw.execute(q).to_pylist() == ref
    refused = tpch_session(
        0.05, query_max_memory_bytes=1_000_000,
        distinct_agg_rewrite=False, spill_enabled=False,
    )
    with pytest.raises(ExceededMemoryLimitError):
        refused.execute(q)


def test_multiple_tiles_used(free):
    """The tight limit must actually produce more than one tile."""
    from trino_tpu.exec.fragment_exec import FragmentExecutor

    created = []
    orig = FragmentExecutor.__init__

    def spy(self, *a, **k):
        created.append(1)
        return orig(self, *a, **k)

    FragmentExecutor.__init__ = spy
    try:
        rows = _streamed(Q6)
    finally:
        FragmentExecutor.__init__ = orig
    assert len(created) > 2, f"expected tiled executors, got {len(created)}"


def test_streamed_tiles_count_lines_from_the_index(free):
    """A streamed scan asks for the line count of the same order ranges on
    every query; from the second execution on the block-prefix index
    answers (connectors/tpch_device.lineitem_count_hashed) and only the
    tiles' edge blocks go through the host hash.  The tiles' counter
    reaches session.last_kernel_profile, and the answers do not change."""
    from trino_tpu.connectors import tpch, tpch_device

    asked = []
    orig = tpch_device.lineitem_count_hashed

    def spy(lo, hi):
        asked.append((lo, hi))
        return orig(lo, hi)

    s = tpch_session(0.05, query_max_memory_bytes=12_000_000,
                     result_cache=False)
    tpch_device.lineitem_count_hashed = spy
    try:
        first = s.execute(Q1).to_pylist()
        assert "lineCountOrdersHashed" in s.last_kernel_profile
        asked.clear()
        second = s.execute(Q1).to_pylist()
    finally:
        tpch_device.lineitem_count_hashed = orig
    tiles = len(asked)
    orders = tpch._counts(0.05)["orders"]
    assert tiles >= 2 and sorted(asked)[0][0] == 0
    assert sorted(asked)[-1][1] == orders
    hashed = s.last_kernel_profile["lineCountOrdersHashed"]
    assert hashed <= 2 * tiles * (tpch_device.LINE_COUNT_BLOCK - 1)
    assert hashed < orders  # a full re-hash would read `orders`
    assert first == second == free.execute(Q1).to_pylist()


def test_pure_sort_falls_back_to_spill():
    """Non-reducing plans must refuse streaming (spilled sort owns them:
    tiling a bare scan would re-materialize the table downstream)."""
    refused = []
    orig = streaming.execute_streaming
    streaming.execute_streaming = lambda *a, **k: refused.append(1) or orig(*a, **k)
    try:
        q = ("select l_orderkey, l_extendedprice from lineitem "
             "order by l_extendedprice desc, l_orderkey")
        s = tpch_session(0.01, query_max_memory_bytes=600_000)
        base = tpch_session(0.01)
        assert s.execute(q).to_pylist() == base.execute(q).to_pylist()
    finally:
        streaming.execute_streaming = orig
    assert not refused, "streaming engaged for a non-reducing sort plan"


def test_global_count_distinct_streams_instead_of_refusing():
    """count(DISTINCT x) over an oversized scan used to refuse streaming
    (raw rows gathered to one task); the decomposed plan (count over a
    hash-partitioned Distinct) tiles the scan and dedups per tile."""
    from trino_tpu.session import tpch_session

    s = tpch_session(0.05)
    sql = "select count(distinct l_orderkey) from lineitem"
    expected = s.execute(sql).to_pylist()
    # tiny budget: the lineitem scan cannot be device-resident at once
    tiny = tpch_session(0.05, query_max_memory_bytes=1 << 20)
    got = tiny.execute(sql).to_pylist()
    assert got == expected


def test_count_distinct_rewrite_plan_shape_and_parity():
    import trino_tpu.plan.nodes as P
    from trino_tpu.session import tpch_session

    s = tpch_session(0.01)
    sql = "select count(distinct l_suppkey) c from lineitem where l_quantity < 10"
    plan = s.plan(sql)
    found = []

    def walk(n):
        if isinstance(n, P.Distinct):
            found.append(n)
        for x in n.sources:
            walk(x)

    walk(plan)
    assert found, P.plan_to_string(plan)
    r1 = s.execute(sql).to_pylist()
    s.execute("set session distinct_agg_rewrite = false")
    r2 = s.execute(sql).to_pylist()
    assert r1 == r2
