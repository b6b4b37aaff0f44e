"""Streaming (bounded-working-set) execution tests.

Reference parity: operator/Driver.java:372 bounded-page streaming,
ScanFilterAndProjectOperator.java:190 split-at-a-time pull — here the
streaming unit is an HBM-sized tile of splits through the regular
fragment DAG (see exec/streaming.py docstring).
"""
import pytest

from oracle import assert_q1_fuses_past_the_int64_gate
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


def test_streamed_q1_fuses_past_the_int64_gate(monkeypatch):
    """Each tile's PARTIAL aggregate fuses although the table-wide bound
    of sum_charge is past the (patched) int64 gate, and the tile
    executors' counters reach the outer profile."""
    prof = assert_q1_fuses_past_the_int64_gate(
        monkeypatch,
        lambda sf, **props: tpch_session(
            sf, query_max_memory_bytes=1_000_000, **props),
    )
    assert prof["streamedFragments"] >= 2, prof


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


def _tiled_q1_session(**props):
    """Q1 at SF 0.05 under a limit that tiles lineitem in four."""
    return tpch_session(0.05, query_max_memory_bytes=12_000_000,
                        result_cache=False, **props)


def _run_q1(s, sql=Q1):
    """One execution: (rows, tiles found resident, tiles generated,
    names of the spans it opened)."""
    s.tracer.spans.clear()
    rows = s.execute(sql).to_pylist()
    prof = s.last_kernel_profile
    return (rows, prof.get("residentTileHits", 0),
            prof.get("residentTileMisses", 0),
            [(sp.name, sp.attributes.get("resident"))
             for sp in s.tracer.spans])


def _one_tile_short(s):
    """Shrink the session's scan cache to one tile less than Q1's tiles
    (sized from a session that kept them)."""
    kept = _tiled_q1_session()
    kept.execute(Q1)
    sizes = [e["nbytes"] for e in kept._scan_cache.entries.values()]
    assert len(sizes) >= 2
    s._scan_cache.max_bytes = sum(sizes) - 1
    return len(sizes)


def test_streamed_tiles_count_lines_from_the_index(free):
    """A streamed scan whose tiles are not kept asks for the line count of
    the same order ranges on every query; from the second execution on the
    block-prefix index answers
    (connectors/tpch_device.lineitem_count_hashed) and only the tiles'
    edge blocks go through the host hash.  The tiles' counter reaches
    session.last_kernel_profile, and the answers do not change."""
    from trino_tpu.connectors import tpch, tpch_device

    asked = []
    orig = tpch_device.lineitem_count_hashed

    def spy(lo, hi):
        asked.append((lo, hi))
        return orig(lo, hi)

    s = _tiled_q1_session()
    _one_tile_short(s)
    tpch_device.lineitem_count_hashed = spy
    try:
        first = s.execute(Q1).to_pylist()
        assert "lineCountOrdersHashed" in s.last_kernel_profile
        asked.clear()
        second = s.execute(Q1).to_pylist()
    finally:
        tpch_device.lineitem_count_hashed = orig
    tiles = len(set(asked))  # the first tile is also asked when admitting
    orders = tpch._counts(0.05)["orders"]
    assert tiles >= 2 and sorted(asked)[0][0] == 0
    assert sorted(asked)[-1][1] == orders
    hashed = s.last_kernel_profile["lineCountOrdersHashed"]
    assert hashed <= 2 * tiles * (tpch_device.LINE_COUNT_BLOCK - 1)
    assert hashed < orders  # a full re-hash would read `orders`
    assert first == second == free.execute(Q1).to_pylist()


def test_resident_tiles_are_generated_once(free):
    """A streamed scan whose device-generated tiles all fit the session's
    scan cache is generated by the first query and found by the second:
    no generator dispatch, no line-count lookup, the same answer."""
    from trino_tpu.connectors import tpch_device

    asked = []
    orig = tpch_device.lineitem_count_hashed
    tpch_device.lineitem_count_hashed = (
        lambda lo, hi: asked.append((lo, hi)) or orig(lo, hi))
    s = _tiled_q1_session()
    try:
        first, hits, misses, spans = _run_q1(s)
        tiles = misses
        assert tiles >= 2 and hits == 0
        assert [n for n, _ in spans].count("devgen") == tiles
        assert [r for n, r in spans if n == "tile_stage"] == [False] * tiles
        asked.clear()
        second, hits, misses, spans = _run_q1(s)
    finally:
        tpch_device.lineitem_count_hashed = orig
    assert (hits, misses) == (tiles, 0) and not asked
    assert "devgen" not in [n for n, _ in spans]
    assert [r for n, r in spans if n == "tile_stage"] == [True] * tiles
    assert first == second == free.execute(Q1).to_pylist()
    # what the harness's device check reads, and an entry charged what its
    # lanes hold in HBM at the tiles' shared rung
    cache = s._scan_cache
    assert len(cache.entries) == tiles
    for key, entry in cache.entries.items():
        assert key[1] == "lineitem" and entry["devgen"] is not None
        assert not any(hasattr(v, "dtype") for v, _ in entry["merged"].values())
        planes = {id(ok): ok for _, ok in entry["dev"].values()}
        assert entry["nbytes"] == sum(
            v.nbytes for v, _ in entry["dev"].values()
        ) + sum(ok.nbytes for ok in planes.values())
    assert cache.bytes == sum(e["nbytes"] for e in cache.entries.values())


def test_tiles_one_short_of_fitting_are_not_kept(free):
    """All or none: a cache that cannot hold every tile holds none, on
    any execution, and the tiles leave its tallies alone."""
    s = _tiled_q1_session()
    tiles = _one_tile_short(s)
    # another table's entry is there first, and stays
    s.execute("select count(*) from orders where o_orderkey < 100")
    def tallies():
        stats = s._scan_cache.stats()
        return {k: stats[k] for k in ("puts", "evictions", "entries", "bytes")}

    before = tallies()
    assert before["entries"] == 1
    for _ in range(3):
        rows, hits, misses, spans = _run_q1(s)
        assert (hits, misses) == (0, tiles)
        assert [n for n, _ in spans].count("devgen") == tiles
        assert rows == free.execute(Q1).to_pylist()
    assert tallies() == before


def test_kept_tiles_never_evict_another_tables_entry(free):
    """The tiles are admitted against what the cache has FREE: an entry
    that leaves too little room stays, and the tiles are not kept."""
    s = _tiled_q1_session()
    tiles = _one_tile_short(s)
    s._scan_cache.max_bytes += 1  # exactly the tiles
    s.execute("select count(*) from orders where o_orderkey < 100")
    (held,) = s._scan_cache.entries
    rows, hits, misses, _ = _run_q1(s)
    assert (hits, misses) == (0, tiles)
    assert list(s._scan_cache.entries) == [held]
    assert s._scan_cache.evictions == 0
    s._scan_cache.max_bytes += s._scan_cache.bytes  # now both fit
    assert _run_q1(s)[1:3] == (0, tiles)
    assert _run_q1(s)[1:3] == (tiles, 0)
    assert held in s._scan_cache.entries


def test_dropped_resident_tiles_regenerate(free):
    """The memory manager may revoke the scan cache between two queries:
    the next one regenerates every tile and answers the same."""
    s = _tiled_q1_session()
    first, _, tiles, _ = _run_q1(s)
    assert _run_q1(s)[1:3] == (tiles, 0)
    assert s._scan_cache.drop_all() > 0
    again, hits, misses, spans = _run_q1(s)
    assert (hits, misses) == (0, tiles)
    assert [n for n, _ in spans].count("devgen") == tiles
    assert _run_q1(s)[1:3] == (tiles, 0)
    assert first == again == free.execute(Q1).to_pylist()


def test_resident_tiles_are_kept_per_pushed_down_predicate(free):
    """The scan's constraint is part of its identity: another DELTA's
    tiles are other entries, and the first text still finds its own."""
    other = Q1.replace("1998-09-02", "1998-08-01")
    s = _tiled_q1_session()
    first, _, tiles, _ = _run_q1(s)
    rows, hits, misses, _ = _run_q1(s, other)
    assert (hits, misses) == (0, tiles)
    assert rows == free.execute(other).to_pylist()
    assert len(s._scan_cache.entries) == 2 * tiles
    again, hits, misses, _ = _run_q1(s)
    assert (hits, misses) == (tiles, 0) and again == first
    assert _run_q1(s, other)[1:3] == (tiles, 0)


def test_cached_lane_of_another_rung_is_not_reused():
    """Cached lanes are keyed by column alone, and a tile's are padded to
    the tiles' shared rung, not the rung of its own row count: an executor
    that reads the same splits at another rung generates its own lanes
    instead of handing these to a program traced for its shape."""
    s = _tiled_q1_session()
    s.execute(Q1)
    (_plan, frags), = s._fragment_cache.values()
    source = next(f for f in frags if f.partitioning == "source")
    (node,) = streaming._find_scan_nodes(source.root)
    conn = s.catalogs.get(node.catalog)
    splits = conn.split_manager().get_splits(
        node.table, len(s._scan_cache.entries), node.constraint)[:1]
    ex = s._executor()
    scans, dicts, counts = {}, {}, {}
    ex._load_one_scan(node, splits, scans, dicts, counts)
    entry = s._scan_cache.entries[ex._scan_keys[id(node)]]
    (tile_cap,) = {ok.shape[-1] for _, ok in entry["dev"].values()}
    same = ex._device_lanes(node, scans[id(node)], counts[id(node)])
    assert all(same[sym][0] is entry["dev"][col][0]
               for sym, col in node.assignments)
    ex.config["scan_cap_override"] = 2 * tile_cap
    lanes = ex._device_lanes(node, scans[id(node)], counts[id(node)])
    assert {v.shape[0] for v, _ in lanes.values()} == {2 * tile_cap}


def test_scan_cache_is_safe_between_a_putting_and_a_getting_thread():
    """The prefetch thread puts tiles while the query thread looks them
    up (and the memory manager may drop everything): no exception, and
    the byte count stays the sum of the entries'."""
    import sys
    import threading

    from trino_tpu.exec.local import DeviceScanCache

    cache = DeviceScanCache(max_bytes=64 * 100)
    rounds, errors = 10_000, []

    def run(body):
        try:
            for i in range(rounds):
                body(i)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def put(i):
        cache.put(("t", i % 257), {"dev": {}}, 100)

    def get(i):
        cache.get(("t", (7 * i) % 257))

    def drop(i):
        if i % 1000 == 999:
            cache.drop_all()

    threads = [threading.Thread(target=run, args=(body,))
               for body in (put, put, get, get, drop)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not errors
    assert cache.bytes == sum(e["nbytes"] for e in cache.entries.values())
    assert cache.bytes <= cache.max_bytes
    assert cache.hits + cache.misses == 2 * rounds
    assert cache.puts == 2 * rounds


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
