"""TPC-H Q21 (`tpch_sf10_q21.q21`): Q21 through the session against the
benchmark's plain reference (`benchmark/queries/q21.py` over
`benchmark/datagen.py`, `_q5_columns.py` and `_q21_columns.py`: numpy, by
counting, nothing of the program), the reference's extra columns against
the program's generator, the estimate of a comparison between two columns
of one row that sizes Q21's buffers at plan time, and the reader this cell
brought."""
import numpy as np
import pytest

from tpch_sql import QUERIES
from trino_tpu.connectors import tpch
from trino_tpu.plan import nodes as P
from trino_tpu.plan.cost import (
    UNKNOWN_FILTER, StatsProvider, _column_pair_share, annotate)
from trino_tpu.session import tpch_session
from trino_tpu.spi import ColumnStatistics

from oracle import bench_module


@pytest.fixture(scope="module")
def q21():
    return bench_module("queries", "q21")


@pytest.fixture(scope="module")
def datagen():
    return bench_module("datagen")


@pytest.mark.parametrize("sf,nation", [
    (0.01, "SAUDI ARABIA"), (0.01, "ALGERIA"), (0.01, "UNITED STATES"),
    (0.1, "SAUDI ARABIA"), (0.1, "JAPAN"), (0.1, "PERU")])
def test_q21_equals_the_reference(q21, datagen, sf, nation):
    p = {"nation": nation}
    (ref,), rows = q21.reference(datagen, sf, [p])
    s = tpch_session(sf, device_cpu_fallback=False, result_cache=False,
                     compile_cache=False)
    got = s.execute(q21.sql(p)).to_pylist()
    assert got and q21.check(got, ref), (got[:5], ref[:5])
    assert rows == {t: tpch.generate(t, sf, columns=q21.TABLES[t][:1])[2]
                    for t in q21.TABLES}
    prof = s.last_kernel_profile
    assert prof["semiJoins"] == prof["residualSemiJoins"] == 2
    assert prof["semiWorkSlots"] > 0


def test_q21_compiles_once_at_sf1():
    """The late-line filter estimated from both dates' ranges (0.51, true
    0.63) sizes every compaction above its survivors at the first rung:
    no `ladder_rung` compile (at UNKNOWN_FILTER's 0.3 the supplier join's
    compaction held 131,072 slots for about 152,000 survivors)."""
    s = tpch_session(1.0, device_cpu_fallback=False, result_cache=False,
                     compile_cache=False)
    s.execute(QUERIES[21][0]).to_pylist()
    prof = s.last_kernel_profile
    assert prof["summary"]["compilesByCause"] == {"first_compile": 1}
    assert prof["residualSemiJoins"] == 2
    # both mark joins read each key's extremes: the build's slots plus
    # the probe's, no pair buffer
    lineitem_slots = 8_388_608
    assert prof["semiWorkSlots"] < 2 * (lineitem_slots + 1_048_576)


@pytest.mark.parametrize("sf", [0.01, 1.0])
def test_q21_columns_equal_the_programs_generator(datagen, sf):
    cols = bench_module("queries", "_q21_columns")
    lo, hi = 1000, 1000 + min(20_000, datagen.counts(sf)["orders"] - 1000)
    mine = datagen.lineitem(sf, ["l_orderkey", "l_linestatus"], lo, hi)
    values, _, _ = tpch.generate(
        "lineitem", sf, columns=["l_orderkey", "l_commitdate", "l_receiptdate"])
    at = np.searchsorted(values["l_orderkey"], mine["l_orderkey"][0])
    span = slice(at, at + len(mine["l_orderkey"]))
    assert np.array_equal(values["l_orderkey"][span], mine["l_orderkey"])
    for c in ("l_commitdate", "l_receiptdate"):
        assert np.array_equal(getattr(cols, c)(datagen, mine["l_orderkey"]),
                              values[c][span])
    keys, status = cols.o_orderstatus(mine["l_orderkey"], mine["l_linestatus"])
    orders, dicts, _ = tpch.generate(
        "orders", sf, columns=["o_orderkey", "o_orderstatus"])
    assert list(dicts["o_orderstatus"]) == cols.ORDER_STATUS
    at = np.searchsorted(orders["o_orderkey"], keys[0])
    assert np.array_equal(orders["o_orderkey"][at:at + len(keys)], keys)
    assert np.array_equal(orders["o_orderstatus"][at:at + len(keys)], status)
    assert set(status.tolist()) == {0, 1, 2}
    values, dicts, n = tpch.generate("supplier", sf, columns=["s_name"])
    names = dicts["s_name"]
    assert cols.s_name(np.arange(1, n + 1)) == [names[c] for c in values["s_name"]]


def _uniform(lo, hi, nulls=0.0):
    return ColumnStatistics(min_value=lo, max_value=hi, null_fraction=nulls)


@pytest.mark.parametrize("a,b,op,share", [
    ((0.0, 1.0), (0.0, 1.0), "<", 0.5),
    ((0.0, 1.0), (0.0, 1.0), ">=", 0.5),
    ((0.0, 1.0), (2.0, 3.0), "<", 1.0),
    ((0.0, 1.0), (2.0, 3.0), ">", 0.0),
    ((0.0, 2.0), (1.0, 3.0), "<", 0.875),
    ((1.0, 3.0), (0.0, 2.0), ">", 0.875),
    ((0.0, 4.0), (1.0, 2.0), "<=", 0.375),
])
def test_a_two_column_comparison_takes_the_uniform_overlap(a, b, op, share):
    assert _column_pair_share(_uniform(*a), _uniform(*b), op) == \
        pytest.approx(share)
    # each side's NULLs make the comparison unknown
    assert _column_pair_share(_uniform(*a, 0.5), _uniform(*b, 0.2), op) == \
        pytest.approx(share * 0.4)


def test_an_unknown_range_or_an_equality_keeps_unknown_filter():
    known = _uniform(0.0, 1.0)
    assert _column_pair_share(ColumnStatistics(), known, "<") is None
    assert _column_pair_share(known, ColumnStatistics(min_value=0.0), "<") is None
    assert _column_pair_share(known, known, "=") is None
    assert _column_pair_share(known, known, "<>") is None
    s = tpch_session(0.01)
    plan = s.plan("select count(*) from lineitem "
                  "where l_partkey < l_suppkey + l_linenumber")
    est = annotate(plan, s.metadata)
    stats = StatsProvider(s.metadata)
    (f,) = [n for n in _walk(plan) if isinstance(n, P.Filter)]
    assert est[id(f)]["rows"] == pytest.approx(
        stats.estimate(f.source).rows * UNKNOWN_FILTER)


def _walk(n):
    yield n
    for s in n.sources:
        yield from _walk(s)


def test_q21_sizes_its_mark_joins_at_plan_time_at_sf10(q21):
    """Planning only: each mark join carries its pair estimate (the
    source's rows x lineitem's rows per order key, the pair buffer's
    size where pairs are expanded), and the filters on the two marks do
    not compact (EXISTS 0.3 estimated, 0.96 true at SF 1)."""
    s = tpch_session(10.0)
    plan = s.plan(q21.sql({"nation": "SAUDI ARABIA"}))
    stats = StatsProvider(s.metadata)
    semis = [n for n in _walk(plan) if isinstance(n, P.SemiJoin)]
    assert len(semis) == 2 and all(n.filter is not None for n in semis)
    for n in semis:
        per_key = stats.estimate(n.filtering).rows / tpch._counts(10.0)["orders"]
        assert n.pair_rows == pytest.approx(
            stats.estimate(n.source).rows * per_key, rel=1e-3)
    marks = [n for n in _walk(plan) if isinstance(n, P.Filter)
             and isinstance(n.source, P.SemiJoin)]
    assert len(marks) == 2 and all(m.compact_rows is None for m in marks)


def test_the_late_line_estimate_is_within_1_3x_of_the_truth():
    """`l_receiptdate > l_commitdate` at SF 10, from the connector's ranges:
    0.632 of the lines at SF 0.1 (379,604 of 600,886)."""
    s = tpch_session(10.0)
    plan = s.plan("select count(*) from lineitem "
                  "where l_receiptdate > l_commitdate")
    stats = StatsProvider(s.metadata)
    (f,) = [n for n in _walk(plan) if isinstance(n, P.Filter)]
    share = stats.estimate(f).rows / stats.estimate(f.source).rows
    assert 0.632 / 1.3 <= share <= 0.632


# the row estimates of every node of each plan as the tree before the
# two-column estimate gave them (EXPLAIN's `rows`, planning only, SF 10):
# none of these queries compares two columns of one row
PLANNED_ROWS = {
    1: [6.0, 6.0, 6.0, 6.0, 57861386.13861386, 57861386.13861386, 60000000.0],
    3: [
        10.0, 10.0, 4702084.1892921105, 4702084.1892921105,
        4702084.1892921105, 4702084.1892921105, 32245544.554455448,
        60000000.0, 2187318.0873180875, 7291060.291060291, 15000000.0,
        300000.0, 1500000.0],
    5: [
        25.0, 25.0, 25.0, 25.0, 72848.23284823284, 72848.23284823284,
        364241.1642411642, 364241.1642411642, 9106029.106029106, 60000000.0,
        2276507.2765072766, 2276507.2765072766, 15000000.0, 1500000.0,
        100000.0, 25.0, 1.0, 5.0],
    6: [1.0, 1.0, 1.0, 60.0, 60.0, 60000000.0],
    18: [
        100.0, 100.0, 18000000.0, 18000000.0, 18000000.0, 18000000.0,
        60000000.0, 60000000.0, 60000000.0, 15000000.0, 15000000.0, 1500000.0,
        4500000.0, 4500000.0, 15000000.0, 60000000.0, 60000000.0],
}


@pytest.mark.parametrize("query", sorted(PLANNED_ROWS))
def test_plans_without_a_two_column_comparison_are_unchanged(query):
    s = tpch_session(10.0)
    plan = s.plan(QUERIES[query][0])
    est = annotate(plan, s.metadata)
    assert [est[id(n)]["rows"] for n in _walk(plan)] == \
        pytest.approx(PLANNED_ROWS[query])


def test_q21_refuses_a_program_without_the_residual_mark_join(
        q21, monkeypatch):
    from trino_tpu.exec import local

    q21._refuse_a_program_that_cannot_hold_the_cell()   # this program: silent
    monkeypatch.setattr(local, "OP_COUNTERS", tuple(
        c for c in local.OP_COUNTERS if c != "residualSemiJoins"))
    with pytest.raises(SystemExit, match="streams Q21"):
        q21._refuse_a_program_that_cannot_hold_the_cell()


def test_the_semi_work_slots_reader():
    reader = bench_module("layers", "semi_work_slots_per_query")
    assert reader.read({"setup_profiles": [{}, {"joinSlots": 5}]}) is None
    profiles = [{"semiWorkSlots": 9}, {"semiWorkSlots": 4}, {}]
    assert reader.read({"setup_profiles": profiles}) == 4
