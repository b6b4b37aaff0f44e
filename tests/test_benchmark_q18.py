"""TPC-H Q18 through the session against the benchmark's plain reference
(`benchmark/queries/q18.py` over `benchmark/datagen.py`, numpy, nothing of
the program), the reference's two extra columns against the program's
generator, and the two per-layer readers this query brought.  QUANTITY is
lowered to where rows survive at these sizes (the spec's 312 leaves none)."""
import numpy as np
import pytest

from trino_tpu.connectors import tpch
from trino_tpu.page import FormattedKeys
from trino_tpu.session import tpch_session

from oracle import bench_module


@pytest.fixture(scope="module")
def q18():
    return bench_module("queries", "q18")


@pytest.fixture(scope="module")
def datagen():
    return bench_module("datagen")


@pytest.fixture(scope="module")
def sessions():
    made = {}

    def get(sf):
        if sf not in made:
            # compile_cache off: the compiling query's profile is asserted
            made[sf] = tpch_session(sf, device_cpu_fallback=False,
                                    result_cache=False, compile_cache=False)
        return made[sf]

    return get


@pytest.mark.parametrize("sf,quantity", [
    (0.01, 200), (0.01, 250), (0.05, 200), (0.05, 250)])
def test_q18_equals_the_reference(q18, datagen, sessions, sf, quantity):
    p = {"quantity": quantity}
    (ref,), rows = q18.reference(datagen, sf, [p])
    got = sessions(sf).execute(q18.sql(p)).to_pylist()
    assert got and len(got) == min(len(ref), q18.LIMIT)
    assert q18.check(got, ref), (got[:3], ref[:3])
    assert rows == {t: tpch.generate(t, sf, columns=q18.TABLES[t][:1])[2]
                    for t in q18.TABLES}
    # exact, in the reference's order where the keys decide it
    want = ref[:q18.LIMIT]
    assert [(r[0], r[1], r[2]) for r in got] == [(w[0], w[1], w[2]) for w in want]


def test_q18_scans_are_device_generated_and_counted(q18):
    # the jit cache stays on (a warm query must trace nothing): SF 0.02 with
    # QUANTITY 220 is this test's own text, so its first execution compiles
    s = tpch_session(0.02, device_cpu_fallback=False, result_cache=False)
    first = s.execute(q18.sql({"quantity": 220})).to_pylist()
    prof = dict(s.last_kernel_profile)
    entries = {k[1]: e for k, e in s._scan_cache.entries.items()}
    assert set(entries) == set(q18.TABLES)
    for table, e in entries.items():
        assert e.get("devgen") is not None, table
        assert not [c for c, (v, _) in e["merged"].items() if hasattr(v, "dtype")]
    names = entries["customer"]["dicts"]["c_name"]
    assert isinstance(names, FormattedKeys) and len(names) == 3000
    distinct = len({r[0] for r in first})
    assert names.formatted == distinct <= q18.LIMIT   # what the page read
    assert prof["lazyDictionaryColumns"] == 1
    assert prof["sortGroupBys"] == 2 and prof["semiJoins"] == 1
    assert prof["directJoins"] == 2 and "sortJoins" not in prof
    assert "directGroupBys" not in prof
    # one trace's: the two aggregates' input slots, whatever the ladder did
    line_slots = prof["sortGroupRows"] // 2
    assert prof["sortGroupRows"] == 2 * line_slots >= 2 * 120_000 * 0.9
    assert line_slots & (line_slots - 1) == 0          # the lineitem rung
    assert 0 < prof["sortGroupCapacity"] <= prof["sortGroupRows"]
    # a warm query of the cached program traces nothing, and formats its page
    s.tracer.spans.clear()
    rows = s.execute(q18.sql({"quantity": 220})).to_pylist()
    warm = s.last_kernel_profile
    assert not any(k in warm for k in (
        "sortGroupBys", "sortGroupRows", "semiJoins", "directJoins",
        "lazyDictionaryColumns"))
    fmt = [sp for sp in s.tracer.spans if sp.name == "dictionary_format"]
    assert len(fmt) == 1 and fmt[0].attributes["rows"] == len(rows)
    assert rows == first and names.formatted == 2 * distinct


def test_a_retrace_replaces_the_counters(q18, datagen, monkeypatch):
    """A capacity-ladder rung lowers the operators again: the profile reads
    the last trace's counts, not the sum over rungs."""
    from trino_tpu.exec.local import LocalExecutor

    # no plan-time estimate: the ladder climbs from 128 groups to 15,000
    monkeypatch.setattr(LocalExecutor, "_estimate_group_capacity",
                        lambda self, plan, counts: None)
    s = tpch_session(0.01, device_cpu_fallback=False, result_cache=False,
                     compile_cache=False, group_capacity=128)
    p = {"quantity": 230}
    got = s.execute(q18.sql(p)).to_pylist()
    assert q18.check(got, q18.reference(datagen, 0.01, [p])[0][0])
    prof = s.last_kernel_profile
    assert prof["summary"]["compilesByCause"].get("ladder_rung", 0) >= 2
    assert prof["sortGroupBys"] == 2 and prof["directJoins"] == 2
    assert prof["semiJoins"] == 1 and prof["lazyDictionaryColumns"] == 1


@pytest.mark.parametrize("sql,want", [
    # ONE key straight off its whole scan: its NDV (1.5M orders), uncapped
    ("q18", 2097152),
    ("select l_orderkey, sum(l_quantity) from lineitem group by 1", 2097152),
    ("select o_custkey, count(*) from orders group by 1", 131072),
    # a filter, a join or a second key below or beside it: the capped first try
    ("q3", 262144),
    ("select l_orderkey, count(*) from lineitem where l_quantity < 5 group by 1",
     262144),
    ("select l_orderkey, l_suppkey, count(*) from lineitem group by 1, 2", 262144),
    ("select l_returnflag, l_linestatus, count(*) from lineitem group by 1, 2",
     None)])
def test_first_rung_of_the_group_capacity(q18, sql, want):
    """Planning only (SF 1 generates nothing): Q18's first rung holds its
    1.5M groups, so its set-up compiles the fragment once; Q3's stays."""
    if sql == "q18":
        sql = q18.sql({"quantity": 313})
    elif sql == "q3":
        sql = bench_module("queries", "q3").sql(
            {"segment": "BUILDING", "date": "1995-03-15"})
    s = tpch_session(1.0)
    est = s._executor()._estimate_group_capacity(s.plan(sql), {0: 8388608})
    assert est == want


def test_q18_refuses_a_program_that_scans_on_the_host(q18, monkeypatch):
    from trino_tpu.connectors import tpch_device

    q18._refuse_host_scans()                      # this program: nothing said
    cols = dict(tpch_device.DEVICE_COLS)
    cols["customer"] = cols["customer"] - {"c_name"}
    monkeypatch.setattr(tpch_device, "DEVICE_COLS", cols)
    with pytest.raises(SystemExit, match="customer.c_name on the host"):
        q18._refuse_host_scans()


@pytest.mark.parametrize("sf", [0.01, 1.0])
def test_q18_columns_equal_the_programs_generator(datagen, sf):
    cols = bench_module("queries", "_q18_columns")
    values, _, n = tpch.generate("orders", sf, columns=["o_totalprice"])
    got = cols.o_totalprice(datagen, sf)
    assert len(got) == n and np.array_equal(got, values["o_totalprice"])
    values, dicts, n = tpch.generate("customer", sf, columns=["c_custkey", "c_name"])
    pick = np.r_[0:50, n - 50:n, np.random.default_rng(3).integers(0, n, 100)]
    assert cols.c_name(values["c_custkey"][pick]) == \
        dicts["c_name"][values["c_name"][pick]].tolist()
    # a slice of the order space alone
    assert np.array_equal(cols.o_totalprice(datagen, sf, 10, 20), got[10:20])


@pytest.mark.parametrize("profiles,want", [
    ([], None),
    ([{"summary": {}}], None),                               # the parent's
    ([{"sortGroupRows": 16}, {"summary": {}}], 16),          # cold, then warm
    ([{"sortGroupRows": 8}, {}, {"sortGroupRows": 32}, {}], 32),
    ([{"sortGroupRows": 0}], 0)])
def test_sort_group_rows_reader(profiles, want):
    read = bench_module("layers", "sort_group_rows_per_query").read
    assert read({"setup_profiles": profiles}) == want


@pytest.mark.parametrize("profiles,want", [
    ([], None),
    ([{}, {"summary": {}}], None),
    ([{"summary": {"compilesByCause": {"first_compile": 1}}},
      {"summary": {"compilesByCause": {}}}], 0),
    ([{"summary": {"compilesByCause": {"first_compile": 1, "ladder_rung": 2}}},
      {"summary": {"compilesByCause": {}}},
      {"summary": {"compilesByCause": {"ladder_rung": 1}}}], 3)])
def test_capacity_retraces_reader(profiles, want):
    read = bench_module("layers", "capacity_retraces").read
    assert read({"setup_profiles": profiles}) == want


def test_q18_on_a_four_device_mesh_equals_the_reference(q18, datagen):
    """The sharded path shares `_join_batches` / `_semi_hit` (their counters
    stand in its profile too) and serves `c_name` from the same dictionary."""
    s = tpch_session(0.01, distributed=True, num_devices=4,
                     device_cpu_fallback=False, result_cache=False,
                     compile_cache=False)
    p = {"quantity": 230}
    got = s.execute(q18.sql(p)).to_pylist()
    assert got and q18.check(got, q18.reference(datagen, 0.01, [p])[0][0])
    prof = s.last_kernel_profile
    assert prof["meshProgramCache"] == "miss"
    assert prof["directJoins"] == 2 and prof["semiJoins"] == 1
