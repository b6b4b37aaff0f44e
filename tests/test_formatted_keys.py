"""The formatting dictionary of the key-formatted varchars (`page.FormattedKeys`):
it answers what a dictionary array is asked and formats only what is read."""
import numpy as np
import pytest

from trino_tpu.connectors import tpch, tpch_device
from trino_tpu.exec.local import dict_fingerprint
from trino_tpu.page import Column, FormattedKeys, same_dictionary
from trino_tpu import types as T

N = 1000


def eager(prefix, width, first, n):
    """What the host generator's string list held before the dictionary."""
    if width:
        return [f"{prefix}{k:0{width}d}" for k in range(first, first + n)]
    return [f"{prefix}{k}" for k in range(first, first + n)]


SHAPES = [("Customer#", 9, 1), ("Supplier#", 9, 1), ("Clerk#", 9, 1),
          ("addr-c-", 0, 1), ("addr-s-", 0, 1)]


@pytest.mark.parametrize("prefix,width,first", SHAPES)
def test_equals_the_eager_list(prefix, width, first):
    d = FormattedKeys(prefix, width, first, N)
    want = eager(prefix, width, first, N)
    assert len(d) == N
    assert list(d) == want
    assert np.asarray(d).tolist() == want and np.asarray(d).dtype == object
    assert np.asarray(d, dtype=str).tolist() == want
    assert [d[i] for i in (0, 1, 499, N - 1, -1)] == [
        want[i] for i in (0, 1, 499, N - 1, -1)]


@pytest.mark.parametrize("index", [
    slice(None), slice(10, 20), slice(990, 2000), slice(0, 0), slice(5, 500, 7),
    slice(None, None, -1)])
def test_slices(index):
    d = FormattedKeys("Customer#", 9, 1, N)
    assert list(d[index]) == eager("Customer#", 9, 1, N)[index]
    if index.step in (None, 1):
        assert isinstance(d[index], FormattedKeys)   # still nothing formatted
        assert d.formatted == 0


@pytest.mark.parametrize("index", [
    [3, 1, 2], np.array([999, 0, 500, 500]), np.array([], dtype=np.int64),
    np.array([-1, -1000]), np.arange(N)[::-1], np.arange(N) % 2 == 0,
    np.array([[1, 2], [3, 4]])])
def test_index_arrays(index):
    d = FormattedKeys("Supplier#", 9, 1, N)
    want = np.array(eager("Supplier#", 9, 1, N), dtype=object)[index]
    got = d[index]
    assert got.dtype == object and got.shape == want.shape
    assert got.tolist() == want.tolist()


@pytest.mark.parametrize("index", [N, -N - 1, [0, N], np.array([-N - 1])])
def test_out_of_range_raises(index):
    with pytest.raises(IndexError):
        FormattedKeys("Clerk#", 9, 1, N)[index]


def test_reading_100_of_1_5_million_formats_100():
    d = FormattedKeys("Customer#", 9, 1, 1_500_000)
    codes = np.random.default_rng(7).integers(0, len(d), 100)
    got = d[codes]
    assert d.formatted == 100
    assert got.tolist() == ["Customer#%09d" % (k + 1) for k in codes.tolist()]
    col = Column(T.VARCHAR, codes.astype(np.int32), None, d)
    assert col.to_python() == got.tolist() and d.formatted == 200


@pytest.mark.parametrize("prefix,width,first,n,by_code", [
    ("Customer#", 9, 1, 1_500_000, True), ("Clerk#", 9, 1, 1000, True),
    ("Customer#", 9, 1, 10 ** 9, False),   # a tenth digit breaks the padding
    ("addr-c-", 0, 1, 150_000, False), ("Customer#", 9, -5, 10, False)])
def test_sorted_by_code_is_claimed_only_where_it_holds(prefix, width, first, n, by_code):
    d = FormattedKeys(prefix, width, first, n)
    assert d.sorted_by_code is by_code
    if n <= 150_000:
        order = np.argsort(np.asarray(d[: min(n, 2000)], dtype=str), kind="stable")
        assert (order.tolist() == list(range(len(order)))) is by_code


@pytest.mark.parametrize("prefix,width", [("Customer#", 9), ("addr-c-", 0)])
def test_index_of_parses_and_formats_nothing(prefix, width):
    d = FormattedKeys(prefix, width, 1, N)
    want = eager(prefix, width, 1, N)
    assert [d.index_of(s) for s in want[::37]] == list(range(N))[::37]
    for s in ("", prefix, prefix + "x", prefix + "0", prefix + str(N + 1),
              prefix + "%010d" % 5, prefix + " 5", prefix + "+5", "x" + want[3],
              want[3] + "0" * (width == 0) + " ", prefix + "5" * (width > 0)):
        assert d.index_of(s) == (want.index(s) if s in want else -1), s
    assert d.formatted == 0


def test_equality_and_fingerprint_format_nothing():
    a = FormattedKeys("Customer#", 9, 1, 1_500_000)
    b = FormattedKeys("Customer#", 9, 1, 1_500_000)
    c = FormattedKeys("Customer#", 9, 1, 150_000)
    assert same_dictionary(a, b) and not same_dictionary(a, c)
    assert dict_fingerprint({"x": a}, ["x"]) == dict_fingerprint({"x": b}, ["x"])
    assert dict_fingerprint({"x": a}, ["x"]) != dict_fingerprint({"x": c}, ["x"])
    assert a.formatted == b.formatted == c.formatted == 0
    small = FormattedKeys("Clerk#", 9, 1, 10)
    assert same_dictionary(small, np.array(eager("Clerk#", 9, 1, 10), dtype=object))
    assert not same_dictionary(small, np.array(["x"], dtype=object))


@pytest.mark.parametrize("table,col", [
    ("customer", "c_name"), ("customer", "c_address"), ("supplier", "s_name"),
    ("supplier", "s_address"), ("orders", "o_clerk")])
def test_host_and_device_generators_share_the_dictionary(table, col):
    sf = 0.01
    prefix, width, _ = tpch.KEY_FORMATS[col]
    values, dicts, count = tpch.generate(table, sf, columns=[col])
    d = dicts[col]
    assert isinstance(d, FormattedKeys) and d.formatted == 0
    assert values[col].dtype == np.int32
    assert 0 <= values[col].min() and values[col].max() < len(d)
    # every split carries the same dictionary and codes into it
    parts = [tpch.generate(table, sf, split=i, num_splits=3, columns=[col])
             for i in range(3)]
    assert all(same_dictionary(p[1][col], d) for p in parts)
    assert np.array_equal(np.concatenate([p[0][col] for p in parts]), values[col])
    # the device generator's lane is the same code
    cap = 1 << (count - 1).bit_length()
    lane = np.asarray(tpch_device.device_lanes(
        table, [col], 0, count, cap, sf, count)[col][0])
    assert lane.dtype == np.int32 and np.array_equal(lane[:count], values[col])
    # and the strings are the generator's old ones
    if col != "o_clerk":
        assert d[values[col][:50]].tolist() == eager(prefix, width, 1, 50)
    else:
        key = tpch.uint_in("o_clerk", np.arange(50, dtype=np.int64), 1, len(d))
        assert d[values[col][:50]].tolist() == ["Clerk#%09d" % k for k in key]


@pytest.fixture(scope="module")
def session():
    from trino_tpu.session import tpch_session

    return tpch_session(0.01, result_cache=False)


def test_order_by_and_min_max_take_the_code_as_the_rank(session):
    n = tpch._counts(0.01)["customer"]
    names = eager("Customer#", 9, 1, n)
    got = session.execute(
        "select c_name from customer order by c_name desc limit 5").to_pylist()
    assert [r[0] for r in got] == sorted(names, reverse=True)[:5]
    got = session.execute("select min(c_name), max(c_name) from customer").to_pylist()
    assert got == [(names[0], names[-1])]


def test_unpadded_keys_sort_as_strings(session):
    n = tpch._counts(0.01)["customer"]
    want = sorted(eager("addr-c-", 0, 1, n))
    got = session.execute(
        "select c_address from customer order by c_address limit 7").to_pylist()
    assert [r[0] for r in got] == want[:7]          # addr-c-1, addr-c-10, ...
    got = session.execute("select max(c_address) from customer").to_pylist()
    assert got == [(want[-1],)]


def test_equal_clerks_share_a_code(session):
    values, dicts, _ = tpch.generate("orders", 0.01, columns=["o_clerk"])
    clerks = dicts["o_clerk"][values["o_clerk"]]
    want = sorted(
        {c: int((clerks == c).sum()) for c in set(clerks.tolist())}.items())
    got = session.execute(
        "select o_clerk, count(*) from orders group by o_clerk "
        "order by o_clerk").to_pylist()
    assert got == want and len(got) == len(dicts["o_clerk"]) == 10


def test_a_name_predicate_reads_the_dictionary(session):
    got = session.execute(
        "select c_custkey from customer where c_name = 'Customer#000000042'"
    ).to_pylist()
    assert got == [(42,)]
    got = session.execute(
        "select count(*) from customer where c_name like 'Customer#00000001%'"
    ).to_pylist()
    assert got == [(10,)]          # the keys 10..19


@pytest.mark.parametrize("sql,want", [
    ("select substr(c_name, 10, 9), length(c_name), upper(c_name) from customer "
     "where c_custkey = 7", [("000000007", 18, "CUSTOMER#000000007")]),
    ("select c_custkey from customer where c_name in "
     "('Customer#000000003', 'Customer#000000005', 'nope') order by 1", [(3,), (5,)]),
    ("select count(*) from customer where c_name < 'Customer#000000010'", [(9,)]),
    ("select count(*) from customer where c_name >= 'Customer#000001490' "
     "and c_name <> 'Customer#000001495'", [(10,)]),
    ("select count(distinct c_name), count(distinct o_clerk) from customer, orders "
     "where c_custkey = o_custkey", [(1000, 10)]),
    ("select a.c_custkey from customer a, customer b where a.c_name = b.c_name "
     "and a.c_custkey < 3 order by 1", [(1,), (2,)]),
    ("select s_name, s_address from supplier order by s_address desc limit 2",
     [("Supplier#000000099", "addr-s-99"), ("Supplier#000000098", "addr-s-98")]),
    ("select case when c_custkey = 1 then c_name else 'other' end from customer "
     "where c_custkey < 3 order by c_custkey", [("Customer#000000001",), ("other",)])])
def test_expressions_over_a_formatted_column(session, sql, want):
    assert session.execute(sql).to_pylist() == want
