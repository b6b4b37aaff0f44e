"""The sort group-by verifies its hash runs on SORTED key lanes
(`ops/aggregation.run_collisions`): the rows are permuted once, by the
caller (`exec/local._TraceCtx._group_sort`), and a run's neighbours are
compared by a one-row shift.  The count is the one the gathering formula
gave (kept here as the reference), on every kind of key."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops import aggregation as agg
from trino_tpu.parallel.mesh_executor import MeshExecutor, default_mesh
from trino_tpu.session import Session

NAN = float("nan")
# limbs of a two-limb (wide) key: equal low limbs under different high ones
WIDE = np.array([[7, 0], [7, 0], [7, 1], [8, 1], [8, 1], [7, 0], [0, 0],
                 [0, 0], [5, 5], [5, 5], [9, 9], [9, 8]], dtype=np.int64)
ALL = np.ones(12, dtype=bool)
# rows 2 and 9 are dead in every case and hold keys unlike their neighbours'
SEL = ~np.isin(np.arange(12), [2, 9])

# name -> [(values, validity)] of 12 rows
KEYS = {
    "int64": [(np.array([3, 3, -99, 3, 2**62, 2**62, -2**63, -2**63, 0, 77,
                         0, 1], dtype=np.int64), ALL)],
    "int32_date": [(np.array([9204, 9204, 1, 9205, 9205, 9204, 9204, 0, 0,
                              -1, 0, 0], dtype=np.int32), ALL)],
    "float_nan_and_zeros": [(np.array(
        [NAN, NAN, 5.0, NAN, 0.0, -0.0, 0.0, 5e-324, 1.5, 2.5, 1.5,
         -1.5]), ALL)],
    "wide_two_limbs": [(WIDE, ALL)],
    "null_against_values": [(np.array([4, 4, 4, 4, 4, 4, 0, 0, 6, 6, 6, 6],
                                      dtype=np.int64),
                             np.array([1, 0, 1, 1, 0, 1, 1, 0, 1, 1, 0, 0],
                                      dtype=bool))],
    # what lies under a NULL never counts: NULL equals NULL
    "null_against_null": [(np.arange(12, dtype=np.int64),
                           np.array([0, 0, 1, 0, 0, 1, 0, 0, 0, 1, 0, 0],
                                    dtype=bool))],
}
KEYS["mixed_tuple"] = [lane for name in (
    "int64", "int32_date", "float_nan_and_zeros", "wide_two_limbs",
    "null_against_values") for lane in KEYS[name]]


def _canon(v, ok):
    """One row's key as python compares it under the engine's rule."""
    if not ok:
        return None
    if np.ndim(v):
        return tuple(int(x) for x in v)
    if isinstance(v, (float, np.floating)):
        if math.isnan(v):
            return "nan"
        return 0.0 if abs(v) < 2.2250738585072014e-308 else float(v)
    return int(v)


def _tuples(keys):
    return [tuple(_canon(v[i], ok[i]) for v, ok in keys) for i in range(12)]


def _gathering_collisions(key_lanes, perm, same_run):
    """`sort_group_ids`' verification as it stood before the rows were
    permuted once: both neighbours of every key lane gathered by `perm`."""
    n = perm.shape[0]
    prev = jnp.concatenate([perm[:1], perm[:-1]])
    all_eq = jnp.ones(n, dtype=bool)
    for v, ok in key_lanes:
        okp, okq = ok[perm], ok[prev]
        vals_eq = jnp.ones(n, dtype=bool)
        for bits in agg._key_bit_lanes(v):
            vals_eq = vals_eq & (bits[perm] == bits[prev])
        all_eq = all_eq & (okp == okq) & (~okp | vals_eq)
    return jnp.sum(same_run & ~all_eq)


@pytest.mark.parametrize("locator", ["constant", "real"])
@pytest.mark.parametrize("kind", sorted(KEYS))
def test_collisions_on_sorted_lanes_equal_the_gathering_formula(
        kind, locator, monkeypatch):
    if locator == "constant":   # every live row in one hash run
        monkeypatch.setattr(
            agg, "_group_hash",
            lambda key_lanes, salt: jnp.zeros(12, dtype=jnp.int64))
    lanes = [(jnp.asarray(v), jnp.asarray(ok)) for v, ok in KEYS[kind]]
    perm, gid, ngroups, sel_sorted, same_run = agg.sort_group_ids(
        lanes, jnp.asarray(SEL), 16)
    got = int(agg.run_collisions(
        [(v[perm], ok[perm]) for v, ok in lanes], same_run))
    assert got == int(_gathering_collisions(lanes, perm, same_run))
    assert np.array_equal(np.asarray(sel_sorted), SEL[np.asarray(perm)])
    live = [t for t, s in zip(_tuples(KEYS[kind]), SEL) if s]
    if locator == "constant":
        # live rows keep their order: each one unlike the live row above
        assert np.array_equal(np.asarray(perm)[:10], np.flatnonzero(SEL))
        assert got == sum(a != b for a, b in zip(live, live[1:])) > 0
        assert int(ngroups) == 1
    else:
        assert got == 0 and int(ngroups) == len(set(live))


def _weak_at_salt_0(monkeypatch):
    real, salts = agg._group_hash, []

    def weak_then_real(key_lanes, salt):
        salts.append(salt)
        if salt == 0:
            n = key_lanes[0][0].shape[0]
            return jnp.zeros(n, dtype=jnp.int64)
        return real(key_lanes, salt)

    monkeypatch.setattr(agg, "_group_hash", weak_then_real)
    return salts


ROWS = [  # k bigint, d date, f double, w decimal(38,2), v bigint
    (1, "1995-03-15", "1.5e0", "12345678901234567890123456.78", 1),
    (1, "1995-03-15", "1.5e0", "12345678901234567890123456.78", 2),
    (1, "1995-03-16", "1.5e0", "12345678901234567890123456.79", 3),
    (1, "1995-03-16", "1.5e0", "12345678901234567890123456.78", 4),
    (None, None, None, None, 5),
    (None, None, None, None, 6),
    (2, None, "0e0", None, 7),
    (2, None, "-0e0", None, 8),
    (None, "1995-03-15", "1.5e0", "1.00", 9),
    (2, None, "2.5e0", None, 10),
]


def _mixed_table(s, name):
    def lit(r):
        k, d, f, w, v = r
        return "(%s, %s, %s, %s, %d)" % (
            "null" if k is None else k,
            "null" if d is None else "date '%s'" % d,
            "null" if f is None else f, "null" if w is None else w, v)

    s.execute("create table %s (k bigint, d date, f double, "
              "w decimal(38,2), v bigint)" % name)
    s.execute("insert into %s values %s"
              % (name, ", ".join(lit(r) for r in ROWS)))


def test_a_query_over_mixed_keys_answers_exactly_after_the_salt_retry(
        monkeypatch):
    """Salt 0 puts every row into one hash run: `run_collisions` counts,
    the executor re-runs the fragment under salt 1, the answer is exact."""
    salts = _weak_at_salt_0(monkeypatch)
    s = Session()
    s.create_catalog("memory", "memory", {})
    _mixed_table(s, "t")
    got = s.execute(
        "select k, d, f, w, count(*), sum(v) from t group by k, d, f, w"
    ).to_pylist()
    big = "12345678901234567890123456.7"
    assert sorted(((k, d, None if f is None else abs(f),
                    None if w is None else str(w), n, v)
                   for k, d, f, w, n, v in got), key=repr) == sorted([
        (1, "1995-03-15", 1.5, big + "8", 2, 3),
        (1, "1995-03-16", 1.5, big + "9", 1, 3),
        (1, "1995-03-16", 1.5, big + "8", 1, 4),
        (None, None, None, None, 2, 11),
        (2, None, 0.0, None, 2, 15),       # -0 and +0 are one key
        (None, "1995-03-15", 1.5, "1.00", 1, 9),
        (2, None, 2.5, None, 1, 10)], key=repr)
    assert 0 in salts and max(salts) >= 1


# the rows of `a` and `b` (x bigint, y varchar-free: a nullable date), with
# duplicates and NULL rows on both sides
A = [(1, "1995-01-01"), (1, "1995-01-01"), (2, None), (2, None),
     (None, None), (None, None), (3, "1995-01-02"), (None, "1995-01-03")]
B = [(1, "1995-01-01"), (None, None), (4, None), (2, "1995-01-01"),
     (None, "1995-01-03"), (None, "1995-01-03")]
SETOPS = {
    "distinct": ("select distinct x, y from a", set(A)),
    "union": ("select x, y from a union select x, y from b",
              set(A) | set(B)),
    "intersect": ("select x, y from a intersect select x, y from b",
                  set(A) & set(B)),
    "except": ("select x, y from a except select x, y from b",
               set(A) - set(B)),
}


@pytest.fixture(scope="module")
def setop_session():
    s = Session()
    s.create_catalog("memory", "memory", {})
    for name, rows in (("a", A), ("b", B)):
        s.execute("create table %s (x bigint, y date)" % name)
        s.execute("insert into %s values %s" % (name, ", ".join(
            "(%s, %s)" % ("null" if x is None else x,
                          "null" if y is None else "date '%s'" % y)
            for x, y in rows)))
    return s


@pytest.mark.parametrize("where", ["one_chip", "mesh"])
@pytest.mark.parametrize("op", sorted(SETOPS))
def test_distinct_and_set_operations_answer_exactly_with_null_rows(
        op, where, setop_session, monkeypatch):
    """Each goes through `_group_sort` (on the mesh DISTINCT's second,
    local pass and `_setop_tag_reduce` after the repartition): one permute,
    the runs verified on the sorted lanes, NULL rows one group."""
    from trino_tpu.exec.local import _TraceCtx

    calls = []
    group_sort = _TraceCtx._group_sort

    def spy(self, lanes, keys, sel, cap):
        calls.append(type(self).__name__)
        return group_sort(self, lanes, keys, sel, cap)

    monkeypatch.setattr(_TraceCtx, "_group_sort", spy)
    sql, want = SETOPS[op]
    if where == "mesh":
        ex = MeshExecutor(setop_session.catalogs, default_mesh(4))
        rows = ex.execute(setop_session.plan(sql)).to_pylist()
        # DISTINCT dedupes before and after the repartition of its
        # sharded input
        assert calls == ["_MeshTraceCtx"] * (2 if op == "distinct" else 1)
    else:
        rows = setop_session.execute(sql).to_pylist()
        assert calls
    assert len(rows) == len(want) and set(rows) == want
