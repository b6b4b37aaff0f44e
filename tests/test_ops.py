"""Physical operator kernel tests (reference: operator/Test* unit style —
hand-built batches, direct operator invocation)."""
import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu.ops import aggregation as agg
from trino_tpu.ops import join as J
from trino_tpu.ops import sort as S
from trino_tpu.ops.aggregation import AggSpec
from trino_tpu import types as T


def lane(vals, valid=None, dtype=jnp.int64):
    v = jnp.asarray(np.array(vals), dtype=dtype)
    ok = (
        jnp.ones(v.shape, dtype=bool)
        if valid is None
        else jnp.asarray(np.array(valid, dtype=bool))
    )
    return (v, ok)


def allsel(n):
    return jnp.ones(n, dtype=bool)


# --- aggregation -------------------------------------------------------


def test_direct_group_by_sum_count():
    keys = [lane([0, 1, 0, 1, 0], dtype=jnp.int32)]
    gid, cap = agg.direct_group_ids(keys, [2])
    vals = {"x": lane([10, 20, 30, 40, 50])}
    specs = [
        AggSpec("sum", "x", "s"),
        AggSpec("count_star", None, "c"),
    ]
    accs = agg.accumulate(specs, vals, gid, allsel(5), cap)
    out = agg.finalize(specs, accs)
    s, sok = out["s"]
    c, _ = out["c"]
    assert list(np.asarray(s[:2])) == [90, 60]
    assert list(np.asarray(c[:2])) == [3, 2]


def test_group_by_null_key_is_own_group():
    keys = [lane([0, 0, 5], valid=[True, False, True], dtype=jnp.int32)]
    gid, cap = agg.direct_group_ids(keys, [8])
    vals = {"x": lane([1, 2, 4])}
    specs = [AggSpec("sum", "x", "s")]
    accs = agg.accumulate(specs, vals, gid, allsel(3), cap)
    out = agg.finalize(specs, accs)
    s, sok = out["s"]
    # group code 8 = null group
    assert int(np.asarray(s)[8]) == 2
    assert int(np.asarray(s)[0]) == 1
    assert int(np.asarray(s)[5]) == 4


def test_sum_ignores_nulls_and_empty_group_is_null():
    keys = [lane([0, 0, 1], dtype=jnp.int32)]
    gid, cap = agg.direct_group_ids(keys, [2])
    vals = {"x": lane([1, 2, 7], valid=[True, False, False])}
    specs = [AggSpec("sum", "x", "s"), AggSpec("count", "x", "c")]
    accs = agg.accumulate(specs, vals, gid, allsel(3), cap)
    out = agg.finalize(specs, accs)
    s, sok = out["s"]
    c, _ = out["c"]
    assert int(np.asarray(s)[0]) == 1
    assert list(np.asarray(sok)[:2]) == [True, False]  # group 1: all null -> NULL
    assert list(np.asarray(c)[:2]) == [1, 0]


def test_min_max_avg():
    keys = [lane([0, 0, 0, 1], dtype=jnp.int32)]
    gid, cap = agg.direct_group_ids(keys, [2])
    vals = {"x": lane([5, 1, 9, 4])}
    specs = [
        AggSpec("min", "x", "mn"),
        AggSpec("max", "x", "mx"),
        AggSpec("avg", "x", "av", T.BIGINT, T.DOUBLE),
    ]
    accs = agg.accumulate(specs, vals, gid, allsel(4), cap)
    out = agg.finalize(specs, accs)
    assert int(np.asarray(out["mn"][0])[0]) == 1
    assert int(np.asarray(out["mx"][0])[0]) == 9
    assert abs(float(np.asarray(out["av"][0])[0]) - 5.0) < 1e-9
    assert abs(float(np.asarray(out["av"][0])[1]) - 4.0) < 1e-9


def test_sort_based_grouping_multi_key():
    k1 = lane([3, 1, 3, 1, 3], dtype=jnp.int64)
    k2 = lane([0, 1, 0, 1, 1], dtype=jnp.int64)
    sel = allsel(5)
    perm, gid, ngroups, sel_sorted, same_run = agg.sort_group_ids(
        [k1, k2], sel, 8)
    assert int(ngroups) == 3
    sorted_keys = [(k1[0][perm], k1[1][perm]), (k2[0][perm], k2[1][perm])]
    assert int(agg.run_collisions(sorted_keys, same_run)) == 0
    # aggregate x by groups through the permutation
    x = jnp.asarray([10.0, 20.0, 30.0, 40.0, 50.0])
    xs = x[perm]
    specs = [AggSpec("sum", "x", "s")]
    accs = agg.accumulate(specs, {"x": (xs, jnp.ones(5, bool))}, gid, sel_sorted, 8)
    out = agg.finalize(specs, accs)
    keys_out = agg.group_keys_output(sorted_keys, gid, sel_sorted, 8)
    got = {}
    s = np.asarray(out["s"][0])
    kv1, kv2 = np.asarray(keys_out[0][0]), np.asarray(keys_out[1][0])
    for g in range(int(ngroups)):
        got[(int(kv1[g]), int(kv2[g]))] = float(s[g])
    assert got == {(1, 1): 60.0, (3, 0): 40.0, (3, 1): 50.0}


def test_partial_final_merge_roundtrip():
    """PARTIAL on two splits then FINAL merge == single-step aggregation."""
    keys_a = [lane([0, 1, 0], dtype=jnp.int32)]
    keys_b = [lane([1, 1, 2], dtype=jnp.int32)]
    xa = {"x": lane([1, 2, 3])}
    xb = {"x": lane([10, 20, 30])}
    specs = [AggSpec("sum", "x", "s"), AggSpec("avg", "x", "a", T.BIGINT, T.DOUBLE)]
    parts = []
    for keys, vals in ((keys_a, xa), (keys_b, xb)):
        gid, cap = agg.direct_group_ids(keys, [4])
        accs = agg.accumulate(specs, vals, gid, allsel(3), cap)
        parts.append((keys, accs, cap))
    # merge: concatenate accumulator rows keyed by group key value
    # (each partial has capacity 5 = domain 4 + null slot)
    key_rows = jnp.concatenate(
        [jnp.arange(5, dtype=jnp.int64), jnp.arange(5, dtype=jnp.int64)]
    )
    acc_lanes = {}
    for name in parts[0][1]:
        cat = jnp.concatenate([parts[0][1][name], parts[1][1][name]])
        acc_lanes[name] = (cat, jnp.ones(cat.shape, bool))
    gid2, cap2 = agg.direct_group_ids([(key_rows, jnp.ones(10, bool))], [4])
    merged = agg.merge_accumulators(specs, acc_lanes, gid2, allsel(10), cap2)
    out = agg.finalize(specs, merged)
    s = np.asarray(out["s"][0])
    assert s[0] == 4 and s[1] == 32 and s[2] == 30
    a = np.asarray(out["a"][0])
    assert abs(a[0] - 2.0) < 1e-9 and abs(a[1] - 32 / 3) < 1e-9 and a[2] == 30


# --- join --------------------------------------------------------------


def test_lookup_join_inner():
    # build: orders (orderkey -> custkey)
    bkey = lane([100, 200, 300])
    bcols = {"o_cust": lane([1, 2, 3])}
    src = J.build_unique(bkey, allsel(3))
    assert int(src.dup_count) == 0
    # probe: lineitems
    pkey = lane([200, 999, 100, 300])
    row, matched = J.probe(src, pkey, allsel(4))
    out = J.gather_build(bcols, row, matched)
    v, ok = out["o_cust"]
    assert list(np.asarray(matched)) == [True, False, True, True]
    got = [int(x) for x, m in zip(np.asarray(v), np.asarray(matched)) if m]
    assert got == [2, 1, 3]


def test_lookup_join_null_keys_never_match():
    bkey = lane([100, 200], valid=[True, False])
    src = J.build_unique(bkey, allsel(2))
    pkey = lane([200, 100], valid=[False, True])
    row, matched = J.probe(src, pkey, allsel(2))
    assert list(np.asarray(matched)) == [False, True]


def test_build_duplicate_detection():
    bkey = lane([5, 5, 7])
    src = J.build_unique(bkey, allsel(3))
    assert int(src.dup_count) == 1


def test_composite_key_join():
    k1, k2 = lane([1, 1, 2]), lane([10, 20, 10])
    ck = J.composite_key([k1, k2], allsel(3))
    src = J.build_unique(ck, allsel(3))
    assert int(src.dup_count) == 0
    pk = J.composite_key([lane([1, 2, 9]), lane([20, 10, 9])], allsel(3))
    row, matched = J.probe(src, pk, allsel(3))
    assert list(np.asarray(matched)) == [True, True, False]
    assert list(np.asarray(row)[:2]) == [1, 2]


# --- sort / topn / limit ----------------------------------------------


def test_sort_multi_key_desc_nulls():
    lanes = {
        "a": lane([2, 1, 2, 1], valid=[True, True, True, False]),
        "b": lane([5, 6, 7, 8]),
    }
    sel = allsel(4)
    # ORDER BY a ASC NULLS LAST, b DESC
    perm = S.sort_perm(
        [S.SortKey("a", True, False), S.SortKey("b", False)], lanes, sel
    )
    out, s2 = S.apply_perm(lanes, perm, sel)
    av, aok = out["a"]
    bv, _ = out["b"]
    assert list(np.asarray(bv)) == [6, 7, 5, 8]
    assert list(np.asarray(aok)) == [True, True, True, False]


def test_sort_desc_int64_min():
    """DESC must reverse via bitwise complement: -INT64_MIN wraps to
    itself, so negation would sort INT64_MIN first instead of last."""
    lo = np.iinfo(np.int64).min
    hi = np.iinfo(np.int64).max
    lanes = {"x": lane([lo, 5, -1, hi])}
    perm = S.sort_perm([S.SortKey("x", False)], lanes, allsel(4))
    out, _ = S.apply_perm(lanes, perm, allsel(4))
    v, _ = out["x"]
    assert list(np.asarray(v)) == [hi, 5, -1, lo]


def test_topn():
    lanes = {"x": lane([5, 3, 9, 1, 7])}
    out, sel, _ = S.topn([S.SortKey("x", False)], lanes, allsel(5), 2)
    v, _ = out["x"]
    assert list(np.asarray(v)) == [9, 7]
    assert v.shape == (2,)


def test_limit_respects_selection():
    lanes = {"x": lane([1, 2, 3, 4, 5])}
    sel = jnp.asarray(np.array([True, False, True, True, True]))
    _, s2 = S.limit(lanes, sel, 2)
    assert list(np.asarray(s2)) == [True, False, True, False, False]


def test_jit_compatibility():
    """All kernels must trace under jit with static capacities."""

    @jax.jit
    def pipeline(xv, kv):
        sel = jnp.ones(xv.shape, bool)
        keys = [(kv, sel)]
        gid, cap = agg.direct_group_ids(keys, [4])
        specs = [AggSpec("sum", "x", "s")]
        accs = agg.accumulate(specs, {"x": (xv, sel)}, gid, sel, cap)
        return agg.finalize(specs, accs)["s"][0]

    r = pipeline(jnp.arange(8, dtype=jnp.int64), jnp.arange(8, dtype=jnp.int64) % 3)
    assert int(np.asarray(r)[0]) == 0 + 3 + 6


def test_group_hash_collision_retry(monkeypatch):
    """A grouping locator collision must be detected and retried with a
    fresh salt, never silently merging distinct groups."""
    import jax.numpy as jnp

    from trino_tpu.ops import aggregation as agg_ops
    from trino_tpu.session import Session

    real = agg_ops._group_hash

    def weak_then_real(key_lanes, salt):
        if salt == 0:  # force every key into 2 buckets on the first try
            h = real(key_lanes, salt)
            return h % jnp.int64(2)
        return real(key_lanes, salt)

    monkeypatch.setattr(agg_ops, "_group_hash", weak_then_real)
    s = Session()
    s.create_catalog("memory", "memory", {})
    s.execute("create table t (k bigint, v bigint)")
    s.execute("insert into t values (1,1),(2,2),(3,3),(4,4),(1,5)")
    got = s.execute(
        "select k, count(*), sum(v) from t group by k order by k"
    ).to_pylist()
    assert got == [(1, 2, 6), (2, 1, 2), (3, 1, 3), (4, 1, 4)]


def test_f64_order_bits_matches_ieee():
    """The arithmetic f64 encoder must equal the radix-sortable transform
    of the true IEEE bit pattern (injective + order preserving), modulo
    XLA's DAZ semantics (subnormals/-0 == +0)."""
    import jax.numpy as jnp
    import numpy as np

    from trino_tpu.ops.aggregation import f64_order_bits

    rng = np.random.default_rng(5)
    vals = np.concatenate([
        rng.standard_normal(20000) * 10.0 ** rng.integers(-300, 300, 20000),
        np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, 2.0, 4.0, 0.5,
                  np.nextafter(1.0, 2.0), np.nextafter(2.0, 1.0),
                  2.2250738585072014e-308,
                  1.7976931348623157e308, -1.7976931348623157e308]),
        10.0 ** rng.uniform(-300, 308, 20000) * rng.choice([-1., 1.], 20000),
    ])
    got = np.asarray(f64_order_bits(jnp.asarray(vals)))
    bits = vals.view(np.uint64).copy()
    bits[np.isnan(vals)] = 0x7FF8000000000000
    # canonicalize what XLA cannot distinguish: -0 -> +0, subnormal -> 0
    tiny = np.abs(vals) < 2.2250738585072014e-308
    bits[tiny & ~np.isnan(vals)] = 0
    neg = (bits >> 63 == 1) & ~np.isnan(vals) & ~tiny
    exp = np.where(neg, ~bits, bits | np.uint64(1 << 63)).astype(np.uint64)
    assert np.array_equal(got, exp)
