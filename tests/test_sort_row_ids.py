"""The sorts of ops/join.py and ops/aggregation.py carry int32 row ids as the
last key of an unstable sort (XLA:TPU compiles a sort by the 32-bit words it
carries): same answers as the stable int64 form they replace."""
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops import aggregation as agg
from trino_tpu.ops import join


def test_row_ids_are_int32_where_the_count_allows():
    assert join.row_ids(8).dtype == jnp.int32
    assert np.array_equal(np.asarray(join.row_ids(5)), np.arange(5))


@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("dtype", [np.int64, np.int32])
@pytest.mark.parametrize("seed", [0, 1])
def test_merge_rank_is_searchsorted(side, dtype, seed):
    rng = np.random.default_rng(seed)
    build = np.sort(rng.integers(-50, 50, 300).astype(dtype))   # many ties
    probe = rng.integers(-60, 60, 1000).astype(dtype)
    got = join.merge_rank(jnp.asarray(build), jnp.asarray(probe), side)
    assert got.dtype == jnp.int64
    assert np.array_equal(np.asarray(got), np.searchsorted(build, probe, side))


def test_merge_rank_keeps_the_extremes_of_int64():
    build = np.array([-2**63, -5, 7, 2**63 - 1, 2**63 - 1], dtype=np.int64)
    probe = np.array([2**63 - 1, -2**63, 0, 7], dtype=np.int64)
    for side in ("left", "right"):
        got = join.merge_rank(jnp.asarray(build), jnp.asarray(probe), side)
        assert np.array_equal(np.asarray(got), np.searchsorted(build, probe, side))


def test_sort_live_first_orders_as_the_stable_sort():
    rng = np.random.default_rng(2)
    n = 500
    kv = rng.integers(0, 40, n).astype(np.int64)
    live = rng.random(n) < 0.7
    kv[~live] = join._SENTINEL
    kv[:3], live[:3] = join._SENTINEL, True     # a live key at the sentinel
    keys, perm = join._sort_live_first(jnp.asarray(kv), jnp.asarray(live), n)
    want = np.lexsort((np.arange(n), ~live, kv))
    assert perm.dtype == jnp.int64
    assert np.array_equal(np.asarray(perm), want)
    assert np.array_equal(np.asarray(keys), kv[want])


def test_probe_counts_over_duplicate_build_keys():
    rng = np.random.default_rng(3)
    bk = rng.integers(0, 30, 200).astype(np.int64)
    bsel = rng.random(200) < 0.8
    pk = rng.integers(-5, 35, 400).astype(np.int64)
    ones = lambda n: jnp.ones(n, dtype=bool)   # noqa: E731
    src = join.build_multi((jnp.asarray(bk), ones(200)), jnp.asarray(bsel))
    counts, lo = join.probe_counts(src, (jnp.asarray(pk), ones(400)), ones(400))
    live = np.sort(bk[bsel])
    assert np.array_equal(
        np.asarray(counts),
        np.searchsorted(live, pk, "right") - np.searchsorted(live, pk, "left"))
    hit = np.asarray(counts) > 0
    assert np.array_equal(np.asarray(lo)[hit], np.searchsorted(live, pk, "left")[hit])


def test_sort_group_ids_keeps_the_rows_order_inside_a_group():
    rng = np.random.default_rng(4)
    n = 600
    k = rng.integers(0, 25, n).astype(np.int64)
    sel = rng.random(n) < 0.9
    perm, gid, ngroups, sel_sorted, same_run = agg.sort_group_ids(
        [(jnp.asarray(k), jnp.ones(n, dtype=bool))], jnp.asarray(sel), 64)
    coll = agg.run_collisions(
        [(jnp.asarray(k)[perm], jnp.ones(n, dtype=bool))], same_run)
    perm, gid = np.asarray(perm), np.asarray(gid)
    assert perm.dtype == np.int64 and sorted(perm) == list(range(n))
    assert int(ngroups) == len(set(k[sel])) and int(coll) == 0
    live = sel[perm]
    assert np.array_equal(np.asarray(sel_sorted), live)
    assert not live[live.sum():].any()               # unselected rows last
    for g in range(int(ngroups)):
        rows = perm[live & (gid == g)]
        assert len(set(k[rows])) == 1 and list(rows) == sorted(rows)


@pytest.mark.parametrize("cap", [8, 64])
def test_sorted_segments_ranges(cap):
    gid = np.sort(np.random.default_rng(5).integers(0, cap, 300)).astype(np.int64)
    seg = agg.SortedSegments(jnp.asarray(gid), cap)
    want = np.bincount(gid, minlength=cap)
    assert np.array_equal(np.asarray(seg.counts_all), want)
    v = np.arange(300, dtype=np.int64)
    assert np.array_equal(np.asarray(seg.sum(jnp.asarray(v))),
                          np.bincount(gid, weights=v, minlength=cap).astype(np.int64))
