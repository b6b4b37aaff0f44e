"""A group's first live row read off the sorted run (`SortedSegments.first`)
against the scatter-min of masked row ids it replaces (`_seg_min`): the same
row, so `arbitrary` answers alike whichever lowering serves it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops import aggregation as agg
from trino_tpu.ops.aggregation import AggSpec


def _sorted_ids(runs, cap, dead):
    """Sorted group ids as `sort_group_ids` hands them out: `runs[g]` rows of
    group g, then `dead` unselected rows that carry `cap - 1` and sort last."""
    gid = np.repeat(np.arange(len(runs)), runs)
    gid = np.concatenate([gid, np.full(dead, cap - 1)]).astype(np.int64)
    sel = np.arange(gid.shape[0]) < gid.shape[0] - dead
    return gid, sel


def _nulls(n, every):
    return (np.arange(n) % every) != 0


RNG = np.random.default_rng(36)

# name -> (runs, cap, dead rows, validity of the value lane)
CASES = {
    "nulls_inside_a_run": ([4, 3, 5], 8, 2, lambda n: _nulls(n, 2)),
    "a_run_with_no_live_row": (
        [3, 4, 2], 8, 1,
        lambda n: ~np.isin(np.arange(n), [3, 4, 5, 6])),
    "empty_groups": ([2, 0, 0, 3, 0, 1], 16, 3, lambda n: np.ones(n, bool)),
    "ngroups_equals_cap": ([2, 1, 3, 2], 4, 5, lambda n: _nulls(n, 3)),
    "last_group_all_null_beside_dead_rows": (
        [2, 2, 2, 3], 4, 4, lambda n: np.arange(n) < 6),
    "no_selected_row": ([], 8, 7, lambda n: np.ones(n, bool)),
    "n_not_a_power_of_two": (
        list(RNG.integers(0, 9, 37)), 64, 11, lambda n: RNG.random(n) < 0.5),
    "n_is_one": ([1], 4, 0, lambda n: np.ones(n, bool)),
    "n_is_one_and_dead": ([], 4, 1, lambda n: np.ones(n, bool)),
    "n_is_one_and_null": ([1], 1, 0, lambda n: np.zeros(n, bool)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_first_is_the_row_the_scatter_min_picks(case):
    runs, cap, dead, validity = CASES[case]
    gid, sel = _sorted_ids(runs, cap, dead)
    n = gid.shape[0]
    live = sel & validity(n)
    g, lv = jnp.asarray(gid), jnp.asarray(live)
    want = np.asarray(agg._seg_min(
        jnp.where(lv, jnp.arange(n, dtype=jnp.int64), n), g, cap))
    row, has = agg.SortedSegments(g, cap).first(lv)
    row, has = np.asarray(row), np.asarray(has)
    assert row.dtype == np.int32            # one 32-bit plane on the chip
    np.testing.assert_array_equal(has, want < n)
    np.testing.assert_array_equal(row[has], want[has])
    # a group that has a live row reads one of its own, and a live one
    assert (gid[row[has]] == np.flatnonzero(has)).all() and live[row[has]].all()


@pytest.mark.parametrize("n", [1, 1023, 1024, 1025, 5000, 1024 * 1024 + 7])
def test_blocked_suffix_minimum_is_the_cumulative_one(n):
    """One block, a padded last block, and two levels of blocks."""
    v = jnp.asarray(RNG.integers(0, 2**31 - 1, n), jnp.int32)
    got = agg._suffix_min(v)
    assert got.dtype == v.dtype and got.shape == v.shape
    np.testing.assert_array_equal(got, jax.lax.cummin(v, reverse=True))


def _value_lane(kind, n):
    if kind == "int64":
        return jnp.asarray(RNG.integers(-2**40, 2**40, n), jnp.int64)
    if kind == "int32_code":
        return jnp.asarray(RNG.integers(0, 1000, n), jnp.int32)
    return jnp.asarray(RNG.random(n) < 0.5)


@pytest.mark.parametrize("lane", ["int64", "int32_code", "boolean"])
@pytest.mark.parametrize("case", ["nulls_inside_a_run", "ngroups_equals_cap",
                                  "a_run_with_no_live_row",
                                  "n_not_a_power_of_two"])
def test_arbitrary_accumulates_and_merges_alike_with_and_without_runs(
        case, lane):
    runs, cap, dead, validity = CASES[case]
    gid, sel = _sorted_ids(runs, cap, dead)
    n = gid.shape[0]
    g, s = jnp.asarray(gid), jnp.asarray(sel)
    lanes = {"x": (_value_lane(lane, n), jnp.asarray(validity(n)))}
    specs = [AggSpec("arbitrary", "x", "a")]
    seg = agg.SortedSegments(g, cap)

    plain = agg.accumulate(specs, lanes, g, s, cap)
    by_run = agg.accumulate(specs, lanes, g, s, cap, seg=seg)
    assert sorted(by_run) == sorted(plain) == ["a$val", "a$valid"]
    for name in plain:
        assert by_run[name].dtype == plain[name].dtype
        np.testing.assert_array_equal(by_run[name], plain[name])

    # FINAL step: the same rows as shipped partial state, some of it empty
    acc = {"a$val": (lanes["x"][0], jnp.ones(n, bool)),
           "a$valid": (lanes["x"][1].astype(jnp.int64), jnp.ones(n, bool))}
    plain = agg.merge_accumulators(specs, acc, g, s, cap)
    by_run = agg.merge_accumulators(specs, acc, g, s, cap, seg=seg)
    for name in plain:
        assert by_run[name].dtype == plain[name].dtype
        np.testing.assert_array_equal(by_run[name], plain[name])


def test_final_keys_off_the_run_heads_equal_the_scattered_ones():
    """`group_keys_output(starts=)` over a FINAL step's sorted ids: present
    groups read the key their first selected row carries."""
    runs, cap, dead, _ = CASES["ngroups_equals_cap"]
    gid, sel = _sorted_ids(runs, cap, dead)
    n = gid.shape[0]
    g, s = jnp.asarray(gid), jnp.asarray(sel)
    keys = [(jnp.asarray(gid * 10 + 1), jnp.asarray(_nulls(n, 4)))]
    (pv, pok), = agg.group_keys_output(keys, g, s, cap)
    (rv, rok), = agg.group_keys_output(
        keys, g, s, cap, starts=agg.SortedSegments(g, cap).starts)
    np.testing.assert_array_equal(rok, pok)
    np.testing.assert_array_equal(rv, pv)
