"""The compaction's row ids (`ops/filter_project.compact_indices`): the
survivors of a filter or an inner join in row order, from one single-key
sort.  `jnp.nonzero(sel, size=cap)` gave the same ids through a
scatter-add of one update per input slot, half of TPC-H Q3 on the chip.
"""
import sqlite3

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oracle import assert_rows_match, load_tpch
from tpch_sql import QUERIES, oracle_dialect
from trino_tpu.ops.filter_project import compact_indices, permute_lanes
from trino_tpu.plan import optimizer
from trino_tpu.session import tpch_session


def _mask(n, density, seed):
    return np.random.default_rng(seed).random(n) < density


CASES = {
    "density_0.01": (_mask(4096, 0.01, 1), 256),
    "density_0.3": (_mask(4096, 0.3, 2), 2048),
    "density_0.5_cap_n": (_mask(1024, 0.5, 3), 1024),
    "density_0.9": (_mask(4096, 0.9, 4), 4096),
    "none_selected": (np.zeros(2048, bool), 512),
    "all_selected": (np.ones(2048, bool), 2048),
    "more_selected_than_cap": (_mask(4096, 0.5, 5), 1024),
    "all_selected_past_cap": (np.ones(1000, bool), 128),
    "n_not_a_power_of_two": (_mask(6001, 0.2, 6), 2048),
    "n_odd_and_dense": (_mask(777, 0.7, 7), 640),
    "cap_1_one_selected": (np.arange(513) == 400, 1),
    "cap_1_many_selected": (_mask(513, 0.5, 8), 1),
    "cap_1_none_selected": (np.zeros(64, bool), 1),
    "only_row_0": (np.arange(256) == 0, 16),
    "only_last_row": (np.arange(256) == 255, 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compact_indices_is_nonzero_with_a_size(case):
    """The contract of the line it replaced: the first `cap` selected row
    numbers in row order, fill value 0 — also when more are selected than
    `cap` holds (the capacity check then re-runs the fragment)."""
    mask, cap = CASES[case]
    got = np.asarray(jax.jit(compact_indices, static_argnums=1)(
        jnp.asarray(mask), cap))
    want = np.zeros(cap, np.int64)
    rows = np.nonzero(mask)[0][:cap]
    want[:len(rows)] = rows
    assert got.shape == (cap,) and got.dtype == np.int32
    assert np.array_equal(got, want)


def test_a_compaction_lowers_to_no_scatter():
    """Row ids and the stacked row gather: a sort and gathers, and neither
    a scatter nor a 64-bit scan (`nonzero` was cumsum + bincount + an int64
    cumsum over the counters)."""
    n, cap = 1 << 16, 1 << 14

    def compact(sel, a, b):
        idx = compact_indices(sel, cap)
        return permute_lanes({"a": (a, sel), "b": (b, sel)}, idx)

    text = jax.jit(compact).lower(
        jax.ShapeDtypeStruct((n,), jnp.bool_),
        jax.ShapeDtypeStruct((n,), jnp.int64),
        jax.ShapeDtypeStruct((n,), jnp.int64),
    ).as_text()
    assert "stablehlo.sort" in text and "stablehlo.gather" in text
    assert "stablehlo.scatter" not in text
    assert "reduce_window" not in text and "cumsum" not in text
    # the old line, for the record of what is being asserted away
    old = jax.jit(lambda s: jnp.nonzero(s, size=cap, fill_value=0)[0]).lower(
        jax.ShapeDtypeStruct((n,), jnp.bool_)).as_text()
    assert "stablehlo.scatter" in old


# TPC-H Q3 with both date ranges closed, so that the filters keep under
# 60 % of their tables and the optimizer marks them for compaction
Q3_SHAPED = QUERIES[3][0].replace(
    "o_orderdate < date '1995-03-15'",
    "o_orderdate < date '1995-03-15' and o_orderdate >= date '1994-09-01'",
).replace(
    "l_shipdate > date '1995-03-15'",
    "l_shipdate > date '1995-03-15' and l_shipdate < date '1995-08-01'",
)


@pytest.fixture()
def toy_scale_compacts(monkeypatch):
    """The optimizer compacts tables of 2^20 rows and more; SF 0.01 has
    60,000."""
    monkeypatch.setattr(optimizer, "_COMPACT_MIN_ROWS", 1 << 10)


def _oracle(sql, sf=0.01):
    conn = sqlite3.connect(":memory:")
    load_tpch(conn, sf, ["customer", "orders", "lineitem"])
    return conn.execute(oracle_dialect(sql)).fetchall()


def test_q3_shaped_query_compacts_and_answers_as_the_oracle(
        toy_scale_compacts, monkeypatch):
    s = tpch_session(0.01, result_cache=False, compile_cache=False,
                     device_cpu_fallback=False)
    rows = s.execute(Q3_SHAPED).to_pylist()
    prof = s.last_kernel_profile
    assert prof.get("compactions", 0) >= 1, prof
    # input slots and capacities are rungs of the ladder, each narrower
    assert prof["compactCapacity"] < prof["compactRows"]
    assert prof["compactRows"] % 1024 == 0
    assert len(rows) == 10
    assert_rows_match(rows, _oracle(Q3_SHAPED), tol=1e-6, ordered=True)
    # without compaction: the same rows in the same order
    off = tpch_session(0.01, result_cache=False, compile_cache=False,
                       device_cpu_fallback=False)
    monkeypatch.setattr(optimizer, "_COMPACT_MIN_ROWS", 1 << 40)
    assert off.execute(Q3_SHAPED).to_pylist() == rows
    assert "compactions" not in off.last_kernel_profile


def test_a_compaction_that_overflows_reruns_wider(toy_scale_compacts,
                                                  monkeypatch):
    """More survivors than the estimate's rung holds: the ids are the first
    `cap` (no row invented), the capacity check sees the true count and the
    ladder re-runs the fragment wider: same answer, the counters of the
    last trace."""
    import dataclasses as dc

    from trino_tpu.exec.local import _TraceCtx

    seen = []
    orig = _TraceCtx._maybe_compact

    def starved(self, b, node):
        est = getattr(node, "compact_rows", None)
        if est is not None:   # a twentieth of what the optimizer reckoned
            node = dc.replace(node, compact_rows=max(1, est // 20))
            seen.append(est)
        return orig(self, b, node)

    monkeypatch.setattr(_TraceCtx, "_maybe_compact", starved)
    s = tpch_session(0.01, result_cache=False, compile_cache=False,
                     device_cpu_fallback=False)
    rows = s.execute(Q3_SHAPED).to_pylist()
    prof = s.last_kernel_profile
    assert seen
    assert prof["summary"]["compilesByCause"].get("ladder_rung", 0) >= 1
    assert_rows_match(rows, _oracle(Q3_SHAPED), tol=1e-6, ordered=True)
