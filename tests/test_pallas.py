"""Pallas grouped-sum kernel: bit-exactness vs segment_sum (the TPU slot of
the reference's generated bytecode; ops/pallas_kernels.py docstring).

On CPU CI the kernel runs in pallas interpret mode; on the TPU backend the
same code path is the default for small-capacity segment sums.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trino_tpu.ops.pallas_kernels import (  # noqa: E402
    MAX_GROUPS,
    grouped_count,
    grouped_sum_i64,
    seg_count_maybe,
)



@pytest.mark.parametrize("n,groups", [(1000, 4), (300_000, 12), (5000, 32)])
def test_grouped_sum_i64_exact(n, groups):
    rng = np.random.default_rng(n)
    vals = rng.integers(-(10**12), 10**12, n)
    gid = rng.integers(0, groups, n)
    got = np.asarray(
        grouped_sum_i64(
            jnp.asarray(vals), jnp.asarray(gid), groups, interpret=True
        )
    )
    exp = np.zeros(groups, dtype=np.int64)
    np.add.at(exp, gid, vals)
    assert np.array_equal(got, exp)


def test_grouped_sum_negative_and_wrap():
    # negatives + sums near int64 range: plane recombination must wrap
    # exactly like int64 addition
    vals = np.array([2**62, 2**62, -(2**61), -5, 7], dtype=np.int64)
    gid = np.array([0, 0, 0, 1, 1])
    got = np.asarray(
        grouped_sum_i64(jnp.asarray(vals), jnp.asarray(gid), 2,
                        interpret=True)
    )
    exp = np.zeros(2, dtype=np.int64)
    with np.errstate(over="ignore"):
        np.add.at(exp, gid, vals)
    assert np.array_equal(got, exp)


def test_grouped_count_exact():
    rng = np.random.default_rng(3)
    n, groups = 300_000, 9
    flags = rng.integers(0, 2, n).astype(bool)
    gid = rng.integers(0, groups, n)
    got = np.asarray(
        grouped_count(jnp.asarray(flags), jnp.asarray(gid), groups,
                      interpret=True)
    )
    exp = np.zeros(groups, dtype=np.int64)
    np.add.at(exp, gid, flags.astype(np.int64))
    assert np.array_equal(got, exp)


def test_seg_count_maybe_gates():
    # CPU backend: disabled (returns None -> XLA fallback)
    v = jnp.zeros(10, dtype=bool)
    g = jnp.zeros(10, dtype=jnp.int64)
    if jax.devices()[0].platform != "tpu":
        assert seg_count_maybe(v, g, 4) is None
    # capacity beyond the VMEM scratch bound is always rejected
    assert seg_count_maybe(v, g, MAX_GROUPS + 1) is None
