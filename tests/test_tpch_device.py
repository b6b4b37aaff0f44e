"""Bit-parity of the on-device TPC-H generator vs the host generator.

The device path (connectors/tpch_device.py) must produce EXACTLY the
arrays the numpy path (connectors/tpch.generate) produces — splitmix64 is
pure integer math, so any divergence is a bug, not noise.

This file proves parity on the CPU backend; ``chip_smoke.py`` holds the
same generator to the host generator on real HBM at SF10.
"""
import numpy as np
import pytest

from trino_tpu.connectors import tpch, tpch_device
from trino_tpu.session import tpch_session

SF = 0.01


def _pad(cap, arr):
    out = np.zeros((cap,) + arr.shape[1:], dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


@pytest.mark.parametrize("table", sorted(tpch_device.DEVICE_COLS))
def test_device_matches_host(table):
    cols = sorted(tpch_device.DEVICE_COLS[table])
    values, dicts, count = tpch.generate(table, SF, columns=cols)
    n = tpch._counts(SF)
    base = n["orders"] if table == "lineitem" else n[table]
    cap = max(128, 1 << (count - 1).bit_length())
    got = tpch_device.device_lanes(
        table, cols, 0, base, cap, SF, count
    )
    for c in cols:
        host = _pad(cap, np.asarray(values[c]))
        dev = np.asarray(got[c][0])
        assert dev.dtype == host.dtype, (c, dev.dtype, host.dtype)
        assert np.array_equal(dev, host), (
            table, c,
            np.nonzero(dev != host)[0][:5],
            dev[:5], host[:5],
        )


def test_lineitem_split_ranges():
    """Device generation of a middle split must equal the host split."""
    num_splits = 3
    n = tpch._counts(SF)
    for split in range(num_splits):
        values, _d, count = tpch.generate(
            "lineitem", SF, split=split, num_splits=num_splits,
            columns=["l_orderkey", "l_extendedprice", "l_shipdate"],
        )
        lo = (n["orders"] * split) // num_splits
        hi = (n["orders"] * (split + 1)) // num_splits
        assert tpch_device.lineitem_count(lo, hi) == count
        cap = max(128, 1 << (count - 1).bit_length())
        got = tpch_device.device_lanes(
            "lineitem", ["l_orderkey", "l_extendedprice", "l_shipdate"],
            lo, hi, cap, SF, count,
        )
        for c in ("l_orderkey", "l_extendedprice", "l_shipdate"):
            assert np.array_equal(
                np.asarray(got[c][0]), _pad(cap, values[c])
            ), (split, c)


def test_lineitem_shared_executable_across_tiles():
    """Tiles with equal caps but different [lo, hi) must reuse ONE
    compiled generator (lo/hi are traced scalars, not baked)."""
    tpch_device._JIT_CACHE.clear()
    cols = ["l_orderkey", "l_quantity"]
    n = tpch._counts(SF)
    span = n["orders"] // 4
    cap_orders = span + 8
    cap = 1 << 17
    for t in range(3):
        lo = t * span
        cnt = tpch_device.lineitem_count(lo, lo + span)
        tpch_device.device_lanes(
            "lineitem", cols, lo, lo + span, cap, SF, cnt,
            cap_orders=cap_orders,
        )
    assert len(tpch_device._JIT_CACHE) == 1


def test_session_device_generation_end_to_end():
    """Full engine pass over device-generated scans: the session default
    (device_generation=True) must return byte-identical results to the
    host numpy generator, for scans with numeric, date, and dictionary
    columns.  This is the query-level complement of the per-array parity
    tests above — it exercises the _LazyDeviceLane plumbing, padded-cap
    generation, and dictionary merge inside exec/local.py."""
    queries = [
        # numeric + date filter over lineitem (the q6 shape)
        "select sum(l_extendedprice * l_discount) from lineitem "
        "where l_discount between 0.05 and 0.07 and l_quantity < 24",
        # dictionary-encoded group keys from the device generator
        "select l_returnflag, l_linestatus, count(*), sum(l_quantity) "
        "from lineitem group by l_returnflag, l_linestatus "
        "order by l_returnflag, l_linestatus",
        # a second table + join through device-generated keys
        "select o_orderstatus, count(*) from orders "
        "group by o_orderstatus order by o_orderstatus",
    ]
    tpch_device._JIT_CACHE.clear()
    dev = tpch_session(SF)
    host = tpch_session(SF, device_generation=False)
    for sql in queries:
        assert dev.execute(sql).to_pylist() == host.execute(sql).to_pylist(), sql
    # the device path actually engaged (otherwise this test proves nothing)
    assert tpch_device._JIT_CACHE, "device generator never compiled"
