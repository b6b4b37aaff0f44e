"""Bit-parity of the on-device TPC-H generator vs the host generator.

The device path (connectors/tpch_device.py) must produce EXACTLY the
arrays the numpy path (connectors/tpch.generate) produces — splitmix64 is
pure integer math, so any divergence is a bug, not noise.

This file proves parity on the CPU backend; on real HBM at SF10 the
benchmark (``benchmark/run.py``) holds the device generator's answers to
its own numpy copy of the generator.
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


# ---------------------------------------------------------------------
# lineitem_count: the block-prefix index against the brute-force hash

_B = tpch_device.LINE_COUNT_BLOCK


def _brute_lines(lo, hi):
    j = np.arange(lo, max(hi, lo), dtype=np.int64)
    return int((1 + (tpch.h64("l_count", j) % np.uint64(7)).astype(np.int64)).sum())


# (name, lo, hi, expected total or None for brute force, orders a SECOND
#  call of the same range runs through the hash)
_LINE_COUNT_CASES = [
    ("empty", 5, 5, 0, 0),
    ("empty_reversed", 9, 3, 0, 0),
    ("inside_one_block", 10, 1_000, None, 990),
    ("straddles_no_whole_block", _B - 7, _B + 9, None, 16),
    ("one_block_exact", _B, 2 * _B, None, 0),
    ("block_aligned", 3 * _B, 40 * _B, None, 0),
    ("unaligned_both_ends", 3 * _B + 17, 40 * _B + 5, None, _B - 17 + 5),
    ("aligned_lo_unaligned_hi", 0, 300 * _B + 1, None, 1),
    ("lo_beyond_built", 700 * _B + 3, 705 * _B - 3, None, 2 * _B - 6),
    ("crosses_build_chunk", 255 * _B - 1, 258 * _B + 1, None, 2),
    ("q1_sf10_tile0", 0, 7_500_000, 30_007_369, 7_500_000 % _B),
    ("q1_sf10_tile1", 7_500_000, 15_000_000, 29_990_818,
     _B - 7_500_000 % _B + 15_000_000 % _B),
]


@pytest.mark.parametrize(
    "lo,hi,total,rehashed",
    [c[1:] for c in _LINE_COUNT_CASES],
    ids=[c[0] for c in _LINE_COUNT_CASES],
)
def test_lineitem_count_index_matches_hash(lo, hi, total, rehashed):
    """Every branch of the indexed count equals the hash sum of the whole
    range; once the range is built a call hashes its edge blocks only."""
    index = tpch_device.LineCountIndex()
    if total is None:
        total = _brute_lines(lo, hi)
    first, hashed_first = index.count(lo, hi)
    assert first == total
    whole = hi // _B > -(-lo // _B)  # a whole block inside: index extended
    assert index.blocks == (hi // _B if whole else 0)
    assert hashed_first == rehashed + index.blocks * _B
    again, hashed_again = index.count(lo, hi)
    assert (again, hashed_again) == (total, rehashed)
    assert hashed_again <= 2 * (_B - 1)
    # the process-wide index (whatever it holds by now) agrees
    assert tpch_device.lineitem_count(lo, hi) == total


def test_lineitem_count_index_shorter_range_after_longer():
    """A range below what is built extends nothing: the index only grows."""
    index = tpch_device.LineCountIndex()
    index.count(0, 50 * _B)
    got, hashed = index.count(2 * _B + 1, 9 * _B + 2)
    assert got == _brute_lines(2 * _B + 1, 9 * _B + 2)
    assert hashed == _B - 1 + 2
    assert index.blocks == 50


def test_lineitem_count_index_concurrent_extension():
    """Threads asking for overlapping unbuilt ranges (the prefetch pool and
    the query thread do) get equal, correct answers, and the index they
    leave is the one a single caller would have built."""
    import sys
    import threading

    index = tpch_device.LineCountIndex()
    ranges = [(11, 600 * _B + 5), (300 * _B - 9, 900 * _B + 77)]
    want = [_brute_lines(lo, hi) for lo, hi in ranges]
    got = {}
    gate = threading.Barrier(8)

    def ask(slot, lo, hi):
        gate.wait(timeout=60)
        got[slot] = index.count(lo, hi)[0]

    threads = [
        threading.Thread(target=ask, args=(i, *ranges[i % 2]))
        for i in range(8)
    ]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert [got[i] for i in range(8)] == want * 4
    assert index.blocks == 900
    assert index.count(0, 600 * _B) == (_brute_lines(0, 600 * _B), 0)
    assert index.count(0, 900 * _B) == (_brute_lines(0, 900 * _B), 0)
