"""The benchmark's own copy of the TPC-H population the engine serves.

The plain reference reads its data from here and from nowhere in the
program.  This is a copy (PR 25) of the counter-based host generator in
`trino_tpu/connectors/tpch.py` (`_Gen.customer`, `_Gen.orders`,
`_Gen.lineitem_for_orders`), cut to the columns the benchmark's queries
name: every value is a pure function of (column key, row counter), so a
slice of the order space can be made alone and in any order.  The engine's
own scans come from `connectors/tpch_device.py` in HBM; the two agree bit
for bit (`benchmark/tests/test_datagen.py` holds the copy to the original).

Decimals are scaled int64 (cents, hundredths), dates int32 days since
1970-01-01, dictionary columns int32 codes into the lists below.
"""
import concurrent.futures
import os

import numpy as np

EPOCH_1992 = 8035  # 1992-01-01 in days since 1970-01-01
# as the program has it: the 151 days are taken off twice, so o_orderdate
# ends 1998-03-04 (TPC-H cl.4.2.3 says 1998-08-02); see the configurations'
# `assumed` and PERF.md Open questions
ORDER_DATE_SPAN = 2406 - 151
CURRENT_DATE = 9298  # 1995-06-17 (dbgen's CURRENTDATE)

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
RETURN_FLAGS = ["A", "N", "R"]
LINE_STATUS = ["F", "O"]
VOCABS = {
    "c_mktsegment": SEGMENTS,
    "l_returnflag": RETURN_FLAGS,
    "l_linestatus": LINE_STATUS,
}

_MASK = 0xFFFFFFFFFFFFFFFF


def _fnv(s):
    h = 0xCBF29CE484222325
    for ch in s.encode():
        h = ((h ^ ch) * 0x100000001B3) & _MASK
    return h


def _mix64(x):
    """splitmix64 finalizer over a uint64 array it may overwrite."""
    x += np.uint64(0x9E3779B97F4A7C15)
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


def h64(key, idx):
    """Deterministic uint64 per (key, index)."""
    return _mix64(idx.astype(np.uint64) ^ np.uint64(_fnv(key)))


def uint_in(key, idx, lo, hi):
    """Uniform integer in [lo, hi], inclusive."""
    return (h64(key, idx) % np.uint64(hi - lo + 1)).astype(np.int64) + lo


def counts(sf):
    """Rows of the tables whose count the scale factor fixes."""
    return {
        "customer": max(1, int(150_000 * sf)),
        "part": max(1, int(200_000 * sf)),
        "orders": max(1, int(1_500_000 * sf)),
    }


def _orderkey(j):
    return (j // 8) * 32 + (j % 8) + 1


def _line_count(j):
    return 1 + (h64("l_count", j) % np.uint64(7)).astype(np.int64)


def _retail_price_cents(partkey):
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def customer(sf, cols):
    idx = np.arange(counts(sf)["customer"], dtype=np.int64)
    out = {}
    for c in cols:
        if c == "c_custkey":
            out[c] = idx + 1
        elif c == "c_mktsegment":
            out[c] = (h64(c, idx) % np.uint64(5)).astype(np.int32)
        else:
            raise KeyError("customer.%s is not in the benchmark's copy" % c)
    return out


def orders(sf, cols, lo=0, hi=None):
    n = counts(sf)
    j = np.arange(lo, n["orders"] if hi is None else hi, dtype=np.int64)
    out = {}
    for c in cols:
        if c == "o_orderkey":
            out[c] = _orderkey(j)
        elif c == "o_custkey":
            usable = n["customer"] - n["customer"] // 3
            i = (h64(c, j) % np.uint64(max(1, usable))).astype(np.int64)
            out[c] = 3 * (i // 2) + 1 + (i % 2)
        elif c == "o_orderdate":
            out[c] = (
                EPOCH_1992 + uint_in(c, j, 0, ORDER_DATE_SPAN - 1)
            ).astype(np.int32)
        elif c == "o_shippriority":
            out[c] = np.zeros(len(j), dtype=np.int64)
        else:
            raise KeyError("orders.%s is not in the benchmark's copy" % c)
    return out


def lineitem(sf, cols, lo, hi):
    """The lines of orders [lo, hi) of the order index space."""
    j = np.arange(lo, hi, dtype=np.int64)
    cnt = _line_count(j)
    total = int(cnt.sum())
    oj = np.repeat(j, cnt)
    starts = np.cumsum(cnt) - cnt
    lid = oj * 8 + (np.arange(total, dtype=np.int64) - np.repeat(starts, cnt))
    memo = {}

    def ship():
        if "ship" not in memo:
            odate = EPOCH_1992 + uint_in("o_orderdate", oj, 0, ORDER_DATE_SPAN - 1)
            memo["ship"] = odate + 1 + (
                h64("l_shipdate", lid) % np.uint64(121)
            ).astype(np.int64)
        return memo["ship"]

    def qty():
        if "qty" not in memo:
            memo["qty"] = uint_in("l_quantity", lid, 1, 50)
        return memo["qty"]

    out = {}
    for c in cols:
        if c == "l_orderkey":
            out[c] = _orderkey(oj)
        elif c == "l_quantity":
            out[c] = qty() * 100
        elif c == "l_extendedprice":
            npart = counts(sf)["part"]
            partkey = 1 + (h64("l_partkey", lid) % np.uint64(npart)).astype(np.int64)
            out[c] = qty() * _retail_price_cents(partkey)
        elif c == "l_discount":
            out[c] = uint_in(c, lid, 0, 10)
        elif c == "l_tax":
            out[c] = uint_in(c, lid, 0, 8)
        elif c == "l_shipdate":
            out[c] = ship().astype(np.int32)
        elif c == "l_returnflag":
            receipt = ship() + uint_in("l_receiptdate", lid, 1, 30)
            rnd = (h64(c, lid) % np.uint64(2)).astype(np.int32)
            out[c] = np.where(receipt <= CURRENT_DATE, rnd * 2, 1).astype(np.int32)
        elif c == "l_linestatus":
            out[c] = (ship() > CURRENT_DATE).astype(np.int32)
        else:
            raise KeyError("lineitem.%s is not in the benchmark's copy" % c)
    return out


def map_lineitem(sf, cols, fn, chunk_orders=250_000):
    """[fn(columns) for each slice of the order space], in order.  Slices
    bound the host memory; numpy releases the interpreter lock in its
    array passes, so a few threads share the work."""
    n = counts(sf)["orders"]
    bounds = [(lo, min(n, lo + chunk_orders)) for lo in range(0, n, chunk_orders)]
    workers = max(1, min(8, (os.cpu_count() or 2) - 1, len(bounds)))
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        return list(pool.map(lambda b: fn(lineitem(sf, cols, *b)), bounds))
