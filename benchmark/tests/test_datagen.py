"""The benchmark's copy of the generator against the program's original."""
import numpy as np
import pytest

import datagen
from trino_tpu.connectors import tpch

LINEITEM = ["l_orderkey", "l_quantity", "l_extendedprice", "l_discount", "l_tax",
            "l_returnflag", "l_linestatus", "l_shipdate"]
ORDERS = ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]
CUSTOMER = ["c_custkey", "c_mktsegment"]


@pytest.mark.parametrize("sf,split,splits", [(0.01, 0, 1), (1.0, 17, 40)])
def test_lineitem_slice_equals_the_program_s(sf, split, splits):
    want, dicts, count = tpch.generate("lineitem", sf, split, splits, LINEITEM)
    n = datagen.counts(sf)["orders"]
    got = datagen.lineitem(sf, LINEITEM, n * split // splits, n * (split + 1) // splits)
    for c in LINEITEM:
        assert got[c].dtype == want[c].dtype, c
        assert np.array_equal(got[c], want[c]), c
    assert len(got["l_orderkey"]) == count
    for c in ("l_returnflag", "l_linestatus"):
        assert list(dicts[c]) == datagen.VOCABS[c]


@pytest.mark.parametrize("sf", [0.01, 1.0])
def test_orders_and_customer_equal_the_program_s(sf):
    want, _, _ = tpch.generate("orders", sf, columns=ORDERS)
    got = datagen.orders(sf, ORDERS)
    for c in ORDERS:
        assert got[c].dtype == want[c].dtype and np.array_equal(got[c], want[c]), c
    want, dicts, _ = tpch.generate("customer", sf, columns=CUSTOMER)
    got = datagen.customer(sf, CUSTOMER)
    for c in CUSTOMER:
        assert got[c].dtype == want[c].dtype and np.array_equal(got[c], want[c]), c
    assert list(dicts["c_mktsegment"]) == datagen.SEGMENTS


def test_map_lineitem_covers_every_order_once_in_order():
    sf = 0.01
    parts = datagen.map_lineitem(sf, ["l_orderkey"], lambda v: v["l_orderkey"],
                                 chunk_orders=4000)
    whole = datagen.lineitem(sf, ["l_orderkey"], 0, datagen.counts(sf)["orders"])
    assert np.array_equal(np.concatenate(parts), whole["l_orderkey"])


def test_the_configurations_row_counts_are_the_generator_s():
    import yardstick

    for name in ("tpch_sf10", "tpch_sf1"):
        cfg = yardstick.load_json("configs", name + ".json")
        n = datagen.counts(cfg["sf"])
        assert cfg["tables"]["orders"]["rows"] == n["orders"]
        assert cfg["tables"]["customer"]["rows"] == n["customer"]
        lines = int(datagen._line_count(np.arange(n["orders"], dtype=np.int64)).sum())
        assert cfg["tables"]["lineitem"]["rows"] == lines
