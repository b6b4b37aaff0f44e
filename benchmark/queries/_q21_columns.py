"""The columns TPC-H Q21 reads that `datagen.py` and `_q5_columns.py` do not
copy, made the way the program's host generator makes them
(`trino_tpu/connectors/tpch.py`: `_Gen.lineitem_for_orders`, `_Gen.orders`,
`KEY_FORMATS`): pure functions of the row counter, through the `data`
module the reference is handed.  It imports nothing of the program;
`tests/test_benchmark_q21.py` holds it to the original."""
import numpy as np

ORDER_STATUS = ["F", "O", "P"]     # the codes of o_orderstatus


def _order_and_line(l_orderkey):
    """(order counter, line counter) of each line, from its order key and
    its place among its order's lines (a slice of `data.map_lineitem`
    holds whole orders, in order)."""
    k = np.asarray(l_orderkey, dtype=np.int64) - 1
    oj = (k // 32) * 8 + k % 32
    n = len(oj)
    head = np.r_[True, oj[1:] != oj[:-1]] if n else np.zeros(0, bool)
    first = np.maximum.accumulate(np.where(head, np.arange(n), 0))
    return oj, oj * 8 + (np.arange(n) - first)


def _order_date(data, oj):
    return data.EPOCH_1992 + data.uint_in(
        "o_orderdate", oj, 0, data.ORDER_DATE_SPAN - 1)


def l_commitdate(data, l_orderkey):
    """Days since 1970-01-01: the order date plus 30..90 days (cl.4.2.3)."""
    oj, lid = _order_and_line(l_orderkey)
    return _order_date(data, oj) + data.uint_in("l_commitdate", lid, 30, 90)


def l_receiptdate(data, l_orderkey):
    """Days since 1970-01-01: the ship date (order date plus 1..121 days)
    plus 1..30 days (cl.4.2.3)."""
    oj, lid = _order_and_line(l_orderkey)
    ship = _order_date(data, oj) + 1 + (
        data.h64("l_shipdate", lid) % np.uint64(121)).astype(np.int64)
    return ship + data.uint_in("l_receiptdate", lid, 1, 30)


def o_orderstatus(l_orderkey, l_linestatus):
    """(order keys, status codes into ORDER_STATUS) of the whole orders of a
    slice of lines: F where every line's l_linestatus is F, O where every
    one is O, else P (cl.4.2.3)."""
    key = np.asarray(l_orderkey, dtype=np.int64)
    if not len(key):
        return key, np.zeros(0, np.int32)
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    shipped = np.asarray(l_linestatus) == 0      # LINE_STATUS: F=0, O=1
    lines = np.diff(np.r_[starts, len(key)])
    done = np.add.reduceat(shipped.astype(np.int64), starts)
    status = np.where(done == lines, 0, np.where(done == 0, 1, 2))
    return key[starts], status.astype(np.int32)


def s_name(suppkeys):
    """`Supplier#` and the key in nine digits (cl.4.2.3)."""
    return ["Supplier#%09d" % k for k in np.asarray(suppkeys).tolist()]
