"""TPC-H Q18 (large volume customer): lineitem grouped by its order, the
orders whose quantity passes QUANTITY kept (a semi join), looked up in orders
and customer, grouped again on the order and cut to the 100 dearest.
Substitution parameter: cl.2.4.18.3."""
import decimal

import numpy as np

import _q18_columns
from _rows import date

TABLES = {
    "customer": ["c_custkey", "c_name"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"],
    "lineitem": ["l_orderkey", "l_quantity"],
}
LIMIT = 100


def _refuse_host_scans():
    """The configuration's guarantee is "scans generated in HBM".  A program
    whose device generator lacks one of TABLES' columns (`c_name`, before the
    formatting dictionary) runs Q18 all the same from a host-generated scan:
    `host_generated_scans` would say so after the window, but that program's
    set-up (the fragment compiled twice, ~190 s each on the chip's host)
    outlasts a run's time limit first.  So the cell refuses it at once, by an
    exit code of its own, as run.py refuses a machine without the chip.  The
    one thing this file reads of the program; the reference reads nothing."""
    from trino_tpu.connectors import tpch_device

    host = ["%s.%s" % (t, c) for t, cols in TABLES.items() for c in cols
            if not tpch_device.supports(t, [c])]
    if host:
        raise SystemExit("q18: this program generates %s on the host: the "
                         "configuration's guarantee (scans generated in HBM) "
                         "cannot hold" % ", ".join(host))


_refuse_host_scans()


# the spec's range; a workload file may narrow it ("parameters")
RANGES = {
    "quantity": [312, 315],    # first and last value, inclusive
}


def draw(rng, ranges):
    first, last = ranges["quantity"]
    return {"quantity": first + int(rng.integers(0, last - first + 1))}


def sql(p):
    return (
        "select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,\n"
        "       sum(l_quantity)\n"
        "from customer, orders, lineitem\n"
        "where o_orderkey in (\n"
        "        select l_orderkey from lineitem\n"
        "        group by l_orderkey having sum(l_quantity) > %(quantity)d)\n"
        "  and c_custkey = o_custkey and o_orderkey = l_orderkey\n"
        "group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice\n"
        "order by o_totalprice desc, o_orderdate\n"
        "limit %(limit)d\n" % dict(p, limit=LIMIT)
    )


def _order_sums(data, sf, acc):
    """(order key, sum of l_quantity in hundredths) of every order, and the
    line count.  Lines come in order of their order, and an order's lines
    stay in one slice: one `reduceat` a slice."""

    def part(v):
        key, qty = v["l_orderkey"], v["l_quantity"]
        if acc is not None:
            qty = qty.astype(acc)
        if not len(key):
            return key, qty, 0
        starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
        return key[starts], np.add.reduceat(qty, starts), len(key)

    parts = data.map_lineitem(sf, TABLES["lineitem"], part)
    return (np.concatenate([k for k, _, _ in parts]),
            np.concatenate([s for _, s, _ in parts]),
            sum(n for _, _, n in parts))


def _one(tables, p, acc):
    c, o, (lkey, lsum) = tables
    big = lsum > p["quantity"] * 100
    bigkey, bigsum = lkey[big], lsum[big]
    # the semi join and the join with lineitem keep the same orders; the join
    # with customer keeps an order whose customer exists
    om = np.isin(o["o_orderkey"], bigkey) & np.isin(o["o_custkey"], c["c_custkey"])
    total = o["o_totalprice"][om]
    if acc is not None:
        total = total.astype(acc)
    sums = dict(zip(bigkey.tolist(), bigsum.tolist()))
    ans = [
        (name, int(ck), int(ok), int(od), int(round(float(tp))),
         int(round(float(sums[int(ok)]))))
        for name, ck, ok, od, tp in zip(
            _q18_columns.c_name(o["o_custkey"][om]), o["o_custkey"][om],
            o["o_orderkey"][om], o["o_orderdate"][om], total)
    ]
    ans.sort(key=lambda r: (-r[4], r[3], r[2]))
    return ans   # ALL the large orders, ordered; check() cuts to the LIMIT


def reference(data, sf, params, acc=None):
    c = data.customer(sf, ["c_custkey"])
    o = data.orders(sf, ["o_orderkey", "o_custkey", "o_orderdate"])
    o["o_totalprice"] = _q18_columns.o_totalprice(data, sf)
    lkey, lsum, n_line = _order_sums(data, sf, acc)
    rows = {"customer": len(c["c_custkey"]), "orders": len(o["o_orderkey"]),
            "lineitem": n_line}
    return [_one((c, o, (lkey, lsum)), p, acc) for p in params], rows


def _hundredths(x):
    """A decimal(…, 2) of the engine (a python float where it is narrow, a
    Decimal where it is a sum) -> exact hundredths; the reference's own ints
    pass through.  A value that is no whole hundredth stays as it is, and
    compares unequal."""
    if isinstance(x, int):
        return x
    v = (x if isinstance(x, decimal.Decimal) else decimal.Decimal(repr(x))).scaleb(2)
    return int(v) if v == v.to_integral_value() else x


def check(rows, ref):
    got = [(r[0], r[1], r[2], date(r[3]), _hundredths(r[4]), _hundredths(r[5]))
           for r in rows]
    want = ref[:LIMIT]
    if got == want:
        return True
    # ORDER BY leaves ties on (o_totalprice, o_orderdate) open: then the sort
    # keys must agree in order and every row must be a true group, once
    truth = set(ref)
    return (
        [(g[4], g[3]) for g in got] == [(w[4], w[3]) for w in want]
        and all(g in truth for g in got)
        and len(set(got)) == len(got)
    )
