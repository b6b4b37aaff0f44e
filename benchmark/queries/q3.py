"""TPC-H Q3 (shipping priority): customer x orders x lineitem, a group-by on
the order and a top-10 by revenue.  Substitution parameters: cl.2.4.3.3."""
import numpy as np

from _rows import date, days, iso, scaled

TABLES = {
    "customer": ["c_custkey", "c_mktsegment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "lineitem": ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"],
}
LIMIT = 10


# the spec's ranges; a workload file may narrow one ("parameters")
RANGES = {
    "segment": ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"],
    "date": ["1995-03-01", "1995-03-31"],    # first and last day, inclusive
}


def draw(rng, ranges):
    first, last = (days(d) for d in ranges["date"])
    return {
        "segment": ranges["segment"][int(rng.integers(0, len(ranges["segment"])))],
        "date": iso(first + int(rng.integers(0, last - first + 1))),
    }


def sql(p):
    return (
        "select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,\n"
        "       o_orderdate, o_shippriority\n"
        "from customer, orders, lineitem\n"
        "where c_mktsegment = '%(segment)s'\n"
        "  and c_custkey = o_custkey and l_orderkey = o_orderkey\n"
        "  and o_orderdate < date '%(date)s' and l_shipdate > date '%(date)s'\n"
        "group by l_orderkey, o_orderdate, o_shippriority\n"
        "order by revenue desc, o_orderdate\n"
        "limit %(limit)d\n" % dict(p, limit=LIMIT)
    )


def _one(data, sf, p, acc):
    cutoff = days(p["date"])
    c = data.customer(sf, TABLES["customer"])
    cust = c["c_custkey"][c["c_mktsegment"] == data.SEGMENTS.index(p["segment"])]
    o = data.orders(sf, TABLES["orders"])
    om = (o["o_orderdate"] < cutoff) & np.isin(o["o_custkey"], cust)
    okey = o["o_orderkey"][om]
    order = np.argsort(okey, kind="stable")
    okey = okey[order]
    odate = o["o_orderdate"][om][order]
    oprio = o["o_shippriority"][om][order]

    def part(v):
        lm = v["l_shipdate"] > cutoff
        lk = v["l_orderkey"][lm]
        pos = np.searchsorted(okey, lk)
        pos[pos >= len(okey)] = 0
        hit = okey[pos] == lk if len(okey) else np.zeros(len(lk), bool)
        ext, disc = v["l_extendedprice"][lm][hit], v["l_discount"][lm][hit]
        if acc is not None:
            ext, disc = ext.astype(acc), disc.astype(acc)
        rev = ext * (100 - disc)
        pos = pos[hit]
        srt = np.argsort(pos, kind="stable")
        pos, rev = pos[srt], rev[srt]
        got = {}
        if len(pos):
            starts = np.flatnonzero(np.r_[True, pos[1:] != pos[:-1]])
            sums = np.add.reduceat(rev, starts)
            got = dict(zip(pos[starts].tolist(), sums.tolist()))
        return got, len(lm)

    groups = {}
    n_line = 0
    for got, n in data.map_lineitem(sf, TABLES["lineitem"], part):
        n_line += n
        for pos, s in got.items():   # an order's lines stay in one slice
            groups[pos] = groups.get(pos, 0) + s
    ans = [(int(okey[p]), int(round(r)), int(odate[p]), int(oprio[p]))
           for p, r in groups.items()]
    ans.sort(key=lambda r: (-r[1], r[2], r[0]))
    rows = {"customer": len(c["c_custkey"]), "orders": len(o["o_orderkey"]),
            "lineitem": n_line}
    return ans, rows   # ALL groups, ordered; check() cuts to the LIMIT


def reference(data, sf, params, acc=None):
    out = [_one(data, sf, p, acc) for p in params]
    return [a for a, _ in out], out[0][1]


def check(rows, ref):
    got = [(r[0], scaled(r[1]), date(r[2]), r[3]) for r in rows]
    want = ref[:LIMIT]
    if got == want:
        return True
    # ORDER BY leaves ties on (revenue, o_orderdate) open: then the sort
    # keys must agree in order and every row must be a true group
    truth = set(ref)
    return (
        [(g[1], g[2]) for g in got] == [(w[1], w[2]) for w in want]
        and all(g in truth for g in got)
    )
