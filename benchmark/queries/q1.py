"""TPC-H Q1 (pricing summary report): one scan, a date filter that keeps
nearly every row, and eight aggregates over four groups.  Substitution
parameter: cl.2.4.1.3."""
import numpy as np

from _rows import days, fold, scaled, total

TABLES = {"lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                       "l_extendedprice", "l_discount", "l_tax", "l_shipdate"]}


# the spec's range, inclusive; a workload file may narrow it ("parameters")
RANGES = {"delta": [60, 120]}


def draw(rng, ranges):
    lo, hi = ranges["delta"]
    return {"delta": int(rng.integers(lo, hi + 1))}


def sql(p):
    return (
        "select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,\n"
        "       sum(l_extendedprice) as sum_base_price,\n"
        "       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,\n"
        "       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,\n"
        "       avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,\n"
        "       avg(l_discount) as avg_disc, count(*) as count_order\n"
        "from lineitem\n"
        "where l_shipdate <= date '1998-12-01' - interval '%(delta)d' day\n"
        "group by l_returnflag, l_linestatus\n"
        "order by l_returnflag, l_linestatus\n" % p
    )


def _avg(tot, count, shift):
    """avg of scaled ints at `shift` more digits, rounded half up."""
    num = tot * 10 ** shift
    return (2 * num + count) // (2 * count)


def reference(data, sf, params, acc=None):
    def part(v):
        out = []
        for p in params:
            m = v["l_shipdate"] <= days("1998-12-01") - p["delta"]
            qty, ext = v["l_quantity"][m], v["l_extendedprice"][m]
            disc, tax = v["l_discount"][m], v["l_tax"][m]
            if acc is not None:
                qty, ext, disc, tax = (x.astype(acc) for x in (qty, ext, disc, tax))
            disc_price = ext * (100 - disc)
            charge = disc_price * (100 + tax)
            key = v["l_returnflag"][m] * 2 + v["l_linestatus"][m]
            groups = {}
            for k in np.unique(key):
                g = key == k
                groups[int(k)] = [total(x[g], acc) for x in
                                  (qty, ext, disc_price, charge, disc)] + [int(g.sum())]
            out.append((groups, len(m)))
        return out

    parts = data.map_lineitem(sf, TABLES["lineitem"], part)
    rows = {"lineitem": sum(p[0][1] for p in parts)}
    answers = []
    for i in range(len(params)):
        ans = []
        for k in sorted({k for p in parts for k in p[i][0]}):
            cols = [p[i][0][k] for p in parts if k in p[i][0]]
            q, e, dp, ch, d = (fold((c[x] for c in cols), acc) for x in range(5))
            n = sum(c[5] for c in cols)
            ans.append((data.RETURN_FLAGS[k // 2], data.LINE_STATUS[k % 2],
                        q, e, dp, ch, _avg(q, n, 4), _avg(e, n, 4), _avg(d, n, 4), n))
        answers.append(ans)
    return answers, rows


def check(rows, ref):
    return [tuple(r[:2]) + tuple(scaled(x) for x in r[2:]) for r in rows] == ref
