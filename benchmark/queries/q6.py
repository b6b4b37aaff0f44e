"""TPC-H Q6 (forecasting revenue change): one scan, a conjunctive filter and
a single exact decimal sum.  Substitution parameters: cl.2.4.6.3."""
from _rows import days, fold, scaled, total

TABLES = {"lineitem": ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]}


# the spec's ranges, inclusive; a workload file may narrow one ("parameters")
RANGES = {"year": [1993, 1997], "discount": [2, 9],   # hundredths: 0.02 .. 0.09
          "quantity": [24, 25]}


def draw(rng, ranges):
    return {k: int(rng.integers(lo, hi + 1)) for k, (lo, hi) in ranges.items()}


def sql(p):
    return (
        "select sum(l_extendedprice * l_discount) as revenue\n"
        "from lineitem\n"
        "where l_shipdate >= date '%(year)d-01-01'\n"
        "  and l_shipdate < date '%(year)d-01-01' + interval '1' year\n"
        "  and l_discount between 0.0%(discount)d - 0.01 and 0.0%(discount)d + 0.01\n"
        "  and l_quantity < %(quantity)d\n" % p
    )


def reference(data, sf, params, acc=None):
    """One answer per parameter set, over one pass of the host columns."""

    def part(v):
        out = []
        for p in params:
            lo, hi = days("%d-01-01" % p["year"]), days("%d-01-01" % (p["year"] + 1))
            m = (
                (v["l_shipdate"] >= lo) & (v["l_shipdate"] < hi)
                & (v["l_discount"] >= p["discount"] - 1)
                & (v["l_discount"] <= p["discount"] + 1)
                & (v["l_quantity"] < p["quantity"] * 100)
            )
            ext, disc = v["l_extendedprice"][m], v["l_discount"][m]
            if acc is not None:
                ext, disc = ext.astype(acc), disc.astype(acc)
            out.append((total(ext * disc, acc), len(v["l_shipdate"])))
        return out

    parts = data.map_lineitem(sf, TABLES["lineitem"], part)
    rows = {"lineitem": sum(p[0][1] for p in parts)}
    # sum(l_extendedprice * l_discount) at scale 4
    return [[(fold((p[i][0] for p in parts), acc),)] for i in range(len(params))], rows


def check(rows, ref):
    return [(scaled(r[0]),) for r in rows] == ref
