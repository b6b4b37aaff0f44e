"""TPC-H Q21 (suppliers who kept orders waiting): the suppliers of one
nation whose line was the only late one of a multi-supplier order that is
finished — lineitem read three times, once for the late lines, once in an
EXISTS and once in a NOT EXISTS, each correlated on the order key and on
`l_suppkey <>`.  Substitution parameter: cl.2.4.21.3."""
import numpy as np

import _q5_columns
import _q21_columns
from _q5_columns import NATIONS

TABLES = {
    "supplier": ["s_suppkey", "s_name", "s_nationkey"],
    "lineitem": ["l_orderkey", "l_suppkey", "l_commitdate", "l_receiptdate"],
    "orders": ["o_orderkey", "o_orderstatus"],
    "nation": ["n_nationkey", "n_name"],
}
LIMIT = 100


def _refuse_a_program_that_cannot_hold_the_cell():
    """A program that counts lineitem's lanes once for each of Q21's three
    reads and adds every read's generator temporaries estimates the
    fragment at 10.9 GB at SF 10, past the session's 8 GiB query limit, and
    streams the query in tiles: on one v5e that run died after 217 s of
    set-up, RESOURCE_EXHAUSTED loading lineitem's generator.  So the cell
    refuses such a program at once, by an exit code of its own, as `q5.py`
    does.  The mark of a program that holds Q21 resident is
    the counter that arrived with the mark join sized at plan time
    (`exec/local.OP_COUNTERS`); the one thing this file reads of the
    program, and the reference reads nothing."""
    from trino_tpu.exec import local

    if "residualSemiJoins" not in getattr(local, "OP_COUNTERS", ()):
        raise SystemExit(
            "q21: this program is from before the mark join with a residual "
            "was sized at plan time: it streams Q21 at the cell's SF in "
            "tiles, and runs out of device memory")


_refuse_a_program_that_cannot_hold_the_cell()

# the spec's range; a workload file may narrow it ("parameters")
RANGES = {
    "nation": [name for name, _ in NATIONS],
}


def draw(rng, ranges):
    names = ranges["nation"]
    return {"nation": names[int(rng.integers(0, len(names)))]}


def sql(p):
    return (
        "select s_name, count(*) as numwait\n"
        "from supplier, lineitem l1, orders, nation\n"
        "where s_suppkey = l1.l_suppkey and o_orderkey = l1.l_orderkey\n"
        "  and o_orderstatus = 'F' and l1.l_receiptdate > l1.l_commitdate\n"
        "  and exists (select * from lineitem l2\n"
        "              where l2.l_orderkey = l1.l_orderkey\n"
        "                and l2.l_suppkey <> l1.l_suppkey)\n"
        "  and not exists (select * from lineitem l3\n"
        "                  where l3.l_orderkey = l1.l_orderkey\n"
        "                    and l3.l_suppkey <> l1.l_suppkey\n"
        "                    and l3.l_receiptdate > l3.l_commitdate)\n"
        "  and s_nationkey = n_nationkey and n_name = '%(nation)s'\n"
        "group by s_name\n"
        "order by numwait desc, s_name\n"
        "limit %(limit)d\n" % dict(p, limit=LIMIT)
    )


def _one(data, sf, p, acc):
    """[(s_name, numwait)] in ORDER BY order, the first LIMIT; the table row
    counts.  By counting, slice by slice of whole orders: a late line of an
    F order of a supplier of the nation counts where its order has a line
    of another supplier and no late line of another supplier."""
    nation = RANGES["nation"].index(p["nation"])
    snat = _q5_columns.s_nationkey(data, sf)
    nsupp = len(snat)
    kind = np.int64 if acc is None else acc

    def part(v):
        key = v["l_orderkey"]
        if not len(key):
            return np.zeros(nsupp + 1, kind), 0
        supp = _q5_columns.l_suppkey(data, sf, key)
        late = (_q21_columns.l_receiptdate(data, key)
                > _q21_columns.l_commitdate(data, key)).astype(np.int64)
        _, status = _q21_columns.o_orderstatus(key, v["l_linestatus"])
        order = np.cumsum(np.r_[True, key[1:] != key[:-1]]) - 1
        lines, late_lines = np.bincount(order), np.bincount(order, late)
        # lines of each (order, supplier)
        _, pair, same = np.unique(order * (nsupp + 1) + supp,
                                  return_inverse=True, return_counts=True)
        late_same = np.bincount(pair, late)
        other = lines[order] - same[pair] > 0
        late_other = late_lines[order] - late_same[pair] > 0
        hit = ((late == 1) & (status[order] == 0) & (snat[supp - 1] == nation)
               & other & ~late_other)
        return np.bincount(supp[hit], minlength=nsupp + 1).astype(kind), len(key)

    waits = np.zeros(nsupp + 1, kind)
    n_line = 0
    for w, n in data.map_lineitem(sf, ["l_orderkey", "l_linestatus"], part):
        waits = waits + w
        n_line += n
    keys = np.flatnonzero(waits)
    counts = [int(round(float(waits[k]))) for k in keys]
    names = _q21_columns.s_name(keys)
    ans = sorted(zip(names, counts), key=lambda r: (-r[1], r[0]))[:LIMIT]
    rows = {"supplier": nsupp, "lineitem": n_line,
            "orders": data.counts(sf)["orders"], "nation": len(NATIONS)}
    return ans, rows


def reference(data, sf, params, acc=None):
    """`acc`, the control's float type, accumulates the counts: Q21 has no
    sums, and a count below 2**24 is exact in float32, so the control's
    answer equals the exact one (PERF.md)."""
    out = [_one(data, sf, p, acc) for p in params]
    return [a for a, _ in out], out[0][1]


def check(rows, ref):
    """Exact, in ORDER BY order: s_name is unique, so the keys leave no tie."""
    return [(r[0], int(r[1])) for r in rows] == ref
