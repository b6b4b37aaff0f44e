"""Semi and anti joins whose correlation carries a residual predicate (a
mark join: `EXISTS (... where f.k = s.k and f.c <> s.c)`) against sqlite,
over small tables with duplicate keys, NULL keys and NULL residual columns.
Each case names the lowering it takes in `exec/local._semi_residual`:
`extremes` (one comparison of a filtering column with a source column: the
key's least and greatest value decide) or `pairs` (any other residual: the
(source, filtering) pairs are expanded and the residual checked on each)."""
import sqlite3

import pytest

from oracle import assert_rows_match
from trino_tpu.exec import local
from trino_tpu.session import Session

S_ROWS = [
    (1, 10, 1), (1, 11, 2), (2, 20, 3), (2, None, 4), (3, 30, 5),
    (None, 10, 6), (4, 40, 7), (5, 50, 8), (6, 60, 9), (7, 70, 10),
]
F_ROWS = [
    (1, 10, 1), (1, 10, 5), (1, 12, 2),       # key 1: values 10, 10, 12
    (2, 20, 3), (2, 20, 4),                   # key 2: one value, twice
    (3, None, 9), (3, 30, 1),                 # key 3: a NULL value
    (4, None, 2),                             # key 4: NULL values alone
    (None, 10, 3), (None, 50, 7),             # NULL keys never match
    (5, 49, 1), (5, 51, 8),                   # key 5 brackets 50
    (6, 60, 2),                               # key 6: the value itself
]


def _load(conn_rows):
    s = Session(config={"compile_cache": False, "result_cache": False})
    s.create_catalog("memory", "memory", {})
    conn = sqlite3.connect(":memory:")
    for name, data in conn_rows:
        key = "integer" if name.endswith("m") else "bigint"
        s.execute("create table %s (k %s, c bigint, x bigint)" % (name, key))
        conn.execute("create table %s (k integer, c integer, x integer)" % name)
        values = ", ".join(
            "(%s)" % ", ".join("null" if v is None else str(v) for v in r)
            for r in data)
        s.execute("insert into %s values %s" % (name, values))
        conn.executemany("insert into %s values (?, ?, ?)" % name, data)
    return s, conn


WIDE = 2**40    # keys or values past int32: the int64 sort words


def _wide(rows, columns):
    return [tuple(v if v is None or i not in columns else v + WIDE
                  for i, v in enumerate(r)) for r in rows]


@pytest.fixture(scope="module")
def tables():
    """s/f; sw/fw with keys and values past int32; sm/fm with an integer
    key and values past int32."""
    return _load([("s", S_ROWS), ("f", F_ROWS),
                  ("sw", _wide(S_ROWS, (0, 1))), ("fw", _wide(F_ROWS, (0, 1))),
                  ("sm", _wide(S_ROWS, (1,))), ("fm", _wide(F_ROWS, (1,)))])


CASES = [
    ("extremes", "exists", "f.c <> s.c"),
    ("extremes", "not exists", "f.c <> s.c"),
    ("extremes", "exists", "f.c < s.c"),
    ("extremes", "not exists", "s.c <= f.c"),
    ("extremes", "exists", "f.c >= s.c"),
    ("extremes", "not exists", "f.c > s.c"),
    ("extremes", "exists", "f.c <> s.c and f.x > 2"),
    ("extremes", "not exists", "s.c <> f.c and f.x < 5"),
    ("pairs", "exists", "f.c + 1 <> s.c"),
    ("pairs", "not exists", "f.c + f.x > s.c"),
    ("pairs", "exists", "f.c <> s.c and f.x <> s.x"),
    ("pairs", "not exists", "(f.c <> s.c or f.x = s.x)"),
    ("pairs", "exists", "f.x = s.x and f.c < s.c"),
]


@pytest.mark.parametrize("wide", ["", "w", "m"])
@pytest.mark.parametrize("form,kind,residual", CASES)
def test_a_residual_mark_join_equals_sqlite(tables, monkeypatch, form, kind,
                                            residual, wide):
    s, conn = tables
    ctx = local.LocalExecutor.trace_ctx_cls
    real = ctx._extreme_residual
    seen = []

    def spy(self, node, src, filt):
        out = real(self, node, src, filt)
        seen.append("pairs" if out is None else "extremes")
        return out

    monkeypatch.setattr(ctx, "_extreme_residual", spy)
    sql = ("select s.k, s.c, s.x from s where %s "
           "(select * from f where f.k = s.k and %s) order by s.x"
           % (kind, residual))
    if wide:
        sql = sql.replace(" s ", " s%s s " % wide).replace(
            " f ", " f%s f " % wide)
    got = s.execute(sql).to_pylist()
    assert_rows_match(got, conn.execute(sql).fetchall(), ordered=True)
    prof = s.last_kernel_profile
    assert prof["residualSemiJoins"] == 1 and prof["semiWorkSlots"] > 0
    causes = prof["summary"]["compilesByCause"]
    if form == "pairs" or not wide:
        assert seen == [form] and set(causes) == {"first_compile"}
    else:
        # int32 sort words cannot hold the keys: one retrace at int64
        assert seen == [form, form] and sum(causes.values()) == 2
