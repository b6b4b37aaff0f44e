"""What the benchmark measures against: the table of peaks and the functions
that count a query's rows and bytes from its configuration.  The counts are
of the work the query needs, whatever implements it, so a later change to
the program's lanes or kernels moves a share and cannot move its base."""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def peaks(device_kind):
    """Published peaks of one chip; a kind that is not listed is an error."""
    table = load_json("peaks.json")
    if device_kind not in table:
        raise KeyError(
            "no published peaks for device kind %r: add it to "
            "benchmark/peaks.json with its source" % device_kind)
    return table[device_kind]


def rows_per_query(cfg, tables):
    """Base-table rows one query reads: the configuration's row counts of
    the tables the query names."""
    return sum(cfg["tables"][t]["rows"] for t in tables)


def bytes_per_query(cfg, tables, width):
    """Bytes of the referenced columns, `width` = "min_bytes" (the narrowest
    of 1, 2, 4 or 8 bytes that holds the column's TPC-H domain) or
    "stored_bytes" (what today's lanes hold)."""
    return sum(
        cfg["tables"][t]["rows"] * cfg["tables"][t]["columns"][c][width]
        for t, cols in tables.items() for c in cols
    )
