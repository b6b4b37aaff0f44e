"""On-device (TPU) TPC-H column generation.

Reference parity: plugin/trino-tpch generates rows IN-PROCESS during the
scan (TpchPageSourceProvider streams generator output straight into the
operator pipeline) — the data never exists anywhere else.  The TPU-native
analog generates columns directly in HBM: the connector's counter-based
design (tpch.py: every attribute is a pure function of (table, column,
row-index) via the splitmix64 finalizer) is exactly a device kernel, so
a scan's arrays materialize on-chip from a seed + row range with ZERO
host datagen and ZERO host->device transfer.

This is the scan path's equivalent of the reference's in-process
generation, not a benchmark shortcut: the QUERY program is unchanged (it
reads the same padded HBM lanes the upload path would have produced, and
the jit cache keys are identical); only the producer of those lanes
moved from numpy + a host->device upload to an XLA program.  Exact
bit-parity with the host generator is enforced by
tests/test_tpch_device.py (splitmix64 is pure integer math: jnp.uint64
and np.uint64 agree exactly).

Key-formatted varchars (c_name, s_name, the addresses, o_clerk) are device
columns too: the lane is the code `key - 1` and the dictionary a
`page.FormattedKeys`, which formats an entry when a result row reads it.
Columns whose strings are composed from hashes (phones, p_name) are not
device-generatable; a scan touching one falls back to the host generator
wholesale.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from . import tpch as H

# ---------------------------------------------------------------------
# splitmix64 core (jnp port of tpch.mix64 / h64 / uint_in — python-int
# constants converted at trace time; module-level jnp constants would
# become hidden const args, see ops/int128.py)

_M_GOLD = 0x9E3779B97F4A7C15
_M_B = 0xBF58476D1CE4E5B9
_M_C = 0x94D049BB133111EB


def _mix64(x: jnp.ndarray) -> jnp.ndarray:
    x = x + jnp.uint64(_M_GOLD)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(_M_B)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(_M_C)
    return x ^ (x >> jnp.uint64(31))


def _h64(key: str, idx: jnp.ndarray, salt: int = 0) -> jnp.ndarray:
    base = int(H._fnv(key)) ^ (salt * _M_GOLD & 0xFFFFFFFFFFFFFFFF)
    return _mix64(idx.astype(jnp.uint64) ^ jnp.uint64(base))


def _uint_in(key: str, idx, lo: int, hi: int, salt: int = 0) -> jnp.ndarray:
    return (
        _h64(key, idx, salt) % jnp.uint64(hi - lo + 1)
    ).astype(jnp.int64) + lo


def _orderkey(j: jnp.ndarray) -> jnp.ndarray:
    return (j // 8) * 32 + (j % 8) + 1


def _custkey_for_order(j: jnp.ndarray, ncust: int) -> jnp.ndarray:
    usable = ncust - ncust // 3
    i = (_h64("o_custkey", j) % jnp.uint64(max(1, usable))).astype(jnp.int64)
    return 3 * (i // 2) + 1 + (i % 2)


def _retail_price_cents(partkey: jnp.ndarray) -> jnp.ndarray:
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def _ps_suppkey(partkey: jnp.ndarray, i, nsupp: int) -> jnp.ndarray:
    return (partkey + i * (nsupp // 4 + (partkey - 1) // nsupp)) % nsupp + 1


def _line_count(j: jnp.ndarray) -> jnp.ndarray:
    return 1 + (_h64("l_count", j) % jnp.uint64(7)).astype(jnp.int64)


# ---------------------------------------------------------------------
# per-table device column generators.  Each returns values for rows
# [lo, lo+cap) masked so rows >= hi produce 0 (mirroring the host path's
# zero padding); `lo`/`hi` are TRACED scalars so every streaming tile of
# the same padded shape shares one compiled generator.

# columns the device path can produce (everything except the hash-composed
# strings); comments/names with fixed vocabularies and the key-formatted
# varchars of tpch.KEY_FORMATS are dict CODES
DEVICE_COLS: Dict[str, frozenset] = {
    "region": frozenset({"r_regionkey", "r_name", "r_comment"}),
    "nation": frozenset(
        {"n_nationkey", "n_name", "n_regionkey", "n_comment"}
    ),
    "supplier": frozenset(
        {"s_suppkey", "s_nationkey", "s_acctbal", "s_comment", "s_name",
         "s_address"}
    ),
    "customer": frozenset(
        {"c_custkey", "c_nationkey", "c_acctbal", "c_mktsegment",
         "c_comment", "c_name", "c_address"}
    ),
    "part": frozenset(
        {"p_partkey", "p_mfgr", "p_brand", "p_type", "p_size",
         "p_container", "p_retailprice", "p_comment"}
    ),
    "partsupp": frozenset(
        {"ps_partkey", "ps_suppkey", "ps_availqty", "ps_supplycost",
         "ps_comment"}
    ),
    "orders": frozenset(
        {"o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice",
         "o_orderdate", "o_orderpriority", "o_shippriority", "o_comment",
         "o_clerk"}
    ),
    "lineitem": frozenset(
        {"l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
         "l_quantity", "l_extendedprice", "l_discount", "l_tax",
         "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate",
         "l_receiptdate", "l_shipinstruct", "l_shipmode", "l_comment"}
    ),
}

_NCOMMENT = len(H.COMMENTS)


def _dict_code(key: str, idx, n: int) -> jnp.ndarray:
    return (_h64(key, idx) % jnp.uint64(n)).astype(jnp.int32)


def _base_table(table: str, cols, idx, n: Dict[str, int], sf: float):
    """Columns for non-lineitem tables at order/global index `idx`."""
    out: Dict[str, jnp.ndarray] = {}
    key = idx.astype(jnp.int64) + 1
    for c in cols:
        if c in ("r_regionkey", "n_nationkey"):
            out[c] = idx.astype(jnp.int64)
        elif c in ("r_name", "n_name"):
            out[c] = idx.astype(jnp.int32)
        elif c == "n_regionkey":
            region_of = jnp.asarray(
                np.array([r for _, r in H.NATIONS], dtype=np.int64)
            )
            out[c] = region_of[jnp.clip(idx, 0, len(H.NATIONS) - 1)]
        elif c in ("s_suppkey", "c_custkey", "p_partkey"):
            out[c] = key
        elif c in ("s_name", "s_address", "c_name", "c_address"):
            out[c] = idx.astype(jnp.int32)  # code of H.formatted_keys(c)
        elif c == "o_clerk":
            out[c] = (_uint_in(c, idx, 1, H._clerks(sf)) - 1).astype(
                jnp.int32
            )
        elif c in ("s_nationkey", "c_nationkey"):
            out[c] = _uint_in(c, idx, 0, 24)
        elif c in ("s_acctbal", "c_acctbal"):
            out[c] = _uint_in(c, idx, -99999, 999999)
        elif c == "c_mktsegment":
            out[c] = _dict_code(c, idx, 5)
        elif c == "p_mfgr":
            out[c] = _dict_code("p_mfgr", idx, 5)
        elif c == "p_brand":
            m = (_h64("p_mfgr", idx) % jnp.uint64(5)).astype(jnp.int64)
            b = (_h64("p_brand", idx) % jnp.uint64(5)).astype(jnp.int64)
            out[c] = (m * 5 + b).astype(jnp.int32)
        elif c == "p_type":
            out[c] = _dict_code(c, idx, len(H.P_TYPES))
        elif c == "p_size":
            out[c] = _uint_in(c, idx, 1, 50)
        elif c == "p_container":
            out[c] = _dict_code(c, idx, len(H.CONTAINERS))
        elif c == "p_retailprice":
            out[c] = _retail_price_cents(key)
        elif c == "ps_partkey":
            out[c] = (idx // 4).astype(jnp.int64) + 1
        elif c == "ps_suppkey":
            p = (idx // 4).astype(jnp.int64) + 1
            out[c] = _ps_suppkey(p, (idx % 4).astype(jnp.int64),
                                 n["supplier"])
        elif c == "ps_availqty":
            out[c] = _uint_in(c, idx, 1, 9999)
        elif c == "ps_supplycost":
            out[c] = _uint_in(c, idx, 100, 100000)
        elif c == "o_orderkey":
            out[c] = _orderkey(idx.astype(jnp.int64))
        elif c == "o_custkey":
            out[c] = _custkey_for_order(idx.astype(jnp.int64), n["customer"])
        elif c == "o_orderdate":
            out[c] = (
                H.EPOCH_1992
                + _uint_in("o_orderdate", idx, 0, H.ORDER_DATE_SPAN - 1)
            ).astype(jnp.int32)
        elif c == "o_totalprice":
            out[c] = _uint_in(c, idx, 100000, 50000000)
        elif c == "o_orderpriority":
            out[c] = _dict_code(c, idx, 5)
        elif c == "o_shippriority":
            out[c] = jnp.zeros(idx.shape[0], dtype=jnp.int64)
        elif c == "o_orderstatus":
            j = idx.astype(jnp.int64)
            odate = H.EPOCH_1992 + _uint_in(
                "o_orderdate", j, 0, H.ORDER_DATE_SPAN - 1
            )
            counts = _line_count(j)
            all_f = jnp.ones(j.shape[0], dtype=bool)
            all_o = jnp.ones(j.shape[0], dtype=bool)
            for ln in range(7):
                has = counts > ln
                ship = odate + 1 + (
                    _h64("l_shipdate", j * jnp.int64(8) + ln)
                    % jnp.uint64(121)
                ).astype(jnp.int64)
                f = ship <= H.CURRENT_DATE
                all_f &= ~has | f
                all_o &= ~has | ~f
            out[c] = jnp.where(
                all_f, 0, jnp.where(all_o, 1, 2)
            ).astype(jnp.int32)
        elif c.endswith("_comment"):
            out[c] = _dict_code(c, idx, _NCOMMENT)
        else:  # pragma: no cover — guarded by DEVICE_COLS
            raise KeyError(c)
    return out


def _lineitem(cols, oj, ln, n: Dict[str, int]):
    """Lineitem columns at (order index oj, line number ln)."""
    lid = oj * jnp.int64(8) + ln
    out: Dict[str, jnp.ndarray] = {}
    odate = H.EPOCH_1992 + _uint_in("o_orderdate", oj, 0,
                                    H.ORDER_DATE_SPAN - 1)
    ship = odate + 1 + (
        _h64("l_shipdate", lid) % jnp.uint64(121)
    ).astype(jnp.int64)
    partkey = 1 + (
        _h64("l_partkey", lid) % jnp.uint64(n["part"])
    ).astype(jnp.int64)
    qty = _uint_in("l_quantity", lid, 1, 50)
    for c in cols:
        if c == "l_orderkey":
            out[c] = _orderkey(oj)
        elif c == "l_partkey":
            out[c] = partkey
        elif c == "l_suppkey":
            slot = (_h64("l_supp_slot", lid) % jnp.uint64(4)).astype(
                jnp.int64
            )
            out[c] = _ps_suppkey(partkey, slot, n["supplier"])
        elif c == "l_linenumber":
            out[c] = ln + 1
        elif c == "l_quantity":
            out[c] = qty * 100
        elif c == "l_extendedprice":
            out[c] = qty * _retail_price_cents(partkey)
        elif c == "l_discount":
            out[c] = _uint_in(c, lid, 0, 10)
        elif c == "l_tax":
            out[c] = _uint_in(c, lid, 0, 8)
        elif c == "l_shipdate":
            out[c] = ship.astype(jnp.int32)
        elif c == "l_commitdate":
            out[c] = (odate + _uint_in(c, lid, 30, 90)).astype(jnp.int32)
        elif c == "l_receiptdate":
            out[c] = (ship + _uint_in(c, lid, 1, 30)).astype(jnp.int32)
        elif c == "l_returnflag":
            receipt = ship + _uint_in("l_receiptdate", lid, 1, 30)
            rnd = (_h64(c, lid) % jnp.uint64(2)).astype(jnp.int32)
            out[c] = jnp.where(
                receipt <= H.CURRENT_DATE, rnd * 2, 1
            ).astype(jnp.int32)
        elif c == "l_linestatus":
            out[c] = (ship > H.CURRENT_DATE).astype(jnp.int32)
        elif c == "l_shipinstruct":
            out[c] = _dict_code(c, lid, 4)
        elif c == "l_shipmode":
            out[c] = _dict_code(c, lid, 7)
        elif c == "l_comment":
            out[c] = _dict_code(c, lid, _NCOMMENT)
        else:  # pragma: no cover
            raise KeyError(c)
    return out


# ---------------------------------------------------------------------
# traced entry points (jitted once per (table, cols, caps, sf, mesh);
# lo/hi ride as traced scalars so all same-shape tiles share one
# executable).  On a mesh the same per-range function runs under
# shard_map: lo/hi are [ndev] vectors, every device generates its own
# (order-)range into its own HBM, and the lanes come back [ndev, cap]
# sharded on the mesh axis — nothing crosses the host or the interconnect.

_JIT_CACHE: Dict[tuple, object] = {}
_SHARD_OK = "$ok"  # the sharded program's validity plane (no column is named so)


def clear_jit_cache() -> int:
    """Drop every compiled generator executable.  Called by the executor
    on CPU-fallback entry: these executables are bound to the faulted
    device, and this module-level cache is outside the executor's own
    jit cache."""
    n = len(_JIT_CACHE)
    _JIT_CACHE.clear()
    return n


def _named_jit(fn, table: str):
    """jit the generator under the name `devgen_<table>`: the name a
    profile's `XLA Modules` line and idle-gap labels show for it."""
    fn.__name__ = fn.__qualname__ = "devgen_" + table
    # no-donate: generator args are two scalars (lo, hi); lanes are outputs
    return jax.jit(jax.named_scope(fn.__name__)(fn))


def _per_shard(fn, mesh):
    """`fn(lo, hi)` of one range, run by every device of `mesh` over its
    own entry of the [ndev] vectors lo/hi; lanes (and one all-true
    validity plane) come back [ndev, cap], one row block a device."""
    spec = PartitionSpec(mesh.axis_names[0])

    def shard(lo, hi):
        out = {c: v[None] for c, v in fn(lo[0], hi[0]).items()}
        cap = next(iter(out.values())).shape[1]
        out[_SHARD_OK] = jnp.ones((1, cap), dtype=bool)
        return out

    return jax.shard_map(shard, mesh=mesh, in_specs=(spec, spec),
                         out_specs=spec, check_vma=False)


def _gen_flat(table: str, cols: tuple, cap: int, sf: float):
    n = H._counts(sf)

    def fn(lo, hi):
        idx = lo + jnp.arange(cap, dtype=jnp.int64)
        live = idx < hi
        idx = jnp.where(live, idx, 0)
        vals = _base_table(table, cols, idx, n, sf)
        return {
            c: jnp.where(live, v, jnp.zeros((), v.dtype))
            for c, v in vals.items()
        }

    return fn


def _gen_lineitem(cols: tuple, cap_orders: int, cap_rows: int, sf: float):
    n = H._counts(sf)

    def fn(lo, hi):
        j = lo + jnp.arange(cap_orders, dtype=jnp.int64)
        jlive = j < hi
        counts = jnp.where(jlive, _line_count(jnp.where(jlive, j, 0)), 0)
        cum = jnp.cumsum(counts)  # cum[k] = lines of orders lo..lo+k
        total = cum[-1] if cap_orders else jnp.int64(0)
        r = jnp.arange(cap_rows, dtype=jnp.int32)
        live = r < total
        # order slot of each output row = (order start rows <= r) - 1:
        # every live order holds >= 1 line, so start rows are distinct,
        # and one scatter of start marks plus a running count stands in
        # for a binary search of `cum` (log2(cap_orders) gather passes
        # over every row: 52-56 s per dispatch at SF10 on a v5e, against
        # the 60 s dispatch watchdog).  Dead order slots scatter out of
        # bounds (after every live start, so the indices stay sorted)
        # and are dropped.
        starts = jnp.where(jlive, cum - counts, cap_rows)
        mark = jnp.zeros(cap_rows, dtype=jnp.int32).at[starts].add(
            1, mode="drop", indices_are_sorted=True
        )
        slot = jnp.clip(jnp.cumsum(mark) - 1, 0, max(cap_orders - 1, 0))
        # line number = rows since the order's start row
        start_row = jax.lax.cummax(jnp.where(mark > 0, r, 0))
        oj = jnp.where(live, lo + slot.astype(jnp.int64), 0)
        ln = jnp.where(live, (r - start_row).astype(jnp.int64), 0)
        vals = _lineitem(cols, oj, ln, n)
        return {
            c: jnp.where(live, v, jnp.zeros((), v.dtype))
            for c, v in vals.items()
        }

    return fn


def supports(table: str, cols: Sequence[str]) -> bool:
    dev = DEVICE_COLS.get(table)
    return dev is not None and all(c in dev for c in cols)


def _generator(table: str, cols: tuple, lo, hi, cap: int,
               sf: float, cap_orders: Optional[int], mesh=None):
    """(executable, compiled_now) for one (table, cols, caps, sf, mesh)
    shape: the generator is compiled ahead of time on first use and
    cached.  With a `mesh`, lo/hi are one entry per device and the
    executable is the SPMD program over exactly those devices."""
    if table == "lineitem":
        if cap_orders is None:
            cap_orders = int(np.max(np.subtract(hi, lo)))
        key = (table, cols, cap_orders, cap, sf)
    else:
        key = (table, cols, cap, sf)
    if mesh is not None:
        key += (mesh.axis_names, tuple(d.id for d in mesh.devices.flat))
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        return fn, False
    if table == "lineitem":
        raw = _gen_lineitem(cols, cap_orders, cap, sf)
    else:
        raw = _gen_flat(table, cols, cap, sf)
    if mesh is None:
        arg = jax.ShapeDtypeStruct((), jnp.int64)
    else:
        raw = _per_shard(raw, mesh)
        arg = jax.ShapeDtypeStruct(
            (mesh.devices.size,), jnp.int64,
            sharding=NamedSharding(mesh, PartitionSpec(mesh.axis_names[0])),
        )
    fn = _JIT_CACHE[key] = _named_jit(raw, table).lower(arg, arg).compile()
    return fn, True


def compile_lanes(
    table: str, cols: Sequence[str], lo, hi, cap: int, sf: float,
    cap_orders: Optional[int] = None, mesh=None,
) -> bool:
    """Compile (if not yet cached) the generator `device_lanes` will run
    for these arguments; True when a compile happened.  Callers that
    supervise the generator dispatch with a watchdog call this first, so
    the watchdog times execution and not the XLA compile."""
    return _generator(
        table, tuple(cols), lo, hi, cap, sf, cap_orders, mesh
    )[1]


def device_lanes(
    table: str,
    cols: Sequence[str],
    lo,
    hi,
    cap: int,
    sf: float,
    count,
    cap_orders: Optional[int] = None,
    mesh=None,
) -> Dict[str, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Generate the padded device lanes for rows of `table` whose
    (order-)index lies in [lo, hi).  `cap` is the padded row capacity;
    `count` the exact live row count (host-computed for lineitem);
    `cap_orders` a STATIC upper bound on hi-lo (padded so streaming
    tiles whose spans differ by a few rows share one executable).

    With a `mesh`, lo/hi/count hold one entry per device: device d
    generates [lo[d], hi[d]) into its own HBM (an empty range is an
    all-dead shard) and every lane is [ndev, cap], sharded on the mesh
    axis; the live rows of the shards, in device order, are the rows of
    [lo[0], hi[-1]) when the ranges are contiguous."""
    cols = tuple(cols)
    fn, _ = _generator(table, cols, lo, hi, cap, sf, cap_orders, mesh)
    if mesh is None:
        vals = fn(jnp.int64(lo), jnp.int64(hi))
        ok = jnp.ones(cap, dtype=bool)
    else:
        vals = fn(np.asarray(lo, dtype=np.int64),
                  np.asarray(hi, dtype=np.int64))
        ok = vals[_SHARD_OK]
    return {c: (vals[c], ok) for c in cols}


# ---------------------------------------------------------------------
# exact lineitem row count of an order range.  The count is one
# splitmix64 hash per ORDER on the host (numpy): ~0.17 us an order, so
# 1.3 s for a 7.5 M-order tile at SF10 — and a streamed scan asks for the
# same ranges again on every query.  It is a pure function of the order
# index (not even of the scale factor), so a process-wide block-prefix
# index (LineCountIndex) answers it: lines of orders [0, k*LINE_COUNT_BLOCK)
# for every k, built once up to the highest `hi` asked for, 8 bytes per
# 4,096 orders (29 KB at SF10).
# A range then costs two lookups plus the hash of its (at most two)
# partial edge blocks, whatever its alignment and whether or not it was
# asked for before (a memo keyed on (lo, hi) loses to any cyclic walk over
# more ranges than it holds, and tile geometry moves with the memory limit).

LINE_COUNT_BLOCK = 4096
_LC_CHUNK_BLOCKS = 256  # the build hashes ~1 M orders at a time (tens of MB)


def _hash_line_counts(lo: int, hi: int) -> np.ndarray:
    """Lines of each order in [lo, hi), by the host generator's hash."""
    return H._line_count(np.arange(lo, hi, dtype=np.int64))


class LineCountIndex:
    """prefix[k] = lines of orders [0, k * LINE_COUNT_BLOCK), grown on
    demand.  The prefetch pool, the query thread and the pools of
    concurrent queries all ask: extension runs under the lock and
    publishes a new array (never mutated), so readers need no lock."""

    def __init__(self):
        self._lock = threading.Lock()
        self._prefix = np.zeros(1, dtype=np.int64)

    @property
    def blocks(self) -> int:
        return len(self._prefix) - 1

    def _upto(self, nblocks: int) -> Tuple[np.ndarray, int]:
        """The prefix covering at least `nblocks` blocks, and how many
        orders this call hashed to extend it (0 when it was built)."""
        prefix = self._prefix
        if len(prefix) > nblocks:
            return prefix, 0
        B = LINE_COUNT_BLOCK
        with self._lock:
            prefix = self._prefix
            have = len(prefix) - 1
            for k0 in range(have, nblocks, _LC_CHUNK_BLOCKS):
                k1 = min(k0 + _LC_CHUNK_BLOCKS, nblocks)
                per_block = _hash_line_counts(k0 * B, k1 * B).reshape(-1, B)
                prefix = np.concatenate(
                    [prefix, prefix[-1] + np.cumsum(per_block.sum(axis=1))]
                )
            self._prefix = prefix
        return prefix, max(nblocks - have, 0) * B

    def count(self, lo: int, hi: int) -> Tuple[int, int]:
        """(exact line rows of orders [lo, hi), orders this call ran
        through the host hash).  Whole blocks come from the prefix; only
        the partial blocks at the two edges (< 2 * LINE_COUNT_BLOCK
        orders) are hashed per call, plus whatever the index had to be
        extended by.  A range with no whole block inside hashes directly."""
        B = LINE_COUNT_BLOCK
        if hi <= lo:
            return 0, 0
        b_lo, b_hi = -(-lo // B), hi // B
        if b_hi <= b_lo:
            return int(_hash_line_counts(lo, hi).sum()), hi - lo
        prefix, built = self._upto(b_hi)
        edges = (
            int(_hash_line_counts(lo, b_lo * B).sum())
            + int(_hash_line_counts(b_hi * B, hi).sum())
        )
        return (
            int(prefix[b_hi] - prefix[b_lo]) + edges,
            built + (b_lo * B - lo) + (hi - b_hi * B),
        )


_LINE_COUNTS = LineCountIndex()


def lineitem_count_hashed(lo: int, hi: int) -> Tuple[int, int]:
    """`LineCountIndex.count` on the process-wide index: the exact line
    rows of orders [lo, hi) and how many orders the call had to hash."""
    return _LINE_COUNTS.count(lo, hi)


def lineitem_count(lo: int, hi: int) -> int:
    """Exact line rows for orders [lo, hi), host-side (columns stay on
    device).  A full hash would cost ~0.17 us an order; see
    `lineitem_count_hashed` for how the block-prefix index answers it."""
    return lineitem_count_hashed(lo, hi)[0]
