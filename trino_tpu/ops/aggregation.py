"""Group-by aggregation kernels.

Reference parity: operator/HashAggregationOperator.java:53,
operator/GroupByHash.java:29 (FlatGroupByHash/FlatHash open addressing),
operator/aggregation/ (112 aggregate function classes built on
AccumulatorCompiler bytecode accumulators),
aggregation/builder/InMemoryHashAggregationBuilder.java:50.

TPU-first redesign — hash tables with random scatter are hostile to the MXU/
VPU, so grouping uses two strategies (SURVEY §7 "sort-or-scatter group-by"):

  1. direct: group keys that are dictionary codes / small ints map to a
     dense group id by mixed-radix combination; accumulators are
     jax.ops.segment_sum over a static group capacity.  This is the analog
     of the reference's BigintGroupByHash fast path and covers low-
     cardinality group-bys (TPC-H Q1: 2x2 codes -> 6 ids).

  2. hash-sort: rows sorted by a salted 64-bit locator of the key tuple
     (single-operand sort — multi-key comparators explode XLA:TPU
     compile time), adjacent rows exactly verified on the real columns,
     detected collisions re-run under a fresh salt (never probabilistic),
     then the same segment accumulators.

Group capacity is static per compilation; the kernel returns the true group
count so the host can recompile with a larger capacity when exceeded
(the "recompile-on-bucket-change" idiom replacing FlatHash rehashing).

Aggregation steps mirror AggregationNode.Step (plan/AggregationNode.java:346):
PARTIAL produces accumulator columns keyed by group; FINAL re-groups partial
rows and merges accumulators — the same kernel pair handles both, which is
also the distributed merge path (all-gather partials -> final, SURVEY §2.2).

Aggregate function families (reference operator/aggregation/*):
  count/count_star/count_if, sum, min, max, avg          — basic
  var_pop/var_samp/stddev_pop/stddev_samp (+aliases)     — 2nd moments
  covar_pop/covar_samp/corr/regr_slope/regr_intercept    — binary moments
  geometric_mean                                          — log-sum
  bool_and/bool_or (every)                                — boolean
  bitwise_and_agg/bitwise_or_agg/bitwise_xor_agg          — bit-plane kernels
  checksum                                                — order-independent
  arbitrary (any_value)                                   — first non-null
  min_by/max_by                                           — argmin/argmax
  approx_distinct   — exact at SINGLE step; HLL sketch PARTIAL/FINAL
  approx_percentile — exact at SINGLE step; k-min-hash sample sketch
  array_agg/map_agg/listagg — host-staged per-group dictionaries

NULL semantics: a NULL key is its own group (tracked via the validity bit as
an extra radix/sort key); sum/min/max ignore NULL inputs and return NULL for
empty groups; count counts non-NULL only.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as T
from ..expr.lower import Lane
from .join import row_ids

I64_MAX = 2**62  # python int (see ops/int128.py const-arg note)

# kinds whose accumulators are 2nd-moment sums over one input
MOMENT_KINDS = ("var_samp", "var_pop", "stddev_samp", "stddev_pop")
# kinds whose accumulators are moment sums over two inputs (y, x) —
# argument order follows the reference (e.g. regr_slope(y, x))
BINARY_MOMENT_KINDS = (
    "covar_pop", "covar_samp", "corr", "regr_slope", "regr_intercept",
)
BITWISE_KINDS = ("bitwise_and_agg", "bitwise_or_agg", "bitwise_xor_agg")
# kinds that cannot be split into PARTIAL/FINAL (computed at SINGLE step
# from raw rows; the planner must not push them through exchanges).
# approx_distinct / approx_percentile left this list in round 2: at
# SINGLE step they stay exact, but PARTIAL/FINAL ship mergeable sketch
# state (ops/sketches.py: HLL registers / k-min-hash samples), the
# reference's HyperLogLog + digest accumulator design.
# array_agg/map_agg/listagg build variable-length host dictionaries per
# group (host-staged, like UNNEST): raw rows must be colocated
NON_DECOMPOSABLE = ("array_agg", "map_agg", "listagg")
HOST_STAGED_KINDS = ("array_agg", "map_agg", "listagg")
SKETCHED_KINDS = ("approx_distinct", "approx_percentile")

TWO_ARG_KINDS = ("min_by", "max_by") + BINARY_MOMENT_KINDS


def _sum_overflow_flag(vv, gid, cap):
    """int64 accumulators wrap silently; this flags any per-group sum
    whose magnitude approaches the wrap point so the query FAILS LOUDLY
    until decimal(38) storage exists.  Two stages so the safe common case
    is ~free: a scalar sum(|v|) gate (an upper bound on EVERY group's
    |sum|), and only when it fires, a per-group f64 shadow under lax.cond
    (compiled both ways, executed only on the hot side; f64 error
    ~1e-16*n cannot confuse 9.0e18 with the 9.22e18 wrap point)."""
    gate = (
        jnp.sum(jnp.abs(vv).astype(jnp.float64)) > 9.0e18
    )

    def precise():
        shadow = _seg_sum(vv.astype(jnp.float64), gid, cap)
        return jnp.sum(jnp.abs(shadow) > 9.0e18).astype(jnp.int64)

    return jax.lax.cond(
        gate, precise, lambda: jnp.zeros((), dtype=jnp.int64)
    )


def _merge_overflow_check(vals, w, gid, cap, overflow_flags):
    """Shadow re-merge of partial int sums: flags a FINAL-side wrap
    (partials fine per worker, total beyond int64)."""
    if overflow_flags is None or jnp.issubdtype(vals.dtype, jnp.floating):
        return
    overflow_flags.append(
        _sum_overflow_flag(jnp.where(w, vals, 0), gid, cap)
    )


def _sum_could_overflow(nrows: int, input_type) -> bool:
    """Static filter for the shadow overflow check: can nrows values
    of this type exceed int64?  (decimal(p,s) raw values < 10^p)."""
    digits = (
        input_type.precision
        if input_type is not None and input_type.is_decimal
        else 19
    )
    return nrows * (10.0 ** digits) > 9.0e18


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregate function instance (AggregatorFactory analog)."""

    kind: str
    input: Optional[str]  # input column name (None for count_star)
    output: str
    input_type: Optional[T.Type] = None
    output_type: Optional[T.Type] = None
    distinct: bool = False
    input2: Optional[str] = None  # second arg (min_by/max_by/corr/...)
    input2_type: Optional[T.Type] = None
    param: Optional[float] = None  # constant parameter (approx_percentile)

    @property
    def _wide_sum(self) -> bool:
        """Wide (two-limb) chunked accumulation: any decimal sum/avg —
        sum outputs are typed decimal(38,s) (Int128 accumulator analog,
        spi/type/Int128Math.java), so the state is four 32-bit chunk
        sums that merge by plain addition (psum-able)."""
        from . import wide_decimal as wd

        if self.kind == "sum":
            return wd.is_wide_type(self.output_type)
        if self.kind == "avg":
            return (
                self.input_type is not None
                and self.input_type.is_decimal
                and self.output_type is not None
                and self.output_type.is_decimal
            )
        return False

    @property
    def accumulator_names(self) -> List[str]:
        o = self.output
        if self.kind == "avg":
            if self._wide_sum:
                return [f"{o}$c0", f"{o}$c1", f"{o}$c2", f"{o}$c3",
                        f"{o}$count"]
            return [f"{o}$sum", f"{o}$count"]
        if self.kind == "sum" and self._wide_sum:
            return [f"{o}$c0", f"{o}$c1", f"{o}$c2", f"{o}$c3",
                    f"{o}$valid"]
        if self.kind in ("sum", "min", "max"):
            return [f"{o}$val", f"{o}$valid"]
        if self.kind in MOMENT_KINDS:
            return [f"{o}$sum", f"{o}$sumsq", f"{o}$count"]
        if self.kind == "geometric_mean":
            return [f"{o}$sumlog", f"{o}$count"]
        if self.kind in BINARY_MOMENT_KINDS:
            return [f"{o}$sy", f"{o}$sx", f"{o}$sxy", f"{o}$sxx",
                    f"{o}$syy", f"{o}$n"]
        if self.kind == "approx_distinct":
            from . import sketches

            return [f"{o}$hll{i}" for i in range(sketches.HLL_LANES)]
        if self.kind == "approx_percentile":
            from . import sketches

            K = sketches.KMV_K
            return (
                [f"{o}$pv{i}" for i in range(K)]
                + [f"{o}$ph{i}" for i in range(K)]
                + [f"{o}$pmin", f"{o}$pmax"]
            )
        if self.kind in HOST_STAGED_KINDS:
            return [f"{o}$val", f"{o}$valid"]  # host-staged; not shipped
        if self.kind in ("bool_and", "bool_or", "checksum",
                         "arbitrary") or self.kind in BITWISE_KINDS:
            return [f"{o}$val", f"{o}$valid"]
        if self.kind in ("min_by", "max_by"):
            return [f"{o}$val", f"{o}$key", f"{o}$valid", f"{o}$has"]
        # count / count_star / count_if / approx_distinct
        return [f"{o}$count"]

    def psum_kind(self, name: str) -> Optional[str]:
        """How to merge this accumulator across mesh devices with a single
        collective: 'sum' | 'min' | 'max', or None when a collective cannot
        merge it (the executor must fall back to the gather+merge path)."""
        if self.kind in ("min", "max") and name.endswith("$val"):
            from . import wide_decimal as wd

            if wd.is_wide_type(self.output_type):
                # per-limb min/max is not lexicographic 128-bit min/max
                return None
            return self.kind
        if self.kind == "bool_and" and name.endswith("$val"):
            return "min"
        if self.kind == "bool_or" and name.endswith("$val"):
            return "max"
        if self.kind in ("arbitrary", "min_by", "max_by") or (
            self.kind in BITWISE_KINDS
        ):
            if not (name.endswith("$valid") or name.endswith("$has")
                    or name.endswith("$count")):
                return None
        if self.kind in SKETCHED_KINDS:
            # packed registers / sample slots need unpack-style merges
            # (gather path), not a single collective
            return None
        return "sum"


def direct_group_ids(
    key_lanes: Sequence[Lane], domains: Sequence[int]
) -> Tuple[jnp.ndarray, int]:
    """Mixed-radix dense group id from small-domain keys.

    Each key contributes radix (domain+1): slot `domain` encodes NULL.
    Returns (gid array, capacity).
    """
    gid = None
    cap = 1
    for (v, ok), dom in zip(key_lanes, domains):
        radix = dom + 1
        code = jnp.where(ok, jnp.clip(v.astype(jnp.int64), 0, dom - 1), dom)
        gid = code if gid is None else gid * radix + code
        cap *= radix
    return gid, cap


# >int64 bit patterns must wrap in jnp.uint64(...) AT USE (trace-time
# literal); raw python ints overflow the default int64 weak promotion
_GOLDEN = 0x9E3779B97F4A7C15
_SALT_C = 0x632BE59BD9B4E019


def _exp2i_pair(e: jnp.ndarray):
    """Exact 2^e for integer |e| <= 1046, as TWO f64 factors (apply
    sequentially to stay in range).  Built by binary factorization from
    exact power-of-two constants — no ldexp/exp2 primitive is trusted,
    since XLA:TPU's x64 rewrite lacks ldexp/frexp/64-bit bitcasts and
    library exp2 makes no exactness promise."""
    half = e // 2
    rest = e - half

    def pow_part(k):
        r = jnp.ones(k.shape, dtype=jnp.float64)
        a = jnp.abs(k)
        for j in range(10):  # covers |k| <= 1023
            c = jnp.where(
                k >= 0, jnp.float64(2.0 ** (1 << j)),
                jnp.float64(2.0 ** -(1 << j)),
            )
            r = r * jnp.where((a >> j) & 1 == 1, c, jnp.float64(1.0))
        return r

    return pow_part(half), pow_part(rest)


def f64_order_bits(v: jnp.ndarray) -> jnp.ndarray:
    """IEEE-754-equivalent uint64 for doubles, built ARITHMETICALLY
    because bitcast f64<->u64 (and frexp/ldexp) are unimplemented in
    XLA:TPU's x64 rewrite.  Exponent comes from a log2 estimate corrected
    by exact comparisons; the mantissa is extracted with exact
    power-of-two scaling, so the result EQUALS the IEEE bit pattern:
    injective (collision-verify soundness) and order-preserving, with NaN
    above +inf (Trino's NaN-largest rule).  The result is the classic
    radix-sortable float transform of that pattern."""
    v = v.astype(jnp.float64)
    av = jnp.abs(v)
    # normal path: av = m * 2^e0 with m in [1, 2)
    e0 = jnp.clip(
        jnp.floor(jnp.log2(jnp.where(av > 0, av, 1.0))), -1022.0, 1023.0
    ).astype(jnp.int32)
    s1, s2 = _exp2i_pair(-e0)
    m = av * s1 * s2
    for _ in range(2):  # log2 may misbin by one near boundaries
        big = m >= 2.0
        m = jnp.where(big, m * 0.5, m)
        e0 = e0 + big.astype(jnp.int32)
        small = (m < 1.0) & (m > 0)
        m = jnp.where(small, m * 2.0, m)
        e0 = e0 - small.astype(jnp.int32)
    safe_m = jnp.clip(m, 1.0, 2.0 - 2.0**-52)
    m_int = ((safe_m - 1.0) * jnp.float64(2.0**52)).astype(jnp.uint64)
    E = jnp.clip(e0 + 1023, 1, 2046).astype(jnp.uint64)
    bits = (E << jnp.uint64(52)) | m_int
    # subnormals, -0 and +0 all encode as 0: XLA arithmetic/comparisons
    # flush subnormals (DAZ) — verified on BOTH the TPU and CPU backends
    # ((5e-324 == 0.0) is True, (5e-324 != 0) is False in-engine) — so
    # one shared encoding is exactly consistent with the comparison
    # semantics the sort/verify kernels use
    tiny = av < jnp.float64(2.2250738585072014e-308)
    bits = jnp.where(tiny, jnp.uint64(0), bits)
    bits = jnp.where(jnp.isinf(av), jnp.uint64(0x7FF0000000000000), bits)
    bits = jnp.where(jnp.isnan(v), jnp.uint64(0x7FF8000000000000), bits)
    neg = (v < 0) & ~jnp.isnan(v)
    pattern = bits | (neg.astype(jnp.uint64) << jnp.uint64(63))
    # total order: flip all bits for negatives, set the sign bit for
    # non-negatives (the classic radix-sortable float transform)
    return jnp.where(neg, ~pattern, pattern | jnp.uint64(1 << 63))


def _key_bits(v: jnp.ndarray) -> jnp.ndarray:
    """Key column as uint64 bit material: floats get an injective
    order-preserving arithmetic encoding (no f64 bitcast on TPU), so
    distinct values never merge before hashing and NaN has a stable
    identity for both hashing and exact verification."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        return f64_order_bits(v)
    return v.astype(jnp.uint64)


def _key_bit_lanes(v: jnp.ndarray):
    """Key column as one or two uint64 bit-material lanes (wide decimals
    contribute each limb as its own hashing/verification round)."""
    if v.ndim == 2:
        return [v[:, 0].astype(jnp.uint64), v[:, 1].astype(jnp.uint64)]
    return [_key_bits(v)]


def _group_hash(key_lanes: Sequence[Lane], salt: int) -> jnp.ndarray:
    """Salted 64-bit key-tuple locator.  The NULL flag is mixed as its own
    round (not as a sentinel value), so `NULL` and any real value can never
    permanently collide — a salt change re-randomizes every collision."""
    n = key_lanes[0][0].shape[0]
    h = jnp.full(n, jnp.uint64(salt * 2 + 1) * jnp.uint64(_GOLDEN), dtype=jnp.uint64)
    for v, ok in key_lanes:
        h = h * jnp.uint64(_GOLDEN) + ok.astype(jnp.uint64) + jnp.uint64(_SALT_C)
        h = h ^ (h >> jnp.uint64(31))
        for bits in _key_bit_lanes(v):
            h = h * jnp.uint64(_GOLDEN) + jnp.where(ok, bits, jnp.uint64(0))
            h = h ^ (h >> jnp.uint64(29))
    return (h % jnp.uint64(2**61)).astype(jnp.int64)


@jax.named_scope("sort_group_ids")
def sort_group_ids(
    key_lanes: Sequence[Lane],
    sel: jnp.ndarray,
    capacity: int,
    salt: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Hash-sort grouping: returns (perm, gid_sorted, ngroups, sel_sorted,
    same_run).

    perm reorders rows so equal keys are adjacent (unselected rows last);
    gid_sorted[i] is the group id of sorted row i (unselected rows get
    capacity-1 but are excluded by weight later); sel_sorted is `sel[perm]`,
    read off the sorted locator (dead rows are keyed 2**61); same_run[i]
    says that sorted row i is live and has the locator of row i-1.

    TPU-first design note: a lexicographic multi-key `lax.sort` compiles a
    (1+2k)-operand comparator whose XLA:TPU compile time explodes with k
    (~190s for k=3 at 8M rows vs ~50s for one key).  Instead rows sort by
    ONE salted 64-bit locator hash of the key tuple, and adjacent rows in
    the same hash run are verified equal on the real key columns
    (`run_collisions`; probability of a mismatch ~n²/2⁻⁶⁴), and the
    executor re-runs with a fresh salt when it is ever nonzero, so results
    are exact, never probabilistic (same protocol as the join locators).

    Nothing here gathers: the CALLER permutes its rows by `perm`, keys
    included, once (`exec/local._TraceCtx._group_sort`: one stacked
    `permute_lanes` over the whole batch), and `run_collisions` verifies
    on those sorted key lanes.  A random gather over all slots is what a
    sort group-by costs on the chip (45-50 M elements/s), so no key lane
    is gathered twice."""
    n = key_lanes[0][0].shape[0]
    hk = _group_hash(key_lanes, salt)
    key = jnp.where(sel, hk, jnp.int64(2**61))  # dead rows sort last
    # row ids as the last key of an unstable sort: the stable order, at a
    # fraction of the compile (join.row_ids)
    sorted_key, perm = jax.lax.sort(
        (key, row_ids(n)), num_keys=2, is_stable=False
    )
    perm = perm.astype(jnp.int64)
    sel_sorted = sorted_key < jnp.int64(2**61)
    diff = jnp.concatenate(
        [jnp.ones(1, bool), sorted_key[1:] != sorted_key[:-1]]
    )
    boundary = diff & sel_sorted
    same_run = (~diff) & sel_sorted
    gid = jnp.cumsum(boundary.astype(jnp.int64)) - 1
    ngroups = boundary.sum()
    gid = jnp.where(sel_sorted, jnp.clip(gid, 0, capacity - 1), capacity - 1)
    return perm, gid, ngroups, sel_sorted, same_run


def _shift_down(x: jnp.ndarray) -> jnp.ndarray:
    """Row i reads row i-1 (row 0 itself): the neighbour above."""
    return jnp.concatenate([x[:1], x[:-1]])


@jax.named_scope("run_collisions")
def run_collisions(
    sorted_key_lanes: Sequence[Lane], same_run: jnp.ndarray
) -> jnp.ndarray:
    """Exact adjacent verification of `sort_group_ids`' hash runs
    (PagesHashStrategy positionEquals analog), on key lanes ALREADY
    permuted by its `perm`: the count of live rows that share the locator
    of the row above but not its key tuple.  NULL equals NULL and no
    value; floats compare by `f64_order_bits` (NaN equals NaN, -0 equals
    +0), wide keys limb by limb.  Elementwise and a one-row shift: no
    gather."""
    n = same_run.shape[0]
    all_eq = jnp.ones(n, dtype=bool)
    for v, ok in sorted_key_lanes:
        vals_eq = jnp.ones(n, dtype=bool)
        for bits in _key_bit_lanes(v):
            vals_eq = vals_eq & (bits == _shift_down(bits))
        lane_eq = (ok == _shift_down(ok)) & (~ok | vals_eq)
        all_eq = all_eq & lane_eq
    return jnp.sum(same_run & ~all_eq)


def distinct_first_mask(
    gid: jnp.ndarray, lane: Lane, live: jnp.ndarray
) -> jnp.ndarray:
    """First-occurrence mask per (group, value) over live rows, returned
    in the CALLER's row order (MarkDistinctOperator analog,
    /root/reference/core/trino-main/src/main/java/io/trino/operator/
    MarkDistinctOperator.java:34 — but as one sort by (liveness, gid,
    value-bits) + adjacent-first flags + an inverse-permutation scatter,
    instead of a row-at-a-time hash table).  Any aggregate then runs its
    NORMAL accumulator over `live & mask` — sum/avg/stddev(DISTINCT) and
    multi-distinct all reduce to this one mask per (agg, input) pair
    (DistinctAccumulatorFactory.java:36)."""
    v, _ok = lane
    n = gid.shape[0]
    bit_lanes = list(_key_bit_lanes(v))
    dead = jnp.logical_not(live)
    ops = (dead, gid, *bit_lanes, jnp.arange(n, dtype=jnp.int64))
    res = jax.lax.sort(ops, num_keys=2 + len(bit_lanes))
    d2, g2 = res[0], res[1]
    perm = res[-1]
    neq = g2[1:] != g2[:-1]
    for b in res[2:-1]:
        neq = neq | (b[1:] != b[:-1])
    first = jnp.concatenate([jnp.ones(1, bool), neq]) & jnp.logical_not(d2)
    # perm is a permutation (unique indices): one n-sized scatter back
    return jnp.zeros(n, dtype=bool).at[perm].set(first)


# DISTINCT is semantically a no-op for these kinds (duplicates cannot
# change an extremum / boolean fold / arbitrary pick)
_DISTINCT_NOOP = ("min", "max", "bool_and", "bool_or", "arbitrary",
                  "approx_distinct")
# kinds whose accumulators correctly consume a dedup-refined live mask
_DISTINCT_MASKED = ("sum", "avg", "count_if", "geometric_mean") + MOMENT_KINDS


def distinct_count(
    gid: jnp.ndarray, lane: Lane, sel: jnp.ndarray, capacity: int
) -> jnp.ndarray:
    """count(DISTINCT x) per group: sort by (gid, x), count first
    occurrences (MarkDistinctOperator + count, in one sort)."""
    v, ok = lane
    live = sel & ok
    n = gid.shape[0]
    vv = v.astype(jnp.int64) if v.dtype.kind in ("i", "u", "b") else v
    dead = jnp.logical_not(live)
    # dead rows sort last; within live rows, equal (gid, value) adjacent
    sorted_ops = jax.lax.sort(
        (dead, gid, vv, jnp.arange(n, dtype=jnp.int64)), num_keys=3
    )
    d2, g2, v2, perm = sorted_ops
    live2 = jnp.logical_not(d2)
    first = jnp.concatenate(
        [jnp.ones(1, bool), (g2[1:] != g2[:-1]) | (v2[1:] != v2[:-1])]
    )
    flags = (first & live2).astype(jnp.int64)
    return jax.ops.segment_sum(flags, jnp.clip(g2, 0, capacity - 1),
                               num_segments=capacity)


# Scatter-add is slow on TPU (no native scatter unit): for small group
# capacities a one-hot masked reduction is several times faster (measured
# ~0.15s vs ~0.6s for 6M rows x 12 groups on v5e), so segment reductions
# pick their implementation by capacity and backend.
_SMALL_SEG_CAP = 32


def _use_masked(cap: int) -> bool:
    try:
        return cap <= _SMALL_SEG_CAP and jax.default_backend() == "tpu"
    except Exception:
        return False


def _seg_sum(v, gid, cap):
    if _use_masked(cap) and v.ndim == 1:
        m = gid[None, :] == jnp.arange(cap, dtype=gid.dtype)[:, None]
        zero = jnp.zeros((), dtype=v.dtype)
        return jnp.sum(jnp.where(m, v[None, :], zero), axis=1)
    return jax.ops.segment_sum(v, gid, num_segments=cap)


def _seg_count(mask, gid, cap):
    """Per-group count of a boolean mask.  Counts are the pallas
    single-f32-plane case (ops/pallas_kernels.grouped_count, ~14x the XLA
    lowering on TPU at SF1 shapes); general int64 sums measured SLOWER in
    pallas (int ops lack VPU MACs) and stay on _seg_sum."""
    from . import pallas_kernels

    ps = pallas_kernels.seg_count_maybe(mask, gid, cap)
    if ps is not None:
        return ps
    return _seg_sum(mask.astype(jnp.int64), gid, cap)


def _seg_min(v, gid, cap):
    if _use_masked(cap) and v.ndim == 1:
        if v.dtype.kind == "f":
            sent = jnp.asarray(jnp.inf, dtype=v.dtype)
        else:
            sent = jnp.asarray(jnp.iinfo(v.dtype).max, dtype=v.dtype)
        m = gid[None, :] == jnp.arange(cap, dtype=gid.dtype)[:, None]
        return jnp.min(jnp.where(m, v[None, :], sent), axis=1)
    return jax.ops.segment_min(v, gid, num_segments=cap)


def _seg_max(v, gid, cap):
    if _use_masked(cap) and v.ndim == 1:
        if v.dtype.kind == "f":
            sent = jnp.asarray(-jnp.inf, dtype=v.dtype)
        else:
            sent = jnp.asarray(jnp.iinfo(v.dtype).min, dtype=v.dtype)
        m = gid[None, :] == jnp.arange(cap, dtype=gid.dtype)[:, None]
        return jnp.max(jnp.where(m, v[None, :], sent), axis=1)
    return jax.ops.segment_max(v, gid, num_segments=cap)


def _seg_minmax_wide(v, live, gid, cap, take_min: bool):
    """Lexicographic segment min/max of a wide (two-limb) decimal lane:
    extreme high limb first, then the extreme unsigned low limb among
    rows whose high limb attains it (two segment passes, both exact).

    Sentinels are the TRUE int64 extremes (not the engine's 2^62
    I64_MAX): limbs span the full 64-bit domain."""
    from . import wide_decimal as wd

    lo, hi = wd.limbs(v)
    lo_u = lo ^ jnp.int64(-0x8000000000000000)  # unsigned order, signed domain
    seg = _seg_min if take_min else _seg_max
    sent = (
        jnp.int64(0x7FFFFFFFFFFFFFFF)
        if take_min
        else jnp.int64(-0x8000000000000000)
    )
    hi_ext = seg(jnp.where(live, hi, sent), gid, cap)
    on_ext = live & (hi == hi_ext[gid])
    lo_ext = seg(jnp.where(on_ext, lo_u, sent), gid, cap)
    return wd.make_wide(
        lo_ext ^ jnp.int64(-0x8000000000000000), hi_ext
    )


def _splitmix64(v: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer — order-independent per-value hash for checksum.
    (The reference's checksum xors XxHash64 values: aggregation/ChecksumAggregationFunction;
    we sum splitmix64 hashes, equally order-independent.)"""
    z = v.astype(jnp.uint64) + jnp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> 27)) * jnp.uint64(0x94D049BB133111EB)
    z = z ^ (z >> 31)
    return z.astype(jnp.int64)


def _segment_bitwise(vals, live, gid, cap, op: str, live_cnt=None):
    """Per-group bitwise and/or/xor via one 2-D segment_sum over bit planes.

    No segment_and/or exists in XLA; instead decompose into a [n, 64] 0/1
    matrix, segment-sum it to per-group bit counts [cap, 64], then
    AND = (count == group_size), OR = (count > 0), XOR = (count & 1).
    """
    # created at trace time (a module-level arange would become a hidden
    # const arg — see ops/int128.py const-arg note)
    bit_shifts = jnp.arange(64, dtype=jnp.uint64)
    u = vals.astype(jnp.uint64)
    bits = ((u[:, None] >> bit_shifts[None, :]) & jnp.uint64(1)).astype(
        jnp.int32
    )
    bits = jnp.where(live[:, None], bits, 0)
    sums = _seg_sum(bits, gid, cap)  # [cap, 64]
    if live_cnt is None:
        live_cnt = _seg_sum(live.astype(jnp.int32), gid, cap)
    if op == "or":
        outbits = (sums > 0)
    elif op == "and":
        outbits = (sums == live_cnt[:, None]) & (live_cnt[:, None] > 0)
    else:  # xor
        outbits = (sums & 1) == 1
    vals64 = (outbits.astype(jnp.uint64) << bit_shifts[None, :]).sum(
        axis=1, dtype=jnp.uint64
    )
    return vals64.astype(jnp.int64)


def _first_by_key(xlane, key, live, gid, cap, take_min: bool):
    """Per-group x-value at the min/max key row (min_by/max_by kernel).

    Two-pass argmin: (1) segment extremum of the key, (2) first row index
    whose key equals the extremum, (3) gather x there."""
    x, xok = xlane
    n = gid.shape[0]
    if key.dtype.kind == "f":
        sentinel = jnp.inf if take_min else -jnp.inf
        kv = jnp.where(live, key, sentinel)
    else:
        sentinel = I64_MAX if take_min else -I64_MAX
        kv = jnp.where(live, key.astype(jnp.int64), sentinel)
    seg = _seg_min if take_min else _seg_max
    extremum = seg(kv, gid, cap)
    cand = live & (kv == extremum[gid])
    ridx = _seg_min(
        jnp.where(cand, jnp.arange(n, dtype=jnp.int64), n), gid, cap
    )
    has = ridx < n
    safe = jnp.clip(ridx, 0, n - 1)
    xv = x[safe]
    xvalid = xok[safe] & has
    zero = jnp.zeros_like(extremum)
    return (
        jnp.where(has, xv, jnp.zeros_like(xv)),
        jnp.where(has, extremum, zero),
        xvalid,
        has,
    )


def _percentile(lane: Lane, sel, gid, cap, frac: float):
    """Exact per-group percentile by sort (the engine's approx_percentile:
    zero-error flavor of the reference's qdigest-based one)."""
    v, ok = lane
    live = sel & ok
    n = gid.shape[0]
    dead = jnp.logical_not(live)
    vv = v.astype(jnp.int64) if v.dtype.kind in ("i", "u", "b") else v
    d2, g2, v2 = jax.lax.sort((dead, gid, vv), num_keys=3)
    live2 = jnp.logical_not(d2)
    cnt = _seg_sum(live2.astype(jnp.int64), jnp.clip(g2, 0, cap - 1), cap)
    start = jnp.cumsum(cnt) - cnt  # live rows sort before dead ones per gid?
    # live rows of group g occupy a contiguous run; compute each sorted row's
    # rank within its group
    g2c = jnp.clip(g2, 0, cap - 1)
    rank = jnp.arange(n, dtype=jnp.int64) - start[g2c]
    target = jnp.clip(
        jnp.floor(frac * (cnt - 1).astype(jnp.float64) + 0.5).astype(jnp.int64),
        0,
        jnp.maximum(cnt - 1, 0),
    )
    pick = live2 & (rank == target[g2c])
    if v2.dtype.kind == "f":
        out = _seg_max(jnp.where(pick, v2, -jnp.inf), g2c, cap)
        out = jnp.where(cnt > 0, out, 0.0)
    else:
        out = _seg_max(jnp.where(pick, v2, -I64_MAX), g2c, cap)
        out = jnp.where(cnt > 0, out, 0)
    return out.astype(v.dtype) if v.dtype.kind != "f" else out, cnt > 0


def _as_double(v: jnp.ndarray, t: Optional[T.Type]) -> jnp.ndarray:
    """Numeric lane -> float64, unscaling fixed-point decimals."""
    if t is None:
        return v.astype(jnp.float64)
    from ..expr.functions import to_double

    return to_double(v, t)


def _moment_sums(v, live, gid, cap, in_t):
    x = jnp.where(live, _as_double(v, in_t), 0.0)
    return (
        _seg_sum(x, gid, cap),
        _seg_sum(x * x, gid, cap),
        _seg_sum(live.astype(jnp.int64), gid, cap),
    )


_SCAN_BLOCK = 1024


@jax.named_scope("_suffix_min")
def _suffix_min(v: jnp.ndarray) -> jnp.ndarray:
    """`lax.cummin(v, reverse=True)` of a 1-D integer lane, by blocks: the
    suffix minimum inside each block of `_SCAN_BLOCK`, and under it the
    (recursive) suffix minimum of the blocks that follow.  The same
    numbers; the one-pass form is XLA:TPU's to compile, and over 2^20 or
    2^21 int32 elements that takes it 35-42 s (7-10 s at 2^22 and 2^24;
    compiled for a described v5e), the blocked form under a second."""
    n = v.shape[0]
    if n <= _SCAN_BLOCK:
        return jax.lax.cummin(v, reverse=True)
    big = jnp.iinfo(v.dtype).max
    blocks = jnp.pad(
        v, (0, -n % _SCAN_BLOCK), constant_values=big
    ).reshape(-1, _SCAN_BLOCK)
    within = jax.lax.cummin(blocks, axis=1, reverse=True)
    after = jnp.concatenate(
        [_suffix_min(within[:, 0])[1:], jnp.full(1, big, v.dtype)]
    )
    return jnp.minimum(within, after[:, None]).reshape(-1)[:n]


class SortedSegments:
    """Scatter-free grouped reductions over a SORTED gid lane (the
    hash-sort grouping path: rows arrive permuted so equal groups are
    adjacent, gid non-decreasing).

    XLA:TPU scatter runs ~16M updates/s regardless of sortedness hints
    (round-3 micro-benchmark, record deleted in PR 22), so at capacities beyond the masked-matrix range
    every accumulator cost ~0.5s at SF1.  Sorted runs instead admit:
      - ONE extra single-key sort (merge_rank of arange(cap) into the
        sorted gids) shared by all aggregates, giving each group's
        [start, end) row range, then
      - per-aggregate cumsum + two cap-sized gathers (sums/counts) or a
        segmented scan + end-gather (min/max) — all bandwidth-bound.
    """

    def __init__(self, gid: jnp.ndarray, cap: int):
        from .join import merge_rank

        self.gid = gid
        self.cap = cap
        self.n = gid.shape[0]
        # group ids are below cap: they sort as the row ids' dtype
        probe = row_ids(cap)
        keys = gid.astype(probe.dtype)
        self.starts = merge_rank(keys, probe, side="left")
        self.ends = merge_rank(keys, probe, side="right")
        self.counts_all = self.ends - self.starts  # incl. non-live rows

    def _range_diff(self, cs: jnp.ndarray) -> jnp.ndarray:
        """cs = inclusive prefix over rows -> per-group range totals."""
        zero = jnp.zeros(1, dtype=cs.dtype)
        cs0 = jnp.concatenate([zero, cs])  # cs0[i] = sum of rows < i
        return cs0[self.ends] - cs0[self.starts]

    @jax.named_scope("SortedSegments.sum")
    def sum(self, v: jnp.ndarray) -> jnp.ndarray:
        return self._range_diff(jnp.cumsum(v))

    def count(self, mask: jnp.ndarray) -> jnp.ndarray:
        return self._range_diff(jnp.cumsum(mask.astype(jnp.int64)))

    def _scan_extreme(self, v: jnp.ndarray, take_min: bool) -> jnp.ndarray:
        boundary = jnp.concatenate(
            [jnp.ones(1, bool), self.gid[1:] != self.gid[:-1]]
        )
        op = jnp.minimum if take_min else jnp.maximum

        def combine(a, b):
            f1, v1 = a
            f2, v2 = b
            return (f1 | f2, jnp.where(f2, v2, op(v1, v2)))

        _, run = jax.lax.associative_scan(combine, (boundary, v))
        # group extremum lands at each run's LAST row = ends-1
        last = jnp.clip(self.ends - 1, 0, self.n - 1)
        return run[last]

    def min(self, v: jnp.ndarray) -> jnp.ndarray:
        return self._scan_extreme(v, True)

    def max(self, v: jnp.ndarray) -> jnp.ndarray:
        return self._scan_extreme(v, False)

    @jax.named_scope("SortedSegments.first")
    def first(self, live: jnp.ndarray):
        """Each group's first row at which `live` holds, as `(row, has)`:
        the row `_seg_min` of the masked row ids picks, with no scatter.
        The suffix minimum of the masked ids is monotone across runs, so
        no segmented scan is needed: read it at each run's start, and the
        run has a live row iff that row lies before the run's end.  Dead
        rows share gid cap-1 but sort last (sort_group_ids), so a real
        last group still reads its own first live row."""
        n = self.n
        suf = _suffix_min(jnp.where(live, row_ids(n), n))
        row = jnp.concatenate([suf, jnp.full(1, n, suf.dtype)])[self.starts]
        return row, row < self.ends


def _first_live_row(live, gid, cap, seg: Optional[SortedSegments]):
    """Per group the first row at which `live` holds, as `(row, has)`: off
    the sorted run where `seg` is given, else (direct-domain and global
    group-bys, unsorted callers) by a scatter-min of the masked row ids."""
    if seg is not None:
        return seg.first(live)
    n = gid.shape[0]
    ridx = _seg_min(
        jnp.where(live, jnp.arange(n, dtype=jnp.int64), n), gid, cap
    )
    return ridx, ridx < n


# aggregate kinds the SortedSegments fast path covers; others fall back
# to the generic segment ops
SORTED_FAST_KINDS = ("sum", "avg", "count", "count_star", "count_if",
                     "min", "max", "arbitrary")


@jax.named_scope("accumulate")
def accumulate(
    specs: Sequence[AggSpec],
    lanes: Dict[str, Lane],
    gid: jnp.ndarray,
    sel: jnp.ndarray,
    capacity: int,
    step: str = "single",
    overflow_flags: Optional[list] = None,
    wide_flags: Optional[list] = None,
    force_wide: bool = True,
    seg: Optional["SortedSegments"] = None,
) -> Dict[str, jnp.ndarray]:
    """Compute accumulator arrays (shape [capacity]) per spec.

    step='single' keeps approx_* exact (sort-based); step='partial'
    emits mergeable sketch state instead (ops/sketches.py), the
    decomposable PARTIAL/FINAL form shipped across exchanges.

    wide_flags/force_wide drive the decimal(38) sum fast path: callers
    wired into the executor retry ladder pass the lowering's wide-mul
    flag list and its force_wide_mul state; unwired callers keep the
    always-exact (slower) chunked default via force_wide=True."""
    out: Dict[str, jnp.ndarray] = {}
    cap = capacity
    # one dedup mask per DISTINCT input column, shared across specs
    # (sum(DISTINCT x) + avg(DISTINCT x) sort once, not twice)
    distinct_masks: Dict[str, jnp.ndarray] = {}

    # Scatter-free sorted-run reductions when the caller's gid is sorted
    # (hash-sort grouping).  Integer-only for sums: float range-diffs
    # would trade scatter cost for cancellation error.
    def seg_cnt(mask):
        if seg is not None:
            return seg.count(mask)
        return _seg_count(mask, gid, cap)

    def seg_isum(vv):
        if seg is not None and vv.dtype.kind != "f":
            return seg.sum(vv)
        return _seg_sum(vv, gid, cap)

    def seg_ext(vv, take_min):
        if seg is not None and vv.dtype.kind != "f":
            return seg.min(vv) if take_min else seg.max(vv)
        return (_seg_min if take_min else _seg_max)(vv, gid, cap)

    for s in specs:
        o = s.output
        if getattr(s, "distinct", False) and s.kind == "count":
            # count(DISTINCT x): specialized one-sort path (the mask
            # route would spend an extra scatter for the same answer)
            out[f"{o}$count"] = distinct_count(gid, lanes[s.input], sel, cap)
            continue
        if s.kind == "count_star":
            out[f"{o}$count"] = seg_cnt(sel)
            continue
        v, ok = lanes[s.input]
        live = sel & ok
        if getattr(s, "distinct", False) and s.kind not in _DISTINCT_NOOP:
            if s.kind not in _DISTINCT_MASKED:
                raise NotImplementedError(
                    f"{s.kind}(DISTINCT) not supported"
                )
            if step != "single":
                raise NotImplementedError(
                    "DISTINCT aggregates are non-decomposable: the "
                    "planner must not split them PARTIAL/FINAL"
                )
            m = distinct_masks.get(s.input)
            if m is None:
                m = distinct_masks[s.input] = distinct_first_mask(
                    gid, (v, ok), live
                )
            live = live & m
        if s.kind == "count":
            out[f"{o}$count"] = seg_cnt(live)
        elif s.kind == "count_if":
            hit = live & (v.astype(bool))
            out[f"{o}$count"] = seg_cnt(hit)
        elif s.kind == "approx_distinct":
            if step == "single":
                out[f"{o}$count"] = distinct_count(gid, (v, ok), sel, cap)
            else:
                from . import sketches

                packed = sketches.hll_accumulate(
                    _key_bits(v), live, gid, cap
                )
                for i, arr in packed.items():
                    out[f"{o}$hll{i}"] = arr
        elif s.kind in ("sum", "avg"):
            cnt = seg_cnt(live)
            if s._wide_sum:
                # exact 128-bit decimal sum with a NARROW fast path: the
                # accumulator SCHEMA is always four 32-bit chunk lanes
                # ($c0..$c3, stable across retraces), but when the input
                # is one limb and wide math is not forced, the sum runs
                # as ONE int64 segment sum + a shadow overflow flag; a
                # detected wrap retraces with force_wide (the same
                # ladder as wide multiplies), where true chunked sums
                # take over.  TPC-H Q1/Q6-scale sums never trip it, so
                # exactness at decimal(38) costs ~nothing steady-state.
                from . import wide_decimal as wd

                if wd.is_wide(v) or force_wide:
                    chunks = (
                        wd.wide_row_chunks(v, live)
                        if wd.is_wide(v)
                        else wd.narrow_row_chunks(v, live)
                    )
                    cs = wd.seg_sum_chunks(chunks, gid, cap, seg=seg)
                else:
                    vv = jnp.where(live, v.astype(jnp.int64), 0)
                    ssum = seg_isum(vv)
                    if wide_flags is not None and _sum_could_overflow(
                        v.shape[0], s.input_type
                    ):
                        wide_flags.append(_sum_overflow_flag(vv, gid, cap))
                    cs = wd.normalize_chunks([
                        ssum & 0xFFFFFFFF, ssum >> jnp.int64(32),
                        jnp.zeros_like(ssum), jnp.zeros_like(ssum),
                    ])
                for i, c in enumerate(cs):
                    out[f"{o}$c{i}"] = c
                out[f"{o}$valid" if s.kind == "sum" else f"{o}$count"] = cnt
                continue
            if v.dtype.kind == "f":
                vv = jnp.where(live, v, 0.0)
            else:
                vv = jnp.where(live, v.astype(jnp.int64), 0)
            ssum = seg_isum(vv)
            if (
                v.dtype.kind != "f"
                and overflow_flags is not None
                and _sum_could_overflow(v.shape[0], s.input_type)
            ):
                overflow_flags.append(_sum_overflow_flag(vv, gid, cap))
            if s.kind == "sum":
                out[f"{o}$val"] = ssum
                out[f"{o}$valid"] = cnt
            else:
                out[f"{o}$sum"] = ssum
                out[f"{o}$count"] = cnt
        elif s.kind in ("min", "max"):
            from . import wide_decimal as wd

            if wd.is_wide(v):
                out[f"{o}$val"] = _seg_minmax_wide(
                    v, live, gid, cap, s.kind == "min"
                )
                out[f"{o}$valid"] = _seg_count(live, gid, cap)
                continue
            if v.dtype.kind == "f":
                sentinel = jnp.inf if s.kind == "min" else -jnp.inf
                vv = jnp.where(live, v, sentinel)
            else:
                sentinel = I64_MAX if s.kind == "min" else -I64_MAX
                vv = jnp.where(live, v.astype(jnp.int64), sentinel)
            out[f"{o}$val"] = seg_ext(vv, s.kind == "min")
            out[f"{o}$valid"] = seg_cnt(live)
        elif s.kind in MOMENT_KINDS:
            sm, sq, cnt = _moment_sums(v, live, gid, cap, s.input_type)
            out[f"{o}$sum"], out[f"{o}$sumsq"], out[f"{o}$count"] = sm, sq, cnt
        elif s.kind == "geometric_mean":
            x = _as_double(v, s.input_type)
            lx = jnp.where(live & (x > 0), jnp.log(jnp.maximum(x, 1e-300)), 0.0)
            out[f"{o}$sumlog"] = _seg_sum(lx, gid, cap)
            out[f"{o}$count"] = _seg_count(live, gid, cap)
        elif s.kind in BINARY_MOMENT_KINDS:
            y, yok = lanes[s.input]
            x, xok = lanes[s.input2]
            both = sel & yok & xok
            xf = jnp.where(both, _as_double(x, s.input2_type), 0.0)
            yf = jnp.where(both, _as_double(y, s.input_type), 0.0)
            out[f"{o}$sy"] = _seg_sum(yf, gid, cap)
            out[f"{o}$sx"] = _seg_sum(xf, gid, cap)
            out[f"{o}$sxy"] = _seg_sum(xf * yf, gid, cap)
            out[f"{o}$sxx"] = _seg_sum(xf * xf, gid, cap)
            out[f"{o}$syy"] = _seg_sum(yf * yf, gid, cap)
            out[f"{o}$n"] = _seg_count(both, gid, cap)
        elif s.kind in ("bool_and", "bool_or"):
            cnt = _seg_count(live, gid, cap)
            if s.kind == "bool_and":
                vv = jnp.where(live, v.astype(jnp.int64), 1)
                out[f"{o}$val"] = _seg_min(vv, gid, cap)
            else:
                vv = jnp.where(live, v.astype(jnp.int64), 0)
                out[f"{o}$val"] = _seg_max(vv, gid, cap)
            out[f"{o}$valid"] = cnt
        elif s.kind in BITWISE_KINDS:
            op = {"bitwise_and_agg": "and", "bitwise_or_agg": "or",
                  "bitwise_xor_agg": "xor"}[s.kind]
            cnt = _seg_count(live, gid, cap)
            out[f"{o}$val"] = _segment_bitwise(
                v, live, gid, cap, op, cnt.astype(jnp.int32)
            )
            out[f"{o}$valid"] = cnt
        elif s.kind == "checksum":
            addend = jnp.where(
                ok, _splitmix64(v), jnp.int64(0x6E67_6C6C_7561)
            )
            out[f"{o}$val"] = _seg_sum(jnp.where(sel, addend, 0), gid, cap)
            out[f"{o}$valid"] = _seg_count(sel, gid, cap)
        elif s.kind == "arbitrary":
            ridx, has = _first_live_row(live, gid, cap, seg)
            safe = jnp.clip(ridx, 0, gid.shape[0] - 1)
            out[f"{o}$val"] = jnp.where(has, v[safe], jnp.zeros_like(v[safe]))
            out[f"{o}$valid"] = has.astype(jnp.int64)
        elif s.kind in ("min_by", "max_by"):
            key, kok = lanes[s.input2]
            xv, kv, xvalid, has = _first_by_key(
                (v, ok), key, sel & kok, gid, cap, s.kind == "min_by"
            )
            out[f"{o}$val"] = xv
            out[f"{o}$key"] = kv
            out[f"{o}$valid"] = xvalid.astype(jnp.int64)
            out[f"{o}$has"] = has.astype(jnp.int64)
        elif s.kind == "approx_percentile":
            if step == "single":
                val, valid = _percentile(
                    (v, ok), sel, gid, cap, float(s.param)
                )
                out[f"{o}$val"] = val
                out[f"{o}$valid"] = valid.astype(jnp.int64)
            else:
                from . import sketches

                K = sketches.KMV_K
                vals, hs = sketches.kmv_accumulate(v, live, gid, cap)
                vals2 = vals.reshape(cap, K)
                hs2 = hs.reshape(cap, K)
                for i in range(K):
                    out[f"{o}$pv{i}"] = vals2[:, i]
                    out[f"{o}$ph{i}"] = hs2[:, i]
                if v.dtype.kind == "f":
                    lo = jnp.where(live, v, jnp.inf)
                    hi = jnp.where(live, v, -jnp.inf)
                else:
                    lo = jnp.where(live, v.astype(jnp.int64), I64_MAX)
                    hi = jnp.where(live, v.astype(jnp.int64), -I64_MAX)
                out[f"{o}$pmin"] = _seg_min(lo, gid, cap)
                out[f"{o}$pmax"] = _seg_max(hi, gid, cap)
        elif s.kind in HOST_STAGED_KINDS:
            raise NotImplementedError(
                f"{s.kind} is host-staged (exec/local.py _host_agg_lanes)"
                " and cannot run inside a traced kernel (mesh path)"
            )
        else:
            raise NotImplementedError(s.kind)
    return out


def _merge_wide_chunks(s, acc_lanes, w, gid, cap, out):
    """Merge shipped wide-sum chunk columns: segment sums + one carry
    pass (chunk sums stay canonical, so cross-worker merges never
    overflow below 2^31 merged partials)."""
    from . import wide_decimal as wd

    o = s.output
    merged = wd.merge_chunk_lanes(
        [acc_lanes[f"{o}$c{i}"][0] for i in range(4)], w, gid, cap
    )
    for i, c in enumerate(merged):
        out[f"{o}$c{i}"] = c


@jax.named_scope("merge_accumulators")
def merge_accumulators(
    specs: Sequence[AggSpec],
    acc_lanes: Dict[str, Lane],
    gid: jnp.ndarray,
    sel: jnp.ndarray,
    capacity: int,
    overflow_flags: Optional[list] = None,
    seg: Optional["SortedSegments"] = None,
) -> Dict[str, jnp.ndarray]:
    """FINAL step: merge partial accumulator rows grouped by gid.  With
    `seg` (gid sorted), `arbitrary` reads its row off the sorted run."""
    out: Dict[str, jnp.ndarray] = {}
    cap = capacity
    w = sel

    def msum(name, zero=0):
        v, _ = acc_lanes[name]
        z = 0.0 if v.dtype.kind == "f" else zero
        out[name] = _seg_sum(jnp.where(w, v, z), gid, cap)

    for s in specs:
        o = s.output
        if s.kind == "approx_distinct":
            from . import sketches

            packed = sketches.hll_merge(
                {i: acc_lanes[f"{o}$hll{i}"][0]
                 for i in range(sketches.HLL_LANES)},
                w, gid, cap,
            )
            for i, arr in packed.items():
                out[f"{o}$hll{i}"] = arr
        elif s.kind == "approx_percentile":
            from . import sketches

            K = sketches.KMV_K
            n = gid.shape[0]
            vals = jnp.stack(
                [acc_lanes[f"{o}$pv{i}"][0] for i in range(K)], axis=1
            )
            hs = jnp.stack(
                [acc_lanes[f"{o}$ph{i}"][0] for i in range(K)], axis=1
            )
            hs = jnp.where(w[:, None], hs, sketches._H_EMPTY)
            mv, mh = sketches.kmv_merge(vals, hs, w, gid, cap)
            mv2 = mv.reshape(cap, K)
            mh2 = mh.reshape(cap, K)
            for i in range(K):
                out[f"{o}$pv{i}"] = mv2[:, i]
                out[f"{o}$ph{i}"] = mh2[:, i]
            lo, _ = acc_lanes[f"{o}$pmin"]
            hi, _ = acc_lanes[f"{o}$pmax"]
            if lo.dtype.kind == "f":
                lo = jnp.where(w, lo, jnp.inf)
                hi = jnp.where(w, hi, -jnp.inf)
            else:
                lo = jnp.where(w, lo, I64_MAX)
                hi = jnp.where(w, hi, -I64_MAX)
            out[f"{o}$pmin"] = _seg_min(lo, gid, cap)
            out[f"{o}$pmax"] = _seg_max(hi, gid, cap)
        elif s.kind in ("count", "count_star", "count_if"):
            msum(f"{o}$count")
        elif s.kind == "avg":
            if s._wide_sum:
                _merge_wide_chunks(s, acc_lanes, w, gid, cap, out)
                msum(f"{o}$count")
                continue
            msum(f"{o}$sum")
            msum(f"{o}$count")
            _merge_overflow_check(
                acc_lanes[f"{o}$sum"][0], w, gid, cap, overflow_flags
            )
        elif s.kind == "sum":
            if s._wide_sum:
                _merge_wide_chunks(s, acc_lanes, w, gid, cap, out)
                msum(f"{o}$valid")
                continue
            msum(f"{o}$val")
            msum(f"{o}$valid")
            _merge_overflow_check(
                acc_lanes[f"{o}$val"][0], w, gid, cap, overflow_flags
            )
        elif s.kind in MOMENT_KINDS:
            msum(f"{o}$sum")
            msum(f"{o}$sumsq")
            msum(f"{o}$count")
        elif s.kind == "geometric_mean":
            msum(f"{o}$sumlog")
            msum(f"{o}$count")
        elif s.kind in BINARY_MOMENT_KINDS:
            for suf in ("$sy", "$sx", "$sxy", "$sxx", "$syy", "$n"):
                msum(o + suf)
        elif s.kind in ("min", "max"):
            from . import wide_decimal as wd

            sv, _ = acc_lanes[f"{o}$val"]
            cv, _ = acc_lanes[f"{o}$valid"]
            has = w & (cv > 0)
            if wd.is_wide(sv):
                out[f"{o}$val"] = _seg_minmax_wide(
                    sv, has, gid, cap, s.kind == "min"
                )
                out[f"{o}$valid"] = _seg_sum(jnp.where(w, cv, 0), gid, cap)
                continue
            if sv.dtype.kind == "f":
                sentinel = jnp.inf if s.kind == "min" else -jnp.inf
            else:
                sentinel = I64_MAX if s.kind == "min" else -I64_MAX
            vv = jnp.where(has, sv, sentinel)
            ext = _seg_min if s.kind == "min" else _seg_max
            out[f"{o}$val"] = ext(vv, gid, cap)
            out[f"{o}$valid"] = _seg_sum(jnp.where(w, cv, 0), gid, cap)
        elif s.kind in ("bool_and", "bool_or"):
            sv, _ = acc_lanes[f"{o}$val"]
            cv, _ = acc_lanes[f"{o}$valid"]
            has = w & (cv > 0)
            if s.kind == "bool_and":
                vv = jnp.where(has, sv, 1)
                out[f"{o}$val"] = _seg_min(vv, gid, cap)
            else:
                vv = jnp.where(has, sv, 0)
                out[f"{o}$val"] = _seg_max(vv, gid, cap)
            out[f"{o}$valid"] = _seg_sum(jnp.where(w, cv, 0), gid, cap)
        elif s.kind in BITWISE_KINDS:
            sv, _ = acc_lanes[f"{o}$val"]
            cv, _ = acc_lanes[f"{o}$valid"]
            has = w & (cv > 0)
            op = {"bitwise_and_agg": "and", "bitwise_or_agg": "or",
                  "bitwise_xor_agg": "xor"}[s.kind]
            out[f"{o}$val"] = _segment_bitwise(sv, has, gid, cap, op)
            out[f"{o}$valid"] = _seg_sum(jnp.where(w, cv, 0), gid, cap)
        elif s.kind == "checksum":
            msum(f"{o}$val")
            msum(f"{o}$valid")
        elif s.kind == "arbitrary":
            sv, _ = acc_lanes[f"{o}$val"]
            cv, _ = acc_lanes[f"{o}$valid"]
            has = w & (cv > 0)
            ridx, ok2 = _first_live_row(has, gid, cap, seg)
            safe = jnp.clip(ridx, 0, gid.shape[0] - 1)
            out[f"{o}$val"] = jnp.where(ok2, sv[safe], jnp.zeros_like(sv[safe]))
            out[f"{o}$valid"] = ok2.astype(jnp.int64)
        elif s.kind in ("min_by", "max_by"):
            sv, _ = acc_lanes[f"{o}$val"]
            kv, _ = acc_lanes[f"{o}$key"]
            xval, _ = acc_lanes[f"{o}$valid"]
            hv, _ = acc_lanes[f"{o}$has"]
            has = w & (hv > 0)
            xv, kk, xvalid, has2 = _first_by_key(
                (sv, xval > 0), kv, has, gid, cap, s.kind == "min_by"
            )
            out[f"{o}$val"] = xv
            out[f"{o}$key"] = kk
            out[f"{o}$valid"] = xvalid.astype(jnp.int64)
            out[f"{o}$has"] = has2.astype(jnp.int64)
        else:
            raise NotImplementedError(s.kind)
    return out


def finalize(
    specs: Sequence[AggSpec], accs: Dict[str, jnp.ndarray]
) -> Dict[str, Lane]:
    """Accumulators -> output lanes (SINGLE/FINAL output step)."""
    out: Dict[str, Lane] = {}
    for s in specs:
        o = s.output
        if s.kind == "approx_distinct" and f"{o}$count" not in accs:
            # sketched (PARTIAL/FINAL) form: HLL estimator
            from . import sketches

            lanes = {i: accs[f"{o}$hll{i}"]
                     for i in range(sketches.HLL_LANES)}
            cap = lanes[0].shape[0]
            c = sketches.hll_cardinality(lanes, cap)
            out[o] = (c, jnp.ones(c.shape, bool))
        elif s.kind == "approx_percentile" and f"{o}$val" not in accs:
            from . import sketches

            K = sketches.KMV_K
            cap = accs[f"{o}$pmin"].shape[0]
            vals = jnp.stack(
                [accs[f"{o}$pv{i}"] for i in range(K)], axis=1
            ).reshape(-1)
            hs = jnp.stack(
                [accs[f"{o}$ph{i}"] for i in range(K)], axis=1
            ).reshape(-1)
            q = float(s.param)
            v, has = sketches.kmv_quantile(vals, hs, cap, q)
            lo = accs[f"{o}$pmin"]
            hi = accs[f"{o}$pmax"]
            # p=0 / p=1 exact; interior estimates clamp into range
            if q <= 0.0:
                v = lo
            elif q >= 1.0:
                v = hi
            else:
                v = jnp.clip(v, lo, hi)
            out[o] = (v, has)
        elif s.kind in ("count", "count_star", "count_if",
                        "approx_distinct"):
            c = accs[f"{o}$count"]
            out[o] = (c, jnp.ones(c.shape, bool))
        elif s.kind == "sum":
            if s._wide_sum:
                from . import wide_decimal as wd

                cs = wd.normalize_chunks(
                    [accs[f"{o}$c{i}"] for i in range(4)]
                )
                cnt = accs[f"{o}$valid"]
                out[o] = (wd.chunks_to_wide(cs), cnt > 0)
                continue
            v = accs[f"{o}$val"]
            cnt = accs[f"{o}$valid"]
            out[o] = (v, cnt > 0)
        elif s.kind in ("min", "max"):
            from . import wide_decimal as wd

            v = accs[f"{o}$val"]
            cnt = accs[f"{o}$valid"]
            zero = jnp.zeros_like(v)
            has = cnt > 0
            if wd.is_wide(v):
                has = has[:, None]
            out[o] = (jnp.where(has, v, zero), cnt > 0)
        elif s.kind == "avg":
            if s._wide_sum:
                from . import wide_decimal as wd

                cs = wd.normalize_chunks(
                    [accs[f"{o}$c{i}"] for i in range(4)]
                )
                cnt = accs[f"{o}$count"]
                den = jnp.maximum(cnt, 1)
                ot, it = s.output_type, s.input_type
                # exact: 128-bit sum rescaled to the output scale, then
                # one round-half-away 128/64 divide (Int128Math.divide)
                num = wd.rescale(wd.chunks_to_wide(cs), ot.scale - it.scale)
                q = wd.div_round(num, den)
                if wd.is_wide_type(ot):
                    out[o] = (q, cnt > 0)
                else:
                    # narrow output: averages are bounded by the input
                    # magnitude, which fits one limb
                    out[o] = (wd.narrow(q), cnt > 0)
                continue
            ssum = accs[f"{o}$sum"]
            cnt = accs[f"{o}$count"]
            den = jnp.maximum(cnt, 1)
            ot = s.output_type
            if ssum.dtype.kind == "f":
                v = ssum / den
            elif ot is not None and ot.name in ("double", "real"):
                # Trino: avg(integer-type) -> double
                v = ssum.astype(ot.np_dtype) / den
            elif ot is not None and ot.is_decimal and s.input_type is not None:
                # rescale sum to output scale before integer divide
                shift = 10 ** (ot.scale - s.input_type.scale)
                num = ssum * shift
                sign = jnp.sign(num)
                anum = jnp.abs(num)
                q = anum // den
                rem = anum - q * den
                v = sign * (q + (2 * rem >= den))
            else:
                v = ssum // den
            out[o] = (v, cnt > 0)
        elif s.kind in MOMENT_KINDS:
            sm = accs[f"{o}$sum"]
            sq = accs[f"{o}$sumsq"]
            cnt = accs[f"{o}$count"]
            n = jnp.maximum(cnt, 1).astype(jnp.float64)
            m2 = jnp.maximum(sq - sm * sm / n, 0.0)
            pop = s.kind in ("var_pop", "stddev_pop")
            if pop:
                var = m2 / n
                valid = cnt > 0
            else:
                var = m2 / jnp.maximum(n - 1, 1.0)
                valid = cnt > 1
            v = jnp.sqrt(var) if s.kind.startswith("stddev") else var
            out[o] = (v, valid)
        elif s.kind == "geometric_mean":
            sl = accs[f"{o}$sumlog"]
            cnt = accs[f"{o}$count"]
            n = jnp.maximum(cnt, 1).astype(jnp.float64)
            out[o] = (jnp.exp(sl / n), cnt > 0)
        elif s.kind in BINARY_MOMENT_KINDS:
            sy = accs[f"{o}$sy"]
            sx = accs[f"{o}$sx"]
            sxy = accs[f"{o}$sxy"]
            sxx = accs[f"{o}$sxx"]
            syy = accs[f"{o}$syy"]
            cnt = accs[f"{o}$n"]
            n = jnp.maximum(cnt, 1).astype(jnp.float64)
            cxy = sxy - sx * sy / n
            cxx = jnp.maximum(sxx - sx * sx / n, 0.0)
            cyy = jnp.maximum(syy - sy * sy / n, 0.0)
            if s.kind == "covar_pop":
                v, valid = cxy / n, cnt > 0
            elif s.kind == "covar_samp":
                v, valid = cxy / jnp.maximum(n - 1, 1.0), cnt > 1
            elif s.kind == "corr":
                den = jnp.sqrt(cxx * cyy)
                v = jnp.where(den > 0, cxy / jnp.maximum(den, 1e-300), 0.0)
                valid = (cnt > 0) & (den > 0)
            elif s.kind == "regr_slope":
                v = jnp.where(cxx > 0, cxy / jnp.maximum(cxx, 1e-300), 0.0)
                valid = (cnt > 0) & (cxx > 0)
            else:  # regr_intercept
                slope = jnp.where(cxx > 0, cxy / jnp.maximum(cxx, 1e-300), 0.0)
                v = (sy - slope * sx) / n
                valid = (cnt > 0) & (cxx > 0)
            out[o] = (v, valid)
        elif s.kind in ("bool_and", "bool_or"):
            v = accs[f"{o}$val"]
            cnt = accs[f"{o}$valid"]
            out[o] = (v.astype(bool), cnt > 0)
        elif s.kind in BITWISE_KINDS or s.kind == "checksum":
            v = accs[f"{o}$val"]
            cnt = accs[f"{o}$valid"]
            out[o] = (v, cnt > 0)
        elif s.kind in ("arbitrary", "approx_percentile"):
            v = accs[f"{o}$val"]
            cnt = accs[f"{o}$valid"]
            out[o] = (v, cnt > 0)
        elif s.kind in ("min_by", "max_by"):
            v = accs[f"{o}$val"]
            xvalid = accs[f"{o}$valid"]
            out[o] = (v, xvalid > 0)
        else:
            raise NotImplementedError(s.kind)
    return out


def group_keys_output(
    key_lanes: Sequence[Lane],
    gid: jnp.ndarray,
    sel: jnp.ndarray,
    capacity: int,
    starts: Optional[jnp.ndarray] = None,
) -> List[Lane]:
    """Representative key values per group id (first selected row wins).
    With `starts` (sorted-gid run starts from SortedSegments), the
    representative is simply the run-head row — no segment pass."""
    n = gid.shape[0]
    if starts is not None:
        present = starts < n
        safe = jnp.clip(starts, 0, n - 1)
        out = []
        for v, ok in key_lanes:
            out.append((v[safe], ok[safe] & present & sel[safe]))
        return out
    first, present = _first_live_row(sel, gid, capacity, None)
    safe = jnp.clip(first, 0, n - 1)
    out = []
    for v, ok in key_lanes:
        out.append((v[safe], ok[safe] & present))
    return out
