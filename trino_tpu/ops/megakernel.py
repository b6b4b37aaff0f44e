"""Fused scan->filter->aggregate megakernels.

The hot TPC-H aggregation fragments (Q6: filter + global sums; Q1:
filter + low-cardinality grouped multi-aggregate) normally lower to a
chain of XLA ops that each re-read the scan columns from HBM: the
filter mask, one select+sum per aggregate plane, one count per
aggregate.  This module collapses the whole Filter*/Project*/Aggregate
chain over a TableScan into ONE grid-free pallas kernel
(ops/pallas_kernels.fused_agg_sums) that streams every referenced scan
column through VMEM exactly once and accumulates every (term, group)
partial in registers.

The fusion is only attempted when it is PROVEN exact at plan time:

  - every referenced scan column has connector statistics with
    null_fraction == 0 and a known [min, max] range (interval
    arithmetic then bounds every intermediate of the compiled
    expressions);
  - all in-kernel arithmetic stays in int32 (the recorded Mosaic
    constraint: in-kernel int64 conversion recurses), so every
    expression node's proven interval must fit int32;
  - each aggregate input decomposes into int32-safe TERMS whose
    per-chunk partial sums cannot wrap: raw values bounded by
    TERM_MAX, 16-bit planes of values bounded by int32, and for one
    level of oversized products a 16-bit limb split of the long factor
    against a short (<= 15-bit) factor -- the exact decomposition the
    flight-recorder bench rounds validated for Q1's extendedprice *
    (1 - discount) * (1 + tax);
  - every term value is bounded by TERM_MAX, so a term's int64 sum over
    the whole table (stats row count x TERM_MAX) cannot wrap; a NARROW
    accumulator ($val / $sum: merged downstream by int64 addition)
    additionally needs the whole-table sum of its input below 2^62
    (row count x value bound), because its terms recombine by int64
    shifts; a WIDE accumulator (any decimal sum/avg) recombines its
    term sums straight into the four 32-bit chunk lanes
    (wide_decimal.shifted_sum_chunks) and needs only the chunk-lane
    bound of _prove_chunk_lanes.

Anything unproven raises Reject and the executor silently falls back
to the unfused path -- fusion is an optimization, never a semantics
change.  Group keys ride the same mixed-radix dense group-id scheme as
ops/aggregation.direct_group_ids (dictionary/boolean domains, capacity
<= pallas_kernels.MAX_GROUPS) computed INSIDE the kernel, and the
accumulator layout emitted here is byte-identical to
ops/aggregation.accumulate's narrow fast path, so agg_ops.finalize and
the PARTIAL/FINAL exchange contract are reused unchanged.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..expr import ir
from ..plan import nodes as P
from . import aggregation as agg_ops
from . import pallas_kernels as pk
from . import wide_decimal as wd

I32_MAX = 2 ** 31 - 1
# one [CHUNK_ROWS, 128] column of raw values this small sums in int32
# without wrapping (CHUNK_ROWS * TERM_MAX < 2^31)
TERM_MAX = I32_MAX // pk.CHUNK_ROWS
# whole-table int64 sum headroom of a NARROW accumulator: rows * bound
# must stay below this
SUM_GATE = 2 ** 62
# headroom of one chunk lane of a WIDE accumulator before the carry pass
CHUNK_LANE_GATE = 2 ** 62
# short factor cap for the limb split: 0xFFFF * LIMB_B_MAX < 2^31
LIMB_B_MAX = 32767

FUSABLE_KINDS = ("sum", "avg", "count", "count_star")

_CMP = {
    "=": lambda a, b: a == b,
    "<>": lambda a, b: a != b,
    "!=": lambda a, b: a != b,
    "is_distinct": lambda a, b: a != b,  # exact: inputs proven null-free
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
}


class Reject(Exception):
    """Fusion not applicable; the message lands in kernel_profile."""


def _scale(t) -> int:
    return int(t.scale) if getattr(t, "is_decimal", False) else 0


_INT_KINDS = ("bigint", "integer", "smallint", "tinyint", "date",
              "time", "timestamp")


def _int_kind(t) -> bool:
    return bool(getattr(t, "is_decimal", False)) or t.name in _INT_KINDS


@dataclasses.dataclass
class _CV:
    """A compiled kernel value: ``fn(tiles) -> int32 array`` plus the
    interval [lo, hi] and decimal scale proven at plan time."""

    fn: Callable
    lo: int
    hi: int
    scale: int


def _check32(lo: int, hi: int, what: str) -> None:
    if lo < -I32_MAX or hi > I32_MAX:
        raise Reject(f"{what} interval [{lo}, {hi}] exceeds int32")


class _Compiler:
    """Restricted Expr -> in-kernel int32 compiler with interval
    arithmetic.  ``env`` maps scan symbols to their stats-proven
    bounds; every column touched is recorded in ``used`` so the runner
    uploads exactly the referenced tiles."""

    def __init__(self, env: Dict[str, dict]):
        self.env = env
        self.used: List[str] = []

    # -- columns -------------------------------------------------------
    def _info(self, name: str) -> dict:
        info = self.env.get(name)
        if info is None:
            raise Reject(f"column {name} lacks null-free bounded stats")
        return info

    def col(self, name: str) -> _CV:
        info = self._info(name)
        if info.get("dict"):
            raise Reject(f"dictionary column {name} in value position")
        if name not in self.used:
            self.used.append(name)
        return _CV(lambda t, nm=name: t[nm],
                   info["lo"], info["hi"], info["scale"])

    # -- values --------------------------------------------------------
    def value(self, e: ir.Expr) -> _CV:
        if isinstance(e, ir.ColumnRef):
            if e.type.name == "boolean":
                raise Reject("boolean column in value position")
            return self.col(e.name)
        if isinstance(e, ir.Constant):
            if e.value is None:
                raise Reject("NULL constant")
            v = int(e.value)
            _check32(v, v, "constant")
            return _CV(lambda t, c=v: c, v, v, _scale(e.type))
        if isinstance(e, ir.Cast):
            if not (_int_kind(e.type) and _int_kind(e.term.type)):
                raise Reject(f"cast to {e.type.name}")
            return self._rescaled(self.value(e.term), _scale(e.type))
        if isinstance(e, ir.Call):
            return self._call(e)
        raise Reject(f"unfusable value node {type(e).__name__}")

    def _rescaled(self, cv: _CV, scale: int) -> _CV:
        k = scale - cv.scale
        if k < 0:
            raise Reject("rescale down (rounding) in kernel")
        if k == 0:
            return dataclasses.replace(cv, scale=scale)
        m = 10 ** k
        lo, hi = cv.lo * m, cv.hi * m
        _check32(lo, hi, "rescale")
        return _CV(lambda t, f=cv.fn, m=m: f(t) * m, lo, hi, scale)

    def _call(self, e: ir.Call) -> _CV:
        s = _scale(e.type)
        if e.name in ("add", "subtract"):
            l = self._rescaled(self.value(e.args[0]), s)
            r = self._rescaled(self.value(e.args[1]), s)
            if e.name == "add":
                lo, hi = l.lo + r.lo, l.hi + r.hi
                fn = lambda t, f=l.fn, g=r.fn: f(t) + g(t)  # noqa: E731
            else:
                lo, hi = l.lo - r.hi, l.hi - r.lo
                fn = lambda t, f=l.fn, g=r.fn: f(t) - g(t)  # noqa: E731
            _check32(lo, hi, e.name)
            return _CV(fn, lo, hi, s)
        if e.name == "negate":
            v = self.value(e.args[0])
            v = self._rescaled(v, s)
            return _CV(lambda t, f=v.fn: -f(t), -v.hi, -v.lo, s)
        if e.name == "multiply":
            l = self.value(e.args[0])
            r = self.value(e.args[1])
            corners = [l.lo * r.lo, l.lo * r.hi, l.hi * r.lo, l.hi * r.hi]
            lo, hi = min(corners), max(corners)
            _check32(lo, hi, "product")
            prod = _CV(
                lambda t, f=l.fn, g=r.fn: f(t) * g(t),
                lo, hi, l.scale + r.scale,
            )
            return self._rescaled(prod, s)
        raise Reject(f"unfusable call {e.name}")

    # -- predicates ----------------------------------------------------
    def pred(self, e: ir.Expr):
        if isinstance(e, ir.Logical):
            fns = [self.pred(t) for t in e.terms]
            if e.op == "and":
                return lambda t, fs=fns: _fold(fs, t, True)
            if e.op == "or":
                return lambda t, fs=fns: _fold(fs, t, False)
            raise Reject(f"logical op {e.op}")
        if isinstance(e, ir.Not):
            f = self.pred(e.term)
            return lambda t, f=f: jnp.logical_not(f(t))
        if isinstance(e, ir.Comparison):
            return self._cmp(e.op, e.left, e.right)
        if isinstance(e, ir.Between):
            lo = self._cmp("<=", e.low, e.value)
            hi = self._cmp("<=", e.value, e.high)
            if e.negate:
                return lambda t, a=lo, b=hi: jnp.logical_not(a(t) & b(t))
            return lambda t, a=lo, b=hi: a(t) & b(t)
        if isinstance(e, ir.In):
            if not all(isinstance(i, ir.Constant) for i in e.items):
                raise Reject("IN over non-constant items")
            eqs = [self._cmp("=", e.value, i) for i in e.items]
            if e.negate:
                return lambda t, fs=eqs: jnp.logical_not(_fold(fs, t, False))
            return lambda t, fs=eqs: _fold(fs, t, False)
        if isinstance(e, ir.Constant) and e.type.name == "boolean":
            if e.value is None:
                raise Reject("NULL boolean constant")
            return lambda t, c=bool(e.value): c
        if isinstance(e, ir.ColumnRef) and e.type.name == "boolean":
            info = self._info(e.name)
            if not info.get("bool"):
                raise Reject("boolean column lacks stats")
            if e.name not in self.used:
                self.used.append(e.name)
            return lambda t, nm=e.name: t[nm] != 0
        raise Reject(f"unfusable predicate node {type(e).__name__}")

    def _cmp(self, op: str, left: ir.Expr, right: ir.Expr):
        cmp = _CMP.get(op)
        if cmp is None:
            raise Reject(f"comparison op {op}")
        l = self.value(left)
        r = self.value(right)
        m = max(l.scale, r.scale)
        l = self._rescaled(l, m)
        r = self._rescaled(r, m)
        return lambda t, f=l.fn, g=r.fn, c=cmp: c(f(t), g(t))

    # -- aggregate-input term decomposition ----------------------------
    def decompose(self, e: ir.Expr) -> Tuple[List[Tuple[Callable, int]], int]:
        """Split one aggregate input into int32-safe (fn, shift) terms
        whose shifted per-group sums recombine to the exact value sum.
        Returns (terms, value upper bound)."""
        try:
            cv = self.value(e)
        except Reject:
            cv = None
        terms: List[Tuple[Callable, int]] = []
        if cv is not None:
            if cv.lo < 0:
                raise Reject("negative aggregate input")
            _planes(cv.fn, cv.hi, 0, terms)
            return terms, cv.hi
        # one oversized level allowed: a product whose long factor fits
        # int32 and whose short factor fits 15 bits -- split the long
        # factor into 16-bit limbs, multiply each by the short factor
        if not (isinstance(e, ir.Call) and e.name == "multiply"
                and len(e.args) == 2):
            raise Reject("aggregate input exceeds int32 and is no product")
        a = self.value(e.args[0])
        b = self.value(e.args[1])
        if a.hi < b.hi:
            a, b = b, a
        k = _scale(e.type) - (a.scale + b.scale)
        if k < 0:
            raise Reject("oversized product rescales down")
        b = self._rescaled(b, b.scale + k)  # fold 10^k into short factor
        if a.lo < 0 or b.lo < 0:
            raise Reject("negative factor in oversized product")
        if b.hi > LIMB_B_MAX:
            raise Reject("no short factor for limb split")
        hi_lo = 0xFFFF * b.hi
        hi_hi = (a.hi >> 16) * b.hi
        _check32(0, max(hi_lo, hi_hi), "limb product")
        p_lo = lambda t, f=a.fn, g=b.fn: (f(t) & 0xFFFF) * g(t)  # noqa: E731
        p_hi = lambda t, f=a.fn, g=b.fn: (f(t) >> 16) * g(t)  # noqa: E731
        _planes(p_lo, hi_lo, 0, terms)
        _planes(p_hi, hi_hi, 16, terms)
        return terms, a.hi * b.hi


def _planes(fn: Callable, hi: int, shift: int, out: list) -> None:
    """Append fn as one raw term, or as two 16-bit planes when one
    chunk-column of raw values could wrap int32."""
    if hi <= TERM_MAX:
        out.append((fn, shift))
        return
    out.append(((lambda t, f=fn: f(t) & 0xFFFF), shift))
    out.append(((lambda t, f=fn: f(t) >> 16), shift + 16))


def _fold(fns, tiles, conj: bool):
    acc = None
    for f in fns:
        v = f(tiles)
        if acc is None:
            acc = v
        else:
            acc = (acc & v) if conj else (acc | v)
    return acc


def _conjuncts(e: ir.Expr) -> List[ir.Expr]:
    if isinstance(e, ir.Logical) and e.op == "and":
        out: List[ir.Expr] = []
        for t in e.terms:
            out.extend(_conjuncts(t))
        return out
    return [e]


# ----------------------------------------------------------------------
# matcher


def match_plan(node: P.Aggregate):
    """The part of the fusion rule the plan alone decides, with no trace
    context: (scan, symbol -> expression over scan symbols, predicate
    conjuncts), or Reject.  The rest (the retry rung, dictionaries, the
    group cap, int32 bounds) is decided while tracing, in `_run`."""
    if node.step not in ("single", "partial"):
        raise Reject(f"step {node.step}")
    if not node.aggs:
        raise Reject("no aggregates")
    for a in node.aggs:
        if a.distinct:
            raise Reject("DISTINCT aggregate")
        if a.kind not in FUSABLE_KINDS:
            raise Reject(f"aggregate kind {a.kind}")
    chain: List[P.PlanNode] = []
    cur = node.source
    while isinstance(cur, (P.Project, P.Filter)):
        chain.append(cur)
        cur = cur.source
    if not isinstance(cur, P.TableScan):
        raise Reject("source is not a Filter/Project chain over a scan")
    scan = cur
    # compose the chain bottom-up into expressions over scan symbols
    mapping: Dict[str, ir.Expr] = {
        s: ir.ColumnRef(t, s) for s, t in scan.types
    }
    preds: List[ir.Expr] = []
    for nd in reversed(chain):
        if isinstance(nd, P.Filter):
            preds.extend(_conjuncts(ir.replace_refs(nd.predicate, mapping)))
        else:
            mapping = {
                s: ir.replace_refs(e, mapping) for s, e in nd.assignments
            }
    # group ids are mixed-radix over dictionary/boolean domains alone
    for k in node.keys:
        e = mapping.get(k)
        if not isinstance(e, ir.ColumnRef):
            raise Reject(f"group key {k} is not a scan column")
        if not (e.type.is_dictionary or e.type.name == "boolean"):
            raise Reject(f"group key {e.name} is not low-cardinality")
    return scan, mapping, preds


def plan_reads(node: P.Aggregate):
    """(scan, the scan symbols the fused kernel reads for `node`:
    predicates, summed inputs, group keys), or None where `match_plan`
    rejects it."""
    try:
        scan, mapping, preds = match_plan(node)
    except Reject:
        return None
    exprs = list(preds) + [mapping.get(k) for k in node.keys] + [
        mapping.get(a.arg) for a in node.aggs
        if a.kind not in ("count", "count_star")
    ]
    names: List[str] = []
    for e in exprs:
        if e is None:
            continue
        names += [c for c in ir.referenced_columns(e) if c not in names]
    return scan, names


def _column_env(ex, scan: P.TableScan, types) -> Tuple[Dict[str, dict], object]:
    try:
        stats = ex.metadata.table_statistics(scan.catalog, scan.table)
    except Exception:
        raise Reject("no table statistics")
    env: Dict[str, dict] = {}
    for sym, col in scan.assignments:
        t = types[sym]
        cs = stats.columns.get(col)
        if cs is None or cs.null_fraction:
            continue  # unusable: any reference rejects fusion
        if t.is_dictionary:
            env[sym] = {"dict": True}
            continue
        if t.name == "boolean":
            env[sym] = {"lo": 0, "hi": 1, "scale": 0, "bool": True}
            continue
        if cs.min_value is None or cs.max_value is None:
            continue
        lo = int(math.floor(cs.min_value))
        hi = int(math.ceil(cs.max_value))
        if lo < -I32_MAX or hi > I32_MAX:
            continue
        env[sym] = {"lo": lo, "hi": hi, "scale": _scale(t)}
    return env, stats


def _key_domains(ex, node: P.Aggregate, mapping, types, env):
    """Mixed-radix dense grouping over dictionary/boolean scan columns
    -- the in-kernel mirror of ops/aggregation.direct_group_ids (radix
    dom+1 per key keeps the unfused NULL slot layout, so capacities and
    group ids agree exactly with the fallback path)."""
    doms: List[Tuple[str, str, int]] = []
    cap = 1
    for k in node.keys:
        sk = mapping[k].name  # a dictionary/boolean column (match_plan)
        info = env.get(sk)
        if info is None:
            raise Reject(f"group key {sk} lacks null-free stats")
        if info.get("dict"):
            d = ex.dicts.get(sk)
            if d is None or len(d) == 0:
                raise Reject(f"no dictionary for key {sk}")
            dom = len(d)
        else:  # boolean
            dom = 2
        doms.append((k, sk, dom))
        cap *= dom + 1
    if node.keys and cap > pk.MAX_GROUPS:
        raise Reject(f"group capacity {cap} > {pk.MAX_GROUPS}")
    return doms, (cap if node.keys else 1)


def _prove_chunk_lanes(rows_bound: int, shifts: Sequence[int]) -> None:
    """Plan-time proof for wd.shifted_sum_chunks over one wide
    aggregate's terms: every term sum is at most TERM_MAX * rows_bound
    in magnitude (the mesh merges these per-term sums across shards, so
    the bound is table-wide there too); cut at 32 bits and shifted by
    sh % 32 it adds under 2^32 to one chunk lane and under
    2^31 + (high part << sh % 32) to the next."""
    term = TERM_MAX * rows_bound
    if term >= 2 ** 63:
        raise Reject("one term's table-wide sum could exceed int64")
    lane = total = 0
    for sh in shifts:
        if sh >= 96:
            raise Reject(f"term shift {sh} leaves the 128-bit accumulator")
        lane += (3 << 31) + (((term >> 32) + 1) << (sh % 32))
        total += term << sh
    if lane >= CHUNK_LANE_GATE or total >= 2 ** 126:
        raise Reject("wide sum's chunk lanes could exceed int64")


# ----------------------------------------------------------------------
# entry point


def try_fused(ctx, node: P.Aggregate):
    """Attempt the fused megakernel for this Aggregate; returns the
    finished Batch or None (caller runs the unfused path)."""
    ex = ctx.ex
    if ex._megakernel_mode() != "on":
        return None
    try:
        return _run(ctx, node)
    except Reject as r:
        prof = ex.kernel_profile
        prof["fusionRejects"] = prof.get("fusionRejects", 0) + 1
        prof["lastFusionReject"] = str(r)
        from ..obs import journal

        journal.emit(
            journal.FUSION_REJECT,
            query_id=getattr(ex, "query_id", "") or "",
            reason=str(r)[:200],
        )
        return None


def _run(ctx, node: P.Aggregate):
    ex = ctx.ex
    scan, mapping, preds = match_plan(node)
    if getattr(ctx.lowering, "force_wide_mul", False):
        raise Reject("wide-multiply retry rung")
    types = dict(scan.types)
    env, stats = _column_env(ex, scan, types)
    doms, cap = _key_domains(ex, node, mapping, types, env)

    comp = _Compiler(env)
    pred_fns = [comp.pred(p) for p in preds]

    # term 0 is always the live-row count (the $valid/$count lane every
    # fused kind shares); value terms append after it, deduplicated by
    # structural expression equality (sum+avg over one column share)
    terms: List[Tuple[Callable, int]] = [((lambda t: 1), 0)]
    rows_bound = max(int(stats.row_count), 1) + 256  # pad-capacity slack
    input_terms: Dict[ir.Expr, Tuple[List[Tuple[int, int]], int]] = {}
    plans: List[Optional[List[Tuple[int, int]]]] = []
    specs = [a.to_spec() for a in node.aggs]
    past_int64 = 0
    for a, s in zip(node.aggs, specs):
        if a.kind == "count_star":
            plans.append(None)
            continue
        e = mapping.get(a.arg)
        if e is None:
            raise Reject(f"aggregate arg {a.arg} escapes the fused chain")
        if a.kind == "count":
            # null-free inputs make count(x) == count(live rows); only
            # prove the references are null-free, no value needed
            for c in ir.referenced_columns(e):
                if env.get(c) is None:
                    raise Reject(f"count over unproven column {c}")
            plans.append(None)
            continue
        if e not in input_terms:
            tlist, hi = comp.decompose(e)
            slots = []
            for fn, sh in tlist:
                slots.append((len(terms), sh))
                terms.append((fn, sh))
            input_terms[e] = (slots, hi)
        slots, hi = input_terms[e]
        # the ACCUMULATOR decides the proof, not the input: one
        # expression may feed a wide and a narrow aggregate
        if s._wide_sum:
            _prove_chunk_lanes(rows_bound, [sh for _i, sh in slots])
            past_int64 += rows_bound * hi >= SUM_GATE
        elif rows_bound * hi >= SUM_GATE:
            raise Reject("table-wide sum could exceed int64")
        plans.append(slots)

    # the kernel reads each referenced column plus the key columns once
    names = list(comp.used)
    for _k, sk, _dom in doms:
        if sk not in names:
            names.append(sk)

    def emit(tiles):
        p = _fold(pred_fns, tiles, True) if pred_fns else None
        gid = None
        for _k, sk, dom in doms:
            # int32 bounds: python ints would enter jnp.clip as int64
            # scalars, and an in-kernel int64 convert recurses in Mosaic
            code = jnp.clip(tiles[sk], jnp.int32(0), jnp.int32(dom - 1))
            gid = code if gid is None else gid * (dom + 1) + code
        return p, gid, [fn(tiles) for fn, _sh in terms]

    # -- runner (still inside the fragment trace) ----------------------
    b = ctx.visit(scan)
    live = b.sel
    cols32 = {}
    for nm in names:
        v, ok = b.lanes[nm]
        if v.ndim != 1 or v.dtype.kind not in ("i", "u"):
            raise Reject(f"column {nm} lane is not a narrow integer")
        if ok is not None:
            live = live & ok
        cols32[nm] = v.astype(jnp.int32)

    n_terms = len(terms)
    # interpret mode is for off-TPU parity tests (megakernels=on); on
    # the chip pk.enabled() holds, so the kernel is a real Mosaic call
    interpret = not pk.enabled()
    assert not (interpret and jax.default_backend() == "tpu"), (
        "fused megakernel would run in pallas interpret mode on a TPU"
    )
    kernel = "megakernel:%s/t%d/g%d" % (scan.table, n_terms, cap)
    with jax.named_scope(kernel):
        sums = pk.fused_agg_sums(
            cols32, live, emit, n_terms, cap, interpret=interpret,
        )
    # mesh shard bodies: each device fused ITS split shard; the trace
    # context merges the int64 (term, group) partials across the mesh
    # before the shared finalize tail (identity on a single device).
    # What crosses the mesh is each TERM's sum, not a recombined total:
    # term values are <= TERM_MAX and rows_bound is the TABLE's row
    # count, so the cross-shard sum stays under TERM_MAX * rows_bound.
    sums = ctx._merge_fused_sums(sums)
    cnt = sums[0]

    accs: Dict[str, jnp.ndarray] = {}
    for s, slots in zip(specs, plans):
        o = s.output
        if slots is None:  # count / count_star
            accs[f"{o}$count"] = cnt
            continue
        if s._wide_sum:
            # term sums -> the 128-bit accumulator's chunk lanes, never
            # through an int64 total (_prove_chunk_lanes held at plan
            # time); the canonical chunks the unfused path emits
            cs = wd.shifted_sum_chunks(
                [sums[i] for i, _sh in slots], [sh for _i, sh in slots],
            )
            for i, c in enumerate(cs):
                accs[f"{o}$c{i}"] = c
            accs[f"{o}$valid" if s.kind == "sum" else f"{o}$count"] = cnt
            continue
        # narrow accumulator: SUM_GATE proved the total fits int64
        val = jnp.zeros_like(cnt)
        for i, sh in slots:
            val = val + (sums[i] << jnp.int64(sh))
        if s.kind == "sum":
            accs[f"{o}$val"] = val
            accs[f"{o}$valid"] = cnt
        else:  # narrow avg
            accs[f"{o}$sum"] = val
            accs[f"{o}$count"] = cnt

    if node.step == "partial":
        out = {
            nm: (v, jnp.ones(v.shape, bool)) for nm, v in accs.items()
        }
    else:
        out = agg_ops.finalize(specs, accs)

    keys_out = []
    if node.keys:
        # arithmetic key decode: slot -> per-key dictionary codes (the
        # mixed-radix inverse of the in-kernel gid); code == dom is the
        # never-hit NULL slot, masked by present anyway
        rem = jnp.arange(cap, dtype=jnp.int64)
        codes: List[jnp.ndarray] = [None] * len(doms)  # type: ignore
        for i in range(len(doms) - 1, -1, -1):
            radix = doms[i][2] + 1
            codes[i] = rem % radix
            rem = rem // radix
        for (k, sk, dom), code in zip(doms, codes):
            kv, _kok = b.lanes[sk]
            keys_out.append((code.astype(kv.dtype), code < dom))
            if k != sk and sk in ex.dicts:
                ex.dicts.setdefault(k, ex.dicts[sk])
        present = cnt > 0
    else:
        present = jnp.ones(1, bool)

    prof = ex.kernel_profile
    prof["fusedAggregates"] = prof.get("fusedAggregates", 0) + 1
    prof["fusedTerms"] = prof.get("fusedTerms", 0) + n_terms
    if past_int64:
        # aggregates the table-wide int64 gate would have refused
        prof["fusedSumsPastInt64"] = (
            prof.get("fusedSumsPastInt64", 0) + past_int64
        )
    ex._record_kernel(kernel, 0.0, True, mode="megakernel")
    return ctx._finish_aggregate(node, keys_out, out, present, cap)
