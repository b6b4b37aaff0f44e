"""Local execution: logical plan -> one jitted XLA program per fragment.

Reference parity: sql/planner/LocalExecutionPlanner.java:393 (fragment ->
OperatorFactory chain) + operator/Driver.java:66 (the page-passing loop).

TPU-first redesign: instead of a pull/push operator loop moving 8192-row
pages between codegen'd operators, the whole fragment is *one traced jax
function* over padded column arrays — XLA fuses scan->filter->project->
aggregate into a single kernel schedule (the PageProcessor, GroupByHash and
accumulator codegen collapse into the compiler).  The host side only:
  1. generates/loads splits (numpy), pads to static tile capacities,
  2. invokes the compiled program,
  3. re-runs with a larger group capacity if the true group count
     overflowed (recompile-on-bucket-change, replacing FlatHash rehash),
  4. compacts the final selection mask and decodes dictionaries.

Batch representation inside the trace: dict[symbol -> (values, valid)] plus
a boolean selection mask 'sel' (the SelectedPositions analog) and an
ordering guarantee flag.  Aggregate group outputs use their group-id order;
Sort/TopN emit compacted, ordered prefixes.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import shapes
from .. import types as T
from ..catalog import CatalogManager, Metadata
from ..expr import ir
from ..expr.lower import LoweringContext, compile_expr
from ..ops import aggregation as agg_ops
from ..ops import join as join_ops
from ..ops import sort as sort_ops
from ..obs import compile_observatory as _compile_obs
from ..ops import window as window_ops
from ..page import Column, FormattedKeys, Page, pad_to, same_dictionary
from ..plan import nodes as P
from ..runtime import Breadcrumb, DeviceFaultError, default_supervisor
from ..spi import Split
from ..utils.metrics import REGISTRY
from ..utils.tracing import TRACER

DEFAULT_GROUP_CAPACITY = 4096


def _shape_summary(tree, limit: int = 24) -> dict:
    """Compact ``lane -> dtype[shape]`` summary of a dispatch's inputs,
    recorded in the crash-forensics breadcrumb before the dispatch."""
    out: dict = {}

    def add(name, v):
        if len(out) < limit and hasattr(v, "shape") and hasattr(v, "dtype"):
            out[name] = "%s%s" % (v.dtype, tuple(v.shape))

    for k, lanes in (tree or {}).items():
        if isinstance(lanes, dict):
            for s, v in lanes.items():
                if isinstance(v, tuple):
                    for i, vi in enumerate(v):
                        add("%s.%s.%d" % (k, s, i), vi)
                else:
                    add("%s.%s" % (k, s), v)
        else:
            add(str(k), lanes)
    return out


class DeviceScanCache:
    """Cross-query scan cache: host merged arrays + padded device lanes.

    The reference streams pages from disk/page-cache every query; here the
    analog of a warm OS page cache is warm HBM — repeated scans of an
    unchanged (connector-versioned) table reuse uploaded device arrays,
    and skip the host->HBM copy (or the on-device regeneration).
    Entries evict in insertion order once the byte budget is exceeded.

    One lock covers the table and its tallies: a streamed query's prefetch
    thread looks tiles up and puts them while the query thread reads, and
    the memory manager may revoke (`drop_all`) from a third."""

    def __init__(self, max_bytes: int = 6 << 30):
        self.max_bytes = max_bytes
        self.entries: Dict[tuple, dict] = {}
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.puts = 0
        self.evictions = 0
        self._lock = threading.Lock()

    def get(self, key: tuple, record: bool = True):
        """record=False for secondary lookups of an already-counted entry
        (the device-lane rebind path re-reads what _load_one_scan found)."""
        with self._lock:
            entry = self.entries.get(key)
            if record:
                if entry is not None:
                    self.hits += 1
                else:
                    self.misses += 1
            return entry

    def put(self, key: tuple, entry: dict, nbytes: int):
        with self._lock:
            self._evict(key)  # a key put again replaces its entry
            while self.bytes + nbytes > self.max_bytes and self.entries:
                self._evict(next(iter(self.entries)))
            entry["nbytes"] = nbytes
            self.entries[key] = entry
            self.bytes += nbytes
            self.puts += 1

    def _evict(self, key: tuple) -> None:
        entry = self.entries.pop(key, None)
        if entry is not None:
            self.bytes -= entry.get("nbytes", 0)
            self.evictions += 1

    def drop(self, keys) -> None:
        """Evict these entries, where held."""
        with self._lock:
            for key in keys:
                self._evict(key)

    def drop_all(self) -> int:
        """Evict everything; returns bytes freed.  Registered with the
        LocalMemoryManager as a revocable resource — warm-HBM cache is
        the first thing to go under memory pressure."""
        with self._lock:
            freed = self.bytes
            self.evictions += len(self.entries)
            self.entries.clear()
            self.bytes = 0
            return freed

    def stats(self) -> Dict[str, int]:
        return {
            "name": "scan_cache",
            "hits": self.hits,
            "misses": self.misses,
            "puts": self.puts,
            "evictions": self.evictions,
            "entries": len(self.entries),
            "bytes": self.bytes,
            "max_bytes": self.max_bytes,
            "heals": 0,
            "invalidations": 0,
        }


class ExecutionError(RuntimeError):
    pass


@dataclasses.dataclass
class Batch:
    lanes: Dict[str, Tuple[jnp.ndarray, jnp.ndarray]]
    sel: jnp.ndarray
    ordered: bool = False  # rows already compacted+ordered (sort output)
    replicated: bool = False  # identical on every mesh device (mesh exec)


def _single_row_plan(n: P.PlanNode) -> bool:
    """Does this plan emit at most one row, statically?  (Global
    aggregates and LIMIT<=1, through projections/filters — filters may
    drop the row, which cross-join semantics must and do preserve.)"""
    if isinstance(n, P.Aggregate):
        return not n.keys and n.step in ("single", "final")
    if isinstance(n, P.Limit):
        return n.count <= 1 or _single_row_plan(n.sources[0])
    if isinstance(n, P.Values):
        return len(n.rows) <= 1
    if isinstance(n, (P.Project, P.Filter)):
        return _single_row_plan(n.sources[0])
    return False


def _contains(plan: P.PlanNode, node_type, pred=None) -> bool:
    if isinstance(plan, node_type) and (pred is None or pred(plan)):
        return True
    return any(_contains(s, node_type, pred) for s in plan.sources)


def _contains_host_aggs(plan: P.PlanNode) -> bool:
    """Aggregates building per-group host dictionaries (array_agg /
    map_agg / listagg) run eagerly, like UNNEST."""
    from ..ops.aggregation import HOST_STAGED_KINDS

    return _contains(
        plan, P.Aggregate,
        lambda n: any(a.kind in HOST_STAGED_KINDS for a in n.aggs),
    )


class _LazyDeviceLane:
    """Placeholder for a scan column that will be GENERATED on-device
    (no host array exists).  Carries the estimated byte size so memory
    accounting sees the eventual HBM footprint."""

    __slots__ = ("nbytes",)

    def __init__(self, nbytes: int):
        self.nbytes = int(nbytes)


def devgen_lane_bytes(spec: dict, cols, rows: int) -> int:
    """HBM bytes of a device-generated scan padded to `rows` rows: each
    column's value lane at the recipe's width, and the one validity plane
    the generator's lanes share."""
    widths = spec.get("widths") or {}
    return int(rows) * (sum(int(widths.get(c, 8)) for c in cols) + 1)


def merge_pages_to_arrays(pages, symbols, types, dicts):
    """Concatenate pages column-wise into host arrays; varchar dictionaries
    from different producers (splits / exchange tasks) are merged with codes
    remapped (the cross-task DictionaryBlock unification).  Fast path: when
    every page shares one dictionary (the common same-connector case) codes
    pass through untouched."""
    tmap = dict(types)
    merged = {}
    total = sum(p.count for p in pages)
    for sym in symbols:
        t = tmap[sym]
        vals_parts: List[np.ndarray] = []
        ok_parts: List[np.ndarray] = []
        live = [p for p in pages if p.count > 0]
        if t.is_dictionary:
            page_dicts = []
            for p in live:
                d = p.by_name(sym).dictionary
                if d is None:
                    raise ExecutionError(f"varchar column {sym} without dict")
                page_dicts.append(d)
            shared = True
            for d in page_dicts[1:]:
                if not same_dictionary(page_dicts[0], d):
                    shared = False
                    break
            if shared:
                dicts[sym] = (
                    page_dicts[0]
                    if page_dicts
                    else np.array([], dtype=object)
                )
                for p in live:
                    col = p.by_name(sym)
                    vals_parts.append(
                        np.asarray(col.values)[: p.count].astype(np.int32)
                    )
                    ok_parts.append(_valid_of(col, p.count))
            else:
                index: Dict[str, int] = {}
                entries: List[str] = []
                for p, d in zip(live, page_dicts):
                    col = p.by_name(sym)
                    codes = np.asarray(col.values)[: p.count]
                    remap = np.empty(len(d), dtype=np.int32)
                    for i, s in enumerate(d):
                        s = str(s)
                        if s not in index:
                            index[s] = len(entries)
                            entries.append(s)
                        remap[i] = index[s]
                    safe = np.clip(codes, 0, max(len(d) - 1, 0))
                    vals_parts.append(
                        np.where(codes >= 0, remap[safe], -1).astype(np.int32)
                    )
                    ok_parts.append(_valid_of(col, p.count))
                dicts[sym] = np.array(entries, dtype=object)
        else:
            for p in live:
                col = p.by_name(sym)
                vals_parts.append(np.asarray(col.values)[: p.count])
                ok_parts.append(_valid_of(col, p.count))
        if vals_parts:
            vals = np.concatenate(vals_parts)
            ok = np.concatenate(ok_parts)
        else:
            vals = np.zeros(0, dtype=t.np_dtype)
            ok = np.zeros(0, dtype=bool)
        merged[sym] = (vals, None if ok.all() else ok)
    return merged, total


def dict_fingerprint(dicts: Dict[str, np.ndarray], symbols) -> int:
    """Exact content hash of the dictionaries for these symbols (dict
    codes are baked into traced programs as constants; identical
    fingerprints are required to share a compiled executable).  blake2b,
    not hash(): the fingerprint flows into compile-cache keys whose
    persistent tier must be stable across processes, and str hashing is
    salted per process."""
    h = hashlib.blake2b(digest_size=8)
    for s in sorted(symbols):
        d = dicts.get(s)
        if d is None:
            continue
        h.update(f"{s}\x1f{len(d)}\x1f".encode())
        if isinstance(d, FormattedKeys):  # its fields say every entry
            h.update(d.fingerprint().encode())
            continue
        for x in d:
            h.update(str(x).encode() + b"\x00")
    return int.from_bytes(h.digest(), "little")


# which lowering each operator of a traced fragment took (one dictionary
# update per traced operator; a warm query of a cached program carries
# none).  Names in PERF.md section 3.  The last five are the mesh's
# (parallel/mesh_executor): its exchanges, in slots one device receives.
OP_COUNTERS = (
    "sortGroupBys", "sortGroupRows", "sortGroupCapacity", "directGroupBys",
    "directJoins", "sortJoins", "semiJoins", "lazyDictionaryColumns",
    "compactions", "compactRows", "compactCapacity",
    "broadcastExchanges", "broadcastExchangeSlots",
    "partitionedExchanges", "partitionedExchangeSlots",
    "groupStateExchangeSlots",
)


def _is_null_expr(e: ir.Expr) -> bool:
    while isinstance(e, ir.Cast):
        e = e.term
    if isinstance(e, ir.Constant) and e.value is None:
        return True
    # a column of UNKNOWN type can only hold NULLs (NULL-literal columns)
    return e.type.name == "unknown"


def _valid_of(col: Column, n: int) -> np.ndarray:
    return (
        np.ones(n, bool)
        if col.validity is None
        else np.asarray(col.validity)[:n]
    )


class LocalExecutor:
    """Executes an optimized logical plan on the local device(s)."""

    trace_ctx_cls: type  # bound after _TraceCtx definition
    # the device mesh scans are sharded over (parallel/mesh_executor);
    # None: one device holds every lane whole
    mesh = None

    def __init__(self, catalogs: CatalogManager, config: Optional[dict] = None):
        self.catalogs = catalogs
        self.metadata = Metadata(catalogs)
        self.config = config or {}
        self.query_id = str(self.config.get("query_id", "query"))
        # the bucketed-batch ABI: every padded capacity in this executor
        # quantizes through one ladder, so split sizes collapse onto a
        # bounded set of compiled shapes per kernel family.  The session
        # resolves the ladder once and shares the object via config;
        # bare executors (tests) resolve from the spec/file props here.
        self.ladder = shapes.resolve_ladder(self.config)
        # scan-node id -> capacity actually dispatched (ladder rung after
        # scan_cap_override): the kernel profile reports padded rows
        # from these, never from recomputed lane alignment
        self._scan_caps: Dict[int, int] = {}
        self.scan_bytes = 0
        # EXPLAIN ANALYZE: id(plan node) -> {rows, bytes, wall_s,
        # device_wall_s, calls} (OperatorStats analog, filled when
        # collect_node_stats is set; obs/opstats.frames_from_plan turns
        # these into the wire-shape timeline frames)
        self.node_stats: Dict[int, dict] = {}
        # query-level blocked time (OperatorStats blocked walls): waiting
        # on a memory reservation / on exchange pages.  Attributed at the
        # task rollup; exchange wait is set by the worker around
        # ExchangeClient.fetch_sources
        self.blocked_memory_s = 0.0
        self.blocked_exchange_s = 0.0
        # per-query TPU kernel profile: one record per compiled (or eager)
        # fragment program — compile wall, recompiles, padded-vs-actual
        # rows, host<->device byte estimates.  Surfaced via EXPLAIN
        # ANALYZE, /v1/query/{id}/profile, the web UI, and bench output.
        self.kernel_profile: Dict[str, object] = {"kernels": [], "summary": {}}
        # scan-node id -> DeviceScanCache key (None when uncacheable)
        self._scan_keys: Dict[int, tuple] = {}
        self._scan_nodes: Dict[int, P.TableScan] = {}
        # scan-node id -> dictionary-content fingerprint (jit-key part)
        self._scan_dictfp: Dict[int, int] = {}
        # scan-node id -> on-device generation spec (connector-provided;
        # lanes materialize in HBM, no host arrays exist)
        self._devgen: Dict[int, dict] = {}
        # supervised dispatch boundary: session/worker-owned supervisor
        # when wired, process default otherwise (bare executors in tests)
        self.supervisor = self.config.get("device_supervisor") \
            or default_supervisor()
        self.device_bytes = 0
        # True while re-executing on the CPU backend after a device fault:
        # dispatches bypass supervision (the watchdog side thread would
        # escape the thread-local jax.default_device context).  Inherited
        # through the config so spill/streaming sub-executors created
        # mid-fallback stay on the CPU path too.
        self._device_fallback = bool(self.config.get("_in_device_fallback"))

    # ------------------------------------------------------------------
    def execute(self, plan: P.PlanNode) -> Page:
        assert isinstance(plan, P.Output)
        if isinstance(plan.source, P.TableWriter):
            return self._execute_write(plan.source)
        sup = self.supervisor
        if not self._device_fallback:
            sup.maybe_probe()
            if not sup.healthy():
                # device already out: degrade up front (or refuse with
                # the structured error when fallback is disabled)
                bc = Breadcrumb(
                    "pre-dispatch", query_id=self.query_id,
                    task_id=str(self.config.get("task_id") or ""),
                    mode="gate",
                )
                fault = DeviceFaultError(
                    "device_" + sup.device_state().lower(), bc
                )
                if not self._cpu_fallback_enabled():
                    raise fault
                return self._run_cpu_fallback(plan, fault)
        try:
            return self._execute_inner(plan)
        except DeviceFaultError:
            if self._device_fallback or not self._cpu_fallback_enabled():
                raise
            return self._run_cpu_fallback(plan, None)

    def _cpu_fallback_enabled(self) -> bool:
        v = self.config.get("device_cpu_fallback", True)
        if isinstance(v, str):
            v = v.strip().lower() not in ("false", "0", "no", "off", "")
        return bool(v)

    def _run_cpu_fallback(self, plan: P.PlanNode, fault) -> Page:
        """Degraded mode: re-run the whole fragment eagerly on the CPU
        backend.  The faulted device's compiled programs and cached
        device arrays are unusable, so jit and the scan cache are
        disabled for the retry; the supervisor keeps advertising the
        sick device so schedulers route around this node meanwhile."""
        sup = self.supervisor
        sup.note_fallback_attempt(query_id=self.query_id)
        orig_config = self.config
        cfg = dict(orig_config)
        cfg["jit_fragments"] = False
        cfg["scan_cache"] = None
        cfg["device_generation"] = False
        cfg["_in_device_fallback"] = True
        self.config = cfg
        self._preloaded = None
        self._device_fallback = True
        # compiled devgen generators are bound to the faulted device;
        # drop them so a recovered device recompiles fresh executables
        from ..connectors import tpch_device

        tpch_device.clear_jit_cache()
        try:
            with jax.default_device(jax.devices("cpu")[0]):
                page = self.execute(plan)
            sup.note_fallback_completed()
            return page
        finally:
            self.config = orig_config
            self._device_fallback = False

    # -- supervised dispatch helpers -----------------------------------
    def _dispatch_crumb(self, kernel: str, mode: str, tree=None) -> Breadcrumb:
        bc = Breadcrumb(
            kernel,
            query_id=self.query_id,
            task_id=str(self.config.get("task_id") or ""),
            mode=mode,
            shapes=_shape_summary(tree),
            hbm_reserved_bytes=getattr(self, "device_bytes", 0),
        )
        # forensics ride the per-query kernel profile too (EXPLAIN
        # ANALYZE / /v1/query/{id}/profile / bench artifacts)
        self.kernel_profile["last_breadcrumb"] = bc.to_dict()
        return bc

    def _dispatch(self, thunk, bc: Breadcrumb):
        if self._device_fallback:
            return thunk()
        return self.supervisor.dispatch(thunk, bc)

    def _device_get(self, objs, bc: Breadcrumb):
        if self._device_fallback:
            return jax.device_get(objs)  # dispatch-guard: ok
        return self.supervisor.device_get(objs, bc)

    def _megakernel_mode(self) -> str:
        """Effective fused scan->filter->aggregate mode: 'on'/'off'.
        Session prop `megakernels`: 'auto' fuses only where the pallas
        TPU path is live (interpret-mode fusion on CPU would just slow
        eager tests down); 'on' forces fusion (interpret mode off-TPU —
        how the parity tests drive the fused path); 'off' disables."""
        v = str(self.config.get("megakernels", "auto") or "auto").lower()
        if v not in ("auto", "on", "off"):
            v = "auto"
        if v == "auto":
            from ..ops import pallas_kernels

            if self._device_fallback or not pallas_kernels.enabled():
                return "off"
            return "on"
        return v

    # ------------------------------------------------------------------
    def _execute_inner(self, plan: P.PlanNode) -> Page:
        # out-of-core path: when the estimated scan working set exceeds the
        # memory limit and the plan allows it, aggregate in split batches
        # (MemoryRevokingScheduler -> spill, host RAM as the spill tier)
        limit = self.config.get("memory_limit_bytes")
        if limit and self.config.get("spill_enabled", True):
            with TRACER.span("stream_plan"):
                out_of_core = self._plan_out_of_core(plan, int(limit))
            if out_of_core is not None:
                return out_of_core()
        # 1. host side: load scans, collect dictionaries — or adopt the
        # arrays a streaming prefetcher loaded on a background thread
        # while the previous tile computed on-device (double buffering)
        pool = self.config.get("memory_pool")
        manager = self.config.get("memory_manager")
        self.device_bytes = 0
        exceeded = None
        with TRACER.span("load_scans"):
            pre = getattr(self, "_preloaded", None)
            if pre is not None and pre[0] is plan:
                _, scans, dicts, counts = pre
                self._preloaded = None
            else:
                scans = {}
                dicts = {}
                counts = {}
                self._load_scans(plan, scans, dicts, counts)
            self._account_memory(scans, limit)
            if manager is not None:
                # HBM tier: every kernel is static-shape, so the device
                # working set (padded batches + compiled program) is known
                # before dispatch; a query that would blow HBM is blocked,
                # spilled via revocation, or failed cleanly here instead
                # of kernel-faulting the backend
                from ..memory import QueryKilledError
                from ..utils.memory import ExceededMemoryLimitError
                from .streaming import estimate_program_bytes

                est = int(max(self.scan_bytes,
                              estimate_program_bytes(self, plan)))
                try:
                    _blk_t0 = time.perf_counter()
                    manager.reserve(
                        self.query_id, est, tier="device",
                        timeout=float(
                            self.config.get("memory_blocked_timeout_s")
                            or 0.0
                        ),
                    )
                    self.blocked_memory_s += time.perf_counter() - _blk_t0
                    self.device_bytes = est
                except ExceededMemoryLimitError as exc:
                    manager.free(
                        self.query_id, self.scan_bytes, tier="host"
                    )
                    self.scan_bytes = 0
                    exceeded = exc
        if exceeded is not None:
            # outside the span: a streamed re-run opens its own phases
            if not isinstance(exceeded, QueryKilledError):
                out = self._try_forced_streaming(plan)
                if out is not None:
                    return out
            raise exceeded
        try:
            self.dicts = dicts
            self._ladder_start(plan, counts)

            use_jit = (
                self.config.get("jit_fragments")
                and not self.config.get("collect_node_stats")
                and not _contains(plan, (P.Unnest, P.MatchRecognize))
                and not _contains_host_aggs(plan)
                # unversioned sources (system tables, hive files) may change
                # without shape changes: no safe compiled-fragment reuse
                and all(
                    self._scan_keys.get(nid) is not None
                    for nid in scans
                    if nid in self._scan_nodes
                )
            )
            for attempt in range(7):
                # the observatory classifies attempt>0 compiles as
                # ladder rungs (capacity/fallback re-traces)
                self._ladder_attempt = attempt
                # ONE device_get for all control scalars AND the output
                # lanes (each device_get is a host sync; on the rare
                # retry the prefetched outputs are simply discarded).
                # Dispatch is async, so a runtime error can surface
                # either at the fn call or at device_get: the handler
                # wraps both.
                try:
                    if use_jit:
                        (out_lanes, sel, ordered, checks, dups, colls,
                         wides, sflags) = self._run_jitted(
                            plan, scans, counts
                        )
                    else:
                        eager_start = time.time()
                        ctx = self.trace_ctx_cls(self, scans, counts)
                        bc = self._dispatch_crumb(
                            "eager-%d" % attempt, "eager", scans
                        )
                        self._last_crumb = bc
                        with TRACER.span("launch"):
                            out_lanes, sel, ordered, checks = (
                                self._dispatch(
                                    lambda: self._run(plan, ctx), bc
                                )
                            )
                        self._note_op_counts(ctx.op_counts)
                        dups = ctx.dup_checks
                        colls = ctx.collision_checks
                        wides = ctx.lowering.overflow_flags
                        sflags = ctx.sum_overflow
                        # eager mode has no XLA compile step; the trace
                        # wall is the honest analog (and each ladder rung
                        # re-traces, so rungs count as recompiles)
                        ev = _compile_obs.record_compile(
                            kernel="eager-%d" % attempt,
                            family=self._compile_family(plan),
                            mode="eager",
                            shapes=_shape_summary(scans),
                            shape_sig=self._compile_shape_sig(counts),
                            actual_rows=sum(
                                int(c) for c in counts.values()
                            ),
                            padded_rows=self._padded_rows(counts),
                            compile_wall_s=time.time() - eager_start,
                            query_id=self.query_id,
                            task_id=str(
                                self.config.get("task_id") or ""
                            ),
                            node_id=str(
                                self.config.get("node_id") or ""
                            ),
                            ladder_attempt=attempt,
                            scan_rows=[
                                int(c) for c in counts.values()
                            ],
                        )
                        self._record_kernel(
                            "eager-%d" % attempt,
                            compile_s=time.time() - eager_start,
                            cached=False,
                            mode="eager",
                            cause=ev["cause"],
                        )
                    last = getattr(self, "_last_crumb", None)
                    with TRACER.span("device_get"):
                        (dup_vals, check_vals, coll_vals, wide_vals,
                         sflag_vals, host_lanes, sel_np) = self._device_get(
                            ([d for _, d in dups],
                             [ng for ng, _, _ in checks],
                             list(colls), list(wides), list(sflags),
                             {s: out_lanes[s] for s in plan.symbols}, sel),
                            self._dispatch_crumb(
                                last.kernel if last else "device_get",
                                "device_get",
                            ),
                        )
                except jax.errors.JaxRuntimeError as e:
                    # a compile-time HBM OOM is PERMANENT for the
                    # monolithic program — XLA's buffer assignment proved
                    # it cannot fit the chip — so run the plan as
                    # streaming tiles instead.  Every other runtime error
                    # surfaces as it is: a retry would only turn a real
                    # error into a slower, different run.
                    if "Ran out of memory" in str(e):
                        stream_page = self._try_forced_streaming(plan)
                        if stream_page is not None:
                            return stream_page
                    raise
                if self._ladder_settled(
                    [n for n, _ in dups], dup_vals, coll_vals, wide_vals,
                    [(cap, kind) for _, cap, kind in checks], check_vals,
                    sflag_vals,
                ):
                    break
            else:
                raise ExecutionError("group capacity overflow after retries")

            self._ladder_remember(plan)
            with TRACER.span("materialize_host"):
                self._finalize_kernel_profile(
                    scans, counts, host_lanes, sel_np
                )
                return self._materialize_host(plan, host_lanes, sel_np)
        finally:
            if manager is not None:
                manager.free(self.query_id, self.scan_bytes, tier="host")
                if self.device_bytes:
                    manager.free(
                        self.query_id, self.device_bytes, tier="device"
                    )
            elif pool is not None:
                pool.free(self.query_id, self.scan_bytes)

    def _ladder_start(self, plan, counts) -> None:
        """Set the retry ladder's state for one execution of `plan`: the
        capacities the last execution of it settled on when the session
        remembers them, else the first rung, its group capacity raised to
        the plan-time estimate over the scans' row `counts` (a rung short
        is a second trace and compile of the whole fragment)."""
        self.group_capacity = int(
            self.config.get("group_capacity", DEFAULT_GROUP_CAPACITY)
        )
        self.join_factor = 1
        self.compact_factor = 1
        # join nodes whose build side turned out to hold duplicate (or
        # hash-colliding) keys: re-traced with the expansion kernel
        # (HashBuilderOperator never assumes uniqueness; we learn it)
        self.force_expansion = set()
        # direct-address joins whose domain proof failed at runtime
        # (stale stats): first rung retries the sorted UNIQUE kernel
        # (still exact for a unique key outside its claimed domain);
        # only a genuine duplicate then escalates to expansion
        self.force_no_direct = set()
        self.group_salt = 0
        self.topn_factor = int(
            self.config.get("topn_initial_factor") or 1
        )
        self.force_wide_mul = False
        # start at the last successful capacities for this plan: the
        # overflow ladder re-runs (and on first touch, re-COMPILES) the
        # whole fragment per rung, so remembering the landing spot makes
        # warm repeats single-shot (FlatHash keeps its size the same way)
        hints = self.config.get("capacity_hints")
        hint = hints.get(id(plan)) if hints is not None else None
        if hint is None:
            est = self._estimate_group_capacity(plan, counts)
            if est is not None:
                self.group_capacity = max(self.group_capacity, est)
            return
        (self.group_capacity, self.join_factor, self.topn_factor,
         self.force_wide_mul, forced, _) = hint[:6]
        self.compact_factor = hint[6] if len(hint) > 6 else 1
        self.force_no_direct = set(hint[7]) if len(hint) > 7 else set()
        self.force_expansion = set(forced)

    def _ladder_settled(self, dup_nodes, dup_vals, coll_vals, wide_vals,
                        limits, check_vals, sflag_vals) -> bool:
        """Read one attempt's control scalars: True when the attempt
        stands, else the ladder state has moved to the next rung (a
        forced kernel, a fresh salt, a larger capacity) and the fragment
        is to be traced and run again."""
        fell_back = False
        for join_node, dup in zip(dup_nodes, dup_vals):
            if int(dup) > 0:
                if join_node is None:
                    # ordinal from a foreign trace did not resolve
                    # in this plan (should be impossible for
                    # fingerprint-matched plans): no node to force
                    raise ExecutionError(
                        "duplicate build keys in unresolvable join"
                    )
                if (
                    getattr(join_node, "direct_domain", None) is not None
                    and id(join_node) not in self.force_no_direct
                ):
                    # direct-table domain/dup proof failed: retry
                    # on the sorted unique kernel first
                    self.force_no_direct.add(id(join_node))
                else:
                    # duplicate (or colliding) build keys: re-trace
                    # with the many-to-many expansion kernel for
                    # this join
                    self.force_expansion.add(id(join_node))
                fell_back = True
        for cv in coll_vals:
            if int(cv) > 0:
                # locator hash collision in grouping: re-run the
                # fragment under a fresh salt (exactness)
                self.group_salt += 1
                fell_back = True
        for wv in wide_vals:
            if int(wv) > 0 and not self.force_wide_mul:
                # decimal product/quotient near int64 range: re-trace
                # with the 128-bit kernels
                self.force_wide_mul = True
                fell_back = True
        if fell_back:
            return False
        over_kinds = set()
        for ngroups, (cap, kind) in zip(check_vals, limits):
            if int(ngroups) > cap:
                over_kinds.add(kind)
        if not over_kinds:
            # only a settled attempt may raise: a capacity overflow or
            # collision retry piles unrelated groups into one segment,
            # making the shadow flag spurious
            for sv in sflag_vals:
                if int(sv) > 0:
                    raise ExecutionError(
                        "sum overflows the bigint accumulator"
                    )
            return True
        if "group" in over_kinds:
            self.group_capacity *= 8
        if "join" in over_kinds:
            self.join_factor *= 8
        if "topn" in over_kinds:
            self.topn_factor *= 8
        if "compact" in over_kinds:
            # x8 rapidly reaches the input width, where _maybe_compact
            # becomes a no-op — a bad estimate costs at most a couple
            # of recompiles, never a loop
            self.compact_factor *= 8
        return False

    def _ladder_remember(self, plan) -> None:
        """Keep the rung this execution settled on for the next one."""
        hints = self.config.get("capacity_hints")
        if hints is None:
            return
        # the plan reference keeps id(plan) stable (no reuse after gc)
        hints[id(plan)] = (
            self.group_capacity, self.join_factor,
            self.topn_factor, self.force_wide_mul,
            frozenset(self.force_expansion), plan,
            self.compact_factor,
            frozenset(self.force_no_direct),
        )
        for k in list(hints)[:-512]:
            hints.pop(k, None)

    # ------------------------------------------------------------------
    def _plan_out_of_core(self, plan, limit: int):
        """The out-of-core planners, tried in order before the resident
        path; returns the chosen path as a thunk, or None to stay
        resident."""
        from . import spill, streaming

        # DISTINCT aggregation first: the streaming fragmenter keeps
        # a distinct Aggregate single-step behind one hash exchange,
        # which locally gathers every input row into one in-memory
        # fragment — the spill rewrite partitions host-side instead
        sp = spill.plan_distinct_spill(self, plan, limit)
        if sp is not None:
            return lambda: spill.execute_spilled_distinct(self, plan, *sp)
        # streaming (fragment-tiled) execution next: the general
        # bounded-working-set path; shape-matched spill rewrites
        # remain for plans the fragmenter cannot tile
        frags = streaming.plan_streaming(self, plan, limit)
        if frags is not None:
            return lambda: streaming.execute_streaming(
                self, plan, frags, limit
            )
        for planner, run in (
            (spill.plan_spill, spill.execute_spilled_aggregation),
            (spill.plan_join_spill, spill.execute_spilled_join),
            (spill.plan_sort_spill, spill.execute_spilled_sort),
            (spill.plan_window_spill, spill.execute_spilled_window),
        ):
            sp = planner(self, plan, limit)
            if sp is not None:
                return lambda: run(self, plan, *sp)
        return None

    def _try_forced_streaming(self, plan) -> Optional[Page]:
        """Compile-OOM fallback: re-run the query through the streaming
        tiled executor even though the scan-bytes gate did not trigger —
        XLA already proved the monolithic program exceeds HBM.  Returns
        None when the plan is untileable or streaming itself fails (the
        caller then surfaces the ORIGINAL compile error)."""
        limit = self.config.get("memory_limit_bytes")
        if not (limit and self.config.get("spill_enabled", True)):
            return None
        if not isinstance(plan, P.Output):
            return None
        from . import streaming

        try:
            frags = streaming.plan_streaming(
                self, plan, int(limit), force=True
            )
            if frags is None:
                return None
            out = streaming.execute_streaming(
                self, plan, frags, int(limit)
            )
        except Exception:
            return None
        if out is not None:
            from ..obs import journal

            journal.emit(
                journal.FORCED_STREAMING, query_id=self.query_id,
                severity=journal.WARN,
                fragments=len(frags) if hasattr(frags, "__len__") else 0,
            )
        return out

    # ------------------------------------------------------------------
    def _execute_write(self, w: P.TableWriter) -> Page:
        """INSERT/CTAS/DELETE execution (TableWriterOperator +
        TableFinishOperator collapsed: run the source query, stream the
        result into the connector PageSink, commit at finish())."""
        conn = self.catalogs.get(w.catalog)
        md = conn.metadata()
        if w.create_schema is not None:
            from ..spi import ColumnSchema, TableSchema

            if w.if_not_exists and w.table in md.list_tables():
                return Page(
                    [Column(T.BIGINT, np.zeros(1, dtype=np.int64))], 1,
                    ["rows"],
                )
            md.create_table(
                TableSchema(
                    w.table,
                    tuple(ColumnSchema(c, t) for c, t in w.create_schema),
                )
            )
        before = 0
        if w.report_deleted or w.count_mode == "merge":
            before = int(md.get_table_statistics(w.table).row_count)
        names = list(w.columns)
        if w.count_symbol is not None:
            names.append("__update_count__")
        inner = P.Output(
            w.source, tuple(names), tuple(w.source.output_symbols())
        )
        page = self.execute(inner)
        sink = conn.page_sink_provider().create_sink(
            w.table, list(w.columns), overwrite=w.overwrite
        )
        sink.append(page)
        written = sink.finish()
        if w.count_symbol is not None and w.count_mode == "merge":
            m = np.asarray(
                page.by_name("__update_count__").values
            )[: page.count]
            updates = int((m == 1).sum())
            inserts = int((m == 2).sum())
            deletes = before + inserts - page.count
            result = updates + inserts + deletes
        elif w.count_symbol is not None:
            marker = page.by_name("__update_count__")
            result = int(
                np.asarray(marker.values)[: page.count].sum()
            )
        elif w.report_deleted:
            result = before - written
        else:
            result = written
        return Page(
            [Column(T.BIGINT, np.array([result], dtype=np.int64))], 1,
            ["rows"],
        )

    # ------------------------------------------------------------------
    def _load_scans(self, node: P.PlanNode, scans, dicts, counts):
        if isinstance(node, P.TableScan):
            conn = self.catalogs.get(node.catalog)
            splits = conn.split_manager().get_splits(
                node.table, 1, node.constraint
            )
            self._load_one_scan(node, splits, scans, dicts, counts)
            return
        for s in node.sources:
            self._load_scans(s, scans, dicts, counts)

    def _account_memory(self, scans, limit):
        """Reserve the scan working set against the pool and enforce the
        per-query limit (MemoryPool.reserve + ExceededMemoryLimitException).
        Scan arrays dominate this engine's footprint; kernel temporaries are
        proportional and covered by the limit's headroom."""
        from ..utils.memory import ExceededMemoryLimitError

        scan_total = 0
        for arrays in scans.values():
            for v, ok in arrays.values():
                scan_total += (
                    int(v.nbytes) + (int(ok.nbytes) if ok is not None else 0)
                )
        # fragment tasks also hold the raw exchange pages they fetched —
        # counted toward the node's host reservation below, but NOT
        # against the spillability limit: that limit gates the device
        # working set, and exchange buffers stay in host RAM (a streaming
        # sub-fragment legitimately holds pages + merged copies past it)
        total = scan_total + int(getattr(self, "exchange_bytes", 0))
        self.scan_bytes = total
        if limit and scan_total > int(limit):
            raise ExceededMemoryLimitError(
                f"query exceeded memory limit: scan working set "
                f"{scan_total} > {limit} bytes (and plan is not spillable)"
            )
        manager = self.config.get("memory_manager")
        if manager is not None:
            # revoke -> block -> clean-error semantics (and the seeded
            # `oom` fault site) live in the manager; freed after
            # materialize alongside the device-tier reservation.  Time
            # spent blocked in reserve is OperatorStats blocked-on-memory
            import time as _time

            _blk_t0 = _time.perf_counter()
            manager.reserve(
                self.query_id, total, tier="host",
                timeout=float(
                    self.config.get("memory_blocked_timeout_s") or 0.0
                ),
            )
            self.blocked_memory_s += _time.perf_counter() - _blk_t0
            return
        pool = self.config.get("memory_pool")
        if pool is not None:
            pool.reserve(self.query_id, total)  # freed after materialize

    def _scan_cache_key(self, node: P.TableScan, splits):
        conn = self.catalogs.get(node.catalog)
        if not getattr(conn, "cacheable", False):
            return None
        return (
            node.catalog,
            node.table,
            tuple(c for _, c in node.assignments),
            node.constraint,
            tuple(repr(sp) for sp in splits),
            conn.data_version(node.table),
        )

    def _load_one_scan(self, node: P.TableScan, splits, scans, dicts, counts):
        """Load the given splits of one scan into host arrays (shared by
        local execution — all splits — and per-task fragment execution —
        the assigned subset, SqlTaskExecution.addSplitAssignments:256).
        Per-split string dictionaries are merged with codes remapped, so
        connectors may emit divergent dictionaries across splits (e.g.
        parquet row-group dictionaries).  Results are cached across queries
        when the connector is versioned-cacheable (DeviceScanCache)."""
        cache: Optional[DeviceScanCache] = self.config.get("scan_cache")
        # ALWAYS computed (even with caching off): the compiled-fragment
        # path keys on it, and streaming tiles must stay jitted — hive's
        # per-TABLE data_version walk is cheap (the table dir only)
        key = self._scan_cache_key(node, splits)
        if cache is not None and key is not None:
            hit = cache.get(key)
            if hit is not None:
                # re-bind cached arrays to this plan's symbols
                sym_of = {c: self._sym_for(node, c)
                          for _, c in node.assignments}
                merged = {}
                for col, lane in hit["merged"].items():
                    merged[sym_of[col]] = lane
                for col, d in hit["dicts"].items():
                    dicts[sym_of[col]] = d
                scans[id(node)] = merged
                counts[id(node)] = hit["total"]
                self._scan_keys[id(node)] = key
                self._scan_nodes[id(node)] = node
                self._scan_dictfp[id(node)] = hit.get("dictfp", 0)
                if hit.get("devgen") is not None:
                    # device-generated scan: keep the recipe so cleared
                    # dev arrays (evicted or dropped) can regenerate
                    self._devgen[id(node)] = hit["devgen"]
                return
        conn = self.catalogs.get(node.catalog)
        cols = [c for _, c in node.assignments]
        self._scan_nodes[id(node)] = node
        if self._try_device_generation(
            conn, node, cols, splits, key, cache, scans, dicts, counts
        ):
            return
        self._load_host_scan(node, splits, key, cache, scans, dicts, counts)

    def _split_pages(self, node: P.TableScan, splits, cols, sym_of):
        """The pages of `splits`, columns named by the plan's symbols and
        each carrying its dictionary (the column's own, else the page
        source's)."""
        provider = self.catalogs.get(node.catalog).page_source_provider()
        pages: List[Page] = []
        for sp in splits:
            src = provider.create_page_source(sp, cols)
            for page in src.pages():
                src_dicts = src.dictionaries()
                new_cols = []
                for c, col in zip(page.names, page.columns):
                    d = (
                        col.dictionary
                        if col.dictionary is not None
                        else src_dicts.get(c)
                    )
                    new_cols.append(
                        Column(col.type, col.values, col.validity, d)
                    )
                pages.append(
                    Page(new_cols, page.count,
                         [sym_of[c] for c in page.names])
                )
        return pages

    def _load_host_scan(self, node: P.TableScan, splits, key, cache,
                        scans, dicts, counts):
        """The host side of `_load_one_scan`: read the splits' pages
        through the connector, merge them into one array per column and
        keep the result in the scan cache."""
        cols = [c for _, c in node.assignments]
        tmap = dict(node.types)
        sym_of = {c: self._sym_for(node, c) for c in cols}
        pages = self._split_pages(node, splits, cols, sym_of)
        symbols = [sym_of[c] for c in cols]
        types = [(s, tmap[s]) for s in symbols]
        merged, total = merge_pages_to_arrays(pages, symbols, types, dicts)
        for s, t in types:
            # dict-typed symbols need a (possibly empty) dictionary even
            # when this task got zero splits/rows, for literal lowering
            if t.is_dictionary and s not in dicts:
                dicts[s] = np.array([], dtype=object)
        scans[id(node)] = merged
        counts[id(node)] = total
        self._scan_keys[id(node)] = key
        fp = dict_fingerprint(dicts, symbols)
        self._scan_dictfp[id(node)] = fp
        if cache is not None and key is not None:
            col_of = {s: c for s, c in node.assignments}
            host_merged = {col_of[s]: lane for s, lane in merged.items()}
            host_dicts = {
                col_of[s]: dicts[s] for s, _ in node.assignments
                if s in dicts
            }
            nbytes = sum(
                int(v.nbytes) + (int(ok.nbytes) if ok is not None else 0)
                for v, ok in merged.values()
            )
            cache.put(
                key,
                {"merged": host_merged, "dicts": host_dicts, "total": total,
                 "dev": {}, "dictfp": fp},
                nbytes,
            )

    def _jit_scan_component(self, nid):
        """Per-scan jit-key part: scan-cache key WITHOUT the split list,
        plus the dictionary-content fingerprint (dict codes are baked
        into traced programs as constants, so equal fingerprints are
        REQUIRED for a safe executable share — and sufficient, together
        with shapes, because the program reads nothing else from the
        split identity)."""
        key = self._scan_keys.get(nid)
        if key is None:
            # keyless sources (RemoteSource without a streaming cache)
            # still carry baked dictionaries: the fingerprint must stay
            # in the component or executables could outlive dict drift
            return (None, self._scan_dictfp.get(nid))
        no_splits = key[:4] + key[5:]
        return (no_splits, self._scan_dictfp.get(nid))

    def _try_device_generation(
        self, conn, node, cols, splits, key, cache, scans, dicts, counts
    ) -> bool:
        """On-device scan materialization: when the connector can produce
        every requested column as a pure function of the row index
        (counter-based generators — connectors/tpch_device.py), skip host
        arrays entirely; _device_lanes runs the generator program straight
        into HBM.  The reference's TPCH connector likewise generates rows
        in-process during the scan (TpchPageSourceProvider) — here the
        'process' is the chip."""
        devgen_fn = getattr(conn, "device_generation", None)
        if devgen_fn is None or not self.config.get(
            "device_generation", True
        ):
            return False
        try:
            spec = self._devgen_spec(devgen_fn, node.table, cols, splits)
        except Exception:  # noqa: BLE001 — any trouble: host path
            spec = None
        if spec is None:
            return False
        prof = self.kernel_profile
        prof["lineCountOrdersHashed"] = (
            prof.get("lineCountOrdersHashed", 0)
            + int(spec.get("orders_hashed", 0))
        )
        sym_of = {c: self._sym_for(node, c) for c in cols}
        count = int(spec["count"])
        merged = {
            sym_of[c]: (
                _LazyDeviceLane(count * spec["widths"].get(c, 8)), None
            )
            for c in cols
        }
        tmap = dict(node.types)
        for c, d in spec["dicts"].items():
            dicts[sym_of[c]] = d
        for c in cols:
            s = sym_of[c]
            if tmap[s].is_dictionary and s not in dicts:
                dicts[s] = np.array([], dtype=object)
        scans[id(node)] = merged
        counts[id(node)] = count
        self._scan_keys[id(node)] = key
        symbols = [sym_of[c] for c in cols]
        fp = dict_fingerprint(dicts, symbols)
        self._scan_dictfp[id(node)] = fp
        self._devgen[id(node)] = spec
        if cache is not None and key is not None:
            col_of = {s: c for s, c in node.assignments}
            # charged what the lanes will hold in HBM: every shard padded
            # to the rung `_device_lanes` dispatches at (a streamed tile's
            # shared shape is well above its own row count)
            shard_rows = [n for _, _, n in spec.get("shards") or ()] or [count]
            cache.put(
                key,
                {
                    "merged": {col_of[s]: merged[s] for s in merged},
                    "dicts": dict(spec["dicts"]),
                    "total": count, "dev": {}, "dictfp": fp,
                    "devgen": spec,
                },
                devgen_lane_bytes(
                    spec, cols,
                    len(shard_rows) * self._scan_cap(
                        node, max(max(shard_rows), 1)
                    ),
                ),
            )
        return True

    def _devgen_spec(self, devgen_fn, table, cols, splits):
        """The connector's generation recipe for these splits (None: the
        host path).  One device generates the whole range."""
        return devgen_fn(table, cols, splits)

    def _generate_device_scan(self, spec: dict, syms, sym_to_col, cap):
        """Run the connector's on-device generator for one scan at padded
        capacity `cap`; returns {symbol: (values, ok)} resident in HBM.

        The generator's XLA compile (one per new (table, cols, cap)
        shape) happens first, unsupervised; its execution then runs under
        the supervisor like every other device program, so a device lost
        while generating is attributed to this breadcrumb.  The
        breadcrumb carries synthetic output-lane shapes (the generator
        has no host input arrays) so `scripts/flightrec.py replay` can
        reconstruct it."""
        from ..connectors import tpch_device

        cols = [sym_to_col.get(s, s) for s in syms]
        # one range, or one per mesh device (`shards`: the recipe of a
        # scan sharded by parallel/mesh_executor; lanes are then
        # [ndev, cap], each shard generated in its own device's HBM)
        shards = spec.get("shards")
        if shards is None:
            lo, hi, count = int(spec["lo"]), int(spec["hi"]), int(spec["count"])
            span = hi - lo
        else:
            lo, hi, count = (list(x) for x in zip(*shards))
            span = max(h - l for l, h in zip(lo, hi))
        widths = spec.get("widths") or {}
        bc = self._dispatch_crumb(
            "devgen:%s" % spec["table"], "devgen"
        )
        bc.shapes = {
            c: "int%d(%d,)" % (8 * int(widths.get(c, 8)), cap)
            for c in cols
        }
        self.kernel_profile["last_breadcrumb"] = bc.to_dict()
        cap_orders = (
            self.ladder.quantize(max(span, 1))
            if spec["table"] == "lineitem" else None
        )
        sf = float(spec["sf"])
        mesh = self.mesh if shards is not None else None
        with TRACER.span("devgen", table=spec["table"],
                         shards=len(shards or (None,))) as sp:
            t0 = time.time()
            compiled = tpch_device.compile_lanes(
                spec["table"], cols, lo, hi, cap, sf,
                cap_orders=cap_orders, mesh=mesh,
            )
            compile_s = time.time() - t0
            sp.attributes["compiled"] = bool(compiled)
            t0 = time.perf_counter()
            # blocked: generation is timed (and watched) as its own
            # dispatch instead of landing on the query's device_get
            lanes = self._dispatch(
                lambda: jax.block_until_ready(  # dispatch-guard: ok (in thunk)
                    tpch_device.device_lanes(
                        spec["table"], cols, lo, hi, cap, sf, count,
                        cap_orders=cap_orders, mesh=mesh,
                    )
                ),
                bc,
            )
        prof = self.kernel_profile
        prof["devgenWallS"] = (
            prof.get("devgenWallS", 0.0) + time.perf_counter() - t0
        )
        if compiled:
            prof["devgenCompileS"] = (
                prof.get("devgenCompileS", 0.0) + compile_s
            )
        return {s: lanes[c] for s, c in zip(syms, cols)}

    def _scan_cap(self, node, count) -> int:
        """The rung one scan's lanes are padded to: the ladder's for its
        row count, raised to the tiles' shared shape in a streamed tile."""
        cap = self.ladder.quantize(count)
        override = int(self.config.get("scan_cap_override") or 0)
        if override and isinstance(node, P.TableScan):
            cap = max(cap, override)
        return cap

    def _device_lanes(self, node: P.TableScan, arrays, count, nid=None):
        """Pad + upload one scan's host arrays to device lanes, reusing
        cached device arrays when the scan is version-cacheable (the
        host->HBM transfer is the cold cost of a scan).
        `nid` keys the scan-keys table for node-less sources (streaming
        RemoteSource inputs, cached per run)."""
        cap = self._scan_cap(node, count)
        # a table scan's lanes live in the session's cache where the
        # executor was given it; a streamed run's remote inputs (and the
        # tiles of a scan it does not keep) in the run's own
        cache: Optional[DeviceScanCache] = getattr(
            self, "_streaming_cache", None
        )
        if isinstance(node, P.TableScan):
            cache = self.config.get("scan_cache") or cache
        if nid is None and node is not None:
            nid = id(node)
        if nid is not None:
            # the rung actually dispatched — the kernel profile reads
            # padded rows from here, so EXPLAIN ANALYZE ratios match the
            # observatory census
            self._scan_caps[nid] = cap
        # lanes staged ahead by FragmentExecutor.preupload (prefetch
        # thread): consume them instead of re-uploading.  Donatability
        # was recorded when they were staged.
        staged = getattr(self, "_preuploaded", None)
        if staged and nid in staged:
            return staged.pop(nid)
        key = self._scan_keys.get(nid) if nid is not None else None
        entry = (
            cache.get(key, record=False)
            if (cache is not None and key) else None
        )
        # RemoteSource (exchange input) reuses this load path but has no
        # column mapping and never caches (key is None for it)
        sym_to_col = {
            s: c for s, c in getattr(node, "assignments", None) or ()
        }
        # lanes with no cache entry are per-dispatch uploads nothing else
        # references: the fused jit may donate their buffers back to XLA
        # (cache-resident lanes are reused across tiles/queries and must
        # survive the dispatch)
        donatable = getattr(self, "_lane_donatable", None)
        if donatable is None:
            donatable = self._lane_donatable = {}
        if nid is not None:
            donatable[nid] = entry is None
        # cached lanes are keyed by column alone: one padded to another
        # rung than this dispatch's is not this program's input (the
        # validity plane is [cap], or [ndev, cap] on a mesh)
        held = {
            col: lane
            for col, lane in (entry["dev"] if entry is not None else {}).items()
            if lane[1].shape[-1] == cap
        }
        lanes = {}
        gen_out = None
        for sym, (arr, valid) in arrays.items():
            col = sym_to_col.get(sym, sym)
            if col in held:
                lanes[sym] = held[col]
                continue
            if isinstance(arr, _LazyDeviceLane):
                if gen_out is None:
                    spec = self._devgen.get(nid)
                    lazy_syms = [
                        s for s, (a, _v) in arrays.items()
                        if isinstance(a, _LazyDeviceLane)
                        and sym_to_col.get(s, s) not in held
                    ]
                    gen_out = self._generate_device_scan(
                        spec, lazy_syms, sym_to_col, cap
                    )
                lanes[sym] = gen_out[sym]
                if entry is not None:
                    entry["dev"][col] = gen_out[sym]
                continue
            lanes[sym] = self._upload_lane(arr, valid, cap)
            if entry is not None:
                entry["dev"][col] = lanes[sym]
        return lanes

    def _upload_lane(self, arr, valid, cap):
        """One host column as a device lane: (values padded to `cap`,
        validity)."""
        if arr.shape[0] < cap:
            pad = np.zeros(
                (cap - arr.shape[0],) + arr.shape[1:], dtype=arr.dtype
            )
            arr = np.concatenate([arr, pad])
        v = jnp.asarray(arr)
        if valid is None:
            ok = jnp.ones(cap, dtype=bool)
        else:
            vv = np.zeros(cap, dtype=bool)
            vv[: valid.shape[0]] = valid
            ok = jnp.asarray(vv)
        return v, ok

    @staticmethod
    def _sym_for(scan: P.TableScan, col: str) -> str:
        for s, c in scan.assignments:
            if c == col:
                return s
        raise KeyError(col)

    # ------------------------------------------------------------------
    def _estimate_group_capacity(self, plan: P.PlanNode, counts) -> Optional[int]:
        """Initial sort-group-by capacity from connector NDV statistics
        (the CBO's AggregationStatsRule role): every overflow rung re-runs
        and re-compiles the fragment, so landing near the real group count
        on the first try matters.  Bounded by the scan row count (a group
        per input row at worst)."""
        ndv: Dict[str, float] = {}
        max_rows = max(counts.values(), default=0)

        def walk(n: P.PlanNode):
            if isinstance(n, P.TableScan):
                try:
                    stats = self.metadata.table_statistics(n.catalog, n.table)
                except Exception:
                    return
                for sym, col in n.assignments:
                    cs = stats.columns.get(col)
                    if cs is not None and cs.distinct_count:
                        ndv[sym] = cs.distinct_count
                    else:
                        ndv.setdefault(sym, stats.row_count)
            for s in n.sources:
                walk(s)

        walk(plan)
        # NDV products wildly overestimate for correlated keys (brand_id
        # determines brand; orderkey determines orderdate), a filter or a
        # join below leaves few of a key's values, and every segment op
        # pays O(capacity).  Cap the first try; the overflow ladder (x8
        # per rung) covers genuinely huge group counts with one recompile
        # instead of every query paying worst-case capacity.  One case
        # is no estimate: ONE key grouped straight off its whole scan
        # (Q18's 1.5M orders of lineitem) has exactly its NDV in groups,
        # and stands uncapped — a rung short there costs a second trace
        # and compile of the whole fragment.
        first_try_cap = 1 << 18
        best = None

        def whole_scan(n: P.PlanNode) -> bool:
            while isinstance(n, P.Project):
                n = n.source
            return isinstance(n, P.TableScan) and not n.constraint

        def walk2(n: P.PlanNode):
            nonlocal best
            if isinstance(n, P.Aggregate) and n.keys:
                est = 1.0
                for k in n.keys:
                    est *= ndv.get(k, float(DEFAULT_GROUP_CAPACITY))
                    if est > 1e12:
                        break
                exact = (len(n.keys) == 1 and n.keys[0] in ndv
                         and whole_scan(n.source))
                if not exact:
                    est = min(est * 2, first_try_cap)
                best = max(best or 0, int(min(est, float(max_rows) or est)))
            for s in n.sources:
                walk2(s)

        walk2(plan)
        if best is None or best <= DEFAULT_GROUP_CAPACITY:
            return None
        return self.ladder.quantize(best)

    # ------------------------------------------------------------------
    def _compile_family(self, plan) -> str:
        """Shape- and capacity-invariant kernel-family digest: the
        observatory's unit of 'same program modulo padding bucket'."""
        from ..cache.compile_cache import stable_key_digest
        from ..cache.signature import fragment_fingerprint

        try:
            fp = fragment_fingerprint(plan)
        except Exception:  # unknown node kinds: per-object identity
            fp = id(plan)
        return stable_key_digest(("family", fp))[:12]

    def _compile_shape_sig(self, counts) -> str:
        """Ladder-rung signature of one execution's scan shapes (the
        eager/mesh analog of the jit key's per-scan bucket component)."""
        from ..cache.compile_cache import stable_key_digest

        return stable_key_digest(tuple(sorted(
            self.ladder.quantize(int(c)) for c in counts.values()
        )))[:12]

    def _dispatched_cap(self, nid, count: int) -> int:
        """The padded capacity actually dispatched for one scan: the
        recorded rung when `_device_lanes` ran (includes any
        scan_cap_override), the ladder's rung otherwise."""
        cap = self._scan_caps.get(nid)
        return int(cap) if cap else self.ladder.quantize(int(count))

    def _padded_rows(self, counts) -> int:
        """Total dispatched padded rows across the fragment's scans —
        what the observatory census and EXPLAIN ANALYZE both report."""
        return sum(
            self._dispatched_cap(nid, int(c)) for nid, c in counts.items()
        )

    def _record_kernel(
        self, digest: str, compile_s: float, cached: bool, mode: str = "jit",
        cause: Optional[str] = None,
    ) -> dict:
        """Accumulate one fragment-program execution into kernel_profile."""
        kernels: List[dict] = self.kernel_profile["kernels"]  # type: ignore[assignment]
        rec = None
        for k in kernels:
            if k["digest"] == digest:
                rec = k
                break
        if rec is None:
            rec = {
                "digest": digest,
                "mode": mode,
                "compiles": 0,
                "compileWallS": 0.0,
                "executions": 0,
                "cacheHits": 0,
                "causes": {},
            }
            kernels.append(rec)
        rec["executions"] += 1
        if cached:
            rec["cacheHits"] += 1
        else:
            rec["compiles"] += 1
            rec["compileWallS"] += compile_s
            cause = cause or _compile_obs.FIRST_COMPILE
            causes = rec.setdefault("causes", {})
            causes[cause] = causes.get(cause, 0) + 1
            REGISTRY.histogram(
                "trino_tpu_kernel_compile_seconds",
                "XLA fragment compile (or eager trace) wall time",
            ).observe(compile_s)
            if cause != _compile_obs.FIRST_COMPILE:
                # recompiles split by the observatory's cause taxonomy:
                # ladder rungs, shape misses, persistent-tier loads —
                # no longer conflated
                REGISTRY.counter(
                    "trino_tpu_kernel_recompile_total",
                    "Fragment programs compiled beyond a family's first,"
                    " by cause",
                ).inc(cause=cause)
        return rec

    def _finalize_kernel_profile(self, scans, counts, host_lanes, sel_np):
        """Fill the profile summary once the fragment settles: padding
        waste and estimated host<->device transfer volume."""
        actual = sum(int(c) for c in counts.values())
        padded = self._padded_rows(counts)
        h2d = 0
        for nid, arrays in scans.items():
            count = max(int(counts.get(nid, 1)), 1)
            scale = self._dispatched_cap(nid, count) / count
            for v, ok in arrays.values():
                nb = int(v.nbytes) + (int(ok.nbytes) if ok is not None else 0)
                h2d += int(nb * scale)
        d2h = int(getattr(sel_np, "nbytes", 0))
        for v, ok in host_lanes.values():
            d2h += int(getattr(v, "nbytes", 0))
            d2h += int(getattr(ok, "nbytes", 0)) if ok is not None else 0
        kernels: List[dict] = self.kernel_profile["kernels"]  # type: ignore[assignment]
        compiles = sum(k["compiles"] for k in kernels)
        by_cause: Dict[str, int] = {}
        for k in kernels:
            for c, n in (k.get("causes") or {}).items():
                by_cause[c] = by_cause.get(c, 0) + n
        self.kernel_profile["summary"] = {
            "kernels": len(kernels),
            "compiles": compiles,
            # a recompile is any compile whose cause is NOT a family's
            # first — the old max(0, compiles - 1) conflated ladder
            # rungs and genuine shape misses
            "recompiles": max(
                0,
                compiles - by_cause.get(_compile_obs.FIRST_COMPILE, 0),
            ),
            "compilesByCause": by_cause,
            "cacheHits": sum(k["cacheHits"] for k in kernels),
            "compileWallS": sum(k["compileWallS"] for k in kernels),
            "actualRows": actual,
            "paddedRows": padded,
            "paddingRatio": (padded / actual) if actual else 1.0,
            "h2dBytes": h2d,
            "d2hBytes": d2h,
        }
        REGISTRY.counter(
            "trino_tpu_kernel_h2d_bytes", "Estimated host-to-device scan upload bytes"
        ).inc(h2d)
        REGISTRY.counter(
            "trino_tpu_kernel_d2h_bytes", "Estimated device-to-host result bytes"
        ).inc(d2h)

    # ------------------------------------------------------------------
    def _run_jitted(self, plan: P.Output, scans, counts):
        """One jitted XLA program per fragment (the architecture's codegen
        slot: LocalExecutionPlanner -> generated bytecode in the reference,
        -> one traced+compiled jax function here).  The compiled callable is
        cached per (plan, shapes, capacities) in the session-owned jit
        cache; eager mode remains for EXPLAIN ANALYZE and host-staged
        operators (UNNEST)."""
        cache = self.config.get("jit_cache")
        if cache is None:
            cache = {}
        # the key is built by the cache subsystem: (fragment fingerprint,
        # capacity ladder state, per-scan shape bucket + versioned scan
        # identity + dict fingerprint), with plan-local ids translated to
        # traversal ordinals — a compiled program is a pure function of
        # (plan, capacities, padded lane shapes, BAKED dictionary
        # contents), NOT of which splits produced the rows or which
        # session traced it, so structurally identical fragments from
        # other sessions (or, via the persistent tier, other processes)
        # share one executable.
        from ..cache.compile_cache import fragment_key, stable_key_digest

        # one phase: the fragment key, the lanes it is called with, the
        # donation split and the cache lookup
        with TRACER.span("device_lanes"):
            key, order, by_ord = fragment_key(
                self, plan, scans, counts, self.ladder.quantize
            )
            # prep is keyed by plan ordinal, NOT id(node): dict keys are part
            # of the jit pytree structure, so id-based keys would force a
            # retrace (into the WRONG captured plan) for every session sharing
            # an entry; ordinals make the structure session-invariant
            prep = {}
            donatable_ords = set()
            for nid, arrays in scans.items():
                lanes = dict(self._device_lanes(
                    self._scan_nodes.get(nid), arrays, counts[nid], nid
                ))
                # the true row count rides as a TRACED scalar: baking it as
                # a constant would specialize the executable per exact count
                # (streaming tiles differ by a few rows while sharing the
                # padded shape — they must share one program)
                lanes["__count__"] = jnp.asarray(counts[nid], dtype=jnp.int64)
                o = order.get(nid, nid)
                prep[o] = lanes
                if getattr(self, "_lane_donatable", {}).get(nid):
                    donatable_ords.add(o)
            # donation split: per-dispatch scan uploads ride in a separate
            # pytree arg the compiled program may consume in place
            # (donate_argnums, per the pjit residency protocol) — the
            # copy-on-write round trip for every tile page disappears.
            # Cache-resident lanes (scan cache hits, streaming build tables)
            # stay in the non-donated arg.  CPU donation is a no-op warning,
            # so only a real accelerator backend donates.
            donate = (
                bool(self.config.get("donate_pages", True))
                and not self._device_fallback
                and jax.default_backend() != "cpu"
            )
            if not donate:
                donatable_ords = set()
            # the split is part of the traced structure AND of the executable
            # contract, so it keys the cache alongside the fused-agg mode
            key = key + (
                ("donate", donate, tuple(sorted(donatable_ords))),
                ("megakernels", self._megakernel_mode()),
            )
            digest = stable_key_digest(key)[:12]
            resident_prep = {
                o: v for o, v in prep.items() if o not in donatable_ords
            }
            tile_prep = {
                o: v for o, v in prep.items() if o in donatable_ords
            }
            if donate and tile_prep:
                self.kernel_profile["donated_dispatches"] = (
                    self.kernel_profile.get("donated_dispatches", 0) + 1
                )
                self.kernel_profile["donated_bytes"] = (
                    self.kernel_profile.get("donated_bytes", 0)
                    + sum(
                        int(getattr(x, "nbytes", 0) or 0)
                        for lanes in tile_prep.values()
                        for lane in lanes.values()
                        for x in (lane if isinstance(lane, tuple) else (lane,))
                    )
                )
            entry = cache.get(key)
        if entry is None:
            cell: Dict[str, object] = {}
            # ordinal -> id(node) of the TRACING plan, for the closure
            ids = {o: i for i, o in order.items()}

            def raw(resident_arg, tile_arg):
                prep_arg = dict(resident_arg)
                prep_arg.update(tile_arg)
                ctx = self.trace_ctx_cls(
                    self,
                    {ids.get(o, o): v for o, v in prep_arg.items()},
                    counts,
                )
                ctx.prepared = True
                out_lanes, sel, ordered, checks = self._run(plan, ctx)
                cell["op_counts"] = dict(ctx.op_counts)
                cell["ordered"] = ordered
                cell["caps"] = [(c, k) for _, c, k in checks]
                # dup-check join nodes are recorded as plan ordinals so a
                # different session hitting this entry resolves them to
                # ITS OWN plan's node objects (force sets are id-based)
                cell["dup_ords"] = [
                    order.get(id(n), -1) for n, _ in ctx.dup_checks
                ]
                return (
                    out_lanes,
                    sel,
                    tuple(ng for ng, _, _ in checks),
                    tuple(d for _, d in ctx.dup_checks),
                    tuple(ctx.collision_checks),
                    tuple(ctx.lowering.overflow_flags),
                    tuple(ctx.sum_overflow),
                )

            compile_start = time.time()
            bc = self._dispatch_crumb(digest, "jit", prep)
            self._last_crumb = bc
            # observatory cause, classified BEFORE the compile so the
            # tracer span carries it: ladder rung > persistent-tier load
            # > shape miss vs first compile
            family = self._compile_family(plan)
            # the program's name in profiles and idle-gap labels: the
            # family, so every padding bucket of one plan reads alike
            raw.__name__ = raw.__qualname__ = "frag_" + family
            persistent = bool(
                getattr(cache, "persistent_known", None) is not None
                and cache.persistent_known(key)
            )
            ladder_attempt = int(getattr(self, "_ladder_attempt", 0))
            cause = _compile_obs.get_observatory().classify(
                family, digest, ladder_attempt=ladder_attempt,
                persistent=persistent,
                query_id=self.query_id,
            )
            shapes = _shape_summary(prep)
            actual_rows = sum(int(c) for c in counts.values())
            padded_rows = self._padded_rows(counts)
            with TRACER.span(
                "xla_compile", fragment=digest, cause=cause,
                shapeSig=";".join(
                    "%s=%s" % kv for kv in sorted(shapes.items())
                ),
                actualRows=actual_rows, paddedRows=padded_rows,
                paddedRatio=round(
                    padded_rows / actual_rows, 3
                ) if actual_rows else 1.0,
            ):
                if donate and donatable_ords:
                    fn = jax.jit(  # dispatch-guard: ok (lazy wrapper)
                        raw, donate_argnums=(1,)
                    )
                else:
                    # no-donate: cpu backend / every lane cache-resident
                    fn = jax.jit(raw)  # dispatch-guard: ok (lazy wrapper)
                # trace + XLA compile run HERE, outside the watchdog: a
                # compile is host work that can take minutes (one TPU
                # sort program) and is not a wedged device.  Only the
                # execution of the finished executable is supervised.
                fn = self._compile_fragment(fn, resident_prep, tile_prep)
                census = self._program_census(fn, digest)
                compile_s = time.time() - compile_start
                with TRACER.span("launch"):
                    out = self._dispatch(
                        lambda: fn(resident_prep, tile_prep), bc
                    )
            _compile_obs.record_compile(
                kernel=digest, family=family, cause=cause,
                mode="jit", shapes=shapes,
                actual_rows=actual_rows, padded_rows=padded_rows,
                compile_wall_s=compile_s,
                query_id=self.query_id,
                task_id=str(self.config.get("task_id") or ""),
                node_id=str(self.config.get("node_id") or ""),
                scan_rows=[int(c) for c in counts.values()],
            )
            self._record_kernel(
                digest, compile_s=compile_s, cached=False, cause=cause
            )
            self._note_op_counts(cell["op_counts"])
            cell["dicts"] = dict(self.dicts)
            # the plan reference pins id(plan) (fingerprint memo validity)
            entry = {"fn": fn, "cell": cell, "plan": plan, "census": census}
            cache[key] = entry
        else:
            cell = entry["cell"]
            self.dicts.update(cell["dicts"])
            # dispatch is async: a runtime error of this execution
            # surfaces at the execute() loop's device_get
            with TRACER.span("launch"):
                bc = self._dispatch_crumb(digest, "jit", prep)
                self._last_crumb = bc
                out = self._dispatch(
                    lambda: entry["fn"](resident_prep, tile_prep), bc
                )
            self._record_kernel(digest, compile_s=0.0, cached=True)
        # one object for the compile and for every warm query of the entry
        self.kernel_profile["programCensus"] = entry["census"]
        out_lanes, sel, ngroups, dup_vals, colls, wides, sflags = out
        checks = [
            (ng, cap, kind)
            for ng, (cap, kind) in zip(ngroups, cell["caps"])
        ]
        dups = [
            (by_ord.get(o), d) for o, d in zip(cell["dup_ords"], dup_vals)
        ]
        return (out_lanes, sel, cell["ordered"], checks, dups, colls,
                wides, sflags)

    def _note_op_counts(self, op_counts: Dict[str, int]) -> None:
        """The operator counters of the trace just made stand in the
        kernel profile as ONE trace's: a capacity-ladder retrace lowers
        the operators again, and its counts replace the last rung's."""
        prof = self.kernel_profile
        for name in OP_COUNTERS:
            prof.pop(name, None)
        prof.update(op_counts)

    @staticmethod
    def _program_census(compiled, digest: str) -> dict:
        """The census of the program just compiled (obs/program_census);
        the caller keeps it on the cache entry and puts it into the kernel
        profile of every query that runs the entry."""
        from ..obs import program_census

        with TRACER.span("program_census", fragment=digest):
            census = program_census.census(compiled)
        census["fragment"] = digest
        return census

    @staticmethod
    def _compile_fragment(fn, *args):
        """Ahead-of-time trace + compile of one jitted program for the
        concrete `args`; returns the executable.  Kept apart from the
        supervised dispatch so the watchdog times device execution only."""
        return fn.lower(*args).compile()

    # ------------------------------------------------------------------
    def _run(self, plan: P.Output, ctx: "_TraceCtx"):
        from ..cache.compile_cache import plan_ordinals

        ctx.ordinals = plan_ordinals(plan)[0]
        batch = ctx.visit(plan.source)
        out = {s: batch.lanes[s] for s in plan.symbols}
        return out, batch.sel, batch.ordered, ctx.capacity_checks

    # ------------------------------------------------------------------
    def _materialize(self, plan: P.Output, lanes, sel, ordered) -> Page:
        # single device->host transfer for the selection mask and every
        # output lane (per-array np.asarray would pay one host sync each)
        last = getattr(self, "_last_crumb", None)
        with TRACER.span("device_get"):
            host_lanes, sel_np = self._device_get(
                ({s: lanes[s] for s in plan.symbols}, sel),
                self._dispatch_crumb(
                    last.kernel if last else "materialize", "device_get"
                ),
            )
        return self._materialize_host(plan, host_lanes, sel_np)

    def _materialize_host(self, plan: P.Output, host_lanes, sel_np) -> Page:
        types = plan.source.output_types()
        cols = []
        idx = np.nonzero(sel_np)[0]
        n = len(idx)
        for name, sym in zip(plan.names, plan.symbols):
            v, ok = host_lanes[sym]
            vals = v[idx]
            valid = ok[idx]
            t = types[sym]
            if getattr(t, "wide", False) and vals.ndim == 1:
                # lane-narrow/type-wide (fast-path arithmetic kept one
                # limb): widen host-side so clients decode two limbs
                vals = np.stack([vals, vals >> np.int64(63)], axis=-1)
            validity = None if valid.all() else valid
            d = self.dicts.get(sym)
            if isinstance(d, FormattedKeys) and n < len(d):
                # the page carries the entries its rows read, formatted
                # now; the scan's dictionary stays four fields
                with TRACER.span("dictionary_format", rows=n):
                    live = vals >= 0
                    used, inverse = np.unique(vals[live], return_inverse=True)
                    vals = np.full(n, -1, dtype=np.int32)
                    vals[live] = inverse
                    d = d[used]
            cols.append(Column(t, vals, validity, d))
        return Page(cols, n, list(plan.names))


class _TraceCtx:
    """One trace of the plan (shapes fixed by the loaded scan sizes)."""

    def __init__(self, ex: LocalExecutor, scans, counts):
        self.ex = ex
        self.scans = scans
        self.counts = counts
        self.capacity_checks: List[Tuple[jnp.ndarray, int]] = []
        self.dup_checks: List[Tuple[P.PlanNode, jnp.ndarray]] = []
        self.collision_checks: List[jnp.ndarray] = []
        # BIGINT sum-accumulator overflow flags (decimal sums are exact
        # via wide chunk accumulators; bigint wrap raises loudly per SQL
        # semantics, never silently)
        self.sum_overflow: List[jnp.ndarray] = []
        # which lowering each operator of THIS trace took (OP_COUNTERS);
        # the executor copies them into its kernel profile after the trace
        self.op_counts: Dict[str, int] = {}
        # id(plan node) -> pre-order ordinal in the fragment (`_run`)
        self.ordinals: Dict[int, int] = {}
        self.lowering = LoweringContext(ex.dicts)
        self.lowering.force_wide_mul = getattr(ex, 'force_wide_mul', False)

    def _count(self, name: str, n: int = 1) -> None:
        self.op_counts[name] = self.op_counts.get(name, 0) + int(n)

    def _count_sort_group(self, slots: int, cap: int) -> None:
        """One `_group_sort` over `slots` input slots at capacity `cap`."""
        self._count("sortGroupBys")
        self._count("sortGroupRows", slots)
        self._count("sortGroupCapacity", cap)

    # -- dispatch -------------------------------------------------------
    # the eager per-node probes concretize row counts (int(jnp.sum(sel))),
    # which no trace inside shard_map can: _MeshTraceCtx turns them off
    node_probes = True

    def visit(self, node: P.PlanNode) -> Batch:
        name = type(node).__name__
        m = getattr(self, f"_visit_{name.lower()}", None)
        if m is None:
            raise ExecutionError(f"no executor for {name}")
        # the operator's name in the compiled program's metadata
        # (obs/program_census): its type and its pre-order position in
        # the fragment, the same in every process that traces this text
        o = self.ordinals.get(id(node))
        with jax.named_scope(name if o is None else "%s#%d" % (name, o)):
            if not (self.node_probes
                    and self.ex.config.get("collect_node_stats")):
                return m(node)
            # EXPLAIN ANALYZE instrumentation (OperatorContext timing analog);
            # wall time is inclusive of children — the printer (and
            # obs/opstats.frames_from_plan) subtracts.  The dispatch-to-sync
            # split approximates host (trace + dispatch) vs device (waiting
            # on the computation) wall in eager mode.
            import time as _time

            t0 = _time.perf_counter()
            b = m(node)
            t1 = _time.perf_counter()
            # EXPLAIN ANALYZE timing sync; runs inside the supervised eager
            # dispatch, so it is already covered by the boundary
            jax.block_until_ready((b.sel,))  # dispatch-guard: ok
            t2 = _time.perf_counter()
            st = self.ex.node_stats.setdefault(
                id(node),
                {"rows": 0, "bytes": 0, "wall_s": 0.0,
                 "device_wall_s": 0.0, "calls": 0},
            )
            rows = int(jnp.sum(b.sel))
            cap = int(b.sel.shape[0]) if getattr(b.sel, "shape", None) else 0
            lane_bytes = 0
            for v in b.lanes.values():
                parts = v if isinstance(v, tuple) else (v,)
                lane_bytes += sum(
                    int(getattr(p, "nbytes", 0))
                    for p in parts if p is not None
                )
            st["rows"] = rows
            # logical (unpadded) bytes: padded lane footprint scaled by the
            # live-row fraction, matching rows x width hand-computation
            st["bytes"] = (
                int(lane_bytes * rows / cap) if cap else lane_bytes
            )
            st["wall_s"] += t2 - t0
            st["device_wall_s"] = st.get("device_wall_s", 0.0) + (t2 - t1)
            st["calls"] += 1
            return b

    # -- leaves ---------------------------------------------------------
    def _visit_tablescan(self, node: P.TableScan) -> Batch:
        count = self.counts[id(node)]
        cap = self.ex.ladder.quantize(count)
        override = int(self.ex.config.get("scan_cap_override") or 0)
        if override and isinstance(node, P.TableScan):
            # streaming tiles share one padded shape (and therefore one
            # compiled program) even when their exact row counts differ
            cap = max(cap, override)
        if getattr(self, "prepared", False):
            # jitted-fragment mode: lanes are traced jit arguments and
            # the true row count is the traced "__count__" scalar
            lanes = dict(self.scans[id(node)])
            cnt = lanes.pop("__count__", count)
        else:
            lanes = self.ex._device_lanes(node, self.scans[id(node)], count)
            cnt = count
        sel = jnp.arange(cap) < cnt
        lazy = sum(
            isinstance(self.ex.dicts.get(s), FormattedKeys) for s in lanes
        )
        if lazy:
            self._count("lazyDictionaryColumns", lazy)
        return Batch(lanes, sel)

    def _visit_values(self, node: P.Values) -> Batch:
        n = len(node.rows)
        cap = self.ex.ladder.quantize(max(n, 1))
        lanes = {}
        tmap = dict(node.types_)
        for sym, d in getattr(node, "dicts", ()):
            self.ex.dicts[sym] = np.array(list(d), dtype=object)
        for i, sym in enumerate(node.symbols):
            colvals = [r[i] for r in node.rows]
            t = tmap[sym]
            ok = np.zeros(cap, dtype=bool)
            if getattr(t, "wide", False):
                from ..ops.wide_decimal import from_python_int

                arr = np.zeros((cap, 2), dtype=np.int64)
                for j, v in enumerate(colvals):
                    if v is not None:
                        arr[j, 0], arr[j, 1] = from_python_int(int(v))
                        ok[j] = True
            else:
                arr = np.zeros(cap, dtype=t.np_dtype)
                for j, v in enumerate(colvals):
                    if v is not None:
                        arr[j] = v
                        ok[j] = True
            lanes[sym] = (jnp.asarray(arr), jnp.asarray(ok))
        sel = jnp.arange(cap) < n
        return Batch(lanes, sel)

    # -- unary ----------------------------------------------------------
    def _visit_sample(self, node: P.Sample) -> Batch:
        b = self.visit(node.source)
        n = b.sel.shape[0]
        # deterministic splitmix64 of the row index -> uniform [0, 1)
        z = jnp.arange(n, dtype=jnp.uint64) + jnp.uint64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> 27)) * jnp.uint64(0x94D049BB133111EB)
        z = z ^ (z >> 31)
        u = (z >> 11).astype(jnp.float64) / float(1 << 53)
        keep = u < node.fraction
        return Batch(b.lanes, b.sel & keep, b.ordered, b.replicated)

    # single-device trace: compaction capacities are global row counts;
    # mesh shards see 1/ndev of the rows, so _MeshTraceCtx disables this
    allow_compaction = True

    @jax.named_scope("_maybe_compact")
    def _maybe_compact(self, b: Batch, node) -> Batch:
        """Tighten survivors into a smaller static capacity (the
        optimizer's compact_rows estimate, grown by the ladder's
        compact_factor).  One single-key int32 sort for the survivors'
        row ids (ops/filter_project.compact_indices: no scatter, no
        int64 scan) + one stacked row-gather; every downstream
        sort/gather then runs at the tightened width and the fragment's
        HBM peak shrinks with it.  Exactness: the
        true survivor count rides the capacity checks — overflow re-runs
        with a wider (eventually input-width, i.e. no-op) capacity."""
        est = getattr(node, "compact_rows", None)
        if (
            est is None
            or not self.allow_compaction
            or b.ordered
            or b.replicated
        ):
            return b
        factor = getattr(self.ex, "compact_factor", 1)
        cap = self.ex.ladder.quantize(int(est * 1.3) * factor)
        n = b.sel.shape[0]
        if cap >= n:
            return b
        from ..ops.filter_project import compact_indices, permute_lanes

        self._count("compactions")
        self._count("compactRows", n)
        self._count("compactCapacity", cap)
        total = b.sel.sum()
        idx = compact_indices(b.sel, cap)
        self._note_capacity(total, cap, "compact")
        lanes = permute_lanes(b.lanes, idx)
        sel = jnp.arange(cap) < total
        return Batch(lanes, sel, b.ordered, b.replicated)

    def _visit_filter(self, node: P.Filter) -> Batch:
        b = self.visit(node.source)
        f = compile_expr(node.predicate, self.lowering)
        v, ok = f(b.lanes)
        out = Batch(b.lanes, b.sel & v & ok, b.ordered, b.replicated)
        return self._maybe_compact(out, node)

    def _visit_project(self, node: P.Project) -> Batch:
        b = self.visit(node.source)
        out = {}
        for sym, e in node.assignments:
            out[sym] = compile_expr(e, self.lowering)(b.lanes)
            # propagate dictionaries: pass-through refs and derived strings
            if isinstance(e, ir.ColumnRef) and e.name in self.ex.dicts:
                self.ex.dicts[sym] = self.ex.dicts[e.name]
            else:
                d = self.lowering.dict_for_expr(e)
                if d is not None:
                    self.ex.dicts[sym] = d
                elif e.type.is_dictionary and _is_null_expr(e):
                    # NULL literal projected as varchar (e.g. unmentioned
                    # MERGE insert columns): every row invalid, empty dict
                    self.ex.dicts[sym] = np.array([], dtype=object)
        return Batch(out, b.sel, b.ordered, b.replicated)

    def _visit_limit(self, node: P.Limit) -> Batch:
        b = self.visit(node.source)
        lanes, sel = sort_ops.limit(
            b.lanes, b.sel, node.count, node.offset
        )
        return Batch(lanes, sel, b.ordered, b.replicated)

    def _visit_distinct(self, node: P.Distinct) -> Batch:
        b = self.visit(node.source)
        return Batch(
            *self._distinct_rows(b.lanes, node.output_symbols(), b.sel)
        )

    def _distinct_rows(self, lanes, syms, sel):
        """(lanes, sel) with one live row for each distinct tuple of
        `syms`: the first row of every group of the group sort."""
        lanes, sel_sorted, gid, _ = self._group_sort(
            lanes, syms, sel, sel.shape[0]
        )
        boundary = jnp.concatenate(
            [jnp.ones(1, dtype=bool), gid[1:] != gid[:-1]]
        )
        return lanes, sel_sorted & boundary

    def _visit_unnest(self, node: P.Unnest) -> Batch:
        """UNNEST via host-side expansion: lengths come from the array
        dictionary, rows replicate with np.repeat, elements flatten into a
        fresh lane (UnnestOperator's row-replication, staged on host since
        output size is data-dependent — the same reason the reference
        streams it row-by-row)."""
        b = self.visit(node.source)
        sel = np.asarray(b.sel)
        rows = np.nonzero(sel)[0]
        av, aok = b.lanes[node.array_symbol]
        codes = np.asarray(av)[rows]
        avalid = np.asarray(aok)[rows]
        entries = self.ex.dicts.get(node.array_symbol)
        if entries is None:
            raise ExecutionError(
                f"no dictionary for array column {node.array_symbol}"
            )
        lengths = np.array(
            [
                len(entries[c]) if (ok and c >= 0) else 0
                for c, ok in zip(codes, avalid)
            ],
            dtype=np.int64,
        )
        eff = np.maximum(lengths, 1) if node.outer else lengths
        total = int(eff.sum())
        cap = self.ex.ladder.quantize(max(total, 1))
        rep = np.repeat(rows, eff)  # source row per output row
        elems: list = []
        for c, ok, ln in zip(codes, avalid, lengths):
            if ln:
                elems.extend(entries[c])
            elif node.outer:
                elems.append(None)  # LEFT JOIN UNNEST: NULL element row
        lanes = {}
        for sym, (v, ok) in b.lanes.items():
            if sym == node.array_symbol:
                continue
            vv = np.asarray(v)[rep]
            vo = np.asarray(ok)[rep]
            lanes[sym] = (
                jnp.asarray(pad_to(vv, cap)),
                jnp.asarray(pad_to(vo, cap, False)),
            )
        et = node.element_type
        from ..page import column_from_pylist

        if et.is_dictionary and not getattr(et, "is_array", False):
            col = column_from_pylist(et, elems)
            self.ex.dicts[node.element_symbol] = col.dictionary
            ev = col.values
            eo = (
                np.ones(total, dtype=bool)
                if col.validity is None
                else col.validity
            )
        elif getattr(et, "is_array", False):
            raise ExecutionError("UNNEST of nested arrays is not supported")
        else:
            ev = np.array(
                [0 if x is None else x for x in elems], dtype=et.np_dtype
            )
            eo = np.array([x is not None for x in elems], dtype=bool)
        lanes[node.element_symbol] = (
            jnp.asarray(pad_to(ev, cap)),
            jnp.asarray(pad_to(eo, cap, False)),
        )
        if node.ordinality_symbol:
            ovals: list = []
            ovalid: list = []
            for ln in lengths:
                if ln:
                    ovals.extend(range(1, int(ln) + 1))
                    ovalid.extend([True] * int(ln))
                elif node.outer:  # null-extended row: ordinality is NULL
                    ovals.append(0)
                    ovalid.append(False)
            lanes[node.ordinality_symbol] = (
                jnp.asarray(pad_to(np.array(ovals, dtype=np.int64), cap)),
                jnp.asarray(
                    pad_to(np.array(ovalid, dtype=bool), cap, False)
                ),
            )
        return Batch(lanes, jnp.arange(cap) < total)

    def _visit_matchrecognize(self, node: P.MatchRecognize) -> Batch:
        """MATCH_RECOGNIZE, host-staged (output size is data-dependent and
        the automaton is inherently sequential per partition — the
        reference's window/matcher is also a row-at-a-time NFA)."""
        import functools

        from ..ops.matcher import find_matches

        b = self.visit(node.source)
        sel = np.asarray(b.sel)
        rows = np.nonzero(sel)[0]
        n = len(rows)
        src_types = node.source.output_types()
        cols: Dict[str, list] = {}
        for sym in node.source.output_symbols():
            if sym not in b.lanes:
                continue
            v, ok = b.lanes[sym]
            vv = np.asarray(v)[rows]
            oo = np.asarray(ok)[rows]
            t = src_types[sym]
            if t.is_dictionary and not getattr(t, "is_array", False):
                d = self.ex.dicts.get(sym)
                cols[sym] = [
                    (str(d[int(c)]) if (okk and int(c) >= 0) else None)
                    for c, okk in zip(vv, oo)
                ]
            else:
                cols[sym] = [
                    (v_.item() if okk else None)
                    for v_, okk in zip(vv, oo)
                ]
        # order rows: partition keys first, then ORDER BY keys
        keys = [(s, True, False) for s in node.partition_by] + [
            (k.column, k.ascending, k.nulls_first)
            for k in node.order_by
        ]

        def cmp(a, bidx):
            for col, asc, nulls_first in keys:
                va, vb = cols[col][a], cols[col][bidx]
                if va is None and vb is None:
                    continue
                if va is None:
                    return -1 if nulls_first else 1
                if vb is None:
                    return 1 if nulls_first else -1
                if va == vb:
                    continue
                lt = va < vb
                return (-1 if lt else 1) if asc else (1 if lt else -1)
            return 0

        order = sorted(range(n), key=functools.cmp_to_key(cmp))
        defines = dict(node.defines)
        measures = [(s, e) for s, e, _ in node.measures]
        out_rows: List[dict] = []
        i = 0
        while i < n:
            j = i
            pkey = tuple(cols[s][order[i]] for s in node.partition_by)
            while j < n and tuple(
                cols[s][order[j]] for s in node.partition_by
            ) == pkey:
                j += 1
            part_idx = order[i:j]
            pcols = {c: [vals[k] for k in part_idx] for c, vals in cols.items()}
            all_rows = node.rows_per_match == "all"
            for m in find_matches(
                pcols, len(part_idx), node.pattern, defines, measures,
                node.after_match, all_rows,
            ):
                if all_rows:
                    r = m.pop("__row__")
                    for c in pcols:
                        m[c] = pcols[c][r]
                else:
                    for s, v in zip(node.partition_by, pkey):
                        m[s] = v
                out_rows.append(m)
            i = j
        total = len(out_rows)
        cap = self.ex.ladder.quantize(max(total, 1))
        out_types = node.output_types()
        lanes = {}
        from ..page import column_from_pylist

        for sym in node.output_symbols():
            t = out_types[sym]
            vals = [m.get(sym) for m in out_rows]
            if t.is_dictionary and not getattr(t, "is_array", False):
                col = column_from_pylist(t, vals)
                self.ex.dicts[sym] = col.dictionary
                arr = np.asarray(col.values)
                okv = (
                    np.ones(total, bool) if col.validity is None
                    else np.asarray(col.validity)
                )
            else:
                arr = np.array(
                    [0 if x is None else x for x in vals], dtype=t.np_dtype
                )
                okv = np.array([x is not None for x in vals], dtype=bool)
            lanes[sym] = (
                jnp.asarray(pad_to(arr, cap)),
                jnp.asarray(pad_to(okv, cap, False)),
            )
        return Batch(lanes, jnp.arange(cap) < total)

    def _visit_groupid(self, node: P.GroupId) -> Batch:
        """GROUPING SETS row expansion: tile every lane once per grouping
        set and mask grouping keys absent from each set to NULL; a
        replicated [0..G) group-id lane distinguishes the copies."""
        b = self.visit(node.source)
        G = len(node.sets)
        n = b.sel.shape[0]
        key_union = {s for st in node.sets for s in st}
        lanes = {}
        for sym, (v, ok) in b.lanes.items():
            v2 = jnp.tile(v, G)
            ok2 = jnp.tile(ok, G)
            if sym in key_union and any(sym not in st for st in node.sets):
                keep = np.array([sym in st for st in node.sets], dtype=bool)
                ok2 = ok2 & jnp.repeat(jnp.asarray(keep), n)
            lanes[sym] = (v2, ok2)
        gid = jnp.repeat(jnp.arange(G, dtype=jnp.int64), n)
        lanes[node.gid_symbol] = (gid, jnp.ones(G * n, dtype=bool))
        return Batch(lanes, jnp.tile(b.sel, G), replicated=b.replicated)

    # -- aggregation -----------------------------------------------------
    def _visit_aggregate(self, node: P.Aggregate, b: Optional[Batch] = None) -> Batch:
        """Handles all three steps (AggregationNode.java:346): SINGLE and
        PARTIAL accumulate raw rows; FINAL merges shipped accumulator
        columns (the distributed merge path)."""
        if b is None:
            if node.step in ("single", "partial"):
                from ..ops import megakernel

                fused = megakernel.try_fused(self, node)
                if fused is not None:
                    return fused
            b = self.visit(node.source)
        types = node.source.output_types()
        b, aggs = self._agg_dict_setup(node, b)
        all_specs = [a.to_spec() for a in aggs]
        host_specs = [
            s for s in all_specs if s.kind in agg_ops.HOST_STAGED_KINDS
        ]
        specs = [
            s for s in all_specs if s.kind not in agg_ops.HOST_STAGED_KINDS
        ]
        final = node.step in ("final", "intermediate")  # merges accumulators
        partial = node.step in ("partial", "intermediate")  # emits them
        if host_specs and (final or partial):
            raise ExecutionError(
                "host-staged aggregates cannot split PARTIAL/FINAL"
            )

        def reduce_rows(lanes, gid, sel, cap, seg=None):
            if final:
                acc_in = {
                    n: lanes[n] for s in specs for n in s.accumulator_names
                }
                return agg_ops.merge_accumulators(
                    specs, acc_in, gid, sel, cap,
                    overflow_flags=self.sum_overflow,
                    seg=seg,
                )
            return agg_ops.accumulate(
                specs, lanes, gid, sel, cap,
                step="partial" if partial else "single",
                overflow_flags=self.sum_overflow,
                # decimal(38) sums ride the wide-mul retry ladder: the
                # narrow fast path flags a wrap, the retrace forces
                # true chunked 128-bit sums
                wide_flags=self.lowering.overflow_flags,
                force_wide=self.lowering.force_wide_mul,
                seg=seg,
            )

        def out_lanes(accs):
            if partial:
                return {
                    n: (v, jnp.ones(v.shape, bool)) for n, v in accs.items()
                }
            return agg_ops.finalize(specs, accs)

        if not node.keys:
            # global aggregation: one group
            gid = jnp.zeros(b.sel.shape[0], dtype=jnp.int64)
            accs = reduce_rows(b.lanes, gid, b.sel, 1)
            lanes = out_lanes(accs)
            for hs in host_specs:
                lanes[hs.output] = self._host_agg_lanes(
                    hs, b.lanes, gid, b.sel, 1
                )
            return self._finish_aggregate(
                node, [], lanes, jnp.ones(1, dtype=bool), 1
            )
        key_lanes = [b.lanes[k] for k in node.keys]
        domains = self._direct_domains(node.keys, types)
        if domains is not None:
            self._count("directGroupBys")
            gid, cap = agg_ops.direct_group_ids(key_lanes, domains)
            accs = reduce_rows(b.lanes, gid, b.sel, cap)
            # _seg_count picks the masked/pallas form at small caps — a
            # raw segment_sum scatter here cost ~0.4s at SF1 (a round-3
            # micro-benchmark, record deleted in PR 22: scatter 0.58s vs
            # masked 0.08s at 8.4M)
            present = agg_ops._seg_count(b.sel, gid, cap) > 0
            keys_out = agg_ops.group_keys_output(key_lanes, gid, b.sel, cap)
            host_src = (b.lanes, gid, b.sel)
        else:
            cap = min(self.ex.group_capacity, b.sel.shape[0])
            self._count_sort_group(b.sel.shape[0], cap)
            sorted_lanes, sel_sorted, gid, ngroups = self._group_sort(
                b.lanes, node.keys, b.sel, cap
            )
            self._note_capacity(ngroups, cap)
            # gid is SORTED here: one shared run-range computation
            # replaces per-aggregate scatters (SortedSegments)
            ss = agg_ops.SortedSegments(gid, cap)
            accs = reduce_rows(sorted_lanes, gid, sel_sorted, cap, seg=ss)
            present = jnp.arange(cap) < ngroups
            keys_out = agg_ops.group_keys_output(
                [sorted_lanes[k] for k in node.keys], gid, sel_sorted, cap,
                starts=ss.starts,
            )
            host_src = (sorted_lanes, gid, sel_sorted)
        out = out_lanes(accs)
        for hs in host_specs:
            out[hs.output] = self._host_agg_lanes(hs, *host_src, cap)
        return self._finish_aggregate(node, keys_out, out, present, cap)

    def _merge_fused_sums(self, sums):
        """Fused-megakernel partial-sum merge seam: one device has
        nothing to merge; the mesh trace context overrides this with a
        cross-shard collective before the shared finalize tail."""
        return sums

    def _finish_aggregate(self, node, keys_out, out, present, cap):
        """Shared aggregate tail (unfused and megakernel paths): merge
        key and output lanes, pad to the static 128-aligned capacity."""
        lanes = {}
        for k, kl in zip(node.keys, keys_out):
            lanes[k] = kl
        for s in out:
            lanes[s] = out[s]
        pad_cap = self.ex.ladder.quantize(cap)
        if pad_cap != cap:
            from ..ops.wide_decimal import pad_rows

            lanes = {
                s: (pad_rows(v, pad_cap - cap), jnp.pad(ok, (0, pad_cap - cap)))
                for s, (v, ok) in lanes.items()
            }
            present = jnp.pad(present, (0, pad_cap - cap))
        return Batch(lanes, present)

    def _agg_dict_setup(self, node: P.Aggregate, b: "Batch"):
        """Dictionary handling for ordering/value-carrying aggregates.

        Dictionary codes are first-seen order, not string order, so min/max
        over a varchar (and the min_by/max_by ordering key) must compare
        lexicographic *ranks*: remap the code lane through the sorted
        dictionary and register the sorted dictionary for the output — the
        code-space analog of the reference ordering real strings through
        TypeOperators.  Value-carrying aggregates (arbitrary, min_by value)
        propagate the input dictionary unchanged.  Dictionaries are also
        registered for the $val/$key accumulator columns so PARTIAL-step
        output pages (shipped over exchanges) stay decodable."""
        raw_step = node.step in ("single", "partial")
        lanes = None
        aggs = []

        def rank_lane(sym: str):
            nonlocal lanes
            d = self.ex.dicts.get(sym)
            if d is None or len(d) == 0:
                return sym, d if d is not None else np.array([], dtype=object)
            if getattr(d, "sorted_by_code", False):
                return sym, d  # a code is its own rank
            order = np.argsort(np.array([str(x) for x in d]))
            rank = np.empty(len(d), dtype=np.int32)
            rank[order] = np.arange(len(d), dtype=np.int32)
            v, ok = b.lanes[sym]
            rk = jnp.asarray(rank)[jnp.clip(v, 0, len(d) - 1)]
            rsym = sym + "$rank"
            if lanes is None:
                lanes = dict(b.lanes)
            lanes[rsym] = (jnp.where(v >= 0, rk, -1).astype(v.dtype), ok)
            return rsym, d[order]

        for a in node.aggs:
            it, i2t = a.input_type, a.input2_type
            if (a.kind in ("min", "max") and it is not None
                    and it.is_dictionary):
                if raw_step:
                    rsym, sorted_d = rank_lane(a.arg)
                    a = dataclasses.replace(a, arg=rsym)
                    self.ex.dicts[a.output] = sorted_d
                    self.ex.dicts[f"{a.output}$val"] = sorted_d
                elif f"{a.output}$val" in self.ex.dicts:
                    self.ex.dicts[a.output] = self.ex.dicts[f"{a.output}$val"]
            elif a.kind in ("min_by", "max_by"):
                if i2t is not None and i2t.is_dictionary and raw_step:
                    rsym, sorted_d = rank_lane(a.arg2)
                    a = dataclasses.replace(a, arg2=rsym)
                    self.ex.dicts[f"{a.output}$key"] = sorted_d
                if it is not None and it.is_dictionary:
                    if raw_step and a.arg in self.ex.dicts:
                        self.ex.dicts[a.output] = self.ex.dicts[a.arg]
                        self.ex.dicts[f"{a.output}$val"] = self.ex.dicts[a.arg]
                    elif f"{a.output}$val" in self.ex.dicts:
                        self.ex.dicts[a.output] = (
                            self.ex.dicts[f"{a.output}$val"]
                        )
            elif a.output_type.is_dictionary:  # arbitrary etc.
                if raw_step and a.arg in self.ex.dicts:
                    self.ex.dicts[a.output] = self.ex.dicts[a.arg]
                    self.ex.dicts[f"{a.output}$val"] = self.ex.dicts[a.arg]
                elif f"{a.output}$val" in self.ex.dicts:
                    self.ex.dicts[a.output] = self.ex.dicts[f"{a.output}$val"]
            aggs.append(a)
        if lanes is not None:
            b = dataclasses.replace(b, lanes=lanes)
        return b, aggs

    def _direct_domains(self, keys, types) -> Optional[List[int]]:
        domains = []
        prod = 1
        for k in keys:
            t = types[k]
            if t.is_dictionary and k in self.ex.dicts:
                d = len(self.ex.dicts[k])
            elif t.name == "boolean":
                d = 2
            else:
                return None
            domains.append(d)
            prod *= d + 1
        return domains if prod <= 4096 else None

    # -- joins -----------------------------------------------------------
    def _note_capacity(self, ngroups, cap, kind="group"):
        # kind selects which knob the retry ladder grows on overflow:
        # group -> group_capacity, join -> join_factor (expansion /
        # shuffle buffers), topn -> topn_factor (candidate sets) —
        # uncoupled so a TopN tie burst cannot 8x every join buffer
        self.capacity_checks.append((ngroups, cap, kind))

    def _note_collision(self, coll):
        self.collision_checks.append(coll)

    def _group_sort(self, lanes, keys, sel, cap):
        """Salted hash-sort grouping with exact verification; a
        detected locator collision re-runs the fragment under a fresh
        salt (executor retry ladder), so grouping is always exact.

        The one place that sorts, permutes and verifies: every lane of
        `lanes` is gathered by the sort's permutation ONCE (one stacked
        gather a dtype), and the hash runs are verified on those sorted
        key lanes.  Returns (sorted_lanes, sel_sorted, gid, ngroups),
        rows grouped by `keys`, dead rows last."""
        from ..ops.filter_project import permute_lanes

        perm, gid, ngroups, sel_sorted, same_run = agg_ops.sort_group_ids(
            [lanes[k] for k in keys], sel, cap,
            getattr(self.ex, 'group_salt', 0),
        )
        sorted_lanes = permute_lanes(lanes, perm)
        self._note_collision(agg_ops.run_collisions(
            [sorted_lanes[k] for k in keys], same_run
        ))
        return sorted_lanes, sel_sorted, gid, ngroups

    def _visit_join(self, node: P.Join) -> Batch:
        left = self.visit(node.left)
        right = self.visit(node.right)
        out = self._join_batches(node, left, right)
        if node.kind == "inner":
            out = self._maybe_compact(out, node)
        return out

    def _join_batches(self, node: P.Join, left: Batch, right: Batch) -> Batch:
        if node.kind == "cross":
            return self._cross_join(node, left, right)
        if node.expansion or id(node) in getattr(
            self.ex, "force_expansion", ()
        ):
            self._count("sortJoins")
            return self._expansion_join(node, left, right)
        # unique-keyed build on right, probe on left
        lkeys = [left.lanes[l] for l, _ in node.criteria]
        rkeys = [right.lanes[r] for _, r in node.criteria]
        self._check_join_dicts(node)
        # JOINT hashing decision: either side being multi-column or wide
        # forces both sides onto the hashed locator + exact verification
        need_verify = join_ops.needs_verification(
            rkeys
        ) or join_ops.needs_verification(lkeys)
        bkey = join_ops.composite_key(rkeys, right.sel, need_verify)
        pkey = join_ops.composite_key(lkeys, left.sel, need_verify)
        if (
            node.direct_domain is not None
            and not need_verify
            and id(node) not in getattr(self.ex, "force_no_direct", ())
        ):
            # dense-domain direct addressing: one scatter builds, one
            # gather probes; a violation/duplicate count retries on the
            # sorted unique kernel (then expansion if genuinely dup)
            self._count("directJoins")
            lo, hi = node.direct_domain
            dsrc = join_ops.build_direct(
                bkey, right.sel, lo, hi - lo + 1
            )
            self.dup_checks.append((node, dsrc.violations))
            row, matched = join_ops.probe_direct(dsrc, pkey, left.sel)
        else:
            self._count("sortJoins")
            src = join_ops.build_unique(bkey, right.sel)
            self.dup_checks.append((node, src.dup_count))
            row, matched = join_ops.probe(src, pkey, left.sel)
        if need_verify:
            # exact equality on the real key columns: a 64-bit locator
            # collision must reject the candidate, not return a wrong row
            matched = matched & join_ops.verify_rows(rkeys, lkeys, row)
        build_cols = join_ops.gather_build(right.lanes, row, matched)
        lanes = dict(left.lanes)
        lanes.update(build_cols)
        if node.kind == "inner":
            sel = left.sel & matched
        elif node.kind == "left":
            sel = left.sel
        else:
            raise ExecutionError(f"join kind {node.kind} not supported yet")
        if node.filter is not None:
            f = compile_expr(node.filter, self.lowering)
            v, ok = f(lanes)
            if node.kind == "inner":
                sel = sel & v & ok
            else:
                # left join residual: failed residual nulls the build side
                keep = matched & v & ok
                for name in build_cols:
                    bv, bok = lanes[name]
                    lanes[name] = (bv, bok & keep)
        return Batch(lanes, sel)

    def _expansion_join(self, node: P.Join, left: Batch, right: Batch) -> Batch:
        """General (duplicate-build-key) join with static output capacity +
        host retry (vectorized LookupJoinOperator page building).

        Candidates come from the 64-bit locator ranges; `verify_rows` then
        enforces exact multi-column equality, and for outer joins the
        null-extended row is emitted per probe row only when *no* candidate
        survives key verification + residual filter (segment any-match),
        matching LookupJoinOperator.java:36 probe semantics exactly."""
        lkeys = [left.lanes[l] for l, _ in node.criteria]
        rkeys = [right.lanes[r] for _, r in node.criteria]
        self._check_join_dicts(node)
        need_verify = join_ops.needs_verification(
            rkeys
        ) or join_ops.needs_verification(lkeys)
        bkey = join_ops.composite_key(rkeys, right.sel, need_verify)
        pkey = join_ops.composite_key(lkeys, left.sel, need_verify)
        src = join_ops.build_multi(bkey, right.sel)
        counts, lo = join_ops.probe_counts(src, pkey, left.sel)
        if node.kind not in ("inner", "left"):
            raise ExecutionError(
                f"join kind {node.kind} not supported by the expansion "
                "kernel (right/full rewrite to left at planning)"
            )
        outer = node.kind == "left"
        probe_cap = left.sel.shape[0]
        capacity = self.ex.ladder.quantize(
            int(probe_cap * getattr(self.ex, "join_factor", 1))
        )
        probe_row, build_row, matched, total, k = join_ops.expand_join_slots(
            src, counts, lo, capacity, outer=outer
        )
        # the internal eff uses max(counts,1) for outer including unselected
        # rows; mask them below via probe sel gather
        self._note_capacity(total, capacity, "join")
        psel = left.sel[probe_row]
        if need_verify:
            matched = matched & join_ops.verify_rows(
                rkeys, lkeys, build_row, probe_row
            )
        from ..ops.filter_project import permute_lanes

        lanes = dict(permute_lanes(left.lanes, probe_row))
        for s, (v, ok) in right.lanes.items():
            lanes[s] = (v[build_row], ok[build_row] & matched)
        surviving = matched & psel  # matched is already within-capacity
        if node.filter is not None:
            f = compile_expr(node.filter, self.lowering)
            v, ok = f(lanes)
            surviving = surviving & v & ok
        if node.kind == "inner":
            sel = surviving
        else:
            any_match = (
                jax.ops.segment_sum(
                    surviving.astype(jnp.int32), probe_row,
                    num_segments=probe_cap,
                )
                > 0
            )
            within = jnp.arange(capacity) < total
            outer_emit = within & (k == 0) & psel & ~any_match[probe_row]
            sel = surviving | outer_emit
            for s in right.lanes:
                bv, bok = lanes[s]
                lanes[s] = (bv, bok & surviving)
        return Batch(lanes, sel)

    def _host_agg_lanes(self, spec, lanes, gid, sel, cap):
        """array_agg / map_agg / listagg: build per-group variable-length
        values HOST-side into a fresh dictionary (the engine's model for
        complex values — codes into a host dictionary, like
        expr/arrays.py).  Runs eagerly (the jit gate excludes plans with
        these aggregates), one python pass over the selected rows — the
        same single-threaded row walk the reference's accumulators do.
        Element values keep IR-constant conventions; Page.to_pylist
        decodes them (page._element_decoder)."""
        import numpy as np

        v, ok = lanes[spec.input]
        gid_np = np.asarray(gid)
        sel_np = np.asarray(sel)
        v_np = np.asarray(v)
        ok_np = np.asarray(ok)
        d_in = self.ex.dicts.get(spec.input)

        def v_of(i, arr, okarr, d):
            if not okarr[i]:
                return None
            x = arr[i].item()
            if d is not None:
                x = str(d[int(x)])
            return x

        groups: dict = {}
        if spec.kind == "map_agg":
            k2, ok2 = lanes[spec.input2]
            k_np, k_ok = np.asarray(k2), np.asarray(ok2)
            d_key = d_in
            d_val = self.ex.dicts.get(spec.input2)
            # spec.input is the KEY, input2 the VALUE (map_agg(key, value))
            for i in np.nonzero(sel_np)[0]:
                key = v_of(i, v_np, ok_np, d_key)
                if key is None:
                    continue  # NULL keys are skipped (reference behavior)
                g = groups.setdefault(int(gid_np[i]), {})
                g.setdefault(key, v_of(i, k_np, k_ok, d_val))
        else:
            for i in np.nonzero(sel_np)[0]:
                g = groups.setdefault(int(gid_np[i]), [])
                g.append(v_of(i, v_np, ok_np, d_in))

        entries: list = []
        index: dict = {}
        codes = np.full(cap, -1, dtype=np.int32)
        has = np.zeros(cap, dtype=bool)
        for gi, val in groups.items():
            if spec.kind == "array_agg":
                obj = tuple(val)
            elif spec.kind == "listagg":
                obj = str(spec.param).join(
                    str(x) for x in val if x is not None
                )
            else:  # map_agg: sorted key-value pair tuple
                obj = tuple(sorted(val.items(), key=lambda kv: repr(kv[0])))
            code = index.get(obj)
            if code is None:
                code = len(entries)
                index[obj] = code
                entries.append(obj)
            codes[gi] = code
            has[gi] = True
        # 1-D object array even when all entries are equal-length
        # tuples (np.array would build a 2-D array)
        d_out = np.empty(len(entries), dtype=object)
        d_out[:] = entries
        self.ex.dicts[spec.output] = d_out
        return (
            jnp.asarray(np.where(has, codes, 0)),
            jnp.asarray(has),
        )

    def _check_join_dicts(self, node: P.Join):
        for l, r in node.criteria:
            dl, dr = self.ex.dicts.get(l), self.ex.dicts.get(r)
            if (dl is None) != (dr is None):
                raise ExecutionError(
                    f"join key {l}={r} mixes varchar dictionary and non-dict"
                )
            if dl is not None and not same_dictionary(dl, dr):
                raise ExecutionError(
                    f"join on varchar keys {l}={r} requires shared dictionary"
                )

    def _cross_join(self, node: P.Join, left: Batch, right: Batch) -> Batch:
        # a side whose PLAN guarantees at most one row (global aggregate,
        # LIMIT 1) broadcasts instead of repeat/tile — the scalar-ratio
        # query shape (TPC-DS Q90's amc/pmc) stays capacity-lean no
        # matter how wide the other side padded
        if _single_row_plan(node.right):
            return self._scalar_cross(left, right)
        if _single_row_plan(node.left):
            return self._scalar_cross(right, left)
        # only small-right cross joins (scalar-ish); replicate rows
        rcap = right.sel.shape[0]
        lcap = left.sel.shape[0]
        if rcap * lcap > 1 << 22:
            raise ExecutionError("cross join too large")
        # rows = left x right
        n = lcap * rcap
        li = jnp.repeat(jnp.arange(lcap), rcap)
        ri = jnp.tile(jnp.arange(rcap), lcap)
        lanes = {}
        for s, (v, ok) in left.lanes.items():
            lanes[s] = (v[li], ok[li])
        for s, (v, ok) in right.lanes.items():
            lanes[s] = (v[ri], ok[ri])
        sel = left.sel[li] & right.sel[ri]
        return Batch(lanes, sel)

    def _scalar_cross(self, keep: Batch, single: Batch) -> Batch:
        """Cross join against a ≤1-row side: broadcast its first selected
        row onto the kept side (empty single side = empty result, exactly
        the cross-join semantics)."""
        first = jnp.argmax(single.sel)
        has = single.sel.sum() > 0
        n = keep.sel.shape[0]
        lanes = dict(keep.lanes)
        for s, (v, ok) in single.lanes.items():
            lanes[s] = (
                jnp.broadcast_to(v[first], (n,) + v.shape[1:]),
                jnp.broadcast_to(ok[first] & has, (n,)),
            )
        return Batch(lanes, keep.sel & has)

    def _visit_semijoin(self, node: P.SemiJoin) -> Batch:
        src = self.visit(node.source)
        filt = self.visit(node.filtering)
        hit = self._semi_hit(node, src, filt)
        lanes = dict(src.lanes)
        lanes[node.output] = (hit, jnp.ones(hit.shape, bool))
        return Batch(lanes, src.sel, src.ordered, src.replicated)

    @jax.named_scope("_semi_hit")
    def _semi_hit(self, node: P.SemiJoin, src: Batch, filt: Batch):
        """Membership mark; duplicates in the filtering side are fine
        (sorted search, any match counts).  Single-column keys compare the
        real value directly (collision-free); multi-column keys and residual
        predicates go through the expansion path with exact verification."""
        self._count("semiJoins")
        skeys = [src.lanes[k] for k in node.source_keys]
        fkeys0 = [filt.lanes[k] for k in node.filtering_keys]
        if (
            node.filter is not None
            or join_ops.needs_verification(skeys)
            or join_ops.needs_verification(fkeys0)
        ):
            return self._semi_hit_expanded(node, src, filt)
        build = join_ops.build_multi(
            filt.lanes[node.filtering_keys[0]], filt.sel
        )
        counts, _ = join_ops.probe_counts(
            build, src.lanes[node.source_keys[0]], src.sel
        )
        return counts > 0

    def _semi_hit_expanded(self, node: P.SemiJoin, src: Batch, filt: Batch):
        """Mark join via candidate expansion: expand (source, filtering)
        pairs on the equi-key locator ranges, verify exact key equality,
        evaluate the residual if any, reduce any-match per source row
        (EXISTS with non-equality correlation, e.g. TPC-H Q21)."""
        fkeys = [filt.lanes[k] for k in node.filtering_keys]
        skeys = [src.lanes[k] for k in node.source_keys]
        need_verify = join_ops.needs_verification(
            fkeys
        ) or join_ops.needs_verification(skeys)
        bkey = join_ops.composite_key(fkeys, filt.sel, need_verify)
        pkey = join_ops.composite_key(skeys, src.sel, need_verify)
        build = join_ops.build_multi(bkey, filt.sel)
        counts, lo = join_ops.probe_counts(build, pkey, src.sel)
        n_src = src.sel.shape[0]
        capacity = self.ex.ladder.quantize(
            int(n_src * getattr(self.ex, "join_factor", 1))
        )
        probe_row, build_row, matched, total, _ = join_ops.expand_join_slots(
            build, counts, lo, capacity
        )
        self._note_capacity(total, capacity, "join")
        if need_verify:
            matched = matched & join_ops.verify_rows(
                fkeys, skeys, build_row, probe_row
            )
        pair_ok = matched & src.sel[probe_row]
        if node.filter is not None:
            lanes = {}
            for s, (v, ok) in src.lanes.items():
                lanes[s] = (v[probe_row], ok[probe_row])
            for s, (v, ok) in filt.lanes.items():
                lanes[s] = (v[build_row], ok[build_row] & matched)
            f = compile_expr(node.filter, self.lowering)
            fv, fok = f(lanes)
            pair_ok = pair_ok & fv & fok
        marks = jax.ops.segment_sum(
            pair_ok.astype(jnp.int32), probe_row, num_segments=n_src
        )
        return marks > 0

    def _visit_scalarjoin(self, node: P.ScalarJoin) -> Batch:
        src = self.visit(node.source)
        sub = self.visit(node.subquery)
        # single row: first selected row of sub (EnforceSingleRow)
        first = jnp.argmax(sub.sel)
        n = src.sel.shape[0]
        lanes = dict(src.lanes)
        for s, (v, ok) in sub.lanes.items():
            val = v[first]
            okv = ok[first] & (sub.sel.sum() > 0)
            shape = (n,) + val.shape  # wide decimals keep their limb dim
            lanes[s] = (
                jnp.broadcast_to(val, shape),
                jnp.broadcast_to(okv, (n,)),
            )
        return Batch(lanes, src.sel, src.ordered, src.replicated)

    # -- ordering --------------------------------------------------------
    def _visit_sort(self, node: P.Sort) -> Batch:
        b = self.visit(node.source)
        keys = self._rank_sort_keys(node.keys, b)
        perm = sort_ops.sort_perm(keys, b.lanes, b.sel)
        lanes, sel = sort_ops.apply_perm(b.lanes, perm, b.sel)
        return Batch(lanes, sel, ordered=True, replicated=b.replicated)

    def _visit_topn(self, node: P.TopN) -> Batch:
        b = self.visit(node.source)
        keys = self._rank_sort_keys(node.keys, b)
        lanes, sel, check = sort_ops.topn(
            keys, b.lanes, b.sel, node.count,
            getattr(self.ex, 'topn_factor', 1),
        )
        if check is not None:
            self._note_capacity(check[0], check[1], "topn")
        return Batch(lanes, sel, ordered=True, replicated=b.replicated)

    def _rank_sort_keys(self, keys, b: Batch):
        """Replace dict-coded sort columns by their lexicographic ranks."""
        out = []
        for k in keys:
            d = self.ex.dicts.get(k.column)
            if d is not None and (
                len(d) == 0  # zero-row split: codes are all sentinels
                or getattr(d, "sorted_by_code", False)  # code == rank
            ):
                d = None
            if d is not None:
                # DENSE ranks: generated dictionaries can carry duplicate
                # strings under distinct codes, and ordinal ranks would
                # order equal values by dictionary layout — hiding the
                # next sort key and making the order differ between the
                # monolithic and tiled (merged-dictionary) paths
                dd = np.asarray(d, dtype=str)
                order = np.argsort(dd, kind="stable")
                sd = dd[order]
                dense = np.zeros(len(d), dtype=np.int64)
                if len(d) > 1:
                    dense[1:] = np.cumsum(sd[1:] != sd[:-1])
                ranks = np.empty(len(d), dtype=np.int64)
                ranks[order] = dense
                v, ok = b.lanes[k.column]
                rank_tbl = jnp.asarray(ranks)
                safe = jnp.clip(v, 0, len(d) - 1)
                rv = jnp.where(v >= 0, rank_tbl[safe], -1)
                hidden = f"{k.column}$rank"
                b.lanes[hidden] = (rv, ok)
                out.append(
                    sort_ops.SortKey(hidden, k.ascending, k.nulls_first)
                )
            else:
                out.append(k)
        return out

    # -- window functions ------------------------------------------------
    def _visit_window(self, node: P.Window) -> Batch:
        """WindowOperator: one sort groups partitions and orders peers,
        then every function is a vector program over the sorted arrays
        (ops/window.py)."""
        b = self.visit(node.source)
        part_keys = tuple(
            sort_ops.SortKey(s) for s in node.partition_by
        )
        order_keys = tuple(self._rank_sort_keys(node.order_by, b))
        perm = sort_ops.sort_perm(part_keys + order_keys, b.lanes, b.sel)
        lanes, sel = sort_ops.apply_perm(b.lanes, perm, b.sel)
        part_lanes = [lanes[s] for s in node.partition_by]
        ord_lanes = [lanes[k.column] for k in order_keys]
        bounds = window_ops.compute_bounds(part_lanes, ord_lanes, sel)
        for f in node.functions:
            lanes[f.output] = self._window_output(f, lanes, sel, bounds)
            if f.args:
                d = self.ex.dicts.get(f.args[0])
                if d is not None and f.output_type.is_dictionary:
                    self.ex.dicts[f.output] = d
        return Batch(lanes, sel, ordered=False, replicated=b.replicated)

    def _window_output(self, f: P.WindowFunc, lanes, sel, b):
        W = window_ops
        if f.kind == "row_number":
            return W.row_number(b)
        if f.kind == "rank":
            return W.rank(b)
        if f.kind == "dense_rank":
            return W.dense_rank(b)
        if f.kind == "percent_rank":
            return W.percent_rank(b, sel)
        if f.kind == "cume_dist":
            return W.cume_dist(b, sel)
        if f.kind == "ntile":
            return W.ntile(b, sel, f.constants[0])
        if f.kind in ("lag", "lead"):
            off, default = f.constants
            return W.shift_value(
                lanes[f.args[0]], b, off, default, f.kind == "lead"
            )
        start, end = W.frame_range(f.frame, b)
        nonempty = end >= start
        if f.kind == "first_value":
            return W.value_at(lanes[f.args[0]], start, nonempty)
        if f.kind == "last_value":
            return W.value_at(lanes[f.args[0]], end, nonempty)
        if f.kind == "nth_value":
            return W.nth_value(lanes[f.args[0]], start, end, f.constants[0])
        if f.kind in ("count", "count_star"):
            lane = lanes[f.args[0]] if f.args else None
            _, cnt = W.framed_sum_count(
                lane, sel, start, end, count_star=f.kind == "count_star"
            )
            return cnt, jnp.ones(cnt.shape, bool)
        if f.kind in ("min", "max"):
            if lanes[f.args[0]][0].ndim == 2:
                # wide (two-limb) decimal lane: limb-wise masked compares
                v, cnt = W.framed_minmax_wide(
                    lanes[f.args[0]], sel, b, f.frame, f.kind
                )
                return (
                    jnp.where((cnt > 0)[:, None], v, jnp.zeros_like(v)),
                    cnt > 0,
                )
            v, cnt = W.framed_minmax(lanes[f.args[0]], sel, b, f.frame, f.kind)
            return jnp.where(cnt > 0, v, jnp.zeros_like(v)), cnt > 0
        if f.kind in ("sum", "avg"):
            ot, it_ = f.output_type, f.input_type
            in_lane = lanes[f.args[0]]
            wide_out = getattr(ot, "wide", False)
            if wide_out or in_lane[0].ndim == 2:
                # exact 128-bit windowed decimal sum (chunk cumsums)
                from ..ops import wide_decimal as wd

                wsum, cnt = W.framed_sum_wide(in_lane, sel, start, end)
                if f.kind == "sum":
                    return (
                        (wsum if wide_out else wd.narrow(wsum)), cnt > 0
                    )
                num = wd.rescale(wsum, ot.scale - it_.scale)
                q = wd.div_round(num, jnp.maximum(cnt, 1))
                return (q if wide_out else wd.narrow(q)), cnt > 0
            ssum, cnt = W.framed_sum_count(in_lane, sel, start, end)
            if f.kind == "sum":
                return ssum, cnt > 0
            den = jnp.maximum(cnt, 1)
            if ssum.dtype.kind == "f":
                v = ssum / den
            elif ot.name in ("double", "real"):
                v = ssum.astype(ot.np_dtype) / den
            elif ot.is_decimal and it_ is not None:
                shift = 10 ** (ot.scale - it_.scale)
                num = ssum * shift
                sign = jnp.sign(num)
                anum = jnp.abs(num)
                q = anum // den
                rem = anum - q * den
                v = sign * (q + (2 * rem >= den))
            else:
                v = ssum // den
            return v, cnt > 0
        raise ExecutionError(f"window function {f.kind} not implemented")

    # -- set ops ---------------------------------------------------------
    def _visit_setoperation(self, node: P.SetOperation) -> Batch:
        """UNION [ALL] / INTERSECT / EXCEPT (UnionNode, IntersectNode,
        ExceptNode).  Intersect/except use distinct semantics via one sort
        over the concatenated inputs with per-side presence counts (the
        reference lowers them to union + mark + filter; here the sort-based
        group machinery does both in one kernel)."""
        if node.kind in ("intersect", "except"):
            return self._intersect_except(node)
        lanes, sel, _ = self._union_lanes(node)
        batch = Batch(lanes, sel)
        if not node.all:
            # UNION DISTINCT via the Distinct path
            batch = Batch(*self._distinct_rows(lanes, node.symbols, sel))
        return batch

    def _union_lanes(self, node: P.SetOperation):
        """Visit and concatenate all inputs positionally; returns
        (lanes, sel, per-input capacities)."""
        batches = [self.visit(i) for i in node.inputs]
        caps = [b.sel.shape[0] for b in batches]
        lanes = {}
        for pos, (out_sym, (_, t)) in enumerate(zip(node.symbols, node.types_)):
            vs, oks = [], []
            src_syms = [inp.output_symbols()[pos] for inp in node.inputs]
            if t.is_dictionary:
                # re-encode each input's codes into a merged dictionary
                in_dicts = [self.ex.dicts.get(s) for s in src_syms]
                if any(d is None for d in in_dicts):
                    raise ExecutionError("union of non-dict varchar")
                merged: List[str] = []
                index: Dict[str, int] = {}
                remaps = []
                for d in in_dicts:
                    table = np.empty(len(d), dtype=np.int32)
                    for i, s in enumerate(d):
                        if s not in index:
                            index[s] = len(merged)
                            merged.append(s)
                        table[i] = index[s]
                    remaps.append(jnp.asarray(table))
                self.ex.dicts[out_sym] = np.array(merged, dtype=object)
                from ..expr.functions import dict_gather

                for b, s, tbl in zip(batches, src_syms, remaps):
                    v, ok = b.lanes[s]
                    vs.append(dict_gather(tbl, v, -1).astype(jnp.int32))
                    oks.append(ok)
            else:
                wide_t = getattr(t, "wide", False)
                for b, s in zip(batches, src_syms):
                    v, ok = b.lanes[s]
                    if wide_t:
                        # inputs may mix two-limb lanes with narrow
                        # fast-path lanes of the same wide type
                        from ..ops.wide_decimal import promote

                        vs.append(promote(v.astype(jnp.int64) if v.ndim == 1 else v))
                    else:
                        vs.append(v.astype(t.np_dtype))
                    oks.append(ok)
            lanes[out_sym] = (jnp.concatenate(vs), jnp.concatenate(oks))
        sel = jnp.concatenate([b.sel for b in batches])
        return lanes, sel, caps

    def _setop_tag_reduce(self, node, lanes0, sel, tag, cap):
        """Shared INTERSECT/EXCEPT membership reduction over tagged
        rows: group-sort by the full row, per-side presence marks,
        keep-group predicate, first-of-group dedup.  Used by the local
        path and (post-repartition) by the mesh path."""
        tagged = {**lanes0, "__tag__": (tag, jnp.ones(tag.shape[0], bool))}
        lanes, sel_sorted, gid, ngroups = self._group_sort(
            tagged, node.symbols, sel, cap
        )
        self._note_capacity(ngroups, cap)
        tag_sorted, _ = lanes.pop("__tag__")
        side0 = agg_ops._seg_count(
            sel_sorted & (tag_sorted == 0), gid, cap
        ) > 0
        side1 = agg_ops._seg_count(
            sel_sorted & (tag_sorted == 1), gid, cap
        ) > 0
        keep_group = (
            side0 & side1 if node.kind == "intersect" else side0 & ~side1
        )
        boundary = jnp.concatenate(
            [jnp.ones(1, dtype=bool), gid[1:] != gid[:-1]]
        )
        return Batch(lanes, sel_sorted & boundary & keep_group[gid])

    def _intersect_except(self, node: P.SetOperation) -> Batch:
        if node.all:
            raise ExecutionError(
                f"{node.kind.upper()} ALL not supported (DISTINCT only)"
            )
        assert len(node.inputs) == 2
        lanes0, sel, caps = self._union_lanes(node)
        tag = jnp.concatenate([
            jnp.zeros(caps[0], dtype=jnp.int32),
            jnp.ones(caps[1], dtype=jnp.int32),
        ])
        return self._setop_tag_reduce(node, lanes0, sel, tag, sel.shape[0])


LocalExecutor.trace_ctx_cls = _TraceCtx
