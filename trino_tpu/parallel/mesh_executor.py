"""Distributed execution over a jax device mesh.

Reference parity: the distributed dataflow stack —
  - split assignment across workers (SourcePartitionedScheduler /
    NodeScheduler/UniformNodeSelector): table splits sharded over the
    mesh's 'workers' axis
  - exchanges (operator/exchange, execution/buffer + HTTP page shuffle,
    HttpPageBufferClient.java:98): XLA collectives over ICI inside one
    shard_map program —
      partial->final aggregation    = psum / all-gather + re-merge
      broadcast join build side     = all_gather  (BroadcastOutputBuffer /
                                       FIXED_BROADCAST_DISTRIBUTION)
      gathering exchange at root    = all_gather  (SINGLE distribution)
      hash repartition              = all_to_all  (parallel/shuffle.py,
                                       FIXED_HASH_DISTRIBUTION)
  - DistributedQueryRunner's "N servers in one process" test story maps
    to N mesh devices in one process (virtual CPU devices in tests).

The program is SPMD: every device runs the same fragment over its split
shard; collectives implement the exchange boundaries that the reference
places with AddExchanges (optimizations/AddExchanges.java:138).  Batch
.replicated tracks which intermediate results are device-identical
(the SINGLE vs partitioned distribution property of PlanFragments).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P_

from ..catalog import CatalogManager
from ..exec.local import (
    Batch,
    ExecutionError,
    LocalExecutor,
    dict_fingerprint,
    merge_pages_to_arrays,
    _shape_summary,
    _TraceCtx,
)
from ..exec.shapes import lane_align
from ..obs import compile_observatory as _compile_obs
from ..obs import device_profile
from ..utils.tracing import TRACER
from ..expr import ir
from ..expr.lower import compile_expr
from ..ops import aggregation as agg_ops
from ..ops import join as join_ops
from ..ops import sketches
from ..ops import sort as sort_ops
from ..ops import tree_nbytes
from . import shuffle
from ..page import Page, same_dictionary
from ..plan import nodes as P
from ..runtime import Breadcrumb, DeviceFaultError

AXIS = "workers"


def _is_hll_lane(spec, name: str) -> bool:
    """True for the packed-register HLL accumulator lanes of
    approx_distinct — the one sketched state a mesh collective CAN merge
    (register-wise max); other sketched lanes (k-min-hash samples) still
    need the gathered merge path."""
    return spec.kind == "approx_distinct" and "$hll" in name


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def default_mesh(n: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    n = n or len(devs)
    return Mesh(np.array(devs[:n]), (AXIS,))


def _agather(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.all_gather(x, AXIS, axis=0, tiled=True)


def _pextreme(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    """Cross-device max/min of a replicated-shape value.  XLA:TPU lowers
    a 64-bit all-reduce only for sums (the v5e compiler refuses the rest:
    "Supported lowering only of Sum all reduce"), so 64-bit lanes are
    gathered and reduced locally; narrower lanes use the collective."""
    if jnp.dtype(x.dtype).itemsize == 8:
        gathered = jax.lax.all_gather(x, AXIS)
        return (jnp.max if kind == "max" else jnp.min)(gathered, axis=0)
    return (jax.lax.pmax if kind == "max" else jax.lax.pmin)(x, AXIS)


def _pmax(x: jnp.ndarray) -> jnp.ndarray:
    return _pextreme(x, "max")


def _shuffle_chunk(cap: int, ndev: int, factor: int, quantize=None) -> int:
    """Per-destination chunk capacity for a hash repartition: expected
    cap/ndev rows per bucket with 2x skew slack, grown by the retry-ladder
    factor on overflow.  `quantize` is the executor ladder's rung
    function (plain lane alignment when absent)."""
    q = quantize or lane_align
    return q(max(128, (2 * cap * factor) // ndev))


def _decode_direct_keys(domains, cap):
    """Recover group key codes from the dense mixed-radix group id —
    avoids cross-device gathers of representative rows."""
    gids = jnp.arange(cap, dtype=jnp.int64)
    radixes = [d + 1 for d in domains]
    strides = []
    s = 1
    for r in reversed(radixes):
        strides.append(s)
        s *= r
    strides = list(reversed(strides))
    out = []
    for dom, stride, radix in zip(domains, strides, radixes):
        code = (gids // stride) % radix
        ok = code < dom  # slot `dom` encodes NULL
        out.append((code.astype(jnp.int32), ok))
    return out


def _stack_shards(shards, cap: int) -> Dict[str, tuple]:
    """Per-device host columns {sym: (values, validity or None)} as one
    {sym: (values [ndev, cap, ...], validity [ndev, cap])}: device d's
    rows lead row block d, zero padding (dead, invalid) follows."""
    out = {}
    for sym, (v0, _) in shards[0].items():
        stacked = np.zeros((len(shards), cap) + v0.shape[1:], dtype=v0.dtype)
        okstack = np.zeros((len(shards), cap), dtype=bool)
        for d, shard in enumerate(shards):
            v, ok = shard[sym]
            stacked[d, : len(v)] = v
            okstack[d, : len(v)] = True if ok is None else ok
        out[sym] = (stacked, okstack)
    return out


def _gather_batch(b: Batch) -> Batch:
    return Batch(
        {s: (_agather(v), _agather(ok)) for s, (v, ok) in b.lanes.items()},
        _agather(b.sel),
        b.ordered,
        replicated=True,
    )


class MeshExecutor(LocalExecutor):
    """Executes a logical plan SPMD over all mesh devices."""

    def __init__(self, catalogs: CatalogManager, mesh: Optional[Mesh] = None,
                 config: Optional[dict] = None):
        super().__init__(catalogs, config)
        self.mesh = mesh or default_mesh()
        # supervisor identity of each mesh position: default_mesh takes
        # the first n jax devices, so position i IS supervisor device i
        self._mesh_device_ids = list(range(self.mesh.devices.size))
        self.mesh_tasks: List[dict] = []

    # ------------------------------------------------------------------
    def execute(self, plan: P.PlanNode) -> Page:
        assert isinstance(plan, P.Output)
        sup = self.supervisor
        if not self._device_fallback:
            for d in list(self._mesh_device_ids):
                sup.maybe_probe(device_id=d)
            self._shrink_to_healthy()
            if not any(
                sup.healthy(device_id=d) for d in self._mesh_device_ids
            ):
                # every mesh device is out: same degrade/refuse gate as
                # the single-device executor
                bc = Breadcrumb(
                    "mesh:%d/pre-dispatch" % self.mesh.devices.size,
                    query_id=self.query_id,
                    task_id=str(self.config.get("task_id") or ""),
                    mode="gate",
                )
                fault = DeviceFaultError(
                    "device_"
                    + sup.device_state(
                        device_id=self._mesh_device_ids[0]
                    ).lower(),
                    bc,
                )
                if not self._cpu_fallback_enabled():
                    raise fault
                return self._run_cpu_fallback(plan, fault)
        try:
            return self._execute_mesh(plan)
        except DeviceFaultError as fault:
            if self._device_fallback:
                raise
            # a device faulted mid-query and the supervisor quarantined
            # it: shrink the mesh to the healthy subset and re-run there
            # (fewer, larger shards) before degrading all the way to CPU
            if self._shrink_to_healthy():
                try:
                    return self._execute_mesh(plan)
                except DeviceFaultError:
                    pass
            if not self._cpu_fallback_enabled():
                raise
            return self._run_cpu_fallback(plan, fault)

    # ------------------------------------------------------------------
    def _shrink_to_healthy(self) -> bool:
        """Drop quarantined/blacklisted devices from the mesh so the
        query keeps executing over the healthy subset instead of
        failing — a lost shard costs parallelism, not the query.
        Returns True when the mesh changed (the caller then re-shards
        scans over the smaller mesh)."""
        sup = self.supervisor
        ids = list(self._mesh_device_ids)
        healthy = [d for d in ids if sup.healthy(device_id=d)]
        if not healthy or len(healthy) == len(ids):
            return False
        from ..obs import journal

        by_id = dict(zip(ids, list(self.mesh.devices.flat)))
        for d in ids:
            if d in healthy:
                continue
            journal.emit(
                journal.MESH_SHRINK,
                query_id=self.query_id,
                severity=journal.WARN,
                deviceId=d,
                deviceState=sup.device_state(device_id=d),
                fromSize=len(ids),
                toSize=len(healthy),
            )
        self.kernel_profile["meshShrinks"] = (
            self.kernel_profile.get("meshShrinks", 0)
            + (len(ids) - len(healthy))
        )
        self.mesh = Mesh(np.array([by_id[d] for d in healthy]), (AXIS,))
        self._mesh_device_ids = healthy
        return True

    # ------------------------------------------------------------------
    def _run_cpu_fallback(self, plan: P.PlanNode, fault) -> Page:
        # the SPMD program pins explicit mesh devices, so re-running it
        # under jax.default_device would still target the faulted chips;
        # degrade to the single-device executor's eager CPU path instead
        local = LocalExecutor(self.catalogs, dict(self.config))
        local.query_id = self.query_id
        page = local._run_cpu_fallback(plan, fault)
        self.kernel_profile.update(local.kernel_profile)
        self.node_stats.update(getattr(local, "node_stats", {}) or {})
        self.scan_bytes = getattr(local, "scan_bytes", self.scan_bytes)
        return page

    # ------------------------------------------------------------------
    def _dispatch(self, thunk, bc):
        if self._device_fallback:
            return thunk()
        return self.supervisor.dispatch(
            thunk, bc, device_id=self._mesh_device_ids[0]
        )

    def _device_get(self, objs, bc):
        if self._device_fallback:
            return jax.device_get(objs)  # dispatch-guard: ok
        return self.supervisor.device_get(
            objs, bc, device_id=self._mesh_device_ids[0]
        )

    def _record_kernel(self, digest, compile_s, cached, mode="jit",
                       cause=None):
        # every mesh-path kernel record carries the axis-size tag, so
        # flight records and kernel profiles can tell 8-way from
        # single-chip executions of the same plan
        tag = "mesh:%d" % self.mesh.devices.size
        if not str(digest).startswith("mesh:"):
            digest = "%s/%s" % (tag, digest)
        return super()._record_kernel(digest, compile_s, cached,
                                      mode=mode, cause=cause)

    def _dispatched_cap(self, nid, count: int) -> int:
        # every shard is padded to the rung of the largest one
        return self.mesh.devices.size * super()._dispatched_cap(nid, count)

    # -- scans: the one-chip path, sharded ------------------------------
    def _mesh_ids(self) -> tuple:
        return tuple(int(d.id) for d in self.mesh.devices.flat)

    def _scan_cache_key(self, node: P.TableScan, splits):
        """The one-chip key plus the devices the lanes are sharded over:
        a mesh of another size, or one shrunk around a lost device, finds
        neither these lanes nor (through `_jit_scan_component`) a program
        compiled for them."""
        key = super()._scan_cache_key(node, splits)
        return None if key is None else key + (("mesh",) + self._mesh_ids(),)

    def _devgen_spec(self, devgen_fn, table, cols, splits):
        """One generation recipe a device: device d takes the splits
        `splits[d::ndev]` (the NodeScheduler's round-robin, devices
        standing in for workers), which a contiguous range connector
        turns into its own (lo, hi, exact row count).  A device with no
        split holds an empty shard."""
        ndev = self.mesh.devices.size
        groups = [splits[d::ndev] for d in range(ndev)]
        specs = [devgen_fn(table, cols, g) if g else None for g in groups]
        if any(sp is None and g for sp, g in zip(specs, groups)):
            return None
        live = [sp for sp in specs if sp is not None]
        if not live:
            return None
        spec = dict(live[0])
        spec["shards"] = [
            (sp["lo"], sp["hi"], sp["count"]) if sp is not None else (0, 0, 0)
            for sp in specs
        ]
        spec["lo"] = min(sp["lo"] for sp in live)
        spec["hi"] = max(sp["hi"] for sp in live)
        spec["count"] = sum(sp["count"] for sp in live)
        spec["orders_hashed"] = sum(
            sp.get("orders_hashed", 0) for sp in live
        )
        return spec

    def _load_host_scan(self, node: P.TableScan, splits, key, cache,
                        scans, dicts, counts):
        """Scans no device can generate: each device's splits are read
        and merged on the host, stacked [ndev, cap] (a row block a
        device) and uploaded per query; the scan cache does not hold
        them."""
        ndev = self.mesh.devices.size
        cols = [c for _, c in node.assignments]
        sym_of = {c: self._sym_for(node, c) for c in cols}
        symbols = [sym_of[c] for c in cols]
        tmap = dict(node.types)
        types = [(s, tmap[s]) for s in symbols]
        per_dev: List[Dict[str, tuple]] = []
        per_dev_dicts: List[Dict[str, np.ndarray]] = []
        dev_counts: List[int] = []
        for d in range(ndev):
            ddicts: Dict[str, np.ndarray] = {}
            merged_d, total = merge_pages_to_arrays(
                self._split_pages(node, splits[d::ndev], cols, sym_of),
                symbols, types, ddicts,
            )
            per_dev.append(merged_d)
            per_dev_dicts.append(ddicts)
            dev_counts.append(total)
        self._merge_split_dicts(per_dev, per_dev_dicts, dicts)
        for s, t in types:
            if t.is_dictionary and s not in dicts:
                dicts[s] = np.array([], dtype=object)
        scans[id(node)] = _stack_shards(
            per_dev, self.ladder.quantize(max(max(dev_counts), 1))
        )
        counts[id(node)] = np.array(dev_counts, dtype=np.int64)
        self._scan_keys[id(node)] = key
        self._scan_dictfp[id(node)] = dict_fingerprint(dicts, symbols)

    def _upload_lane(self, arr, valid, cap):
        # an [ndev, cap] stack (`_stack_shards`), one row block a device;
        # supervised: a device lost while it receives is this crumb's
        ndev = self.mesh.devices.size
        return self._dispatch(
            lambda: jax.device_put(  # dispatch-guard: ok (in thunk)
                (arr, valid), NamedSharding(self.mesh, P_(AXIS))
            ),
            self._dispatch_crumb(
                "mesh:%d/upload" % ndev, "upload", {"lane": arr}
            ),
        )

    def _load_sharded_scans(self, plan: P.PlanNode, ndev: int):
        """Every scan of the plan through `LocalExecutor._load_one_scan`
        (scan cache, device generation recipe, host fall-back), sharded:
        `scans[id(node)]` holds what `_device_lanes` turns into
        [ndev, cap] lanes, `counts[id(node)]` the live rows of each
        shard."""
        scans: Dict[int, dict] = {}
        counts: Dict[int, np.ndarray] = {}
        dicts: Dict[str, np.ndarray] = {}
        # preorder TableScan index: the same ordinal FragmentExecutor's
        # _load_walk uses as the scheduler's split-assignment key, so the
        # cross-host subclass can look up its ASSIGNED splits
        scan_idx = [0]

        def walk(node: P.PlanNode):
            if isinstance(node, P.TableScan):
                idx = scan_idx[0]
                scan_idx[0] += 1
                self._load_one_scan(
                    node, self._scan_splits(node, idx, ndev),
                    scans, dicts, counts,
                )
                spec = self._devgen.get(id(node))
                if spec is not None:
                    counts[id(node)] = np.array(
                        [n for _, _, n in spec["shards"]], dtype=np.int64
                    )
                return
            if isinstance(node, P.RemoteSource):
                self._load_remote_source(node, ndev, scans, counts, dicts)
                return
            for s in node.sources:
                walk(s)

        walk(plan)
        return scans, counts, dicts

    # ------------------------------------------------------------------
    def _execute_mesh(self, plan: P.PlanNode) -> Page:
        t_exec0 = time.perf_counter()
        self.mesh_tasks = []
        ndev = self.mesh.devices.size
        with TRACER.span("load_scans"):
            scans, counts, dicts = self._load_sharded_scans(plan, ndev)
            self.dicts = dicts
            # skew pre-pass: where a partitioned join's key column is on
            # the host (a scan no device generates), measure its real
            # bucket load before tracing, so the shuffle chunk is sized
            # for the observed skew up front.  Lanes resident in HBM are
            # not pulled back for it: the capacity ladder sizes those
            # shuffles, and the rung it settles on is remembered.
            self.shuffle_hints = self._skew_shuffle_hints(
                plan, scans, counts, ndev
            )
        # the estimate is bounded by the tables' rows, not a shard's
        self._ladder_start(
            plan, {nid: int(c.sum()) for nid, c in counts.items()}
        )
        # operator_stats on a backend with device planes: each attempt is
        # profiled, and the settled one's (the last `cap`) gives each
        # device's task wall its measured busy time; while another
        # profile runs the walls stay row shares
        probe = bool(self.config.get("collect_node_stats")) and (
            device_profile.has_device_planes()
        )
        for attempt in range(7):
            self._ladder_attempt = attempt
            with device_profile.capture_if_free(probe) as cap:
                out, cell, prep = self._run_sharded(plan, scans, counts)
                # ONE supervised transfer for every retry-ladder check and
                # the output lanes (a retry simply discards the lanes)
                with TRACER.span("device_get"):
                    (checks, dups, colls, wides, sflags, host_lanes,
                     sel_np) = self._device_get(
                        out[2:] + (
                            {s: out[0][s] for s in plan.symbols}, out[1]
                        ),
                        self._dispatch_crumb(
                            self._last_crumb.kernel, "device_get"
                        ),
                    )
            if self._ladder_settled(
                cell["dup_nodes"], dups, colls, wides,
                cell["caps"], checks, sflags,
            ):
                break
        else:
            raise ExecutionError("group capacity overflow after retries")
        self._ladder_remember(plan)

        with TRACER.span("materialize_host"):
            totals = {nid: int(c.sum()) for nid, c in counts.items()}
            self._finalize_kernel_profile(scans, totals, host_lanes, sel_np)
            # what the program read, as it was padded and sharded:
            # {ordinal: {sym: (values, ok), "__count__": rows}}, every
            # leaf [ndev, ...] on the mesh axis
            self.scan_bytes = tree_nbytes(prep)
            page = self._materialize_host(plan, host_lanes, sel_np)
        if self.config.get("collect_node_stats"):
            census = self.kernel_profile.get("programCensus") or {}
            self._mesh_node_stats(
                plan, scans, counts,
                time.perf_counter() - t_exec0, ndev, page,
                device_profile.reduce(
                    device_profile.last_module(cap.get("planes") or {}),
                    census.get("ops"),
                ),
            )
        return page

    def _run_sharded(self, plan: P.Output, scans, counts):
        """`LocalExecutor._run_jitted` for the mesh: one SPMD program a
        fragment, compiled once per (fragment key, mesh, ladder state)
        into the session's jit cache and launched once a query.  Returns
        (the program's outputs, its trace-time cell, the lanes it was
        called with)."""
        from ..cache.compile_cache import fragment_key, stable_key_digest

        cache = self.config.get("jit_cache")
        if cache is None:
            cache = {}
        ndev = self.mesh.devices.size
        with TRACER.span("device_lanes"):
            # a scan's rung is the rung of its largest shard
            rows = {nid: max(int(c.max()), 1) for nid, c in counts.items()}
            key, order, by_ord = fragment_key(
                self, plan, scans, rows, self.ladder.quantize
            )
            key = key + (
                ("mesh",) + self._mesh_ids(),
                ("shuffle_hints", tuple(sorted(
                    (order.get(nid, nid), side, cap)
                    for (nid, side), cap in self.shuffle_hints.items()
                ))),
                ("megakernels", self._megakernel_mode()),
            )
            digest = "mesh:%d/%s" % (ndev, stable_key_digest(key)[:12])
            # keyed by plan ordinal, as in _run_jitted: dict keys are part
            # of the jit pytree, and ordinals make it session-invariant
            prep = {}
            for nid, arrays in scans.items():
                lanes = dict(self._device_lanes(
                    self._scan_nodes.get(nid), arrays, rows[nid], nid
                ))
                # each shard's live rows ride as a traced [ndev] vector
                lanes["__count__"] = counts[nid]
                prep[order.get(nid, nid)] = lanes
            self.kernel_profile["scanShards"] = {
                "%s.%s" % (o, sym): [
                    (sh.device.id, int(np.prod(sh.data.shape)))
                    for sh in lane[0].addressable_shards
                ]
                for o, lanes in prep.items()
                for sym, lane in lanes.items() if sym != "__count__"
            }
            # unversioned sources may change without a shape change: no
            # safe executable reuse (the jit path's rule)
            keyed = all(
                self._scan_keys.get(nid) is not None for nid in scans
            )
            entry = cache.get(key) if keyed else None
        self.kernel_profile["meshProgramCache"] = (
            "hit" if entry is not None else "miss"
        )
        bc = self._dispatch_crumb(digest, "mesh", prep)
        self._last_crumb = bc
        if entry is not None:
            cell = entry["cell"]
            self.dicts.update(cell["dicts"])
            census = entry["census"]
            fn = entry["fn"]
            with TRACER.span("launch"):
                out = self._dispatch(lambda: fn(prep), bc)
            self._record_kernel(
                digest, compile_s=0.0, cached=True, mode="mesh"
            )
        else:
            cell = {}
            ids = {o: i for i, o in order.items()}

            def fragment(prep_arg):
                # class-attribute hook (LocalExecutor.trace_ctx_cls
                # idiom): the cross-host slice executor swaps in
                # _SliceTraceCtx
                ctx = self.mesh_trace_ctx_cls(
                    self, {ids.get(o, o): v for o, v in prep_arg.items()},
                    None,
                )
                ctx.ordinals = order
                batch = ctx.visit(plan.source)
                if not batch.replicated:
                    batch = _gather_batch(batch)
                cell["caps"] = list(ctx.capacity_limits)
                cell["op_counts"] = dict(ctx.op_counts)  # one shard's
                # dup-check join nodes as plan ordinals: another session
                # hitting this entry resolves them to ITS plan's nodes
                cell["dup_ords"] = [
                    order.get(id(n), -1) for n, _ in ctx.dup_checks
                ]
                return (
                    {s: batch.lanes[s] for s in plan.symbols},
                    batch.sel,
                    tuple(ctx.capacity_checks),
                    tuple(d for _, d in ctx.dup_checks),
                    tuple(ctx.collision_checks),
                    tuple(
                        jax.lax.psum(w, AXIS)
                        for w in ctx.lowering.overflow_flags
                    ),
                    tuple(
                        jax.lax.psum(sv, AXIS) for sv in ctx.sum_overflow
                    ),
                )

            compile_start = time.time()
            family = self._compile_family(plan)
            fragment.__name__ = fragment.__qualname__ = "frag_" + family
            family = "mesh%d:%s" % (ndev, family)
            scan_rows = [int(r) for c in counts.values() for r in c]
            actual_rows = sum(scan_rows)
            padded_rows = self._padded_rows(rows)
            shape_sig = self._compile_shape_sig(rows)
            shapes = _shape_summary(prep)
            cause = _compile_obs.get_observatory().classify(
                family, shape_sig,
                ladder_attempt=self._ladder_attempt,
                persistent=bool(
                    keyed
                    and getattr(cache, "persistent_known", None) is not None
                    and cache.persistent_known(key)
                ),
                query_id=self.query_id,
            )
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
                # trace + compile outside the watchdog (see
                # LocalExecutor._run_jitted); only execution is supervised
                fn = self._compile_fragment(
                    jax.jit(  # dispatch-guard: ok (lazy wrapper)
                        _shard_map(fragment, self.mesh, (P_(AXIS),), P_())
                    ),
                    prep,
                )
                census = self._program_census(fn, digest)   # one shard's
                compile_s = time.time() - compile_start
                with TRACER.span("launch"):
                    out = self._dispatch(lambda: fn(prep), bc)
            _compile_obs.record_compile(
                kernel=digest, family=family, cause=cause,
                mode="mesh", shapes=shapes, shape_sig=shape_sig,
                actual_rows=actual_rows, padded_rows=padded_rows,
                compile_wall_s=compile_s,
                query_id=self.query_id,
                task_id=str(self.config.get("task_id") or ""),
                node_id=str(self.config.get("node_id") or ""),
                scan_rows=scan_rows,
            )
            self._record_kernel(
                digest, compile_s=compile_s,
                cached=False, mode="mesh", cause=cause,
            )
            self._note_op_counts(cell["op_counts"])
            cell["dicts"] = dict(self.dicts)
            if keyed:
                # the plan reference pins id(plan) (fingerprint memo)
                cache[key] = {
                    "fn": fn, "cell": cell, "plan": plan, "census": census,
                }
        self.kernel_profile["programCensus"] = census
        cell = dict(
            cell, dup_nodes=[by_ord.get(o) for o in cell["dup_ords"]]
        )
        return out, cell, prep

    # ------------------------------------------------------------------
    def _mesh_node_stats(self, plan, scans, counts, wall_s, ndev, page,
                         chips=None):
        """Post-execute operator/task stats for the SPMD program.

        The eager per-node row probes cannot run inside shard_map (the
        counts are traced there), so the mesh makes its timeline after
        the program settles: whole-plan node stats feeding
        frames_from_plan, plus one task rollup PER SHARD so stage
        timelines and the straggler detector see shards.  `chips` is the
        execution's device profile (obs/device_profile.reduce): a
        device's task wall, and its root frame's `deviceWallS`, is its
        measured busy time.  Only where the backend
        has no device plane (`chips` None: virtual CPU devices) is a
        shard's wall the program's scaled by its scan-row share relative
        to the heaviest shard."""
        from ..obs import opstats

        shard_rows = np.zeros(ndev, dtype=np.int64)
        total_rows = 0
        total_bytes = 0

        def walk(n):
            nonlocal total_rows, total_bytes
            if isinstance(n, P.TableScan):
                cnts = counts.get(id(n))
                nbytes = tree_nbytes(scans.get(id(n)))
                rows = int(cnts.sum()) if cnts is not None else 0
                total_rows += rows
                total_bytes += nbytes
                if cnts is not None:
                    for d in range(min(ndev, len(cnts))):
                        shard_rows[d] += int(cnts[d])
                self.node_stats[id(n)] = {
                    "rows": rows,
                    "bytes": nbytes,
                    "wall_s": 0.0,
                    "device_wall_s": 0.0,
                    "calls": ndev,
                }
            for s in n.sources:
                walk(s)

        walk(plan)
        out_bytes = sum(
            int(getattr(c.values, "nbytes", 0) or 0) for c in page.columns
        )
        # the fragment root carries the whole program wall (walls are
        # inclusive; frames_from_plan subtracts child walls for own-wall)
        self.node_stats[id(plan.source)] = {
            "rows": int(page.count),
            "bytes": out_bytes,
            "wall_s": float(wall_s),
            "device_wall_s": float(wall_s),
            "calls": 1,
        }
        frames = opstats.frames_from_plan(plan, self.node_stats)
        qid = self.query_id or "query"
        heaviest = int(shard_rows.max()) if ndev else 0
        total = int(shard_rows.sum())
        tasks = []
        for d in range(ndev):
            chip = (chips or {}).get(
                "%s%d" % (device_profile.DEVICE_PLANE,
                          self._mesh_device_ids[d])
            )
            frac = 1.0 if chip else (
                (int(shard_rows[d]) / heaviest) if heaviest else 1.0
            )
            share = (int(shard_rows[d]) / total) if total else 1.0 / ndev
            fl = []
            for f in frames:
                g = dict(f)
                for k in ("inputRows", "inputBytes", "outputRows",
                          "outputBytes"):
                    if k in g:
                        g[k] = int((f.get(k) or 0) * share)
                for k in ("wallS", "deviceWallS", "hostWallS"):
                    if k in g:
                        g[k] = float(f.get(k) or 0.0) * frac
                fl.append(g)
            if chip:
                # the fragment root's frame stands for the whole program
                # (the only operator frames a mesh makes are its and the
                # scans'): the chip's busy time
                device_profile.apply_device_time(fl, {
                    "%s#1" % type(plan.source).__name__: chip["busyMs"],
                })
            tasks.append({
                "taskId": "%s.0.%d" % (qid, d),
                "nodeId": "device-%d" % self._mesh_device_ids[d],
                "operatorStats": opstats.task_rollup(
                    fl, wall_s=chip["busyMs"] / 1e3 if chip
                    else float(wall_s) * frac
                ),
            })
        self.mesh_tasks = tasks

    # ------------------------------------------------------------------
    def _skew_shuffle_hints(self, plan, scans, counts, ndev):
        """Per (join-node, side) shuffle-chunk capacities measured on the
        host scan arrays (scans no device generates; lanes resident in
        HBM are never pulled back): bucket every traceable single-column
        join key
        with the SAME splitmix the device shuffle uses and record the
        worst per-(sender, destination) load.  Filters below the join
        only remove rows, so the measurement is a safe overestimate; the
        capacity ladder remains the backstop for untraceable keys.

        Reference analog: SkewedPartitionRebalancer's observed-load
        sizing, applied to the mesh all_to_all instead of writer tasks."""
        from .shuffle import mix64_np

        hints: Dict[Tuple[int, str], int] = {}

        def scan_col(node, sym):
            while True:
                if isinstance(node, P.Filter):
                    node = node.source
                    continue
                if isinstance(node, P.Project):
                    nxt = None
                    for s, e in node.assignments:
                        if s == sym:
                            if isinstance(e, ir.ColumnRef):
                                nxt = e.name
                            break
                    if nxt is None:
                        return None
                    sym, node = nxt, node.source
                    continue
                if isinstance(node, P.TableScan):
                    return node, sym
                return None

        def measure(side, sym):
            t = scan_col(side, sym)
            if t is None:
                return None
            scan_node, ssym = t
            merged = scans.get(id(scan_node))
            if merged is None or ssym not in merged:
                return None
            arr = merged[ssym][0]
            lens = counts.get(id(scan_node))
            if (
                # lanes generated in HBM stay there: no hint
                not isinstance(arr, np.ndarray)
                or arr.ndim != 2 or arr.dtype.kind not in "iu"
            ):
                return None
            worst = 0
            for d in range(arr.shape[0]):
                n = int(lens[d]) if lens is not None else arr.shape[1]
                # count EVERY row, null keys included: the device buckets
                # by the residual value lane regardless of validity (and
                # sides that drop nulls before shuffling just make this a
                # safe overestimate)
                v = arr[d, :n]
                if len(v) == 0:
                    continue
                b = (mix64_np(v.astype(np.int64)) % np.uint64(ndev))
                worst = max(worst, int(np.bincount(
                    b.astype(np.int64), minlength=ndev
                ).max()))
            if worst == 0:
                return None
            return self.ladder.quantize(max(128, int(worst * 1.3)))

        def _wide_key(node, sym):
            t = node.output_types().get(sym)
            return bool(getattr(t, "wide", False))

        def walk(n):
            if (
                isinstance(n, P.Join)
                and len(n.criteria) == 1
                # only the partitioned path reads the hint; measuring
                # broadcast joins would put O(rows) host hashing on the
                # critical path for nothing
                and n.distribution == "partitioned"
            ):
                l, r = n.criteria[0]
                # wide (two-limb) keys force JOINT composite hashing on
                # the device — a raw-value host measurement would use a
                # different bucket permutation
                if not (_wide_key(n.left, l) or _wide_key(n.right, r)):
                    h = measure(n.left, l)
                    if h is not None:
                        hints[(id(n), "l")] = h
                    h = measure(n.right, r)
                    if h is not None:
                        hints[(id(n), "r")] = h
            if isinstance(n, P.SemiJoin) and len(n.source_keys) == 1:
                if not (
                    _wide_key(n.source, n.source_keys[0])
                    or _wide_key(n.filtering, n.filtering_keys[0])
                ):
                    h = measure(n.source, n.source_keys[0])
                    if h is not None:
                        hints[(id(n), "l")] = h
                    h = measure(n.filtering, n.filtering_keys[0])
                    if h is not None:
                        hints[(id(n), "r")] = h
            for s in n.sources:
                walk(s)

        try:
            walk(plan)
        except Exception:
            return {}
        return hints

    # ------------------------------------------------------------------
    def _scan_splits(self, node: P.TableScan, idx: int, ndev: int):
        """All of a table's splits — this executor owns the whole mesh.
        The cross-host subclass narrows this to the splits the
        coordinator assigned to THIS host's task (split assignment
        happened one level up, across hosts)."""
        conn = self.catalogs.get(node.catalog)
        # real connector splits (hive files/row groups, tpch shards)
        # round-robin over devices — the NodeScheduler split
        # placement, with devices standing in for worker nodes
        return conn.split_manager().get_splits(
            node.table, ndev, node.constraint
        )

    def _load_remote_source(self, node, ndev, scans, counts, dicts):
        # single-process mesh plans have no exchanges inside them; only
        # the cross-host slice executor (which overrides this) feeds
        # fragments containing RemoteSource nodes
        raise ExecutionError(
            "mesh executor cannot read remote sources"
        )

    def _merge_split_dicts(self, per_dev, per_dev_dicts, dicts):
        """Unify per-device varchar dictionaries across the mesh: build one
        union dictionary per symbol and remap each device's codes into it
        (the cross-task DictionaryBlock unification that
        exec/local.py merge_pages_to_arrays performs within one task —
        real hive tables carry per-file dictionaries, so devices holding
        different files legitimately diverge)."""
        all_syms = set()
        for dd in per_dev_dicts:
            all_syms.update(dd)
        for sym in all_syms:
            present = [dd.get(sym) for dd in per_dev_dicts]
            base = next((d for d in present if d is not None), None)
            if all(
                d is None or same_dictionary(d, base)
                for d in present
            ):
                dicts[sym] = base
                continue
            index: Dict[str, int] = {}
            entries: List[str] = []
            for dev, d in enumerate(present):
                if d is None:
                    continue
                remap = np.empty(len(d), dtype=np.int32)
                for i, s in enumerate(d):
                    s = str(s)
                    if s not in index:
                        index[s] = len(entries)
                        entries.append(s)
                    remap[i] = index[s]
                codes, ok = per_dev[dev][sym]
                safe = np.clip(codes, 0, max(len(d) - 1, 0))
                per_dev[dev][sym] = (
                    np.where(codes >= 0, remap[safe], -1).astype(codes.dtype),
                    ok,
                )
            dicts[sym] = np.array(entries, dtype=object)


class _MeshTraceCtx(_TraceCtx):
    """Trace context inside shard_map: exchange points become collectives."""

    # compaction capacities are GLOBAL row estimates; a mesh shard holds
    # 1/ndev of the rows (and skew could overflow a shard-scaled guess)
    allow_compaction = False
    # the executor makes node stats and per-device task rollups after
    # the program settles (_mesh_node_stats)
    node_probes = False

    def __init__(self, ex: MeshExecutor, scans, counts):
        super().__init__(ex, scans, counts)
        self.capacity_limits: List[int] = []
        self.ordered_out = False

    def _note_capacity(self, ngroups, cap, kind="group"):
        # replicate the check value so it can cross the out_specs=P() boundary
        self.capacity_checks.append(_pmax(ngroups))
        self.capacity_limits.append((cap, kind))

    def _note_collision(self, coll):
        self.collision_checks.append(_pmax(coll))

    def _merge_fused_sums(self, sums):
        """Megakernel shard bodies: merge the per-shard fused
        (term, group) int64 partials across the mesh before the shared
        finalize tail.  all_gather + local reduce rather than psum keeps
        the exchange in the canonical all-gather/dynamic-slice HLO form.
        Exact because what is merged is each TERM's sum, before any
        recombination: the megakernel's plan-time proofs bound a term's
        sum over the TABLE's row count (TERM_MAX x rows for a wide
        accumulator, the 2^62 gate for a narrow one) inside int64, so
        the cross-shard sum of per-shard partials cannot wrap."""
        return jax.tree_util.tree_map(
            lambda s: jnp.sum(jax.lax.all_gather(s, AXIS), axis=0), sums
        )

    # -- leaves ---------------------------------------------------------
    def _visit_tablescan(self, node: P.TableScan) -> Batch:
        # this device's row block of every [ndev, cap] lane, and its own
        # entry of the live-row vector
        lanes = dict(self.scans[id(node)])
        count = lanes.pop("__count__")[0]
        lanes = {sym: (v[0], ok[0]) for sym, (v, ok) in lanes.items()}
        sel = jnp.arange(self.ex._scan_caps[id(node)]) < count
        return Batch(lanes, sel, replicated=False)

    def _visit_values(self, node: P.Values) -> Batch:
        b = super()._visit_values(node)
        # identical values exist on every device; select only on device 0
        myidx = jax.lax.axis_index(AXIS)
        return Batch(b.lanes, b.sel & (myidx == 0), b.ordered, False)

    # -- aggregation -----------------------------------------------------
    def _visit_aggregate(self, node: P.Aggregate) -> Batch:
        if node.step in ("single", "partial"):
            from ..ops import megakernel

            fused = megakernel.try_fused(self, node)
            if fused is not None:
                # each shard ran the fused kernel over its own split; the
                # _merge_fused_sums collective already made the finished
                # accumulators identical on every device
                return Batch(
                    fused.lanes, fused.sel, fused.ordered, replicated=True
                )
        b = self.visit(node.source)
        all_specs = [a.to_spec() for a in node.aggs]
        collective_able = all(
            s.psum_kind(n) is not None or _is_hll_lane(s, n)
            for s in all_specs
            for n in s.accumulator_names
        )
        hll = any(
            _is_hll_lane(s, n)
            for s in all_specs
            for n in s.accumulator_names
        )
        # strictly psum-able: the global fast path (1-row accumulators)
        psum_able = collective_able and not hll
        raw_needed = any(
            a.distinct or not a.partializable for a in node.aggs
        )
        if not b.replicated and raw_needed and node.keys:
            # grouped DISTINCT / non-decomposable aggregates: FIXED_HASH
            # exchange on the GROUP BY keys co-locates each group's raw
            # rows, then every device aggregates its own hash range
            # exactly — the count(DISTINCT)-beyond-memory path.  The old
            # gathering exchange replicated the ENTIRE input into every
            # device; here no device ever holds more than its hash range
            # (plus skew slack, backstopped by the capacity ladder).
            b = self._hash_repartition(b, tuple(node.keys))
            out = _TraceCtx._visit_aggregate(self, node, b)
            return Batch(out.lanes, out.sel, out.ordered, replicated=False)
        if not b.replicated and (
            raw_needed or (not psum_able and not node.keys)
        ):
            # global DISTINCT / non-decomposable aggregates need the raw
            # rows in one place (a gathered approx_distinct even stays
            # EXACT: the single-step path counts, it never sketches) —
            # and global aggregates whose accumulators no collective can
            # merge (min_by/bitwise/arbitrary) gather instead of psum.
            b = _gather_batch(b)
        if b.replicated:
            out = _TraceCtx._visit_aggregate(self, node, b)
            return Batch(out.lanes, out.sel, out.ordered, replicated=True)
        types = node.source.output_types()
        b, aggs = self._agg_dict_setup(node, b)
        specs = [a.to_spec() for a in aggs]

        if not node.keys:
            gid = jnp.zeros(b.sel.shape[0], dtype=jnp.int64)
            accs = agg_ops.accumulate(
                specs, b.lanes, gid, b.sel, 1,
                overflow_flags=self.sum_overflow,
                wide_flags=self.lowering.overflow_flags,
                force_wide=self.lowering.force_wide_mul,
            )
            accs = self._psum_accs(specs, accs)
            out = agg_ops.finalize(specs, accs)
            from ..ops.wide_decimal import pad_rows

            lanes = {
                k: (pad_rows(v, 127), jnp.pad(ok, (0, 127)))
                for k, (v, ok) in out.items()
            }
            sel = jnp.pad(jnp.ones(1, bool), (0, 127))
            return Batch(lanes, sel, replicated=True)

        key_lanes = [b.lanes[k] for k in node.keys]
        domains = self._direct_domains(node.keys, types)
        if domains is not None and collective_able:
            gid, cap = agg_ops.direct_group_ids(key_lanes, domains)
            accs = agg_ops.accumulate(
                specs, b.lanes, gid, b.sel, cap,
                # sketched approx_distinct must emit its mergeable HLL
                # register lanes here (the single-step shortcut is an
                # exact per-shard count, which cannot merge across
                # shards); plain accumulators are step-invariant
                step="partial" if hll else "single",
                overflow_flags=self.sum_overflow,
                wide_flags=self.lowering.overflow_flags,
                force_wide=self.lowering.force_wide_mul,
            )
            present_local = agg_ops._seg_count(b.sel, gid, cap) > 0
            # exchange: dense accumulators are psum-able (partial->final)
            accs = self._psum_accs(specs, accs)
            present = jax.lax.psum(present_local.astype(jnp.int32), AXIS) > 0
            out = agg_ops.finalize(specs, accs)
            keys_out = _decode_direct_keys(domains, cap)
        else:
            # partial aggregate locally; gathering exchange of partial
            # group state; re-merge (PARTIAL -> exchange -> FINAL)
            cap = min(self.ex.group_capacity, b.sel.shape[0])
            self._count_sort_group(b.sel.shape[0], cap)
            sorted_lanes, sel_sorted, gid, ngroups = self._group_sort(
                b.lanes, node.keys, b.sel, cap
            )
            self._note_capacity(ngroups, cap)
            ss = agg_ops.SortedSegments(gid, cap)
            accs = agg_ops.accumulate(
                specs, sorted_lanes, gid, sel_sorted, cap, step="partial",
                overflow_flags=self.sum_overflow,
                wide_flags=self.lowering.overflow_flags,
                force_wide=self.lowering.force_wide_mul,
                seg=ss,
            )
            present_local = jnp.arange(cap) < ngroups
            keys_local = agg_ops.group_keys_output(
                [sorted_lanes[k] for k in node.keys], gid, sel_sorted, cap,
                starts=ss.starts,
            )
            with jax.named_scope("gather_group_state"):
                acc_lanes = {
                    name: (
                        _agather(arr),
                        jnp.ones(arr.shape[0] * self._ndev(), bool),
                    )
                    for name, arr in accs.items()
                }
                for k, (v, ok) in zip(node.keys, keys_local):
                    acc_lanes[k] = (_agather(v), _agather(ok))
                present_g = _agather(present_local)
            self._count("groupStateExchangeSlots", present_g.shape[0])
            with jax.named_scope("final_step"):
                fcap = min(self.ex.group_capacity, present_g.shape[0])
                self._count_sort_group(present_g.shape[0], fcap)
                acc_sorted, sel2, gid2, ngroups2 = self._group_sort(
                    acc_lanes, node.keys, present_g, fcap
                )
                self._note_capacity(ngroups2, fcap)
                # gid2 is sorted too: `arbitrary` and the final keys read
                # their rows off its runs (sums still merge by scatter)
                ss2 = agg_ops.SortedSegments(gid2, fcap)
                merged = agg_ops.merge_accumulators(
                    specs, acc_sorted, gid2, sel2, fcap,
                    overflow_flags=self.sum_overflow,
                    seg=ss2,
                )
                out = agg_ops.finalize(specs, merged)
                keys_out = agg_ops.group_keys_output(
                    [acc_sorted[k] for k in node.keys],
                    gid2,
                    sel2,
                    fcap,
                    starts=ss2.starts,
                )
            present = jnp.arange(fcap) < ngroups2
            cap = fcap

        lanes = {}
        for k, kl in zip(node.keys, keys_out):
            lanes[k] = kl
        for s in out:
            lanes[s] = out[s]
        pad_cap = self.ex.ladder.quantize(cap)
        if pad_cap != cap:
            from ..ops.wide_decimal import pad_rows

            lanes = {
                s: (
                    pad_rows(v, pad_cap - cap),
                    jnp.pad(ok, (0, pad_cap - cap)),
                )
                for s, (v, ok) in lanes.items()
            }
            present = jnp.pad(present, (0, pad_cap - cap))
        return Batch(lanes, present, replicated=True)

    def _ndev(self) -> int:
        return self.ex.mesh.devices.size

    # -- exchanges (counted at trace time: exec/local.OP_COUNTERS) ---------
    @jax.named_scope("_broadcast")
    def _broadcast(self, build: Batch) -> Batch:
        """Broadcast exchange of a join's or semi join's build side: every
        device receives every shard's slots, live or not."""
        self._count("broadcastExchanges")
        self._count("broadcastExchangeSlots",
                    self._ndev() * build.sel.shape[0])
        return _gather_batch(build)

    @jax.named_scope("_repartition")
    def _repartition(self, lanes, sel, bucket, keep, chunk):
        """Partitioned exchange (`shuffle.repartition`'s all-to-all): every
        device receives one `chunk` of slots from each device."""
        ndev = self._ndev()
        self._count("partitionedExchanges")
        self._count("partitionedExchangeSlots", ndev * chunk)
        return shuffle.repartition(lanes, sel, bucket, keep, ndev, chunk, AXIS)

    def _psum_accs(self, specs, accs):
        """Cross-device accumulator merge by collective; callers must have
        checked psum_kind != None (or the HLL-lane exception) for every
        accumulator first.  int64 sum accumulators get an f64 shadow psum
        so a cross-device wrap (each shard under the threshold, total
        beyond int64) fails loudly."""
        out = {}
        ops = {
            "sum": lambda x: jax.lax.psum(x, AXIS),
            "min": lambda x: _pextreme(x, "min"),
            "max": _pmax,
        }
        for s in specs:
            hll_names = [
                n for n in s.accumulator_names if _is_hll_lane(s, n)
            ]
            if hll_names:
                # HLL sketches union by ELEMENTWISE register max — a max
                # of the packed int64 words would compare the 8-register
                # concatenation lexicographically, which is wrong
                cap = accs[hll_names[0]].shape[0]
                lanes = {i: accs[n] for i, n in enumerate(hll_names)}
                merged = sketches.hll_pmax_merge(lanes, cap, AXIS)
                for i, n in enumerate(hll_names):
                    out[n] = merged[i]
            for name in s.accumulator_names:
                if _is_hll_lane(s, name):
                    continue
                kind = s.psum_kind(name)
                out[name] = ops[kind](accs[name])
                if (
                    kind == "sum"
                    and s.kind in ("sum", "avg")
                    and accs[name].dtype == jnp.int64
                    and (name.endswith("$val") or name.endswith("$sum"))
                ):
                    shadow = jax.lax.psum(
                        accs[name].astype(jnp.float64), AXIS
                    )
                    self.sum_overflow.append(
                        jnp.sum(jnp.abs(shadow) > 9.0e18).astype(jnp.int64)
                    )
        return out

    # -- joins ----------------------------------------------------------
    def _visit_join(self, node: P.Join) -> Batch:
        left = self.visit(node.left)
        right = self.visit(node.right)
        if self._use_partitioned(node, left, right):
            out = self._partitioned_join(node, left, right)
        else:
            if not right.replicated:
                # broadcast exchange: replicate build side to all workers
                right = self._broadcast(right)
            out = self._join_batches(node, left, right)
            out.replicated = left.replicated
        return out

    def _hinted_chunk(self, node, side, cap, ndev, factor):
        """Shuffle-chunk capacity: the host-measured skew hint when one
        exists (grown by the ladder factor as the backstop), else the
        2x-slack default."""
        h = getattr(self.ex, "shuffle_hints", {}).get((id(node), side))
        q = self.ex.ladder.quantize
        if h is not None:
            return min(q(h * factor), q(max(128, cap)))
        return _shuffle_chunk(cap, ndev, factor, quantize=q)

    def _use_partitioned(self, node: P.Join, left: Batch, right: Batch):
        """The DetermineJoinDistributionType decision at execution time:
        honor the optimizer's choice when present, else fall back to a
        capacity heuristic (broadcasting a build side bigger than the
        threshold would replicate it into every device's HBM)."""
        if (
            node.kind not in ("inner", "left")
            or not node.criteria
            or left.replicated
            or right.replicated
        ):
            return False
        if node.distribution == "partitioned":
            return True
        if node.distribution == "broadcast":
            return False
        return self._exceeds_broadcast_threshold(right)

    def _partitioned_join(self, node: P.Join, left: Batch, right: Batch):
        """HASH-HASH distribution: all-to-all both sides on the join keys,
        then join locally — each device owns one hash range of the key
        space (PartitionedLookupSourceFactory / FIXED_HASH exchange pair).
        NULL-key probe rows of an outer join are retained (routed by the
        garbage hash, they match nothing but must still emit)."""
        ndev = self._ndev()
        factor = getattr(self.ex, "join_factor", 1)
        lkeys = [left.lanes[l] for l, _ in node.criteria]
        rkeys = [right.lanes[r] for _, r in node.criteria]
        joint = join_ops.needs_verification(
            lkeys
        ) or join_ops.needs_verification(rkeys)
        lbuck, lok = shuffle.bucket_of(lkeys, left.sel, ndev, joint)
        rbuck, rok = shuffle.bucket_of(rkeys, right.sel, ndev, joint)
        lkeep = left.sel & (lok | (node.kind == "left"))
        rkeep = right.sel & rok
        lchunk = self._hinted_chunk(node, "l", left.sel.shape[0], ndev,
                                    factor)
        rchunk = self._hinted_chunk(node, "r", right.sel.shape[0], ndev,
                                    factor)
        llanes, lsel, lmax = self._repartition(
            left.lanes, left.sel, lbuck, lkeep, lchunk
        )
        rlanes, rsel, rmax = self._repartition(
            right.lanes, right.sel, rbuck, rkeep, rchunk
        )
        self._note_capacity(lmax, lchunk, "join")
        self._note_capacity(rmax, rchunk, "join")
        out = self._join_batches(
            node,
            Batch(llanes, lsel, replicated=False),
            Batch(rlanes, rsel, replicated=False),
        )
        out.replicated = False
        return out

    def _visit_semijoin(self, node: P.SemiJoin) -> Batch:
        src = self.visit(node.source)
        filt = self.visit(node.filtering)
        if (
            not src.replicated
            and not filt.replicated
            and node.filter is None
            and self._semi_use_partitioned(filt)
        ):
            return self._partitioned_semijoin(node, src, filt)
        if not filt.replicated:
            # broadcast the filtering side (dynamic-filter style exchange)
            filt = self._broadcast(filt)
        hit = self._semi_mark(node, src, filt)
        lanes = dict(src.lanes)
        lanes[node.output] = (hit, jnp.ones(hit.shape, bool))
        return Batch(lanes, src.sel, src.ordered, src.replicated)

    def _semi_use_partitioned(self, filt: Batch) -> bool:
        return self._exceeds_broadcast_threshold(filt)

    def _exceeds_broadcast_threshold(self, build: Batch) -> bool:
        from ..config import BROADCAST_JOIN_THRESHOLD_ROWS

        threshold = int(
            self.ex.config.get(
                "broadcast_join_threshold_rows",
                BROADCAST_JOIN_THRESHOLD_ROWS,
            )
        )
        # shape[0] is the per-device shard capacity; the threshold is
        # total build rows, so broadcasting replicates ndev * shape[0]
        return build.sel.shape[0] * self._ndev() >= threshold

    def _partitioned_semijoin(
        self, node: P.SemiJoin, src: Batch, filt: Batch
    ) -> Batch:
        """HASH-HASH semi join: repartition BOTH sides on the semi keys
        and mark locally per hash range (the reference's partitioned
        SemiJoinNode distribution).  NULL-key source rows route to a
        stable device (they match nothing but must still emit their
        mark=false row); the output stays distributed."""
        ndev = self._ndev()
        skeys = [src.lanes[k] for k in node.source_keys]
        fkeys = [filt.lanes[k] for k in node.filtering_keys]
        joint = join_ops.needs_verification(
            skeys
        ) or join_ops.needs_verification(fkeys)
        sbuck, sok = shuffle.bucket_of(skeys, src.sel, ndev, joint)
        fbuck, fok = shuffle.bucket_of(fkeys, filt.sel, ndev, joint)
        sbuck = jnp.where(sok, sbuck, 0)
        factor = getattr(self.ex, "join_factor", 1)
        schunk = self._hinted_chunk(node, "l", src.sel.shape[0], ndev,
                                    factor)
        fchunk = self._hinted_chunk(node, "r", filt.sel.shape[0], ndev,
                                    factor)
        slanes, ssel, smax = self._repartition(
            src.lanes, src.sel, sbuck, src.sel, schunk
        )
        flanes, fsel, fmax = self._repartition(
            filt.lanes, filt.sel, fbuck, filt.sel & fok, fchunk
        )
        self._note_capacity(smax, schunk, "join")
        self._note_capacity(fmax, fchunk, "join")
        src2 = Batch(slanes, ssel, replicated=False)
        filt2 = Batch(flanes, fsel, replicated=False)
        hit = self._semi_mark(node, src2, filt2)
        lanes = dict(src2.lanes)
        lanes[node.output] = (hit, jnp.ones(hit.shape, bool))
        return Batch(lanes, src2.sel, replicated=False)

    def _visit_scalarjoin(self, node: P.ScalarJoin) -> Batch:
        src = self.visit(node.source)
        sub = self.visit(node.subquery)
        if not sub.replicated:
            sub = _gather_batch(sub)
        first = jnp.argmax(sub.sel)
        n = src.sel.shape[0]
        lanes = dict(src.lanes)
        for s, (v, ok) in sub.lanes.items():
            val = v[first]
            okv = ok[first] & (sub.sel.sum() > 0)
            lanes[s] = (
                jnp.broadcast_to(val, (n,)),
                jnp.broadcast_to(okv, (n,)),
            )
        return Batch(lanes, src.sel, src.ordered, src.replicated)

    def _hash_repartition(self, b: Batch, key_syms) -> Batch:
        """FIXED_HASH exchange of a distributed batch by key columns —
        rows with equal keys co-locate (AddExchanges partitioned
        distribution for window/distinct/set ops)."""
        ndev = self._ndev()
        key_lanes = [b.lanes[s] for s in key_syms]
        bucket, kok = shuffle.bucket_of(key_lanes, b.sel, ndev)
        # NULL keys form their own group: bucket_of hashes value lanes
        # only, so route invalid-key rows to a stable device (0)
        bucket = jnp.where(kok, bucket, 0)
        chunk = _shuffle_chunk(
            b.sel.shape[0], ndev, getattr(self.ex, "join_factor", 1),
            quantize=self.ex.ladder.quantize,
        )
        lanes, sel, mx = self._repartition(
            b.lanes, b.sel, bucket, b.sel, chunk
        )
        self._note_capacity(mx, chunk, "join")
        return Batch(lanes, sel, replicated=False)

    # -- window ----------------------------------------------------------
    def _visit_window(self, node: P.Window) -> Batch:
        """Partitioned windows hash-repartition by the PARTITION BY keys
        (AddExchanges.java:138 window partitioning) and window locally;
        only partition-less windows need the gathering exchange."""
        b = self.visit(node.source)
        part_keys = tuple(node.partition_by)
        if not b.replicated and part_keys:
            b = self._hash_repartition(b, part_keys)
            replicated_out = False
        elif not b.replicated:
            b = _gather_batch(b)
            replicated_out = True
        else:
            replicated_out = True
        saved_visit = self.visit

        def patched_visit(n):
            return b if n is node.source else saved_visit(n)

        self.visit = patched_visit
        try:
            out = _TraceCtx._visit_window(self, node)
        finally:
            self.visit = saved_visit
        out.replicated = replicated_out
        return out

    # -- ordering --------------------------------------------------------
    def _visit_sort(self, node: P.Sort) -> Batch:
        """Distributed sort = RANGE exchange on the leading key + local
        sort per device: device order concatenates into the total order,
        so no global gather-then-sort (MergeOperator by placement).
        Replicated inputs keep the plain local sort."""
        b = self.visit(node.source)
        if b.replicated:
            keys = self._rank_sort_keys(node.keys, b)
            perm = sort_ops.sort_perm(keys, b.lanes, b.sel)
            lanes, sel = sort_ops.apply_perm(b.lanes, perm, b.sel)
            self.ordered_out = True
            return Batch(lanes, sel, ordered=True, replicated=True)
        ndev = self._ndev()
        keys = self._rank_sort_keys(node.keys, b)
        lead = keys[0]
        # _rank_sort_keys always registers its (possibly hidden $rank)
        # lane in b.lanes, so the lead column is present by construction
        bucket = shuffle.range_buckets(
            b.lanes[lead.column], lead, b.sel, ndev, AXIS
        )
        chunk = _shuffle_chunk(
            b.sel.shape[0], ndev, getattr(self.ex, "join_factor", 1),
            quantize=self.ex.ladder.quantize,
        )
        lanes, sel, mx = self._repartition(
            b.lanes, b.sel, bucket, b.sel, chunk
        )
        self._note_capacity(mx, chunk, "join")
        b2 = Batch(lanes, sel, replicated=False)
        keys2 = self._rank_sort_keys(node.keys, b2)
        perm = sort_ops.sort_perm(keys2, b2.lanes, b2.sel)
        lanes2, sel2 = sort_ops.apply_perm(b2.lanes, perm, b2.sel)
        self.ordered_out = True
        # device-ordered: the final all_gather (device order preserved)
        # materializes the total order without any further sort
        return Batch(lanes2, sel2, ordered=True, replicated=False)

    def _visit_topn(self, node: P.TopN) -> Batch:
        b = self.visit(node.source)
        keys = self._rank_sort_keys(node.keys, b)
        lanes, sel, check = sort_ops.topn(
            keys, b.lanes, b.sel, node.count,
            getattr(self.ex, 'topn_factor', 1),
        )
        if check is not None:
            self._note_capacity(check[0], check[1], "topn")
        if not b.replicated:
            # local top-n -> gather candidates -> global top-n (MergeOperator)
            b2 = Batch(
                {s: (_agather(v), _agather(ok)) for s, (v, ok) in lanes.items()},
                _agather(sel),
            )
            keys2 = self._rank_sort_keys(node.keys, b2)
            lanes, sel, check2 = sort_ops.topn(
                keys2, b2.lanes, b2.sel, node.count,
                getattr(self.ex, 'topn_factor', 1),
            )
            if check2 is not None:
                self._note_capacity(check2[0], check2[1], "topn")
        self.ordered_out = True
        return Batch(lanes, sel, ordered=True, replicated=True)

    def _visit_limit(self, node: P.Limit) -> Batch:
        b = self.visit(node.source)
        # per-device partial keeps count+offset; the post-gather limit
        # applies the offset skip
        lanes, sel = sort_ops.limit(
            b.lanes, b.sel, node.count + node.offset
        )
        if not b.replicated:
            b2 = Batch(
                {s: (_agather(v), _agather(ok)) for s, (v, ok) in lanes.items()},
                _agather(sel),
            )
            lanes, sel = sort_ops.limit(
                b2.lanes, b2.sel, node.count, node.offset
            )
            return Batch(lanes, sel, replicated=True)
        lanes, sel = sort_ops.limit(lanes, sel, node.count, node.offset)
        return Batch(lanes, sel, b.ordered, b.replicated)

    def _visit_distinct(self, node: P.Distinct) -> Batch:
        b = super()._visit_distinct(node)
        if not b.replicated:
            # FIXED_HASH exchange on the distinct keys: equal rows
            # co-locate, each device dedupes its hash range, and the
            # output STAYS distributed (MarkDistinct partitioned plan)
            syms = node.output_symbols()
            b = self._hash_repartition(b, tuple(syms))
            b = Batch(*self._distinct_rows(b.lanes, syms, b.sel))
        return b

    def _partitioned_setop(self, node: P.SetOperation) -> Batch:
        """INTERSECT/EXCEPT on the mesh: union the inputs positionally
        (dictionaries merged — so codes are comparable mesh-wide), then
        FIXED_HASH-repartition the tagged rows by the full row value and
        run the tag-mark dedup per device hash range.  Rows from
        replicated inputs are sent by device 0 only (one copy)."""
        if node.all:
            raise ExecutionError(
                f"{node.kind.upper()} ALL not supported (DISTINCT only)"
            )
        assert len(node.inputs) == 2
        batches = [self.visit(i) for i in node.inputs]
        if all(b.replicated for b in batches):
            saved_visit = self.visit
            by_id = {id(i): b for i, b in zip(node.inputs, batches)}
            self.visit = lambda n: by_id.get(id(n)) or saved_visit(n)
            try:
                out = _TraceCtx._visit_setoperation(self, node)
            finally:
                self.visit = saved_visit
            out.replicated = True
            return out
        saved_visit = self.visit
        by_id = {id(i): b for i, b in zip(node.inputs, batches)}
        self.visit = lambda n: by_id.get(id(n)) or saved_visit(n)
        try:
            lanes0, sel, caps = self._union_lanes(node)
        finally:
            self.visit = saved_visit
        tag = jnp.concatenate([
            jnp.full(c, i, dtype=jnp.int32) for i, c in enumerate(caps)
        ])
        # one copy of replicated inputs' rows: only device 0 transmits
        my_dev = jax.lax.axis_index(AXIS)
        rep_row = jnp.concatenate([
            jnp.full(c, b.replicated, dtype=bool)
            for b, c in zip(batches, caps)
        ])
        keep = sel & (~rep_row | (my_dev == 0))
        ndev = self._ndev()
        key_lanes = [lanes0[s] for s in node.symbols]
        bucket, kok = shuffle.bucket_of(key_lanes, sel, ndev)
        bucket = jnp.where(kok, bucket, 0)
        all_lanes = dict(lanes0)
        all_lanes["__tag__"] = (tag, jnp.ones(tag.shape[0], bool))
        chunk = _shuffle_chunk(
            sel.shape[0], ndev, getattr(self.ex, "join_factor", 1),
            quantize=self.ex.ladder.quantize,
        )
        lanes2, sel2, mx = self._repartition(
            all_lanes, sel, bucket, keep, chunk
        )
        self._note_capacity(mx, chunk, "join")
        tag2, _ = lanes2.pop("__tag__")
        out = self._setop_tag_reduce(
            node, lanes2, sel2, tag2, sel2.shape[0]
        )
        out.replicated = False
        return out

    def _visit_setoperation(self, node: P.SetOperation) -> Batch:
        if node.kind in ("intersect", "except"):
            return self._partitioned_setop(node)
        # UNION: gather every non-replicated input, then reuse the local
        # union (ALL keeps the ARBITRARY-exchange path upstream)
        originals = {}
        for inp in node.inputs:
            batch = self.visit(inp)
            if not batch.replicated:
                batch = _gather_batch(batch)
            originals[id(inp)] = batch

        saved_visit = self.visit

        def patched_visit(n):
            if id(n) in originals:
                return originals[id(n)]
            return saved_visit(n)

        self.visit = patched_visit
        try:
            out = _TraceCtx._visit_setoperation(self, node)
        finally:
            self.visit = saved_visit
        out.replicated = True
        return out


class _SliceTraceCtx(_MeshTraceCtx):
    """Trace context for ONE HOST'S slice of a multi-host cluster.

    The mesh here spans only this process's local devices; the global
    exchange between hosts is the server exchange layer (HTTP pages +
    spool), not an XLA collective.  Two consequences:

      - a RemoteSource is a network input this host already fetched: its
        pages were merged once and tiled identically onto every local
        device, so the batch is replicated (the broadcast build side of
        FIXED_BROADCAST_DISTRIBUTION joins)
      - a PARTIAL aggregate must STAY partial: each device emits its
        accumulator rows and the Output gather ships ndev partial rows
        per group through the exchange — the consumer fragment's FINAL
        step merges them exactly as if they came from more tasks.  The
        inherited mesh path would psum/merge to finished values here,
        which double-finalizes once the consumer merges again.
    """

    def _visit_remotesource(self, node: P.RemoteSource) -> Batch:
        b = self._visit_tablescan(node)
        return Batch(b.lanes, b.sel, b.ordered, replicated=True)

    def _visit_aggregate(self, node: P.Aggregate) -> Batch:
        if node.step == "partial":
            # bypass the fused/collective mesh paths (they emit FINALIZED
            # outputs); the plain local partial path emits per-device
            # accumulator lanes, one independent slice per device
            b = self.visit(node.source)
            out = _TraceCtx._visit_aggregate(self, node, b)
            return Batch(
                out.lanes, out.sel, out.ordered, replicated=b.replicated
            )
        return super()._visit_aggregate(node)


# node types a host slice can run SPMD over its local devices.  Sort /
# Window / SetOperation / writers are excluded: they either demand the
# whole input ordered in one place or mutate external state — those
# fragments keep the single-device FragmentExecutor.
_SLICE_NODES = (
    P.Output, P.TableScan, P.RemoteSource, P.Filter, P.Project, P.Values,
    P.Aggregate, P.Join, P.SemiJoin, P.ScalarJoin, P.TopN, P.Limit,
    P.Distinct,
)


def slice_eligible(plan: P.PlanNode) -> bool:
    """True when a fragment can run as a per-host shard_map slice.

    Exactly one TableScan: that makes it a SOURCE fragment whose splits
    the coordinator already partitioned across hosts, and guarantees any
    RemoteSource inputs are broadcast build sides (plan/fragment.py
    places partitioned exchanges only between fragments).  Aggregates
    must be PARTIAL — a final-step merge belongs to the consumer side of
    the network exchange, where the rows from every host meet.
    """
    nscans = 0
    stack = [plan]
    while stack:
        n = stack.pop()
        if not isinstance(n, _SLICE_NODES):
            return False
        if isinstance(n, P.TableScan):
            nscans += 1
        elif isinstance(n, P.Aggregate) and n.step != "partial":
            return False
        stack.extend(n.sources)
    return nscans == 1


class CrossHostFragmentExecutor(MeshExecutor):
    """Runs one fragment task as this host's slice of the global mesh.

    Drop-in for exec.fragment_exec.FragmentExecutor on slice-eligible
    fragments: same constructor shape, same stats surface.  The worker
    hands it the splits the coordinator assigned to THIS task and the
    remote pages it already pulled through the exchange client; the
    executor shards the assigned splits over the local devices and runs
    the fragment SPMD.  Cross-host repartition and partial->final merges
    happen where they always did — in the consumer fragment, fed through
    the HTTP/spool exchange — so one kill -9'd host loses only its slice
    and FTE replays its tasks from committed spools.
    """

    mesh_trace_ctx_cls = _SliceTraceCtx

    def __init__(self, catalogs: CatalogManager, config: Optional[dict],
                 splits_by_scan, remote_pages, dynamic_filters=None):
        super().__init__(catalogs, mesh=None, config=config)
        self.splits_by_scan = splits_by_scan or {}
        self.remote_pages = remote_pages or {}
        # dynamic filters are a scan-pruning optimization; the slice path
        # skips them (semantically a no-op — the probe-side filter still
        # applies) rather than threading them through the stacked loader
        self.dynamic_filters = dynamic_filters or {}
        self.df_rows_pruned = 0
        # same exchange accounting as FragmentExecutor: bytes this task
        # pulled across the network before any operator ran
        self.exchange_bytes = sum(
            int(getattr(c.values, "nbytes", 0))
            + int(getattr(c.validity, "nbytes", 0) or 0)
            for pages in (remote_pages or {}).values()
            for p in pages
            for c in p.columns
        )

    def _scan_splits(self, node: P.TableScan, idx: int, ndev: int):
        # ONLY the splits the coordinator assigned to this task, keyed by
        # the same preorder scan ordinal FragmentExecutor._load_walk uses
        return self.splits_by_scan.get(idx, [])

    def _load_remote_source(self, node, ndev, scans, counts, dicts):
        """Merge the fetched exchange pages once, then tile the rows
        identically onto every local device ([ndev, cap] stacks) — the
        slice ctx marks the batch replicated, so joins treat it as the
        broadcast build side without any per-device repartition."""
        pages = self.remote_pages.get(node.fragment_id, [])
        local_dicts: Dict[str, np.ndarray] = {}
        merged, total = merge_pages_to_arrays(
            pages, list(node.symbols), list(node.types_), local_dicts
        )
        for s, t in node.types_:
            if t.is_dictionary and s not in local_dicts:
                local_dicts[s] = np.array([], dtype=object)
        dicts.update(local_dicts)
        shard = {
            sym: (v[:total], None if ok is None else ok[:total])
            for sym, (v, ok) in merged.items() if sym in node.symbols
        }
        scans[id(node)] = _stack_shards(
            [shard] * ndev, self.ladder.quantize(max(total, 1))
        )
        counts[id(node)] = np.full(ndev, total, dtype=np.int64)
        self._scan_dictfp[id(node)] = dict_fingerprint(
            local_dicts, list(node.symbols)
        )


# class-attribute hook resolution: _MeshTraceCtx is defined below
# MeshExecutor, so the default binding lives here at module bottom
MeshExecutor.mesh_trace_ctx_cls = _MeshTraceCtx
