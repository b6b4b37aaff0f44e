"""Streaming (bounded-working-set) local execution.

Reference parity: the reference's ENTIRE worker runtime streams —
operator/Driver.java:372 moves bounded Pages through the operator chain,
ScanFilterAndProjectOperator.java:190 pulls split by split, and
project/PageProcessor.java:53 caps batches at 8192 rows, so one node can
scan a table far bigger than memory.

TPU-first redesign: XLA wants large static-shape programs, not 8k-row
batches — so the streaming unit here is an HBM-sized TILE of splits, and
the carried state is the same PARTIAL page state the distributed path
ships between workers.  The optimized plan is cut by the regular
Fragmenter (plan/fragment.py); each SOURCE fragment's splits are then
executed tile-by-tile through a FragmentExecutor (one compiled XLA
program, reused across tiles because every tile has the same padded
shape), and its partial output pages accumulate host-side.  Downstream
fragments consume the gathered partials exactly as a remote worker
would.  In effect: local streaming IS distributed execution with one
worker and host RAM as the exchange buffer — one mechanism, both
scales (and any plan the cluster can run, one chip can now run).

Build-side/remote input pages are uploaded to the device once per
streaming run (a shared DeviceScanCache entry keyed by fragment id), so
tiles re-dispatch against resident build tables instead of re-uploading
them (the LazyBlock-stays-resident analog: HBM residency saves the
host->device copy per tile).

A tiled scan's own lanes normally die with their tile.  When the
connector generates the scan on the device and ALL its tiles fit the
session's scan cache beside what is there (`_resident_tile_cache`), the
tiles are kept in it, one entry a tile: the first query generates them,
later ones find them and run the tile programs and nothing else.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

from ..page import Page
from ..plan import nodes as P
from ..plan.fragment import fragment_plan
from ..utils.tracing import TRACER

# a tile's scan working set is bounded to limit/SAFETY so scan arrays +
# kernel temporaries + partial state fit together (same factor the spill
# framework uses)
SAFETY_FACTOR = 3


def _scan_row_bytes(node: P.TableScan) -> int:
    total = 0
    for _sym, _col in node.assignments:
        t = dict(node.types)[_sym]
        width = 8
        try:
            width = t.np_dtype.itemsize
        except NotImplementedError:
            pass
        if getattr(t, "wide", False):
            width = 16
        total += width + 1  # validity byte
    return max(total, 1)


def _table_rows(executor, catalog: str, table: str) -> Optional[int]:
    """The connector's row count of a table, None where it has none."""
    conn = executor.catalogs.get(catalog)
    try:
        stats = conn.metadata().get_table_statistics(table)
    except Exception:  # noqa: BLE001 — unknown stats: assume small
        return None
    return int(stats.row_count)


def _est_scan_bytes(executor, catalog: str, table: str, node) -> float:
    rows = _table_rows(executor, catalog, table)
    return 0.0 if rows is None else float(rows) * _scan_row_bytes(node)


def _find_scan_nodes(root: P.PlanNode) -> List[P.TableScan]:
    out: List[P.TableScan] = []

    def walk(n: P.PlanNode):
        if isinstance(n, P.TableScan):
            out.append(n)
        for s in n.sources:
            walk(s)

    walk(root)
    return out


def estimate_plan_scan_bytes(executor, plan: P.PlanNode) -> float:
    return sum(
        _est_scan_bytes(executor, sc.catalog, sc.table, sc)
        for sc in _find_scan_nodes(plan)
    )


def _aggregates(plan: P.PlanNode) -> List[P.Aggregate]:
    out: List[P.Aggregate] = []

    def walk(node: P.PlanNode):
        if isinstance(node, P.Aggregate):
            out.append(node)
        for s in node.sources:
            walk(s)

    walk(plan)
    return out


def _wide_sums(node: P.Aggregate) -> int:
    """Accumulators of `node` that run 128-bit chunked math (decimal
    sums/avgs)."""
    n = 0
    for a in node.aggs:
        try:
            n += bool(a.to_spec()._wide_sum)
        except Exception:  # noqa: BLE001
            pass
    return n


# HBM a wide-decimal aggregate that does NOT fuse adds to the compiled
# program, as a share of its scan lanes: its 128-bit chunk lanes run at
# input width (estimate_program_bytes' first calibration point)
WIDE_AGG_FACTOR = 0.28

# HBM a fused aggregate's program holds beside the scan's lanes, per
# slot and per 32-bit lane of it (ops/megakernel._run ->
# pallas_kernels.fused_agg_sums, as XLA lays the program out for a v5e):
# an int32 copy of each column the kernel reads whose lane is not int32
# already (an int32 lane is read in place), the `live` mask as int32, and
# one 64-bit lane's low word (`X64SplitLow`) on its way to that copy.
# Its per-term sums are per group, nothing at this scale.  Q1 at SF 10,
# 4 decimal columns of 7: (4 + 1 + 1) x 4 B x 67,108,864 slots =
# 1,610,612,736 B, against the compiled program's `tempBytes`
# 1,614,483,456; Q6, 3 of 4: 1,342,177,280 against 1,342,370,816
# (estimate_program_bytes' second calibration point)
FUSED_LANE_BYTES = 4


def _fused_slot_bytes(scan: P.TableScan, cols: List[str]) -> int:
    types = dict(scan.types)
    copies = sum(types[c].np_dtype != np.int32 for c in cols)
    return FUSED_LANE_BYTES * (copies + 1 + (copies > 0))


def _aggregate_bytes(executor, plan: P.PlanNode, lane_bytes: float,
                     slots: Optional[int] = None):
    """(HBM the plan's aggregates hold beside `lane_bytes` of scan lanes,
    how many of them the fused kernel takes).  One rule for the
    monolithic program and for a tile's: an aggregate that fuses on this
    executor holds `_fused_slot_bytes` a slot of its scan, at `slots` or
    else the ladder's rung for the scan's table; a wide one that does not
    fuse holds WIDE_AGG_FACTOR of the lanes for each wide accumulator."""
    from ..ops import megakernel

    fusing = executor._megakernel_mode() == "on"
    total, fused = 0.0, 0
    for node in _aggregates(plan):
        reads = megakernel.plan_reads(node) if fusing else None
        if reads is None:
            total += lane_bytes * WIDE_AGG_FACTOR * _wide_sums(node)
            continue
        scan, cols = reads
        n = slots
        if n is None:
            rows = _table_rows(executor, scan.catalog, scan.table)
            n = executor.ladder.quantize(max(rows or 0, 1))
        total += _fused_slot_bytes(scan, cols) * n
        fused += 1
    return total, fused


# u64 lanes the generator program keeps live per row on top of its
# output lanes: the row-index lane, the splitmix64 hash state, one value
# lane (reused across columns), and lineitem's cumsum/searchsorted slot
# machinery
DEVGEN_TEMP_LANES = 4


def _devgen_temp_bytes(executor, scans) -> float:
    """HBM temporaries of on-device scan generation.  These were the
    Round-5 bench blind spot: estimate_program_bytes covered scan lanes and
    wide-agg chunk temporaries, but a device-generated scan ALSO runs a
    splitmix64 hash chain over the full padded row range, and its u64
    intermediates sat outside the reserve-before-dispatch accounting —
    so the first q6_sf100 generator compile exceeded the reservation and
    killed the worker process.  Each scan's generator is a program of its
    own, run before the fragment and one at a time: the largest one's
    temporaries are what stands beside the lanes."""
    if not executor.config.get("device_generation", True):
        return 0.0
    most = 0.0
    for sc in scans:
        conn = executor.catalogs.get(sc.catalog)
        if getattr(conn, "device_generation", None) is None:
            continue
        rows = _table_rows(executor, sc.catalog, sc.table)
        if rows is not None:
            most = max(most, float(rows) * 8.0 * DEVGEN_TEMP_LANES)
    return most


def _resident_scans(executor, plan: P.PlanNode) -> List[P.TableScan]:
    """The plan's scans, one for each set of lanes it holds: with the
    scan cache on, a table read twice with the same columns and
    constraint (a self join: TPC-H Q21's `l1` and `l3`) is one entry, so
    one set of lanes."""
    scans = _find_scan_nodes(plan)
    if executor.config.get("scan_cache") is None:
        return scans
    seen = {}
    for sc in scans:
        key = (sc.catalog, sc.table, tuple(c for _, c in sc.assignments),
               sc.constraint)
        seen.setdefault(key, sc)
    return list(seen.values())


def program_estimate(executor, plan: P.PlanNode):
    """(`estimate_program_bytes`, how many of the plan's aggregates the
    fused kernel takes)."""
    scans = _resident_scans(executor, plan)
    scan = sum(_est_scan_bytes(executor, sc.catalog, sc.table, sc)
               for sc in scans)
    aggs, fused = _aggregate_bytes(executor, plan, scan)
    return scan + aggs + _devgen_temp_bytes(executor, scans), fused


def estimate_program_bytes(executor, plan: P.PlanNode) -> float:
    """Estimated HBM peak of the MONOLITHIC compiled program: scan lanes,
    the aggregates' temporaries (`_aggregate_bytes`) and on-device
    generator temporaries, so the gate streams BEFORE submitting a
    compile that XLA would refuse for HBM.  Two calibration points:

    - an aggregate that does not fuse: Q1 at SF 20 (scan est 7.1 GB, 7
      wide aggs) compiled, before the fused kernel took its wide sums,
      to a 20.6 GB buffer assignment (XLA's own hard error) — hence
      WIDE_AGG_FACTOR;
    - a fused one: Q1 at SF 10 as one resident program on one TPU v5e
      (67,108,864 slots, 7 columns read, 4 of them decimal): the fused
      term 1,610,612,736 B against the program's census `tempBytes`
      1,614,483,456 and the run's `memory_peak_bytes` 3,038,161,920 (the
      lanes: temporaries do not show there), under the 6,590,612,736 B
      reckoned — hence FUSED_LANE_BYTES."""
    return program_estimate(executor, plan)[0]


def _resident_tile_cache(executor, frag, node, tile_splits, tile_rows: int):
    """The session's scan cache when every tile of this streamed scan may
    stay in it, else None (the tiles then live and die with their
    dispatch, as a scan that is streamed because it does not fit must).

    A scan is kept when the connector generates it on the device and
    versions it (there are no host arrays to hold, and an entry is
    regenerated from its recipe if dropped), the session caches scans,
    and ALL its tiles at their padded size fit in what the cache has free
    and, with the tile program's temporaries, under the device's limit.
    All or none: the cache evicts in insertion order, so a cycle of tiles
    through a budget one tile short evicts each tile just before it is
    asked for again and never hits, while holding the bytes.  Nothing
    else in the cache is evicted to make room; tiles already there (the
    previous query's) count as held, so a warm query asks nothing more."""
    from ..memory.pools import detect_device_bytes
    from .local import devgen_lane_bytes

    cache = executor.config.get("scan_cache")
    conn = executor.catalogs.get(node.catalog)
    devgen_fn = getattr(conn, "device_generation", None)
    if cache is None or devgen_fn is None or not executor.config.get(
        "device_generation", True
    ):
        return None
    keys = [executor._scan_cache_key(node, sp) for sp in tile_splits]
    if keys[0] is None:
        return None
    missing = sum(cache.get(k, record=False) is None for k in keys)
    if not missing:
        return cache
    cols = [c for _, c in node.assignments]
    try:
        spec = devgen_fn(node.table, cols, tile_splits[0])
    except Exception:  # noqa: BLE001 — the tiles then load on the host
        spec = None
    if spec is not None:
        tile_bytes = devgen_lane_bytes(spec, cols, tile_rows)
        after = cache.bytes + missing * tile_bytes
        # beside the resident tiles: the tile program's aggregate
        # temporaries and, on a miss, the generator's
        room = (
            _aggregate_bytes(executor, frag.root, tile_bytes, tile_rows)[0]
            + 8.0 * DEVGEN_TEMP_LANES * tile_rows
        )
        device = detect_device_bytes()
        if after <= cache.max_bytes and (
            device is None or after + room <= device
        ):
            return cache
    cache.drop(keys)  # a part of the tiles is of no use
    return None


# additive per-dispatch counters a tile executor accumulates that must
# surface in the PARENT executor's kernel profile (the session and bench
# read only the outer profile; tile FragmentExecutors are discarded)
_TILE_COUNTERS = (
    "preuploads", "preupload_bytes", "donated_dispatches",
    "donated_bytes", "fusedAggregates", "fusedTerms", "fusionRejects",
    "fusedSumsPastInt64",
    "devgenWallS", "devgenCompileS", "lineCountOrdersHashed",
    "residentTileHits", "residentTileMisses",
)


def _merge_tile_counters(executor, fe) -> None:
    prof = fe.kernel_profile
    # the tile's program records join the parent's kernel list (same
    # digest = same program, tallies add) and the tile is counted, so the
    # outer profile says which programs ran and that they ran as tiles
    mine = executor.kernel_profile.setdefault("kernels", [])
    by_digest = {k["digest"]: k for k in mine}
    for k in prof.get("kernels") or ():
        have = by_digest.get(k["digest"])
        if have is None:
            have = by_digest[k["digest"]] = dict(k, causes=dict(
                k.get("causes") or {}))
            mine.append(have)
            continue
        for f in ("compiles", "compileWallS", "executions", "cacheHits"):
            have[f] = have.get(f, 0) + k.get(f, 0)
        for c, n in (k.get("causes") or {}).items():
            have.setdefault("causes", {})[c] = (
                have["causes"].get(c, 0) + n
            )
    executor.kernel_profile["streamedFragments"] = (
        executor.kernel_profile.get("streamedFragments", 0) + 1
    )
    from .local import OP_COUNTERS

    for k in _TILE_COUNTERS + OP_COUNTERS:
        v = prof.get(k)
        if v:
            executor.kernel_profile[k] = (
                executor.kernel_profile.get(k, 0) + v
            )
    if prof.get("lastFusionReject"):
        executor.kernel_profile["lastFusionReject"] = (
            prof["lastFusionReject"]
        )
    census = prof.get("programCensus")
    if census is not None:
        from ..obs import program_census

        executor.kernel_profile["programCensus"] = program_census.merge(
            executor.kernel_profile.get("programCensus"), census
        )


def plan_streaming(executor, plan: P.Output, memory_limit: int,
                   force: bool = False):
    """Decide whether to stream: the estimated total scan working set
    exceeds the memory limit and the plan fragments cleanly.  Returns
    the fragment list or None.  `force` skips the scan-bytes gate — the
    compile-OOM fallback path already KNOWS the monolithic program does
    not fit (XLA's buffer assignment said so), whatever the scans sum
    to."""
    # gate on the COMPILED program's peak, not just the scan working set:
    # wide-decimal accumulators inflate XLA's buffer assignment well past
    # the scan bytes (the Q1 SF20 calibration point), and the whole point
    # of the gate is streaming before a compile-OOM can kill the worker
    if not force:
        program, fused = program_estimate(executor, plan)
        estimate = max(estimate_plan_scan_bytes(executor, plan), program)
        # what the gate reckoned, for the `stream_plan` span and the
        # query's kernel profile (`streamed` is the caller's to settle)
        executor.kernel_profile["programEstimate"] = {
            "estimate_bytes": int(estimate),
            "limit_bytes": int(memory_limit),
            "fused_aggregates": fused,
            "streamed": False,
        }
        if estimate <= memory_limit:
            return None
    # cache the fragment DAG per plan object: fragment roots key the jit
    # cache by identity, so re-fragmenting would recompile every tile
    # program on every run (and leak the old executables).  Entries are
    # stored only AFTER the tileability checks pass ("refused" plans are
    # cached as False), so a cache hit is always a vetted DAG.
    fcache = executor.config.get("fragment_cache")
    fkey = (id(plan), int(memory_limit))  # vetting depends on the budget
    cached = fcache.get(fkey) if fcache is not None else None
    # entries carry the plan object itself: the reference pins id(plan)
    # against recycling (the fragment DAG does not reference the plan)
    if cached is not None and cached[0] is plan:
        return None if cached[1] is False else cached[1]

    def _remember(value):
        if fcache is not None:
            fcache[fkey] = (plan, value)
            for k in list(fcache)[:-256]:
                fcache.pop(k, None)
        return None if value is False else value

    try:
        frags = fragment_plan(plan)
    except NotImplementedError:
        return _remember(False)
    if len(frags) < 2:
        return _remember(False)  # nothing to tile (plain scan output)
    # every oversized scan must sit in a tileable SOURCE fragment;
    # oversized build/gather-side scans are the (partitioned) join-spill
    # framework's job, not ours
    budget = max(memory_limit // SAFETY_FACTOR, 1)
    by_id = {f.id: f for f in frags}

    def _reduces(n: P.PlanNode) -> bool:
        if isinstance(
            n, (P.Aggregate, P.TopN, P.Distinct, P.Limit)
        ):
            return True
        return any(_reduces(s) for s in n.sources)

    for f in frags:
        oversized = any(
            _est_scan_bytes(
                executor, cat, tab, _find_scan_nodes(f.root)[idx]
            ) > budget
            for idx, (cat, tab, _cons) in f.scan_tables.items()
        )
        if not oversized:
            continue
        if f.partitioning != "source":
            return _remember(False)
        # an oversized fragment gathered straight into its consumer must
        # REDUCE (partial agg/topN/limit), or the tile outputs simply
        # re-materialize the oversized input downstream (pure sorts
        # belong to the spilled-sort merge).  BROADCAST/HASH outputs are
        # join inputs the consumer needs resident regardless — tiling
        # still bounds the SCAN working set, so those may pass.
        if f.output_partitioning == "single" and not _reduces(f.root):
            return _remember(False)
    if 0 not in by_id:
        return _remember(False)
    return _remember(frags)


def execute_streaming(executor, plan: P.Output, frags, memory_limit: int) -> Page:
    """Run the fragment DAG locally, tiling SOURCE fragments' splits."""
    from .fragment_exec import FragmentExecutor
    from .local import DeviceScanCache

    budget = max(memory_limit // SAFETY_FACTOR, 1)
    by_id = {f.id: f for f in frags}
    pages_by_fragment: Dict[int, List[Page]] = {}
    # device residency for build/remote inputs across tiles, scoped to
    # this streaming run.  Cross-run isolation comes from the FRESH cache
    # object per run; the remote cache keys themselves are stable so the
    # jit-cache key (which embeds scan keys) stays warm across repeat
    # executions.  A tiled scan's own lanes are in neither cache and die
    # with their dispatch, unless all its tiles fit the session's
    # (`_resident_tile_cache`).
    run_cache = DeviceScanCache()

    def tile_config(scan_cache=None) -> dict:
        cfg = dict(executor.config)
        # tiles quantize on the parent's resolved ladder object — not a
        # re-parse of the spec — so a census-tuned ladder file read at
        # session start governs every tile of the run identically
        cfg["padding_ladder"] = executor.ladder
        # the per-query pool would double-count across tiles, and
        # spill-in-tile would recurse — but the LIMIT stays enforced:
        # when split granularity cannot realize the planned tile count
        # (e.g. a hive table stored as one giant row group), the tile's
        # own _account_memory raises loudly instead of silently running
        # unbounded.
        cfg.pop("memory_pool", None)
        cfg.pop("memory_manager", None)
        cfg["spill_enabled"] = False
        # the session's cache only for a scan whose tiles are all kept:
        # tiles that cycle through it one short of fitting would evict
        # each other, and other tables' entries, and never hit
        cfg["scan_cache"] = scan_cache
        return cfg

    done = set()

    def run_fragment(fid: int):
        if fid in done:
            return
        f = by_id[fid]
        for src in f.source_fragments:
            run_fragment(src)
        remote = {
            src: pages_by_fragment[src] for src in f.source_fragments
        }
        scan_nodes = _find_scan_nodes(f.root)
        if f.partitioning == "source":
            (idx, (cat, tab, cons)) = next(iter(f.scan_tables.items()))
            conn = executor.catalogs.get(cat)
            est = _est_scan_bytes(executor, cat, tab, scan_nodes[idx])
            ntiles = max(1, math.ceil(est / budget))
            splits = conn.split_manager().get_splits(tab, ntiles, cons)
            per = max(1, math.ceil(len(splits) / ntiles))
            # one padded shape for (almost) all tiles -> one compiled
            # program; generous slack so row-count jitter stays inside
            try:
                rows = conn.metadata().get_table_statistics(tab).row_count
            except Exception:  # noqa: BLE001
                rows = 0
            est_tile_rows = int(rows * per / max(len(splits), 1) * 1.3)
            # quantize the shared tile shape onto the executor's ladder:
            # tiles from different table sizes / split factors land on
            # the same rung and reuse one compiled program engine-wide
            est_tile_rows = executor.ladder.quantize(max(est_tile_rows, 128))
            tile_starts = list(range(0, len(splits), per))
            # decided once for the scan, before the first tile is staged
            kept = _resident_tile_cache(
                executor, f, scan_nodes[idx],
                [splits[i: i + per] for i in tile_starts], est_tile_rows,
            )

            # the pool thread's spans join the query's trace
            query_span = TRACER.current_span()

            def make_loaded(i: int) -> FragmentExecutor:
                with TRACER.span("tile_stage", parent=query_span,
                                 tile=i // per) as stage:
                    cfg = tile_config(kept)
                    if est_tile_rows:
                        cfg["scan_cap_override"] = est_tile_rows
                    fe = FragmentExecutor(
                        executor.catalogs, cfg,
                        {idx: splits[i: i + per]}, remote,
                    )
                    fe._streaming_cache = run_cache
                    with TRACER.span("tile_load"):
                        fe.preload(f.root)
                    # start the next tile's H2D copies on this (prefetch)
                    # thread: jnp.asarray enqueues the transfer async, so
                    # it overlaps the CURRENT tile's kernel instead of
                    # serializing in front of the next dispatch
                    with TRACER.span("tile_upload"):
                        fe.preupload(f.root)
                    # a device-generated tile was found resident, or its
                    # generator has just run
                    prof = fe.kernel_profile
                    found = bool(fe._devgen) and not prof.get("devgenWallS")
                    stage.attributes["resident"] = found
                    if fe._devgen:
                        prof["residentTileHits" if found
                             else "residentTileMisses"] = 1
                return fe

            # double-buffered tile pipeline: while tile i computes on the
            # device (the execute thread blocks in device_get), tile i+1's
            # host arrays generate/decode AND upload on the prefetch
            # thread — the steady state is bound by max(host, H2D,
            # device), not their sum (SURVEY §7 hard part 6).  One tile
            # is staged ahead of the executing one: each staged tile
            # holds its scan working set in HBM, and a resident tile
            # stages in about a millisecond.
            from collections import deque
            from concurrent.futures import ThreadPoolExecutor

            out: List[Page] = []
            with ThreadPoolExecutor(max_workers=1) as prefetch:
                pending = deque(
                    prefetch.submit(make_loaded, i)
                    for i in tile_starts[:1]
                )
                nexti = 1
                while pending:
                    tile = len(out)
                    with TRACER.span("tile_wait", tile=tile):
                        fe = pending.popleft().result()
                    if nexti < len(tile_starts):
                        pending.append(
                            prefetch.submit(
                                make_loaded, tile_starts[nexti]
                            )
                        )
                        nexti += 1
                    with TRACER.span("tile_execute", tile=tile,
                                     fragment=fid):
                        out.append(fe.execute(f.root))
                        _merge_tile_counters(executor, fe)
            pages_by_fragment[fid] = out
        else:
            splits_by_scan = {}
            for idx, (cat, tab, cons) in f.scan_tables.items():
                conn = executor.catalogs.get(cat)
                splits_by_scan[idx] = conn.split_manager().get_splits(
                    tab, 1, cons
                )
            fe = FragmentExecutor(
                executor.catalogs, tile_config(), splits_by_scan, remote
            )
            fe._streaming_cache = run_cache
            with TRACER.span("tile_execute", tile=0, fragment=fid):
                pages_by_fragment[fid] = [fe.execute(f.root)]
                _merge_tile_counters(executor, fe)
        done.add(fid)

    run_fragment(0)
    (result,) = pages_by_fragment[0]
    return result
