"""Compile observatory: engine-wide, cross-query trace/compile ledger.

ROADMAP item 3 (compile-once fragment ABI, zero recompiles at p99 under
concurrent load) needs two inputs nobody records today: *why* each XLA
compile happened, and *which shapes* real traffic presents.  Recompiles
are exactly the rare many-millisecond events that dominate p99 under
load (Dean & Barroso, *The Tail at Scale*, CACM 2013), and choosing
padding buckets from an observed row-count distribution is the
equi-height-histogram problem (Ioannidis, *The History of Histograms*,
VLDB 2003) applied to cardinalities instead of values.

Every compile choke point — the in-memory/persistent compile-cache
tiers, exec/local's ``xla_compile`` path, the eager trace ladder, and
the mesh executor — reports here with a structured *cause*:

- ``first_compile``   — the kernel family had never been compiled
- ``ladder_rung``     — a capacity-overflow retry re-traced (attempt > 0)
- ``shape_miss``      — the family was warm but this shape signature
  was not: the retrace the zero-retrace gate hunts
- ``persistent_load`` — re-trace whose XLA compile was served by the
  on-disk persistent tier (cheap, but still a trace)

Alongside the ledger a **shape census** accumulates per-kernel-family
row-count distributions as a bounded power-of-two sketch — mergeable
across workers via the announcement piggyback (the opstats pattern) —
and :func:`recommend_ladder` turns a census into a geometric padding
ladder with a predicted waste ratio (``scripts/bucket_ladder.py`` is the
CLI).  Storage is the mmap'd torn-tail-tolerant two-segment JSONL shape
the flight recorder proved out: memory-only by default (a bounded
mirror backs ``system.runtime.compiles``), crash-safe on-disk when
``compile_observatory_dir`` is set.  A sliding-window shape-miss rate
above threshold emits a RETRACE_STORM incident-journal event feeding
the query doctor.
"""
from __future__ import annotations

import glob
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from .journal import (  # the proven segment shape; one implementation
    MAX_RECORD_BYTES,
    MIN_SEGMENT_BYTES,
    _Segment,
)
from ..exec.shapes import lane_align as _lane_align

# lowerCamelCase wire schema, linted by scripts/check_metric_names.py
COMPILE_FIELDS = (
    "compileId",
    "kernel",
    "family",
    "cause",
    "mode",
    "shapes",
    "actualRows",
    "paddedRows",
    "compileWallS",
    "queryId",
    "taskId",
    "nodeId",
    "ts",
)

CENSUS_FIELDS = (
    "family",
    "bucket",
    "count",
    "minRows",
    "maxRows",
    "totalRows",
)

# -- the cause taxonomy (classification precedence is top to bottom) ----
LADDER_RUNG = "ladder_rung"
PERSISTENT_LOAD = "persistent_load"
SHAPE_MISS = "shape_miss"
FIRST_COMPILE = "first_compile"
CAUSES = (
    FIRST_COMPILE,
    LADDER_RUNG,
    SHAPE_MISS,
    PERSISTENT_LOAD,
)

DEFAULT_MAX_BYTES = 1 << 20
DEFAULT_MAX_FAMILIES = 64
# census snapshots flush every N observations (plus on sync())
_CENSUS_FLUSH_EVERY = 32
# shape-miss storm: >= STORM_MISSES shape_miss compiles inside
# STORM_WINDOW_S seconds emits one RETRACE_STORM journal event per window
STORM_WINDOW_S = 10.0
STORM_MISSES = 8
# a family is warm (so an unseen shape is a retrace, not a first
# compile) only once it has been known this long: a cold family's
# task partitions and concurrently-started sibling queries present
# their per-partition shapes within moments of the introduction
FAMILY_COLD_S = 5.0

_FILE_PREFIX = "co-"
_CENSUS_PREFIX = "census-"

_ID_LOCK = threading.Lock()
_NEXT_ID = 0


def _new_compile_id() -> int:
    global _NEXT_ID
    with _ID_LOCK:
        _NEXT_ID += 1
        return _NEXT_ID


def _pow2_bucket(rows: int) -> int:
    """The padding bucket a row count falls in: next power of two >= rows
    (floor 128, the TPU lane width — matching exec/shapes.lane_align's
    floor so census buckets and real padded shapes stay comparable).
    These are exactly the geometric PaddingLadder's rungs, so census
    sketches double as ladder-occupancy histograms."""
    rows = max(int(rows), 1)
    b = 128
    while b < rows:
        b <<= 1
    return b


class ShapeCensus:
    """Bounded per-kernel-family row-count sketch.

    Each family keeps a power-of-two histogram of observed row counts
    plus min/max/total — O(log max_rows) buckets per family, merged by
    summing counts, so worker sketches piggyback on announcements and
    the coordinator's union is exact.  Family overflow beyond
    ``max_families`` folds into ``__other__`` (never dropped: the waste
    predictor must see total mass)."""

    OTHER = "__other__"

    def __init__(self, max_families: int = DEFAULT_MAX_FAMILIES):
        self.max_families = max(int(max_families or DEFAULT_MAX_FAMILIES), 1)
        self.families: Dict[str, dict] = {}

    def observe(self, family: str, rows: int) -> None:
        family = str(family or "unknown")
        rows = max(int(rows), 0)
        fam = self.families.get(family)
        if fam is None:
            if len(self.families) >= self.max_families:
                family = self.OTHER
                fam = self.families.get(family)
            if fam is None:
                fam = self.families[family] = {
                    "count": 0,
                    "minRows": rows,
                    "maxRows": rows,
                    "totalRows": 0,
                    "buckets": {},
                }
        fam["count"] += 1
        fam["minRows"] = min(fam["minRows"], rows)
        fam["maxRows"] = max(fam["maxRows"], rows)
        fam["totalRows"] += rows
        b = str(_pow2_bucket(rows))
        fam["buckets"][b] = fam["buckets"].get(b, 0) + 1

    def merge(self, snapshot: Optional[dict]) -> None:
        """Union another census snapshot (a worker's piggyback) in."""
        for family, other in ((snapshot or {}).get("families") or {}).items():
            if not isinstance(other, dict):
                continue
            fam = self.families.get(family)
            if fam is None:
                if len(self.families) >= self.max_families:
                    family = self.OTHER
                fam = self.families.setdefault(family, {
                    "count": 0,
                    "minRows": int(other.get("minRows", 0)),
                    "maxRows": int(other.get("maxRows", 0)),
                    "totalRows": 0,
                    "buckets": {},
                })
            fam["count"] += int(other.get("count", 0))
            fam["minRows"] = min(fam["minRows"], int(other.get("minRows", 0)))
            fam["maxRows"] = max(fam["maxRows"], int(other.get("maxRows", 0)))
            fam["totalRows"] += int(other.get("totalRows", 0))
            for b, c in (other.get("buckets") or {}).items():
                fam["buckets"][str(b)] = fam["buckets"].get(str(b), 0) + int(c)

    def snapshot(self) -> dict:
        return {"families": {
            f: {
                "count": fam["count"],
                "minRows": fam["minRows"],
                "maxRows": fam["maxRows"],
                "totalRows": fam["totalRows"],
                "buckets": dict(fam["buckets"]),
            }
            for f, fam in self.families.items()
        }}

    def rows(self) -> List[dict]:
        """Flat (family, bucket) rows in the CENSUS_FIELDS wire shape
        (``system.runtime.shape_census``)."""
        out: List[dict] = []
        for family in sorted(self.families):
            fam = self.families[family]
            for b in sorted(fam["buckets"], key=int):
                out.append({
                    "family": family,
                    "bucket": int(b),
                    "count": int(fam["buckets"][b]),
                    "minRows": int(fam["minRows"]),
                    "maxRows": int(fam["maxRows"]),
                    "totalRows": int(fam["totalRows"]),
                })
        return out

    def top_families(self, n: int = 5) -> List[dict]:
        fams = sorted(
            self.families.items(),
            key=lambda kv: kv[1]["count"],
            reverse=True,
        )
        return [
            {"family": f, "count": fam["count"],
             "minRows": fam["minRows"], "maxRows": fam["maxRows"]}
            for f, fam in fams[:n]
        ]


class CompileObservatory:
    """Process-global cross-query ledger of every trace/compile.

    In-memory mirror (bounded deque) + optional mmap'd torn-tail-
    tolerant on-disk segments (``compile_observatory_dir``), the same
    crash-safety contract as the flight recorder and incident journal.
    Segment names carry the writing pid so concurrent processes sharing
    a directory never clobber each other; the census persists as an
    atomically-replaced per-writer JSON snapshot that an offline reader
    (``read_census_dir``) merges across writers."""

    def __init__(
        self,
        directory: Optional[str] = None,
        max_bytes: int = DEFAULT_MAX_BYTES,
        name: Optional[str] = None,
        max_events: int = 4096,
        census_max_families: int = DEFAULT_MAX_FAMILIES,
        storm_window_s: float = STORM_WINDOW_S,
        storm_misses: int = STORM_MISSES,
        family_cold_s: float = FAMILY_COLD_S,
    ):
        self.directory = str(directory or "").strip() or None
        self.max_bytes = max(int(max_bytes or DEFAULT_MAX_BYTES),
                             2 * MIN_SEGMENT_BYTES)
        self.name = name or str(os.getpid())
        self._lock = threading.Lock()
        self.mirror: deque = deque(maxlen=max_events)
        self.census = ShapeCensus(census_max_families)
        self.counts: Dict[str, int] = {c: 0 for c in CAUSES}
        self.compile_wall_s = 0.0
        # family digest -> shape signatures (kernel digests) seen; the
        # classifier's warm/cold memory.  Bounded like the census.
        self._families: Dict[str, set] = {}
        # family digest -> (introducing query, first-seen ts): every
        # partition of that query — and any compile inside the family's
        # cold window — is part of the first execution, so its shapes
        # are first compiles, not retraces
        self._family_intro: Dict[str, tuple] = {}
        self._family_cold_s = float(family_cold_s)
        self._storm_window_s = float(storm_window_s)
        self._storm_misses = max(int(storm_misses), 1)
        self._miss_times: deque = deque()
        self._storm_last_emit = 0.0
        self._census_dirty = 0
        # announcement cursor: local events not yet piggybacked
        self._announced_through = 0
        self._segments: List[_Segment] = []
        self._active = 0
        if self.directory:
            os.makedirs(self.directory, exist_ok=True)
            seg_bytes = max(MIN_SEGMENT_BYTES, self.max_bytes // 2)
            for i in range(2):
                path = os.path.join(
                    self.directory,
                    f"{_FILE_PREFIX}{self.name}-{i}.jsonl",
                )
                seg = _Segment(path, seg_bytes)
                seg.reset()  # a reused path must not replay stale events
                self._segments.append(seg)

    # -- classification -------------------------------------------------
    def classify(
        self,
        family: str,
        shape_sig: str,
        ladder_attempt: int = 0,
        persistent: bool = False,
        query_id: str = "",
    ) -> str:
        """Structured cause for one compile, in precedence order:
        ladder rung beats persistent load beats
        the warm/cold family distinction (shape_miss vs first_compile).
        A family is only warm against queries that arrive after its
        cold window: the query that introduced it — and siblings that
        started alongside it — present their per-partition shapes
        moments later, and those are first compiles, not retraces."""
        if int(ladder_attempt or 0) > 0:
            return LADDER_RUNG
        if persistent:
            return PERSISTENT_LOAD
        with self._lock:
            seen = self._families.get(str(family))
            intro = self._family_intro.get(str(family))
        if seen is None or str(shape_sig) in seen:
            return FIRST_COMPILE
        if intro is not None:
            intro_query, intro_ts = intro
            if query_id and intro_query == str(query_id):
                return FIRST_COMPILE
            if time.time() - intro_ts < self._family_cold_s:
                return FIRST_COMPILE
        return SHAPE_MISS

    def _register(
        self, family: str, shape_sig: str, query_id: str = ""
    ) -> None:
        with self._lock:
            seen = self._families.get(str(family))
            if seen is None:
                if len(self._families) >= 4 * self.census.max_families:
                    return  # bounded: stop learning, never grow unbounded
                seen = self._families[str(family)] = set()
                self._family_intro[str(family)] = (
                    str(query_id or ""), time.time(),
                )
            if len(seen) < 256:
                seen.add(str(shape_sig))

    def seed_family(self, family: str, shape_sig: str) -> None:
        """Boot-time prewarm hook (CompileCache.prewarm): register a
        family/shape pair from the persistent-tier index WITHOUT a
        compile event.  Seeded families get the normal cold-window
        grace, so the first post-restart traffic that re-traces indexed
        programs classifies persistent_load / first_compile — a cold
        boot must never look like a retrace storm."""
        self._register(str(family), str(shape_sig), query_id="__prewarm__")

    # -- record ---------------------------------------------------------
    def record(
        self,
        kernel: str,
        family: str,
        cause: Optional[str] = None,
        mode: str = "jit",
        shapes: Optional[dict] = None,
        actual_rows: int = 0,
        padded_rows: int = 0,
        compile_wall_s: float = 0.0,
        query_id: str = "",
        task_id: str = "",
        node_id: str = "",
        ladder_attempt: int = 0,
        persistent: bool = False,
        scan_rows: Optional[List[int]] = None,
        shape_sig: Optional[str] = None,
    ) -> dict:
        """Append one compile event; returns the ledger record.  When
        ``cause`` is omitted it is classified from the flags and the
        family's warm/cold state.  ``shape_sig`` is the signature the
        warm/cold classifier keys on (defaults to the kernel digest,
        which embeds the padded buckets on the jit path); ``scan_rows``
        (per-scan actual row counts) feeds the shape census."""
        kernel = str(kernel)
        family = str(family or kernel)
        sig = str(shape_sig or kernel)
        if cause is None:
            cause = self.classify(
                family, sig, ladder_attempt=ladder_attempt,
                persistent=persistent,
                query_id=str(query_id or ""),
            )
        self._register(family, sig, query_id=str(query_id or ""))
        event = {
            "compileId": _new_compile_id(),
            "kernel": kernel,
            "family": family,
            "cause": cause,
            "mode": str(mode),
            "shapes": dict(shapes or {}),
            "actualRows": int(actual_rows),
            "paddedRows": int(padded_rows),
            "compileWallS": float(compile_wall_s),
            "queryId": str(query_id or ""),
            "taskId": str(task_id or ""),
            "nodeId": str(node_id or ""),
            "ts": time.time(),
        }
        for rows in (scan_rows if scan_rows is not None
                     else [actual_rows]):
            self.census.observe(family, rows)
        self._append(event)
        self._metrics(event)
        if cause == SHAPE_MISS:
            self._note_shape_miss(event)
        with self._lock:
            self._census_dirty += 1
            dirty = self._census_dirty
        if dirty >= _CENSUS_FLUSH_EVERY:
            self._flush_census()
        return event

    def _append(self, event: dict) -> None:
        data = json.dumps(event, separators=(",", ":"),
                          default=str).encode() + b"\n"
        if len(data) > MAX_RECORD_BYTES:
            event = dict(event, shapes={"truncated": True})
            data = json.dumps(event, separators=(",", ":"),
                              default=str).encode() + b"\n"
        with self._lock:
            self.mirror.append(event)
            self.counts[event["cause"]] = (
                self.counts.get(event["cause"], 0) + 1
            )
            self.compile_wall_s += float(event.get("compileWallS") or 0.0)
            if not self._segments:
                return
            seg = self._segments[self._active]
            if not seg.append(data):
                self._active = 1 - self._active
                seg = self._segments[self._active]
                seg.reset()
                seg.append(data)

    def _metrics(self, event: dict) -> None:
        from ..utils.metrics import REGISTRY

        REGISTRY.counter(
            "trino_tpu_compile_events_total",
            "Trace/compile events observed engine-wide, by cause",
        ).inc(cause=event["cause"], mode=event["mode"])
        REGISTRY.histogram(
            "trino_tpu_compile_wall_seconds",
            "Per-event compile (or trace) wall time from the observatory",
        ).observe(float(event.get("compileWallS") or 0.0))
        REGISTRY.gauge(
            "trino_tpu_compile_census_families_state",
            "Distinct kernel families in the shape census",
        ).set(len(self.census.families))

    def _note_shape_miss(self, event: dict) -> None:
        """Sliding-window storm detector: a burst of shape-miss retraces
        is a p99 incident (each one is many milliseconds of compile on
        the query path), so it lands in the incident journal where the
        doctor can cite it."""
        now = float(event.get("ts") or time.time())
        with self._lock:
            self._miss_times.append(now)
            while (self._miss_times
                   and now - self._miss_times[0] > self._storm_window_s):
                self._miss_times.popleft()
            n = len(self._miss_times)
            fire = (
                n >= self._storm_misses
                and now - self._storm_last_emit > self._storm_window_s
            )
            if fire:
                self._storm_last_emit = now
        if fire:
            from . import journal

            journal.emit(
                journal.RETRACE_STORM,
                query_id=event.get("queryId", ""),
                task_id=event.get("taskId", ""),
                node_id=event.get("nodeId", ""),
                severity=journal.WARN,
                misses=n,
                windowS=self._storm_window_s,
                family=event.get("family", ""),
                kernel=event.get("kernel", ""),
            )

    # -- cross-worker merge (announcement piggyback) --------------------
    def announce_snapshot(self, max_events: int = 256) -> dict:
        """The worker-side piggyback: per-cause counts, census sketch,
        and the ledger events appended since the last announcement
        (bounded; the counts stay exact even when events are elided)."""
        with self._lock:
            events = [
                e for e in self.mirror
                if e["compileId"] > self._announced_through
            ]
            if events:
                self._announced_through = events[-1]["compileId"]
            events = events[-max_events:]
            counts = dict(self.counts)
            wall = self.compile_wall_s
        return {
            "pid": os.getpid(),
            "counts": counts,
            "compileWallS": wall,
            "census": self.census.snapshot(),
            "events": events,
        }

    def ingest(self, node_id: str, snapshot: Optional[dict]) -> None:
        """Coordinator-side union of one worker's piggyback.  Counts and
        census are cumulative per worker, so they replace (keyed by
        node) and merge at read time; events append.  A same-pid
        announcement is this process's own ledger coming back around
        (in-process cluster: testing/runner.py) — ingesting it would
        double every count and compound the census, so it is a no-op."""
        if not isinstance(snapshot, dict):
            return
        try:
            if int(snapshot.get("pid") or -1) == os.getpid():
                return
        except (TypeError, ValueError):
            pass
        remote = getattr(self, "_remote", None)
        if remote is None:
            remote = self._remote = {}
        with self._lock:
            prev = remote.get(str(node_id)) or {}
            seen = set(prev.get("seen") or ())
            entry = {
                "counts": {
                    c: int((snapshot.get("counts") or {}).get(c, 0))
                    for c in CAUSES
                },
                "compileWallS": float(snapshot.get("compileWallS") or 0.0),
                "census": snapshot.get("census") or {},
                "seen": seen,
            }
            remote[str(node_id)] = entry
        for e in snapshot.get("events") or []:
            if not isinstance(e, dict) or "cause" not in e:
                continue
            eid = (str(node_id), e.get("compileId"))
            if eid in seen:
                continue
            seen.add(eid)
            e = dict(e, nodeId=e.get("nodeId") or str(node_id))
            with self._lock:
                self.mirror.append(e)

    # -- read / rollup --------------------------------------------------
    def tail(self, n: Optional[int] = None) -> List[dict]:
        with self._lock:
            events = list(self.mirror)
        return events[-n:] if n else events

    def counts_by_cause(self) -> Dict[str, int]:
        """Engine-wide per-cause totals: local counts plus every
        ingested worker's latest cumulative piggyback."""
        with self._lock:
            totals = dict(self.counts)
            for entry in (getattr(self, "_remote", None) or {}).values():
                for c, v in (entry.get("counts") or {}).items():
                    totals[c] = totals.get(c, 0) + int(v)
        return totals

    def total_compile_wall_s(self) -> float:
        with self._lock:
            wall = self.compile_wall_s
            for entry in (getattr(self, "_remote", None) or {}).values():
                wall += float(entry.get("compileWallS") or 0.0)
        return wall

    def node_family_map(
        self, local_node_id: str = "local"
    ) -> Dict[str, set]:
        """Which kernel-family digests each node holds warm compiles
        for: the local process's census plus every ingested worker's
        latest cumulative piggyback.  The serving observatory joins
        this against per-signature family digests to build
        ``system.runtime.signature_affinity``.  In an in-process
        cluster the workers share this very observatory, so all warmth
        appears under ``local_node_id`` — subprocess/remote workers
        each get their own row via announcements."""
        out: Dict[str, set] = {}
        with self._lock:
            local = set(self.census.families) | set(self._families)
            if local:
                out[str(local_node_id or "local")] = local
            for node_id, entry in (
                getattr(self, "_remote", None) or {}
            ).items():
                fams = set(
                    ((entry.get("census") or {}).get("families") or {})
                )
                if fams:
                    out[str(node_id)] = fams
        return out

    def merged_census(self) -> ShapeCensus:
        """Engine-wide census view: the local sketch plus each ingested
        worker's latest cumulative snapshot (snapshots replace per node,
        so re-announcement never compounds counts)."""
        merged = ShapeCensus(self.census.max_families)
        merged.merge(self.census.snapshot())
        with self._lock:
            remotes = [
                dict(entry.get("census") or {})
                for entry in (getattr(self, "_remote", None) or {}).values()
            ]
        for snap in remotes:
            merged.merge(snap)
        return merged

    def rollup(self, top: int = 5) -> dict:
        """The bench/profile attachment: per-cause counts, total compile
        wall, and the census's busiest families."""
        counts = self.counts_by_cause()
        census = self.merged_census()
        return {
            "byCause": counts,
            "compiles": sum(counts.values()),
            "compileWallS": self.total_compile_wall_s(),
            "censusFamilies": len(census.families),
            "topFamilies": census.top_families(top),
        }

    # -- durability -----------------------------------------------------
    def _census_path(self) -> Optional[str]:
        if not self.directory:
            return None
        return os.path.join(
            self.directory, f"{_CENSUS_PREFIX}{self.name}.json"
        )

    def _flush_census(self) -> None:
        path = self._census_path()
        with self._lock:
            self._census_dirty = 0
            if path is None:
                return
            snap = self.census.snapshot()
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(snap, f)
            os.replace(tmp, path)
        except OSError:
            pass

    def sync(self) -> None:
        """Flush segments + census snapshot (drain/shutdown barrier)."""
        self._flush_census()
        with self._lock:
            for seg in self._segments:
                seg.sync()

    def close(self) -> None:
        self._flush_census()
        with self._lock:
            for seg in self._segments:
                seg.close()
            self._segments = []


# -- the process-global observatory (hook sites have no session ref) ----

_GLOBAL_LOCK = threading.Lock()
_GLOBAL: Optional[CompileObservatory] = None


def get_observatory() -> CompileObservatory:
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = CompileObservatory(None)
        return _GLOBAL


def configure(
    directory,
    max_bytes=None,
    census_max_families=None,
) -> CompileObservatory:
    """Upgrade/re-point the global observatory
    (``compile_observatory_dir`` / ``compile_census_max_families``).
    The memory mirror and census carry over so compiles that fired
    before the owning session finished constructing are not lost."""
    global _GLOBAL
    directory = str(directory or "").strip() or None
    try:
        max_bytes = int(max_bytes or 0) or DEFAULT_MAX_BYTES
    except (TypeError, ValueError):
        max_bytes = DEFAULT_MAX_BYTES
    try:
        fams = int(census_max_families or 0) or DEFAULT_MAX_FAMILIES
    except (TypeError, ValueError):
        fams = DEFAULT_MAX_FAMILIES
    with _GLOBAL_LOCK:
        cur = _GLOBAL
        if (
            cur is not None
            and cur.directory == directory
            and cur.census.max_families == fams
            and (directory is None or cur.max_bytes == max_bytes)
        ):
            return cur
        nxt = CompileObservatory(
            directory, max_bytes=max_bytes, census_max_families=fams
        )
        if cur is not None:
            for event in cur.tail():
                nxt._append(event)
            nxt.census.merge(cur.census.snapshot())
            nxt._families = cur._families
            nxt._family_intro = getattr(cur, "_family_intro", None) or {}
            nxt._family_cold_s = getattr(
                cur, "_family_cold_s", FAMILY_COLD_S
            )
            nxt._remote = getattr(cur, "_remote", None) or {}
            cur.close()
        _GLOBAL = nxt
        return nxt


def record_compile(**kwargs) -> dict:
    """Module-level one-liner for the compile choke points."""
    return get_observatory().record(**kwargs)


def sync():
    global _GLOBAL
    with _GLOBAL_LOCK:
        o = _GLOBAL
    if o is not None:
        o.sync()


def _reset_observatory():
    """Test isolation: drop the process-global observatory."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is not None:
            _GLOBAL.close()
        _GLOBAL = None


# -- offline readers (scripts/bucket_ladder.py, kill -9 post-mortems) ---


def read_observatory_dir(directory: str) -> List[dict]:
    """Parse every ledger segment in ``directory`` (all writer pids)
    into events ordered by (ts, compileId).  Torn trailing lines and
    zeroed tail space are skipped, never an error."""
    events: List[dict] = []
    for path in sorted(
        glob.glob(os.path.join(directory, _FILE_PREFIX + "*.jsonl"))
    ):
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            continue
        for line in data.split(b"\n"):
            line = line.strip(b"\0").strip()
            if not line:
                continue
            try:
                event = json.loads(line)
            except ValueError:
                continue  # torn write: the crash interrupted this line
            if isinstance(event, dict) and "cause" in event:
                events.append(event)
    events.sort(key=lambda e: (e.get("ts", 0.0), e.get("compileId", 0)))
    return events


def read_census_dir(directory: str) -> ShapeCensus:
    """Merge every writer's census snapshot in ``directory`` into one
    sketch (the cross-process analog of the announcement piggyback)."""
    census = ShapeCensus(max_families=1 << 16)
    for path in sorted(
        glob.glob(os.path.join(directory, _CENSUS_PREFIX + "*.json"))
    ):
        try:
            with open(path) as f:
                census.merge(json.load(f))
        except (OSError, ValueError):
            continue
    return census


# -- padding-ladder recommendation (ROADMAP item 3 input) ----------------


def recommend_ladder(
    census: ShapeCensus,
    max_rungs: int = 8,
    lane: int = 128,
) -> dict:
    """Equi-height ladder over the censused row-count mass.

    Pools every family's power-of-two sketch into one weighted
    distribution of observed (bucketed) row counts, places up to
    ``max_rungs`` rung boundaries at equal-mass quantiles (Ioannidis's
    equi-height construction), rounds each rung up to a multiple of
    ``lane`` (the TPU lane width), and predicts the waste ratio
    (padded/actual rows) the ladder would produce against the same
    distribution.  Returns ``{"ladder", "wasteRatio", "observations",
    "perRung"}``; an empty census yields an empty ladder."""
    # pooled (cover_rows, weight) points.  A rung placed at a bucket
    # must COVER it, so the rung candidate is the bucket's ceiling —
    # clamped to the family's observed max for its top bucket (the pow2
    # sketch only bounds it from above).  The bucket's geometric
    # midpoint is kept separately as the actual-rows estimate.
    points: Dict[int, dict] = {}
    total_actual = 0.0
    observations = 0
    for fam in census.families.values():
        buckets = fam["buckets"]
        top_b = max((int(b) for b in buckets), default=0)
        for b, c in buckets.items():
            hi = int(b)
            c = int(c)
            lo = hi // 2 + 1 if hi > lane else 1
            rep = max(int((lo * hi) ** 0.5), 1)
            cover = hi
            if hi == top_b and int(fam.get("maxRows") or 0):
                cover = min(hi, int(fam["maxRows"]))
            cover = max(cover, 1)
            p = points.setdefault(cover, {"count": 0, "actual": 0.0})
            p["count"] += c
            p["actual"] += rep * c
            observations += c
        total_actual += float(fam.get("totalRows") or 0)
    if not observations:
        return {"ladder": [], "wasteRatio": 1.0,
                "observations": 0, "perRung": []}
    covers = sorted(points)
    # equi-height: rung boundaries at equal cumulative-mass quantiles
    rungs: List[int] = []
    mass = 0
    step = observations / float(max_rungs)
    threshold = step
    for cover in covers:
        mass += points[cover]["count"]
        if mass >= threshold or cover == covers[-1]:
            rung = _lane_align(cover, lane)
            if not rungs or rung > rungs[-1]:
                rungs.append(rung)
            while threshold <= mass:
                threshold += step
    # every observation must fit the top rung
    top = _lane_align(covers[-1], lane)
    if rungs[-1] < top:
        rungs.append(top)
    # predicted waste: each observation pads to the smallest rung that
    # covers its bucket (actualRows per rung is the midpoint estimate;
    # the global denominator is the census's exact totalRows)
    padded_total = 0.0
    per_rung = [
        {"rung": r, "count": 0, "actualRows": 0} for r in rungs
    ]
    for cover in covers:
        p = points[cover]
        for pr in per_rung:
            if pr["rung"] >= cover:
                pr["count"] += p["count"]
                pr["actualRows"] += int(p["actual"])
                padded_total += float(pr["rung"]) * p["count"]
                break
    waste = (padded_total / total_actual) if total_actual else 1.0
    return {
        "ladder": rungs,
        "wasteRatio": waste,
        "observations": observations,
        "perRung": per_rung,
    }
