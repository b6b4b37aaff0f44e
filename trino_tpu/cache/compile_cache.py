"""Compiled-fragment (XLA executable) cache with a persistent on-disk tier.

Absorbs the jit-key construction previously inlined in exec/local.py:
the key is (fragment fingerprint, capacity ladder state, per-scan padded
shape bucket + versioned scan identity + dictionary fingerprint), with every
plan-local component (node ids, force-set members) translated to plan
traversal ordinals so structurally identical fragments from different
sessions — or different processes — produce the same key.

Two tiers:

- in-memory, process-global (``shared_compile_cache()``): entries hold the
  jitted callable plus its trace cell; a second session re-running an
  already-seen fragment performs zero re-traces.  Exposes the dict surface
  exec/local expects (get/[]=/pop/clear) plus LRU bounding and stats.

- persistent, on-disk: JAX's persistent compilation cache (the established
  pattern for eliminating cold-start XLA compiles) keyed by the same
  program; ``place_jax_cache`` decides where it lives (the environment's
  ``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``) and
  ``attach_persistent`` maintains an index of (fingerprint, shape-bucket)
  digests this tier has compiled in the directory a session names, so a
  second process re-traces but skips the XLA compile and records the
  reuse as a persistent hit.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
from collections import OrderedDict
from typing import Dict, Optional

from ..plan import nodes as P


def _stable(o) -> str:
    """Canonical textual form with deterministic set ordering (frozenset
    repr follows hash-table order, which is process-dependent)."""
    if isinstance(o, frozenset):
        return "fs{" + ",".join(sorted(_stable(x) for x in o)) + "}"
    if isinstance(o, (tuple, list)):
        return "(" + ",".join(_stable(x) for x in o) + ")"
    if isinstance(o, dict):
        return "{" + ",".join(
            sorted(f"{_stable(k)}:{_stable(v)}" for k, v in o.items())
        ) + "}"
    return repr(o)


def stable_key_digest(key) -> str:
    return hashlib.sha256(_stable(key).encode()).hexdigest()


def plan_ordinals(plan: P.PlanNode):
    """(id -> preorder ordinal, ordinal -> node) over the plan tree.  The
    ordinal is the cross-process-stable stand-in for id(node) in cache keys
    (two plans with equal fragment fingerprints traverse identically)."""
    order: Dict[int, int] = {}
    by_ord: Dict[int, P.PlanNode] = {}

    def walk(n):
        if id(n) in order:
            return
        o = len(order)
        order[id(n)] = o
        by_ord[o] = n
        for s in n.sources:
            walk(s)

    walk(plan)
    return order, by_ord


def fragment_key(ex, plan, scans, counts, pad_capacity):
    """Build the compile-cache key for one fragment execution attempt.
    Returns (key, order, by_ord); order/by_ord also serve the caller for
    translating trace-recorded node references (dup checks, force sets)
    between sessions sharing an entry.  ``ex`` is the executor (capacity
    ladder state + per-scan components); ``pad_capacity`` is passed in to
    avoid importing the executor module from here."""
    from .signature import fragment_fingerprint

    order, by_ord = plan_ordinals(plan)

    def o(i):
        return order.get(i, i)

    try:
        fp = fragment_fingerprint(plan)
    except Exception:  # unknown node kinds: degrade to per-object identity
        fp = id(plan)
    key = (
        fp, ex.group_capacity, ex.join_factor,
        getattr(ex, "topn_factor", 1),
        getattr(ex, "compact_factor", 1),
        getattr(ex, "group_salt", 0),
        getattr(ex, "force_wide_mul", False),
        frozenset(o(i) for i in getattr(ex, "force_expansion", ())),
        frozenset(o(i) for i in getattr(ex, "force_no_direct", ())),
        # a compiled program is a pure function of (plan, capacities,
        # padded lane shapes, BAKED dictionary contents) — NOT of which
        # splits produced the rows.  The per-scan component is therefore
        # (padded shape bucket, versioned-scan-identity-without-splits,
        # dictionary fingerprint).
        tuple(sorted(
            (o(nid),
             max(pad_capacity(counts[nid]),
                 int(ex.config.get("scan_cap_override") or 0)
                 if isinstance(ex._scan_nodes.get(nid), P.TableScan)
                 else 0),
             ex._jit_scan_component(nid))
            for nid in scans
        )),
    )
    return key, order, by_ord


def _key_buckets(key) -> list:
    """The padded shape buckets (ladder rungs) baked into a jit key.

    The per-scan component is the one element that is a tuple of
    ``(ordinal, rung, scan-identity)`` tuples — executors may append
    further marker components (``("donate", ...)``, ``("megakernels",
    ...)``) after it, so it is found by shape, not position."""
    if not isinstance(key, tuple):
        return []
    for comp in reversed(key):
        if (
            isinstance(comp, tuple) and comp
            and all(
                isinstance(c, tuple) and len(c) > 1
                and isinstance(c[1], int) for c in comp
            )
        ):
            return [int(c[1]) for c in comp]
    return []


_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def place_jax_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    The one place this repo chooses that directory: where the environment
    names one (``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself) it is
    left alone; otherwise the cache goes to ``<checkout>/.jax_cache``.
    Every program is worth keeping (one TPU sort program compiles for
    minutes, and the many sub-second ones add up), so the size and
    compile-time thresholds are dropped.  Idempotent."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class CompileCache:
    """LRU of compiled fragment entries ({"fn", "cell", "plan"}) exposing
    the dict surface the executor uses, with hit/miss/eviction accounting
    and an optional persistent tier."""

    def __init__(self, max_entries: int = 256, on_event=None):
        self._entries: "OrderedDict[object, dict]" = OrderedDict()
        self._lock = threading.RLock()
        self._on_event = on_event
        self._persistent_dir: Optional[str] = None
        self._index: Dict[str, dict] = {}
        self.hits = 0
        self.misses = 0
        self.puts = 0
        self.evictions = 0
        self.persistent_hits = 0
        self.last_prewarm: Optional[dict] = None
        self.max_entries = int(max_entries)

    # -- persistent tier -------------------------------------------------
    def attach_persistent(self, cache_dir: str) -> None:
        """Turn on the persistent tier: JAX's persistent compilation cache
        (wherever ``place_jax_cache`` puts it) keeps the executables, and
        ``cache_dir`` keeps this cache's (fingerprint, shape-bucket)
        index.  Idempotent; safe to call from multiple sessions."""
        cache_dir = os.path.abspath(cache_dir)
        if self._persistent_dir == cache_dir:
            return
        os.makedirs(cache_dir, exist_ok=True)
        place_jax_cache()
        self._persistent_dir = cache_dir
        self._index = self._load_index()

    def prewarm(self, cache_dir: str) -> Optional[dict]:
        """Cold-start prewarm of the persistent tier (once per directory).

        Two honest effects — no executables can be conjured without their
        plans, so this does exactly what a cold worker CAN do before the
        first query:

        - stream every cached artifact through a read so the XLA
          executables are in the OS page cache when the first re-trace
          asks for them (the disk read leaves the query path);
        - seed the compile observatory's family registry with every
          (family, kernel-digest) pair in the index, so the boot burst of
          re-traces classifies as ``persistent_load``/``first_compile``
          and the zero-retrace serve gate stays meaningful across
          restarts.

        Returns ``{"entries", "families", "rungShapes", "bytesPreloaded",
        "wallS"}`` for the bench's warm-start accounting, or None when
        already warmed / nothing to warm."""
        import time

        cache_dir = os.path.abspath(cache_dir)
        with self._lock:
            warmed = getattr(self, "_prewarmed_dirs", None)
            if warmed is None:
                warmed = self._prewarmed_dirs = set()
            if cache_dir in warmed:
                return None
            warmed.add(cache_dir)
        t0 = time.perf_counter()
        from ..obs import compile_observatory as _co

        obs = _co.get_observatory()
        families = set()
        rungs = set()
        for digest, rec in list(self._index.items()):
            fp = rec.get("fp")
            if fp is None:
                continue
            family = stable_key_digest(("family", fp))[:12]
            families.add(family)
            obs.seed_family(family, str(digest)[:12])
            for b in rec.get("buckets") or ():
                try:
                    rungs.add(int(b))
                except (TypeError, ValueError):
                    pass
        preloaded = 0
        # the executables live in JAX's own cache directory, which is the
        # session-named one only when the environment says so
        for d in dict.fromkeys((cache_dir, place_jax_cache())):
            try:
                names = os.listdir(d)
            except OSError:
                continue
            for name in names:
                if name.startswith("index.json"):
                    continue
                path = os.path.join(d, name)
                if not os.path.isfile(path):
                    continue
                try:
                    with open(path, "rb") as f:
                        while True:
                            chunk = f.read(1 << 20)
                            if not chunk:
                                break
                            preloaded += len(chunk)
                except OSError:
                    continue
        result = {
            "entries": len(self._index),
            "families": len(families),
            "rungShapes": sorted(rungs),
            "bytesPreloaded": preloaded,
            "wallS": time.perf_counter() - t0,
        }
        self.last_prewarm = result
        return result

    def _index_path(self) -> str:
        return os.path.join(self._persistent_dir, "index.json")

    def _load_index(self) -> Dict[str, dict]:
        try:
            with open(self._index_path(), "r") as f:
                data = json.load(f)
            return dict(data.get("entries", {}))
        except (OSError, ValueError):
            return {}

    def _save_index(self) -> None:
        if self._persistent_dir is None:
            return
        tmp = self._index_path() + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump({"entries": self._index}, f)
            os.replace(tmp, self._index_path())
        except OSError:
            pass

    def _index_record(self, key) -> None:
        if self._persistent_dir is None:
            return
        digest = stable_key_digest(key)
        rec = self._index.get(digest)
        if rec is None:
            self._index[digest] = {
                "fp": key[0] if isinstance(key, tuple) and key else None,
                "buckets": _key_buckets(key),
                "seen": 1,
            }
        else:
            rec["seen"] = rec.get("seen", 0) + 1
        self._save_index()

    # -- dict surface (exec/local duck-types this) -----------------------
    def get(self, key, default=None):
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                self._event("hit")
                return entry
            self.misses += 1
            # advisory: the program was compiled by an earlier process —
            # this execution re-traces, but XLA loads the executable from
            # jax's persistent cache instead of compiling
            if (self._persistent_dir is not None
                    and stable_key_digest(key) in self._index):
                self.persistent_hits += 1
                self._event("persistent_hit")
            else:
                self._event("miss")
            return default

    def __getitem__(self, key):
        entry = self.get(key)
        if entry is None:
            raise KeyError(key)
        return entry

    def __setitem__(self, key, entry) -> None:
        with self._lock:
            self._entries[key] = entry
            self._entries.move_to_end(key)
            self.puts += 1
            self._event("put")
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self.evictions += 1
                self._event("evict")
            self._index_record(key)

    def pop(self, key, default=None):
        with self._lock:
            return self._entries.pop(key, default)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def persistent_known(self, key) -> bool:
        """Advisory, stat-free probe: was this program compiled by an
        earlier process (persistent-tier index hit)?  The compile
        observatory's cause classifier asks before a compile so the
        resulting re-trace is recorded as ``persistent_load`` rather
        than a genuine shape miss."""
        with self._lock:
            return (self._persistent_dir is not None
                    and stable_key_digest(key) in self._index)

    def _event(self, op: str) -> None:
        from ..utils.metrics import REGISTRY

        REGISTRY.counter(
            "trino_tpu_cache_op_total", "Cache operations by tier and op"
        ).inc(tier="compile", op=op)
        # the observatory's tier view of the same ops: which compile-
        # cache tier (in-memory executable vs on-disk persistent index)
        # answered, feeding the cause taxonomy's persistent_load split
        REGISTRY.counter(
            "trino_tpu_compile_cache_tier_total",
            "Compile-cache operations by serving tier",
        ).inc(
            tier="persistent" if op == "persistent_hit" else "memory",
            op=op,
        )
        if self._on_event is not None:
            self._on_event("compile", op, 0)

    def stats(self) -> Dict[str, int]:
        return {
            "name": "compile_cache",
            "hits": self.hits,
            "misses": self.misses,
            "puts": self.puts,
            "evictions": self.evictions,
            "entries": len(self._entries),
            "bytes": 0,
            "max_bytes": 0,
            "heals": 0,
            "invalidations": 0,
            "persistent_hits": self.persistent_hits,
        }


# One compile cache per process: executables are pure functions of the
# (fingerprint, shapes, dict-content) key, so sharing across sessions is
# safe — unlike result pages, which are session-scoped.
_SHARED: Optional[CompileCache] = None


def shared_compile_cache() -> CompileCache:
    global _SHARED
    if _SHARED is None:
        _SHARED = CompileCache()
    return _SHARED
