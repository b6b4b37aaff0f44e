"""Unified cache subsystem tests (cache/): plan signatures, the fragment
result cache (LRU + spill + chaos heal + DML invalidation), the compiled-
fragment cache (cross-session reuse, persistent tier, runtime-error
retry), and the observability surfaces (system.runtime.caches, /v1/cache).

Reference parity: Presto's fragment result cache tests (canonical plan
hashing, version-keyed invalidation) + JAX persistent compilation cache.
"""
import json
import os
import subprocess
import sys
import tempfile
import urllib.request

import jax
import pytest

from trino_tpu import types as T
from trino_tpu.cache import plan_signature
from trino_tpu.cache.compile_cache import (
    CompileCache,
    fragment_key,
    shared_compile_cache,
    stable_key_digest,
)
from trino_tpu.cache.result_cache import FragmentResultCache
from trino_tpu.cache.signature import fragment_fingerprint, shape_bucket
from trino_tpu.page import page_from_pydict
from trino_tpu.session import Session, tpch_session
from trino_tpu.utils.faults import FaultInjector

SF = 0.001

Q6 = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07
  and l_quantity < 24
"""


def _mem_session(**props):
    s = Session(config=props or None)
    s.create_catalog("mem", "memory", {})
    s.catalogs.get("mem").create_table(
        "t", [("x", T.BIGINT), ("y", T.BIGINT)],
        {"x": [1, 2, 3], "y": [10, 20, 30]},
    )
    return s


# --- plan signatures -----------------------------------------------------


def test_signature_alias_invariant():
    s = tpch_session(SF)
    a = plan_signature(s.plan("select sum(n_nationkey) as a from nation"))
    b = plan_signature(s.plan("select sum(n_nationkey) as b from nation"))
    assert a.digest == b.digest
    # the exact fingerprint keeps client-facing names: it must differ
    fa = fragment_fingerprint(s.plan("select sum(n_nationkey) as a from nation"))
    fb = fragment_fingerprint(s.plan("select sum(n_nationkey) as b from nation"))
    assert fa != fb


def test_signature_symbol_rename_invariant():
    s = tpch_session(SF)
    a = plan_signature(
        s.plan("select t.k from (select n_nationkey as k from nation) t")
    )
    b = plan_signature(
        s.plan("select u.m from (select n_nationkey as m from nation) u")
    )
    assert a.digest == b.digest


def test_signature_literal_parameterized():
    s = tpch_session(SF)
    a = plan_signature(s.plan("select * from nation where n_regionkey = 1"))
    b = plan_signature(s.plan("select * from nation where n_regionkey = 3"))
    assert a.digest == b.digest
    assert a.params != b.params  # literals live in the key's param slot


def test_signature_semantics_not_aliased():
    s = tpch_session(SF)
    a = plan_signature(s.plan("select * from nation where n_regionkey = 1"))
    b = plan_signature(s.plan("select * from nation where n_regionkey < 1"))
    assert a.digest != b.digest  # operator is structure, not a literal
    c = plan_signature(s.plan("select * from region where r_regionkey = 1"))
    assert a.digest != c.digest  # table names are protected positions


def test_signature_join_order_sensitive():
    # the signature must NOT canonicalize join order itself — two plans
    # with swapped probe/build sides are different physical plans.  (On
    # optimized plans the build-side chooser happens to canonicalize this
    # pair, which is exactly why the signature may not do it again.)
    s = tpch_session(SF)
    a = plan_signature(s.plan(
        "select n_name from nation join region on n_regionkey = r_regionkey",
        optimized=False,
    ))
    b = plan_signature(s.plan(
        "select n_name from region join nation on n_regionkey = r_regionkey",
        optimized=False,
    ))
    assert a.digest != b.digest


def test_signature_tables_collected():
    s = tpch_session(SF)
    sig = plan_signature(s.plan(
        "select n_name from nation join region on n_regionkey = r_regionkey"
    ))
    assert ("tpch", "nation") in sig.tables
    assert ("tpch", "region") in sig.tables


def test_nondeterministic_plans_refused():
    s = tpch_session(SF)
    for q, why in (
        ("select now() as t", "now"),
        ("select rand() as r from nation", "rand"),
        ("select n_name from nation where rand() < 0.5", "rand-filter"),
    ):
        sig = plan_signature(s.plan(q))
        assert not sig.deterministic, (q, why)
        assert sig.reason


def test_shape_bucket():
    assert shape_bucket(1) == 128
    assert shape_bucket(128) == 128
    assert shape_bucket(129) == 256
    assert shape_bucket(6001215) == 6001280


# --- nondeterministic functions at runtime -------------------------------


def test_rand_executes_and_differs_per_query():
    s = _mem_session()
    p1 = s.execute("select rand() as r from mem.t")
    vals1 = [float(p1.columns[0].values[i]) for i in range(p1.count)]
    assert all(0.0 <= v < 1.0 for v in vals1)
    vals2 = [
        float(v) for v in s.execute(
            "select rand() as r from mem.t"
        ).columns[0].values[:3]
    ]
    assert vals1 != vals2  # fresh seed per query
    assert len(set(vals1)) == 3  # and per row within a query
    # never admitted to the result cache
    assert s.caches.result_cache.puts == 0


def test_now_not_stale_across_queries():
    s = tpch_session(SF)
    a = s.execute("select now() as t").columns[0].values[0]
    b = s.execute("select now() as t").columns[0].values[0]
    assert a != b  # plan cache must not replay the folded timestamp
    assert s.caches.result_cache.puts == 0


# --- fragment result cache: unit level -----------------------------------


def _page(n=100):
    return page_from_pydict([("x", T.BIGINT)], {"x": list(range(n))})


def test_result_cache_lru_eviction_spills():
    with tempfile.TemporaryDirectory() as d:
        rc = FragmentResultCache(
            max_bytes=1000, spill_dir=d, max_entry_fraction=1.0
        )
        rc.put(("k1",), _page())  # 800 bytes
        rc.put(("k2",), _page())  # over budget: k1 (oldest) spills
        st = rc.stats()
        assert st["evictions"] == 1 and rc.spills == 1
        assert st["bytes"] <= 1000
        # spilled entry still serves (promoted back, k2 spills in turn)
        back = rc.get(("k1",))
        assert back is not None and back.count == 100
        assert rc.spill_hits == 1


def test_result_cache_lru_recency():
    rc = FragmentResultCache(max_bytes=1700, max_entry_fraction=1.0)
    rc.put(("k1",), _page())
    rc.put(("k2",), _page())
    assert rc.get(("k1",)) is not None  # touch k1: k2 becomes oldest
    rc.put(("k3",), _page())
    assert rc.evictions == 1
    spill_hits = rc.spill_hits
    assert rc.get(("k1",)) is not None
    assert rc.spill_hits == spill_hits  # k1 stayed hot (recency won)
    assert rc.get(("k2",)) is not None
    assert rc.spill_hits == spill_hits + 1  # k2 was the one spilled


def test_result_cache_rejects_oversized():
    rc = FragmentResultCache(max_bytes=1000)  # entry cap = 500
    assert not rc.put(("k",), _page())
    assert rc.rejected == 1 and rc.stats()["entries"] == 0


def test_result_cache_invalidate_by_table():
    rc = FragmentResultCache(max_bytes=1 << 20)
    rc.put(("k1",), _page(10), tables=(("mem", "a"),))
    rc.put(("k2",), _page(10), tables=(("mem", "b"),))
    assert rc.invalidate("mem", "a") == 1
    assert rc.get(("k1",)) is None
    assert rc.get(("k2",)) is not None
    assert rc.stats()["invalidations"] == 1


def test_result_cache_chaos_corrupt_spill_is_miss_and_heal():
    with tempfile.TemporaryDirectory() as d:
        rc = FragmentResultCache(
            max_bytes=1000, spill_dir=d, max_entry_fraction=1.0
        )
        rc.put(("k1",), _page())
        rc.put(("k2",), _page())  # spills k1
        inj = FaultInjector.from_spec({"seed": 7, "cache_read": {"nth": 1}})
        assert rc.get(("k1",), injector=inj) is None  # corrupt: miss
        assert rc.heals == 1  # frame deleted, never an error
        assert rc.get(("k1",), injector=inj) is None  # healed away
        assert rc.heals == 1  # plain miss now, no second heal


# --- result cache: end to end --------------------------------------------


def test_warm_q6_skips_execution():
    s = tpch_session(SF)
    r1 = s.execute(Q6)
    assert s.last_scan_bytes > 0
    r2 = s.execute(Q6)
    assert r2.to_pylist() == r1.to_pylist()
    assert s.last_scan_bytes == 0  # nothing scanned: served from cache
    rows = s.execute(
        "select name, hits, misses from system.runtime.caches"
    ).to_pylist()
    by_name = {r[0]: r for r in rows}
    assert by_name["result_cache"][1] == 1  # the warm Q6 hit


def test_result_cache_alias_hit_relabeled():
    s = tpch_session(SF)
    s.execute("select sum(n_nationkey) as a from nation")
    page = s.execute("select sum(n_nationkey) as b from nation")
    assert s.caches.result_cache.hits == 1  # alias-invariant digest
    assert page.names == ["b"]  # relabeled to THIS query's alias


def test_insert_invalidates_cached_result():
    s = _mem_session()
    q = "select sum(x) as s from mem.t"
    assert s.execute(q).to_pylist() == [(6,)]
    assert s.execute(q).to_pylist() == [(6,)]
    assert s.caches.result_cache.hits == 1
    s.execute("insert into mem.t values (10, 100)")
    assert s.execute(q).to_pylist() == [(16,)]  # fresh, not the stale 6
    assert s.caches.result_cache.stats()["invalidations"] >= 1


def test_memory_data_version_per_table():
    s = _mem_session()
    conn = s.catalogs.get("mem")
    conn.create_table("u", [("z", T.BIGINT)], {"z": [5]})
    v_t = conn.data_version("t")
    v_u = conn.data_version("u")
    s.execute("insert into mem.u values (6)")
    assert conn.data_version("u") > v_u
    assert conn.data_version("t") == v_t  # t untouched
    # so t's cached result survives a write to u
    q = "select sum(x) as s from mem.t"
    s.execute(q)
    s.execute("insert into mem.u values (7)")
    s.execute(q)
    assert s.caches.result_cache.hits == 1


def test_session_property_disables_result_cache():
    s = tpch_session(SF, result_cache=False)
    s.execute(Q6)
    s.execute(Q6)
    st = s.caches.result_cache.stats()
    assert st["puts"] == 0 and st["hits"] == 0


def test_system_tables_never_result_cached():
    s = tpch_session(SF)
    s.execute("select * from system.runtime.queries")
    s.execute("select * from system.runtime.queries")
    assert s.caches.result_cache.puts == 0  # system connector: live state


# --- compiled-fragment cache ---------------------------------------------


def test_fragment_fingerprint_process_stable_components():
    # the key must survive repr()/digest round-trips with deterministic
    # set ordering (frozenset repr follows hash order)
    k = ("fp", 1, 2, frozenset([3, 1, 2]), (("a", 128, (None, 7)),))
    assert stable_key_digest(k) == stable_key_digest(
        ("fp", 1, 2, frozenset([2, 3, 1]), (("a", 128, (None, 7)),))
    )
    assert stable_key_digest(k) != stable_key_digest(
        ("fp", 1, 2, frozenset([3, 1]), (("a", 128, (None, 7)),))
    )


def test_compile_cache_cross_session_reuse_zero_retraces():
    import trino_tpu.exec.local as L

    cc = CompileCache()
    retraces = [0]
    orig = L.LocalExecutor._run

    def counting(self, plan, ctx):
        retraces[0] += 1
        return orig(self, plan, ctx)

    q = "select count(*) as c from orders where o_orderkey < 100"
    try:
        L.LocalExecutor._run = counting
        a = tpch_session(SF)
        a.caches.compile_cache = a._jit_cache = cc
        pa = a.execute(q)
        t0, h0, p0 = retraces[0], cc.hits, cc.puts
        b = tpch_session(SF)
        b.caches.compile_cache = b._jit_cache = cc
        pb = b.execute(q)
    finally:
        L.LocalExecutor._run = orig
    assert pb.to_pylist() == pa.to_pylist()
    assert cc.hits == h0 + 1 and cc.puts == p0  # shared executable
    assert retraces[0] == t0  # ZERO re-traces in the second session


def test_compile_cache_lru_bounded():
    cc = CompileCache(max_entries=2)
    cc["a"] = {"fn": None, "cell": {}, "plan": None}
    cc["b"] = {"fn": None, "cell": {}, "plan": None}
    cc["c"] = {"fn": None, "cell": {}, "plan": None}
    assert len(cc) == 2 and cc.evictions == 1
    assert cc.get("a") is None  # oldest gone


def test_faulting_cached_executable_surfaces_without_a_recompile():
    """A runtime error from a cached executable is a real error on a
    directly attached chip: it surfaces as it is — no eviction, no
    recompile, no second dispatch."""
    # result cache off so the second execute actually runs the fragment
    s = tpch_session(SF, result_cache=False)
    cc = CompileCache()
    s.caches.compile_cache = s._jit_cache = cc
    q = "select count(*) as c from nation"
    s.execute(q)
    assert len(cc) == 1
    key = next(iter(cc._entries))
    entry = cc._entries[key]
    calls, puts = {"n": 0}, cc.puts

    def faulting(resident_prep, tile_prep):
        calls["n"] += 1
        raise jax.errors.JaxRuntimeError(
            "INVALID_ARGUMENT: executable fault (injected)"
        )

    entry["fn"] = faulting
    with pytest.raises(jax.errors.JaxRuntimeError, match="injected"):
        s.execute(q)
    assert calls["n"] == 1
    assert cc.puts == puts and cc._entries[key] is entry  # nothing evicted


@pytest.mark.parametrize("message", [
    "INVALID_ARGUMENT: injected",
    "RESOURCE_EXHAUSTED: out of memory",
    "INTERNAL: compile failed (injected)",
])
def test_runtime_errors_are_not_retried(message):
    s = tpch_session(SF)
    ex = s._executor()
    calls = {"n": 0}

    def failing(plan, scans, counts):
        calls["n"] += 1
        raise jax.errors.JaxRuntimeError(message)

    ex._run_jitted = failing
    with pytest.raises(jax.errors.JaxRuntimeError):
        ex.execute(s.plan("select count(*) as c from nation"))
    assert calls["n"] == 1  # real errors surface with their real message


def test_compile_time_oom_falls_to_streaming_tiles():
    """The one runtime error with a remedy: XLA's compile-time "Ran out
    of memory" proves the monolithic program cannot fit, so the plan is
    offered to the streaming tiled executor (once)."""
    s = tpch_session(SF)
    ex = s._executor()
    tried = {"n": 0}

    def oom(plan, scans, counts):
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
            "of memory in memory space hbm."
        )

    def streaming(plan):
        tried["n"] += 1
        return None  # untileable: the ORIGINAL error must surface

    ex._run_jitted = oom
    ex._try_forced_streaming = streaming
    with pytest.raises(jax.errors.JaxRuntimeError, match="Ran out of memory"):
        ex.execute(s.plan("select count(*) as c from nation"))
    assert tried["n"] == 1


def test_compile_cache_persistent_second_process(tmp_path):
    """A second process seeing the same (fingerprint, shape-bucket) pair
    loads the executable from jax's persistent compilation cache (zero XLA
    recompiles) and records the reuse in the shared index."""
    script = (
        "import json, trino_tpu\n"
        "trino_tpu.force_cpu(2)\n"
        "from trino_tpu.session import tpch_session\n"
        "from trino_tpu.cache.compile_cache import shared_compile_cache\n"
        f"s = tpch_session({SF}, compile_cache_dir={str(tmp_path)!r})\n"
        "s.execute('select count(*) as c from nation')\n"
        "print(json.dumps(shared_compile_cache().stats()))\n"
    )
    # the executables go where the environment puts jax's cache; the
    # session-named directory holds only the fragment index
    xla_dir = tmp_path / "xla"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(xla_dir),
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    stats = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", script], env=env,
            capture_output=True, text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        stats.append(json.loads(out.stdout.strip().splitlines()[-1]))
    assert stats[0]["persistent_hits"] == 0  # first process: cold disk
    assert stats[1]["persistent_hits"] >= 1  # second: compiled-by-peer
    assert (tmp_path / "index.json").exists()
    assert any(n.endswith("-cache") for n in os.listdir(xla_dir))
    assert not any(n.endswith("-cache") for n in os.listdir(tmp_path))


# --- observability -------------------------------------------------------


def test_system_runtime_caches_schema():
    s = tpch_session(SF)
    page = s.execute("select * from system.runtime.caches")
    assert page.names == [
        "name", "hits", "misses", "puts", "evictions", "entries",
        "bytes", "max_bytes", "heals", "invalidations",
    ]
    names = {r[0] for r in page.to_pylist()}
    assert {"result_cache", "compile_cache", "scan_cache"} <= names


def test_cache_http_endpoint():
    from trino_tpu.server.coordinator import CoordinatorServer

    srv = CoordinatorServer(tpch_session(SF)).start()
    try:
        with urllib.request.urlopen(f"{srv.uri}/v1/cache", timeout=10) as r:
            doc = json.load(r)
        names = {c["name"] for c in doc["caches"]}
        assert {"result_cache", "compile_cache", "scan_cache"} <= names
        for c in doc["caches"]:
            assert "hits" in c and "misses" in c
    finally:
        srv.stop()


def test_cache_events_emitted():
    from trino_tpu.utils.events import CacheEvent, EventListener

    seen = []

    class L(EventListener):
        def cache_event(self, event):
            seen.append(event)

    s = tpch_session(SF)
    s.events.add(L())
    s.execute(Q6)
    s.execute(Q6)
    ops = [(e.tier, e.op) for e in seen]
    assert ("result", "miss") in ops
    assert ("result", "put") in ops
    assert ("result", "hit") in ops
    assert all(isinstance(e, CacheEvent) for e in seen)
