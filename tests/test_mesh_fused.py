"""Mesh-as-first-class-target suite: shard-mapped megakernels, collective
exchanges in the HLO, hash-partitioned memory headroom, and supervised
recovery when a device drops out of the mesh mid-query.

Reference parity: the distributed engine suites run every query on a
multi-worker runner and require results identical to single-node
execution.  Here the 8-virtual-device CPU mesh stands in for an 8-chip
TPU slice; every mesh result must match the LOCAL executor byte-for-byte
(floats to merge-order ulps) and, transitively, the sqlite oracle."""
import json
import re
import sqlite3

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oracle import (
    assert_q1_fuses_past_the_int64_gate, assert_rows_match, load_tpch,
)
from tpch_sql import QUERIES, oracle_dialect
from trino_tpu.obs import journal
from trino_tpu.ops import sketches
from trino_tpu.parallel import mesh_executor as MX
from trino_tpu.runtime.supervisor import QUARANTINED
from trino_tpu.session import tpch_session

SF = 0.001
Q1 = QUERIES[1][0]
Q3 = QUERIES[3][0]
Q6 = QUERIES[6][0]

DISTINCT_SQL = (
    "select o_orderpriority, count(distinct o_custkey) from orders "
    "group by o_orderpriority order by o_orderpriority"
)


@pytest.fixture(scope="module")
def oracle_conn():
    conn = sqlite3.connect(":memory:")
    load_tpch(conn, SF, ["lineitem", "orders", "customer"])
    return conn


def _mesh_session(**props):
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    return tpch_session(
        SF, distributed=True, num_devices=8, result_cache=False, **props
    )


def _megakernels(prof):
    return [
        k for k in (prof or {}).get("kernels", ())
        if k.get("mode") == "megakernel"
    ]


# --- fused shard bodies: mesh vs local vs oracle --------------------------


def test_q6_mesh_fused_parity_and_oracle(oracle_conn):
    on = _mesh_session(megakernels="on")
    off = tpch_session(SF, megakernels="off", result_cache=False)
    a = on.execute(Q6)
    prof = on.last_kernel_profile
    # the fused body ran INSIDE the shard-mapped fragment, once
    assert prof["fusedAggregates"] == 1
    mk = _megakernels(prof)
    assert mk and mk[0]["digest"].startswith("mesh:8/megakernel:lineitem/")
    # every mesh record carries the axis-size tag for the flight recorder
    assert all(
        k["digest"].startswith("mesh:8/") for k in prof["kernels"]
    ), prof["kernels"]
    b = off.execute(Q6)
    assert a.to_pylist() == b.to_pylist()
    expected = oracle_conn.execute(oracle_dialect(Q6)).fetchall()
    assert_rows_match(a.to_pylist(), expected, tol=2e-2, ordered=True)


def test_q1_mesh_fused_parity_and_oracle(oracle_conn):
    """Grouped fusion: per-shard mixed-radix accumulators merge across
    the mesh via all_gather + local sum (integer planes, so the merge is
    EXACT and the avg = sum/count division is bit-identical)."""
    on = _mesh_session(megakernels="on")
    off = tpch_session(SF, megakernels="off", result_cache=False)
    a = on.execute(Q1)
    prof = on.last_kernel_profile
    assert prof["fusedAggregates"] == 1
    mk = _megakernels(prof)
    assert mk and mk[0]["digest"].startswith("mesh:8/megakernel:")
    b = off.execute(Q1)
    assert a.to_pylist() == b.to_pylist()
    expected = oracle_conn.execute(oracle_dialect(Q1)).fetchall()
    assert_rows_match(a.to_pylist(), expected, tol=2e-2, ordered=True)


def test_q1_mesh_fuses_past_the_int64_gate(monkeypatch):
    """Four shards: what crosses the mesh is each term's sum, so the
    shard bodies fuse although sum_charge's TABLE-wide bound is past the
    (patched) int64 gate; the chunks are cut after the merge."""
    prof = assert_q1_fuses_past_the_int64_gate(
        monkeypatch,
        lambda sf, **props: tpch_session(
            sf, distributed=True, num_devices=4, **props),
    )
    mk = _megakernels(prof)
    assert mk and mk[0]["digest"].startswith("mesh:4/megakernel:")


def test_q3_mesh_parity_and_oracle(oracle_conn):
    mesh = _mesh_session()
    local = tpch_session(SF, result_cache=False)
    a = mesh.execute(Q3).to_pylist()
    assert a == local.execute(Q3).to_pylist()
    expected = oracle_conn.execute(oracle_dialect(Q3)).fetchall()
    assert_rows_match(a, expected, tol=2e-2, ordered=True)


# --- the compiled exchange: collectives must be in the HLO ----------------


def _capture_hlo(run):
    """Spy on the mesh executor's ahead-of-time compile and return the
    compiled HLO text of every mesh program `run` triggers (sessions
    with compile_cache=False: an executable another test already put in
    the process-wide cache must not stand in for the compile)."""
    texts = []
    orig = MX.MeshExecutor._compile_fragment

    def spy(fn, *args):
        compiled = orig(fn, *args)
        texts.append(compiled.as_text())
        return compiled

    MX.MeshExecutor._compile_fragment = staticmethod(spy)
    try:
        run()
    finally:
        MX.MeshExecutor._compile_fragment = staticmethod(orig)
    return texts


def test_mesh_fused_q6_hlo_shows_all_gather():
    texts = _capture_hlo(
        lambda: _mesh_session(
            megakernels="on", compile_cache=False
        ).execute(Q6)
    )
    merged = [t for t in texts if "all-gather" in t]
    # the fused fragment merges per-shard partials with a tiled
    # all_gather before the shared finish tail — it must survive into
    # the compiled SPMD module, not get optimized into a local reshape
    assert merged, "no all-gather in any compiled mesh module"


def test_mesh_repartition_hlo_shows_device_side_all_to_all():
    texts = _capture_hlo(
        lambda: _mesh_session(compile_cache=False).execute(DISTINCT_SQL)
    )
    assert texts, "no mesh program was compiled"
    ops = set()
    for t in texts:
        ops |= set(re.findall(
            r"\b(all-gather|all-to-all|infeed|outfeed|host-transfer)", t
        ))
    # the hash repartition compiles to a real device-side exchange: an
    # all-to-all inside the SPMD module (its per-destination chunks are
    # carved by whatever slicing this XLA prints), gathered at the root
    # by an all-gather — and no host round trip in between
    assert "all-to-all" in ops, ops
    assert "all-gather" in ops, ops
    assert not ops & {"infeed", "outfeed", "host-transfer"}, ops


# --- HLL pmax merge -------------------------------------------------------


def test_hll_pmax_merge_is_registerwise_max():
    """The cross-device HLL union must be the ELEMENTWISE register max.
    A pmax over the packed int64 words compares 8-register
    concatenations lexicographically — provably wrong on this data —
    so the merge must unpack, pmax, repack."""
    ndev, cap = 4, 3
    mesh = MX.default_mesh(ndev)
    rng = np.random.default_rng(7)
    regs = rng.integers(
        0, 56, size=(ndev, cap, sketches.HLL_M)
    ).astype(np.int64)

    def body(r):
        lanes = sketches._pack(jnp.asarray(r[0]))
        merged = sketches.hll_pmax_merge(lanes, cap, MX.AXIS)
        out = jnp.stack(
            [merged[i] for i in range(sketches.HLL_LANES)], axis=1
        )
        return out[None]

    fn = MX._shard_map(
        body, mesh, (MX.P_(MX.AXIS),), MX.P_(MX.AXIS)
    )
    out = np.asarray(fn(jnp.asarray(regs)))  # [ndev, cap, HLL_LANES]
    expect = regs.max(axis=0)  # [cap, HLL_M] elementwise union
    for d in range(ndev):
        lanes = {
            i: jnp.asarray(out[d, :, i])
            for i in range(sketches.HLL_LANES)
        }
        got = np.asarray(sketches._unpack(lanes, cap))
        assert (got == expect).all(), f"device {d} diverged from union"

    # sanity: the tempting packed-word max really is a different answer
    packed = [sketches._pack(jnp.asarray(regs[d])) for d in range(ndev)]
    word_max = {
        i: np.max([np.asarray(p[i]) for p in packed], axis=0)
        for i in range(sketches.HLL_LANES)
    }
    wrong = np.asarray(sketches._unpack(
        {i: jnp.asarray(word_max[i]) for i in word_max}, cap
    ))
    assert (wrong != expect).any(), "seed no longer distinguishes the bug"


def test_approx_distinct_global_mesh_matches_local():
    mesh = _mesh_session()
    local = tpch_session(SF, result_cache=False)
    sql = "select approx_distinct(o_custkey) from orders"
    assert mesh.execute(sql).to_pylist() == local.execute(sql).to_pylist()


# --- hash-partitioned memory headroom -------------------------------------


def test_grouped_count_distinct_repartitions_not_gathers():
    """count(DISTINCT) beyond one shard's memory: the mesh path must
    hash-repartition on the group keys (each shard deduplicates its own
    key range) instead of gathering raw rows to every device."""
    calls = []
    orig = MX._MeshTraceCtx._hash_repartition

    def spy(self, b, keys):
        calls.append(keys)
        return orig(self, b, keys)

    MX._MeshTraceCtx._hash_repartition = spy
    try:
        # the spy sees a trace, and a cached executable needs none
        mesh = _mesh_session(compile_cache=False)
        got = mesh.execute(DISTINCT_SQL).to_pylist()
    finally:
        MX._MeshTraceCtx._hash_repartition = orig
    local = tpch_session(SF, result_cache=False)
    assert got == local.execute(DISTINCT_SQL).to_pylist()
    assert calls, "grouped DISTINCT did not take the repartition path"


def test_q3_partitioned_join_exceeds_broadcast_budget(oracle_conn):
    """Q3-shaped scale proxy: with the broadcast budget forced below the
    build side, every join must take the 8-way hash-partitioned path
    (each shard holds 1/8th of the build) and still match the oracle."""
    mesh = _mesh_session(broadcast_join_threshold_rows=1)
    local = tpch_session(SF, result_cache=False)
    a = mesh.execute(Q3).to_pylist()
    assert a == local.execute(Q3).to_pylist()
    expected = oracle_conn.execute(oracle_dialect(Q3)).fetchall()
    assert_rows_match(a, expected, tol=2e-2, ordered=True)


# --- supervised dispatch: mid-mesh device loss ----------------------------


def test_device_loss_mid_mesh_shrinks_and_recovers(oracle_conn):
    """Seeded device_loss at the first mesh fragment: the query must
    finish CORRECTLY on the 7 healthy shards (no CPU fallback), the
    dead device must be quarantined, the shrink journaled, and the
    doctor must cite it below the device fault root cause."""
    spec = json.dumps({"device_loss": {"nth": 1, "match": "mesh:"}})
    s = _mesh_session(
        fault_injection=spec,
        device_probe_backoff_s=30.0,  # park re-probes: observable state
        query_doctor=True,
    )
    page = s.execute(Q6)
    expected = oracle_conn.execute(oracle_dialect(Q6)).fetchall()
    assert_rows_match(page.to_pylist(), expected, tol=2e-2, ordered=True)

    assert s.last_kernel_profile.get("meshShrinks", 0) >= 1
    sup = s.device_supervisor
    assert sup.device_state(device_id=0) == QUARANTINED
    # the shrink-retry succeeded on-device: degraded CPU mode never ran
    assert sup.fallback_completed == 0

    evs = [
        e for e in journal.get_journal().tail(200)
        if e.get("eventType") == journal.MESH_SHRINK
    ]
    assert evs, "mesh shrink left no journal event"
    detail = evs[-1].get("detail") or {}
    assert detail.get("fromSize") == 8 and detail.get("toSize") == 7
    assert detail.get("deviceState") == QUARANTINED

    diag = s.last_diagnosis
    codes = [f.get("code") for f in (diag or {}).get("findings", ())]
    assert "mesh_shrink" in codes
    # precedence: the fault is the root cause, the shrink its effect
    assert codes.index("device_fault") < codes.index("mesh_shrink")


def test_doctor_rule_precedence_mesh_shrink():
    from trino_tpu.obs import doctor

    names = [r.__name__ for r in doctor._RULES]
    assert (
        names.index("_rule_node_churn")
        < names.index("_rule_mesh_shrink")
        < names.index("_rule_memory_pressure")
    )


# --- per-shard task rollups in the timeline -------------------------------


def test_mesh_timeline_has_per_shard_tasks():
    s = _mesh_session(operator_stats=True)
    s.execute(Q6)
    tl = s.last_timeline
    assert tl and tl.get("stages")
    tasks = [t for st in tl["stages"] for t in st["tasks"]]
    assert len(tasks) == 8
    assert {t["nodeId"] for t in tasks} == {
        "device-%d" % d for d in range(8)
    }
    assert all(t["wallS"] >= 0.0 for t in tasks)
    assert sum(t["outputRows"] for t in tasks) > 0
