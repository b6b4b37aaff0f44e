"""Fused scan->filter->aggregate megakernels + the round-5 bench run regression.

Three suites in one file because they are one feature:

1. Parity: Q1/Q6 through the fused megakernel (session prop
   ``megakernels='on'`` forces interpret mode off-TPU) must be
   byte-identical to the unfused operator pipeline AND match the sqlite
   oracle; non-fusable plans must *reject* into the unfused path with the
   reason recorded, never error.
2. Plane/limb recombination: the in-kernel accumulator is int32 (Mosaic
   pins the reduction dtype), so wide sums travel as 16-bit planes that
   recombine on the host via int64 shifts — unit tests drive
   ``fused_agg_sums`` directly at the wraparound boundaries.
3. Round-5 bench crash regression: the on-device TPC-H generator used to
   dispatch OUTSIDE supervision, so the r05 worker crash left no
   breadcrumb.  The generator now dispatches with synthetic output-lane
   shapes; a seeded device_loss at exactly that kernel must be
   attributed, quarantined, degraded to CPU, and the recorded shapes must
   replay through ``scripts/flightrec.py``.
"""
import json
import os
import sqlite3
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from oracle import (
    assert_q1_fuses_past_the_int64_gate, assert_rows_match, load_tpch,
)
from tpch_sql import QUERIES, oracle_dialect
from trino_tpu.connectors import tpch_device
from trino_tpu.ops import megakernel
from trino_tpu.ops import pallas_kernels as pk
from trino_tpu.ops import wide_decimal as wd
from trino_tpu.runtime.supervisor import QUARANTINED
from trino_tpu.session import Session, tpch_session

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "scripts")
)
import flightrec  # noqa: E402

SF = 0.001
Q1 = QUERIES[1][0]
Q6 = QUERIES[6][0]


@pytest.fixture(scope="module")
def oracle_conn():
    conn = sqlite3.connect(":memory:")
    load_tpch(conn, SF, ["lineitem"])
    return conn


def _megakernels(prof):
    return [
        k for k in (prof or {}).get("kernels", ())
        if k.get("mode") == "megakernel"
    ]


# --- fused vs unfused vs oracle parity ------------------------------------


def test_q6_fused_parity_and_oracle(oracle_conn):
    on = tpch_session(SF, megakernels="on", result_cache=False)
    off = tpch_session(SF, megakernels="off", result_cache=False)
    a = on.execute(Q6)
    prof = on.last_kernel_profile
    # Q6 fuses to one single-group dispatch: count + two product limbs
    assert prof["fusedAggregates"] == 1
    assert prof["fusedTerms"] >= 3
    mk = _megakernels(prof)
    assert mk and mk[0]["digest"].startswith("megakernel:lineitem/")
    b = off.execute(Q6)
    assert not _megakernels(off.last_kernel_profile)
    assert a.to_pylist() == b.to_pylist()
    expected = oracle_conn.execute(oracle_dialect(Q6)).fetchall()
    assert_rows_match(a.to_pylist(), expected, tol=2e-2, ordered=True)


def test_q1_fused_parity_and_oracle(oracle_conn):
    on = tpch_session(SF, megakernels="on", result_cache=False)
    off = tpch_session(SF, megakernels="off", result_cache=False)
    a = on.execute(Q1)
    prof = on.last_kernel_profile
    assert prof["fusedAggregates"] == 1
    # 4 sums (plane-split) + 3 avgs + count in one dispatch
    assert prof["fusedTerms"] >= 8
    mk = _megakernels(prof)
    # returnflag (dict dom 3) x linestatus (dict dom 2) -> mixed-radix
    # capacity (3+1)*(2+1)=12 inside the single kernel
    assert mk and mk[0]["digest"].endswith("/g12")
    b = off.execute(Q1)
    assert a.to_pylist() == b.to_pylist()
    expected = oracle_conn.execute(oracle_dialect(Q1)).fetchall()
    assert_rows_match(a.to_pylist(), expected, tol=2e-2, ordered=True)


@pytest.mark.parametrize(
    "sql, reason_frag",
    [
        # min/max are order statistics, not plane-decomposable sums
        ("select min(l_quantity), max(l_discount) from lineitem "
         "where l_shipdate < date '1995-01-01'", "min"),
        # group key without a dictionary/boolean domain: the mixed-radix
        # group id cannot be bounded by MAX_GROUPS
        ("select l_suppkey, sum(l_quantity) from lineitem "
         "group by l_suppkey order by l_suppkey limit 5",
         "low-cardinality"),
    ],
)
def test_non_fusable_rejects_into_unfused_path(sql, reason_frag):
    on = tpch_session(SF, megakernels="on", result_cache=False)
    off = tpch_session(SF, megakernels="off", result_cache=False)
    a = on.execute(sql)
    prof = on.last_kernel_profile
    assert prof.get("fusedAggregates") is None
    assert prof["fusionRejects"] >= 1
    assert reason_frag in prof["lastFusionReject"]
    assert a.to_pylist() == off.execute(sql).to_pylist()


def test_q1_fuses_past_the_int64_gate(monkeypatch):
    """sum_charge's table-wide bound trips the (patched) int64 gate; a
    wide accumulator recombines in chunks and needs no such proof."""
    prof = assert_q1_fuses_past_the_int64_gate(monkeypatch, tpch_session)
    assert _megakernels(prof)[0]["digest"].endswith("/t11/g12")


def test_narrow_sum_keeps_the_int64_gate(monkeypatch):
    """An integer sum ships a narrow $val that merges downstream by
    int64 addition: for it the table-wide proof and its reject stay."""
    monkeypatch.setattr(megakernel, "SUM_GATE", 2 ** 40)
    sql = "select sum(l_orderkey * l_partkey), count(*) from lineitem"
    on = tpch_session(0.01, megakernels="on", result_cache=False,
                      compile_cache=False)
    off = tpch_session(0.01, megakernels="off", result_cache=False)
    a = on.execute(sql)
    prof = on.last_kernel_profile
    assert prof.get("fusedAggregates") is None
    assert prof.get("fusedSumsPastInt64") is None
    assert prof["fusionRejects"] >= 1
    assert prof["lastFusionReject"] == "table-wide sum could exceed int64"
    assert a.to_pylist() == off.execute(sql).to_pylist()


def test_megakernels_auto_is_off_without_tpu():
    """'auto' must not drag interpret-mode fusion into CPU runs: fusion
    only pays when the pallas TPU path is live."""
    s = tpch_session(SF, result_cache=False)  # default: auto
    s.execute(Q6)
    prof = s.last_kernel_profile
    if not pk.enabled():
        assert not _megakernels(prof)


def test_megakernels_prop_validated():
    from trino_tpu.config import SessionProperties

    p = SessionProperties()
    for v in ("auto", "on", "off"):
        p.set("megakernels", v)
        assert p.get("megakernels") == v
    with pytest.raises(ValueError):
        p.set("megakernels", "sometimes")
    assert p.get("donate_pages") is True


# --- plane/limb recombination at the wraparound boundaries ----------------


def _total(sums, shifts):
    return sum(int(s) << sh for s, sh in zip(np.asarray(sums)[:, 0], shifts))


def test_plane_recombination_exceeds_int32():
    """Sum ~5k values of ~2^30 each: the true total (~2.7e12) overflows
    the in-kernel int32 accumulator many times over, so only correct
    16-bit plane splitting + int64 host recombination can match numpy."""
    rng = np.random.default_rng(7)
    vals = rng.integers(0, 2**30, size=5000, dtype=np.int64)
    cols = {"v": jnp.asarray(vals.astype(np.int32))}
    live = jnp.ones(5000, dtype=bool)

    def emit(t):
        v = t["v"]
        return None, None, [v & 0xFFFF, v >> 16]

    sums = pk.fused_agg_sums(cols, live, emit, 2, 1, interpret=True)
    assert _total(sums, (0, 16)) == int(vals.sum())


def test_plane_recombination_all_lanes_saturated():
    """Every row at the lo-plane maximum (0xFFFF): the per-chunk plane
    sum hits 2048*65535 = 134,215,680 — the designed-for worst case,
    still under 2^31 with no headroom wasted."""
    n = 4096
    vals = np.full(n, (1 << 30) - 1, dtype=np.int64)  # lo plane = 0xFFFF
    cols = {"v": jnp.asarray(vals.astype(np.int32))}

    def emit(t):
        return None, None, [t["v"] & 0xFFFF, t["v"] >> 16]

    sums = pk.fused_agg_sums(
        cols, jnp.ones(n, dtype=bool), emit, 2, 1, interpret=True
    )
    assert _total(sums, (0, 16)) == int(vals.sum())


def test_limb_split_product_recombination():
    """The Q6 shape: sum(a*b) where a (extendedprice cents, up to ~10.5M)
    splits into 16-bit limbs against a short factor b <= 32767; each limb
    product then plane-splits again so no per-chunk partial exceeds
    int32.  Recombined total must equal the exact int64 product sum."""
    rng = np.random.default_rng(11)
    n = 3000
    a = rng.integers(90_000, 10_495_001, size=n, dtype=np.int64)
    b = rng.integers(0, 32_768, size=n, dtype=np.int64)
    cols = {
        "a": jnp.asarray(a.astype(np.int32)),
        "b": jnp.asarray(b.astype(np.int32)),
    }

    def emit(t):
        p_lo = (t["a"] & 0xFFFF) * t["b"]   # <= 0xFFFF * 32767 < 2^31
        p_hi = (t["a"] >> 16) * t["b"]
        return None, None, [
            p_lo & 0xFFFF, p_lo >> 16, p_hi & 0xFFFF, p_hi >> 16,
        ]

    sums = pk.fused_agg_sums(
        cols, jnp.ones(n, dtype=bool), emit, 4, 1, interpret=True
    )
    assert _total(sums, (0, 16, 16, 32)) == int((a * b).sum())


def _chunks_of(total: int):
    return [total & 0xFFFFFFFF, (total >> 32) & 0xFFFFFFFF,
            (total >> 64) & 0xFFFFFFFF, total >> 96]


@pytest.mark.parametrize("sign", ["positive", "negative", "mixed"])
@pytest.mark.parametrize(
    "shifts", [(0,), (0, 16), (0, 16, 16, 32), (0, 16, 48)],
    ids=lambda s: "sh" + "_".join(map(str, s)),
)
def test_term_sums_recombine_into_chunks_exactly(shifts, sign):
    """The recombination alone: term sums of up to 2^46 in magnitude go
    straight into the wide accumulator's four chunk lanes and equal the
    python big-integer total's canonical chunks, for totals below 2^63
    (shifts up to 16) and far past it (2^46 << 32 and << 48)."""
    rng = np.random.default_rng(len(shifts) * 7 + len(sign))
    groups = 96
    mags = rng.integers(0, 2 ** 46, size=(len(shifts), groups),
                        dtype=np.int64)
    # the boundaries: the largest magnitude, zero, and both sides of
    # the 32-bit cut that the recombination makes in every term sum
    mags[:, 0] = 2 ** 46
    mags[:, 1] = 0
    mags[:, 2] = 2 ** 32 - 1
    mags[:, 3] = 2 ** 32
    mags[:, 4] = 1
    if sign == "positive":
        sums = mags
    elif sign == "negative":
        sums = -mags
    else:
        sums = mags * rng.choice(np.array([-1, 1]), size=mags.shape)
    got = np.asarray(wd.shifted_sum_chunks(
        [jnp.asarray(row) for row in sums], shifts))
    totals = [
        sum(int(sums[t, g]) << sh for t, sh in enumerate(shifts))
        for g in range(groups)
    ]
    # both sides of int64: 2^46 << 16 stays inside, << 32 and << 48 not
    assert (max(abs(t) for t in totals) > 2 ** 63) == (max(shifts) >= 32)
    for g, total in enumerate(totals):
        assert [int(c) for c in got[:, g]] == _chunks_of(total), (g, total)
    # and the chunks are what the wide lane reads back as 128 bits
    lo, hi = wd.limbs(wd.chunks_to_wide(list(jnp.asarray(got))))
    for g, total in enumerate(totals):
        assert (int(hi[g]) << 64) | (int(lo[g]) & (2 ** 64 - 1)) == total


@pytest.mark.parametrize(
    "rows, shifts, reason",
    [
        # Q1 SF10's sum_charge: four terms, proven
        (59_998_443, (0, 16, 16, 32), None),
        # a hundred Q1s' worth of terms at SF1000 still prove
        (6_000_000_000, (0, 16, 16, 32) * 100, None),
        # a term whose own int64 sum could wrap
        (2 ** 44, (0,), "one term"),
        # a shift that would index past the fourth chunk
        (1000, (0, 96), "leaves the 128-bit"),
        # more 31-bit-shifted high parts than one int64 lane holds
        (2 ** 42, (31,) * 8, "chunk lanes"),
    ],
)
def test_chunk_lane_proof(rows, shifts, reason):
    if reason is None:
        megakernel._prove_chunk_lanes(rows, shifts)
        return
    with pytest.raises(megakernel.Reject, match=reason):
        megakernel._prove_chunk_lanes(rows, shifts)


def test_fused_agg_sums_grouped_with_selection():
    """Grouped path: mixed-radix group ids, dead lanes masked out, the
    count term and value sums both land in the right group slot."""
    rng = np.random.default_rng(3)
    n = 2500
    keys = rng.integers(0, 3, size=n, dtype=np.int64)
    vals = rng.integers(0, 100_000, size=n, dtype=np.int64)
    live = rng.random(n) < 0.6
    cols = {
        "k": jnp.asarray(keys.astype(np.int32)),
        "v": jnp.asarray(vals.astype(np.int32)),
    }

    def emit(t):
        ones = t["k"] * 0 + 1
        return None, t["k"], [ones, t["v"]]

    sums = np.asarray(pk.fused_agg_sums(
        cols, jnp.asarray(live), emit, 2, 3, interpret=True
    ))
    for g in range(3):
        m = live & (keys == g)
        assert int(sums[0, g]) == int(m.sum()), g
        assert int(sums[1, g]) == int(vals[m].sum()), g


def test_fused_agg_sums_predicate_masks_rows():
    n = 1000
    vals = np.arange(n, dtype=np.int64)
    cols = {"v": jnp.asarray(vals.astype(np.int32))}

    def emit(t):
        return t["v"] < 100, None, [t["v"]]

    sums = pk.fused_agg_sums(
        cols, jnp.ones(n, dtype=bool), emit, 1, 1, interpret=True
    )
    assert int(np.asarray(sums)[0, 0]) == int(vals[vals < 100].sum())


# --- the round-5 bench run: the devgen crash site, now supervised ---------------------


def test_devgen_dispatch_is_supervised_with_replayable_shapes():
    """The r05 worker crashed inside the on-device generator program —
    which dispatched OUTSIDE the supervisor, so the flight recorder was
    blind.  Regression: the generator must dispatch under supervision
    with synthetic output-lane shapes, and those recorded shapes must
    rebuild and re-execute through scripts/flightrec.replay_record (the
    CI-testable half of a crash investigation)."""
    s = Session(config={"result_cache": False})
    s.create_catalog("tpch", "tpch", {"tpch.scale-factor": SF})
    sup = s.device_supervisor
    crumbs = []
    orig = sup.dispatch

    def spy(thunk, bc, device_id=0):
        crumbs.append(bc)
        return orig(thunk, bc, device_id)

    sup.dispatch = spy
    try:
        s.execute(Q6)
    finally:
        sup.dispatch = orig
    devgen = [b for b in crumbs if b.mode == "devgen"]
    assert devgen, "generator dispatched outside supervision (r05 blind spot)"
    bc = devgen[0]
    assert bc.kernel.startswith("devgen:lineitem")
    assert bc.shapes, "no output-lane shapes recorded: replay impossible"
    for spec in bc.shapes.values():
        assert flightrec.parse_shape(spec) is not None, spec
    record = {
        "recordType": "dispatch", "seq": 1, "kernel": bc.kernel,
        "queryId": bc.query_id, "taskId": bc.task_id,
        "shapes": dict(bc.shapes),
    }
    result = flightrec.replay_record(record, backend="native")
    assert result["ok"]
    assert result["lanes"] == len(bc.shapes)
    assert result["bytes"] > 0


def test_devgen_device_loss_attributed_quarantined_healed(oracle_conn):
    """Seeded device_loss scoped to the generator kernel itself (the
    exact r05 crash site): the query must still answer correctly via
    degraded CPU execution, the breadcrumb must name the generator, and
    the devgen jit cache must be dropped so a recovered device
    recompiles fresh executables instead of reusing poisoned ones."""
    spec = json.dumps({"device_loss": {"nth": 1, "match": "devgen:"}})
    s = Session(config={
        "result_cache": False,
        "fault_injection": spec,
        "device_probe_backoff_s": 30.0,  # park re-probes: observable state
    })
    s.create_catalog("tpch", "tpch", {"tpch.scale-factor": SF})
    page = s.execute(Q6)
    expected = oracle_conn.execute(oracle_dialect(Q6)).fetchall()
    assert_rows_match(page.to_pylist(), expected, tol=2e-2, ordered=True)

    sup = s.device_supervisor
    assert sup.device_state() == QUARANTINED
    assert sup.fallback_completed >= 1
    snap = sup.snapshot()
    assert snap["devices"][0]["lastFaultKind"] == "device_loss"
    # crash attribution names the generator program, not "unknown"
    assert snap["lastBreadcrumb"]["kernel"].startswith("devgen:lineitem")
    assert snap["lastBreadcrumb"]["shapes"]
    # the faulted device's compiled generators were evicted
    assert not tpch_device._JIT_CACHE, "poisoned devgen executables kept"
