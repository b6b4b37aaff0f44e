"""Serving-observatory acceptance: the signature census rolls up what
the coordinator finalizes, the two-segment store survives restarts and
torn tails, history backfill fills gaps without double counting, SLO
burns journal throttled events the doctor ranks below overload, and the
census/affinity/SLO surfaces answer over SQL and HTTP.
"""
import json
import urllib.request

import pytest

from trino_tpu.obs import compile_observatory as co
from trino_tpu.obs import doctor, journal
from trino_tpu.obs import serving_observatory as so
from trino_tpu.session import tpch_session

TPCH = (("tpch", "tpch", {"tpch.scale-factor": 0.01}),)


@pytest.fixture(autouse=True)
def _fresh_state():
    """Each scenario gets clean process-global ledgers: the serving
    observatory is fed by coordinator finalize, the doctor windows over
    the journal, so bleed-through would flip counts and causes."""
    so._reset_observatory()
    co._reset_observatory()
    journal._reset_journal()
    doctor._reset_diagnoses()
    yield
    so._reset_observatory()
    co._reset_observatory()
    journal._reset_journal()
    doctor._reset_diagnoses()


# --- units: census rollup math -------------------------------------------


def test_census_rollup_matches_hand_computation():
    c = so.SignatureCensus()
    feed = (
        ("q0", 2.0, True, "f1", "a"),
        ("q1", 5.0, False, "f2", "a"),
        ("q2", None, None, "f1", "b"),
    )
    for i, (qid, drift, hit, fam, tenant) in enumerate(feed):
        assert c.observe(
            "sig", tenant=tenant, query_id=qid, latency_s=0.2,
            drift_ratio=drift, cache_hit=hit, families=[fam],
            ts=1000.0 + i,
        )
    # replaying an already-seen query id folds nothing (the property
    # that makes disk merge + history backfill idempotent)
    assert not c.observe("sig", query_id="q0", latency_s=99.0, ts=2000.0)
    (row,) = c.rows()
    assert row["count"] == 3
    assert row["tenant"] == "a"  # dominant tenant of the signature
    assert row["driftRatio"] == 5.0  # max observed; None never shrinks it
    assert row["cacheHits"] == 1 and row["cacheMisses"] == 1
    assert row["families"] == ["f1", "f2"]
    assert row["lastTs"] == 1002.0
    # 1 s cadence: the EWMA of two 1 s intervals is 1 s -> 1 query/s
    assert row["ratePerS"] == pytest.approx(1.0)
    # every latency was 0.2 s: the interpolated quantiles stay inside
    # the containing fixed bucket and keep their order
    assert 0.1 <= row["p50S"] <= row["p95S"] <= row["p99S"] <= 0.5


def test_census_bounds_signatures_with_overflow_bucket():
    """Past max_signatures, new shapes fold into one __other__ bucket:
    an adversarial stream of unique queries cannot grow the census."""
    c = so.SignatureCensus(max_signatures=2)
    for i, sig in enumerate(("s1", "s2", "s3", "s4")):
        c.observe(sig, query_id=f"q{i}", ts=1000.0 + i)
    rows = {r["signature"]: r for r in c.rows()}
    assert set(rows) == {"s1", "s2", so.OTHER_KEY}
    assert rows[so.OTHER_KEY]["count"] == 2


# --- durability: restart merge, torn tail, history backfill --------------


def test_store_survives_restart_and_torn_tail(tmp_path):
    """A new observatory (new pid suffix) merges the old writer's
    surviving segments; a torn trailing line parses to nothing, never
    to an error — the kill -9 contract shared with the journal."""
    a = so.ServingObservatory(str(tmp_path), name="a")
    for i in range(6):
        a.observe_query(
            signature="sig-%d" % (i % 2), tenant="t",
            query_id="q%d" % i, latency_s=0.1, families=["fam"],
            ts=1000.0 + i, quiet=True,
        )
    a.sync()
    seg = a._segments[a._active]
    torn_at, torn_path = seg.offset, seg.path
    a.close()
    with open(torn_path, "r+b") as f:
        f.seek(torn_at)
        f.write(b'{"signature": "sig-torn", "queryId": "q-to')
    b = so.ServingObservatory(str(tmp_path), name="b")
    rows = {r["signature"]: r for r in b.signature_rows()}
    assert set(rows) == {"sig-0", "sig-1"}
    assert rows["sig-0"]["count"] == 3 and rows["sig-1"]["count"] == 3
    # the merged census keeps counting: fresh queries fold in, replays
    # of pre-restart ids do not
    b.observe_query(signature="sig-0", query_id="q6", ts=1010.0,
                    quiet=True)
    b.observe_query(signature="sig-0", query_id="q0", ts=1011.0,
                    quiet=True)
    assert {r["signature"]: r["count"] for r in b.signature_rows()}[
        "sig-0"
    ] == 4
    recs = so.read_observatory_dir(str(tmp_path))
    assert {r["queryId"] for r in recs} >= {"q%d" % i for i in range(6)}
    assert not any(r["signature"] == "sig-torn" for r in recs)
    b.close()


def test_backfill_from_history_fills_gaps_without_double_count():
    obs = so.ServingObservatory(None)
    obs.observe_query(signature="sig-live", query_id="q-live",
                      latency_s=0.1, ts=1000.0, quiet=True)
    n = obs.backfill_from_history([
        # already observed live: skipped
        {"state": "FINISHED", "queryId": "q-live",
         "planSignature": "sig-live", "wallS": 0.1, "finished": 1000.0},
        # the gap the backfill exists for: a pre-restart query
        {"state": "FINISHED", "queryId": "q-old",
         "planSignature": "sig-old", "wallS": 0.4, "finished": 900.0},
        # still running / unsigned records never qualify
        {"state": "RUNNING", "queryId": "q-run",
         "planSignature": "sig-x", "wallS": 0.4},
        {"state": "FINISHED", "queryId": "q-nosig",
         "planSignature": "", "wallS": 0.4},
    ])
    assert n == 1
    rows = {r["signature"]: r["count"] for r in obs.signature_rows()}
    assert rows == {"sig-live": 1, "sig-old": 1}


# --- SLO burn rate -> journal -> doctor ----------------------------------


def test_slo_burn_journals_throttled_and_doctor_ranks_below_overload():
    """Six straight violations at 1 s cadence under a 5 s fast window
    burn at 20x (every query violates, budget 5%): one throttled
    SLO_BURN per window, a doctor verdict naming slo_burn — and when
    shed pressure explains the burn, overload wins the verdict."""
    mon = so.SloMonitor(
        latency_target_s=0.01, error_budget=0.05,
        fast_window_s=5.0, slow_window_s=50.0, burn_threshold=2.0,
    )
    ids = [
        ev for i in range(6)
        if (ev := mon.observe("interactive", 1.0, query_id="q-slo",
                              ts=1000.0 + i)) is not None
    ]
    assert len(ids) == 2, "one SLO_BURN per fast window per tenant"
    burns = [e for e in journal.get_journal().tail()
             if e["eventType"] == journal.SLO_BURN]
    assert [e["eventId"] for e in burns] == ids
    assert burns[0]["detail"]["tenant"] == "interactive"
    assert burns[0]["detail"]["burnRate"] > 2.0
    (row,) = mon.rows(now=1006.0)
    assert row["violationsTotal"] == 6 and row["observedTotal"] == 6
    assert row["burnEvents"] == 2
    assert row["peakFastBurn"] == pytest.approx(20.0)
    d = doctor.diagnose("q-slo", journal.get_journal().tail())
    assert d["verdict"] == doctor.ROOT_CAUSE
    assert d["rootCause"] == "slo_burn"
    assert ids[0] in d["eventIds"]
    events = list(journal.get_journal().tail())
    events.append({
        "eventId": 999, "eventType": journal.QUERY_SHED,
        "queryId": "q-slo", "taskId": "", "nodeId": "",
        "severity": "warn", "detail": {}, "ts": 1006.0,
    })
    d2 = doctor.diagnose("q-slo", events)
    assert d2["rootCause"] == "overload"
    codes = [f["code"] for f in d2["findings"]]
    assert "slo_burn" in codes
    assert codes.index("overload") < codes.index("slo_burn")


def test_per_tenant_objectives_override_defaults():
    mon = so.SloMonitor(latency_target_s=0.01, error_budget=0.05,
                        fast_window_s=5.0, slow_window_s=50.0)
    mon.set_objective("batch", latency_target_s=10.0, error_budget=0.5)
    assert mon.observe("batch", 1.0, ts=1000.0) is None
    rows = {r["tenant"]: r for r in mon.rows(now=1000.0)}
    assert rows["batch"]["violationsTotal"] == 0
    assert rows["batch"]["latencyTargetS"] == 10.0
    assert rows["batch"]["errorBudget"] == 0.5


# --- surfaces: SQL tables, coordinator feed, HTTP ------------------------


def test_observatory_tables_answer_from_sql():
    obs = so.get_observatory()
    obs.observe_query(
        signature="sig-sql", tenant="etl", query_id="q1", latency_s=0.2,
        cache_hit=True, cache_stored=True, families=["famX"],
        node_id="node-1", ts=1000.0, quiet=True,
    )
    s = tpch_session(0.001)
    rows = s.execute(
        "select signature, tenant, count, cache_hits "
        "from system.runtime.plan_signatures"
    ).to_pylist()
    assert ("sig-sql", "etl", 1, 1) in [tuple(r) for r in rows]
    slos = s.execute(
        "select tenant, observed_total, violations_total "
        "from system.runtime.slos"
    ).to_pylist()
    assert ("etl", 1, 0) in [tuple(r) for r in slos]
    # node-1 holds the signature's result-cache entry: an affinity row
    # with the full cache bonus even with zero compile warmth
    aff = s.execute(
        "select signature, node_id, result_cache, score "
        "from system.runtime.signature_affinity"
    ).to_pylist()
    assert ("sig-sql", "node-1", 1, 1.0) in [tuple(r) for r in aff]
    # round 19 history columns exist even before any coordinator ran
    s.execute(
        "select tenant, plan_signature from system.runtime.completed_queries"
    ).to_pylist()


def test_coordinator_feeds_census_slo_and_http_surfaces():
    """End to end through the real protocol: finalize feeds the census
    and the tenant's SLO (objective declared on the resource-group
    spec), history carries the signature for backfill, and the three
    HTTP routes answer."""
    from trino_tpu.testing import DistributedQueryRunner

    with DistributedQueryRunner(
        workers=1, catalogs=TPCH,
        resource_groups={
            "groups": [{
                "name": "serve", "hardConcurrencyLimit": 10,
                "maxQueued": 100,
                "sloLatencyTargetS": 30.0, "sloErrorBudget": 0.5,
            }],
            "selectors": [{"user": ".*", "group": "serve"}],
        },
    ) as runner:
        for _ in range(2):
            runner.execute("select count(*) from lineitem")
        coord = runner.coordinator.coordinator
        obs = so.get_observatory()
        assert obs.slo.objective("serve") == (30.0, 0.5)
        slo_rows = {r["tenant"]: r for r in obs.slo_rows()}
        assert slo_rows["serve"]["observedTotal"] >= 2
        assert slo_rows["serve"]["violationsTotal"] == 0
        # history carries what the backfill eats after a restart; the
        # census may also hold signatures backfilled from older runs,
        # so anchor on this session's own record rather than rows[0]
        recs = runner.session.history.completed()
        signed = [r for r in recs if r.get("planSignature")]
        assert signed and signed[-1]["tenant"] == "serve"
        sig = signed[-1]["planSignature"]
        by_sig = {r["signature"]: r for r in obs.signature_rows()}
        assert sig in by_sig and by_sig[sig]["count"] >= 2, by_sig
        for path, key in (("/v1/signatures", "signatures"),
                          ("/v1/affinity", "affinity"),
                          ("/v1/slo", "slos")):
            with urllib.request.urlopen(
                runner.coordinator.uri + path, timeout=5.0
            ) as resp:
                doc = json.loads(resp.read())
            assert key in doc, path
        _, srows = runner.execute(
            "select tenant, observed_total from system.runtime.slos"
        )
        assert any(r[0] == "serve" and r[1] >= 2 for r in srows)
        # in-process workers share the compile observatory, so compiled
        # warmth for the signature's families lands under the
        # coordinator's node id in the affinity map
        aff = obs.affinity_rows(local_node_id=coord.node_id)
        assert any(
            a["signature"] == sig and a["warmFamilies"] >= 1
            for a in aff
        ), aff


