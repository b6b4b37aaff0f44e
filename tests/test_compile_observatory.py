"""Compile-observatory acceptance: every trace/compile is a ledger
event with a structured cause, the shape census survives processes and
merges across workers, the padding-ladder recommender covers the
censused traffic, and a retrace storm becomes a doctor verdict that
cites its journal events.
"""
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from trino_tpu.obs import compile_observatory as co
from trino_tpu.obs import doctor, journal
from trino_tpu.session import tpch_session

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_state():
    """Each scenario gets clean process-global ledgers: the observatory
    classifier is warm/cold stateful and the doctor windows over the
    journal, so bleed-through would flip causes."""
    co._reset_observatory()
    journal._reset_journal()
    doctor._reset_diagnoses()
    yield
    co._reset_observatory()
    journal._reset_journal()
    doctor._reset_diagnoses()


def _cold_observatory(**kw):
    """An observatory whose family cold window is zero: unit tests for
    the warm/cold taxonomy need 'warm' to mean 'seen before', without
    waiting out the concurrency grace real traffic gets."""
    kw.setdefault("family_cold_s", 0.0)
    return co.CompileObservatory(None, **kw)


# --- units: the cause taxonomy -------------------------------------------


def test_cause_taxonomy_precedence():
    obs = _cold_observatory()
    # cold family: first compile
    assert obs.classify("f1", "s1") == co.FIRST_COMPILE
    ev = obs.record(kernel="k1", family="f1", shape_sig="s1",
                    query_id="qA")
    assert ev["cause"] == co.FIRST_COMPILE
    # same shape again (any query): the trace is cached, a re-record is
    # still not a retrace
    assert obs.classify("f1", "s1", query_id="qB") == co.FIRST_COMPILE
    # new shape from the INTRODUCING query: its other task partitions
    # land moments later and are part of the first execution
    assert obs.classify("f1", "s2", query_id="qA") == co.FIRST_COMPILE
    # new shape from a different query once the family is warm: retrace
    assert obs.classify("f1", "s2", query_id="qB") == co.SHAPE_MISS
    # precedence: ladder rung > persistent load > the warm/cold
    # distinction
    assert obs.classify("f1", "s2", ladder_attempt=2,
                        query_id="qB") == co.LADDER_RUNG
    assert obs.classify("f1", "s2", ladder_attempt=2,
                        persistent=True) == co.LADDER_RUNG
    assert obs.classify("f1", "s1", persistent=True) == co.PERSISTENT_LOAD
    assert obs.counts_by_cause()[co.FIRST_COMPILE] == 1


def test_family_cold_window_absorbs_concurrent_cold_start():
    """Two identical queries racing through a cold family present their
    per-partition shapes within moments of each other: inside the cold
    window the sibling's shape is a first compile, not a retrace."""
    warm = co.CompileObservatory(None, family_cold_s=60.0)
    warm.record(kernel="k", family="f", shape_sig="sA", query_id="qA")
    assert warm.classify("f", "sB", query_id="qB") == co.FIRST_COMPILE
    cold = _cold_observatory()
    cold.record(kernel="k", family="f", shape_sig="sA", query_id="qA")
    assert cold.classify("f", "sB", query_id="qB") == co.SHAPE_MISS


def test_ingest_is_pid_guarded_and_census_replaces_per_node():
    """A same-pid announcement is this process's own ledger coming back
    around (in-process cluster) — a no-op.  A remote worker's census
    REPLACES its node slot, so re-announcing cumulative state never
    compounds the counts."""
    obs = _cold_observatory()
    obs.record(kernel="k", family="f", shape_sig="s", query_id="q1",
               scan_rows=[100])
    own = obs.announce_snapshot()
    obs.ingest("self-node", own)
    assert obs.counts_by_cause()[co.FIRST_COMPILE] == 1  # not doubled
    assert len(obs.tail()) == 1
    remote = {
        "pid": os.getpid() + 1,
        "counts": {co.SHAPE_MISS: 3},
        "compileWallS": 1.5,
        "census": {"families": {"rf": {
            "count": 4, "minRows": 10, "maxRows": 20,
            "totalRows": 60, "buckets": {"32": 4},
        }}},
        "events": [],
    }
    for _ in range(5):  # cumulative re-announcement: replace, not add
        obs.ingest("w2", remote)
    totals = obs.counts_by_cause()
    assert totals[co.SHAPE_MISS] == 3
    merged = obs.merged_census()
    assert merged.families["rf"]["count"] == 4
    assert obs.total_compile_wall_s() == pytest.approx(
        obs.compile_wall_s + 1.5)


# --- engine-level causes: capacity ladder, changed row counts ------------


def test_ladder_rung_cause_via_tiny_group_capacity():
    """A group-by overflowing a deliberately tiny capacity walks the
    execute() ladder: the retries' compiles are LADDER_RUNG events, so
    the recompile split names capacity retreat, not shape churn."""
    s = tpch_session(0.001, group_capacity=2)
    page = s.execute(
        "select l_orderkey, count(*) from lineitem group by l_orderkey"
    )
    assert len(page.to_pylist()) > 2
    causes = co.get_observatory().counts_by_cause()
    assert causes.get(co.LADDER_RUNG, 0) >= 1, causes


def test_shape_miss_cause_via_changed_row_counts():
    """The same fragment presented with a genuinely new padded bucket —
    after the family's cold window — is a SHAPE_MISS."""
    obs = co.get_observatory()
    obs._family_cold_s = 0.0  # no concurrency here; make warm immediate
    s = tpch_session(0.001, result_cache=False)
    sql = "select sum(l_extendedprice * l_discount) from lineitem"
    s.execute(sql)
    events = obs.tail()
    assert events, "first execution recorded no compile events"
    fam = events[-1]["family"]
    sig = "synthetic-new-bucket"
    assert obs.classify(fam, sig, query_id="q_other") == co.SHAPE_MISS


def test_warm_second_query_records_zero_compile_events():
    """Acceptance: a second identical query (result cache off, so it
    really executes) reuses every compiled kernel — the engine-wide
    ledger gains NOTHING."""
    s = tpch_session(0.001, result_cache=False)
    sql = ("select sum(l_extendedprice * l_discount) from lineitem "
           "where l_quantity < 24")
    r1 = s.execute(sql).to_pylist()
    obs = co.get_observatory()
    before_events = len(obs.tail())
    before_counts = dict(obs.counts_by_cause())
    assert before_events >= 1, "first execution recorded no compiles"
    r2 = s.execute(sql).to_pylist()
    assert r2 == r1
    assert len(obs.tail()) == before_events, obs.tail()[before_events:]
    assert dict(obs.counts_by_cause()) == before_counts


# --- durability: cross-process census merge, kill -9 torn tail -----------


_WORKER_CHILD = """
import sys
sys.path.insert(0, %(repo)r)
from trino_tpu.obs.compile_observatory import CompileObservatory

obs = CompileObservatory(%(dir)r, name=%(name)r, family_cold_s=0.0)
for i in range(%(n)d):
    obs.record(kernel="k-%%d" %% i, family=%(family)r,
               shape_sig="s-%%d" %% i, query_id="q-%(name)s",
               scan_rows=[%(rows)d])
obs.sync()
"""


def test_census_merges_across_two_subprocess_workers(tmp_path):
    """Two real worker processes write censuses into one directory;
    the offline reader merges them — same contract the coordinator's
    announcement ingest provides online."""
    for name, n, rows in (("w1", 3, 100), ("w2", 5, 40000)):
        script = _WORKER_CHILD % {
            "repo": REPO, "dir": str(tmp_path), "name": name,
            "n": n, "rows": rows, "family": "shared-fam",
        }
        subprocess.run([sys.executable, "-c", script], check=True,
                       timeout=60)
    census = co.read_census_dir(str(tmp_path))
    fam = census.families["shared-fam"]
    assert fam["count"] == 8
    assert fam["minRows"] == 100 and fam["maxRows"] == 40000
    events = co.read_observatory_dir(str(tmp_path))
    assert len(events) == 8
    assert {e["queryId"] for e in events} == {"q-w1", "q-w2"}


_CRASH_CHILD = """
import os, sys, time
sys.path.insert(0, %(repo)r)
from trino_tpu.obs.compile_observatory import CompileObservatory

obs = CompileObservatory(%(dir)r, name="crashed", family_cold_s=0.0)
for i in range(12):
    obs.record(kernel="k-%%d" %% i, family="fam-crash",
               shape_sig="s-%%d" %% i, query_id="q-crash",
               scan_rows=[256])
# no sync(), no close(), no atexit: MAP_SHARED dirty pages already
# belong to the page cache — signal readiness and hang for SIGKILL
print("READY", flush=True)
time.sleep(60)
"""


def test_kill9_torn_tail_readback(tmp_path):
    """SIGKILL mid-run loses nothing already recorded, and a torn
    trailing line from another writer parses to nothing, never to an
    error."""
    script = _CRASH_CHILD % {"repo": REPO, "dir": str(tmp_path)}
    proc = subprocess.Popen(
        [sys.executable, "-c", script],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        line = proc.stdout.readline()
        assert line.strip() == "READY", proc.stderr.read()
        os.kill(proc.pid, signal.SIGKILL)
    finally:
        proc.wait(timeout=30)
    with open(tmp_path / (co._FILE_PREFIX + "torn-0.jsonl"), "wb") as f:
        f.write(b'{"compileId": 99, "cause": "shape_mi')
    events = co.read_observatory_dir(str(tmp_path))
    kernels = {e["kernel"] for e in events}
    assert kernels == {"k-%d" % i for i in range(12)}
    assert all(e["cause"] == co.FIRST_COMPILE for e in events)


# --- padding-ladder recommendation ---------------------------------------


def test_recommend_ladder_on_bimodal_census():
    """A bimodal row distribution gets one rung per mode: every
    observation is covered (top rung >= the observed max) and the
    predicted waste stays near 1x because the rungs hug the modes."""
    census = co.ShapeCensus()
    for _ in range(200):
        census.observe("small-fam", 100)
    for _ in range(100):
        census.observe("big-fam", 50000)
    rec = co.recommend_ladder(census, max_rungs=4, lane=128)
    assert rec["observations"] == 300
    assert rec["ladder"][0] == 128
    assert rec["ladder"][-1] >= 50000
    assert rec["ladder"][-1] % 128 == 0
    assert sum(pr["count"] for pr in rec["perRung"]) == 300
    # both modes pad within their own rung: far better than one-size
    assert rec["wasteRatio"] < 2.0


def test_bucket_ladder_cli_reads_a_real_census_dir(tmp_path):
    obs = co.CompileObservatory(str(tmp_path), name="cli",
                                family_cold_s=0.0)
    for rows in (90, 110, 30000, 31000):
        obs.record(kernel="k", family="fam", shape_sig=str(rows),
                   query_id="q", scan_rows=[rows])
    obs.sync()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "bucket_ladder.py"),
         "--dir", str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    rec = json.loads(out.stdout)
    assert rec["observations"] == 4
    assert rec["ladder"] and rec["ladder"][-1] >= 31000
    empty = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "bucket_ladder.py"),
         "--dir", str(tmp_path / "nowhere")],
        capture_output=True, text=True, timeout=60,
    )
    assert empty.returncode == 1


# --- retrace storm -> journal -> doctor ----------------------------------


def test_retrace_storm_reaches_doctor_with_cited_events():
    """A burst of shape-miss compiles emits one RETRACE_STORM journal
    event (throttled per window), and the doctor's verdict names
    retrace_storm citing that event id."""
    obs = _cold_observatory(storm_window_s=60.0, storm_misses=3)
    obs.record(kernel="k0", family="fam", shape_sig="s0", query_id="q0")
    for i in range(1, 5):
        ev = obs.record(kernel="k%d" % i, family="fam",
                        shape_sig="s%d" % i, query_id="q_storm")
        assert ev["cause"] == co.SHAPE_MISS
    storms = [e for e in journal.get_journal().tail()
              if e["eventType"] == journal.RETRACE_STORM]
    assert len(storms) == 1, "storm emit must be throttled per window"
    assert storms[0]["detail"]["misses"] >= 3
    d = doctor.diagnose("q_storm", journal.get_journal().tail())
    assert d["verdict"] == doctor.ROOT_CAUSE
    assert d["rootCause"] == "retrace_storm"
    assert storms[0]["eventId"] in d["eventIds"]


def test_retrace_storm_ranks_below_memory_pressure():
    """An engine under memory churn re-traces as a symptom (evictions,
    capacity retreats): when both fire, pressure wins the verdict and
    the storm survives as a lower-ranked finding."""
    events = [
        {"eventId": 1, "eventType": journal.MEMORY_REVOKE,
         "queryId": "q1", "taskId": "", "nodeId": "", "severity": "warn",
         "detail": {"reason": "pool pressure"}, "ts": 1.0},
        {"eventId": 2, "eventType": journal.RETRACE_STORM,
         "queryId": "q1", "taskId": "", "nodeId": "", "severity": "warn",
         "detail": {"misses": 9, "windowS": 10.0}, "ts": 2.0},
    ]
    d = doctor.diagnose("q1", events)
    assert d["rootCause"] == "memory_pressure"
    codes = [f["code"] for f in d["findings"]]
    assert "retrace_storm" in codes
    assert codes.index("memory_pressure") < codes.index("retrace_storm")


# --- surfaces: SQL tables, EXPLAIN ANALYZE -------------------------------


def test_compiles_queryable_over_sql_and_explain_analyze():
    """system.runtime.compiles / .shape_census answer from SQL, and
    EXPLAIN ANALYZE carries the per-query Compiles section."""
    # its own jit cache: the process-wide one may already hold this
    # fragment (another test file on the same worker ran the same text),
    # and a cache hit records no compile
    s = tpch_session(0.001, compile_cache=False)
    s.execute("select count(*) from lineitem")
    rows = s.execute(
        "select cause, kernel from system.runtime.compiles"
    ).to_pylist()
    assert rows and all(r[0] in co.CAUSES for r in rows)
    census = s.execute(
        "select family, bucket, count from system.runtime.shape_census"
    ).to_pylist()
    assert census and all(r[1] >= 0 and r[2] >= 1 for r in census)
    text = "\n".join(
        r[0] for r in s.execute(
            "explain analyze select count(*) from lineitem"
        ).to_pylist()
    )
    assert "Compiles:" in text
