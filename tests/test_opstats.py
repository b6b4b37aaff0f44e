"""Per-operator execution timeline + query history store tests.

Reference parity: io.trino.operator.OperatorStats /
QueryStats.getOperatorSummaries() (rows, bytes, wall, blocked time per
operator), EXPLAIN ANALYZE operator annotations, and the
query.max-history retention semantics of the coordinator's
QueryHistory — here crash-safe via the same mmap'd torn-tail-tolerant
segments as the flight recorder.

Covers the acceptance gates:
  - per-operator rows/bytes on Q1/Q3/Q6 match independently computed
    counts (COUNT(*) probes of the same session);
  - exclusive operator walls sum to the query wall within 10%;
  - the history store survives kill -9 and the survivors are
    SQL-visible after restart via system.runtime.completed_queries;
  - a seeded slow worker is flagged by the straggler detector and
    hedged by the FTE scheduler (dispersion-aware speculation);
  - scripts/lint.py (all three check_* linters) passes — tier-1 wiring.
"""
import json
import os
import signal
import subprocess
import sys

import pytest

from tpch_sql import QUERIES
from trino_tpu.obs.history import (
    QueryHistoryStore,
    read_history_dir,
    _reset_stores,
)
from trino_tpu.obs.opstats import StragglerDetector
from trino_tpu.session import tpch_session
from trino_tpu.testing import DistributedQueryRunner

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "scripts")
)
import lint  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SF = 0.001
# scan/filter frames carry 8-byte device lanes; Q6 touches 4 lineitem
# columns (quantity, extendedprice, discount, shipdate)
LANE_BYTES = 8
Q6_COLUMNS = 4


@pytest.fixture(scope="module")
def session():
    return tpch_session(SF, operator_stats=True)


def _scalar(session, sql):
    return session.execute(sql).to_pylist()[0][0]


def _timeline(session, sql):
    page = session.execute(sql)
    tl = session.last_timeline
    assert tl and tl.get("operators"), "operator_stats produced no frames"
    return page, tl


def _by_type(tl, operator_type):
    return [
        f for f in tl["operators"] if f["operatorType"] == operator_type
    ]


# --- per-operator rows and bytes vs independent counts -------------------


def test_q1_operator_rows_match_counts(session):
    page, tl = _timeline(session, QUERIES[1][0])
    lineitem = _scalar(session, "SELECT count(*) FROM lineitem")
    passing = _scalar(
        session,
        "SELECT count(*) FROM lineitem "
        "WHERE l_shipdate <= DATE '1998-09-02'",
    )
    (scan,) = _by_type(tl, "TableScan")
    assert scan["outputRows"] == lineitem
    assert scan["inputRows"] == 0  # leaves consume nothing
    (filt,) = _by_type(tl, "Filter")
    assert filt["inputRows"] == lineitem
    assert filt["outputRows"] == passing
    (agg,) = _by_type(tl, "Aggregate")
    assert agg["inputRows"] == passing
    assert agg["outputRows"] == page.count  # 4 returnflag/linestatus groups


def test_q3_scan_rows_match_table_cardinalities(session):
    page, tl = _timeline(session, QUERIES[3][0])
    counts = sorted(
        _scalar(session, f"SELECT count(*) FROM {t}")
        for t in ("customer", "orders", "lineitem")
    )
    scans = _by_type(tl, "TableScan")
    assert sorted(f["outputRows"] for f in scans) == counts
    # the root operator's output is the statement's result set
    root = min(tl["operators"], key=lambda f: f["operatorId"])
    assert root["outputRows"] == page.count
    # joins reduce: every Join emits no more than it consumed
    for join in _by_type(tl, "Join"):
        assert join["outputRows"] <= join["inputRows"]


def test_q6_operator_rows_and_bytes(session):
    page, tl = _timeline(session, QUERIES[6][0])
    lineitem = _scalar(session, "SELECT count(*) FROM lineitem")
    passing = _scalar(
        session,
        "SELECT count(*) FROM lineitem "
        "WHERE l_shipdate >= DATE '1994-01-01' "
        "AND l_shipdate < DATE '1995-01-01' "
        "AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24",
    )
    (scan,) = _by_type(tl, "TableScan")
    (filt,) = _by_type(tl, "Filter")
    (agg,) = _by_type(tl, "Aggregate")
    assert scan["outputRows"] == lineitem
    assert scan["outputBytes"] == lineitem * Q6_COLUMNS * LANE_BYTES
    assert filt["inputRows"] == lineitem
    assert filt["outputRows"] == passing
    assert filt["outputBytes"] == passing * Q6_COLUMNS * LANE_BYTES
    assert agg["inputRows"] == passing
    assert agg["outputRows"] == 1 == page.count


def test_operator_walls_sum_to_query_wall(session):
    """Walls are exclusive (own time only), so their sum reconciles with
    the query wall — the acceptance gate is 10%."""
    _, tl = _timeline(session, QUERIES[1][0])
    wall = tl["wallS"]
    op_wall = sum(f["wallS"] for f in tl["operators"])
    assert wall > 0
    assert abs(op_wall - wall) <= max(0.1 * wall, 0.05), (
        f"operator walls {op_wall:.3f}s vs query wall {wall:.3f}s"
    )


# --- history store: crash safety, restart visibility, byte bound --------


_CRASH_CHILD = """
import os, sys, time
sys.path.insert(0, %(repo)r)
from trino_tpu.obs.history import QueryHistoryStore

store = QueryHistoryStore(%(dir)r, max_bytes=1 << 20)
for i in range(5):
    store.put({
        "query_id": "q_crash_%%d" %% i,
        "state": "FINISHED",
        "sql": "SELECT %%d" %% i,
        "user": "crash-test",
        "created": 1000.0 + i,
        "finished": 1001.0 + i,
        "rows": i,
    })
# no close(), no flush, no atexit: readiness then hang for SIGKILL
print("READY", flush=True)
time.sleep(60)
"""


def test_history_survives_kill9_and_restart(tmp_path):
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

    # offline reader sees every record despite the SIGKILL
    records = read_history_dir(str(tmp_path))
    got = {r["queryId"] for r in records}
    assert got >= {f"q_crash_{i}" for i in range(5)}

    # "restart": a fresh session pointed at the directory serves the
    # survivors through SQL
    _reset_stores()
    try:
        s = tpch_session(SF, query_history_dir=str(tmp_path))
        rows = s.execute(
            "SELECT query_id, state, rows FROM "
            "system.runtime.completed_queries"
        ).to_pylist()
        by_id = {r[0]: r for r in rows}
        for i in range(5):
            qid = f"q_crash_{i}"
            assert qid in by_id, f"{qid} not visible after restart"
            assert by_id[qid][1] == "FINISHED"
            assert by_id[qid][2] == i
    finally:
        _reset_stores()


def test_history_store_is_byte_bounded():
    # max_bytes clamps to 2 * MIN_SEGMENT_BYTES (128 KiB); ~1 KiB of SQL
    # per record * 400 records overflows it several times over
    store = QueryHistoryStore(None, max_bytes=4096)
    for i in range(400):
        store.put({
            "query_id": f"q_{i}", "state": "FINISHED",
            "sql": "SELECT " + "x" * 1024, "user": "t",
            "created": float(i), "finished": float(i), "rows": i,
        })
    assert store.total_bytes() <= store.max_bytes
    entries = store.entries()
    assert 0 < len(entries) < 400  # evicted oldest-first
    assert entries[-1]["queryId"] == "q_399"  # newest survives


# --- straggler detector --------------------------------------------------


def test_straggler_detector_hedges_on_dispersion():
    det = StragglerDetector(factor=2.0, min_s=0.1)
    siblings = [1.0, 1.1, 0.9, 1.05]
    assert det.should_hedge(5.0, siblings)  # far past the pack
    assert not det.should_hedge(1.2, siblings)  # inside the pack
    assert not det.should_hedge(5.0, [])  # no pack to compare against
    assert not det.should_hedge(0.05, siblings)  # under the age floor


def test_seeded_slow_worker_is_flagged_and_hedged():
    """Chaos gate: one task of stage 1 stalls 4s; the dispersion-aware
    trigger hedges it (instead of waiting out an age-only deadline) and
    the straggler surfaces in the query JSON."""
    fault = json.dumps({
        "seed": 1,
        "task_stall": {"stall_s": 4.0, "match": ".1.0.", "times": 1},
    })
    r = DistributedQueryRunner(
        workers=2,
        catalogs=(("tpch", "tpch", {"tpch.scale-factor": SF}),),
        properties={
            "retry_policy": "task",
            "fte_speculation_min_s": "0.3",
            "straggler_dispersion_factor": "2.0",
            "fault_injection": fault,
        },
    )
    try:
        _, rows = r.execute(QUERIES[3][0])
        assert len(rows) == 8  # Q3 result at this SF
        coord = r.coordinator.coordinator
        hedged = [
            (q, f)
            for q in coord.queries.values()
            for f in getattr(q, "straggler_flags", ())
            if f.get("action") == "hedge"
        ]
        assert hedged, "stalled task was never hedged"
        # under a loaded host other stages can trip the dispersion
        # trigger too — assert on the SEEDED task's flag, not the last
        seeded = [
            (q, f) for q, f in hedged if ".1.0." in f.get("task", "")
        ]
        assert seeded, "the seeded stalled task was never hedged"
        q, flag = seeded[-1]
        assert flag["stage"] == "1"
        assert ".1.0." in flag["task"]
        assert flag["elapsedS"] >= 0.3
        # the same flags ride GET /v1/query/{id}
        import urllib.request

        with urllib.request.urlopen(
            f"{r.coordinator.uri}/v1/query/{q.query_id}", timeout=10
        ) as resp:
            doc = json.loads(resp.read())
        assert any(
            f.get("action") == "hedge" for f in doc.get("stragglers", ())
        )
        assert doc.get("timeline"), "query JSON missing operator timeline"
    finally:
        r.stop()


# --- lint wiring ---------------------------------------------------------


def test_lint_runs_all_checkers_clean(capsys):
    assert lint.main() == 0
    out = capsys.readouterr().out
    for name, _ in lint.LINTERS:
        assert name in out
    assert "check_donation" in out


def test_donation_lint_flags_bare_jit_and_unregistered_kernel(tmp_path):
    """A bare hot-path jit (no donate_argnums, no waiver) and a kernel
    missing from KERNEL_REGISTRY must both be violations; the waiver
    comment and a donate_argnums continuation line must both pass."""
    import check_donation

    root = str(tmp_path)
    ops = os.path.join(root, "trino_tpu", "ops")
    os.makedirs(os.path.join(root, "trino_tpu", "exec"))
    os.makedirs(os.path.join(root, "trino_tpu", "connectors"))
    os.makedirs(ops)
    with open(os.path.join(root, "trino_tpu", "exec", "hot.py"), "w") as f:
        f.write(
            "bad = jax.jit(fn)\n"
            "ok1 = jax.jit(\n"
            "    fn, donate_argnums=(1,)\n"
            ")\n"
            "# no-donate: scalar args only\n"
            "ok2 = jax.jit(fn)\n"
        )
    with open(os.path.join(ops, "pallas_kernels.py"), "w") as f:
        f.write(
            "def _good_kernel(ref):\n    pass\n\n"
            "def _rogue_kernel(ref):\n    pass\n\n"
            'KERNEL_REGISTRY = {\n    "_good_kernel": {},\n}\n'
        )
    checked, violations = check_donation.check_tree(root)
    assert checked == 5  # 3 jit sites + 2 kernel defs
    msgs = {(r, n) for r, n, _m in violations}
    assert (os.path.join("trino_tpu", "exec", "hot.py"), 1) in msgs
    assert (os.path.join("trino_tpu", "ops", "pallas_kernels.py"), 4) in msgs
    assert len(violations) == 2
