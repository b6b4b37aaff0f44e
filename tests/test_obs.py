"""obs/ subsystem tests: flight recorder (ring rotation, kill -9 crash
survival, fault attribution + standalone replay), HBM bandwidth ledger
math against hand-computed scan bytes, and the bench regression
sentinel's verdicts on synthetic and real BENCH trajectories.
"""
import glob
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from trino_tpu.obs.flight_recorder import (
    FlightRecorder,
    last_unmatched,
    read_dir,
)
from trino_tpu.runtime.supervisor import Breadcrumb
from trino_tpu.session import tpch_session

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "scripts")
)
import bench_sentinel  # noqa: E402
import flightrec  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bc(kernel="k1", **kw):
    return Breadcrumb(kernel, query_id="q1", node_id="n1", **kw)


# -- flight recorder ----------------------------------------------------

def test_ring_rotation_bounds_disk_and_memory(tmp_path):
    rec = FlightRecorder(str(tmp_path), max_records=8, name="t")
    for i in range(100):
        seq = rec.record_dispatch(_bc("k%d" % i))
        rec.record_complete(seq, _bc("k%d" % i), wall_s=0.001)
    # in-memory mirror is bounded
    tail = rec.tail()
    assert len(tail) == 8
    # newest records won; oldest rotated out
    assert tail[-1]["kernel"] == "k99"
    # exactly two fixed-size segments on disk, never more
    segs = sorted(glob.glob(str(tmp_path / "fr-t-*.jsonl")))
    assert len(segs) == 2
    sizes = {os.path.getsize(p) for p in segs}
    rec.close()
    # disk ring still holds the newest records after heavy rotation
    records = read_dir(str(tmp_path))
    assert records
    assert records[-1]["kernel"] == "k99"
    assert {r["recordType"] for r in records} == {"dispatch", "complete"}
    # segments were preallocated, not grown per record
    assert len(sizes) == 1


def test_memory_only_recorder_without_directory():
    rec = FlightRecorder(None, max_records=4)
    for i in range(10):
        rec.record_dispatch(_bc("k%d" % i))
    assert len(rec.tail()) == 4
    assert rec.tail(2)[-1]["kernel"] == "k9"


def test_last_unmatched_names_the_in_flight_dispatch():
    rec = FlightRecorder(None, max_records=16)
    s1 = rec.record_dispatch(_bc("done"))
    rec.record_complete(s1, _bc("done"), wall_s=0.01)
    rec.record_dispatch(_bc("in-flight"))
    culprit = last_unmatched(rec.tail())
    assert culprit["kernel"] == "in-flight"
    assert culprit["recordType"] == "dispatch"


_CRASH_CHILD = """
import os, sys
sys.path.insert(0, %(repo)r)
from trino_tpu.obs.flight_recorder import FlightRecorder
from trino_tpu.runtime.supervisor import Breadcrumb

rec = FlightRecorder(%(dir)r, max_records=64, name="child")
for i in range(40):
    seq = rec.record_dispatch(
        Breadcrumb("kernel-%%d" %% i, node_id="child",
                   shapes={"lane": "int64(1024,)"})
    )
    if i < 39:
        rec.record_complete(seq, Breadcrumb("kernel-%%d" %% i), wall_s=0.0)
# the 40th dispatch never completes: signal readiness and hang so the
# parent can SIGKILL us mid-flight (no close(), no flush, no atexit)
print("READY", flush=True)
import time
time.sleep(60)
"""


def test_kill9_crash_survival_recovers_last_records(tmp_path):
    """SIGKILL mid-write loses nothing: MAP_SHARED dirty pages belong to
    the page cache the moment the store completes, and the reader skips
    any torn trailing line."""
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
    records = read_dir(str(tmp_path))
    assert records, "no records survived the SIGKILL"
    dispatches = [r for r in records if r["recordType"] == "dispatch"]
    # the last dispatch (seq pairs with no complete) is attributable
    culprit = last_unmatched(records)
    assert culprit is not None
    assert culprit["kernel"] == dispatches[-1]["kernel"]
    assert culprit["kernel"] == "kernel-39"
    assert culprit["shapes"] == {"lane": "int64(1024,)"}


def test_forced_device_loss_persists_culprit_and_replays(tmp_path):
    """Acceptance: after a forced device_loss the persisted tail names
    the culprit kernel digest + shapes, and flightrec replay re-executes
    it standalone on the CPU backend."""
    s = tpch_session(0.001)
    s.properties.set("flight_recorder_dir", str(tmp_path))
    s.properties.set(
        "fault_injection",
        json.dumps({"seed": 1, "device_loss": {"nth": 1}}),
    )
    s.properties.set("device_cpu_fallback", False)
    with pytest.raises(Exception, match="device_loss"):
        s.execute("select sum(l_extendedprice) from lineitem")
    records = read_dir(str(tmp_path))
    faults = [r for r in records if r["recordType"] == "fault"]
    assert faults, "device_loss left no fault record on disk"
    fault = faults[-1]
    assert fault["faultKind"] == "device_loss"
    assert fault["kernel"]
    assert fault["shapes"], "culprit record carries no input shapes"
    # replay the culprit standalone: synthesized inputs of the recorded
    # shapes through a fresh supervisor on the CPU backend
    dispatch = [
        r for r in records
        if r["recordType"] == "dispatch" and r["seq"] == fault["seq"]
    ][-1]
    result = flightrec.replay_record(dispatch, backend="cpu")
    assert result["ok"]
    assert result["kernel"] == fault["kernel"]
    assert result["lanes"] == len(dispatch["shapes"])
    assert result["bytes"] > 0


def test_flightrec_shape_parsing():
    assert flightrec.parse_shape("int64(1024,)") == ("int64", (1024,))
    assert flightrec.parse_shape("float32(64, 128)") == (
        "float32", (64, 128),
    )
    assert flightrec.parse_shape("bool()") == ("bool", ())
    assert flightrec.parse_shape("not-a-shape") is None
    arrays = flightrec.synthesize_inputs(
        {"a": "int64(8,)", "b": "float32(2, 3)", "c": "bool()"}
    )
    assert arrays["a"].dtype == np.int64 and arrays["a"].shape == (8,)
    assert arrays["b"].shape == (2, 3)
    assert arrays["c"].shape == ()


def test_system_flight_recorder_table():
    s = tpch_session(0.001)
    s.execute("select count(*) from lineitem")
    rows = s.execute(
        "select record_type, kernel from system.runtime.flight_recorder"
    ).to_pylist()
    assert rows, "default in-memory recorder captured nothing"
    kinds = {r[0] for r in rows}
    assert "dispatch" in kinds and "complete" in kinds


# -- bandwidth ledger ---------------------------------------------------

def test_ledger_math_matches_hand_computed_bytes():
    """Acceptance: the ledger's inputBytes for a Q6-style scan matches
    the hand-computed unpadded scan bytes within 10%, and GB/s is
    exactly totalBytes / wall."""
    s = tpch_session(0.01)
    s.properties.set("bandwidth_ledger", True)
    s.properties.set("result_cache", False)
    page = s.execute(
        "select sum(l_extendedprice * l_discount) from lineitem "
        "where l_discount < 0.05"
    )
    assert page.count == 1
    prof = s.last_kernel_profile
    entries = prof.get("bandwidth")
    assert entries, "ledger enabled but no entries recorded"
    e = entries[0]
    # the system table reads the CURRENT last profile — query it before
    # any later statement overwrites that
    rows = s.execute(
        "select kernel, input_bytes, gbps "
        "from system.runtime.kernel_bandwidth"
    ).to_pylist()
    assert any(r[0] == e["kernel"] for r in rows)
    # hand-computed: two int64 value lanes (extendedprice, discount) at
    # the table's unpadded row count; tpch columns are non-null, so no
    # validity lanes ride along
    nrows = s.execute(
        "select count(*) from lineitem"
    ).to_pylist()[0][0]
    expected = 2 * nrows * 8
    assert abs(e["inputBytes"] - expected) / expected < 0.10, (
        e["inputBytes"], expected,
    )
    assert e["executions"] >= 1
    assert e["deviceWallS"] > 0
    total = e["inputBytes"] + e["outputBytes"] + e["intermediateBytes"]
    assert e["totalBytes"] == total
    assert e["gbps"] == pytest.approx(
        e["totalBytes"] / e["deviceWallS"] / 1e9
    )
    # summary rolled into the kernel profile
    summary = prof["summary"]
    assert summary["ledgerBytes"] >= total
    assert summary["effectiveGbps"] > 0


def test_explain_analyze_shows_bandwidth_ledger():
    s = tpch_session(0.001)
    text = "\n".join(
        r[0] for r in s.execute(
            "explain analyze select sum(l_extendedprice) from lineitem"
        ).to_pylist()
    )
    assert "HBM bandwidth ledger" in text
    assert "GB/s" in text and "roofline" in text


def test_ledger_off_by_default():
    s = tpch_session(0.001)
    s.execute("select count(*) from lineitem")
    prof = s.last_kernel_profile or {}
    assert "bandwidth" not in prof


# -- bench sentinel -----------------------------------------------------

def _wrap(n, rc, parsed=None, tail=""):
    return {"n": n, "cmd": "bench", "rc": rc, "tail": tail,
            "parsed": parsed}


def _write_rounds(tmp_path, rounds):
    for n, doc in rounds:
        with open(
            os.path.join(str(tmp_path), "BENCH_r%02d.json" % n), "w"
        ) as f:
            json.dump(doc, f)


def test_sentinel_synthetic_trajectory(tmp_path):
    cfg = lambda rps: {"configs": {"q6": {"rows_per_sec": rps}}}  # noqa: E731
    _write_rounds(tmp_path, [
        (1, _wrap(1, 0, cfg(100.0))),           # baseline
        (2, _wrap(2, 0, cfg(101.0))),           # steady
        (3, _wrap(3, 0, cfg(50.0))),            # regression (x0.50)
        (4, _wrap(4, 0, cfg(140.0))),           # improved vs r03
        (5, _wrap(5, 0, None,
                  tail='"q6": {"error": "JaxRuntimeError: UNAVAILABLE: '
                       'TPU worker process crashed"}')),
    ])
    rounds = [
        bench_sentinel.load_round(p)
        for p in sorted(glob.glob(str(tmp_path / "BENCH_r*.json")))
    ]
    verdicts = {v["round"]: v["verdict"]
                for v in bench_sentinel.judge(rounds)}
    assert verdicts == {
        1: "baseline", 2: "steady", 3: "regression",
        4: "improved", 5: "crash-introduced",
    }


def test_sentinel_flags_bandwidth_regression_when_wall_holds(tmp_path):
    """Rows/s steady but the ledger's effective GB/s collapses: the same
    answer is moving more bytes (fusion fell back, donation stopped) —
    the sentinel must flag it even though wall-clock verdicts say steady.
    Rounds without bandwidth data are never judged on it."""
    cfg = lambda rps, gbps=None: {"configs": {"q6": dict(  # noqa: E731
        {"rows_per_sec": rps},
        **({"effective_gbps": gbps} if gbps is not None else {}),
    )}}
    _write_rounds(tmp_path, [
        (1, _wrap(1, 0, cfg(100.0, 30.0))),   # baseline
        (2, _wrap(2, 0, cfg(101.0, 12.0))),   # wall holds, GB/s x0.40
        (3, _wrap(3, 0, cfg(100.0, 11.9))),   # vs r02: both hold now
        (4, _wrap(4, 0, cfg(102.0))),         # no ledger data: no verdict
    ])
    rounds = [
        bench_sentinel.load_round(p)
        for p in sorted(glob.glob(str(tmp_path / "BENCH_r*.json")))
    ]
    verdicts = bench_sentinel.judge(rounds)
    by_round = {v["round"]: v for v in verdicts}
    assert by_round[2]["verdict"] == "bandwidth-regression"
    assert by_round[2]["bw_ratio"] == 0.4
    assert "despite wall holding" in by_round[2]["reason"]
    assert by_round[3]["verdict"] == "steady"
    assert by_round[4]["verdict"] == "steady"
    assert "bw_ratio" not in by_round[4]
    md = bench_sentinel.to_markdown(verdicts)
    assert "r02 (bandwidth-regression)" in md


def test_sentinel_timeout_round_is_regression(tmp_path):
    _write_rounds(tmp_path, [
        (1, _wrap(1, 0, {"configs": {"q6": {"rows_per_sec": 10.0}}})),
        (2, _wrap(2, 124, None, tail="WARNING: something\n")),
    ])
    rounds = [
        bench_sentinel.load_round(p)
        for p in sorted(glob.glob(str(tmp_path / "BENCH_r*.json")))
    ]
    v = bench_sentinel.judge(rounds)[-1]
    assert v["verdict"] == "regression"
    assert "124" in v["reason"]


def test_sentinel_recovers_configs_from_truncated_tail():
    # head-truncated mid-object: the partial leader is skipped, the
    # complete objects are recovered
    tail = (
        'per_sec": 1.0, "configs": {"a": {"rows_per_sec": 5.0}, '
        '"b": {"rows_per_sec": 7.0, "scan_bytes": 10}, '
        '"c": {"rows_per'
    )
    cfgs = bench_sentinel.recover_configs(tail)
    assert set(cfgs) == {"a", "b"}
    assert cfgs["b"]["rows_per_sec"] == 7.0


def test_sentinel_markdown_names_flagged_rounds(tmp_path):
    _write_rounds(tmp_path, [
        (1, _wrap(1, 0, {"configs": {"q6": {"rows_per_sec": 10.0}}})),
        (2, _wrap(2, 124, None)),
    ])
    rounds = [
        bench_sentinel.load_round(p)
        for p in sorted(glob.glob(str(tmp_path / "BENCH_r*.json")))
    ]
    md = bench_sentinel.to_markdown(bench_sentinel.judge(rounds))
    assert "| r02 |" in md
    assert "Flagged: r02 (regression)" in md
