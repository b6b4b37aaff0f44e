"""obs/ subsystem tests: flight recorder (ring rotation, kill -9 crash
survival, fault attribution + standalone replay).
"""
import glob
import json
import os
import signal
import subprocess
import sys

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
