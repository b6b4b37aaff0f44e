"""A whole run of the harness with the timed path broken underneath has to
come out as not correct, by the number that is there to catch the fault."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def drive(fault, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "faulty_run.py"), fault, cell],
        capture_output=True, text=True, env=env, timeout=600)
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("cell", ["tpch_sf10.q6", "tpch_sf10.q1", "tpch_sf1.q3"])
def test_a_sound_run_is_correct(cell):
    proc, res = drive("none", cell)
    assert proc.returncode == 0 and res["correct"] is True, proc.stderr[-2000:]
    assert res["failed"] == 0 and res["attempted"] > 0
    # latencies are per query: back to back they fill the window, no more
    p50_s = res["metrics"]["query_p50_ms"]["value"] / 1e3
    assert 0 < p50_s * res["attempted"] < 2 * 2.0 + 2 * p50_s
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0 for c in res["checks"].values())
    assert proc.stderr.strip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("fault,cell,caught_by", [
    ("answer_altered", "tpch_sf10.q6", "wrong_answers"),
    ("answer_altered", "tpch_sf1.q3", "wrong_answers"),
    ("half_rows", "tpch_sf10.q6", "wrong_answers"),
    ("half_rows", "tpch_sf10.q1", "wrong_answers"),
    ("half_rows", "tpch_sf1.q3", "wrong_answers"),
    ("unequal_repeats", "tpch_sf10.q1", "unequal_repeats"),
    ("compile_in_window", "tpch_sf10.q6", "window_compiles"),
    ("cpu_fallback", "tpch_sf10.q1", "cpu_fallbacks"),
    ("query_raises", "tpch_sf10.q6", "failed_queries"),
])
def test_a_planted_fault_is_not_correct(fault, cell, caught_by):
    proc, res = drive(fault, cell)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["correct"] is False
    assert res["checks"][caught_by]["value"] > 0
    assert "FAILED" in proc.stderr


def test_without_a_chip_there_is_no_result():
    proc, res = drive("no_chip", "tpch_sf10.q6")
    assert proc.returncode != 0 and res is None
