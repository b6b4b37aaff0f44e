"""Tier-1 rehearses the harness the driver runs: `benchmark/run.py`, through
its command line, on the CPU at SF 0.01.  What is held here is the harness's
contract with the program: without a chip there is no result unless the run
is a rehearsal, `--sf` belongs to a rehearsal alone, a cell is refused on
fewer devices than it asks for, a sound rehearsal of every cell is `correct`
and prints exactly the cell's end-to-end metrics, and a traced one finds the
spans its per-layer readers read.  The whole-run fault cases are in
`test_benchmark_rehearsal_faults.py`.  Nothing here is a device number."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = {w["name"]: w for w in BENCH["workloads"]}
REHEARSAL = ["--rehearse-cpu", "--sf", "0.01"]


def listed(kind, cell):
    return {m["name"] for m in BENCH[kind]
            if cell in m.get("workloads", [cell])}


def run_cell(cell, *extra, devices=1, trace=0):
    """One run of the benchmark's command on `devices` host devices; the
    result is the last line of standard output, or None where there is none."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=%d" % devices)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, BENCH["command"][1]),
         "--workload", cell, "--seed", "30", "--seconds", "2",
         "--trace", str(trace), *extra],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines else None)


def assert_sound(proc, res):
    assert proc.returncode == 0 and res is not None, proc.stderr[-2000:]
    assert res["correct"] is True, proc.stderr[-2000:]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"] and all(
        c["value"] == 0 and c["limit"] == 0 for c in res["checks"].values())
    assert res["device"]["platform"] == "cpu"


def test_a_cpu_is_refused_unless_rehearsing():
    proc, res = run_cell("tpch_sf10.q6")
    assert proc.returncode == 2 and res is None
    assert "no TPU" in proc.stderr
    assert "one execution" not in proc.stderr     # it ran no query


def test_sf_outside_a_rehearsal_is_an_argument_error():
    proc, res = run_cell("tpch_sf10.q6", "--sf", "0.01")
    assert proc.returncode == 2 and res is None
    assert "--rehearse-cpu" in proc.stderr
    assert "device:" not in proc.stderr           # refused before jax


def test_a_cell_is_refused_on_fewer_devices_than_it_asks_for():
    cell = "tpch_sf10_mesh4.q1"
    proc, res = run_cell(cell, *REHEARSAL, devices=CELLS[cell]["chips"] - 1)
    assert proc.returncode == 2 and res is None
    assert "needs %d chips" % CELLS[cell]["chips"] in proc.stderr


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_rehearsal_is_correct_and_prints_the_cells_end_to_end_metrics(cell):
    proc, res = run_cell(cell, *REHEARSAL, devices=CELLS[cell]["chips"])
    assert_sound(proc, res)
    assert set(res["metrics"]) == listed("end_to_end", cell)
    assert res["device"]["count"] == CELLS[cell]["chips"]


def test_a_traced_rehearsal_reads_the_spans_of_a_resident_query():
    cell = "tpch_sf10.q1"
    read_from_spans = {
        "frontend_ms_per_query", "session_ms_per_query",
        "exec_host_ms_per_query", "device_wait_ms_per_query",
        "launches_per_query", "execute_unaccounted_pct",
        "scan_generations_per_query"}
    assert read_from_spans <= listed("per_layer", cell)
    proc, res = run_cell(cell, *REHEARSAL, trace=1)
    assert_sound(proc, res)
    assert read_from_spans <= set(res["metrics"]), sorted(res["metrics"])
    assert set(res["metrics"]) <= listed("per_layer", cell)
    assert res["metrics"]["launches_per_query"]["value"] >= 1
