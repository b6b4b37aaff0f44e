"""The readers the four-chip cell brought, on synthetic `ctx`: the mesh's
share of the HBM roofline and the generator's runs per query."""
import json
import os

import pytest

import yardstick
from run import load_module

ROOT = os.path.dirname(yardstick.HERE)
CELL = "tpch_sf10_mesh4.q1"
PEAKS = {"hbm_bytes_per_s": 819e9}


def read(name, **ctx):
    base = {"spans": {}, "trace": None, "peaks": PEAKS,
            "least_bytes_per_query": 14 * 59998187}
    return load_module("layers", name).read(dict(base, **ctx))


def trace(chips, busy_s=0.5, queries=4):
    return {"queries": queries, "window_s": 1.0, "busy_s": busy_s,
            "chips": chips, "modules": 1.0, "device_ops": [], "idle_gaps": []}


def test_four_chips_read_a_quarter_of_the_one_chip_formula():
    one = read("hbm_roofline_pct", trace=trace(4))
    assert read("mesh_hbm_roofline_pct", trace=trace(4)) == pytest.approx(one / 4)
    # one chip: the two formulas are the same number
    assert read("mesh_hbm_roofline_pct", trace=trace(1)) == pytest.approx(
        read("hbm_roofline_pct", trace=trace(1)))
    # least time 14 B x 59,998,187 / (4 x 819e9) = 0.2564 ms of 125 ms a query
    assert read("mesh_hbm_roofline_pct", trace=trace(4)) == pytest.approx(
        100 * (14 * 59998187 / (4 * 819e9)) / 0.125)


@pytest.mark.parametrize("ctx", [
    {"trace": None}, {"trace": trace(0, busy_s=None)},
    {"trace": trace(4), "peaks": None}])
def test_no_trace_no_device_or_no_peaks_reads_nothing(ctx):
    assert read("mesh_hbm_roofline_pct", **ctx) is None


@pytest.mark.parametrize("spans, value", [
    ({"query": [5, 900.0], "execute": [5, 890.0]}, 0.0),       # lanes resident
    ({"query": [4, 6400.0], "devgen": [8, 4000.0]}, 2.0),      # two tiles a query
    ({}, None), ({"devgen": [2, 10.0]}, None)])                # untraced; no query
def test_generator_runs_per_query(spans, value):
    assert read("scan_generations_per_query", spans=spans) == value


def test_the_cell_and_its_metrics_are_listed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 4 and cell["config"] == "tpch_sf10_mesh4"
    cfg = yardstick.load_json("configs", "tpch_sf10_mesh4.json")
    one = yardstick.load_json("configs", "tpch_sf10.json")
    assert cfg["tables"] == one["tables"] and cfg["guarantees"] == one["guarantees"]
    assert cfg["session"] == {"distributed": True, "num_devices": 4,
                              "device_cpu_fallback": False, "result_cache": False}
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == {
        "frontend_ms_per_query", "dispatches_per_query", "device_ms_per_query",
        "device_idle_pct", "compile_s", "datagen_s", "session_ms_per_query",
        "exec_host_ms_per_query", "device_wait_ms_per_query",
        "launches_per_query", "execute_unaccounted_pct",
        "mesh_hbm_roofline_pct", "scan_generations_per_query"}
    # four chips' busy time against one chip's peak would read four times the truth
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert CELL not in entries["hbm_roofline_pct"]["workloads"]
    assert entries["scan_generations_per_query"]["workloads"] == [CELL, "tpch_sf10.q1"]
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)
