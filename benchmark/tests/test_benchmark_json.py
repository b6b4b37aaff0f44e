"""BENCHMARK.json against the contract's shapes, and the files it names."""
import json
import os
import re

import pytest

import yardstick

ROOT = os.path.dirname(yardstick.HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for text in [w["why"] for w in bench["workloads"] + bench["configs"]] + \
            [c["source"] for c in bench["configs"]] + bench["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_s_files_are_found_by_name(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) == len(cells)
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        cfg_entry = configs[w["config"]]
        assert cfg_entry["file"].startswith(bench["paths"][0] + "/")
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == cfg_entry["source"]
        assert cfg["reduced"] == cfg_entry["reduced"] and cfg["chips"] == w["chips"]
        workload = yardstick.load_json("workloads", w["name"] + ".json")
        assert workload["config"] == w["config"] and workload["why"] == w["why"]
        assert workload["query"] == w["traffic"]
        assert os.path.exists(os.path.join(
            yardstick.HERE, "queries", workload["query"] + ".py"))
    assert {c["name"] for c in bench["configs"]} == {w["config"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for kind, group in (("end_to_end", bench["end_to_end"]), ("layers", bench["per_layer"])):
        for m in group:
            assert os.path.exists(os.path.join(yardstick.HERE, kind, m["name"] + ".py"))
            assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))


def test_the_harness_names_no_cell_query_or_metric(bench):
    with open(os.path.join(yardstick.HERE, "run.py")) as f:
        text = f.read()
    words = [w["name"] for w in bench["workloads"]] + \
        [w["traffic"] for w in bench["workloads"]] + \
        [c["name"] for c in bench["configs"]] + \
        [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    for word in words:
        assert not re.search(r"\b%s\b" % re.escape(word), text), word


def test_an_unknown_device_kind_is_an_error():
    assert yardstick.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        yardstick.peaks("TPU v9 imaginary")


def test_rows_and_bytes_of_a_query():
    cfg = yardstick.load_json("configs", "tpch_sf10.json")
    tables = {"lineitem": ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]}
    rows = cfg["tables"]["lineitem"]["rows"]
    assert yardstick.rows_per_query(cfg, tables) == rows
    assert yardstick.bytes_per_query(cfg, tables, "min_bytes") == 8 * rows
    assert yardstick.bytes_per_query(cfg, tables, "stored_bytes") == 28 * rows
