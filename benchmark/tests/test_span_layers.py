"""The per-layer readers of the program's phase spans, on synthetic
`ctx["spans"]` (name -> [count, total ms]) for a resident and a streamed
query, and on what a program without the spans gives."""
import json
import os

import pytest

import trace_reduce as tr
import yardstick
from run import load_module

ROOT = os.path.dirname(yardstick.HERE)
NEW = ("session_ms_per_query", "exec_host_ms_per_query",
       "device_wait_ms_per_query", "launches_per_query",
       "execute_unaccounted_pct", "tile_wait_ms_per_query",
       "tile_stage_ms_per_query")
TILE = ("tile_wait_ms_per_query", "tile_stage_ms_per_query")

# two resident queries: per query 0.1 admit, 15 query, 0.2 finish; inside
# query 0.3 parse and 14 execute; inside execute 13 of phases
RESIDENT = {
    "query_admit": [2, 0.2], "query": [2, 30.0], "query_finish": [2, 0.4],
    "parse": [2, 0.6], "execute": [2, 28.0],
    "stream_plan": [2, 1.0], "load_scans": [2, 1.0], "device_lanes": [2, 1.6],
    "launch": [2, 2.0], "device_get": [2, 20.0], "materialize_host": [2, 0.4],
}
# one streamed query of two tiles and two downstream fragments: execute
# 4000 = stream_plan 10 + tile_wait 2400 + 4 x tile_execute (the phases
# nest in it) + 30 outside; tile_stage runs on the pool beside all that
STREAMED = {
    "query_admit": [1, 0.1], "query": [1, 4001.0], "query_finish": [1, 0.2],
    "parse": [1, 0.5], "execute": [1, 4000.0],
    "stream_plan": [1, 10.0], "tile_wait": [2, 2400.0],
    "tile_execute": [4, 1560.0], "tile_stage": [2, 3300.0],
    "tile_load": [2, 1200.0], "tile_upload": [2, 2090.0],
    "stage_lanes": [2, 2080.0], "devgen": [2, 1000.0],
    "load_scans": [4, 5.0], "device_lanes": [4, 13.0], "launch": [4, 20.0],
    "device_get": [4, 1500.0], "materialize_host": [4, 2.0],
}
# the program before the phase spans: six span names, none of the new ones
PARENT = {"query": [3, 45.0], "parse": [3, 0.9], "execute": [3, 42.0]}


def read(name, spans):
    return load_module("layers", name).read({"spans": spans})


@pytest.mark.parametrize("name, resident, streamed", [
    ("session_ms_per_query", (0.2 + 30.0 + 0.4 - 0.6 - 28.0) / 2,
     0.1 + 4001.0 + 0.2 - 0.5 - 4000.0),
    ("exec_host_ms_per_query", (28.0 - 20.0) / 2, 4000.0 - 1500.0 - 2400.0),
    ("device_wait_ms_per_query", 10.0, 1500.0),
    ("launches_per_query", 1.0, 4.0),
    ("execute_unaccounted_pct", 100 * (28.0 - 26.0) / 28.0,
     100 * (4000.0 - 3950.0) / 4000.0),
    ("tile_wait_ms_per_query", None, 2400.0),
    ("tile_stage_ms_per_query", None, 3300.0),
])
def test_reader_on_a_resident_and_a_streamed_query(name, resident, streamed):
    assert read(name, RESIDENT) == (
        None if resident is None else pytest.approx(resident))
    assert read(name, STREAMED) == pytest.approx(streamed)


def test_grouping_spans_are_not_summed():
    base = read("execute_unaccounted_pct", STREAMED)
    more = dict(STREAMED, tile_execute=[4, 9999.0], tile_stage=[2, 9999.0],
                tile_load=[2, 9999.0], tile_upload=[2, 9999.0],
                stage_lanes=[2, 9999.0], devgen=[2, 9999.0],
                xla_compile=[1, 9999.0])
    assert read("execute_unaccounted_pct", more) == base
    assert read("exec_host_ms_per_query", more) == \
        read("exec_host_ms_per_query", STREAMED)
    # a phase is: one more millisecond of it is one less unaccounted
    phase = dict(STREAMED, launch=[4, 21.0])
    assert read("execute_unaccounted_pct", phase) == pytest.approx(
        100 * 49.0 / 4000.0)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("spans", [PARENT, {}])
def test_a_program_without_the_spans_reads_nothing(name, spans):
    assert read(name, spans) is None


def test_entries_list_the_cells_that_have_something_to_read():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    streamed = [w["name"] for w in bench["workloads"]
                if yardstick.load_json("workloads", w["name"] + ".json")
                ["query"] == "q1"]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)
    for name in NEW:
        m = entries[name]
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] == "query_p50_ms"
        assert m["workloads"] == (streamed if name in TILE else cells)


def test_a_span_is_a_host_event_the_idle_gaps_are_labelled_with():
    """What the accepted reduction does with the annotations the tracer
    writes: the innermost one open at a gap's middle names the gap."""
    host = [("bench:query", 0, 1000), ("bench:execute", 0, 900),
            ("query", 5, 890), ("execute", 10, 880), ("launch", 100, 300),
            ("device_get", 300, 870), ("bench:materialize", 900, 1000)]
    planes = {
        "/host:CPU": {"python3": host},
        "/device:TPU:0": {
            "XLA Ops": [("%f = s32[8]{0} fusion(s32[8]{0} %a)", 350, 850)],
            "XLA Modules": [("frag_abc", 350, 850)]},
    }
    gaps = dict(tr.reduce(planes)["idle_gaps"])
    assert set(gaps) == {"execute/launch", "materialize"}   # 0..350, 850..1000
