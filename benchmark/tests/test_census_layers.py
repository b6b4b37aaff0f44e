"""The readers PR 37 brought, on synthetic `ctx`: the work counts of the
compiled program's census, the census's self-check against the trace's own
ten names."""
import json
import os

import pytest

import yardstick
from run import load_module

ROOT = os.path.dirname(yardstick.HERE)
FOUR = ["tpch_sf1.q3", "tpch_sf1.q3_early", "tpch_sf1_q18.q18",
        "tpch_sf10_mesh4_q3.q3"]
COUNTS = {"gather_elements_per_query": "gatherElements",
          "scatter_updates_per_query": "scatterUpdates",
          "sort_operand_elements_per_query": "sortOperandElements"}

OPS = {"fusion.13": ["Aggregate#3/permute_lanes", "gather", "u32[16777216,4]",
                     2],
       "fusion.2": ["Join#5/probe_direct", "gather", "s32[16777216]", 1],
       # named by its operand only (rule 3): placed, not counted
       "fusion.5": ["Join#5/probe_direct", "cumulative", "s64[4194304]", 3],
       "copy.7": ["", "elementwise", "s32[8]", 0]}
CENSUS = {"instructions": 900, "scopedInstructions": 400, "ops": OPS,
          "gatherElements": 7, "scatterUpdates": 5, "sortOperandElements": 3}
# the executable of a tree before the scopes, loaded from a persistent cache
STALE = dict(CENSUS, scopedInstructions=0,
             ops={k: ["", v[1], v[2], 0] for k, v in OPS.items()})
DEVICE_OPS = [["%fusion.13 fusion u32[16777216,4]", 1.5],
              ["%fusion.2 fusion s32[16777216]", 0.3],
              ["%copy.7 copy s32[8]", 0.1],
              ["%fusion.99 fusion s32[4]", 0.1],
              ["%fusion.5 fusion s64[4194304]", 0.5]]


def read(name, profiles=(), device_ops=None):
    trace = None if device_ops is None else {
        "queries": 3, "busy_s": 2.0, "device_ops": device_ops}
    return load_module("layers", name).read(
        {"setup_profiles": list(profiles), "trace": trace, "spans": {}})


@pytest.mark.parametrize("name", sorted(COUNTS))
@pytest.mark.parametrize("profiles, census", [
    ([{"programCensus": CENSUS}], CENSUS),
    # the compile and the warm execution carry the same object: the last
    ([{"programCensus": STALE}, {"programCensus": CENSUS}], CENSUS),
    # opcode counts stand for a stale executable too
    ([{"programCensus": STALE}], STALE),
    # a retrace's census replaces the rung before it; a profile without one
    # (a program from before PR 37, the parent) is passed over
    ([{"programCensus": dict(CENSUS, gatherElements=1, scatterUpdates=1,
                             sortOperandElements=1)},
      {"programCensus": CENSUS}, {}], CENSUS),
    ([{}], None), ([], None),
    # an executable whose text could not be read counted nothing
    ([{"programCensus": dict(CENSUS, instructions=0)}], None)])
def test_work_counts_read_the_last_census(name, profiles, census):
    want = None if census is None else census[COUNTS[name]]
    assert read(name, profiles) == want


@pytest.mark.parametrize("profiles, device_ops, value", [
    ([{"programCensus": CENSUS}], DEVICE_OPS, 100.0 * 1.8 / 2.5),
    ([{"programCensus": CENSUS}], DEVICE_OPS[:2], 100.0),
    # a stale executable says nothing about this tree: no number, not 0
    ([{"programCensus": STALE}], DEVICE_OPS, None),
    ([{"programCensus": CENSUS}], None, None),          # an untraced run
    ([{"programCensus": CENSUS}], [], None),            # a CPU rehearsal
    ([{}], DEVICE_OPS, None),                           # the parent
    # a streamed query's outer census holds fragments, no one `ops`
    ([{"programCensus": {"instructions": 5, "fragments": {}}}],
     DEVICE_OPS, None)])
def test_top_ops_attributed_share(profiles, device_ops, value):
    got = read("top_ops_attributed_pct", profiles, device_ops)
    assert got == (value if value is None else pytest.approx(value))


def test_the_entries_list_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in sorted(COUNTS) + ["top_ops_attributed_pct"]:
        m = by_name[name]
        assert m["workloads"] == FOUR, name
        assert (m["layer"], m["moves"]) == ("kernels (ops/)", "query_p50_ms")
    assert by_name["top_ops_attributed_pct"]["source"] == "device_trace"
    assert all(by_name[n]["source"] == "program_counter" for n in COUNTS)
