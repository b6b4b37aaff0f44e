"""Device time by operator (`obs/device_profile`): the pure reducer on a
hand-made trace, `Session.device_profile` and EXPLAIN ANALYZE on a CPU (no
device planes: census and host spans, device numbers None), and the mesh's
per-device task walls where a profile exists."""
import json

import jax
import numpy as np
import pytest

from trino_tpu.obs import device_profile as dp
from trino_tpu.obs import opstats
from trino_tpu.session import tpch_session

from oracle import bench_module

MS = 1_000_000
OPS = {
    "fusion.1": ["Join#2/permute_lanes", "gather", "u32[1024,4]", 2],
    # named by its operand only (rule 3 of obs/program_census)
    "fusion.2": ["Aggregate#1/accumulate", "scatter", "u32[16]", 3],
    "while.3": ["", "while", "(s32[], s32[8])", 0],
    "sort.4": ["Aggregate#1/sort_group_ids", "sort", "(s32[1024], s32[1024])",
               1],
}


def _line(chip, scale):
    """One chip's `XLA Ops` line: a gather, a `while` that holds a sort
    and an op no census knows, a scatter.  Names as the profiler writes
    them: the whole HLO line."""
    t, s = 1_000 * chip, scale * MS
    return [
        ("%fusion.1 = u32[1024,4]{1,0} fusion(%p)", t, t + 4 * s),
        ("%while.3 = (s32[], s32[8]) while(%t)", t + 5 * s, t + 15 * s),
        ("%sort.4 = (s32[1024], s32[1024]) sort(%a, %b)", t + 6 * s,
         t + 9 * s),
        ("%mystery.9 = s32[8] copy(%x)", t + 9 * s, t + 11 * s),
        ("%fusion.2 = u32[16]{0} fusion(%q)", t + 40 * s, t + 40 * s + MS),
    ]


PLANES = {
    "/host:CPU": {"python": [("launch", 0, 50 * MS)]},
    "/device:TPU:0": {"XLA Ops": _line(0, 1), "XLA Modules": [
        ("jit_frag", 0, 41 * MS)]},
    "/device:TPU:1": {"XLA Ops": _line(1, 2)},
    "/device:TPU:2": {"XLA Ops": []},   # a chip that ran nothing
}


@pytest.fixture(scope="module")
def chips():
    return dp.reduce(PLANES, OPS)


def test_reducer_gives_each_chip_its_own_numbers_not_a_mean(chips):
    assert sorted(chips) == ["/device:TPU:0", "/device:TPU:1"]
    assert chips["/device:TPU:0"]["busyMs"] == pytest.approx(4 + 10 + 1)
    assert chips["/device:TPU:1"]["busyMs"] == pytest.approx(8 + 20 + 1)


@pytest.mark.parametrize("chip, by_operator, attributed, inherited", [
    ("/device:TPU:0", {"Join#2": 4.0, "Aggregate#1": 3.0 + 1.0},
     100.0 * 7 / 15, 100.0 * 1 / 15),
    ("/device:TPU:1", {"Join#2": 8.0, "Aggregate#1": 6.0 + 1.0},
     100.0 * 14 / 29, 100.0 * 1 / 29)])
def test_reducer_counts_self_time_once(chips, chip, by_operator, attributed,
                                       inherited):
    c = chips[chip]
    # an inherited scope places its time, and is counted apart
    assert c["byOperator"] == pytest.approx(by_operator)
    assert c["inheritedPct"] == pytest.approx(inherited)
    # the `while` keeps what its body's ops did not take; the unknown op
    # and the unscoped `while` are busy time no operator is named for
    assert c["attributedPct"] == pytest.approx(attributed) and attributed < 100
    assert sum(c["byKind"].values()) == pytest.approx(c["busyMs"])
    assert c["byKind"]["unknown"] == pytest.approx(
        2.0 * (1 + (chip[-1] == "1")))


def test_reducer_rows_say_step_kind_and_slots(chips):
    rows = chips["/device:TPU:0"]["rows"]
    assert rows[0][:4] == ["", "while", 8, 5.0]       # what its body left
    assert ["Join#2/permute_lanes", "gather", 1024, 4.0, 1, False] in rows
    assert ["Aggregate#1/sort_group_ids", "sort", 1024, 3.0, 1, False] in rows
    assert ["Aggregate#1/accumulate", "scatter", 16, 1.0, 1, True] in rows
    assert chips["/device:TPU:0"]["byStep"]["Aggregate#1/accumulate"] == 1.0


def test_reducer_without_a_device_plane_returns_none():
    assert dp.reduce({"/host:CPU": PLANES["/host:CPU"]}, OPS) is None
    assert dp.reduce({}, None) is None


def test_a_stale_census_attributes_nothing():
    stale = {k: ["", v[1], v[2], 0] for k, v in OPS.items()}
    chip = dp.reduce(PLANES, stale)["/device:TPU:0"]
    assert chip["attributedPct"] == chip["inheritedPct"] == 0.0
    assert not chip["byOperator"]
    assert chip["busyMs"] == pytest.approx(15.0)


def test_frames_take_measured_device_time_where_the_profile_has_it(chips):
    frames = [
        {"operatorId": 1, "operatorType": "Aggregate", "deviceWallS": 9.0},
        {"operatorId": 2, "operatorType": "Join", "deviceWallS": 9.0},
        {"operatorId": 3, "operatorType": "TableScan", "deviceWallS": 9.0},
    ]
    dp.apply_device_time(frames, dp.slowest_by_operator(chips))
    assert [f["deviceWallS"] for f in frames] == [0.007, 0.008, 9.0]


def test_last_module_cuts_each_chip_to_the_program_launched_last():
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_frag", 20, 40), ("jit_devgen_orders", 0, 10)],
            "XLA Ops": [("%fusion.1", 1, 5), ("%fusion.1", 21, 30),
                        ("%sort.4", 30, 40)]},
        "/host:CPU": PLANES["/host:CPU"]}
    cut = dp.last_module(planes)
    assert cut["/device:TPU:0"]["XLA Ops"] == [
        ("%fusion.1", 21, 30), ("%sort.4", 30, 40)]
    assert cut["/host:CPU"] is planes["/host:CPU"]
    assert len(planes["/device:TPU:0"]["XLA Ops"]) == 3   # a copy was cut


def test_format_profile_prints_per_chip_then_the_census(chips):
    text = dp.format_profile({"device": chips, "census": {
        "fragment": "abc", "tempBytes": 123, "generatedCodeBytes": 77,
        "gathers": 2, "instructions": 9, "scopedInstructions": 4,
        "byOperator": {"Join#12": {"gathers": 2, "gatherElements": 2048},
                       "Aggregate#3": {"scatters": 1, "instructions": 5}}}})
    assert text.startswith("Device time by operator (compiled program):")
    assert "/device:TPU:0: busy 15.000ms, attributed 46.7% by the" in text
    assert "6.7% more (~)" in text and "~Aggregate#1/accumulate" in text
    assert "/device:TPU:1: busy 29.000ms" in text
    assert "(unattributed)" in text
    assert "tempBytes 123, argumentBytes 0, outputBytes 0, " \
        "generatedCodeBytes 77" in text
    assert "scoped instructions 4 of 9" in text
    # every operator's counters, in plan order
    assert text.index("  Aggregate#3: instructions 5, scatters 1") < \
        text.index("  Join#12: gathers 2, gatherElements 2048")


# -- through the session ----------------------------------------------------

SF = 0.01


def _q3():
    q = bench_module("queries", "q3")
    return q.sql(q.draw(np.random.default_rng(3700000041), q.RANGES))


@pytest.fixture(scope="module")
def session():
    return tpch_session(SF, device_cpu_fallback=False)


def test_device_profile_on_a_cpu_has_census_and_spans_and_no_device_numbers(
        session):
    # the result cache is on: a profile still executes the program
    rows = session.execute(_q3()).to_pylist()
    p = session.device_profile(_q3())
    assert p["device"] is None and p["wallMs"] > 0
    assert p["census"]["scopedInstructions"] > 0 and p["census"]["ops"]
    assert p["hostSpans"]["launch"][0] == 1
    assert {"device_lanes", "device_get", "materialize_host"} <= set(
        p["hostSpans"])
    assert session.execute(_q3()).to_pylist() == rows


def test_capture_refuses_while_another_profile_runs(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with pytest.raises(dp.ProfileBusy, match="another profile"):
            with dp.capture():
                pass
    finally:
        jax.profiler.stop_trace()
    with dp.capture() as cap:       # and works again afterwards
        pass
    assert isinstance(cap["planes"], dict)


@pytest.mark.parametrize("wanted, busy", [
    (False, False), (False, True), (True, True)])
def test_capture_if_free_gives_nothing_instead_of_failing(
        tmp_path, wanted, busy):
    if busy:
        jax.profiler.start_trace(str(tmp_path))
    try:
        with dp.capture_if_free(wanted) as cap:
            pass
        assert cap == {}
    finally:
        if busy:
            jax.profiler.stop_trace()


@pytest.mark.parametrize("section", [
    "Query:", "Operator timeline", "TPU kernel profile:", "Compiles:"])
def test_explain_analyze_keeps_its_eager_sections(session, section):
    text = "\n".join(
        r[0] for r in session.execute("explain analyze " + _q3()).to_pylist())
    assert section in text
    # then the compiled program's census totals; on a CPU no device table
    tail = text[text.index("Compiles:"):]
    assert "Compiled program census (fragment " in tail
    assert "tempBytes " in tail and "gatherElements " in tail
    assert "generatedCodeBytes " in tail and "  Aggregate#3: " in tail
    assert "Device time by operator" not in text
    assert "[eager]" in text    # the profile printed is the eager pass's
    assert "DEGRADED" not in text and "not profiled" not in text


def test_explain_analyze_says_when_its_eager_pass_ran_degraded(session):
    from trino_tpu.runtime import supervisor as sv
    from trino_tpu.utils.faults import FaultInjector

    sv.reset_default_supervisor()
    # the eager pass runs under the process's default supervisor
    sv.default_supervisor().fault_injector = FaultInjector.from_spec(
        json.dumps({"device_loss": {"nth": 1}}))
    try:
        text = "\n".join(r[0] for r in session.execute(
            "explain analyze " + _q3()).to_pylist())
    finally:
        sv.reset_default_supervisor()
    assert "DEGRADED: this eager pass met a device fault (device " in text
    assert "ran again on the CPU backend" in text
    assert "TopN" in text and "rows=10" in text     # its rows stand


def test_explain_analyze_prints_why_the_compiled_program_was_not_profiled(
        session, monkeypatch):
    def refuse(self, plan):
        raise dp.ProfileBusy("another profile is running\nsecond line")

    monkeypatch.setattr(type(session), "_profile_plan", refuse)
    text = "\n".join(r[0] for r in session.execute(
        "explain analyze " + _q3()).to_pylist())
    assert "Compiled program: not profiled (ProfileBusy: another profile " \
        "is running)" in text
    assert "Operator timeline" in text


def test_mesh_task_walls_are_the_chips_busy_time_where_a_profile_exists(
        monkeypatch):
    ndev = 4
    measured = {
        "/device:TPU:%d" % d: {
            "busyMs": 100.0 + d, "byOperator": {"Aggregate#1": 90.0 + d}}
        for d in range(ndev)}
    monkeypatch.setattr(dp, "reduce", lambda planes, ops: measured)
    s = tpch_session(SF, distributed=True, num_devices=ndev,
                     device_cpu_fallback=False, result_cache=False,
                     operator_stats=True)
    s.execute("select l_returnflag, count(*) from lineitem "
              "group by l_returnflag")
    tasks = sorted((t for st in s.last_timeline["stages"]
                    for t in st["tasks"]), key=lambda t: t["nodeId"])
    assert [t["nodeId"] for t in tasks] == [
        "device-%d" % d for d in range(ndev)]
    assert [t["wallS"] for t in tasks] == pytest.approx(
        [(100.0 + d) / 1e3 for d in range(ndev)])


@pytest.mark.parametrize("planes, busy", [
    (False, False),    # a CPU: no profile is taken
    (True, False),     # every attempt profiled; no device plane in it
    (True, True)])     # another profile runs: the query does not fail
def test_mesh_task_walls_stay_row_shares_without_a_device_plane(
        monkeypatch, tmp_path, planes, busy):
    monkeypatch.setattr(dp, "has_device_planes", lambda: planes)
    s = tpch_session(SF, distributed=True, num_devices=4,
                     device_cpu_fallback=False, result_cache=False,
                     operator_stats=True)
    if busy:
        jax.profiler.start_trace(str(tmp_path))
    try:
        s.execute("select count(*) from lineitem")
    finally:
        if busy:
            jax.profiler.stop_trace()
    walls = [t["wallS"] for st in s.last_timeline["stages"]
             for t in st["tasks"]]
    assert len(walls) == 4 and max(walls) <= s.last_timeline["wallS"]
    assert opstats.OPERATOR_FIELDS   # the frames' schema is untouched
