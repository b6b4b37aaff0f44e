"""Phase spans inside `execute` (utils/tracing + its call sites).

One tracer: a span is cheap, measured on the monotonic clock, mirrored as a
`jax.profiler.TraceAnnotation` (so a running profile records it on the host
plane beside the PJRT events), and every span of one query — prefetch pool
and watchdog threads included — carries one trace id.  Nothing here is
timing-sensitive.
"""
import glob
import json
import os
import re
import threading

import pytest

from trino_tpu.exec.fragment_exec import FragmentExecutor
from trino_tpu.session import tpch_session
from trino_tpu.utils.tracing import OtlpFileExporter, Tracer

SF = 0.01

Q6 = """
select sum(l_extendedprice * l_discount) from lineitem
where l_shipdate >= date '1994-01-01' and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07 and l_quantity < 24
"""

Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) q, count(*) n
from lineitem where l_shipdate <= date '1998-09-02'
group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus
"""

# leaves on the query thread: their sum is set against `execute`
RESIDENT_PHASES = (
    "stream_plan", "load_scans", "device_lanes", "launch", "device_get",
    "materialize_host",
)
SESSION_SPANS = ("query_admit", "query", "parse", "execute", "query_finish")


def _drain(session):
    spans = list(session.tracer.spans)
    session.tracer.clear()
    return spans


def _ancestors(span, by_id):
    names = []
    while span.parent_id in by_id:
        span = by_id[span.parent_id]
        names.append(span.name)
    return names


@pytest.fixture(scope="module")
def resident():
    """One warm resident query's spans (the second execution: plan and
    fragment caches hit)."""
    s = tpch_session(SF, result_cache=False)
    _drain(s)  # the ring is the process's: earlier tests' spans go first
    s.execute(Q6)
    cold = _drain(s)
    page = s.execute(Q6)
    return {"cold": cold, "warm": _drain(s), "page": page}


@pytest.fixture(scope="module")
def streamed():
    """One warm streamed query (a memory limit far under lineitem), with
    the thread and the open span of every prefetch-pool `preload` noted."""
    s = tpch_session(SF, result_cache=False, query_max_memory_bytes=600_000)
    _drain(s)
    s.execute(Q1)
    cold = _drain(s)
    seen = []
    real = FragmentExecutor.preload

    def spy(self, plan):
        seen.append((threading.current_thread(), s.tracer.current_span()))
        return real(self, plan)

    FragmentExecutor.preload = spy
    try:
        page = s.execute(Q1)
    finally:
        FragmentExecutor.preload = real
    return {"spans": _drain(s), "preloads": seen, "page": page,
            "cold": cold}


# --- the tracer itself ----------------------------------------------------


def test_ids_keep_the_w3c_widths_and_differ():
    t = Tracer()
    with t.span("a") as a:
        with t.span("b") as b:
            pass
    with t.span("c") as c:
        pass
    for s in (a, b, c):
        assert re.fullmatch(r"[0-9a-f]{32}", s.trace_id)
        assert re.fullmatch(r"[0-9a-f]{16}", s.span_id)
        assert re.fullmatch(
            r"00-[0-9a-f]{32}-[0-9a-f]{16}-01", s.traceparent)
    assert a.trace_id == b.trace_id != c.trace_id
    assert len({a.span_id, b.span_id, c.span_id}) == 3
    assert b.parent_id == a.span_id and a.parent_id is None


def test_span_is_timed_on_the_monotonic_clock_and_keeps_its_wall_start():
    t = Tracer()
    with t.span("unit", key="value") as s:
        assert s.end is None and s.duration_ms >= 0.0   # still open
    assert s.attributes == {"key": "value"}
    assert s.end is not None and s.end >= s.start > 1e9   # unix seconds
    assert s.duration_ms == pytest.approx((s.end - s.start) * 1e3, abs=1e-3)
    assert list(t.spans) == [s] and t.current_span() is None


def test_span_closes_and_records_when_its_body_raises():
    t = Tracer()
    with pytest.raises(ValueError):
        with t.span("outer"):
            with t.span("inner"):
                raise ValueError("boom")
    assert [s.name for s in t.spans] == ["inner", "outer"]
    assert t.current_span() is None


@pytest.mark.parametrize("parent_open", [True, False])
def test_parent_span_joins_a_thread_with_no_span_open(parent_open):
    t = Tracer()
    box = {}

    def work(parent):
        with t.span("child", parent=parent) as s:
            with t.span("grandchild") as g:
                box["child"], box["grandchild"] = s, g

    with t.span("parent") as parent:
        th = threading.Thread(target=work, args=(parent,))
        if parent_open:
            th.start()
            th.join()
    if not parent_open:          # a closed span hands its trace on too
        work(parent)
    assert box["child"].trace_id == box["grandchild"].trace_id \
        == parent.trace_id
    assert box["child"].parent_id == parent.span_id
    assert box["grandchild"].parent_id == box["child"].span_id
    assert t.current_span() is None


def test_a_span_open_on_the_thread_wins_over_parent():
    t = Tracer()
    with t.span("elsewhere") as elsewhere:
        pass
    with t.span("local") as local:
        with t.span("child", parent=elsewhere) as child:
            pass
    assert child.parent_id == local.span_id
    assert child.trace_id == local.trace_id != elsewhere.trace_id


def test_a_parent_span_wins_over_a_remote_traceparent():
    t = Tracer()
    with t.span("remote") as remote:
        pass
    with t.span("here") as here:
        pass
    with t.span("child", traceparent=remote.traceparent, parent=here) as c:
        pass
    assert (c.trace_id, c.parent_id) == (here.trace_id, here.span_id)


def test_for_trace_is_gone():
    assert not hasattr(Tracer, "for_trace")   # it had no caller


# --- a resident query -----------------------------------------------------


@pytest.mark.parametrize("name", RESIDENT_PHASES + SESSION_SPANS)
def test_resident_query_records_the_span_once(resident, name):
    assert [s.name for s in resident["warm"]].count(name) == 1


@pytest.mark.parametrize("name", RESIDENT_PHASES)
def test_phase_chain_reaches_execute_then_query(resident, name):
    by_id = {s.span_id: s for s in resident["warm"]}
    span = next(s for s in resident["warm"] if s.name == name)
    assert _ancestors(span, by_id) == ["execute", "query", "query_admit"]


def test_resident_query_has_one_trace_id(resident):
    assert len({s.trace_id for s in resident["warm"]}) == 1
    assert len({s.trace_id for s in resident["cold"]}) == 1
    assert {s.trace_id for s in resident["warm"]} \
        != {s.trace_id for s in resident["cold"]}


def test_cold_query_nests_planning_compile_and_generation(resident):
    cold = resident["cold"]
    by_id = {s.span_id: s for s in cold}
    first = {s.name: s for s in reversed(cold)}
    assert _ancestors(first["analyze_plan"], by_id)[0] == "query"
    assert _ancestors(first["optimize"], by_id)[0] == "query"
    assert _ancestors(first["devgen"], by_id)[:2] == ["device_lanes", "execute"]
    assert first["devgen"].attributes["table"] == "lineitem"
    compile_span = first["xla_compile"]
    launch = next(s for s in cold if s.name == "launch")
    assert launch.parent_id == compile_span.span_id


def test_only_the_attributes_with_a_use_are_set(resident):
    # PERF.md section 3 names the use of each; a phase carries none
    warm = {s.name: s for s in resident["warm"]}
    assert warm["query_finish"].attributes == {"state": "FINISHED"}
    assert set(warm["query"].attributes) == {"query_id"}
    # the streaming gate's decision, the one phase that carries some
    gate = warm["stream_plan"].attributes
    assert set(gate) == {"estimate_bytes", "limit_bytes",
                         "fused_aggregates", "streamed"}
    assert gate["streamed"] is False
    for name in RESIDENT_PHASES:
        if name != "stream_plan":
            assert warm[name].attributes == {}


def test_phases_lie_inside_execute(resident):
    warm = {s.name: s for s in resident["warm"]}
    inside = sum(warm[n].duration_ms for n in RESIDENT_PHASES)
    assert 0.0 < inside <= warm["execute"].duration_ms
    outer = warm["parse"].duration_ms + warm["execute"].duration_ms
    assert outer <= warm["query"].duration_ms


# --- a streamed query -----------------------------------------------------


def test_streamed_query_has_one_trace_id_across_threads(streamed):
    assert len({s.trace_id for s in streamed["spans"]}) == 1
    assert streamed["preloads"], "the query did not stream"
    query = next(s for s in streamed["spans"] if s.name == "query")
    for thread, span in streamed["preloads"]:
        assert thread is not threading.main_thread()
        assert span.name == "tile_load" and span.trace_id == query.trace_id


@pytest.mark.parametrize("name", ["tile_wait", "tile_stage", "tile_load",
                                  "tile_upload"])
def test_streamed_query_records_the_span_once_a_tile(streamed, name):
    tiles = len(streamed["preloads"])
    spans = [s for s in streamed["spans"] if s.name == name]
    assert len(spans) == tiles >= 2
    if name in ("tile_wait", "tile_stage"):
        assert sorted(s.attributes["tile"] for s in spans) == list(range(tiles))


def test_tile_execute_groups_the_phases_of_each_tile(streamed):
    spans = streamed["spans"]
    by_id = {s.span_id: s for s in spans}
    tiles = len(streamed["preloads"])
    groups = [s for s in spans if s.name == "tile_execute"]
    source = [s for s in groups if s.attributes["tile"] or
              s.attributes["fragment"] == groups[0].attributes["fragment"]]
    assert len(source) == tiles
    assert len(groups) > tiles          # the downstream fragments too
    launches = [s for s in spans if s.name == "launch"]
    assert len(launches) == len(groups)
    for s in launches:
        assert _ancestors(s, by_id)[:2] == ["tile_execute", "execute"]
    assert [s.name for s in spans].count("stream_plan") == 1


def test_pool_thread_spans_hang_under_execute(streamed):
    spans = streamed["spans"]
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        if s.name == "tile_stage":
            assert _ancestors(s, by_id)[0] == "execute"
        if s.name in ("tile_load", "tile_upload"):
            assert _ancestors(s, by_id)[:2] == ["tile_stage", "execute"]
    # the tiles are generated by the session's first query and found
    # resident by this one; the generator's span opens on the watchdog
    # thread of the staged upload
    assert not any(s.name == "devgen" for s in spans)
    cold = {s.span_id: s for s in streamed["cold"]}
    generated = [s for s in streamed["cold"] if s.name == "devgen"]
    assert generated
    for s in generated:
        assert _ancestors(s, cold)[:3] == [
            "stage_lanes", "tile_upload", "tile_stage"]


# --- the profiler records the spans ---------------------------------------


def _host_events(trace_dir, names):
    import jax.profiler

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def test_profile_holds_the_spans_as_nested_host_events(tmp_path):
    import jax.profiler

    s = tpch_session(SF, result_cache=False)
    s.execute(Q6)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        s.execute(Q6)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path), {"execute", "launch", "device_get"})
    by_name = {}
    for name, start, end in events:
        by_name.setdefault(name, []).append((start, end))
    assert set(by_name) == {"execute", "launch", "device_get"}
    (lo, hi), = by_name["execute"]
    (l0, l1), = by_name["launch"]
    (g0, g1), = by_name["device_get"]
    assert lo <= l0 <= l1 <= g0 <= g1 <= hi   # on the profiler's one clock


# --- a failing query ------------------------------------------------------


@pytest.mark.parametrize("sql, inner", [
    ("select no_such_column from lineitem", "analyze_plan"),
    ("selec 1", "parse"),
])
def test_failing_query_closes_and_flushes_query_finish(tmp_path, sql, inner):
    s = tpch_session(SF)
    path = str(tmp_path / "spans.jsonl")
    prev = s.tracer.exporter
    s.tracer.clear()   # the ring is the process's: drop other tests' spans
    s.tracer.attach_exporter(OtlpFileExporter(path))
    try:
        with pytest.raises(Exception):
            s.execute(sql)
        assert len(s.tracer.spans) == 0 and s.tracer.current_span() is None
    finally:
        s.tracer.exporter = prev
        s.tracer.clear()
    spans = []
    with open(path) as f:
        for line in f:
            for rs in json.loads(line)["resourceSpans"]:
                for ss in rs["scopeSpans"]:
                    spans.extend(ss["spans"])
    by_name = {sp["name"]: sp for sp in spans}
    assert {"query_admit", "query", inner, "query_finish"} <= set(by_name)
    finish = by_name["query_finish"]
    assert {"key": "state", "value": {"stringValue": "FAILED"}} \
        in finish["attributes"]
    assert finish["endTimeUnixNano"] >= finish["startTimeUnixNano"] > 0
    assert len({sp["traceId"] for sp in spans}) == 1
