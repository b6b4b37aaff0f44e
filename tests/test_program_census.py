"""The compiled fragment accounts for itself by operator: named scopes in
the lowering (`_TraceCtx.visit`, the `ops/` steps) and the census of the
optimized program taken once per compile (`obs/program_census`)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.cache.compile_cache import plan_ordinals
from trino_tpu.exec.local import LocalExecutor
from trino_tpu.obs import program_census as pc
from trino_tpu.session import tpch_session

from oracle import bench_module

SF = 0.01
OPERATOR = re.compile(r"\b([A-Z][A-Za-z]*#\d+)\b")


class Lowered(Exception):
    pass


def _session(**props):
    return tpch_session(SF, device_cpu_fallback=False, result_cache=False,
                        **props)


def _lowered_text(monkeypatch, sql):
    """(StableHLO without debug info, with it) of the one fragment `sql`
    traces in a session of its own; nothing is compiled."""
    seen = {}

    def lower_only(fn, *args):
        low = fn.lower(*args)
        seen["plain"] = low.as_text()
        seen["debug"] = low.as_text(debug_info=True)
        raise Lowered()

    monkeypatch.setattr(LocalExecutor, "_compile_fragment",
                        staticmethod(lower_only))
    s = _session(compile_cache=False)
    with pytest.raises(Lowered):
        s.execute(sql)
    return seen["plain"], seen["debug"], s.plan(sql)


def _texts(query):
    q = bench_module("queries", query)
    return [q.sql(q.draw(np.random.default_rng(seed), q.RANGES))
            for seed in (3700000001, 3700000029)]


@pytest.mark.parametrize("query", ["q3", "q18", "q1"])
def test_scopes_are_the_plans_operators_in_two_sessions_and_two_literals(
        query, monkeypatch):
    first, second = _texts(query)
    assert first != second
    names = []
    for sql in (first, second):
        _plain, debug, plan = _lowered_text(monkeypatch, sql)
        order, by_ord = plan_ordinals(plan)
        of_plan = {"%s#%d" % (type(n).__name__, o) for o, n in by_ord.items()}
        found = set(OPERATOR.findall(debug))
        # every scope is a node of the plan under its pre-order ordinal ...
        assert found and found <= of_plan
        # ... and every operator that lowers to work has one
        assert {n for n in of_plan if n.split("#")[0] in (
            "Aggregate", "Join", "SemiJoin", "TopN")} <= found
        names.append(found)
    assert names[0] == names[1]


def test_scopes_live_in_the_debug_info_only(monkeypatch):
    plain, debug, _plan = _lowered_text(monkeypatch, _texts("q3")[0])
    assert not OPERATOR.search(plain) and "permute_lanes" not in plain
    assert "permute_lanes" in debug and "build_direct" in debug


def test_a_scope_leaves_the_stablehlo_as_it_was():
    def step(x, idx):
        return jnp.cumsum(x[idx] * 2)

    def scoped(x, idx):
        with jax.named_scope("Aggregate#3"):
            return jax.named_scope("permute_lanes")(step)(x, idx)

    x, idx = jnp.arange(64), jnp.arange(64)[::-1]
    a = jax.jit(step).lower(x, idx).as_text()
    b = jax.jit(scoped).lower(x, idx).as_text()
    assert a.replace("jit_step", "") == b.replace("jit_scoped", "")


# -- the census -------------------------------------------------------------


def _frag(x, idx):
    with jax.named_scope("Join#2"):
        with jax.named_scope("permute_lanes"):
            y = x[idx] * 2                                       # gather
    with jax.named_scope("Aggregate#1"):
        with jax.named_scope("accumulate"):
            z = jnp.zeros(16, x.dtype).at[idx % 16].add(y)       # scatter
        c = jnp.cumsum(y)                                        # cumulative
    with jax.named_scope("TopN#0"):
        s = jax.lax.sort((y, idx), num_keys=1)                   # sort
    return z, c, s


@pytest.fixture(scope="module")
def census():
    x = jnp.arange(1024, dtype=jnp.int32)
    compiled = jax.jit(_frag).lower(x, (x * 7) % 1024).compile()
    return pc.census(compiled), compiled


@pytest.mark.parametrize("key, expected", [
    ("gathers", 1), ("gatherElements", 1024),
    ("scatters", 1), ("scatterUpdates", 1024),
    ("sorts", 1), ("sortOperandElements", 2 * 1024),
    ("collectives", 0), ("whileLoops", 0)])
def test_census_counts_a_hand_written_program(census, key, expected):
    assert census[0][key] == expected


def test_census_names_each_operator_and_its_memory(census):
    c, compiled = census
    by = c["byOperator"]
    assert by["Join#2"]["gathers"] == 1
    assert by["Aggregate#1"]["scatters"] == 1
    assert by["TopN#0"]["sorts"] == 1
    # `cumsum`'s cached lowering carries no name stack: rule 3 gives it
    # its input's operator, and it counts as cumulative work there
    assert c["cumulativeOps"] >= 1
    assert sum(o["cumulativeOps"] for o in by.values()) == c["cumulativeOps"]
    assert 0 < c["scopedInstructions"] <= c["instructions"]
    assert c["tempBytes"] == compiled.memory_analysis().temp_size_in_bytes
    kinds = {kind for _scope, kind, _shape, _rule in c["ops"].values()}
    assert {"gather", "scatter", "sort", "cumulative"} <= kinds
    assert set(c) <= set(pc.CENSUS_FIELDS)


HLO = """HloModule jit_frag, is_scheduled=true

%fused_computation (p0: s32[8], p1: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  %p1 = s32[8]{0} parameter(1)
  %mul.1 = s32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(frag)/Project#4/mul"}
  %mul.2 = s32[8]{0} multiply(%mul.1, %p0), metadata={op_name="jit(frag)/Project#4/mul"}
  ROOT %gather.1 = s32[8]{0} gather(%mul.2, %p1), offset_dims={}, metadata={op_name="jit(frag)/Join#2/permute_lanes/gather"}
}

%fused_computation.1 (p0.1: s32[8]) -> s32[8] {
  %p0.1 = s32[8]{0} parameter(0)
  ROOT %rw = s32[8]{0:T(128)} reduce-window(%p0.1, %p0.1), window={size=8}, to_apply=%add, metadata={op_name="reduce_window_sum"}
}

%add (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %sum = s32[] add(%a, %b)
}

%body (arg: (s32[], s32[8])) -> (s32[], s32[8]) {
  %arg = (s32[], s32[8]{0}) parameter(0)
  %gte = s32[8]{0} get-tuple-element(%arg), index=1
  %ag = s32[32]{0} all-gather(%gte), dimensions={0}, metadata={op_name="jit(frag)/while/body/Join#2/_broadcast/all_gather"}
  ROOT %t = (s32[], s32[8]{0}) tuple(%gte, %gte)
}

%cond (arg.1: (s32[], s32[8])) -> pred[] {
  %arg.1 = (s32[], s32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (x: s32[8], i: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %i = s32[8]{0} parameter(1)
  %fusion = s32[8]{0:T(128)} fusion(%x, %i), kind=kLoop, calls=%fused_computation
  %fusion.1 = s32[8]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation.1
  %copy = s32[8]{0} copy(%x)
  %w = (s32[], s32[8]{0}) while(%copy), condition=%cond, body=%body
  ROOT %scatter.9 = s32[8]{0} scatter(%x, %i, %fusion.1), to_apply=%add, metadata={op_name="jit(frag)/Aggregate#1/accumulate/scatter-add"}
}
"""


@pytest.mark.parametrize("name, expected", [
    # rule 2: the costliest class inside decides, not the majority
    ("fusion", ["Join#2/permute_lanes", "gather", "s32[8]", 2]),
    # rule 3: nothing inside names an operator, the first operand does
    ("fusion.1", ["Join#2/permute_lanes", "cumulative", "s32[8]", 3]),
    ("scatter.9", ["Aggregate#1/accumulate", "scatter", "s32[8]", 1]),
    # rule 4: parameters carry no scope, so neither does their copy
    ("copy", ["", "elementwise", "s32[8]", 0]),
    ("w", ["", "while", "(s32[], s32[8])", 0]),
    # a `while` body is a top level of its own, transformations dropped
    ("ag", ["Join#2/_broadcast", "collective", "s32[32]", 1])])
def test_attribution_rule_on_a_written_module(name, expected):
    assert pc.census_of_text(HLO)["ops"][name] == expected


def test_a_fusion_named_by_an_inherited_instruction_is_inherited_too():
    # the reduce-window inside takes its scope from the multiply before it
    # (rule 3 inside the fusion): the fusion is placed, and says by which rule
    text = HLO.replace(
        "ROOT %rw = s32[8]{0:T(128)} reduce-window(%p0.1, %p0.1)",
        '%m = s32[8]{0} multiply(%p0.1, %p0.1), metadata={op_name='
        '"jit(frag)/Project#4/mul"}\n'
        "  ROOT %rw = s32[8]{0:T(128)} reduce-window(%m, %m)")
    c = pc.census_of_text(text)
    assert c["ops"]["fusion.1"] == ["Project#4", "cumulative", "s32[8]", 3]
    # rule 1 is counted before any other rule renames an instruction
    assert c["scopedInstructions"] == \
        pc.census_of_text(HLO)["scopedInstructions"] + 1


def test_what_leaves_the_process_has_no_instruction_map():
    one = dict(pc.census_of_text(HLO), fragment="a")
    assert "ops" not in pc.without_ops(one) and one["ops"]
    assert pc.without_ops(one)["byOperator"] is one["byOperator"]
    outer = pc.without_ops(pc.merge(None, one))
    assert outer["gatherElements"] == 8
    assert "ops" not in outer["fragments"]["a"]
    assert pc.without_ops(None) is None


def test_written_module_totals_and_the_stale_case():
    c = pc.census_of_text(HLO)
    assert (c["fusions"], c["mixedFusions"], c["whileLoops"]) == (2, 1, 1)
    assert (c["collectives"], c["collectiveBytes"]) == (1, 32 * 4)
    assert (c["scatterUpdates"], c["gatherElements"]) == (8, 8)
    assert c["byOperator"]["Join#2"]["cumulativeOps"] == 1
    # an executable from before the scopes: the same opcodes, no operator
    stale = pc.census_of_text(re.sub(r"[A-Z][A-Za-z]*#\d+/", "", HLO))
    assert stale["scopedInstructions"] == 0 < stale["instructions"]
    assert stale["gatherElements"] == 8 and not stale["byOperator"]
    assert not any(rec[0] or rec[3] for rec in stale["ops"].values())


def test_scope_of_takes_the_innermost_operator_and_drops_transformations():
    assert pc.scope_of(
        "jit(frag_1)/TopN#1/Aggregate#3/Join#5/jit(probe)/probe_direct/"
        "while/body/gather") == "Join#5/probe_direct"
    assert pc.scope_of("jit(f)/Aggregate#3/mul") == "Aggregate#3"
    assert pc.scope_of("jit(f)/reduce_window_sum") == ""


def test_merge_sums_the_fragments_of_a_streamed_query_once_each():
    one = dict(pc.census_of_text(HLO), fragment="a", tempBytes=100)
    two = dict(one, fragment="b", tempBytes=70)
    outer = pc.merge(pc.merge(pc.merge(None, one), two), one)
    assert sorted(outer["fragments"]) == ["a", "b"]
    assert outer["gatherElements"] == 16 and outer["tempBytes"] == 100
    assert outer["fragments"]["a"] is one


# -- the sort group-by permutes its rows once (PR 38) -------------------------

# `Aggregate#n` -> gathers in the parent's program (PR 37's tree, this
# lowering: CPU, SF 0.01), where `sort_group_ids` gathered both neighbours of
# every key plane and its callers gathered the keys, and `sel`, again
PARENT_GATHERS = {
    "q3": {"Aggregate#3": 17},
    "q18": {"Aggregate#3": 39, "Aggregate#14": 14},
    "mesh_q3": {"Aggregate#3": 39},
}


@pytest.mark.parametrize("cell", sorted(PARENT_GATHERS))
def test_the_group_sort_gathers_nothing_and_its_aggregate_gathers_less(cell):
    """`sort_group_ids` sorts, `_group_sort` permutes every lane once and
    `run_collisions` compares the sorted key lanes with their one-row
    shift: no gather is left under the sort's scope (on the mesh neither
    under the final step's), and none came back under another name."""
    mesh = {"distributed": True, "num_devices": 4} if cell == "mesh_q3" else {}
    s = _session(compile_cache=False, **mesh)
    s.execute(_texts(cell.replace("mesh_", ""))[0])
    census = s.last_kernel_profile["programCensus"]
    assert census["scopedInstructions"]
    gathers = [scope for scope, kind, _shape, _rule in census["ops"].values()
               if kind == "gather"]
    assert gathers
    assert not [g for g in gathers if g.endswith("sort_group_ids")
                or g.endswith("run_collisions")]
    for operator, before in PARENT_GATHERS[cell].items():
        assert 0 < census["byOperator"][operator]["gathers"] < before


# -- where the census goes --------------------------------------------------


def test_warm_query_carries_the_cache_entrys_census_and_takes_none():
    s = _session()
    sql = _texts("q3")[0] + "-- census"
    s.execute(sql)
    cold = s.last_kernel_profile["programCensus"]
    assert cold["instructions"] and cold["scopedInstructions"]
    assert cold["fragment"] in [
        k["digest"] for k in s.last_kernel_profile["kernels"]]
    s.tracer.spans.clear()
    s.execute(sql)
    names = [sp.name for sp in s.tracer.spans]
    assert "launch" in names and "program_census" not in names
    assert s.last_kernel_profile["programCensus"] is cold
    assert any(entry.get("census") is cold
               for entry in s.caches.compile_cache._entries.values())


def test_a_compile_opens_the_census_span_under_xla_compile():
    s = _session(compile_cache=False)
    s.tracer.spans.clear()
    s.execute(_texts("q3")[1])
    spans = {sp.name: sp for sp in s.tracer.spans}
    assert spans["program_census"].parent_id == spans["xla_compile"].span_id
    assert spans["program_census"].attributes["fragment"]
