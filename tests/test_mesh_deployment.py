"""TPC-H on a four-device mesh as a deployment: lineitem (orders, customer)
sharded by order range, every shard generated on its own device and kept
there in the session's scan cache, one cached SPMD program a fragment.

The plain reference is the benchmark's (`benchmark/queries/*.py` over
`benchmark/datagen.py`: numpy, exact scaled integers, nothing of the
program); four of conftest's eight virtual CPU devices stand in for the
four chips of one host."""
import json
import threading

import jax
import numpy as np
import pytest

from trino_tpu.connectors import tpch, tpch_device
from trino_tpu.exec.shapes import resolve_ladder
from trino_tpu.obs import compile_observatory
from trino_tpu.parallel import mesh_executor as MX
from trino_tpu.session import tpch_session

from oracle import bench_module

SF = 0.01
NDEV = 4
SEEDS = (11, 2800000007, 2**31 + 5)
PHASES = ("load_scans", "device_lanes", "launch", "device_get",
          "materialize_host")


def _text(query, seed):
    q = bench_module("queries", query)
    params = q.draw(np.random.default_rng(seed), q.RANGES)
    return q, params, q.sql(params)


def _mesh_session(ndev=NDEV, **props):
    assert len(jax.devices()) >= ndev, "conftest provides 8 virtual devices"
    return tpch_session(SF, distributed=True, num_devices=ndev,
                        device_cpu_fallback=False, result_cache=False, **props)


@pytest.fixture(scope="module")
def mesh():
    return _mesh_session()


@pytest.fixture(scope="module")
def one_chip():
    return tpch_session(SF, device_cpu_fallback=False, result_cache=False)


# -- (a) answers ------------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("query", ["q1", "q6", "q3"])
def test_mesh_answer_equals_the_reference_and_one_chip(query, seed, mesh,
                                                       one_chip):
    q, params, sql = _text(query, seed)
    rows = mesh.execute(sql).to_pylist()
    refs, ref_rows = q.reference(bench_module("datagen"), SF, [params])
    assert q.check(rows, refs[0]), (params, rows[:3])
    assert rows == one_chip.execute(sql).to_pylist()
    assert ref_rows["lineitem"] == tpch_device.lineitem_count(
        0, tpch._counts(SF)["orders"])
    prof = mesh.last_kernel_profile
    assert [k["digest"][:7] for k in prof["kernels"]] == ["mesh:%d/" % NDEV]
    shards = prof["scanShards"]
    assert shards and all(
        len({dev for dev, _ in sh}) == NDEV for sh in shards.values())


# -- (b) the sharded generator ------------------------------------------------

GEN_COLS = {
    "lineitem": ("l_orderkey", "l_quantity", "l_extendedprice", "l_discount",
                 "l_tax", "l_returnflag", "l_linestatus", "l_shipdate"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority",
               "o_orderstatus"),
    "customer": ("c_custkey", "c_mktsegment", "c_nationkey"),
}


def _ranges(n, case):
    if case == "uneven":        # n is not divisible by four
        cuts = [n * d // NDEV for d in range(NDEV + 1)]
    else:                       # three devices hold it all, the last nothing
        cuts = [n * d // (NDEV - 1) for d in range(NDEV)] + [n]
    return cuts[:-1], cuts[1:]


@pytest.mark.parametrize("case", ["uneven", "empty_last_shard"])
@pytest.mark.parametrize("table", sorted(GEN_COLS))
def test_shards_concatenated_are_the_one_chip_lanes(table, case):
    q = resolve_ladder({}).quantize
    cols = GEN_COLS[table]
    base = "orders" if table == "lineitem" else table
    n = tpch._counts(SF)[base] - 3
    assert n % NDEV
    lo, hi = _ranges(n, case)
    if table == "lineitem":
        counts = [tpch_device.lineitem_count(a, b) for a, b in zip(lo, hi)]
        cap_orders = q(max(b - a for a, b in zip(lo, hi)))
        whole_orders = q(n)
    else:
        counts = [b - a for a, b in zip(lo, hi)]
        cap_orders = whole_orders = None
    assert (counts[-1] == 0) == (case == "empty_last_shard")
    cap, total = q(max(counts)), sum(counts)
    mesh = MX.default_mesh(NDEV)
    assert tpch_device.compile_lanes(
        table, cols, lo, hi, cap, SF, cap_orders=cap_orders, mesh=mesh
    ) in (True, False)
    # compiled ahead: the lanes' own call finds the executable
    assert not tpch_device.compile_lanes(
        table, cols, lo, hi, cap, SF, cap_orders=cap_orders, mesh=mesh)
    sharded = tpch_device.device_lanes(
        table, cols, lo, hi, cap, SF, counts, cap_orders=cap_orders, mesh=mesh)
    whole = tpch_device.device_lanes(
        table, cols, 0, n, q(total), SF, total, cap_orders=whole_orders)
    for c in cols:
        v, ok = sharded[c]
        assert v.shape == (NDEV, cap) and ok.shape == (NDEV, cap)
        assert len({sh.device.id for sh in v.addressable_shards}) == NDEV
        assert all(sh.data.shape == (1, cap) for sh in v.addressable_shards)
        v, ok = np.asarray(v), np.asarray(ok)
        assert ok.all()
        live = np.concatenate([v[d, :counts[d]] for d in range(NDEV)])
        expect = np.asarray(whole[c][0])
        assert live.dtype == expect.dtype
        assert np.array_equal(live, expect[:total]), c
        assert all(not v[d, counts[d]:].any() for d in range(NDEV)), c


# -- (c) a warm query generates nothing and compiles nothing ------------------


def _drain(session):
    spans = list(session.tracer.spans)
    session.tracer.spans.clear()
    return spans


@pytest.mark.parametrize("seed", SEEDS)
def test_second_execution_finds_lanes_and_program(seed, mesh):
    _q, _params, sql = _text("q1", seed + 1)
    first = mesh.execute(sql).to_pylist()
    observatory = compile_observatory.get_observatory()
    compiles = sum(observatory.counts.values())
    _drain(mesh)
    assert mesh.execute(sql).to_pylist() == first
    spans = _drain(mesh)
    prof = mesh.last_kernel_profile
    assert sum(observatory.counts.values()) == compiles
    assert prof["summary"]["compiles"] == 0
    assert prof["meshProgramCache"] == "hit"
    assert not [s for s in spans if s.name in ("devgen", "xla_compile")]
    assert not prof.get("devgenWallS")
    # nothing re-hashed: at most the edge blocks of four ranges
    assert prof.get("lineCountOrdersHashed", 0) <= \
        2 * NDEV * tpch_device.LINE_COUNT_BLOCK
    # the pushed-down DELTA is part of a scan's identity: this text's
    # entry is the newest
    key, entry = list(mesh._scan_cache.entries.items())[-1]
    assert key[1] == "lineitem" and "l_tax" in key[2]
    assert key[-1] == ("mesh",) + tuple(range(NDEV))
    assert len(entry["devgen"]["shards"]) == NDEV
    assert sum(n for _, _, n in entry["devgen"]["shards"]) == entry["total"]
    assert not [c for c, (v, _) in entry["merged"].items()
                if hasattr(v, "dtype")]
    assert set(entry["dev"]) == set(entry["merged"])
    for v, ok in entry["dev"].values():
        for lane in (v, ok):
            assert len({sh.device.id for sh in lane.addressable_shards}) == NDEV


# -- (d) the generator compiles ahead of its supervised dispatch --------------


def test_sharded_generator_compiles_before_the_supervised_dispatch(
        monkeypatch):
    tpch_device.clear_jit_cache()
    inside = threading.local()
    seen = []
    real_dispatch = MX.MeshExecutor._dispatch
    real_generator = tpch_device._generator

    def dispatch(self, thunk, bc):
        def watched():
            inside.crumb = bc.kernel
            try:
                return thunk()
            finally:
                inside.crumb = None
        seen.append(("dispatch", bc.kernel))
        return real_dispatch(self, watched, bc)

    def generator(*a, **kw):
        fn, compiled_now = real_generator(*a, **kw)
        seen.append(("generator", getattr(inside, "crumb", None),
                     compiled_now))
        return fn, compiled_now

    monkeypatch.setattr(MX.MeshExecutor, "_dispatch", dispatch)
    monkeypatch.setattr(tpch_device, "_generator", generator)
    _q, _params, sql = _text("q6", SEEDS[0])
    s = _mesh_session()
    assert s.execute(sql).to_pylist()
    gens = [e for e in seen if e[0] == "generator"]
    # the compile happened, outside every supervised dispatch ...
    assert [e for e in gens if e[2]] == [("generator", None, True)]
    # ... and the supervised generator dispatch only ran the executable
    assert ("generator", "devgen:lineitem", False) in gens
    order = [e[:2] for e in seen]
    assert order.index(("generator", None)) < order.index(
        ("dispatch", "devgen:lineitem"))
    assert s.last_kernel_profile["devgenCompileS"] > 0
    assert s.last_kernel_profile["devgenWallS"] > 0
    tpch_device.clear_jit_cache()


# -- (e) the phase spans -------------------------------------------------------


@pytest.mark.parametrize("query", ["q1", "q3"])
def test_phase_spans_open_once_a_query_and_tile_execute(query, mesh):
    _q, _params, sql = _text(query, SEEDS[0])
    _drain(mesh)
    mesh.execute(sql)
    # a table is generated once, by the first query that scans it, and a
    # phase opens once in that query too (Q3: three tables, three `devgen`s)
    first = [s.name for s in _drain(mesh)]
    assert first.count("devgen") <= len(_q.TABLES)
    assert [first.count(n) for n in PHASES] == [1] * len(PHASES)
    shares = []
    for _ in range(3):   # a share of a few milliseconds: the best of three
        _drain(mesh)
        mesh.execute(sql).to_pylist()
        spans = _drain(mesh)
        by_name = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        assert {n: len(by_name.get(n, ())) for n in PHASES + ("execute",)} \
            == dict.fromkeys(PHASES + ("execute",), 1)
        execute = by_name["execute"][0]
        phases = [by_name[n][0] for n in PHASES]
        # leaves of `execute`, on the query's own trace
        assert all(p.parent_id == execute.span_id for p in phases)
        assert len({p.trace_id for p in phases + [execute]}) == 1
        inside = sum(p.duration_ms for p in phases)
        assert inside <= execute.duration_ms
        shares.append(100.0 * (execute.duration_ms - inside)
                      / execute.duration_ms)
    assert min(shares) < 10.0, shares


# -- (f) another mesh finds neither the lanes nor the program -------------------


def test_a_smaller_mesh_generates_and_compiles_its_own(mesh):
    _q, _params, sql = _text("q6", SEEDS[1])
    rows = mesh.execute(sql).to_pylist()
    assert mesh.execute(sql).to_pylist() == rows
    assert mesh.last_kernel_profile["meshProgramCache"] == "hit"
    two = _mesh_session(ndev=2)
    assert two.execute(sql).to_pylist() == rows
    prof = two.last_kernel_profile
    assert prof["meshProgramCache"] == "miss" and prof["devgenWallS"] > 0
    assert prof["summary"]["compiles"] == 1
    assert {k[-1] for k in two._scan_cache.entries} == {("mesh", 0, 1)}
    assert all(len({d for d, _ in sh}) == 2
               for sh in prof["scanShards"].values())


def test_a_shrunk_mesh_reuses_neither_lanes_nor_executable():
    from trino_tpu.runtime.supervisor import QUARANTINED

    _q, _params, sql = _text("q6", SEEDS[2])
    s = _mesh_session(device_probe_backoff_s=30.0)
    rows = s.execute(sql).to_pylist()
    assert s.execute(sql).to_pylist() == rows
    assert s.last_kernel_profile["meshProgramCache"] == "hit"
    # the cached program's launch loses device 0: the mesh shrinks to three
    s.properties.set("fault_injection", json.dumps(
        {"device_loss": {"nth": 1, "match": "mesh:"}}))
    assert s.execute(sql).to_pylist() == rows
    prof = s.last_kernel_profile
    assert s.device_supervisor.device_state(device_id=0) == QUARANTINED
    assert prof["meshShrinks"] >= 1
    assert prof["meshProgramCache"] == "miss" and prof["devgenWallS"] > 0
    assert [k["digest"][:7] for k in prof["kernels"]][-1] == "mesh:3/"
    assert {k[-1] for k in s._scan_cache.entries} == {
        ("mesh", 0, 1, 2, 3), ("mesh", 1, 2, 3)}
    assert all({d for d, _ in sh} == {1, 2, 3}
               for sh in prof["scanShards"].values())


# -- (g) the mesh's exchanges and sort group-bys are counted at trace time ----

EXCHANGE_COUNTERS = ("broadcastExchanges", "broadcastExchangeSlots",
                     "partitionedExchanges", "partitionedExchangeSlots",
                     "groupStateExchangeSlots")


@pytest.fixture(scope="module")
def q3_traced():
    """Q3 traced under each join distribution (`compile_cache=False`: the
    counters are written by the query that traces the fragment, and the
    executable cache is process-wide), with the reference's answer."""
    q, params, sql = _text("q3", SEEDS[1])
    refs, _ = q.reference(bench_module("datagen"), SF, [params])
    out = {}
    for dist in ("automatic", "partitioned"):
        s = _mesh_session(compile_cache=False, join_distribution_type=dist)
        rows = s.execute(sql).to_pylist()
        out[dist] = (rows, q.check(rows, refs[0]),
                     dict(s.last_kernel_profile))
    return out


def _shard_cap(prof, column):
    (shards,) = [sh for name, sh in prof["scanShards"].items()
                 if name.endswith("." + column)]
    return shards[0][1]


@pytest.mark.parametrize("counter, expected", [
    ("broadcastExchanges", 2),
    ("partitionedExchanges", 0), ("partitionedExchangeSlots", 0),
    ("sortGroupBys", 2), ("directJoins", 2), ("compactions", 0),
    # the mesh never compacts, so no join compacts before its build gather
    ("survivorGatherJoins", 0),
    # the compiled program's census: a `build_direct` scatter a join and the
    # sums' six; `arbitrary(o_orderdate)`, `arbitrary(o_shippriority)` and
    # the final step's keys read their rows off the sorted runs (a
    # `scatter-min` each before PR 36: 13)
    ("scatters", 8), ("sorts", 12),
    # each group sort permutes its rows once, the final step's keys and
    # accumulators in one stacked gather (PR 38: 62 before)
    ("gathers", 45)])
def test_q3_counts_two_broadcast_joins_and_a_two_step_group_by(
        q3_traced, counter, expected):
    rows, correct, prof = q3_traced["automatic"]
    assert correct, rows[:3]
    assert dict(prof["programCensus"], **prof).get(counter, 0) == expected


def test_one_chip_q3_counts_its_two_arbitraries_and_answers_as_the_mesh(
        q3_traced):
    _q, _params, sql = _text("q3", SEEDS[1])
    s = tpch_session(SF, device_cpu_fallback=False, result_cache=False,
                     compile_cache=False)
    rows = s.execute(sql).to_pylist()
    prof = s.last_kernel_profile
    # its two `arbitrary`s read the sorted run: the scatters left are the
    # joins' `build_direct` and the wide sum's
    assert prof["programCensus"]["scatters"] == 3
    # ... and the gathers the one permutation of the group sort's rows
    # leaves (PR 38: 40 before)
    assert prof["programCensus"]["gathers"] == 35
    assert rows == q3_traced["automatic"][0]


def test_q3_exchange_slots_are_the_shards_slots_times_the_mesh(q3_traced):
    _rows, _ok, prof = q3_traced["automatic"]
    customer, orders, lineitem = (
        _shard_cap(prof, c) for c in ("c_custkey", "o_orderkey", "l_orderkey"))
    # customer is gathered as it lies; orders x customer keeps orders' slots
    # (the mesh does not compact), so what is gathered is slots, not rows
    assert prof["broadcastExchangeSlots"] == NDEV * (customer + orders)
    # local group sort over the probe's slots, final one over the gathered
    # partial state of every device
    local_cap = prof["groupStateExchangeSlots"] // NDEV
    assert 0 < local_cap <= lineitem
    assert prof["sortGroupRows"] == lineitem + NDEV * local_cap
    assert prof["sortGroupCapacity"] >= local_cap


def test_partitioned_q3_counts_its_repartitions_and_answers_alike(q3_traced):
    rows, correct, prof = q3_traced["partitioned"]
    assert correct, rows[:3]
    assert rows == q3_traced["automatic"][0]
    assert prof["partitionedExchanges"] >= 2
    assert prof["partitionedExchangeSlots"] >= 2 * NDEV * 128
    assert prof["partitionedExchangeSlots"] % NDEV == 0


def test_fused_q1_on_the_mesh_carries_no_exchange_or_sort_counter():
    _q, _params, sql = _text("q1", SEEDS[0] + 7)
    # `megakernels="on"`: off the chip the fused kernel runs interpreted
    s = _mesh_session(compile_cache=False, megakernels="on")
    assert s.execute(sql).to_pylist()
    prof = s.last_kernel_profile
    assert prof["meshProgramCache"] == "miss" and prof.get("fusedAggregates")
    assert not [c for c in EXCHANGE_COUNTERS + ("sortGroupBys",)
                if c in prof]


@pytest.mark.parametrize("profiles, value", [
    ([{"broadcastExchangeSlots": 100, "groupStateExchangeSlots": 20}], 120),
    # a warm execution of a cached program carries none: the trace's count
    ([{"broadcastExchangeSlots": 8, "partitionedExchangeSlots": 4}, {}], 12),
    # a capacity retrace replaces the rung before it
    ([{"groupStateExchangeSlots": 5}, {"groupStateExchangeSlots": 9}], 9),
    ([{"partitionedExchangeSlots": 0}], 0),
    ([{}, {"sortGroupRows": 7}], None), ([], None)])
def test_exchange_slots_reader(profiles, value):
    reader = bench_module("layers", "exchange_slots_per_query")
    assert reader.read({"setup_profiles": profiles}) == value
