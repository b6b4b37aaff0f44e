"""Compile the main path's device programs for a TPU v5e that is described,
not attached: the chip's own compiler (Mosaic for the pallas kernels, XLA
for the rest) accepts or refuses them here, at the real row counts, at no
chip time.  Nothing runs, so nothing here is a device number.

All cases live in this one file and compile in the test's own process:
one process at a time may load the TPU library, and the topology is
described inside a fixture so every xdist worker collects the same tests.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpch_sql import QUERIES
from trino_tpu.connectors import tpch_device
from trino_tpu.exec.local import LocalExecutor
from trino_tpu.exec.shapes import resolve_ladder
from trino_tpu.obs import program_census
from trino_tpu.ops import aggregation as agg_ops
from trino_tpu.ops import pallas_kernels as pk
from trino_tpu.session import tpch_session

ROWS = {"sf1": 6_001_215, "sf10": 59_986_052}  # lineitem, by the generator
HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    """A compile for a described chip is written to jax's persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _q6_emit(t):
    """Q6-shaped closure: date/discount/quantity predicate, one product
    split into 16-bit planes, global aggregate (no group id)."""
    pred = (
        (t["d"] >= 8766) & (t["d"] < 9131)
        & (t["disc"] >= 5) & (t["disc"] <= 7) & (t["q"] < 2400)
    )
    v = t["p"] * t["disc"]
    return pred, None, [1, v & 0xFFFF, v >> 16]


def _kernel_program(kernel, n, sds):
    if kernel == "_count_kernel":
        return (
            jax.jit(lambda f, g: pk.grouped_count(f, g, 9)),
            (sds((n,), jnp.bool_), sds((n,), jnp.int32)),
        )
    if kernel == "_plane_kernel":
        return (
            jax.jit(lambda v, g: pk.grouped_sum_i64(v, g, 9)),
            (sds((n,), jnp.int64), sds((n,), jnp.int32)),
        )
    cols = {k: sds((n,), jnp.int32) for k in ("d", "disc", "q", "p")}
    return (
        jax.jit(lambda c, live: pk.fused_agg_sums(c, live, _q6_emit, 3, 1)),
        (cols, sds((n,), jnp.bool_)),
    )


@pytest.mark.parametrize("size", sorted(ROWS))
@pytest.mark.parametrize("kernel", sorted(pk.KERNEL_REGISTRY))
def test_pallas_kernel_compiles_for_v5e(kernel, size, one_chip,
                                        no_persistent_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_program(kernel, ROWS[size], sds)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES


def test_lineitem_generator_q6_columns_sf1_fits_hbm(one_chip,
                                                    no_persistent_cache):
    q = resolve_ladder({}).quantize
    cols = ("l_quantity", "l_extendedprice", "l_discount", "l_shipdate")
    fn = jax.jit(
        tpch_device._gen_lineitem(cols, q(1_500_000), q(ROWS["sf1"]), 1.0)
    )
    scalar = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    mem = fn.lower(scalar, scalar).compile().memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert 0 < total < HBM_BYTES


def test_sharded_lineitem_generator_q1_columns_sf10_on_four_chips(
        topo, no_persistent_cache):
    """The mesh's scan producer at the size of the four-chip cell: one
    SPMD program, every chip generating its quarter of SF10's order space
    (3.75 M orders, ~15.0 M rows, the 16,777,216 rung) into its own HBM."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    q = resolve_ladder({}).quantize
    cols = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
            "l_returnflag", "l_linestatus", "l_shipdate")
    mesh = Mesh(np.array(topo.devices[:4]), ("workers",))
    fn = jax.jit(tpch_device._per_shard(
        tpch_device._gen_lineitem(
            cols, q(15_000_000 // 4), q(ROWS["sf10"] // 4 + 20_000), 10.0),
        mesh,
    ))
    ranges = jax.ShapeDtypeStruct(
        (4,), jnp.int64,
        sharding=NamedSharding(mesh, PartitionSpec("workers")))
    compiled = fn.lower(ranges, ranges).compile()
    mem = compiled.memory_analysis()   # bytes on each device
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert 0 < total < HBM_BYTES // 4
    # every shard is made where it stays: nothing crosses the interconnect
    text = compiled.as_text()
    assert not any(op in text for op in
                   ("all-reduce", "all-gather", "all-to-all",
                    "collective-permute"))


@contextlib.contextmanager
def _fragments_compiled_for(one_chip):
    """Spy on the executor's ahead-of-time compile: each fragment program
    the engine builds is ALSO traced as the chip would trace it (pallas
    live, TPU reduction choice) and compiled for the described device.
    The query itself carries on, on the CPU."""
    texts = []
    orig = LocalExecutor._compile_fragment
    enabled, use_masked = pk.enabled, agg_ops._use_masked

    def spy(fn, *args):
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), args)
        pk.enabled = lambda: True
        agg_ops._use_masked = lambda cap: cap <= agg_ops._SMALL_SEG_CAP
        try:
            # a fresh wrapper: jit caches traces by the wrapped function
            on_chip = jax.jit(lambda *a: fn.__wrapped__(*a))
            texts.append(on_chip.lower(*shapes).compile().as_text())
        finally:
            pk.enabled, agg_ops._use_masked = enabled, use_masked
        return orig(fn, *args)

    LocalExecutor._compile_fragment = staticmethod(spy)
    try:
        yield texts
    finally:
        LocalExecutor._compile_fragment = staticmethod(orig)


@pytest.mark.parametrize("query", [6, 1])
def test_fused_scan_aggregate_fragment_compiles_for_v5e(
        query, one_chip, no_persistent_cache):
    """The whole jitted fragment of Q6 / Q1 — megakernel with the plan's
    real `emit` closure plus the finalize tail — as the engine builds it.
    (A python-int term or clip bound entering the kernel as an int64
    scalar made Mosaic's lowering recurse; only this compile shows it.)"""
    s = tpch_session(0.01, result_cache=False, device_cpu_fallback=False)
    with _fragments_compiled_for(one_chip) as texts:
        rows = s.execute(QUERIES[query][0]).to_pylist()
    assert rows
    assert texts and all("tpu_custom_call" in t for t in texts)


def test_compaction_at_q3_sizes_is_one_sort_for_v5e(
        one_chip, no_persistent_cache):
    """`_maybe_compact` at `tpch_sf1.q3`'s own sizes (lineitem's 8,388,608
    slots after `l_shipdate >` into the 4,194,304 rung): row ids by one
    single-key sort, then the stacked row gather of two int64 lanes.  The
    `jnp.nonzero` it replaced compiled to a scatter-add fusion (744 ms a
    query on the chip) with 219 MB of temporaries."""
    from trino_tpu.ops.filter_project import compact_indices, permute_lanes

    n, cap = 8_388_608, 4_194_304

    def compact(sel, a, b):
        idx = compact_indices(sel, cap)
        return idx, permute_lanes({"a": (a, sel), "b": (b, sel)}, idx)

    def sds(dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    compiled = jax.jit(compact).lower(
        sds(jnp.bool_), sds(jnp.int64), sds(jnp.int64)).compile()
    text = compiled.as_text()
    # (function names ride in the metadata: none here may say "scatter")
    assert " sort(" in text and "scatter" not in text
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES


def _scatter_kinds(text):
    """The jax primitives (`scatter-add`, `scatter-min`, ...) behind the
    scatter instructions of a compiled program, from their metadata: an
    int64 combiner is split into 32-bit compares, its name is not."""
    return set(re.findall(
        r" scatter\(.*op_name=\"[^\"]*/(scatter[-\w]*)\"", text))


def test_two_arbitraries_at_q3_sizes_read_sorted_runs_for_v5e(
        one_chip, no_persistent_cache):
    """`accumulate(..., seg=)` at `tpch_sf1.q3`'s own sizes (1,048,576
    group-by slots, `cap` 262,144): Q3's two `arbitrary`s of its
    functionally dependent group keys read each group's first live row off
    the sorted run, one reversed `cummin` and a `cap`-sized gather each.
    The `_seg_min` of row ids they replaced compiled to one `scatter`
    fusion each (96 + 73 ms a query on the chip; 1,530 each at the mesh's
    16,777,216 slots)."""
    n, cap = 1_048_576, 262_144
    specs = [agg_ops.AggSpec("arbitrary", "o_orderdate", "d"),
             agg_ops.AggSpec("arbitrary", "o_shippriority", "p")]

    def picks(gid, sel, date, dok, prio, pok, by_run):
        seg = agg_ops.SortedSegments(gid, cap) if by_run else None
        lanes = {"o_orderdate": (date, dok), "o_shippriority": (prio, pok)}
        return agg_ops.accumulate(specs, lanes, gid, sel, cap, seg=seg)

    def sds(dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    args = (sds(jnp.int64), sds(jnp.bool_), sds(jnp.int32), sds(jnp.bool_),
            sds(jnp.int32), sds(jnp.bool_))
    texts = {
        by_run: jax.jit(picks, static_argnums=6).lower(*args, by_run)
        .compile().as_text() for by_run in (True, False)}
    # (function names ride in the metadata: none here may say "scatter")
    assert "scatter" not in texts[True]
    assert "reduce-window" in texts[True]
    # without the runs (direct-domain and global group-bys) it stays
    assert "scatter" in texts[False]


def test_mesh_q3_fragment_at_sf10_shards_fits_four_chips(
        topo, no_persistent_cache, monkeypatch):
    """The SPMD fragment of `tpch_sf10_mesh4_q3.q3` at its own shard sizes
    (lineitem 16,777,216 slots a chip, orders 4,194,304, customer 524,288),
    compiled for the described 2x2 host.  The session plans Q3 at SF 10 on a
    mesh of the described chips; the generators hand out shapes where they
    would hand out lanes, and the query stops where it would launch.  Before
    PR 35 the chip's compiler refused this program: the wide sum stacked its
    four chunk lanes (n, 4) for one `segment_sum`, which XLA:TPU pads 32x
    (two buffers of 8 GB: "Used 16.63G of 15.75G hbm")."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from oracle import bench_module
    from trino_tpu.parallel import mesh_executor as MX

    mesh = Mesh(np.array(topo.devices[:4]), ("workers",))
    sharded = NamedSharding(mesh, PartitionSpec("workers"))

    class Lane(jax.ShapeDtypeStruct):
        addressable_shards = ()      # `scanShards` of the kernel profile

    def generator(table, cols, lo, hi, cap, sf, cap_orders, mesh=None):
        raw = (tpch_device._gen_lineitem(cols, cap_orders, cap, sf)
               if table == "lineitem"
               else tpch_device._gen_flat(table, cols, cap, sf))
        ranges = jax.ShapeDtypeStruct((4,), jnp.int64, sharding=sharded)
        shapes = jax.eval_shape(
            tpch_device._per_shard(raw, mesh), ranges, ranges)
        lanes = jax.tree.map(
            lambda s: Lane(s.shape, s.dtype, sharding=sharded), shapes)
        return (lambda lo, hi: lanes), False

    class Launch(Exception):
        pass

    seen = {}

    class Ctx(MX._MeshTraceCtx):
        def __init__(self, *a):
            super().__init__(*a)
            seen["ctx"] = self

    def compile_only(fn, prep):
        seen["shapes"] = sorted({
            lane[0].shape for lanes in prep.values()
            for sym, lane in lanes.items() if sym != "__count__"})
        seen["compiled"] = fn.lower(prep).compile()
        raise Launch()

    monkeypatch.setattr(MX, "default_mesh", lambda n=None: mesh)
    monkeypatch.setattr(tpch_device, "_generator", generator)
    monkeypatch.setattr(MX.MeshExecutor, "mesh_trace_ctx_cls", Ctx)
    monkeypatch.setattr(MX.MeshExecutor, "_compile_fragment",
                        staticmethod(compile_only))
    # trace as the chip would (as `_fragments_compiled_for` does)
    monkeypatch.setattr(pk, "enabled", lambda: True)
    monkeypatch.setattr(agg_ops, "_use_masked",
                        lambda cap: cap <= agg_ops._SMALL_SEG_CAP)
    q3 = bench_module("queries", "q3")
    s = tpch_session(10.0, distributed=True, num_devices=4,
                     device_cpu_fallback=False, result_cache=False,
                     compile_cache=False)
    with pytest.raises(Launch):
        s.execute(q3.sql({"segment": "BUILDING", "date": "1995-03-15"}))
    assert seen["shapes"] == [(4, 524_288), (4, 4_194_304), (4, 16_777_216)]
    counts = seen["ctx"].op_counts
    assert counts["broadcastExchanges"] == 2
    assert counts["broadcastExchangeSlots"] == 4 * (524_288 + 4_194_304)
    # the plan-time estimate's rung on the first trace: no retrace to come
    assert counts["groupStateExchangeSlots"] == 4 * 262_144
    assert counts["sortGroupRows"] == 16_777_216 + 4 * 262_144
    assert "partitionedExchanges" not in counts
    # both steps' `arbitrary`s and the final keys read their sorted runs:
    # the census of the program compiled for the chip has PR 36's hand
    # counts (no `scatter-min` left: 12 scatters and 9 sorts before)
    census = program_census.census(seen["compiled"])
    assert (census["scatters"], census["sorts"]) == (7, 13)
    # the two group sorts permute their rows once and verify their hash
    # runs on the sorted lanes (PR 38: 89 gathers before, 6 + 3 of them
    # under `sort_group_ids`, over 16,777,216 and 1,048,576 slots)
    assert census["gathers"] == 75
    assert not [scope for scope, kind, _shape, _rule in census["ops"].values()
                if kind == "gather" and scope.endswith("sort_group_ids")]
    assert census["tempBytes"] == (
        seen["compiled"].memory_analysis().temp_size_in_bytes)
    assert census["scopedInstructions"] > 0 and census["collectives"] > 0
    mem = seen["compiled"].memory_analysis()   # bytes on each device
    assert 0 < (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes) < HBM_BYTES // 2
    # the exchanges: the two builds and the partial group state gathered,
    # overflow flags summed; nothing repartitioned under `automatic`
    text = seen["compiled"].as_text()
    assert "all-gather" in text and "all-reduce" in text
    assert "all-to-all" not in text and "collective-permute" not in text
    # no first-row pick is a scatter any more (two of 16,777,216 updates
    # were 29 % of the query); `build_direct`'s scatters combine by
    # `maximum` (`scatter-max`), the final step's sums add: they stay
    kinds = _scatter_kinds(text)
    assert "scatter-max" in kinds and "scatter-min" not in kinds


def _fragment_for_chip(one_chip, monkeypatch, sf, sql, **props):
    """Plan `sql` at `sf`, hand out shapes where the generators would hand
    out lanes, and compile the one fragment for the described chip where
    the query would launch it: what the trace counted and the compiled
    program."""
    def generator(table, cols, lo, hi, cap, sf, cap_orders, mesh=None):
        raw = (tpch_device._gen_lineitem(cols, cap_orders, cap, sf)
               if table == "lineitem"
               else tpch_device._gen_flat(table, cols, cap, sf))
        arg = jax.ShapeDtypeStruct((), jnp.int64)
        lanes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip),
            jax.eval_shape(raw, arg, arg))
        return (lambda lo, hi: lanes), False

    class Launch(Exception):
        pass

    seen = {}

    class Ctx(LocalExecutor.trace_ctx_cls):
        def __init__(self, *a):
            super().__init__(*a)
            seen["ctx"] = self

    def compile_only(fn, *args):
        seen["shapes"] = sorted({
            lane[0].shape for prep in args for lanes in prep.values()
            for sym, lane in lanes.items() if sym != "__count__"})
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), args)
        seen["compiled"] = jax.jit(
            lambda *a: fn.__wrapped__(*a)).lower(*shapes).compile()
        raise Launch()

    monkeypatch.setattr(tpch_device, "_generator", generator)
    monkeypatch.setattr(LocalExecutor, "trace_ctx_cls", Ctx)
    monkeypatch.setattr(LocalExecutor, "_compile_fragment",
                        staticmethod(compile_only))
    monkeypatch.setattr(pk, "enabled", lambda: True)
    monkeypatch.setattr(agg_ops, "_use_masked",
                        lambda cap: cap <= agg_ops._SMALL_SEG_CAP)
    s = tpch_session(sf, device_cpu_fallback=False, result_cache=False,
                     compile_cache=False, **props)
    with pytest.raises(Launch):
        s.execute(sql)
    return seen


def test_q5_fragment_at_sf10_fits_one_chip(
        one_chip, no_persistent_cache, monkeypatch):
    """The one fragment of `tpch_sf10_q5.q5` at its own sizes (lineitem
    67,108,864 slots, orders 16,777,216, customer 2,097,152, supplier
    131,072), compiled for the described chip: the session plans Q5 at SF
    10, the generators hand out shapes where they would hand out lanes, and
    the query stops where it would launch.  The parent's order joined
    customer x supplier on the nation key first (6e9 rows estimated); this
    one joins five times through direct tables, supplier on two columns."""
    from oracle import bench_module

    q5 = bench_module("queries", "q5")
    seen = _fragment_for_chip(one_chip, monkeypatch, 10.0,
                              q5.sql({"region": "ASIA", "date": "1994-01-01"}))
    assert seen["shapes"] == [(128,), (131_072,), (2_097_152,),
                              (16_777_216,), (67_108_864,)]
    counts = seen["ctx"].op_counts
    assert counts["directJoins"] == 5 and counts["residualKeyJoins"] == 1
    assert "hashedKeyJoins" not in counts and "sortJoins" not in counts
    # lineitem's 67,108,864 slots meet orders x customer once; every later
    # join runs on the compacted 16,777,216, then 524,288
    assert counts["joinSlots"] == (67_108_864 + 16_777_216 + 4_194_304
                                   + 2 * 524_288)
    census = program_census.census(seen["compiled"])
    assert census["tempBytes"] < 15.75 * 1024**3
    # lineitem's join compacts before it gathers orders x customer's
    # columns: its gathers run over the 16,777,216 survivors' slots, none
    # over the probe's 67,108,864 (the supplier join, residual key, keeps
    # the gather-then-compact order over 16,777,216)
    assert counts["survivorGatherJoins"] == 1
    widths = {}
    for scope, kind, shape, _rule in census["ops"].values():
        m = re.fullmatch(r"(Join#\d+)/(?:_maybe_compact/)?permute_lanes",
                         scope)
        if kind == "gather" and m:
            widths.setdefault(m.group(1), set()).update(
                int(d) for d in re.findall(r"\[(\d+)", shape))
    assert widths and 67_108_864 not in set.union(*widths.values())
    assert {16_777_216} in widths.values()
    mem = seen["compiled"].memory_analysis()
    assert census["tempBytes"] == mem.temp_size_in_bytes
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES


def test_q21_fragment_at_sf10_fits_one_chip(
        one_chip, no_persistent_cache, monkeypatch):
    """The one fragment of `tpch_sf10_q21.q21` at its own sizes (lineitem
    67,108,864 slots, read three times, orders 16,777,216, supplier
    131,072), compiled for the described chip.  The late lines of the
    nation's suppliers compact to 2,097,152 slots (the late-line share
    estimated from both dates' ranges, 0.51 of 0.63 true); both mark joins
    read each key's least and greatest l_suppkey over lineitem's slots,
    no pair buffer, no slot sized from the source's."""
    from oracle import bench_module

    q21 = bench_module("queries", "q21")
    seen = _fragment_for_chip(one_chip, monkeypatch, 10.0,
                              q21.sql({"nation": "SAUDI ARABIA"}),
                              query_max_memory_bytes=16 * 1024**3)
    assert seen["shapes"] == [(128,), (131_072,), (16_777_216,),
                              (67_108_864,)]
    counts = seen["ctx"].op_counts
    assert counts["semiJoins"] == counts["residualSemiJoins"] == 2
    assert counts["semiWorkSlots"] == 2 * (67_108_864 + 2_097_152)
    census = program_census.census(seen["compiled"])
    scopes = {scope for scope, *_ in census["ops"].values()}
    assert any(re.fullmatch(r"SemiJoin#\d+/_semi_residual(/.*)?", sc)
               for sc in scopes)
    assert not any("_semi_hit" in sc for sc in scopes)
    mem = seen["compiled"].memory_analysis()
    print("q21 sf10 temp %.2f GB, args %.2f GB, counts %s" % (
        mem.temp_size_in_bytes / 1e9, mem.argument_size_in_bytes / 1e9,
        counts))
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES


def test_q1_fragment_at_sf10_is_one_resident_program_for_v5e(
        one_chip, no_persistent_cache, monkeypatch):
    """`tpch_sf10.q1` at its own sizes under the session's 8 GiB: the
    streaming gate reckons the fused aggregate by what the fused kernel
    allocates (an int32 copy of each of its 4 decimal columns, `live` and
    one low word in flight: 24 B a slot), so lineitem's 67,108,864 slots
    compile as one program for the described chip, with no tile.  That
    program's temporaries lie within a third of the fused term the gate
    charged."""
    from oracle import bench_module
    from trino_tpu.exec import streaming

    q1 = bench_module("queries", "q1")
    seen = _fragment_for_chip(one_chip, monkeypatch, 10.0,
                              q1.sql({"delta": 90}))
    assert seen["shapes"] == [(67_108_864,)]
    ex = seen["ctx"].ex
    gate = ex.kernel_profile["programEstimate"]
    assert gate["fused_aggregates"] == 1 and gate["streamed"] is False
    assert ex.kernel_profile["fusedAggregates"] == 1
    fused = 6 * streaming.FUSED_LANE_BYTES * 67_108_864
    mem = seen["compiled"].memory_analysis()
    print("q1 sf10 temp %.3f GB, args %.3f GB, out %.6f GB, fused term "
          "%.3f GB, estimate %.3f GB" % (
              mem.temp_size_in_bytes / 1e9, mem.argument_size_in_bytes / 1e9,
              mem.output_size_in_bytes / 1e9, fused / 1e9,
              gate["estimate_bytes"] / 1e9))
    assert abs(mem.temp_size_in_bytes - fused) <= 0.35 * fused
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < gate["estimate_bytes"]
