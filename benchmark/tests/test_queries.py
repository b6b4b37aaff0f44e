"""Each query file's reference against the engine at SF 0.01 on three seeds,
and the control (float32 in the reference's place) refused by the same
comparison."""
import jax
import numpy as np
import pytest

import control
import datagen
import run

SF = 0.01
QUERIES = ["q6", "q1", "q3"]
SEEDS = [3, 1234567, 3000000019]


@pytest.fixture(scope="module")
def session():
    jax.config.update("jax_enable_x64", True)
    from trino_tpu.session import tpch_session

    return tpch_session(SF, device_cpu_fallback=False, result_cache=False)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", QUERIES)
def test_reference_equals_the_engine(session, name, seed):
    query = run.load_module("queries", name)
    params = run.draw_sets(query, seed, {"param_sets_per_run": 2})
    refs, rows = query.reference(datagen, SF, params)
    assert set(rows) == set(query.TABLES)
    for p, ref in zip(params, refs):
        got = session.execute(query.sql(p)).to_pylist()
        assert got, "an empty answer checks nothing"
        assert query.check(got, ref), (p, got[:3], ref[:3])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", QUERIES)
def test_the_float32_control_is_refused(name, seed):
    query = run.load_module("queries", name)
    params = run.draw_sets(query, seed, {"param_sets_per_run": 2})
    assert not any(control.control_passes(query, SF, params))


@pytest.mark.parametrize("name", QUERIES)
def test_parameters_stay_in_the_spec_s_ranges(name):
    query = run.load_module("queries", name)
    rng = np.random.default_rng(11)
    for _ in range(300):
        p = query.draw(rng, query.RANGES)
        if name == "q6":
            assert 1993 <= p["year"] <= 1997 and 2 <= p["discount"] <= 9
            assert p["quantity"] in (24, 25)
        elif name == "q1":
            assert 60 <= p["delta"] <= 120
        else:
            assert p["segment"] in datagen.SEGMENTS
            assert "1995-03-01" <= p["date"] <= "1995-03-31"


def test_the_seed_is_all_the_parameters_depend_on():
    query = run.load_module("queries", "q6")
    four = {"param_sets_per_run": 4}
    assert run.draw_sets(query, 2 ** 31 + 5, four) == run.draw_sets(query, 2 ** 31 + 5, four)
    assert run.draw_sets(query, 1, four) != run.draw_sets(query, 2, four)
    assert len({str(p) for p in run.draw_sets(query, 9, four)}) == 4


def test_a_workload_may_narrow_a_range_and_only_narrow_it():
    import yardstick

    query = run.load_module("queries", "q3")
    workload = yardstick.load_json("workloads", "tpch_sf1.q3.json")
    lo, hi = workload["parameters"]["date"]
    assert query.RANGES["date"][0] <= lo <= hi <= query.RANGES["date"][1]
    dates = {run.draw_sets(query, seed, workload)[0]["date"] for seed in range(200)}
    assert min(dates) == lo and max(dates) == hi


def test_shared_parameters_are_drawn_once_for_all_sets_of_a_run():
    import yardstick

    query = run.load_module("queries", "q6")
    workload = yardstick.load_json("workloads", "tpch_sf10.q6.json")
    for seed in range(50):
        sets = run.draw_sets(query, seed, workload)
        assert len({p["year"] for p in sets}) == 1
        assert len({str(p) for p in sets}) == workload["param_sets_per_run"]
    assert len({run.draw_sets(query, seed, workload)[0]["year"] for seed in range(50)}) == 5
