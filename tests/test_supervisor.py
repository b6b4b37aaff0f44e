"""Chaos suite for the TPU runtime fault supervisor.

Reference parity: testing/trino-faulttolerant-tests BaseFailureRecoveryTest
extended to DEVICE failure — a seeded device loss or wedge at the
supervised dispatch boundary (runtime/supervisor.py) must cost attribution
(a DeviceFaultError naming the culprit kernel), quarantine, and degraded
CPU execution — never a wrong answer or a dead node.  Every fault here is
deterministic (seeded FaultInjector rules), so a failing run replays.
"""
import json
import os
import sqlite3
import sys
import time
import urllib.request

import pytest

from oracle import assert_rows_match, load_tpch
from tpch_sql import QUERIES, oracle_dialect
from trino_tpu.runtime import (
    Breadcrumb,
    DeviceFaultError,
    DeviceSupervisor,
)
from trino_tpu.runtime.supervisor import (
    ACTIVE,
    BLACKLISTED,
    QUARANTINED,
)
from trino_tpu.server.fte import FaultTolerantScheduler
from trino_tpu.server.scheduler import DistributedScheduler, SchedulerError
from trino_tpu.session import Session
from trino_tpu.sql.parser import parse
from trino_tpu.testing import DistributedQueryRunner
from trino_tpu.utils.faults import FaultInjector

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "scripts")
)
from check_dispatch_guard import check_tree  # noqa: E402

SF = 0.001
TPCH = (("tpch", "tpch", {"tpch.scale-factor": SF}),)
Q6 = QUERIES[6][0]


@pytest.fixture(scope="module")
def oracle_conn():
    conn = sqlite3.connect(":memory:")
    load_tpch(conn, SF, ["lineitem"])
    return conn


def _sup(**kw):
    kw.setdefault("node_id", "t")
    kw.setdefault("probe_backoff_s", 0.05)
    kw.setdefault("watchdog_timeout_s", 0.0)
    return DeviceSupervisor(**kw)


# --- supervised boundary unit behavior -----------------------------------


def test_dispatch_passthrough_when_healthy():
    sup = _sup()
    bc = Breadcrumb("k0", query_id="q0")
    assert sup.dispatch(lambda: 41 + 1, bc) == 42
    assert sup.device_state() == ACTIVE
    assert sup.last_breadcrumb is bc


def test_device_loss_names_culprit_kernel_and_quarantines():
    sup = _sup(fault_injector=FaultInjector({"device_loss": {"nth": 1}}))
    bc = Breadcrumb(
        "frag_abc123", query_id="q1", mode="jit",
        shapes={"l_extendedprice": "float64(6005,)"},
        hbm_reserved_bytes=207360,
    )
    with pytest.raises(DeviceFaultError) as ei:
        sup.dispatch(lambda: 1, bc)
    e = ei.value
    assert e.kind == "device_loss"
    assert e.breadcrumb is bc
    # the message is the crash attribution: kernel + HBM reservation
    assert "frag_abc123" in str(e)
    assert "hbm_reserved=207360" in str(e)
    assert "UNAVAILABLE" in e.cause_text
    assert sup.device_state() == QUARANTINED
    # subsequent dispatches are refused at the gate (caller degrades)
    with pytest.raises(DeviceFaultError) as ei2:
        sup.dispatch(lambda: 2, Breadcrumb("k2"))
    assert ei2.value.kind == "device_quarantined"


def test_device_fault_error_is_not_a_jax_runtime_error():
    """exec/local.py's JaxRuntimeError handler (compile-OOM streaming)
    must never swallow a device fault."""
    import jax

    e = DeviceFaultError("device_loss", Breadcrumb("k"))
    assert isinstance(e, RuntimeError)
    assert not isinstance(e, jax.errors.JaxRuntimeError)


def test_unrelated_errors_pass_through_unchanged():
    sup = _sup()

    def boom():
        raise ValueError("INVALID_ARGUMENT-adjacent but not a loss")

    with pytest.raises(ValueError):
        sup.dispatch(boom, Breadcrumb("k"))
    assert sup.device_state() == ACTIVE  # no strike for non-device errors


def test_wedge_trips_watchdog_and_quarantines():
    sup = _sup(
        watchdog_timeout_s=0.2,
        fault_injector=FaultInjector(
            {"device_wedge": {"nth": 1, "stall_s": 1.5}}
        ),
    )
    t0 = time.time()
    with pytest.raises(DeviceFaultError) as ei:
        sup.dispatch(lambda: 1, Breadcrumb("wedgy"))
    assert ei.value.kind == "device_wedge"
    assert time.time() - t0 < 1.4  # watchdog fired, not the full stall
    assert sup.device_state() == QUARANTINED


def test_probe_backoff_then_recovery():
    sup = _sup(
        probe_backoff_s=0.2,
        fault_injector=FaultInjector({"device_loss": {"nth": 1}}),
    )
    with pytest.raises(DeviceFaultError):
        sup.dispatch(lambda: 1, Breadcrumb("k"))
    assert sup.device_state() == QUARANTINED
    # inside the backoff window the canary is not even attempted
    assert sup.maybe_probe() is False
    assert sup.device_state() == QUARANTINED
    time.sleep(0.25)
    # rule exhausted (nth=1 consumed by the dispatch): the canary passes
    assert sup.maybe_probe() is True
    assert sup.device_state() == ACTIVE
    assert sup.dispatch(lambda: 7, Breadcrumb("k2")) == 7


def test_n_strikes_blacklists_for_process_lifetime():
    sup = _sup(max_strikes=2, probe_backoff_s=0.01)
    for strike in range(2):
        sup.fault_injector = FaultInjector({"device_loss": {"nth": 1}})
        with pytest.raises(DeviceFaultError):
            sup.dispatch(lambda: 1, Breadcrumb(f"k{strike}"))
        if sup.device_state() != BLACKLISTED:
            time.sleep(0.03)
            assert sup.maybe_probe() is True  # recovered between strikes
    assert sup.device_state() == BLACKLISTED
    time.sleep(0.05)
    assert sup.maybe_probe() is False  # never probed again
    assert sup.device_state() == BLACKLISTED
    with pytest.raises(DeviceFaultError) as ei:
        sup.dispatch(lambda: 1, Breadcrumb("after"))
    assert ei.value.kind == "device_blacklisted"


def test_node_state_reflects_fallback_policy():
    sup = _sup(fault_injector=FaultInjector({"device_loss": {"nth": 1}}))
    assert sup.node_state() == "ACTIVE"
    with pytest.raises(DeviceFaultError):
        sup.dispatch(lambda: 1, Breadcrumb("k"))
    sup.cpu_fallback_enabled = True
    assert sup.node_state() == "DEGRADED"
    sup.cpu_fallback_enabled = False
    assert sup.node_state() == "QUARANTINED"


def test_breadcrumb_serialization():
    bc = Breadcrumb(
        "dead_beef", query_id="q9", task_id="q9.0.0", node_id="w1",
        mode="jit", shapes={"a": "int64(10,)"}, hbm_reserved_bytes=80,
    )
    d = bc.to_dict()
    assert d["kernel"] == "dead_beef"
    assert d["queryId"] == "q9"
    assert d["taskId"] == "q9.0.0"
    assert d["hbmReservedBytes"] == 80
    assert d["shapes"] == {"a": "int64(10,)"}
    assert d["ts"] > 0


# --- degraded-mode acceptance (local session) ----------------------------


def test_q6_device_loss_degrades_to_cpu_then_recovers(oracle_conn):
    """THE acceptance path: a device loss mid-Q6 still returns correct
    results (degraded CPU execution), the node reports DEGRADED with the
    culprit kernel in the breadcrumb, and a later re-probe restores
    ACTIVE service."""
    expected = oracle_conn.execute(oracle_dialect(Q6)).fetchall()
    s = Session(config={
        "result_cache": False,  # a cache hit would mask the fault path
        "fault_injection": json.dumps({"device_loss": {"nth": 1}}),
        # park re-probes so DEGRADED is observable, not a race (later
        # queries probe at execute() entry and would heal the device)
        "device_probe_backoff_s": 30.0,
    })
    s.create_catalog("tpch", "tpch", {"tpch.scale-factor": SF})
    page = s.execute(Q6)
    assert_rows_match(page.to_pylist(), expected, tol=2e-2, ordered=True)

    sup = s.device_supervisor
    assert sup.device_state() == QUARANTINED
    assert sup.node_state() == "DEGRADED"
    assert sup.fallback_attempted >= 1
    assert sup.fallback_completed >= 1
    bc = sup.last_breadcrumb
    assert bc is not None and bc.kernel, "no crash attribution recorded"
    snap = sup.snapshot()
    assert snap["devices"][0]["lastFaultKind"] == "device_loss"
    assert snap["lastBreadcrumb"]["kernel"] == bc.kernel

    # system.runtime.nodes surfaces the device health for the local node
    rows = s.execute(
        "select node_id, state, device_state, device_strikes "
        "from system.runtime.nodes"
    ).to_pylist()
    assert len(rows) == 1
    node_id, state, device_state, strikes = rows[0]
    assert (node_id, state) == ("local", "active")
    assert device_state == "DEGRADED"
    assert strikes >= 1

    # the fault condition clears: re-probe restores full device service
    s.properties.set("fault_injection", "")
    with sup._lock:
        sup._device(0).next_probe = 0.0  # backoff elapsed
    assert sup.maybe_probe() is True
    assert sup.node_state() == "ACTIVE"
    page2 = s.execute(Q6)
    assert_rows_match(page2.to_pylist(), expected, tol=2e-2, ordered=True)
    assert sup.device_state() == ACTIVE  # recovered run stayed on device


def test_kernel_profile_and_process_forensics_carry_breadcrumb():
    s = Session(config={"result_cache": False})
    s.create_catalog("tpch", "tpch", {"tpch.scale-factor": SF})
    s.execute(Q6)
    # the executor stores the last dispatch crumb in its kernel profile
    prof = s.last_kernel_profile or {}
    bc = prof.get("last_breadcrumb")
    assert bc is not None
    assert bc["kernel"]
    assert bc["mode"] in ("jit", "eager", "device_get", "gate")
    # ... and mirrors it process-globally, for whoever reads a crashed
    # process's last dispatch without knowing which session made it
    from trino_tpu.runtime import last_breadcrumb

    assert (last_breadcrumb() or {}).get("kernel")


# --- distributed chaos ----------------------------------------------------


def test_distributed_q6_device_loss_completes_and_reports(oracle_conn):
    """Distributed Q6 with a seeded device loss on every worker's first
    dispatch: the statement client still gets correct rows (each faulted
    fragment re-ran on CPU), /v1/info advertises DEGRADED device health,
    and once the fault condition clears the re-probe restores ACTIVE."""
    spec = json.dumps({"device_loss": {"nth": 1}})
    with DistributedQueryRunner(
        workers=2, catalogs=TPCH,
        properties={
            "fault_injection": spec,
            # park re-probes so DEGRADED is observable, not a race
            "device_probe_backoff_s": 30.0,
        },
    ) as runner:
        rows = runner.rows(Q6)
        expected = oracle_conn.execute(oracle_dialect(Q6)).fetchall()
        assert_rows_match(rows, expected, tol=2e-2, ordered=True)

        faulted = [
            w for w in runner.workers
            if w.supervisor.snapshot()["devices"][0]["faults"] >= 1
        ]
        assert faulted, "device_loss never fired: test exercised nothing"
        w = faulted[0]
        snap = w.supervisor.snapshot()
        assert snap["state"] == "DEGRADED"
        assert snap["fallbacksCompleted"] >= 1
        assert snap["lastBreadcrumb"]["kernel"]

        with urllib.request.urlopen(
            f"{w.uri}/v1/info", timeout=5.0
        ) as resp:
            doc = json.loads(resp.read())
        assert doc["state"] == "DEGRADED"
        assert doc["device"]["state"] == "DEGRADED"
        assert doc["device"]["devices"][0]["lastFaultKind"] == "device_loss"

        # fault condition gone: allow the announce-loop probe to run now
        w.supervisor.fault_injector = None
        with w.supervisor._lock:
            for d in w.supervisor._devices.values():
                d.next_probe = 0.0
        deadline = time.time() + 10.0
        while (time.time() < deadline
               and w.supervisor.node_state() != "ACTIVE"):
            time.sleep(0.05)
        assert w.supervisor.node_state() == "ACTIVE"


def test_fte_retries_device_lost_task_on_another_worker(oracle_conn):
    """retry-policy=TASK with CPU fallback disabled: the device-lost task
    FAILS on the sick worker and is retried on another node — the query
    still matches the oracle and the sick node ends QUARANTINED."""
    with DistributedQueryRunner(workers=2, catalogs=TPCH) as runner:
        bad = runner.workers[0]
        bad.supervisor.probe_backoff_s = 60.0  # no recovery mid-test
        bad.supervisor.fault_injector = FaultInjector(
            {"device_loss": {"nth": 1}}
        )
        nm = runner.coordinator.coordinator.node_manager
        fte = FaultTolerantScheduler(
            runner.session.catalogs, nm,
            properties={
                "retry_policy": "task",
                "device_cpu_fallback": False,
                "device_probe_backoff_s": 60.0,
            },
        )
        sql = ("select l_returnflag, count(*) c from lineitem "
               "group by l_returnflag order by l_returnflag")
        plan = runner.session._plan_stmt(parse(sql))
        page = fte.run(plan, "q_chaos_device")
        expected = oracle_conn.execute(oracle_dialect(sql)).fetchall()
        assert_rows_match(
            page.to_pylist(), expected, tol=2e-2, ordered=True
        )
        snap = bad.supervisor.snapshot()
        assert snap["devices"][0]["faults"] >= 1, "fault never fired"
        assert snap["devices"][0]["state"] == QUARANTINED
        # fallback disabled: the whole node refuses, scheduler routes away
        assert snap["state"] == "QUARANTINED"
        assert snap["fallbacksAttempted"] == 0


# --- scheduler health-aware placement ------------------------------------


class _StubNodeManager:
    def __init__(self, states):
        self._states = states

    def device_states(self):
        return dict(self._states)


def _scheduler(states, workers):
    return DistributedScheduler(
        catalogs=None, workers=workers,
        node_manager=_StubNodeManager(states),
    )


def test_pick_single_worker_health_ordering():
    workers = [("w1", "http://w1"), ("w2", "http://w2"),
               ("w3", "http://w3")]
    sched = _scheduler({
        "w1": {"state": "DEGRADED"},
        "w3": {"state": "QUARANTINED"},
        # w2 never announced device health: ranks with ACTIVE
    }, workers)
    # ACTIVE beats DEGRADED regardless of the query hash; QUARANTINED is
    # never picked
    for q in range(16):
        assert sched._pick_single_worker(f"q{q}") == ("w2", "http://w2")


def test_quarantined_workers_excluded_from_stage_placement():
    workers = [("w1", "http://w1"), ("w2", "http://w2"),
               ("w3", "http://w3")]
    sched = _scheduler({"w2": {"state": "QUARANTINED"}}, workers)
    assert sched._schedulable_workers() == [
        ("w1", "http://w1"), ("w3", "http://w3")
    ]
    # every node quarantined: refuse with a structured error naming each
    # excluded node (no silent degrade onto known-bad hardware)
    sched_all = _scheduler(
        {w[0]: {"state": "QUARANTINED"} for w in workers}, workers
    )
    with pytest.raises(SchedulerError) as ei:
        sched_all._schedulable_workers()
    msg = str(ei.value)
    assert "NO_NODES_AVAILABLE" in msg
    for w, _uri in workers:
        assert f"{w}=QUARANTINED" in msg
    with pytest.raises(SchedulerError):
        sched_all._pick_single_worker("qx")


def test_degraded_beats_quarantined_for_single_placement():
    workers = [("w1", "http://w1"), ("w2", "http://w2")]
    sched = _scheduler({
        "w1": {"state": "QUARANTINED"},
        "w2": {"state": "DEGRADED"},
    }, workers)
    for q in range(8):
        assert sched._pick_single_worker(f"q{q}") == ("w2", "http://w2")


# --- static dispatch-guard lint ------------------------------------------


def test_no_naked_device_dispatch_in_exec_or_server():
    root = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..")
    )
    checked, violations = check_tree(root)
    assert checked > 0, "dispatch-guard lint scanned nothing"
    assert violations == [], (
        "unsupervised device dispatch found:\n"
        + "\n".join(f"{r}:{n}: {c}" for r, n, c in violations)
    )


# --- a compile is not a wedge ---------------------------------------------


@pytest.mark.parametrize("mode", ["local", "mesh", "devgen"])
def test_compile_longer_than_watchdog_is_not_a_wedge(mode, monkeypatch):
    """Trace + XLA compile run outside the supervised dispatch: a program
    whose compile outlasts device_watchdog_timeout_s still answers on the
    device — no device_wedge, no quarantine, fallback counter untouched."""
    from trino_tpu.connectors import tpch_device
    from trino_tpu.exec.local import LocalExecutor
    from trino_tpu.session import tpch_session
    from trino_tpu.utils.metrics import REGISTRY

    timeout = 2.0
    slow = {"n": 0}

    def slowly(orig):
        def wrapper(*a, **kw):
            out = orig(*a, **kw)
            time.sleep(timeout * 1.5)  # the compile outlasts the watchdog
            slow["n"] += 1
            return out
        return wrapper

    if mode == "devgen":
        # the generator's compile step, which the executor runs before
        # (not inside) the supervised generator dispatch
        tpch_device.clear_jit_cache()
        monkeypatch.setattr(
            tpch_device, "compile_lanes", slowly(tpch_device.compile_lanes)
        )
    else:
        monkeypatch.setattr(
            LocalExecutor, "_compile_fragment",
            staticmethod(slowly(LocalExecutor._compile_fragment)),
        )
    extra = {"distributed": True, "num_devices": 2} if mode == "mesh" else {}
    s = tpch_session(
        SF, result_cache=False, device_cpu_fallback=False,
        device_watchdog_timeout_s=timeout, **extra,
    )
    fallbacks = REGISTRY.counter("trino_tpu_device_fallback_total").total()
    # compile_cache=False: an executable another test already compiled
    # must not stand in for the slow compile
    s.properties.set("compile_cache", False)
    got = s.execute(Q6).to_pylist()
    assert slow["n"] >= 1, "the slowed compile never ran"
    assert got == tpch_session(SF, result_cache=False).execute(Q6).to_pylist()
    assert s.device_supervisor.device_state() == ACTIVE
    assert s.device_supervisor.fallback_attempted == 0
    assert REGISTRY.counter(
        "trino_tpu_device_fallback_total").total() == fallbacks
    if mode == "devgen":
        tpch_device.clear_jit_cache()

