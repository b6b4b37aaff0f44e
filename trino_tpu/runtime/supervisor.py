"""Supervised kernel dispatch: crash attribution, quarantine, fallback.

The real-TPU bench regressed to ``JaxRuntimeError: UNAVAILABLE: TPU worker
process crashed`` on nearly every config (the round-5 bench run) with nothing naming
the culprit kernel, and a single fault killed the whole worker.  Following
Dean & Barroso's *The Tail at Scale* (CACM 2013) — tolerate component
failure at the system level instead of assuming it away — the TPU runtime
is treated here as a component that WILL crash and wedge:

- every device execution in ``exec/`` crosses :meth:`DeviceSupervisor.
  dispatch`, which records a crash-forensics :class:`Breadcrumb` (kernel
  signature, input shapes/dtypes, HBM reservation, query/task id) BEFORE
  the dispatch, so an ``UNAVAILABLE``/device-loss error — or a wedge
  caught by the watchdog timeout on the dispatch thread — is rethrown as
  a structured :class:`DeviceFaultError` naming the culprit kernel (the
  bisect handle ROADMAP Open item 1 asks for);
- on fault the device goes QUARANTINED with capped-exponential-backoff
  re-probe (a tiny canary kernel); after ``device_fault_max_strikes``
  faults inside STRIKE_WINDOW_S it is BLACKLISTED for the process
  lifetime;
- the owning worker degrades instead of refusing: executors catch the
  fault and re-run the fragment eagerly on the CPU backend, and the node
  advertises DEGRADED through ``/v1/info`` + announcements so schedulers
  route new work toward healthy nodes while FTE retries elsewhere.

Two seeded fault sites (``device_loss``, ``device_wedge`` in
``utils/faults.py``) make the whole path deterministically testable on
the CPU backend.  One supervisor exists per node (each ``WorkerServer``
owns one; a ``Session`` owns one for in-process execution) because the
distributed test runner hosts many nodes in one process — quarantine
must stay node-local.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from ..utils.metrics import REGISTRY

# device health (per device)
ACTIVE = "ACTIVE"
QUARANTINED = "QUARANTINED"
BLACKLISTED = "BLACKLISTED"

# numeric encoding for the trino_tpu_device_state gauge
_STATE_CODE = {ACTIVE: 0.0, QUARANTINED: 1.0, BLACKLISTED: 2.0}

# faults inside this window count toward the blacklist strike total;
# older strikes age out (a flaky hour should not doom a week-old process)
STRIKE_WINDOW_S = 600.0

# re-probe backoff never exceeds this, so a recovered device is found
# within half a minute even after a long quarantine
MAX_PROBE_BACKOFF_S = 30.0

# error-message signatures of a LOST device (runtime/worker crash, device
# dropped off the bus).  Deliberately conservative: INVALID_ARGUMENT and
# compile OOM are program errors that exec/local.py surfaces (or, for a
# compile OOM, streams) and must NOT be swallowed here.
_DEVICE_LOSS_SIGNATURES = (
    "UNAVAILABLE",
    "worker process crashed",
    "DATA_LOSS",
    "DataLoss",
    "device is lost",
    "Device lost",
    "failed to connect to all addresses",
)


def _counter(name: str, help: str):
    return REGISTRY.counter(name, help)


class Breadcrumb:
    """Crash forensics for ONE dispatch, recorded before it happens.

    When the dispatch never returns (crash, wedge, process death) this is
    the only attribution that exists."""

    def __init__(
        self,
        kernel: str,
        query_id: str = "",
        task_id: str = "",
        node_id: str = "",
        mode: str = "jit",
        shapes: Optional[dict] = None,
        hbm_reserved_bytes: int = 0,
    ):
        self.kernel = kernel          # fragment digest / "eager-N" / site
        self.query_id = query_id
        self.task_id = task_id
        self.node_id = node_id
        self.mode = mode              # jit | eager | device_get | probe
        self.shapes = shapes or {}    # input name -> "dtype[shape]"
        self.hbm_reserved_bytes = int(hbm_reserved_bytes)
        self.ts = time.time()

    def to_dict(self) -> dict:
        return {
            "kernel": self.kernel,
            "queryId": self.query_id,
            "taskId": self.task_id,
            "nodeId": self.node_id,
            "mode": self.mode,
            "shapes": dict(self.shapes),
            "hbmReservedBytes": self.hbm_reserved_bytes,
            "ts": self.ts,
        }

    def __str__(self):
        return (
            f"kernel={self.kernel} mode={self.mode} query={self.query_id}"
            f"{' task=' + self.task_id if self.task_id else ''}"
            f" hbm_reserved={self.hbm_reserved_bytes}"
        )


class DeviceFaultError(RuntimeError):
    """A device execution was lost or wedged; names the culprit kernel.

    NOT a ``jax.errors.JaxRuntimeError`` subclass on purpose: the
    executor's JaxRuntimeError handler (compile-OOM streaming
    fallback) must never intercept a
    device fault — this error's handler is the degraded CPU fallback."""

    def __init__(self, kind: str, breadcrumb: Breadcrumb,
                 cause: Optional[BaseException] = None):
        self.kind = kind              # device_loss | device_wedge | ...
        self.breadcrumb = breadcrumb
        self.cause_text = f"{type(cause).__name__}: {cause}" if cause else ""
        detail = f" [{self.cause_text[:200]}]" if cause else ""
        super().__init__(f"{kind}: {breadcrumb}{detail}")


class _WedgeTimeout(Exception):
    """Internal: the watchdog join timed out (dispatch thread wedged)."""


class _SimulatedDeviceLoss(RuntimeError):
    """Seeded ``device_loss`` firing: carries the real fault's signature
    so the classifier treats it exactly like a genuine TPU crash."""


def _is_device_loss(exc: BaseException) -> bool:
    if isinstance(exc, _SimulatedDeviceLoss):
        return True
    msg = str(exc)
    return any(sig in msg for sig in _DEVICE_LOSS_SIGNATURES)


class _DeviceHealth:
    """Per-device fault bookkeeping (state machine + strike window)."""

    def __init__(self, device_id: int):
        self.device_id = device_id
        self.state = ACTIVE
        self.strikes: deque = deque()   # fault timestamps in the window
        self.faults_total = 0
        self.probe_failures = 0
        self.next_probe = 0.0
        self.last_fault_kind = ""


# process-wide forensics: the LAST breadcrumb recorded by ANY supervisor
# instance, plus fallback counters — readable after a crash without
# knowing which session/worker dispatched
_GLOBAL_LOCK = threading.Lock()
_LAST_BREADCRUMB: Optional[Breadcrumb] = None
_FALLBACKS = {"attempted": 0, "completed": 0}


def last_breadcrumb() -> Optional[dict]:
    """The most recent dispatch breadcrumb in this process (or None)."""
    with _GLOBAL_LOCK:
        return _LAST_BREADCRUMB.to_dict() if _LAST_BREADCRUMB else None


def fallback_counts() -> dict:
    """Degraded-CPU-fallback attempts/completions in this process."""
    with _GLOBAL_LOCK:
        return dict(_FALLBACKS)


def _note_fallback(key: str):
    with _GLOBAL_LOCK:
        _FALLBACKS[key] += 1


class DeviceSupervisor:
    """Supervised dispatch boundary + device state machine for one node.

    Wire-up mirrors ``LocalMemoryManager``: the worker/session that owns
    the node creates one and threads it to executors via the exec config
    (``device_supervisor``); chaos specs attach through
    ``fault_injector`` exactly like the memory manager's ``oom`` site."""

    def __init__(
        self,
        node_id: str = "local",
        max_strikes: int = 3,
        probe_backoff_s: float = 1.0,
        watchdog_timeout_s: float = 60.0,
        fault_injector=None,
    ):
        self.node_id = node_id
        self.max_strikes = int(max_strikes)
        self.probe_backoff_s = float(probe_backoff_s)
        self.watchdog_timeout_s = float(watchdog_timeout_s)
        self.fault_injector = fault_injector
        self._lock = threading.RLock()
        self._devices: Dict[int, _DeviceHealth] = {0: _DeviceHealth(0)}
        self.last_breadcrumb: Optional[Breadcrumb] = None
        self.breadcrumbs: deque = deque(maxlen=32)
        self.fallback_attempted = 0
        self.fallback_completed = 0
        # black-box dispatch ring (obs/flight_recorder.py): memory-only by
        # default — the system.runtime.flight_recorder table and bench
        # forensics read the live tail; configure() upgrades it to the
        # crash-safe mmap'd on-disk ring when flight_recorder_dir is set
        from ..obs.flight_recorder import FlightRecorder

        self.flight_recorder = FlightRecorder(
            None, max_records=256, name=node_id
        )
        self._publish_state()

    # -- configuration -------------------------------------------------
    def configure(self, props) -> "DeviceSupervisor":
        """Adopt session/task properties (dict or SessionProperties)."""
        get = props.get if hasattr(props, "get") else None
        if get is None:
            return self
        for attr, key, cast in (
            ("max_strikes", "device_fault_max_strikes", int),
            ("probe_backoff_s", "device_probe_backoff_s", float),
            ("watchdog_timeout_s", "device_watchdog_timeout_s", float),
        ):
            v = get(key)
            if v is not None and v != "":
                try:
                    setattr(self, attr, cast(v))
                except (TypeError, ValueError):
                    pass
        self._configure_flight_recorder(
            get("flight_recorder_dir"), get("flight_recorder_max_records")
        )
        return self

    def _configure_flight_recorder(self, directory, max_records):
        """Re-point the dispatch ring: an empty dir keeps the in-memory
        mirror; a directory turns on the crash-safe on-disk segments."""
        from ..obs.flight_recorder import FlightRecorder

        directory = str(directory or "").strip() or None
        try:
            max_records = int(max_records or 0) or 512
        except (TypeError, ValueError):
            max_records = 512
        cur = self.flight_recorder
        if (
            cur is not None
            and cur.directory == directory
            and (directory is None or cur.max_records == max_records)
        ):
            return
        if cur is not None:
            cur.close()
        self.flight_recorder = FlightRecorder(
            directory, max_records=max_records, name=self.node_id
        )

    # -- state queries -------------------------------------------------
    def _device(self, device_id: int = 0) -> _DeviceHealth:
        d = self._devices.get(device_id)
        if d is None:
            d = self._devices[device_id] = _DeviceHealth(device_id)
        return d

    def healthy(self, device_id: int = 0) -> bool:
        with self._lock:
            return self._device(device_id).state == ACTIVE

    def device_state(self, device_id: int = 0) -> str:
        with self._lock:
            return self._device(device_id).state

    def node_state(self) -> str:
        """ACTIVE (all devices fine) / DEGRADED (some device sick, CPU
        fallback keeps the node serving) / QUARANTINED (every device out
        AND fallback is off — the node cannot host fragments at all)."""
        with self._lock:
            states = [d.state for d in self._devices.values()]
        if all(s == ACTIVE for s in states):
            return "ACTIVE"
        if self.cpu_fallback_enabled:
            return "DEGRADED"
        return "QUARANTINED" if all(s != ACTIVE for s in states) \
            else "DEGRADED"

    # executors consult this before degrading; schedulers consult the
    # announced node_state() — a node with fallback disabled quarantines
    # outright instead of degrading
    cpu_fallback_enabled = True

    def snapshot(self) -> dict:
        """Announcement/``/v1/info`` payload: node + per-device health."""
        with self._lock:
            devices = [
                {
                    "id": d.device_id,
                    "state": d.state,
                    "strikes": len(d.strikes),
                    "faults": d.faults_total,
                    "lastFaultKind": d.last_fault_kind,
                }
                for d in self._devices.values()
            ]
            bc = self.last_breadcrumb
        return {
            "state": self.node_state(),
            "devices": devices,
            "fallbacksAttempted": self.fallback_attempted,
            "fallbacksCompleted": self.fallback_completed,
            "lastBreadcrumb": bc.to_dict() if bc else None,
        }

    # -- breadcrumbs ---------------------------------------------------
    def _record(self, bc: Breadcrumb):
        global _LAST_BREADCRUMB
        bc.node_id = bc.node_id or self.node_id
        with self._lock:
            self.last_breadcrumb = bc
            self.breadcrumbs.append(bc)
        with _GLOBAL_LOCK:
            _LAST_BREADCRUMB = bc

    # -- fault accounting ----------------------------------------------
    def _fault(self, bc: Breadcrumb, kind: str,
               cause: Optional[BaseException],
               device_id: int = 0) -> DeviceFaultError:
        now = time.time()
        transition = None
        with self._lock:
            d = self._device(device_id)
            d.faults_total += 1
            d.last_fault_kind = kind
            d.strikes.append(now)
            while d.strikes and now - d.strikes[0] > STRIKE_WINDOW_S:
                d.strikes.popleft()
            if d.state != BLACKLISTED:
                if len(d.strikes) >= self.max_strikes:
                    # N strikes inside the window: out for the process
                    # lifetime — no probe ever reinstates it
                    d.state = BLACKLISTED
                    transition = BLACKLISTED
                else:
                    d.state = QUARANTINED
                    transition = QUARANTINED
                    d.probe_failures += 1
                    d.next_probe = now + self._backoff(d.probe_failures)
            strikes = len(d.strikes)
        _counter(
            "trino_tpu_device_faults_total",
            "Device faults (loss/wedge) caught at the supervised "
            "dispatch boundary",
        ).inc(kind=kind, node=self.node_id)
        from ..obs import journal

        journal.emit(
            journal.DEVICE_FAULT,
            query_id=bc.query_id, task_id=bc.task_id,
            node_id=self.node_id, severity=journal.ERROR,
            kind=kind, kernel=bc.kernel, device=device_id,
            cause=(f"{type(cause).__name__}: {cause}"[:200]
                   if cause else ""),
        )
        if transition is not None:
            journal.emit(
                journal.DEVICE_QUARANTINE if transition == QUARANTINED
                else journal.DEVICE_BLACKLIST,
                query_id=bc.query_id, node_id=self.node_id,
                severity=journal.ERROR,
                device=device_id, strikes=strikes,
            )
        self._publish_state()
        return DeviceFaultError(kind, bc, cause)

    def _backoff(self, failures: int) -> float:
        base = max(self.probe_backoff_s, 0.001)
        return min(base * (2 ** max(failures - 1, 0)), MAX_PROBE_BACKOFF_S)

    def _publish_state(self):
        g = REGISTRY.gauge(
            "trino_tpu_device_state",
            "Device health per node (0=ACTIVE, 1=QUARANTINED, "
            "2=BLACKLISTED)",
        )
        with self._lock:
            for d in self._devices.values():
                g.set(_STATE_CODE[d.state], node=self.node_id,
                      device=str(d.device_id))

    # -- re-probe ------------------------------------------------------
    def maybe_probe(self, device_id: int = 0) -> bool:
        """Run the canary against a QUARANTINED device once its backoff
        has elapsed; returns True when the device is ACTIVE afterwards.
        BLACKLISTED devices are never probed (process-lifetime ban)."""
        with self._lock:
            d = self._device(device_id)
            if d.state == ACTIVE:
                return True
            if d.state == BLACKLISTED:
                return False
            if time.time() < d.next_probe:
                return False
        ok = self._probe(device_id)
        _counter(
            "trino_tpu_device_probe_total",
            "Canary re-probes of quarantined devices",
        ).inc(node=self.node_id, outcome="ok" if ok else "fail")
        now = time.time()
        with self._lock:
            d = self._device(device_id)
            if d.state == BLACKLISTED:
                return False
            if ok:
                d.state = ACTIVE
                d.probe_failures = 0
                d.next_probe = 0.0
            else:
                d.probe_failures += 1
                d.next_probe = now + self._backoff(d.probe_failures)
        if ok:
            from ..obs import journal

            journal.emit(
                journal.DEVICE_RECOVERED, node_id=self.node_id,
                device=device_id,
            )
        self._publish_state()
        return ok

    def _probe(self, device_id: int) -> bool:
        """Tiny canary kernel.  Consults the fault injector first so a
        seeded fault keeps the canary failing until its rule clears —
        which is what makes quarantine-then-recover deterministic on
        the CPU backend."""
        inj = self.fault_injector
        key = f"probe:{self.node_id}"
        try:
            if inj is not None:
                if inj.fires("device_wedge", key=key):
                    return False  # a wedged device times the canary out
                if inj.fires("device_loss", key=key):
                    raise _SimulatedDeviceLoss(
                        "UNAVAILABLE: TPU worker process crashed "
                        "(injected device_loss, canary)"
                    )
            import jax
            import jax.numpy as jnp

            out = jax.device_get(jnp.arange(8, dtype=jnp.int32) * 2 + 1)
            return int(out[-1]) == 15
        except Exception:
            return False

    # -- the supervised boundary ----------------------------------------
    def dispatch(self, thunk: Callable, bc: Breadcrumb, device_id: int = 0):
        """Run one device execution under supervision.

        Records the breadcrumb, refuses if the device is out (the caller
        degrades to CPU), injects seeded faults, arms the watchdog, and
        translates loss/wedge into :class:`DeviceFaultError`.  Any other
        exception — including the JaxRuntimeErrors the executor handles
        itself (INVALID_ARGUMENT, compile OOM) — passes through."""
        self._record(bc)
        rec = self.flight_recorder
        seq = rec.record_dispatch(bc) if rec is not None else 0
        with self._lock:
            d = self._device(device_id)
            state = d.state
        if state != ACTIVE:
            # no probe here: dispatch is the hot path; probing happens at
            # execute() entry and in the worker's announce loop
            if rec is not None:
                rec.record_fault(seq, bc, "device_" + state.lower())
            raise DeviceFaultError("device_" + state.lower(), bc)
        inj = self.fault_injector
        timeout = self.watchdog_timeout_s

        def supervised():
            if inj is not None:
                rule = inj.rules.get("device_wedge")
                if rule is not None and inj.fires(
                    "device_wedge", key=bc.kernel
                ):
                    # simulated wedge: stall the dispatch thread past the
                    # watchdog (default: twice the timeout)
                    time.sleep(float(rule.get(
                        "stall_s",
                        (timeout * 2.0) if timeout > 0 else 1.0,
                    )))
                if inj.fires("device_loss", key=bc.kernel):
                    raise _SimulatedDeviceLoss(
                        "UNAVAILABLE: TPU worker process crashed "
                        f"(injected device_loss at kernel {bc.kernel})"
                    )
            return thunk()

        start = time.time()
        try:
            if timeout and timeout > 0:
                out = self._with_watchdog(supervised, timeout)
            else:
                out = supervised()
        except _WedgeTimeout as e:
            if rec is not None:
                rec.record_fault(seq, bc, "device_wedge", str(e))
            raise self._fault(bc, "device_wedge", e, device_id) from None
        except Exception as e:
            if _is_device_loss(e):
                if rec is not None:
                    rec.record_fault(seq, bc, "device_loss", str(e))
                raise self._fault(bc, "device_loss", e, device_id) from e
            if rec is not None:
                # not a device fault (INVALID_ARGUMENT, compile OOM, plan
                # errors): still attributed — the ring shows which kernel
                # the error surfaced under before the executor handles it
                rec.record_fault(seq, bc, "error", str(e))
            raise
        if rec is not None:
            rec.record_complete(seq, bc, time.time() - start)
        return out

    def device_get(self, objs, bc: Breadcrumb, device_id: int = 0):
        """Supervised device->host transfer (the sync point where async
        dispatch faults actually surface)."""
        import jax

        return self.dispatch(lambda: jax.device_get(objs), bc, device_id)

    def _with_watchdog(self, fn: Callable, timeout: float):
        """Run fn on a side thread, join with the watchdog timeout.  A
        wedged dispatch cannot be killed (the thread is stuck inside the
        runtime), so it is abandoned as a daemon and the fault raised."""
        box: dict = {}
        done = threading.Event()

        def run():
            try:
                box["result"] = fn()
            except BaseException as e:  # rethrown on the caller thread
                box["error"] = e
            finally:
                done.set()

        t = threading.Thread(
            target=run, daemon=True,
            name=f"dispatch-{self.node_id}",
        )
        t.start()
        if not done.wait(timeout):
            raise _WedgeTimeout(
                f"dispatch exceeded watchdog timeout {timeout:.1f}s"
            )
        if "error" in box:
            raise box["error"]
        return box.get("result")

    # -- degraded-mode bookkeeping --------------------------------------
    def note_fallback_attempt(self, query_id: str = ""):
        with self._lock:
            self.fallback_attempted += 1
        _note_fallback("attempted")
        _counter(
            "trino_tpu_device_fallback_total",
            "Degraded-mode CPU re-executions after a device fault",
        ).inc(node=self.node_id)
        from ..obs import journal

        journal.emit(
            journal.CPU_FALLBACK, query_id=query_id,
            node_id=self.node_id, severity=journal.WARN,
        )

    def note_fallback_completed(self):
        with self._lock:
            self.fallback_completed += 1
        _note_fallback("completed")


# default supervisor for bare executors (no session/worker wiring); the
# distributed runner never uses it — every WorkerServer owns its own
_DEFAULT: Optional[DeviceSupervisor] = None


def default_supervisor() -> DeviceSupervisor:
    global _DEFAULT
    with _GLOBAL_LOCK:
        if _DEFAULT is None:
            _DEFAULT = DeviceSupervisor(node_id="local")
        return _DEFAULT


def reset_default_supervisor():
    """Test isolation: drop the process-default supervisor state."""
    global _DEFAULT, _LAST_BREADCRUMB
    with _GLOBAL_LOCK:
        _DEFAULT = None
        _LAST_BREADCRUMB = None
        _FALLBACKS["attempted"] = 0
        _FALLBACKS["completed"] = 0
