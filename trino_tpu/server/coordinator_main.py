"""Standalone coordinator entrypoint:
``python -m trino_tpu.server.coordinator_main``.

The coordinator-crash chaos harness (tests/test_recovery.py) needs a
coordinator the OS can actually kill — an in-process CoordinatorServer
shares its fate with the test runner, so kill -9 semantics (query state machine vaporized mid-flight,
clients' sockets refuse instantly, only the mmap'd WAL survives) are only
reachable with a real child process.  This entrypoint boots one
distributed CoordinatorServer, prints a single JSON line
``{"nodeId": ..., "uri": ..., "port": ...}`` on stdout so the parent can
target it, and sleeps until killed.  Restarting it on the SAME port with
the same ``coordinator_recovery_dir`` exercises the full recovery path:
surviving workers re-announce to the fixed URI within one heartbeat, the
WAL replays, and in-flight FTE queries resume from committed spools.

Coordinator-level fault injection (``--fault-injection``) arms the
seeded ``coordinator_death`` site — ``os._exit(137)`` immediately after
a chosen WAL transition lands in the mmap'd segment — which the
in-process runner must never fire.
"""
from __future__ import annotations

import argparse
import json
import signal
import sys
import time

DEFAULT_CATALOGS = [["tpch", "tpch", {"tpch.scale-factor": 0.01}]]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="run one trino_tpu coordinator process"
    )
    p.add_argument(
        "--catalogs", default=None,
        help="JSON [[name, connector, config], ...]; default: tpch sf0.01",
    )
    p.add_argument(
        "--properties", default=None,
        help="JSON session properties (coordinator_recovery_dir, "
        "retry_policy, event_journal_dir, ...)",
    )
    p.add_argument(
        "--port", type=int, default=0,
        help="bind port; a restart MUST reuse the crashed coordinator's "
        "port so surviving workers and polling clients reconnect",
    )
    p.add_argument(
        "--fault-injection", default=None,
        help="coordinator-level FaultInjector spec (JSON) arming the "
        "coordinator_death site at a chosen WAL transition",
    )
    args = p.parse_args(argv)

    # parity with the in-process topology: conftest/force_cpu enable
    # x64 everywhere else, and a coordinator stuck on int32 overflows
    # on wide aggregates
    from .. import enable_x64

    enable_x64()

    from ..session import Session
    from .coordinator import CoordinatorServer

    spec = json.loads(args.catalogs) if args.catalogs else DEFAULT_CATALOGS
    props = json.loads(args.properties) if args.properties else {}
    fault_injection = (
        json.loads(args.fault_injection) if args.fault_injection else None
    )
    session = Session(config=props)
    for name, connector, config in spec:
        session.create_catalog(name, connector, config)
    server = CoordinatorServer(
        session, port=args.port, distributed=True,
        fault_injection=fault_injection,
    ).start()
    print(json.dumps({
        "nodeId": server.coordinator.node_id,
        "uri": server.uri,
        "port": server.port,
    }), flush=True)

    # SIGTERM is the graceful stop (tests use it for clean teardown);
    # only SIGKILL is the crash under test
    stopping = {"flag": False}

    def _on_sigterm(_sig, _frame):
        stopping["flag"] = True

    signal.signal(signal.SIGTERM, _on_sigterm)
    try:
        while not stopping["flag"]:
            time.sleep(0.2)
    except KeyboardInterrupt:
        pass
    server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
