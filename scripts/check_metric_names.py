#!/usr/bin/env python
"""Lint: every metric name in the tree follows the naming convention.

Convention: ``trino_tpu_<subsystem>_<name>`` ending in ``_total`` (event
counts), ``_bytes`` (byte counters), ``_seconds`` (histograms), or
``_state`` (state-machine gauges), with ``<subsystem>`` drawn from the
known set in ``trino_tpu.utils.metrics``.
The registry enforces this at runtime; this lint catches names at rest in
the source — including ones on code paths tests never execute.

Run standalone (``python scripts/check_metric_names.py``, exit 1 on
violations) or as a fast test (tests/test_observability.py wraps it).
"""
from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from trino_tpu.utils.metrics import METRIC_NAME_RE  # noqa: E402

# a metric name is the first string literal of a registry call; matching
# at the call site (not every trino_tpu_* literal) keeps unrelated strings
# like tempdir prefixes out of scope
REGISTRATION_RE = re.compile(
    r'\b(?:counter|gauge|histogram)\(\s*["\'](trino_tpu_[a-z0-9_]+)["\']'
)
# bare prefixed literals elsewhere still get a looser check: anything that
# LOOKS like a metric (ends in a unit suffix) must conform fully
LITERAL_RE = re.compile(
    r'["\'](trino_tpu_[a-z0-9_]+_(?:total|bytes|seconds|state))["\']'
)
# memory-subsystem literals are checked unconditionally (suffix or not):
# the trino_tpu_memory_* gauges are scraped by dashboards keyed on the
# full convention, so even a suffixless literal in a test or helper is a
# violation, not an unrelated string
MEMORY_LITERAL_RE = re.compile(r'["\'](trino_tpu_memory_[a-z0-9_]*)["\']')
# node-lifecycle literals get the same unconditional treatment: the
# trino_tpu_node_* series drive churn dashboards and the chaos harness
# asserts on them by full name
NODE_LITERAL_RE = re.compile(r'["\'](trino_tpu_node_[a-z0-9_]*)["\']')
# incident-journal and query-doctor literals likewise: the doctor's
# acceptance tests assert on these series by full name
JOURNAL_LITERAL_RE = re.compile(
    r'["\'](trino_tpu_journal_[a-z0-9_]*)["\']'
)
DOCTOR_LITERAL_RE = re.compile(r'["\'](trino_tpu_doctor_[a-z0-9_]*)["\']')
# resource-group and autoscaler literals likewise: the fairness
# acceptance tests assert on these series by full name
RESOURCE_GROUP_LITERAL_RE = re.compile(
    r'["\'](trino_tpu_resource_group_[a-z0-9_]*)["\']'
)
AUTOSCALER_LITERAL_RE = re.compile(
    r'["\'](trino_tpu_autoscaler_[a-z0-9_]*)["\']'
)
# compile-observatory literals likewise: the retrace gate and the
# observatory acceptance tests assert on these series by full name
COMPILE_LITERAL_RE = re.compile(
    r'["\'](trino_tpu_compile_[a-z0-9_]*)["\']'
)
# serving-observatory literals likewise: the signature-census
# acceptance tests assert on these series by full name
SLO_LITERAL_RE = re.compile(r'["\'](trino_tpu_slo_[a-z0-9_]*)["\']')
SIGNATURE_LITERAL_RE = re.compile(
    r'["\'](trino_tpu_signature_[a-z0-9_]*)["\']'
)
# object-store and lakehouse literals likewise: the concurrent-writer
# acceptance tests assert on these series by full name
OBJSTORE_LITERAL_RE = re.compile(
    r'["\'](trino_tpu_objstore_[a-z0-9_]*)["\']'
)
LAKE_LITERAL_RE = re.compile(r'["\'](trino_tpu_lake_[a-z0-9_]*)["\']')
# multi-host cluster literals likewise: the kill -9 host-loss acceptance
# test and the multihost smoke assert on these series by full name
HOST_LITERAL_RE = re.compile(r'["\'](trino_tpu_host_[a-z0-9_]*)["\']')

# one naming regime across the observability surface: metric names above,
# span names at tracer call sites (snake_case, like the metric stems),
# and flight-recorder record fields (lowerCamelCase, like breadcrumb
# to_dict() keys and every other JSON surface the server emits)
SPAN_CALL_RE = re.compile(r'\.span\(\s*["\']([^"\']+)["\']')
SPAN_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
RECORD_FIELD_RE = re.compile(r"^[a-z][a-zA-Z0-9]*$")

SCAN_DIRS = ("trino_tpu", "tests", "scripts")


def iter_source_files(root: str):
    for d in SCAN_DIRS:
        base = os.path.join(root, d)
        for dirpath, _dirnames, filenames in os.walk(base):
            if "__pycache__" in dirpath:
                continue
            for fn in filenames:
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def check_tree(root: str):
    """Returns (checked_count, violations) over every Python file."""
    checked = 0
    violations = []
    for path in iter_source_files(root):
        with open(path, "r", encoding="utf-8") as f:
            text = f.read()
        seen_spans = set()
        for regex in (
            REGISTRATION_RE, LITERAL_RE, MEMORY_LITERAL_RE,
            NODE_LITERAL_RE, JOURNAL_LITERAL_RE, DOCTOR_LITERAL_RE,
            RESOURCE_GROUP_LITERAL_RE, AUTOSCALER_LITERAL_RE,
            COMPILE_LITERAL_RE, SLO_LITERAL_RE, SIGNATURE_LITERAL_RE,
            OBJSTORE_LITERAL_RE, LAKE_LITERAL_RE, HOST_LITERAL_RE,
        ):
            for m in regex.finditer(text):
                if m.span(1) in seen_spans:
                    continue
                seen_spans.add(m.span(1))
                name = m.group(1)
                checked += 1
                # histogram series names render with _bucket/_sum/_count
                # suffixes; literals naming those are exposition artifacts,
                # not registrations
                base = re.sub(r"_(bucket|sum|count)$", "", name)
                if not (METRIC_NAME_RE.match(name) or METRIC_NAME_RE.match(base)):
                    rel = os.path.relpath(path, root)
                    lineno = text.count("\n", 0, m.start(1)) + 1
                    violations.append((rel, lineno, name))
        for m in SPAN_CALL_RE.finditer(text):
            name = m.group(1)
            checked += 1
            if not SPAN_NAME_RE.match(name):
                rel = os.path.relpath(path, root)
                lineno = text.count("\n", 0, m.start(1)) + 1
                violations.append((rel, lineno, "span:" + name))
    # wire-record schemas are data, not literals-at-rest: lint each
    # authoritative field tuple its writer serializes from (flight
    # recorder, OperatorStats frames, query history records)
    sys.path.insert(0, root)
    field_schemas = (
        ("trino_tpu/obs/flight_recorder.py",
         "trino_tpu.obs.flight_recorder", "RECORD_FIELDS"),
        ("trino_tpu/obs/opstats.py",
         "trino_tpu.obs.opstats", "OPERATOR_FIELDS"),
        ("trino_tpu/obs/history.py",
         "trino_tpu.obs.history", "HISTORY_FIELDS"),
        ("trino_tpu/server/discovery.py",
         "trino_tpu.server.discovery", "NODE_FIELDS"),
        ("trino_tpu/obs/journal.py",
         "trino_tpu.obs.journal", "EVENT_FIELDS"),
        ("trino_tpu/obs/doctor.py",
         "trino_tpu.obs.doctor", "DIAGNOSIS_FIELDS"),
        ("trino_tpu/obs/compile_observatory.py",
         "trino_tpu.obs.compile_observatory", "COMPILE_FIELDS"),
        ("trino_tpu/obs/compile_observatory.py",
         "trino_tpu.obs.compile_observatory", "CENSUS_FIELDS"),
        ("trino_tpu/server/recovery.py",
         "trino_tpu.server.recovery", "WAL_FIELDS"),
        ("trino_tpu/obs/serving_observatory.py",
         "trino_tpu.obs.serving_observatory", "OBSERVATION_FIELDS"),
        ("trino_tpu/obs/serving_observatory.py",
         "trino_tpu.obs.serving_observatory", "SIGNATURE_FIELDS"),
        ("trino_tpu/obs/serving_observatory.py",
         "trino_tpu.obs.serving_observatory", "AFFINITY_FIELDS"),
        ("trino_tpu/obs/serving_observatory.py",
         "trino_tpu.obs.serving_observatory", "SLO_FIELDS"),
        ("trino_tpu/connectors/lakehouse.py",
         "trino_tpu.connectors.lakehouse", "SNAPSHOT_FIELDS"),
        ("trino_tpu/distributed/topology.py",
         "trino_tpu.distributed.topology", "TOPOLOGY_FIELDS"),
        ("trino_tpu/obs/program_census.py",
         "trino_tpu.obs.program_census", "CENSUS_FIELDS"),
    )
    for rel, mod, attr in field_schemas:
        try:
            import importlib

            fields = getattr(importlib.import_module(mod), attr)
        except Exception:
            fields = ()
        for field in fields:
            checked += 1
            if not RECORD_FIELD_RE.match(field):
                violations.append((rel, 0, "field:" + field))
    return checked, violations


def main() -> int:
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    checked, violations = check_tree(root)
    if violations:
        for rel, lineno, name in violations:
            if name.startswith("span:"):
                print(
                    f"{rel}:{lineno}: span name {name[5:]!r} violates "
                    "snake_case ^[a-z][a-z0-9_]*$"
                )
            elif name.startswith("field:"):
                print(
                    f"{rel}:{lineno}: wire-record field {name[6:]!r} "
                    "violates lowerCamelCase ^[a-z][a-zA-Z0-9]*$"
                )
            else:
                print(
                    f"{rel}:{lineno}: metric name {name!r} violates "
                    "trino_tpu_<subsystem>_<name>{_total|_bytes|_seconds|_state}"
                )
        return 1
    print(
        f"ok: {checked} metric/span/record-field name literals conform"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
