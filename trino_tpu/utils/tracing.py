"""Tracing: span recording through engine layers.

Reference parity: OpenTelemetry integration (tracing/TracingMetadata.java,
TrinoAttributes span-attribute schema, query/task spans created in
DispatchManager.java:155 and SqlTaskManager).  This is an OTel-compatible
span model (name, trace/span ids, parent, start/end, attributes) with an
in-memory recorder; an exporter can forward to a real OTel endpoint.
"""
from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import Any, Dict, List, Optional

# ids: a per-process random prefix and a counter, formatted to the W3C
# widths (32 hex trace id, 16 hex span id) — unique across the processes
# of one distributed trace without a uuid4 per span
_TRACE_PREFIX = os.urandom(8).hex()
_SPAN_PREFIX = os.urandom(3).hex()
_next_id = itertools.count(1).__next__

_annotation = None


def _load_annotation():
    """`jax.profiler.TraceAnnotation`, imported on first use: while a
    profile runs the profiler records every span on the host plane, on
    its own clock, beside the PJRT events."""
    global _annotation
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    return TraceAnnotation


class Span:
    """One span, and its own context manager (opened by `Tracer.span`).

    `start` is the wall clock (OTLP); the duration comes from
    `time.perf_counter_ns()`, which cannot step.  The hex ids are
    formatted when first read: most spans are leaves nobody asks."""

    __slots__ = (
        "name", "start", "attributes", "_tracer", "_remote", "_parent",
        "_trace", "_n", "_span_id", "_stack", "_t0", "_ns", "_note",
    )

    def __init__(self, tracer: "Tracer", name: str,
                 traceparent: Optional[str], parent: Optional["Span"],
                 attributes: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self._remote = traceparent
        self._parent = parent
        self.attributes = attributes
        self._ns = None

    def __enter__(self) -> "Span":
        local = self._tracer._local
        try:
            stack = local.stack
        except AttributeError:
            stack = local.stack = []
        if stack:
            self._parent = stack[-1]   # a local parent wins
        parent = self._parent
        if parent is not None:
            self._trace = parent._trace
        else:
            remote = self._remote
            if remote is not None:
                remote = self._remote = parse_traceparent(remote)
            # a remote trace id as it came, or this process's counter
            self._trace = remote["trace_id"] if remote else _next_id()
        self._n = _next_id()
        self._span_id = None
        stack.append(self)
        self._stack = stack
        note = self._note = (_annotation or _load_annotation())(self.name)
        self.start = time.time()
        note.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._ns = time.perf_counter_ns() - self._t0
        self._note.__exit__(exc_type, exc, tb)
        self._stack.pop()
        self._tracer.spans.append(self)

    @property
    def trace_id(self) -> str:
        t = self._trace
        return t if isinstance(t, str) else "%s%016x" % (_TRACE_PREFIX, t)

    @property
    def span_id(self) -> str:
        sid = self._span_id
        if sid is None:
            sid = self._span_id = "%s%010x" % (_SPAN_PREFIX, self._n)
        return sid

    @property
    def parent_id(self) -> Optional[str]:
        if self._parent is not None:
            return self._parent.span_id
        return self._remote["parent_id"] if self._remote else None

    @property
    def end(self) -> Optional[float]:
        return None if self._ns is None else self.start + self._ns / 1e9

    @property
    def duration_ms(self) -> float:
        ns = self._ns
        if ns is None:
            ns = time.perf_counter_ns() - self._t0
        return ns / 1e6

    @property
    def traceparent(self) -> str:
        return format_traceparent(self.trace_id, self.span_id)


def format_traceparent(trace_id: str, span_id: str) -> str:
    """W3C Trace Context `traceparent`: version-traceid-spanid-flags."""
    return "00-%s-%s-01" % (trace_id, span_id)


def parse_traceparent(header: Optional[str]) -> Optional[Dict[str, str]]:
    """Parse a W3C `traceparent` header into trace/parent span ids.

    Returns None for absent or malformed headers (per spec, an invalid
    header means "start a fresh trace", never an error).
    """
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) < 4:
        return None
    _version, trace_id, span_id = parts[0], parts[1], parts[2]
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16)
        int(span_id, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return {"trace_id": trace_id, "parent_id": span_id}


class Tracer:
    """Per-process tracer with thread-local span stacks.

    Completed spans land in a bounded ring buffer (oldest dropped first)
    so a long-lived process with no exporter attached holds at most
    `max_spans` spans in memory.
    """

    DEFAULT_MAX_SPANS = 4096

    def __init__(self, max_spans: int = DEFAULT_MAX_SPANS):
        self.max_spans = max_spans
        # appended and drained from several threads: deque.append and
        # popleft are atomic, so no lock sits on the span path
        self.spans: "collections.deque[Span]" = collections.deque(maxlen=max_spans)
        self.exporter: Optional["OtlpFileExporter"] = None
        self._local = threading.local()

    def span(self, name: str, traceparent: Optional[str] = None,
             parent: Optional[Span] = None, **attributes) -> Span:
        """Open a span (`with tracer.span(...) as s`).  When the calling
        thread has no span open, `parent` — a Span of this process, open
        or closed, from any thread (`current_span()` taken where the work
        was handed over) — or else a remote `traceparent` (the Dapper
        cross-process link: coordinator->worker dispatch, exchange fetch
        threads) gives the new span its trace and its parent."""
        return Span(self, name, traceparent, parent, attributes)

    def current_span(self) -> Optional[Span]:
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else None

    def current_traceparent(self) -> Optional[str]:
        s = self.current_span()
        return s.traceparent if s is not None else None

    def clear(self):
        self.spans.clear()

    # -- export ---------------------------------------------------------
    def attach_exporter(self, exporter: "OtlpFileExporter"):
        self.exporter = exporter

    def flush(self):
        """Export + drop all recorded spans (called at query completion —
        the airlift OTel exporter's batch-flush role).  Without an exporter
        spans stay in memory for tests/system tables."""
        exporter = self.exporter
        if exporter is None:
            return
        spans, ring = [], self.spans
        try:
            while True:
                spans.append(ring.popleft())
        except IndexError:
            pass
        if spans:
            exporter.export(spans)


class OtlpFileExporter:
    """OTLP/JSON span exporter writing one `resourceSpans` document per
    flush to a local file (newline-delimited) — the OpenTelemetry wire
    schema (trace service ExportTraceServiceRequest JSON mapping), minus
    the network: an OTel collector can tail the file, and air-gapped
    environments (like the bench TPU) still get durable traces.

    Reference parity: airlift's OpenTelemetry exporter wired through
    tracing/TracingMetadata.java + TrinoAttributes span schema.
    """

    def __init__(self, path: str, service_name: str = "trino-tpu"):
        self.path = path
        self.service_name = service_name
        self._lock = threading.Lock()

    def export(self, spans: List[Span]):
        import json

        doc = {
            "resourceSpans": [{
                "resource": {"attributes": [{
                    "key": "service.name",
                    "value": {"stringValue": self.service_name},
                }]},
                "scopeSpans": [{
                    "scope": {"name": "trino_tpu"},
                    "spans": [
                        {
                            "traceId": s.trace_id,
                            "spanId": s.span_id,
                            "parentSpanId": s.parent_id or "",
                            "name": s.name,
                            "startTimeUnixNano": int(s.start * 1e9),
                            "endTimeUnixNano": int(
                                (s.end or s.start) * 1e9
                            ),
                            "attributes": [
                                {"key": k,
                                 "value": {"stringValue": str(v)}}
                                for k, v in s.attributes.items()
                            ],
                        }
                        for s in spans
                    ],
                }],
            }]
        }
        with self._lock:
            with open(self.path, "a") as f:
                f.write(json.dumps(doc) + "\n")


TRACER = Tracer()

# TRINO_TPU_OTLP_FILE wires the process tracer to a file exporter at
# import (the etc/config.properties tracing.* binding analog)
_otlp = os.environ.get("TRINO_TPU_OTLP_FILE")
if _otlp:
    TRACER.attach_exporter(OtlpFileExporter(_otlp))
