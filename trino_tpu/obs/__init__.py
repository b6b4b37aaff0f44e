"""Observability subsystem: black-box flight recorder, operator timeline,
history, journal and doctor.

Always-on production-profiling surfaces in the spirit of Kanev et al.
(*Profiling a Warehouse-Scale Computer*, ISCA 2015) and Dean & Barroso
(*The Tail at Scale*, CACM 2013):

- :mod:`.flight_recorder` — a per-process bounded ring of dispatch
  records (in-memory always; crash-safe mmap'd JSONL segments on disk
  when ``flight_recorder_dir`` is set) so the last N device dispatches
  survive a hard TPU crash and ``scripts/flightrec.py`` can bisect the
  culprit kernel offline;
- :mod:`.opstats` — per-operator OperatorStats frames (rows/bytes/wall/
  blocked-time, estimated vs observed rows) rolled up pipeline -> task ->
  stage -> query into the EXPLAIN ANALYZE / ``system.runtime.operator_stats``
  timeline, plus the live wall-dispersion straggler detector feeding FTE
  hedging (``trino_tpu_straggler_*`` metrics);
- :mod:`.history` — crash-safe byte-bounded persisted query history
  (``query_history_dir``), same torn-tail-tolerant mmap'd JSONL shape as
  the flight recorder, backing ``system.runtime.completed_queries``;
- :mod:`.journal` — the engine-wide incident journal: every subsystem
  that bumps an anomaly metric also appends a typed query/task/node-
  correlated event (``event_journal_dir`` upgrades it to the crash-safe
  on-disk segments), backing ``system.runtime.events``;
- :mod:`.doctor` — the query doctor: deterministic ordered-rule
  correlation of the journal with the flight recorder, timeline,
  and history into a ranked causal verdict (EXPLAIN
  ANALYZE "Diagnosis", ``system.runtime.diagnoses``,
  ``scripts/doctor.py``).
"""
from .doctor import (
    DIAGNOSIS_FIELDS,
    classify_error,
    diagnose,
    diagnose_from_dir,
    diagnose_query,
    format_diagnosis,
    recent_diagnoses,
    record_diagnosis,
)
from .journal import (
    EVENT_FIELDS,
    EventJournal,
    get_journal,
    read_journal_dir,
)
from .flight_recorder import (
    RECORD_FIELDS,
    FlightRecorder,
    last_recorder,
    last_unmatched,
    read_dir,
)
from .history import (
    HISTORY_FIELDS,
    QueryHistoryStore,
    get_store,
    read_history_dir,
)
from .opstats import (
    OPERATOR_FIELDS,
    StragglerDetector,
    format_timeline,
    frames_from_plan,
    merge_frames,
    task_rollup,
    timeline_from_tasks,
)

__all__ = [
    "DIAGNOSIS_FIELDS",
    "classify_error",
    "diagnose",
    "diagnose_from_dir",
    "diagnose_query",
    "format_diagnosis",
    "recent_diagnoses",
    "record_diagnosis",
    "EVENT_FIELDS",
    "EventJournal",
    "get_journal",
    "read_journal_dir",
    "RECORD_FIELDS",
    "FlightRecorder",
    "last_recorder",
    "last_unmatched",
    "read_dir",
    "HISTORY_FIELDS",
    "QueryHistoryStore",
    "get_store",
    "read_history_dir",
    "OPERATOR_FIELDS",
    "StragglerDetector",
    "format_timeline",
    "frames_from_plan",
    "merge_frames",
    "task_rollup",
    "timeline_from_tasks",
]
