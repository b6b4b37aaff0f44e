"""Session facade: SQL in, Pages out.

Reference parity: the in-process query path of testing/PlanTester.java:250 /
StandaloneQueryRunner — parse -> analyze/plan -> optimize -> execute, plus
the session machinery around it:
  - typed session properties + SET/SHOW SESSION (SystemSessionProperties)
  - OpenTelemetry-style spans per phase (DispatchManager querySpan)
  - query events to registered listeners (EventListenerManager)
  - per-query memory reservation against a shared pool (MemoryPool)
  - utility statements: SHOW TABLES / SHOW COLUMNS / EXPLAIN
The coordinator HTTP server (server/coordinator.py) wraps this same path.
"""
from __future__ import annotations

import time
import uuid
from typing import Optional

from . import types as T
from .catalog import CatalogManager, Metadata
from .config import SessionProperties
from .connectors.tpch import TpchConnectorFactory
from .exec.local import LocalExecutor
from .page import Page, column_from_pylist, page_from_pydict
from .plan import nodes as P
from .plan.optimizer import optimize
from .sql import ast
from .sql.analyzer import Analyzer
from .sql.parser import parse
from .utils.events import EventListenerManager
from .utils.memory import MemoryPool, estimate_batch_bytes
from .utils.tracing import TRACER


class Session:
    def __init__(
        self,
        catalog: Optional[str] = None,
        config: Optional[dict] = None,
        user: str = "user",
    ):
        self.catalogs = CatalogManager()
        self.catalogs.register_factory(TpchConnectorFactory())
        from .connectors.tpcds import TpcdsConnectorFactory

        self.catalogs.register_factory(TpcdsConnectorFactory())
        try:
            from .connectors.memory import MemoryConnectorFactory
            from .connectors.blackhole import BlackholeConnectorFactory

            self.catalogs.register_factory(MemoryConnectorFactory())
            self.catalogs.register_factory(BlackholeConnectorFactory())
        except ImportError:
            pass
        try:
            from .connectors.hive import HiveConnectorFactory

            self.catalogs.register_factory(HiveConnectorFactory())
        except ImportError:  # pyarrow not installed
            pass
        from .connectors.lakehouse import LakehouseConnectorFactory

        self.catalogs.register_factory(LakehouseConnectorFactory())
        self.default_catalog = catalog
        self.properties = SessionProperties(config)
        self.metadata = Metadata(self.catalogs)
        self.events = EventListenerManager()
        # per-node memory arbitration (memory/ subsystem): the legacy
        # session-level MemoryPool is absorbed as the manager's general
        # pool, so existing reserve/free call sites keep working
        from .memory import LocalMemoryManager

        self.memory_manager = LocalMemoryManager(
            self.properties.get("query_max_memory_bytes"),
            node_id="session",
        )
        self.memory_pool = self.memory_manager.general
        # supervised kernel-dispatch boundary (runtime/): one per node,
        # like the memory manager — device quarantine is node-local
        from .runtime import DeviceSupervisor

        self.device_supervisor = DeviceSupervisor(node_id="session")
        self.tracer = TRACER
        # PREPARE name FROM ... statements (QueryPreparer / prepared
        # statement store; the reference keeps these per client session)
        self.prepared: dict = {}
        # CREATE FUNCTION registry (LanguageFunctionManager analog)
        self.sql_functions: dict = {}
        from .security import AccessControlManager, Identity

        self.identity = Identity(user)
        self.access_control = AccessControlManager()
        # system.runtime.queries / completed_queries backing store
        # (QueryTracker history): crash-safe persisted store shared by
        # ALL sessions, bounded by bytes (not count) — mmap'd JSONL
        # segments in obs/history survive kill -9 up to the torn tail
        from .obs.history import get_store as _history_store

        self.history = _history_store(
            self.properties.get("query_history_dir") or None,
            max_bytes=int(
                self.properties.get("query_history_max_bytes")
                or (1 << 20)
            ),
        )
        # engine-wide incident journal (obs/journal.py): process-global,
        # memory-only until a directory upgrades it to the crash-safe
        # mmap'd segment store that scripts/doctor.py reads post-mortem
        from .obs import journal as _journal

        if self.properties.get("event_journal_dir"):
            _journal.configure(
                self.properties.get("event_journal_dir"),
                max_bytes=int(
                    self.properties.get("event_journal_max_bytes")
                    or (1 << 20)
                ),
            )
        # compile observatory (obs/compile_observatory.py): the process-
        # global trace/compile ledger + shape census; a directory
        # upgrades it to the same crash-safe segment store
        from .obs import compile_observatory as _compile_obs

        _census_fams = int(
            self.properties.get("compile_census_max_families")
            or _compile_obs.DEFAULT_MAX_FAMILIES
        )
        if self.properties.get("compile_observatory_dir"):
            _compile_obs.configure(
                self.properties.get("compile_observatory_dir"),
                census_max_families=_census_fams,
            )
        elif _census_fams != _compile_obs.DEFAULT_MAX_FAMILIES:
            # resize the census without re-pointing (or dropping) the
            # directory an earlier session configured
            _compile_obs.configure(
                _compile_obs.get_observatory().directory,
                census_max_families=_census_fams,
            )
        # ranked root-cause verdict of the most recent doctored query
        self.last_diagnosis: Optional[dict] = None
        # stats of the most recent persistent-compile-cache prewarm
        # (cold-start path)
        self.last_prewarm: Optional[dict] = None
        # operator timeline of the last instrumented execution (EXPLAIN
        # ANALYZE / operator_stats=true), backing
        # system.runtime.operator_stats
        self.last_timeline: Optional[dict] = None
        # the built-in system catalog (system.runtime.* etc.)
        from .connectors.system import SystemConnectorFactory

        self.catalogs.register_factory(SystemConnectorFactory())
        self.catalogs.create_catalog("system", "system", {"session": self})
        # cross-query scan cache (warm-HBM reuse; exec/local.DeviceScanCache)
        from .exec.local import DeviceScanCache

        self._scan_cache = DeviceScanCache()
        # under memory pressure the warm-HBM scan cache is revoked
        # (spilled to nothing — it can always be re-uploaded) before any
        # query is blocked or killed
        self.memory_manager.register_revocable(
            "scan-cache", self._scan_cache.max_bytes,
            self._scan_cache.drop_all,
        )
        # unified cache subsystem (cache/): session-scoped fragment result
        # cache + process-global compiled-fragment cache, with the scan
        # cache adopted for stats (system.runtime.caches, /v1/cache)
        from .cache import CacheManager, FragmentResultCache
        from .cache import shared_compile_cache

        self.caches = CacheManager(
            FragmentResultCache(
                max_bytes=self.properties.get("result_cache_max_bytes"),
                on_event=self.events.cache_event,
            ),
            shared_compile_cache(),
            self._scan_cache,
            events=self.events,
        )
        # back-compat alias (bench/tests reach the compiled-fragment
        # cache through this name); plan cache stays keyed by SQL text
        self._jit_cache = self.caches.compile_cache
        self._plan_cache: dict = {}
        # FaultInjector instances per spec text: rules are stateful
        # (nth counters), so the same spec must reuse one injector
        self._fault_injectors: dict = {}
        self._capacity_hints: dict = {}
        # streaming fragment DAGs keyed by id(plan): re-fragmenting per
        # run would mint fresh plan objects and defeat jit-cache reuse
        self._fragment_cache: dict = {}
        # ANALYZE run registry (system.runtime.table_stats backing store):
        # (catalog, table) -> last run's shape + timings
        self.analyzed_tables: dict = {}

    def create_catalog(self, name: str, connector: str, config: dict):
        self.catalogs.create_catalog(name, connector, config)
        if self.default_catalog is None:
            self.default_catalog = name

    @property
    def query_history(self) -> list:
        """Legacy-shaped view over the persisted history store (the
        system.runtime.queries backing read): latest record per query,
        across every session sharing the store."""
        out = []
        for r in self.history.entries():
            out.append({
                "query_id": r.get("queryId"),
                "state": r.get("state"),
                "sql": r.get("sql"),
                "user": r.get("user"),
                "created": r.get("created"),
                "finished": r.get("finished"),
                "rows": r.get("rows"),
                "error": r.get("error"),
                "error_code": r.get("errorCode"),
            })
        return out

    # ------------------------------------------------------------------
    def _executor(self):
        # SET SESSION query_max_memory_bytes resizes the pool for later
        # queries (the pool object is shared; only its budget moves)
        self.memory_pool.size = self.properties.get("query_max_memory_bytes")
        inj = self._fault_injector()
        self.memory_manager.fault_injector = inj
        sup = self.device_supervisor.configure(self.properties)
        sup.fault_injector = inj
        sup.cpu_fallback_enabled = bool(
            self.properties.get("device_cpu_fallback")
        )
        exec_config = {
            "device_supervisor": sup,
            "device_cpu_fallback": self.properties.get(
                "device_cpu_fallback"
            ),
            "group_capacity": self.properties.get("group_capacity"),
            "memory_limit_bytes": self.properties.get(
                "query_max_memory_bytes"
            ),
            "spill_enabled": self.properties.get("spill_enabled"),
            "memory_pool": self.memory_pool,
            "memory_manager": self.memory_manager,
            "memory_blocked_timeout_s": self.properties.get(
                "memory_blocked_timeout_s"
            ),
            "scan_cache": self._scan_cache,
            "topn_initial_factor": self.properties.get(
                "topn_initial_factor"
            ),
            # operator_stats=true runs eager with per-node timing (jit
            # would fuse the fragment and hide the operator boundaries)
            "collect_node_stats": bool(
                self.properties.get("operator_stats")
            ),
        }
        exec_config["jit_fragments"] = bool(
            self.properties.get("jit_fragments")
        )
        exec_config["device_generation"] = bool(
            self.properties.get("device_generation")
        )
        exec_config["megakernels"] = self.properties.get("megakernels")
        exec_config["donate_pages"] = self.properties.get("donate_pages")
        exec_config["broadcast_join_threshold_rows"] = self.properties.get(
            "broadcast_join_threshold_rows"
        )
        # bucketed-batch ABI: resolve the ladder once per (spec, file)
        # and hand every executor (and its streaming tiles / mesh shards)
        # the same PaddingLadder object, so the whole session quantizes
        # onto identical rungs
        ladder_key = (
            self.properties.get("padding_ladder"),
            self.properties.get("padding_ladder_file"),
        )
        cached = getattr(self, "_ladder_cache", None)
        if not cached or cached[0] != ladder_key:
            from .exec.shapes import resolve_ladder

            cached = (ladder_key, resolve_ladder({
                "padding_ladder": ladder_key[0],
                "padding_ladder_file": ladder_key[1],
            }))
            self._ladder_cache = cached
        exec_config["padding_ladder"] = cached[1]
        cc = self.caches.compile_cache
        cache_dir = self.properties.get("compile_cache_dir")
        if cache_dir:
            # persistent tier: point jax's compilation cache at the shared
            # directory so a second process skips the XLA compile
            cc.attach_persistent(cache_dir)
            if self.properties.get("compile_prewarm"):
                # cold-start prewarm: page the persistent executables into
                # the OS cache and seed the observatory's family registry
                # from the index, so boot-time compiles classify as
                # persistent_load / first_compile — never shape_miss.
                # Idempotent per directory.
                warm = cc.prewarm(cache_dir)
                if warm is not None:
                    self.last_prewarm = warm
        # session property compile_cache=false detaches the shared cache
        # (a throwaway dict keeps the executor's duck-typed surface)
        exec_config["jit_cache"] = (
            cc if self.properties.get("compile_cache") else {}
        )
        exec_config["capacity_hints"] = self._capacity_hints
        exec_config["fragment_cache"] = self._fragment_cache
        if self.properties.get("distributed"):
            from .parallel.mesh_executor import MeshExecutor, default_mesh

            n = self.properties.get("num_devices") or None
            return MeshExecutor(self.catalogs, default_mesh(n), exec_config)
        return LocalExecutor(self.catalogs, exec_config)

    # ------------------------------------------------------------------
    def plan(self, sql: str, optimized: bool = True) -> P.PlanNode:
        stmt = parse(sql)
        if isinstance(stmt, ast.Explain):
            stmt = stmt.query
        analyzer = Analyzer(self.metadata, self.default_catalog,
                            self.sql_functions)
        plan = analyzer.plan_statement(stmt)
        if optimized:
            plan = optimize(plan, self.metadata, self.properties)
        return plan

    def explain(self, sql: str) -> str:
        return P.plan_to_string(self.plan(sql))

    # ------------------------------------------------------------------
    def execute(self, sql: str, user: Optional[str] = None) -> Page:
        from .security import Identity

        identity = Identity(user) if user else self.identity
        with self.tracer.span("query_admit") as admit:
            query_id = f"q_{uuid.uuid4().hex[:12]}"
            created = self.events.query_created(query_id, sql)
            entry = {
                "query_id": query_id, "sql": sql, "state": "RUNNING",
                "user": identity.user, "created": created,
            }
            self.history.put(entry)
        # query_admit, query and query_finish follow one another: the
        # later two join the first one's trace as its children
        try:
            with self.tracer.span("query", parent=admit, query_id=query_id):
                with self.tracer.span("parse"):
                    stmt = parse(sql)
                self.access_control.check_can_execute_query(identity)
                page = self._execute_statement(
                    stmt, sql, query_id, identity
                )
            with self.tracer.span("query_finish", parent=admit,
                                  state="FINISHED"):
                self.events.query_completed(
                    query_id, sql, "FINISHED", created, page.count
                )
                entry.update(
                    state="FINISHED", finished=time.time(),
                    rows=page.count, wall_s=time.time() - created,
                )
                # only THIS query's timeline (last_timeline is kept across
                # queries so system.runtime.operator_stats can read it)
                tl = self.last_timeline
                if tl and tl.get("queryId") == query_id:
                    entry["operators"] = tl.get("operators")
                self.history.put(entry)
                self._finalize_doctor(query_id, created)
            return page
        except Exception as e:
            from .obs.doctor import classify_error

            with self.tracer.span("query_finish", parent=admit,
                                  state="FAILED"):
                self.events.query_completed(
                    query_id, sql, "FAILED", created, error=str(e)
                )
                entry.update(
                    state="FAILED", finished=time.time(),
                    error=str(e), error_code=classify_error(e),
                    wall_s=time.time() - created,
                )
                self.history.put(entry)
                try:
                    from .obs import journal

                    journal.emit(
                        journal.QUERY_FAILED, query_id=query_id,
                        severity=journal.ERROR, error=str(e)[:400],
                        errorCode=classify_error(e),
                    )
                except Exception:  # noqa: BLE001 — journaling is best-effort
                    pass
                self._finalize_doctor(query_id, created, error=e)
            raise
        finally:
            # batch-export completed spans on EVERY completion path —
            # success, failure, and non-Query statements alike (no-op
            # without an attached OTLP exporter); query_finish has closed
            # by now, so the batch holds it
            self.tracer.flush()

    def _finalize_doctor(self, query_id: str, created: float,
                         error=None):
        """Query-finalize doctor pass (query_doctor session property):
        correlate the incident journal, operator timeline, and kernel
        profile into a ranked verdict.  Observability must never fail
        (or re-fail) the query, so everything is best-effort."""
        try:
            if not self.properties.get("query_doctor"):
                return
            from .obs import doctor

            now = time.time()
            tl = self.last_timeline
            diag = doctor.diagnose_query(
                query_id,
                window=(created, now),
                timeline=tl if (tl or {}).get("queryId") == query_id
                else None,
                profile=getattr(self, "last_kernel_profile", None),
                error=error,
                wall_s=now - created,
            )
            doctor.record_diagnosis(diag)
            self.last_diagnosis = diag
        except Exception:  # noqa: BLE001
            pass

    def _execute_statement(self, stmt, sql: str, query_id: str,
                           identity=None) -> Page:
        if identity is None:
            identity = self.identity
        if isinstance(stmt, (
            ast.Prepare, ast.Deallocate, ast.CreateFunction,
            ast.DropFunction, ast.CreateTable, ast.DropTable, ast.Use,
            ast.SetSession, ast.CreateView, ast.DropView,
        )):
            # statements that change planning state invalidate cached
            # plans; read-only EXECUTE/SHOW/EXPLAIN keep them.  Compiled
            # fragments survive: their keys embed the plan fingerprint,
            # capacity state and per-table data versions, so entries for
            # changed schemas/data simply stop being addressable (and the
            # compile cache is process-shared — clearing it here would
            # nuke other sessions' warm programs).
            self._plan_cache.clear()
            self._capacity_hints.clear()
        if isinstance(stmt, ast.SetSession):
            self.access_control.check_can_set_session(identity, stmt.name)
            if "." in stmt.name:
                # per-catalog session property (SET SESSION catalog.name):
                # validated against the connector's declared metadata
                cat, _, prop = stmt.name.partition(".")
                conn = self.catalogs.get(cat)
                meta = conn.session_property_metadata().get(prop)
                if meta is None:
                    raise KeyError(
                        f"unknown catalog session property: {stmt.name}"
                    )
                value = (
                    meta.parse(stmt.value)
                    if isinstance(stmt.value, str) else stmt.value
                )
                conn.set_session_property(prop, value)
            else:
                self.properties.set(stmt.name, stmt.value)
            return page_from_pydict([("result", T.BOOLEAN)], {"result": [True]})
        if isinstance(stmt, ast.ShowSession):
            rows = list(self.properties.show())
            # per-catalog session properties (Trino's SHOW SESSION lists
            # catalog properties alongside system ones)
            for cat in self.catalogs.names():
                conn = self.catalogs.get(cat)
                for name, meta in sorted(
                    conn.session_property_metadata().items()
                ):
                    rows.append((
                        f"{cat}.{name}",
                        str(conn.get_session_property(name)),
                        str(meta.default),
                        meta.description,
                    ))
            return page_from_pydict(
                [("name", T.VARCHAR), ("value", T.VARCHAR),
                 ("default", T.VARCHAR), ("description", T.VARCHAR)],
                {
                    "name": [r[0] for r in rows],
                    "value": [r[1] for r in rows],
                    "default": [r[2] for r in rows],
                    "description": [r[3] for r in rows],
                },
            )
        if isinstance(stmt, ast.CreateView):
            from .catalog import ViewDefinition
            from .sql.analyzer import Analyzer

            catalog, name = self.metadata.resolve_new_table(
                stmt.name, self.default_catalog
            )
            # views are named schema objects: the create-table rule
            # governs them (the reference has a dedicated
            # checkCanCreateView with the same default policy)
            self.access_control.check_can_create_table(
                identity, catalog, name
            )
            # plan the query now: validates it and captures the view's
            # declared column names/types (ViewDefinition column list)
            analyzer = Analyzer(self.metadata, self.default_catalog,
                                self.sql_functions)
            plan = analyzer.plan_statement(stmt.query)
            types = plan.source.output_types()
            cols = tuple(
                (n, str(types[s]))
                for n, s in zip(plan.names, plan.symbols)
            )
            seen = set()
            for n, _t in cols:
                if n.lower() in seen:
                    raise ValueError(f"duplicate view column name {n}")
                seen.add(n.lower())
            self.metadata.create_view(
                ViewDefinition(catalog, name, stmt.query_sql, stmt.query,
                               cols, context_catalog=self.default_catalog),
                stmt.replace,
            )
            return page_from_pydict([("result", T.BOOLEAN)], {"result": [True]})
        if isinstance(stmt, ast.DropView):
            catalog, name = self.metadata.resolve_new_table(
                stmt.name, self.default_catalog
            )
            self.access_control.check_can_drop_table(
                identity, catalog, name
            )
            self.metadata.drop_view(
                stmt.name, self.default_catalog, stmt.if_exists
            )
            return page_from_pydict([("result", T.BOOLEAN)], {"result": [True]})
        if isinstance(stmt, ast.ShowCreateView):
            view = self.metadata.lookup_view(stmt.name, self.default_catalog)
            if view is None:
                raise KeyError(f"view not found: {'.'.join(stmt.name)}")
            ddl = (
                f"CREATE VIEW {view.catalog}.{view.name} AS\n"
                f"{view.original_sql}"
            )
            return page_from_pydict(
                [("create_view", T.VARCHAR)], {"create_view": [ddl]}
            )
        if isinstance(stmt, ast.ShowTables):
            conn = self.catalogs.get(self.default_catalog)
            tables = sorted(
                set(conn.metadata().list_tables())
                | set(self.metadata.list_views(self.default_catalog))
            )
            return page_from_pydict([("table", T.VARCHAR)], {"table": tables})
        if isinstance(stmt, ast.ShowColumns):
            view = self.metadata.lookup_view(stmt.table, self.default_catalog)
            if view is not None:
                return page_from_pydict(
                    [("column", T.VARCHAR), ("type", T.VARCHAR)],
                    {
                        "column": [c for c, _ in view.columns],
                        "type": [t for _, t in view.columns],
                    },
                )
            _, schema = self.metadata.resolve_table(
                stmt.table, self.default_catalog
            )
            return page_from_pydict(
                [("column", T.VARCHAR), ("type", T.VARCHAR)],
                {
                    "column": [c.name for c in schema.columns],
                    "type": [str(c.type) for c in schema.columns],
                },
            )
        if isinstance(stmt, ast.CreateFunction):
            from .sql.analyzer import SqlFunction

            name = stmt.name.lower()
            if name in self.sql_functions and not stmt.replace:
                raise ValueError(f"function {name} already exists")
            T.parse_type(stmt.return_type)  # validate eagerly
            for _, pt in stmt.params:
                T.parse_type(pt)
            self.sql_functions[name] = SqlFunction(
                name, tuple((p.lower(), t) for p, t in stmt.params),
                stmt.return_type, stmt.body,
            )
            return page_from_pydict([("result", T.BOOLEAN)], {"result": [True]})
        if isinstance(stmt, ast.DropFunction):
            name = stmt.name.lower()
            if name not in self.sql_functions:
                if stmt.if_exists:
                    return page_from_pydict(
                        [("result", T.BOOLEAN)], {"result": [True]}
                    )
                raise KeyError(f"function not found: {name}")
            del self.sql_functions[name]
            return page_from_pydict([("result", T.BOOLEAN)], {"result": [True]})
        if isinstance(stmt, ast.ShowFunctions):
            from .expr.functions import SIGNATURES
            from .sql.analyzer import AGGREGATES

            names = sorted(
                set(SIGNATURES) | AGGREGATES | set(self.sql_functions)
            )
            kinds = [
                "sql" if n in self.sql_functions
                else "aggregate" if n in AGGREGATES
                else "scalar"
                for n in names
            ]
            return page_from_pydict(
                [("function", T.VARCHAR), ("kind", T.VARCHAR)],
                {"function": names, "kind": kinds},
            )
        if isinstance(stmt, ast.Use):
            catalog = stmt.name[0]
            self.catalogs.get(catalog)  # raises if unknown
            self.default_catalog = catalog
            return page_from_pydict([("result", T.BOOLEAN)], {"result": [True]})
        if isinstance(stmt, ast.TransactionControl):
            if stmt.kind == "rollback":
                raise ValueError(
                    "ROLLBACK is not supported: statements auto-commit "
                    "(one transaction per query)"
                )
            return page_from_pydict([("result", T.BOOLEAN)], {"result": [True]})
        if isinstance(stmt, ast.ShowStats):
            catalog, schema = self.metadata.resolve_table(
                stmt.table, self.default_catalog
            )
            stats = self.metadata.table_statistics(catalog, schema.name)
            names, dvs, nfs, lows, highs = [], [], [], [], []
            for c in schema.columns:
                cs = stats.columns.get(c.name)
                names.append(c.name)
                dvs.append(None if cs is None else cs.distinct_count)
                nfs.append(None if cs is None else cs.null_fraction)
                lows.append(
                    None if cs is None or cs.min_value is None
                    else str(cs.min_value)
                )
                highs.append(
                    None if cs is None or cs.max_value is None
                    else str(cs.max_value)
                )
            # summary row (the reference's NULL-column row_count row)
            names.append(None)
            dvs.append(None)
            nfs.append(None)
            lows.append(None)
            highs.append(None)
            rc = [None] * len(schema.columns) + [float(stats.row_count)]
            return page_from_pydict(
                [("column_name", T.VARCHAR),
                 ("distinct_values_count", T.DOUBLE),
                 ("nulls_fraction", T.DOUBLE),
                 ("row_count", T.DOUBLE),
                 ("low_value", T.VARCHAR),
                 ("high_value", T.VARCHAR)],
                {"column_name": names, "distinct_values_count": dvs,
                 "nulls_fraction": nfs, "row_count": rc,
                 "low_value": lows, "high_value": highs},
            )
        if isinstance(stmt, ast.Analyze):
            return self.execute_analyze(stmt, identity)
        if isinstance(stmt, ast.ShowCreateTable):
            catalog, schema = self.metadata.resolve_table(
                stmt.table, self.default_catalog
            )
            cols = ",\n   ".join(
                f"{c.name} {c.type}" for c in schema.columns
            )
            ddl = (
                f"CREATE TABLE {catalog}.{schema.name} (\n   {cols}\n)"
            )
            return page_from_pydict(
                [("create_table", T.VARCHAR)], {"create_table": [ddl]}
            )
        if isinstance(stmt, ast.ShowSchemas):
            cat = stmt.catalog or self.default_catalog
            self.catalogs.get(cat)  # raises if unknown
            # catalogs here are single-schema; expose the flattened layout
            return page_from_pydict(
                [("schema", T.VARCHAR)],
                {"schema": ["default", "information_schema"]},
            )
        if isinstance(stmt, ast.ShowCatalogs):
            return page_from_pydict(
                [("catalog", T.VARCHAR)],
                {"catalog": sorted(self.catalogs.names())},
            )
        if isinstance(stmt, ast.Prepare):
            self.prepared[stmt.name.lower()] = stmt.statement
            return page_from_pydict([("result", T.BOOLEAN)], {"result": [True]})
        if isinstance(stmt, ast.Deallocate):
            if stmt.name.lower() not in self.prepared:
                raise KeyError(f"prepared statement not found: {stmt.name}")
            del self.prepared[stmt.name.lower()]
            return page_from_pydict([("result", T.BOOLEAN)], {"result": [True]})
        if isinstance(stmt, ast.ExecutePrepared):
            if stmt.name.lower() not in self.prepared:
                raise KeyError(f"prepared statement not found: {stmt.name}")
            bound = ast.substitute_parameters(
                self.prepared[stmt.name.lower()], stmt.args
            )
            nparams = ast.count_parameters(bound)
            if nparams:
                raise ValueError(
                    f"{nparams} parameter(s) left unbound; "
                    f"EXECUTE ... USING must supply all values"
                )
            return self._execute_statement(bound, sql, query_id, identity)
        if isinstance(stmt, ast.Describe):
            if stmt.name.lower() not in self.prepared:
                raise KeyError(f"prepared statement not found: {stmt.name}")
            target = self.prepared[stmt.name.lower()]
            if stmt.kind == "input":
                n = ast.count_parameters(target)
                return page_from_pydict(
                    [("position", T.BIGINT), ("type", T.VARCHAR)],
                    {"position": list(range(1, n + 1)),
                     "type": ["unknown"] * n},
                )
            # DESCRIBE OUTPUT: plan with NULL-bound parameters for typing
            n = ast.count_parameters(target)
            bound = ast.substitute_parameters(
                target, tuple(ast.Literal("null", None) for _ in range(n))
            )
            plan = self._plan_stmt(bound)
            types = plan.source.output_types()
            return page_from_pydict(
                [("column", T.VARCHAR), ("type", T.VARCHAR)],
                {
                    "column": list(plan.names),
                    "type": [str(types[s]) for s in plan.symbols],
                },
            )
        if isinstance(stmt, ast.Explain):
            if stmt.analyze:
                return self._explain_analyze(stmt.query, query_id)
            plan = self._plan_stmt(stmt.query)
            costs = None
            try:
                from .plan.cost import annotate

                costs = annotate(plan, self.metadata, self.properties)
            except Exception:
                pass
            if stmt.plan_type == "distributed":
                from .plan.fragment import fragment_plan

                parts = []
                for f in fragment_plan(plan):
                    parts.append(
                        f"Fragment {f.id} [{f.partitioning}"
                        + (f" keys={list(f.partition_keys)}"
                           if f.partition_keys else "")
                        + f" -> output {f.output_partitioning}]"
                    )
                    parts.append(
                        "\n".join(
                            "  " + line
                            for line in P.plan_to_string(f.root).split("\n")
                        )
                    )
                text = "\n".join(parts)
            else:
                text = P.plan_to_string(plan, costs=costs)
            col = column_from_pylist(T.VARCHAR, text.split("\n"))
            return Page([col], len(text.split("\n")), ["Query Plan"])
        if isinstance(stmt, ast.CreateTable):
            from .spi import ColumnSchema, TableSchema

            catalog, table = self.metadata.resolve_new_table(
                stmt.table, self.default_catalog
            )
            self.access_control.check_can_create_table(
                identity, catalog, table
            )
            if self.metadata.lookup_view(stmt.table, self.default_catalog):
                raise ValueError(
                    f"view with that name already exists: {table}"
                )
            md = self.catalogs.get(catalog).metadata()
            if stmt.if_not_exists and table in md.list_tables():
                return page_from_pydict([("rows", T.BIGINT)], {"rows": [0]})
            md.create_table(
                TableSchema(
                    table,
                    tuple(
                        ColumnSchema(c.lower(), T.parse_type(t))
                        for c, t in stmt.columns
                    ),
                )
            )
            return page_from_pydict([("rows", T.BIGINT)], {"rows": [0]})
        if isinstance(stmt, ast.DropTable):
            catalog, table = self.metadata.resolve_new_table(
                stmt.table, self.default_catalog
            )
            self.access_control.check_can_drop_table(
                identity, catalog, table
            )
            md = self.catalogs.get(catalog).metadata()
            if stmt.if_exists and table not in md.list_tables():
                return page_from_pydict([("rows", T.BIGINT)], {"rows": [0]})
            md.drop_table(table)
            self.caches.result_cache.invalidate(catalog, table)
            return page_from_pydict([("rows", T.BIGINT)], {"rows": [0]})

        if isinstance(stmt, ast.Query):
            cached = self._plan_cache.get(sql)
            if cached is None:
                cached = self._plan_stmt(stmt)
                from .cache import plan_signature

                # nondeterministic plans carry query-time folded constants
                # (now() timestamps, rand() seeds): caching the plan by SQL
                # text would replay the first execution's values forever
                if plan_signature(cached).deterministic:
                    self._plan_cache[sql] = cached
                    del_keys = list(self._plan_cache)[:-256]
                    for k in del_keys:  # bound the cache
                        self._plan_cache.pop(k, None)
            plan = cached
        else:
            # writes (INSERT/DELETE/UPDATE/MERGE/CTAS) change data: cached
            # plans are stale (compiled fragments stay — their keys embed
            # per-table data versions)
            self._plan_cache.clear()
            self._capacity_hints.clear()
            plan = self._plan_stmt(stmt)
        self._check_plan_access(plan, identity)
        rkey = None
        if isinstance(stmt, ast.Query):
            rkey, page = self.cached_result(plan)
            if page is not None:
                return page
        executor = self._executor()
        # journal/flight-recorder correlation: breadcrumbs and incident
        # events this execution emits carry the real query id, not the
        # executor's generic "query" placeholder
        executor.query_id = query_id
        with self.tracer.span("execute", query_id=query_id):
            _t0 = time.time()
            page = executor.execute(plan)
            _exec_wall = time.time() - _t0
        # input working-set size of the last query (bench + stats surface)
        self.last_scan_bytes = getattr(executor, "scan_bytes", 0)
        # per-query TPU kernel profile (compile wall / recompiles /
        # padding), surfaced via /v1/query/{id}/profile and bench output
        self.last_kernel_profile = getattr(executor, "kernel_profile", None)
        if getattr(executor, "node_stats", None):
            # operator_stats=true: node stats -> OperatorStats frames
            # (system.runtime.operator_stats + history "operators")
            from .obs import opstats as _opstats

            self.last_timeline = {
                "queryId": query_id,
                "wallS": _exec_wall,
                "operators": _opstats.frames_from_plan(
                    plan, executor.node_stats,
                    blocked_memory_s=getattr(
                        executor, "blocked_memory_s", 0.0
                    ),
                    blocked_exchange_s=getattr(
                        executor, "blocked_exchange_s", 0.0
                    ),
                ),
            }
            if getattr(executor, "mesh_tasks", None):
                # mesh execution: per-shard task rollups become stage
                # timelines, and the straggler detector sees shards the
                # way it sees worker tasks (row-skew apportioned wall)
                det = _opstats.StragglerDetector(
                    factor=float(
                        self.properties.get("straggler_dispersion_factor")
                        or 2.0
                    )
                )
                mesh_tl = _opstats.timeline_from_tasks(
                    executor.mesh_tasks, detector=det
                )
                self.last_timeline["stages"] = mesh_tl["stages"]
                if det.flags:
                    self.last_timeline["stragglers"] = det.flags
        if rkey is not None:
            self.store_result(rkey, page, plan)
        if not isinstance(stmt, ast.Query):
            self._invalidate_written_tables(plan)
        return page

    # -- fragment result cache (cache/result_cache) --------------------
    def _fault_injector(self):
        """Session FaultInjector from the fault_injection property, cached
        per spec text (rules hold nth-counters, so the same spec must keep
        reusing one injector instance)."""
        spec = self.properties.get("fault_injection")
        if not spec:
            return None
        key = spec if isinstance(spec, str) else repr(spec)
        inj = self._fault_injectors.get(key)
        if inj is None:
            from .utils.faults import FaultInjector

            inj = self._fault_injectors[key] = FaultInjector.from_spec(spec)
        return inj

    def _result_cache_key(self, plan):
        """(digest, params, table versions) result-cache key, or None when
        the plan must not be result-cached: tier disabled, nondeterministic
        plan, or any scanned connector that is not cacheable."""
        if not self.properties.get("result_cache"):
            return None
        from .cache import plan_signature

        sig = plan_signature(plan)
        if not sig.deterministic:
            return None
        versions = []
        for cat, tab in sig.tables:
            try:
                conn = self.catalogs.get(cat)
            except Exception:
                return None
            if not getattr(conn, "cacheable", True):
                return None
            versions.append((cat, tab, conn.data_version(tab)))
        return (sig.digest, sig.params, tuple(versions))

    def cached_result(self, plan):
        """Consult the result cache for a planned Query.  Returns
        (key, page): key is None when the plan is uncacheable; a non-None
        page is a hit and the query skips fragment execution entirely."""
        key = self._result_cache_key(plan)
        if key is None:
            return None, None
        rc = self.caches.result_cache
        # SET SESSION result_cache_max_bytes resizes the live budget
        rc.max_bytes = int(self.properties.get("result_cache_max_bytes"))
        page = rc.get(key, injector=self._fault_injector())
        if page is None:
            return key, None
        self.last_scan_bytes = 0  # served from cache: nothing was scanned
        self.last_kernel_profile = None  # no kernel ran either
        # relabel with THIS plan's output aliases: the digest is alias-
        # invariant, so the cached page may carry another query's names
        return key, Page(list(page.columns), page.count, list(plan.names))

    def store_result(self, key, page: Page, plan) -> None:
        if key is None:
            return
        # scanned tables ride inside the key's version component
        tables = tuple((c, t) for c, t, _v in key[2])
        self.caches.result_cache.put(key, page, tables=tables)

    def _invalidate_written_tables(self, plan) -> None:
        """Eagerly drop cached results over tables a write touched (the
        version-keyed lookups already miss; this reclaims the bytes)."""
        rc = self.caches.result_cache

        def walk(n):
            if isinstance(n, P.TableWriter):
                rc.invalidate(n.catalog, n.table)
            for s in n.sources:
                walk(s)

        walk(plan)

    def device_profile(self, sql: str) -> dict:
        """Device time by operator of `sql`, measured on the program this
        session runs for it (obs/device_profile): `census` (the compiled
        program's, obs/program_census), `hostSpans` (name -> [count, ms]
        of the profiled execution), `wallMs`, and `device`: per chip busy
        ms and self ms by operator, by operator/step and by kind, None
        where the backend has no device planes.  Raises `ProfileBusy`
        while another profile runs in this process."""
        return self._profile_plan(
            self._plan_cache.get(sql) or self._plan_stmt(parse(sql))
        )

    def _profile_plan(self, plan) -> dict:
        import contextlib

        from .obs import device_profile as dp

        if isinstance(plan.source, P.TableWriter):
            raise ValueError(
                "a profile executes its plan more than once: not for a "
                "statement that writes"
            )

        def executor():
            ex = self._executor()
            ex.config["collect_node_stats"] = False   # the compiled path
            return ex

        on_chip = dp.has_device_planes()
        if on_chip:
            # untimed: compiles what is not cached, settles the ladder
            executor().execute(plan)
        ex = executor()
        with (dp.capture() if on_chip else contextlib.nullcontext({})) as cap:
            with self.tracer.span("device_profile") as root:
                ex.execute(plan)
        spans: dict = {}
        for s in list(self.tracer.spans):
            if s.trace_id == root.trace_id and s is not root:
                rec = spans.setdefault(s.name, [0, 0.0])
                rec[0] += 1
                rec[1] += s.duration_ms
        census = (ex.kernel_profile or {}).get("programCensus")
        return {
            "wallMs": root.duration_ms,
            "hostSpans": spans,
            "census": census,
            "device": dp.reduce(
                cap.get("planes") or {}, (census or {}).get("ops")
            ),
        }

    def _explain_analyze(self, query, query_id: str) -> Page:
        """EXPLAIN ANALYZE: execute with per-node instrumentation and print
        the plan annotated with rows + wall time (ExplainAnalyzeOperator +
        PlanPrinter.textDistributedPlan analog; single-node executor)."""
        import time

        plan = self._plan_stmt(query)
        executor = LocalExecutor(
            self.catalogs,
            {
                "group_capacity": self.properties.get("group_capacity"),
                "collect_node_stats": True,
                "spill_enabled": False,
                "query_id": query_id,
            },
        )
        t0 = time.perf_counter()
        t_created = time.time()  # wall-clock window for the doctor
        fallbacks = executor.supervisor.fallback_attempted
        page = executor.execute(plan)
        wall = time.perf_counter() - t0
        self.last_kernel_profile = getattr(executor, "kernel_profile", None)
        text = P.plan_to_string(plan, executor.node_stats)
        text += (
            f"\n\nQuery: {page.count} output rows in {wall * 1000:.2f}ms "
            f"(single node)"
        )
        if executor.supervisor.fallback_attempted > fallbacks:
            # this pass has its own executor and the process's default
            # supervisor: the session's fallback setting does not reach it
            text += (
                "\n  DEGRADED: this eager pass met a device fault (device "
                f"{executor.supervisor.device_state()}) and ran again on "
                "the CPU backend: its rows are exact, its walls the CPU's"
            )
        # per-operator timeline (OperatorStats frames): estimated rows
        # come from the cost model so estimate-vs-observed divergence is
        # visible per operator
        from .obs import opstats as _opstats

        costs = None
        try:
            from .plan.cost import annotate

            costs = annotate(plan, self.metadata, self.properties)
        except Exception:
            pass
        frames = _opstats.frames_from_plan(
            plan, executor.node_stats, costs=costs,
            blocked_memory_s=getattr(executor, "blocked_memory_s", 0.0),
            blocked_exchange_s=getattr(
                executor, "blocked_exchange_s", 0.0
            ),
        )
        # the compiled program of the same plan, through the session's own
        # executor: its census and, where the backend has device planes,
        # its measured device time (a mesh: the slowest chip's, by operator)
        compiled, compiled_error = None, ""
        try:
            compiled = self._profile_plan(plan)
        except Exception as e:  # noqa: BLE001 — the eager pass stands alone
            compiled_error = "%s: %s" % (type(e).__name__, e)
        chips = (compiled or {}).get("device")
        if chips:
            from .obs import device_profile as _dp

            _dp.apply_device_time(frames, _dp.slowest_by_operator(chips))
        self.last_timeline = {
            "queryId": query_id, "wallS": wall, "operators": frames,
        }
        text += "\n\n" + _opstats.format_timeline(frames, wall)
        prof = self.last_kernel_profile or {}
        summary = prof.get("summary") or {}
        if summary:
            text += (
                "\n\nTPU kernel profile:"
                f"\n  kernels: {summary.get('kernels', 0)}"
                f" (compile wall {summary.get('compileWallS', 0.0) * 1000:.2f}ms,"
                f" recompiles {summary.get('recompiles', 0)},"
                f" cache hits {summary.get('cacheHits', 0)})"
                f"\n  padding: {summary.get('actualRows', 0)} rows padded to "
                f"{summary.get('paddedRows', 0)} "
                f"(ratio {summary.get('paddingRatio', 1.0):.2f}x)"
                f"\n  transfers: ~{summary.get('h2dBytes', 0)}B host->device, "
                f"~{summary.get('d2hBytes', 0)}B device->host"
            )
            for k in prof.get("kernels") or []:
                text += (
                    f"\n  kernel {k['digest']} [{k['mode']}]: "
                    f"compile {k['compileWallS'] * 1000:.2f}ms, "
                    f"executions {k['executions']}, "
                    f"compiles {k['compiles']}"
                )
            # the observatory's cause taxonomy: benign first compiles
            # vs the shape-miss retraces ROADMAP item 3 wants at zero
            # in steady state
            from .obs import compile_observatory as _co

            by_cause = summary.get("compilesByCause") or {}
            text += "\n\nCompiles:"
            if any(by_cause.values()):
                for cause in _co.CAUSES:
                    n = by_cause.get(cause, 0)
                    if n:
                        text += f"\n  {cause}: {n}"
            else:
                text += "\n  (no compiles this query)"
        if compiled and compiled.get("census"):
            from .obs import device_profile as _dp

            text += "\n\n" + _dp.format_profile(compiled)
            if chips:
                text += (
                    "\n  (the timeline's device= is this table's, by "
                    "operator, the slowest chip's)"
                )
        elif compiled_error:
            text += (
                "\n\nCompiled program: not profiled ("
                + compiled_error.splitlines()[0][:300] + ")"
            )
        # the doctor's causal verdict over the same evidence (EXPLAIN
        # ANALYZE is the interactive "why was this slow" surface)
        if self.properties.get("query_doctor"):
            try:
                from .obs import doctor

                diag = doctor.diagnose_query(
                    query_id,
                    window=(t_created, time.time()),
                    timeline=self.last_timeline,
                    profile=prof,
                    wall_s=wall,
                )
                doctor.record_diagnosis(diag)
                self.last_diagnosis = diag
                text += "\n\n" + doctor.format_diagnosis(diag)
            except Exception:  # noqa: BLE001 — diagnosis is best-effort
                pass
        col = column_from_pylist(T.VARCHAR, text.split("\n"))
        return Page([col], len(text.split("\n")), ["Query Plan"])

    def _check_plan_access(self, plan: P.PlanNode, identity) -> None:
        """Table-level authorization over the planned statement: SELECT on
        every scanned table, INSERT/DELETE/CREATE on write targets
        (AccessControlManager checks made by StatementAnalyzer /
        planner in the reference)."""
        ac = self.access_control

        def walk(n: P.PlanNode):
            if isinstance(n, P.TableScan):
                ac.check_can_select(
                    identity, n.catalog, n.table,
                    [c for _, c in n.assignments],
                )
            if isinstance(n, P.TableWriter):
                if n.create_schema is not None:
                    ac.check_can_create_table(identity, n.catalog, n.table)
                elif n.count_symbol is not None:  # UPDATE rewrites rows
                    ac.check_can_insert(identity, n.catalog, n.table)
                    ac.check_can_delete(identity, n.catalog, n.table)
                elif n.report_deleted:
                    ac.check_can_delete(identity, n.catalog, n.table)
                else:
                    ac.check_can_insert(identity, n.catalog, n.table)
            for s in n.sources:
                walk(s)

        walk(plan)

    def _plan_stmt(self, stmt) -> P.PlanNode:
        with self.tracer.span("analyze_plan"):
            analyzer = Analyzer(self.metadata, self.default_catalog,
                            self.sql_functions)
            plan = analyzer.plan_statement(stmt)
        with self.tracer.span("optimize"):
            plan = optimize(plan, self.metadata, self.properties)
        return plan

    # -- ANALYZE (stats/ collection) -----------------------------------
    def execute_analyze(self, stmt, identity=None, execute_plan=None):
        """ANALYZE <table> [(cols)]: collect, store, register.  The
        coordinator passes `execute_plan` to run the synthesized
        aggregations through the distributed fragment scheduler instead
        of the in-process executor."""
        if identity is None:
            identity = self.identity
        catalog, schema = self.metadata.resolve_table(
            stmt.table, self.default_catalog
        )
        self.access_control.check_can_select(
            identity, catalog, schema.name,
            list(stmt.columns) or [c.name for c in schema.columns],
        )
        started = time.time()
        stats = self.collect_statistics(
            catalog, schema, stmt.columns, execute_plan=execute_plan
        )
        version = self.metadata.store_table_statistics(
            catalog, schema.name, stats
        )
        self.record_analyze(
            catalog, schema.name,
            stmt.columns or tuple(c.name for c in schema.columns),
            stats, version, started,
        )
        return page_from_pydict(
            [("rows", T.BIGINT)], {"rows": [int(stats.row_count)]}
        )

    def collect_statistics(self, catalog: str, schema, columns=(),
                           execute_plan=None):
        """Run the synthesized ANALYZE aggregations and assemble a
        TableStatistics.  The collection is ordinary SQL through the
        normal planner (QueryPlanner.planStatisticsAggregation analog),
        so under distributed=true the HLL/KMV partial-final merge rides
        the mesh like any aggregation; `execute_plan` lets the
        coordinator dispatch the same plans through its scheduler."""
        from .stats import analyze_queries, assemble, column_tasks

        buckets = max(1, int(self.properties.get("analyze_histogram_buckets")))
        tasks = column_tasks(schema, columns)
        qualified = f"{catalog}.default.{schema.name}"
        if execute_plan is None:
            executor = self._executor()
            execute_plan = executor.execute
        chunk_results = []
        with self.tracer.span("analyze_collect", table=qualified):
            for csql, chunk in analyze_queries(qualified, tasks, buckets):
                page = execute_plan(self._plan_stmt(parse(csql)))
                row = [
                    c.to_python(page.count)[0] if page.count else None
                    for c in page.columns
                ]
                chunk_results.append((chunk, row))
        return assemble(chunk_results, buckets)

    def record_analyze(self, catalog: str, table: str, columns,
                       stats, data_version: int, started: float) -> None:
        """Registry entry + invalidation after statistics storage: cached
        plans were costed without these stats."""
        from .utils.metrics import counter

        self.analyzed_tables[(catalog, table)] = {
            "catalog": catalog,
            "table": table,
            "columns": tuple(columns),
            "row_count": float(stats.row_count),
            "data_version": int(data_version),
            "analyzed_at": started,
            "duration_s": max(0.0, time.time() - started),
        }
        self._plan_cache.clear()
        self._capacity_hints.clear()
        counter("trino_tpu_stats_analyze_total").inc()


def tpch_session(sf: float = 0.01, **config) -> Session:
    """One-liner dev entry (TpchQueryRunner analog, SURVEY appendix A)."""
    s = Session(config=config)
    s.create_catalog("tpch", "tpch", {"tpch.scale-factor": sf})
    return s


def tpcds_session(sf: float = 0.01, **config) -> Session:
    s = Session(config=config)
    s.create_catalog("tpcds", "tpcds", {"tpcds.scale-factor": sf})
    return s
