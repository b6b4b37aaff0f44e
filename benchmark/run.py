"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one client, closed loop: the queries' substitution parameters
are drawn from --seed, warmed in set-up, and cycled back to back through
`tpch_session(sf, ...).execute(sql).to_pylist()` until the window is over.
The answers the window's own queries returned are then compared with the
plain reference (`queries/<name>.py` over `datagen.py`).  The last line of
standard output is the result; everything a cell, a query or a metric is
made of sits in a file of its own, found by its name in BENCHMARK.json.
"""
import time

T0 = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE, os.path.join(HERE, "queries")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import datagen  # noqa: E402
import trace_reduce  # noqa: E402
import yardstick  # noqa: E402

MIN_TRACED_QUERIES = 3


def unmarked(name):
    return contextlib.nullcontext()


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def load_module(kind, name):
    """benchmark/<kind>/<name>.py, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location("%s.%s" % (kind, name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric, cell):
    return cell in metric.get("workloads", [cell])


def draw_sets(query, seed, workload):
    """The run's distinct parameter sets, a pure function of the seed: drawn
    over the query's (the spec's) ranges, narrowed where the workload says."""
    import numpy as np

    n = workload["param_sets_per_run"]
    ranges = dict(query.RANGES, **workload.get("parameters", {}))
    shared = workload.get("shared_parameters", [])   # one draw for all sets
    rng = np.random.default_rng(seed)
    sets = []
    for _ in range(1000):
        p = query.draw(rng, ranges)
        if sets:
            p.update({k: sets[0][k] for k in shared})
        if p not in sets:
            sets.append(p)
        if len(sets) == n:
            return sets
    raise ValueError("the query's ranges hold fewer than %d parameter sets" % n)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="sandbox rehearsal only: accept a non-TPU device")
    p.add_argument("--sf", type=float, default=0.0,
                   help="rehearsal only: a small scale factor in place of "
                        "the configuration's")
    p.add_argument("--keep-trace", default="",
                   help="copy the raw trace into this directory (to read one "
                        "by hand; see trace_dump.py)")
    args = p.parse_args(argv)
    if args.sf and not args.rehearse_cpu:
        p.error("--sf is for the CPU rehearsal (--rehearse-cpu) only")
    return args


class Window:
    """The closed loop and what it saw."""

    def __init__(self, session, texts, traced, slice_s):
        self.session, self.texts = session, texts
        self.traced, self.slice_s = traced, slice_s
        self.latencies = []
        self.answers = [None] * len(texts)
        self.unequal = self.failed = self.attempted = self.compiles = 0
        self.spans = {}          # tracer span name -> [count, total ms]
        self.trace_dir = None

    def query(self, i, note):
        """One query of the loop; `note(name)` is a context manager, the
        profiler's annotation inside the traced slice."""
        self.attempted += 1
        began = time.perf_counter()
        try:
            with note("bench:query"):
                with note("bench:execute"):
                    page = self.session.execute(self.texts[i])
                with note("bench:materialize"):
                    rows = page.to_pylist()
        except Exception as e:  # noqa: BLE001 — a failed query is counted
            self.failed += 1
            log("query failed: %s: %s" % (type(e).__name__, str(e)[:300]))
            return
        self.latencies.append(time.perf_counter() - began)
        if self.answers[i] is not None and self.answers[i] != rows:
            self.unequal += 1
        self.answers[i] = rows
        summary = (self.session.last_kernel_profile or {}).get("summary") or {}
        self.compiles += int(summary.get("compiles") or 0)

    def drain_spans(self):
        """The program's tracer keeps a ring of 4096 spans: empty it after
        each query of a traced run."""
        spans = self.session.tracer.spans
        while spans:
            s = spans.popleft()
            rec = self.spans.setdefault(s.name, [0, 0.0])
            rec[0] += 1
            rec[1] += s.duration_ms

    def run(self, seconds):
        import jax.profiler

        n = len(self.texts)
        tracing, traced_n, t_trace = "off", 0, 0.0
        if self.traced:
            tracing = "due"
            self.session.tracer.spans.clear()
        start = time.perf_counter()
        i = 0
        while True:
            if tracing == "due" and i >= 1:
                self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
                tracing, t_trace = "on", time.perf_counter()
            self.query(i % n, jax.profiler.TraceAnnotation if tracing == "on"
                       else unmarked)
            i += 1
            now = time.perf_counter()
            if self.traced:
                self.drain_spans()
            if tracing == "on":
                traced_n += 1
                if (traced_n >= MIN_TRACED_QUERIES
                        and now - t_trace >= self.slice_s):
                    jax.profiler.stop_trace()
                    tracing = "done"
                    now = time.perf_counter()
            if now - start >= seconds and tracing != "on":
                return now - start


def device_checks(session, query, platform, interpret_calls, setup_profiles):
    """The program's own state has to show that the device did the work."""
    from trino_tpu.runtime.supervisor import ACTIVE
    from trino_tpu.utils.metrics import REGISTRY

    host_scans = 0
    for key, entry in session._scan_cache.entries.items():
        if key[1] in query.TABLES:
            host_arrays = [c for c, (v, _ok) in entry["merged"].items()
                           if hasattr(v, "dtype")]
            host_scans += int(entry.get("devgen") is None or bool(host_arrays))
    if not any((p.get("devgenWallS") or 0) > 0 for p in setup_profiles):
        host_scans += 1
    return {
        "cpu_fallbacks": int(REGISTRY.counter(
            "trino_tpu_device_fallback_total").total()),
        "devices_not_active": int(
            session.device_supervisor.device_state(0) != ACTIVE),
        "interpret_kernels": sum(interpret_calls) if platform == "tpu" else 0,
        "host_generated_scans": host_scans,
    }


def main(argv=None):
    args = parse_args(argv)
    bench = yardstick.load_json(os.pardir, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log("no cell %r in BENCHMARK.json" % args.workload)
        return 2
    cell = cells[args.workload]
    workload = yardstick.load_json("workloads", cell["name"] + ".json")
    cfg = yardstick.load_json("configs", cell["config"] + ".json")
    query = load_module("queries", workload["query"])

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log("device: %s jax=%s" % (json.dumps(device), jax.__version__))
    if device["platform"] != "tpu" and not args.rehearse_cpu:
        log("no TPU: jax.devices()[0].platform is %r" % device["platform"])
        return 2
    if len(devs) < cell["chips"]:
        log("the cell needs %d chips, jax reports %d" % (cell["chips"], len(devs)))
        return 2
    peaks = yardstick.peaks(device["kind"]) if device["platform"] == "tpu" else None
    jax.config.update("jax_enable_x64", True)
    from trino_tpu.cache.compile_cache import place_jax_cache
    from trino_tpu.obs import compile_observatory
    from trino_tpu.ops import pallas_kernels
    from trino_tpu.session import tpch_session

    log("compile cache: %s" % place_jax_cache())
    sf = args.sf or cfg["sf"]
    if args.sf:   # the rehearsal's row counts are its own
        cfg = dict(cfg, tables={t: dict(v, rows=None) for t, v in cfg["tables"].items()})

    # observe (not steer) every pallas call the engine builds
    interpret_calls = []
    real_call = pallas_kernels.pl.pallas_call

    def spy(*a, **kw):
        interpret_calls.append(bool(kw.get("interpret")))
        return real_call(*a, **kw)

    pallas_kernels.pl.pallas_call = spy
    try:
        # -- set-up: session, parameter sets, compile and one warm run each
        log("set-up %.1f s: imports and device" % (time.perf_counter() - T0))
        session = tpch_session(sf, **cfg["session"])
        params = draw_sets(query, args.seed, workload)
        texts = [query.sql(p) for p in params]
        log("parameter sets: %s" % json.dumps(params))
        setup_profiles = []
        for text in texts:
            for _ in range(2):
                session.execute(text).to_pylist()
                setup_profiles.append(dict(session.last_kernel_profile or {}))
                log("set-up %.1f s: one execution" % (time.perf_counter() - T0))
        observatory = compile_observatory.get_observatory()
        compiles_before = sum(observatory.counts.values())
        window = Window(session, texts, bool(args.trace), workload["trace_slice_s"])
        setup_seconds = time.perf_counter() - T0

        # -- the measured window
        window_s = window.run(args.seconds)
        stats = devs[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use") or 0)
        checks = {
            "failed_queries": window.failed,
            "unanswered_texts": sum(a is None for a in window.answers),
            "unequal_repeats": window.unequal,
            "window_compiles": max(
                window.compiles,
                sum(observatory.counts.values()) - compiles_before),
        }
        checks.update(device_checks(
            session, query, device["platform"], interpret_calls, setup_profiles))
    finally:
        pallas_kernels.pl.pallas_call = real_call

    # -- the plain reference, once the window has closed (not counted as set-up)
    t_ref = time.perf_counter()
    refs, ref_rows = query.reference(datagen, sf, params)
    checks["wrong_answers"] = sum(
        a is not None and not query.check(a, r)
        for a, r in zip(window.answers, refs))
    if args.sf:
        for t, n in ref_rows.items():
            cfg["tables"][t]["rows"] = n
    checks["row_count_gap"] = sum(
        abs(cfg["tables"][t]["rows"] - n) for t, n in ref_rows.items())
    log("reference: %.1f s for %d parameter set(s), rows %s" % (
        time.perf_counter() - t_ref, len(params), json.dumps(ref_rows)))

    ctx = {
        "cell": cell, "workload": workload, "config": cfg, "query": query,
        "peaks": peaks, "setup_seconds": setup_seconds, "window_s": window_s,
        "latencies_s": window.latencies, "setup_profiles": setup_profiles,
        "spans": window.spans, "trace": None,
        "rows_per_query": yardstick.rows_per_query(cfg, query.TABLES),
        "least_bytes_per_query": yardstick.bytes_per_query(
            cfg, query.TABLES, "min_bytes"),
        "stored_bytes_per_query": yardstick.bytes_per_query(
            cfg, query.TABLES, "stored_bytes"),
    }
    result = {
        "correct": not any(checks.values()),
        "attempted": window.attempted, "failed": window.failed,
        "metrics": {}, "device": device,
    }
    if args.trace:
        kind, listed = "layers", bench["per_layer"]
        try:
            path = trace_reduce.find_xplane(window.trace_dir)
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(path, args.keep_trace)
            ctx["trace"] = trace = trace_reduce.reduce(trace_reduce.load(path))
        finally:
            shutil.rmtree(window.trace_dir, ignore_errors=True)
        if trace and trace["busy_s"]:
            device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
            result["breakdown"] = {
                "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    else:
        kind, listed = "end_to_end", bench["end_to_end"]
    for metric in listed:
        if applies(metric, cell["name"]):
            value = load_module(kind, metric["name"]).read(ctx)
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}

    lat = sorted(window.latencies)
    if lat:
        log("window: %.3f s, %d queries, latency ms min %.3f p50 %.3f p95 %.3f "
            "max %.3f; %.3f GB/s of stored bytes" % (
                window_s, len(lat), lat[0] * 1e3, lat[len(lat) // 2] * 1e3,
                lat[min(len(lat) - 1, int(len(lat) * 0.95))] * 1e3, lat[-1] * 1e3,
                ctx["stored_bytes_per_query"] * len(lat) / window_s / 1e9))
    # each number compared beside its limit: last on stderr, last in the line
    result["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    for k, v in checks.items():
        log("check %s: %d (limit 0)%s" % (k, v, "" if not v else "  <-- FAILED"))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
