"""Standing proof that the SQL main path runs on the chip.

    python chip_smoke.py            # one TPU chip: Q6 + Q1 at SF10, Q3 at SF1
    python chip_smoke.py --mesh 4   # four chips: Q1 at SF10 + Q3 at SF1 on a mesh

SQL text -> parser -> planner -> executor -> XLA/pallas on the device -> host
rows, through `trino_tpu.session.tpch_session(sf).execute(sql)`, in ONE
process.  Every answer is compared with a plain numpy reference over the
HOST generator's columns, and the engine's own state must show that the
device did the work (no CPU fallback, no interpret-mode kernel, lineitem
generated in HBM, zero compiles in the warm repeats).

The numbers printed are findings of a smoke run, not benchmark results.
The last line of stdout is one JSON object; exit code 0 only when every
phase passed on a TPU.  `--sf` and `--rehearse-cpu` exist for rehearsing
the control flow on the CPU at a tiny size.
"""
import argparse
import datetime
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

SF_SCAN = 10.0   # Q6, Q1: scan -> filter -> aggregate over ~60M lineitem rows
SF_JOIN = 1.0    # Q3: customer x orders x lineitem, the sort-merge join path
WARM_RUNS = 3
EPOCH = datetime.date(1970, 1, 1)


def _days(y, m, d):
    return (datetime.date(y, m, d) - EPOCH).days


def log(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------
# plain numpy reference over the host generator (shares no operator code
# with the engine): decimals are scaled int64, dates int32 days


def _lineitem_chunks(sf, cols):
    """Host-generated lineitem columns, a slice of the order space at a
    time (SF10 is ~60M rows; slices bound the host memory)."""
    from trino_tpu.connectors import tpch

    n = max(1, int(round(sf * 4)))
    for i in range(n):
        vals, dicts, _ = tpch.generate("lineitem", sf, i, n, cols)
        yield vals, dicts


def ref_q6(sf):
    lo, hi = _days(1994, 1, 1), _days(1995, 1, 1)
    total = 0
    for v, _ in _lineitem_chunks(
        sf, ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]
    ):
        m = (
            (v["l_shipdate"] >= lo) & (v["l_shipdate"] < hi)
            & (v["l_discount"] >= 5) & (v["l_discount"] <= 7)
            & (v["l_quantity"] < 2400)
        )
        total += int((v["l_extendedprice"][m] * v["l_discount"][m]).sum())
    return [(total,)]  # sum(l_extendedprice * l_discount), scale 4


def _avg(total, count, shift):
    """avg of scaled ints at `shift` more digits, rounded half up."""
    num = total * 10 ** shift
    return (2 * num + count) // (2 * count)


def ref_q1(sf):
    import numpy as np

    cutoff = _days(1998, 12, 1) - 90
    acc = {}
    for v, dicts in _lineitem_chunks(
        sf, ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
             "l_discount", "l_tax", "l_shipdate"]
    ):
        m = v["l_shipdate"] <= cutoff
        rf, ls = v["l_returnflag"][m], v["l_linestatus"][m]
        qty, ext = v["l_quantity"][m], v["l_extendedprice"][m]
        disc, tax = v["l_discount"][m], v["l_tax"][m]
        disc_price = ext * (100 - disc)
        charge = disc_price * (100 + tax)
        for a in np.unique(rf):
            for b in np.unique(ls):
                g = (rf == a) & (ls == b)
                n = int(g.sum())
                if not n:
                    continue
                key = (str(dicts["l_returnflag"][a]),
                       str(dicts["l_linestatus"][b]))
                row = acc.setdefault(key, [0] * 6)
                for i, x in enumerate((qty, ext, disc_price, charge, disc)):
                    row[i] += int(x[g].sum())
                row[5] += n
    out = []
    for key in sorted(acc):
        q, e, dp, ch, d, n = acc[key]
        out.append(key + (q, e, dp, ch, _avg(q, n, 4), _avg(e, n, 4),
                          _avg(d, n, 4), n))
    return out


def ref_q3(sf):
    import numpy as np

    from trino_tpu.connectors import tpch

    cutoff = _days(1995, 3, 15)
    c, cd, _ = tpch.generate("customer", sf, columns=["c_custkey", "c_mktsegment"])
    building = list(cd["c_mktsegment"]).index("BUILDING")
    cust = c["c_custkey"][c["c_mktsegment"] == building]
    o, _, _ = tpch.generate(
        "orders", sf,
        columns=["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    )
    om = (o["o_orderdate"] < cutoff) & np.isin(o["o_custkey"], cust)
    okey = o["o_orderkey"][om]
    order = np.argsort(okey, kind="stable")
    okey = okey[order]
    odate = o["o_orderdate"][om][order]
    oprio = o["o_shippriority"][om][order]
    groups = {}
    for v, _ in _lineitem_chunks(
        sf, ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"]
    ):
        lm = v["l_shipdate"] > cutoff
        lk = v["l_orderkey"][lm]
        pos = np.searchsorted(okey, lk)
        pos[pos >= len(okey)] = 0
        hit = okey[pos] == lk if len(okey) else np.zeros(len(lk), bool)
        rev = (v["l_extendedprice"][lm] * (100 - v["l_discount"][lm]))[hit]
        pos = pos[hit]
        srt = np.argsort(pos, kind="stable")
        pos, rev = pos[srt], rev[srt]
        if not len(pos):
            continue
        starts = np.flatnonzero(np.r_[True, pos[1:] != pos[:-1]])
        for p, s in zip(pos[starts], np.add.reduceat(rev, starts)):
            groups[int(p)] = groups.get(int(p), 0) + int(s)
    rows = [
        (int(okey[p]), r, int(odate[p]), int(oprio[p]))
        for p, r in groups.items()
    ]
    rows.sort(key=lambda r: (-r[1], r[2], r[0]))
    return rows  # ALL groups, ordered; the caller cuts to the LIMIT


# ---------------------------------------------------------------------
# engine rows -> the reference's encoding


def _scaled(x):
    """Decimal / float / int engine value -> exact scaled python int."""
    import decimal

    if isinstance(x, decimal.Decimal):
        return int(x.scaleb(-x.as_tuple().exponent))
    if isinstance(x, float):
        return int(decimal.Decimal(repr(x)).scaleb(6).to_integral_value())
    return x


def _date(x):
    return (datetime.date.fromisoformat(x) - EPOCH).days if isinstance(x, str) else x


def check_q6(rows, ref):
    return [(_scaled(r[0]),) for r in rows] == ref


def check_q1(rows, ref):
    got = [tuple(r[:2]) + tuple(_scaled(x) for x in r[2:]) for r in rows]
    return got == ref


def check_q3(rows, ref):
    got = [(r[0], _scaled(r[1]), _date(r[2]), r[3]) for r in rows]
    want = ref[:10]
    if got == want:
        return True
    # ORDER BY leaves ties on (revenue, o_orderdate) open: then the sort
    # keys must agree in order and every row must be a true group
    truth = set(ref)
    return (
        [(g[1], g[2]) for g in got] == [(w[1], w[2]) for w in want]
        and all(g in truth for g in got)
    )


# ---------------------------------------------------------------------


class Smoke:
    def __init__(self, args):
        self.args = args
        self.failures = []
        self.interpret_calls = []

    def fail(self, msg):
        self.failures.append(msg)
        log("FAIL: " + msg)

    def expect(self, cond, msg):
        if cond:
            log("ok: " + msg)
        else:
            self.fail(msg)

    # -- set-up -----------------------------------------------------------
    def start(self):
        import jax

        args = self.args
        devs = jax.devices()
        d0 = devs[0]
        self.device = {
            "platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs),
        }
        stats = d0.memory_stats() or {}
        log("device: platform=%s kind=%s count=%d jax=%s bytes_limit=%s" % (
            d0.platform, d0.device_kind, len(devs), jax.__version__,
            stats.get("bytes_limit"),
        ))
        if d0.platform != "tpu" and not args.rehearse_cpu:
            log("no TPU: jax.devices()[0].platform is %r" % d0.platform)
            sys.exit(2)
        want = args.mesh or 1
        if len(devs) < want:
            log("need %d devices, jax reports %d" % (want, len(devs)))
            sys.exit(2)
        jax.config.update("jax_enable_x64", True)
        from trino_tpu.cache.compile_cache import place_jax_cache

        cache_dir = place_jax_cache()
        try:
            held = len(os.listdir(cache_dir))
        except OSError:
            held = 0
        log("compile cache: %s (%d entries at start)" % (cache_dir, held))

        # observe (not steer) every pallas call the engine builds
        from trino_tpu.ops import pallas_kernels as pk

        real = pk.pl.pallas_call

        def spy(*a, **kw):
            self.interpret_calls.append(bool(kw.get("interpret")))
            return real(*a, **kw)

        pk.pl.pallas_call = spy
        from trino_tpu.connectors import native_gen

        log("host generator for the reference: %s" % (
            "native (native/tpchgen.cpp, built on first use)"
            if native_gen.available() else "numpy"
        ))

    # -- one query: cold + warm, checked ------------------------------
    def run_query(self, session, name, sql, check, ref, rows_in):
        import jax
        from trino_tpu.obs import compile_observatory as co

        obs = co.get_observatory()
        d0 = jax.devices()[0]
        t0 = time.perf_counter()
        page = session.execute(sql)
        rows = page.to_pylist()
        cold = time.perf_counter() - t0
        prof = dict(session.last_kernel_profile or {})
        summ = prof.get("summary") or {}
        compiles_before = sum(obs.counts.values())
        warm = []
        for _ in range(WARM_RUNS):
            t0 = time.perf_counter()
            rows_w = session.execute(sql).to_pylist()
            warm.append(time.perf_counter() - t0)
            if rows_w != rows:
                self.fail("%s: a warm repeat answered differently" % name)
            wsum = (session.last_kernel_profile or {}).get("summary") or {}
            if wsum.get("compiles"):
                self.fail("%s: warm repeat compiled %s program(s)" % (
                    name, wsum.get("compiles")))
        warm_compiles = sum(obs.counts.values()) - compiles_before
        med = statistics.median(warm)
        stats = d0.memory_stats() or {}
        kernels = prof.get("kernels") or []
        path = {
            "programs": [
                "%s:%s" % (k.get("mode"), k.get("digest")) for k in kernels
            ],
            "fusedAggregates": prof.get("fusedAggregates", 0),
            "fusionRejects": prof.get("fusionRejects", 0),
            "lastFusionReject": prof.get("lastFusionReject"),
            "streamedFragments": prof.get("streamedFragments", 0),
        }
        path["shape"] = (
            "streamed tiles" if path["streamedFragments"]
            else "one monolithic program"
        ) + (", fused pallas kernel" if path["fusedAggregates"]
             else ", XLA segment ops")
        log("%s: cold_s=%r warm_median_s=%r warm_s=%r rows_per_s=%r "
            "compile_s=%r peak_bytes_in_use=%s bytes_in_use=%s" % (
                name, cold, med, warm, rows_in / med if med else None,
                sum(k.get("compileWallS", 0.0) for k in kernels),
                stats.get("peak_bytes_in_use"),
                stats.get("bytes_in_use")))
        log("%s: path=%s" % (name, json.dumps(path, default=str)))
        log("%s: device generation: compile_s=%r run_s=%r; summary=%s" % (
            name, prof.get("devgenCompileS"), prof.get("devgenWallS"),
            json.dumps(summ, default=str)))
        # a streamed query's tiles bypass the session scan cache, so the
        # cold run's own profile is the witness of device generation
        self.expect((prof.get("devgenWallS") or 0) > 0,
                    "%s: its scans were generated on the device" % name)
        self.expect(warm_compiles == 0,
                    "%s: zero compiles in the compile observatory over %d "
                    "warm repeats (saw %d)" % (name, WARM_RUNS, warm_compiles))
        ok = check(rows, ref)
        self.expect(ok, "%s: answer equals the numpy reference (%d rows)" % (
            name, len(rows)))
        if not ok:
            log("%s engine:    %r" % (name, rows[:4]))
            log("%s reference: %r" % (name, ref[:4]))
        return prof

    # -- engine state after the queries ------------------------------
    def check_device_did_the_work(self, sessions, devgen_tables):
        from trino_tpu.runtime.supervisor import ACTIVE
        from trino_tpu.utils.metrics import REGISTRY

        fallbacks = int(REGISTRY.counter(
            "trino_tpu_device_fallback_total").total())
        self.expect(fallbacks == 0,
                    "trino_tpu_device_fallback_total is 0 (saw %d)" % fallbacks)
        for s in sessions:
            n = self.args.mesh or 1
            states = [s.device_supervisor.device_state(i) for i in range(n)]
            self.expect(all(st == ACTIVE for st in states),
                        "supervisor device state is ACTIVE (%s)" % states)
        if self.device["platform"] == "tpu":
            self.expect(not any(self.interpret_calls),
                        "no pallas call was built with interpret=True "
                        "(%d built)" % len(self.interpret_calls))
        for s in sessions:
            for key, entry in s._scan_cache.entries.items():
                table = key[1]
                gen = entry.get("devgen") is not None
                host_arrays = [
                    c for c, (v, _ok) in entry["merged"].items()
                    if hasattr(v, "dtype")
                ]
                log("scan %s%s: device_generated=%s device_lanes=%d "
                    "host_arrays=%d" % (table, list(key[2]), gen,
                                        len(entry.get("dev") or {}),
                                        len(host_arrays)))
                if table in devgen_tables:
                    self.expect(
                        gen and not host_arrays,
                        "%s scan came from device generation, not host "
                        "upload" % table)

    # -- one chip ---------------------------------------------------------
    def one_chip(self):
        from tpch_sql import QUERIES
        from trino_tpu.connectors import tpch
        from trino_tpu.session import tpch_session

        sf_scan = self.args.sf or SF_SCAN
        sf_join = self.args.sf or SF_JOIN
        n_line = tpch._counts(sf_scan)["lineitem"]
        log("phase scan: tpch_session(%r), lineitem ~%d rows" % (sf_scan, n_line))
        s10 = tpch_session(sf_scan, device_cpu_fallback=False,
                           result_cache=False)
        mode = s10._executor()._megakernel_mode()
        log("megakernels=auto resolved to %r" % mode)
        if self.device["platform"] == "tpu":
            self.expect(mode == "on", "megakernels=auto resolved to on")
        t0 = time.perf_counter()
        r6, r1 = ref_q6(sf_scan), ref_q1(sf_scan)
        log("reference Q6+Q1 (host numpy, sf %r): %.1fs" % (
            sf_scan, time.perf_counter() - t0))
        self.run_query(s10, "Q6", QUERIES[6][0], check_q6, r6, n_line)
        self.run_query(s10, "Q1", QUERIES[1][0], check_q1, r1, n_line)
        # the SF10 lanes leave HBM before the join phase
        self.check_device_did_the_work([s10], {"lineitem"})
        s10._scan_cache.entries.clear()
        s10._scan_cache.bytes = 0

        log("phase join: tpch_session(%r)" % sf_join)
        s1 = tpch_session(sf_join, device_cpu_fallback=False,
                          result_cache=False)
        t0 = time.perf_counter()
        r3 = ref_q3(sf_join)
        log("reference Q3 (host numpy, sf %r): %.1fs, %d groups" % (
            sf_join, time.perf_counter() - t0, len(r3)))
        rows_in = sum(tpch._counts(sf_join)[t]
                      for t in ("customer", "orders", "lineitem"))
        self.run_query(s1, "Q3", QUERIES[3][0], check_q3, r3, rows_in)
        self.check_device_did_the_work(
            [s1], {"lineitem", "orders", "customer"})

    # -- four chips -------------------------------------------------------
    def mesh(self):
        from tpch_sql import QUERIES
        from trino_tpu.obs import journal
        from trino_tpu.parallel import mesh_executor as MX
        from trino_tpu.session import tpch_session

        n = self.args.mesh
        texts = []
        orig = MX.MeshExecutor._compile_fragment

        def spy(fn, *a):
            compiled = orig(fn, *a)
            texts.append(compiled.as_text())
            return compiled

        MX.MeshExecutor._compile_fragment = staticmethod(spy)
        sessions = []
        seen_ops = set()
        for name, q, sf, ref_fn, check in (
            ("Q1", 1, self.args.sf or SF_SCAN, ref_q1, check_q1),
            ("Q3", 3, self.args.sf or SF_JOIN, ref_q3, check_q3),
        ):
            log("phase mesh %s: tpch_session(%r, distributed=True, "
                "num_devices=%d)" % (name, sf, n))
            s = tpch_session(sf, distributed=True, num_devices=n,
                             device_cpu_fallback=False, result_cache=False)
            sessions.append(s)
            t0 = time.perf_counter()
            ref = ref_fn(sf)
            log("reference %s (host numpy): %.1fs" % (
                name, time.perf_counter() - t0))
            del texts[:]
            t0 = time.perf_counter()
            rows = s.execute(QUERIES[q][0]).to_pylist()
            cold = time.perf_counter() - t0
            prof = s.last_kernel_profile or {}
            kernels = prof.get("kernels") or []
            log("mesh %s: cold_s=%r compile_s=%r programs=%s" % (
                name, cold,
                sum(k.get("compileWallS", 0.0) for k in kernels),
                [k.get("digest") for k in kernels]))
            self.expect(check(rows, ref),
                        "mesh %s: answer equals the numpy reference" % name)
            shards = prof.get("scanShards") or {}
            bad = {
                lane: sh for lane, sh in shards.items()
                if len({d for d, _ in sh}) != n or any(r <= 0 for _, r in sh)
            }
            one = next(iter(shards.values()), None)
            log("mesh %s: %d scan lanes, shards of the first: %s" % (
                name, len(shards), one))
            self.expect(bool(shards) and not bad,
                        "mesh %s: every scan lane has %d non-empty shards on "
                        "%d distinct devices" % (name, n, n))
            ops = {op for t in texts
                   for op in ("all-to-all", "all-gather", "all-reduce")
                   if op in t}
            seen_ops |= ops
            log("mesh %s: collectives in the compiled text: %s" % (
                name, sorted(ops)))
            self.expect(bool(ops), "mesh %s: compiled text contains a "
                        "device collective" % name)
        self.expect(bool(seen_ops & {"all-to-all", "all-gather"}),
                    "the mesh programs exchange rows on the device "
                    "(all-to-all / all-gather in the compiled text)")
        shrinks = [e for e in journal.get_journal().tail()
                   if journal.MESH_SHRINK in json.dumps(e, default=str)]
        self.expect(not shrinks, "no mesh_shrink journal event")
        # mesh scans are generated on the mesh, each shard in its own
        # chip's HBM, and kept in the session's scan cache
        self.check_device_did_the_work(
            sessions, {"lineitem", "orders", "customer"})

    def main(self):
        self.start()
        try:
            if self.args.mesh:
                self.mesh()
            else:
                self.one_chip()
        except SystemExit:
            raise
        except BaseException as e:  # noqa: BLE001 — report, then fail
            import traceback

            traceback.print_exc(file=sys.stdout)
            self.fail("exception: %s: %s" % (type(e).__name__, str(e)[:500]))
        if self.failures:
            log("FAILED: %d check(s): %s" % (len(self.failures), self.failures))
            return 1
        print(json.dumps({"ok": True, "device": self.device}), flush=True)
        return 0


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--mesh", type=int, default=0, metavar="N",
                   help="run ONLY the N-device mesh phase (builder-run, N=4)")
    p.add_argument("--sf", type=float, default=0.0,
                   help="CPU rehearsal only: one small scale factor for "
                        "every phase")
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="rehearsal only: accept a non-TPU device")
    args = p.parse_args(argv)
    if args.sf and not args.rehearse_cpu:
        p.error("--sf is for the CPU rehearsal (--rehearse-cpu) only")
    return args


if __name__ == "__main__":
    sys.exit(Smoke(parse_args()).main())
