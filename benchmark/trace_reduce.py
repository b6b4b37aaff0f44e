"""Reduction of a profiler trace (`.xplane.pb`) to the numbers the per-layer
readers use.  Everything is taken between the start of the first and the end
of the last `bench:query` annotation the harness wrote, so the traced slice
holds whole queries only.

A trace is planes -> lines -> events (start_ns, duration_ns).  On a TPU each
chip is a plane `/device:TPU:<n>`; its line `XLA Ops` has one event per
device operation and `XLA Modules` one per program launched.  The harness's
annotations are events on a line of the host plane.
"""
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
QUERY = "bench:query"
PHASES = ("bench:execute", "bench:materialize")
TOP = 10


def find_xplane(trace_dir):
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def load(path):
    """The trace as plain data: {plane: {line: [(name, start_ns, end_ns)]}}."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events)
    return out


def short_name(name):
    """A device operation's trace name is its whole HLO line: keep the
    result's name, its opcode, a custom call's target and the first shape."""
    m = re.match(r"(%[^ ]+) = \(?([a-z0-9]+\[[0-9,]*\]).*?\b([a-z][a-z\-]*)\(", name)
    if not m:
        return name[:120]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    parts = (m.group(1), m.group(3)) + ((target.group(1),) if target else ())
    return " ".join(parts + (m.group(2),))[:120]


def union(intervals):
    """Disjoint, sorted union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def _labeller(events):
    """label(t) for non-decreasing t: the harness's phase at t on the host
    thread that ran the queries, and the innermost host event under it."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    state = {"i": 0, "open": []}

    def label(t):
        while state["i"] < len(evs) and evs[state["i"]][1] <= t:
            state["open"].append(evs[state["i"]])
            state["i"] += 1
        state["open"] = [ev for ev in state["open"] if ev[2] > t]
        phase, inner = "between", None
        for name, _, _ in state["open"]:
            if name in PHASES:
                phase = name.split(":", 1)[1]
            elif name != QUERY:
                inner = name
        return phase if inner is None else "%s/%s" % (phase, inner)

    return label


def reduce(planes):
    """The traced slice in numbers, or None where the harness's annotations
    are not in the trace.  Device numbers are None where no device plane has
    an operation (a CPU rehearsal)."""
    host = next((evs for lines in planes.values() for evs in lines.values()
                 if any(ev[0] == QUERY for ev in evs)), None)
    if host is None:
        return None
    marks = [ev for ev in host if ev[0] == QUERY]
    lo, hi = min(s for _, s, _ in marks), max(e for _, _, e in marks)
    out = {
        "queries": len(marks), "window_s": (hi - lo) / 1e9,
        "busy_s": None, "chips": 0, "modules": None,
        "device_ops": [], "idle_gaps": [],
    }
    busy, modules, by_op, gaps = [], 0, {}, {}
    for name, lines in planes.items():
        ops = _clip(lines.get(OPS_LINE, ()), lo, hi)
        if not name.startswith(DEVICE_PLANE) or not ops:
            continue
        out["chips"] += 1
        modules += len(_clip(lines.get(MODULES_LINE, ()), lo, hi))
        for n, s, e in ops:
            n = short_name(n)
            by_op[n] = by_op.get(n, 0.0) + (e - s) / 1e9
        merged = union((s, e) for _, s, e in ops)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        label = _labeller(host)
        for s, e in zip(edges[0::2], edges[1::2]):   # the idle stretches
            if e > s:
                name = label((s + e) // 2)
                gaps[name] = gaps.get(name, 0.0) + (e - s) / 1e9
    if busy:
        n = len(busy)
        out["busy_s"] = sum(busy) / n
        out["modules"] = modules / n
        out["device_ops"] = [
            [k, v / n] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]]
        out["idle_gaps"] = [
            [k, v / n] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]]
    return out
