"""Device time by plan operator, measured on the program that runs.

`capture()` profiles what executes inside it with `jax.profiler` (device
planes only where the backend has them: a TPU) and gives the trace back as
plain data, `{plane: {line: [(name, start_ns, end_ns)]}}`; `reduce()` is
pure: it joins every `XLA Ops` event of every chip with the
`programCensus.ops` of the program (obs/program_census: instruction name ->
`[scope, kind, shape, rule]`) and returns, PER CHIP and never a mean, the
busy time and the self time by operator, by operator/step and by kind.

Self time: the events of one line nest (a `while` holds its body's ops); a
parent's time is its own minus its children's, so nothing counts twice.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional

from .program_census import COUNTERS, MEMORY_KEYS

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_INSTRUCTION = re.compile(r"%?([\w.\-]+)")
_SLOTS = re.compile(r"\[(\d+)")


class ProfileBusy(RuntimeError):
    """Another profile is running in this process (one at a time)."""


def has_device_planes() -> bool:
    """Whether a profile of this backend carries device planes (a TPU)."""
    import jax

    return jax.default_backend() == "tpu"


def load(trace_dir: str) -> dict:
    """The newest `.xplane.pb` under `trace_dir` as plain data."""
    import jax.profiler

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {}
    out: Dict[str, Dict[str, list]] = {}
    for plane in jax.profiler.ProfileData.from_file(paths[-1]).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events)
    return out


@contextlib.contextmanager
def capture():
    """Profile the block; the dict it yields holds `planes` afterwards.
    The python tracer is off and the trace directory is removed."""
    import jax.profiler

    trace_dir = tempfile.mkdtemp(prefix="trino_tpu_profile_")
    out: dict = {}
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        try:
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        except RuntimeError as e:
            raise ProfileBusy(
                "another profile is running in this process (a traced "
                "benchmark slice?): %s" % e) from e
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        out["planes"] = load(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


@contextlib.contextmanager
def capture_if_free(wanted: bool = True):
    """`capture()` for a query that must not fail of its observability:
    an empty dict where no profile is `wanted` or another one is running
    (the caller falls back to what it had without one)."""
    with contextlib.ExitStack() as stack:
        out: dict = {}
        if wanted:
            try:
                out = stack.enter_context(capture())
            except ProfileBusy:
                pass
        yield out


def self_times(events: Iterable[tuple]) -> List[tuple]:
    """`(name, self_ns)` of every event of one line, children's time taken
    off their parent's."""
    out, stack = [], []   # stack: [name, end, self_ns]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            out.append((name, max(own, 0)))

    for name, start, end in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    close(float("inf"))
    return out


def last_module(planes: dict) -> dict:
    """The planes with each chip's `XLA Ops` cut to its last `XLA Modules`
    event: the program a query launched last (a query that also generated
    its scans ran the generators' programs before it)."""
    out = {}
    for plane, lines in planes.items():
        modules = lines.get(MODULES_LINE)
        if modules:
            _, lo, hi = max(modules, key=lambda ev: ev[1])
            lines = dict(lines)
            lines[OPS_LINE] = [ev for ev in lines.get(OPS_LINE) or ()
                               if lo <= ev[1] <= hi]
        out[plane] = lines
    return out


def reduce(planes: dict, ops: Optional[dict]) -> Optional[dict]:
    """`{chip plane: {...}}` or None where no device plane ran an
    operation (a CPU).  Per chip: `busyMs` (the self times' sum, which
    is the union of the events), `byOperator`, `byStep` (operator/step),
    `byKind` (self ms each), `rows` (`[scope, kind, slots, ms, events,
    inherited]`, costliest first; slots is the leading dimension of the
    op's result), `attributedPct`: the share of self time whose
    instruction `ops` names by the program's own metadata (rules 1 and 2
    of obs/program_census), and `inheritedPct`: the share it places by
    data flow only (rule 3), which is in `byOperator` all the same."""
    ops = ops or {}
    chips = {}
    for plane, lines in sorted(planes.items()):
        events = lines.get(OPS_LINE) or ()
        if not plane.startswith(DEVICE_PLANE) or not events:
            continue
        by_op: Dict[str, float] = {}
        by_step: Dict[str, float] = {}
        by_kind: Dict[str, float] = {}
        rows: Dict[tuple, list] = {}
        total = named = guessed = 0
        for name, own in self_times(events):
            m = _INSTRUCTION.match(name)
            scope, kind, shape, *rule = ops.get(
                m.group(1) if m else name) or ("", "unknown", "")
            inherited = rule == [3]
            ms = own / 1e6
            total += own
            if scope:
                named += 0 if inherited else own
                guessed += own if inherited else 0
                op = scope.split("/", 1)[0]
                by_op[op] = by_op.get(op, 0.0) + ms
                by_step[scope] = by_step.get(scope, 0.0) + ms
            by_kind[kind] = by_kind.get(kind, 0.0) + ms
            slots = _SLOTS.search(shape)
            row = rows.setdefault(
                (scope, kind, int(slots.group(1)) if slots else 0,
                 inherited), [0.0, 0])
            row[0] += ms
            row[1] += 1
        chips[plane] = {
            "busyMs": total / 1e6,
            "attributedPct": 100.0 * named / total if total else 0.0,
            "inheritedPct": 100.0 * guessed / total if total else 0.0,
            "byOperator": by_op, "byStep": by_step, "byKind": by_kind,
            "rows": sorted(
                ([s, k, n, ms, c, g]
                 for (s, k, n, g), (ms, c) in rows.items()),
                key=lambda r: -r[3]),
        }
    return chips or None


def slowest_by_operator(chips: dict) -> Dict[str, float]:
    """Per operator the largest self ms over the chips: a lockstep
    program's wall is its slowest chip's."""
    out: Dict[str, float] = {}
    for chip in chips.values():
        for op, ms in chip["byOperator"].items():
            out[op] = max(out.get(op, 0.0), ms)
    return out


def apply_device_time(frames: List[dict], by_operator_ms: dict) -> None:
    """An OperatorStats frame's `deviceWallS` becomes its operator's
    measured self time where the compiled program's profile has it (a
    frame's `operatorId` is the plan's pre-order ordinal, as a scope's)."""
    for f in frames:
        ms = by_operator_ms.get(
            "%s#%s" % (f.get("operatorType"), f.get("operatorId")))
        if ms is not None:
            f["deviceWallS"] = ms / 1e3


def _counters(rec: dict) -> str:
    return ", ".join("%s %d" % (k, rec[k]) for k in COUNTERS if rec.get(k))


def format_profile(profile: dict, top: int = 16) -> str:
    """The EXPLAIN ANALYZE section: per chip the costliest rows, then the
    census totals and the attributed share."""
    census = profile.get("census") or {}
    out = []
    chips = profile.get("device") or {}
    if chips:
        out.append("Device time by operator (compiled program):")
    for plane, chip in chips.items():
        busy = chip["busyMs"]
        out.append(
            "  %s: busy %.3fms, attributed %.1f%% by the program's own "
            "names, %.1f%% more (~) by its operands' only" % (
                plane, busy, chip["attributedPct"],
                chip.get("inheritedPct", 0.0)))
        out.append("    %-44s %-11s %10s %10s %6s" % (
            "operator/step", "kind", "slots", "ms", "share"))
        for scope, kind, slots, ms, _n, inherited in chip["rows"][:top]:
            out.append("    %-44s %-11s %10d %10.3f %5.1f%%" % (
                ("~" * inherited + scope) or "(unattributed)", kind, slots,
                ms, 100.0 * ms / busy if busy else 0.0))
        rest = sum(r[3] for r in chip["rows"][top:])
        if rest:
            out.append("    %-44s %-11s %10s %10.3f %5.1f%%" % (
                "(%d more)" % (len(chip["rows"]) - top), "", "", rest,
                100.0 * rest / busy if busy else 0.0))
    if census:
        out.append("Compiled program census%s:" % (
            " (fragment %s)" % census["fragment"]
            if census.get("fragment") else ""))
        out.append("  " + ", ".join(
            "%s %d" % (k, census.get(k, 0)) for k in MEMORY_KEYS))
        out.append("  " + _counters(census))
        out.append("  scoped instructions %d of %d" % (
            census.get("scopedInstructions", 0),
            census.get("instructions", 0)))
        for op, rec in sorted(
                (census.get("byOperator") or {}).items(),
                key=lambda kv: int(kv[0].rsplit("#", 1)[1])):
            out.append("  %s: %s" % (op, _counters(rec)))
    return "\n".join(out)
