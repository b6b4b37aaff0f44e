"""Kernels (`ops/`): of the device time in `trace["device_ops"]` (the ten
costliest operations of the traced slice, by the harness's own trace), the
share whose instruction `programCensus.ops` names an operator's scope for by
the program's own metadata: the instruction's, or that of an instruction
inside the fusion (rules 1 and 2 of `trino_tpu/obs/program_census.py`).  A
scope that the census only inherits from an operand (rule 3, a guess by data
flow) does not count, so the share can fail.  The census's self-check against
the chip, no lever (as `execute_unaccounted_pct` is the tracer's).  An
executable compiled before the program named its scopes (`scopedInstructions`
0: loaded from a persistent cache that an older tree wrote) says nothing
about this tree and reads nothing; so does a program without the census, or a
streamed query (several programs whose instruction names collide)."""
import re

NAME = re.compile(r"%?([\w.\-]+)")


def read(ctx):
    trace = ctx["trace"]
    census = next((p["programCensus"] for p in reversed(ctx["setup_profiles"])
                   if (p.get("programCensus") or {}).get("ops")), None)
    if (not trace or not trace["device_ops"] or census is None
            or not census.get("scopedInstructions")):
        return None
    total = named = 0.0
    for name, seconds in trace["device_ops"]:
        total += seconds
        m = NAME.match(name)
        rec = census["ops"].get(m.group(1)) if m else None
        if rec and rec[0] and list(rec[3:]) != [3]:
            named += seconds
    return 100.0 * named / total if total else None
