"""Fragment program (`exec/local`, `exec/streaming`): the share of the
program's tracer span `execute` that lies outside every phase span.  Phases
are leaves on the query thread, so their sum can be set against `execute`;
the grouping spans (`tile_execute`, `tile_stage` and its children, `devgen`,
`xla_compile`) hold phases or run on another thread and are not summed.

A self-check of the tracer's coverage, not a lever on the query: a new phase
name lowers it and moves no latency, and a cold query's compile (in
`xla_compile`, outside every phase) raises it with nothing slower.  It says
whether the phase spans still tile `execute` (10 % is the line)."""

PHASES = ("stream_plan", "load_scans", "device_lanes", "launch", "device_get",
          "materialize_host", "tile_wait")


def read(ctx):
    spans = ctx["spans"]
    execute = spans.get("execute", [0, 0.0])[1]
    if not execute or "launch" not in spans:
        return None
    inside = sum(spans.get(s, [0, 0.0])[1] for s in PHASES)
    return 100.0 * (execute - inside) / execute
