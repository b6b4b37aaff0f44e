"""TPC-H Q3 for the four-chip cell `tpch_sf10_mesh4_q3.q3`: everything is
`q3.py`'s (the text, the spec's ranges, the plain reference, the check), and
one refusal at import.

A program from before PR 35 cannot hold the cell: at the cell's shard sizes
(16,777,216 lineitem slots a chip, which the mesh never compacts) its SPMD
fragment stacks the wide sum's chunk lanes `(n, 4)` for one `segment_sum`, and
the chip's compiler refuses it ("Used 16.63G of 15.75G hbm") after the three
generator compiles of set-up, minutes into a run and past the time a run is
given.  So the cell refuses such a program at once, by an exit code of its own,
as `q18.py` refuses a host-scanning program and `run.py` a machine without the
chip.  The mark of a program that can hold the cell is the exchange counters
that arrived with the repair (`exec/local.OP_COUNTERS`); the one thing this
file reads of the program, and the reference reads nothing."""
from q3 import LIMIT, RANGES, TABLES, check, draw, reference, sql  # noqa: F401


def _refuse_a_program_that_cannot_hold_the_cell():
    from trino_tpu.exec import local

    if "broadcastExchanges" not in getattr(local, "OP_COUNTERS", ()):
        raise SystemExit(
            "q3_mesh4: this program is from before the mesh's sort group-by "
            "stopped stacking the wide sum's chunks (n, 4): its fragment "
            "does not fit a chip at the cell's 16,777,216-slot shards")


_refuse_a_program_that_cannot_hold_the_cell()
