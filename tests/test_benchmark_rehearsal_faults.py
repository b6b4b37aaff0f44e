"""The benchmark's own whole-run tests, collected by tier-1: a sound run of
each one-chip cell is `correct`, and a run with the timed path broken
underneath is not, by the number that is there to catch the fault.  They are
re-exported, not copied (`benchmark/tests/test_faults.py` finds
`faulty_run.py` beside itself); the rest of `benchmark/tests` runs by hand."""
from benchmark.tests.test_faults import (  # noqa: F401
    test_a_planted_fault_is_not_correct,
    test_a_sound_run_is_correct,
)
