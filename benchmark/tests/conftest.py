"""The benchmark's own tests, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are not part of the repo's tier-1 suite (`tests/`)."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.dirname(BENCH), BENCH, os.path.join(BENCH, "queries")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
