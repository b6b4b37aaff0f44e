"""Test harness config: force CPU backend with 8 virtual devices.

Mirrors the reference's DistributedQueryRunner strategy (SURVEY §4):
"N servers in one process" — here, an 8-device virtual CPU mesh stands in
for an 8-chip TPU slice so sharding/collective paths compile and execute
without TPU hardware.
"""
import os
import sys


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# Share compiled XLA executables across every process the suite spawns:
# the distributed/lifecycle/recovery/multihost tests each stand up fresh
# worker processes that would otherwise recompile identical fragment
# programs from scratch.  The cache is keyed by HLO + compile options +
# jax version, so reuse is always sound; min-compile-time 0 catches the
# many sub-second fragment programs that dominate on the CPU tier-1 path.
# Env (not jax.config) so subprocess workers inherit it; a directory the
# caller already chose stays, otherwise it is the checkout's own
# .jax_cache (the same default trino_tpu.cache.compile_cache uses).
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(_REPO, ".jax_cache")
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import trino_tpu

def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (-m 'not slow'); run "
        "explicitly or via the full suite",
    )


trino_tpu.force_cpu(8)
