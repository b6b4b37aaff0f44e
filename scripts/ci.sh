#!/usr/bin/env bash
# One-command CI gate: source linters, the tier-1 test suite, and the
# lakehouse and multi-host smokes, in that order.  Exit non-zero when
# any stage fails.  Speed is not judged here: that is `benchmark/run.py`
# on the chip (BENCHMARK.json, PERF.md).
#
# Usage: scripts/ci.sh [pytest args...]
set -o pipefail

root="$(cd "$(dirname "$0")/.." && pwd)"
cd "$root"
rc=0

echo "== lint =="
python scripts/lint.py || rc=1

echo "== tier-1 tests =="
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly "$@" || rc=1

echo "== lake smoke =="
# ~15s concurrent-writer lakehouse smoke: 2 writer sessions racing the
# metadata-pointer CAS x 1 polling reader, seeded objstore_error /
# objstore_latency faults active — zero lost updates, complete snapshot
# history, stable pinned time-travel reads (scripts/lake_smoke.py)
timeout -k 10 180 env JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 \
    python scripts/lake_smoke.py || rc=1

echo "== multihost smoke =="
# ~30s multi-host cluster smoke: coordinator + 2 real host processes on
# localhost (2 virtual devices each, cross-host mesh mode on), one
# grouped aggregation whose repartition crosses the process boundary —
# byte-identical to single-host, mesh-mode compiles on every host, the
# cross-host exchange metric strictly positive, zero failed queries
# (scripts/multihost_smoke.py)
timeout -k 10 180 env JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 \
    python scripts/multihost_smoke.py || rc=1

echo "== ci: $([ "$rc" -eq 0 ] && echo ok || echo FAIL) =="
exit "$rc"
