"""HBM bandwidth ledger: per-kernel bytes-touched over timed device wall.

Scan-aggregate queries are HBM-bandwidth bound, and closing the gap to
the chip's peak needs a per-operator accounting of where the bytes go.  Each supervised dispatch that runs
under the ``bandwidth_ledger`` session property is bracketed with
``block_until_ready`` in the executor, and the ledger folds

    input bytes   (unpadded host scan/exchange arrays fed to the program)
  + output bytes  (padded device output lanes + selection mask)
  + intermediate  (wide-decimal accumulator estimate from
                   ``estimate_program_bytes``)

over the measured device wall into effective GB/s and %-of-roofline per
kernel digest.  Entries surface in EXPLAIN ANALYZE, the query profile
endpoint, ``system.runtime.kernel_bandwidth``, and the
``trino_tpu_kernel_bandwidth_*`` histograms.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

from ..utils.metrics import BYTES_BUCKETS, REGISTRY

# Published per-chip peaks, keyed by jax's `device_kind`.  A roofline is
# the attached chip's: a kind that is not listed is an error, never a
# default borrowed from another part.
DEVICE_PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "hbm_gbps": 819.0,
        "hbm_bytes": 16 * 10**9,
        "source": "Google Cloud documentation, \"TPU v5e\": 16 GB of HBM "
                  "at 819 GB/s per chip",
    },
}


def device_peaks(device_kind: Optional[str] = None) -> Dict:
    """Published peaks of `device_kind` (default: the first jax device)."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it "
            "to trino_tpu.obs.bandwidth.DEVICE_PEAKS with its source"
        ) from None


def roofline_bytes_per_s() -> Optional[float]:
    """HBM peak of the attached chip in bytes/s; None on the CPU backend,
    which has no HBM (a CPU run never reports a share of a chip's peak)."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    return device_peaks(dev.device_kind)["hbm_gbps"] * 1e9


def _roofline_pct(gbps: float, peak: Optional[float]) -> Optional[float]:
    return None if not peak else 100.0 * gbps * 1e9 / peak


class BandwidthLedger:
    """Accumulates per-kernel byte/wall observations for one executor
    (one query — or one task in distributed mode)."""

    def __init__(self, roofline_gbps: Optional[float] = None):
        self.roofline_bytes_per_s = (
            float(roofline_gbps) * 1e9
            if roofline_gbps else roofline_bytes_per_s()
        )
        # remote-exchange input held by a FragmentExecutor: counted once
        # per task (the merged arrays also feed per-dispatch inputBytes)
        self.exchange_bytes = 0
        self._lock = threading.Lock()
        self._entries: Dict[str, Dict] = {}

    def record(
        self,
        digest: str,
        mode: str,
        input_bytes: int,
        output_bytes: int,
        intermediate_bytes: int,
        wall_s: float,
        task_id: str = "",
    ) -> Dict:
        total = (
            int(input_bytes) + int(output_bytes) + int(intermediate_bytes)
        )
        with self._lock:
            e = self._entries.get(digest)
            if e is None:
                e = self._entries[digest] = {
                    "kernel": digest,
                    "mode": mode,
                    "taskId": task_id,
                    "executions": 0,
                    "inputBytes": 0,
                    "outputBytes": 0,
                    "intermediateBytes": 0,
                    "totalBytes": 0,
                    "deviceWallS": 0.0,
                }
            e["executions"] += 1
            e["inputBytes"] += int(input_bytes)
            e["outputBytes"] += int(output_bytes)
            e["intermediateBytes"] += int(intermediate_bytes)
            e["totalBytes"] += total
            e["deviceWallS"] += float(wall_s)
        REGISTRY.histogram(
            "trino_tpu_kernel_bandwidth_bytes",
            "Bytes touched (input+output+intermediate) per supervised "
            "dispatch under the bandwidth ledger",
            buckets=BYTES_BUCKETS,
        ).observe(total)
        REGISTRY.histogram(
            "trino_tpu_kernel_bandwidth_seconds",
            "Timed device wall (block_until_ready bracketing) per "
            "supervised dispatch under the bandwidth ledger",
        ).observe(wall_s)
        return e

    def _annotate(self, e: Dict) -> Dict:
        wall = e["deviceWallS"]
        gbps = (e["totalBytes"] / wall / 1e9) if wall > 0 else 0.0
        out = dict(e)
        out["gbps"] = gbps
        out["rooflinePct"] = _roofline_pct(gbps, self.roofline_bytes_per_s)
        return out

    def entries(self) -> List[Dict]:
        """Per-kernel rows, heaviest byte movers first."""
        with self._lock:
            entries = [dict(e) for e in self._entries.values()]
        return sorted(
            (self._annotate(e) for e in entries),
            key=lambda e: e["totalBytes"],
            reverse=True,
        )

    def top(self, n: int) -> List[Dict]:
        return self.entries()[:n]

    def summary(self) -> Dict:
        with self._lock:
            total = sum(e["totalBytes"] for e in self._entries.values())
            wall = sum(e["deviceWallS"] for e in self._entries.values())
        gbps = (total / wall / 1e9) if wall > 0 else 0.0
        return {
            "totalBytes": total,
            "deviceWallS": wall,
            "exchangeBytes": self.exchange_bytes,
            "effectiveGbps": gbps,
            "rooflinePct": _roofline_pct(gbps, self.roofline_bytes_per_s),
            "rooflineGbps": (
                self.roofline_bytes_per_s / 1e9
                if self.roofline_bytes_per_s else None
            ),
        }
