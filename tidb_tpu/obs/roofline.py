"""copgauge roofline attribution: achieved vs peak bytes/s and FLOPs/s
per program digest.

Reference analog: Flare's roofline framing (PAPERS.md) — a measured
"0.05x numpy" is unactionable until it is decomposed into WHERE the
time went: a digest running at 80% of peak memory bandwidth is
memory-bound (tiling/width levers), one at 60% of peak FLOPs is
compute-bound (algorithmic levers), and one whose whole launch fits in
dispatch overhead is launch-bound (fusion/batching levers).  The
ROADMAP's queued real-TPU window reports the hndv SCATTER-vs-SEGMENT
verdict through exactly this surface.

Per digest, the store combines measured launch wall time (the PR 5/10
marginal-bytes attribution) with the static ``LaunchCost`` flops and
transfer bytes into achieved GB/s and GFLOP/s against a per-backend
peak table: DECLARED constants per TPU device kind (they define the
denominator of a percentage, not a claim about any chip's true ceiling)
and a calibrated-at-boot microbench number for CPU meshes, so tier-1
exercises the whole classification path.

Everything here is measured-nanoseconds + frozen LaunchCost arithmetic:
no jax import, no device touch (the peak microbench runs numpy on the
host exactly once).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Optional

from ..analysis.calibrate import BoundedLRU, CALIB_ALPHA

# bounded per-digest attribution entries (the calibration store's
# eviction policy)
ROOFLINE_STORE_CAP = 128
# a digest whose EWMA launch time sits under this is launch-bound: the
# program is dominated by dispatch/launch overhead, not by data or math
LAUNCH_BOUND_MS = 0.5

# declared per-device-kind peaks: (part, substrings of jax's
# device_kind, (bytes/s of HBM bandwidth, flops/s)), matched in order.
# jax reports a v5e chip as "TPU v5 lite" and a v5p chip as "TPU v5p"
# or plain "TPU v5"; the bare generation names come last so a lite
# part never takes the full part's row.  These are roofline
# DENOMINATORS — round public numbers (Google Cloud TPU docs; bf16
# flops).  A TPU that matches no row is an error, not a default.
TPU_PEAKS = (
    ("v5e", ("v5 lite", "v5e"), (819e9, 197e12)),
    ("v6e", ("v6 lite", "v6e"), (1640e9, 918e12)),
    ("v5p", ("v5",), (2765e9, 459e12)),
    ("v4", ("v4",), (1228e9, 275e12)),
    ("v3", ("v3",), (900e9, 123e12)),
    ("v2", ("v2",), (700e9, 46e12)),
)

# CPU microbench shape: one stacked copy + one small matmul, best of
# REPS — a stable-enough boot-time denominator, not a benchmark
_CPU_BENCH_MB = 16
_CPU_BENCH_N = 192
_CPU_BENCH_REPS = 3

_cpu_peaks_cache: Optional[tuple] = None
_cpu_mu = threading.Lock()


def _cpu_microbench() -> tuple:
    """Calibrated-at-boot CPU peaks: measured host copy bandwidth and
    matmul flops (best-of-reps).  Cached for the process lifetime."""
    import numpy as np
    a = np.ones((_CPU_BENCH_MB << 20) // 8, dtype=np.float64)
    best_bw = 0.0
    for _ in range(_CPU_BENCH_REPS):
        t0 = time.perf_counter()
        b = a.copy()
        dt = time.perf_counter() - t0
        best_bw = max(best_bw, 2.0 * a.nbytes / max(dt, 1e-9))
    del b
    m = np.ones((_CPU_BENCH_N, _CPU_BENCH_N), dtype=np.float64)
    best_fl = 0.0
    flops = 2.0 * _CPU_BENCH_N ** 3
    for _ in range(_CPU_BENCH_REPS):
        t0 = time.perf_counter()
        m @ m
        dt = time.perf_counter() - t0
        best_fl = max(best_fl, flops / max(dt, 1e-9))
    return (best_bw, best_fl)


def backend_peaks(device_kind: str) -> tuple:
    """(bytes_per_s, flops_per_s, source) for a device kind string."""
    kind = (device_kind or "").lower()
    if "tpu" in kind:
        for part, subs, peaks in TPU_PEAKS:
            if any(sub in kind for sub in subs):
                return (*peaks, f"declared:{part}")
        raise ValueError(
            f"no roofline peaks declared for TPU device_kind "
            f"{device_kind!r}: add its row to obs/roofline.TPU_PEAKS")
    global _cpu_peaks_cache
    with _cpu_mu:
        if _cpu_peaks_cache is None:
            _cpu_peaks_cache = _cpu_microbench()
        bw, fl = _cpu_peaks_cache
    return (bw, fl, "microbench:cpu")


# id(mesh)-free memo: device kind -> peaks (kinds are few)
_mesh_peaks_cache: dict = {}


def peaks_for_mesh(mesh) -> tuple:
    """Per-mesh peak lookup (device kind of chip 0); aggregate peaks
    scale by mesh size — the attribution compares whole-mesh bytes and
    flops against whole-mesh capability."""
    try:
        dev = mesh.devices.reshape(-1)[0]
        kind = str(getattr(dev, "device_kind", "") or dev.platform)
        n_dev = int(mesh.devices.size)
    except (AttributeError, IndexError, TypeError):
        kind, n_dev = "", 1
    ent = _mesh_peaks_cache.get((kind, n_dev))
    if ent is None:
        bw, fl, src = backend_peaks(kind)
        ent = _mesh_peaks_cache[(kind, n_dev)] = (
            bw * n_dev, fl * n_dev, src)
        if len(_mesh_peaks_cache) > 16:
            _mesh_peaks_cache.clear()
    return ent


@dataclass
class RoofStat:
    """One digest's measured utilization state (EWMA over launches)."""
    ewma_ms: float = 0.0
    transfer_bytes: int = 0      # static LaunchCost bytes per launch
    flops: int = 0               # static LaunchCost flops per launch
    measured_hbm: int = 0        # last measured launch peak (copgauge)
    samples: int = 0

    def attribution(self, peaks: tuple) -> dict:
        """Achieved rates vs the peak table + the roofline verdict."""
        t_s = max(self.ewma_ms, 1e-6) / 1e3
        bw, fl = peaks[0], peaks[1]
        bytes_pct = 100.0 * (self.transfer_bytes / t_s) / max(bw, 1.0)
        flops_pct = 100.0 * (self.flops / t_s) / max(fl, 1.0)
        if self.ewma_ms < LAUNCH_BOUND_MS:
            bound = "launch-bound"
        elif bytes_pct >= flops_pct:
            bound = "memory-bound"
        else:
            bound = "compute-bound"
        return {
            "ewma_ms": round(self.ewma_ms, 3),
            "achieved_gbps": round(self.transfer_bytes / t_s / 1e9, 3),
            "achieved_gflops": round(self.flops / t_s / 1e9, 3),
            "bytes_pct": round(min(bytes_pct, 100.0), 3),
            "flops_pct": round(min(flops_pct, 100.0), 3),
            # distance from the roofline: the optimization headroom
            "gap_pct": round(
                100.0 - min(max(bytes_pct, flops_pct), 100.0), 3),
            "bound": bound,
            "measured_hbm": self.measured_hbm,
            "samples": self.samples,
        }


class RooflineStore:
    """Bounded per-digest utilization store; one per process like the
    calibration correction store it mirrors."""

    def __init__(self, cap: int = ROOFLINE_STORE_CAP):
        self._mu = threading.Lock()
        self._entries = BoundedLRU(cap)
        self._peaks: tuple = (0.0, 0.0, "unknown")
        self.observed = 0
        from ..utils.metrics import global_registry
        reg = global_registry()
        self._m_bytes = reg.gauge(
            "tidb_tpu_roofline_bytes_pct",
            "achieved memory bandwidth as % of the backend peak, per "
            "program digest", labels=("digest",))
        self._m_flops = reg.gauge(
            "tidb_tpu_roofline_flops_pct",
            "achieved FLOP rate as % of the backend peak, per program "
            "digest", labels=("digest",))

    def observe(self, digest: str, cost, measured_ns: int,
                peaks: tuple, measured_hbm: int = 0) -> None:
        """Feed one measured launch: EWMA the digest's wall time and
        refresh its static work terms; gauges follow."""
        if cost is None or measured_ns <= 0:
            return
        meas_ms = measured_ns / 1e6
        short = digest[:12]
        with self._mu:
            self._peaks = peaks
            ent = self._entries.get(digest)
            if ent is None:
                ent = RoofStat()
                self._entries.put(digest, ent)
            ent.ewma_ms = meas_ms if ent.samples == 0 else \
                (1.0 - CALIB_ALPHA) * ent.ewma_ms + CALIB_ALPHA * meas_ms
            ent.transfer_bytes = int(cost.transfer_bytes)
            ent.flops = int(cost.flops)
            if measured_hbm > 0:
                ent.measured_hbm = int(measured_hbm)
            ent.samples += 1
            self.observed += 1
            att = ent.attribution(peaks)
        self._m_bytes.set(att["bytes_pct"], digest=short)
        self._m_flops.set(att["flops_pct"], digest=short)

    def get(self, digest: str) -> Optional[dict]:
        with self._mu:
            ent = self._entries.get(digest)
            if ent is None:
                return None
            return ent.attribution(self._peaks)

    def top(self, n: int = 8) -> dict:
        """Top digests by roofline gap (furthest from peak) and by
        measured residency — the /hbm drill-down tables."""
        with self._mu:
            peaks = self._peaks
            rows = [(d, ent.attribution(peaks))
                    for d, ent in self._entries.items()]
        by_gap = sorted(rows, key=lambda kv: -kv[1]["gap_pct"])[:n]
        by_res = sorted(rows, key=lambda kv: -kv[1]["measured_hbm"])[:n]
        return {"by_gap": {d[:16]: att for d, att in by_gap},
                "by_residency": {d[:16]: att for d, att in by_res}}

    def stats(self) -> dict:
        counts: dict = {}
        with self._mu:
            peaks = self._peaks
            n = len(self._entries)
            for _d, ent in self._entries.items():
                b = ent.attribution(peaks)["bound"]
                counts[b] = counts.get(b, 0) + 1
        return {
            "entries": n,
            "observed": self.observed,
            "peak_bytes_per_s": peaks[0],
            "peak_flops_per_s": peaks[1],
            "peak_source": peaks[2],
            "bounds": counts,
        }

    def reset(self) -> None:
        with self._mu:
            self._entries.clear()
            self.observed = 0


_STORE: Optional[RooflineStore] = None
_STORE_MU = threading.Lock()


def roofline_store() -> RooflineStore:
    global _STORE
    with _STORE_MU:
        if _STORE is None:
            _STORE = RooflineStore()
        return _STORE


def roofline_status(n: int = 8) -> dict:
    """The roofline half of the ``/hbm`` status route."""
    store = roofline_store()
    return {**store.stats(), **store.top(n)}


__all__ = ["RoofStat", "RooflineStore", "roofline_store",
           "roofline_status", "backend_peaks", "peaks_for_mesh",
           "LAUNCH_BOUND_MS", "ROOFLINE_STORE_CAP", "TPU_PEAKS"]
