"""What the run ran on: the device check, the table of peaks, peak memory,
and the watch on compilation."""

from __future__ import annotations

from typing import Dict

#: Published peaks of one chip, by ``device_kind``: (bf16 FLOP/s, HBM
#: bytes/s).  Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
#: bf16, 819 GB/s).  Copied from ``fmda_tpu.obs.device.DEVICE_PEAKS``.  A
#: kind that is not here is an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
}


def peaks_for(kind: str):
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peak for device kind {kind!r}; add it to "
            "benchmark/harness/device.py with its source")
    return DEVICE_PEAKS[kind]


def describe(devices) -> Dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it
    (0 where it reports nothing: the CPU)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileWatch:
    """Counts what jax compiled or fetched from its persistent cache,
    over the whole run and inside the measured window."""

    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.hits = self.misses = self.backend_compiles = 0
        self.backend_compile_s = 0.0
        self.in_window = False
        self.window_events = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1
        else:
            return
        if self.in_window:
            self.window_events += 1

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == self.BACKEND:
            self.backend_compiles += 1
            self.backend_compile_s += duration
            if self.in_window:
                self.window_events += 1

    def summary(self) -> Dict:
        return {"cache_hits": self.hits, "cache_misses": self.misses,
                "backend_compiles": self.backend_compiles,
                "backend_compile_s": self.backend_compile_s,
                "compile_events_in_window": self.window_events}
