"""Percentiles, the sample-count rule, and histogram arithmetic."""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import numpy as np

#: A percentile is reported only with this many samples beyond it
#: (choosing-metrics guide, section 1).
MIN_SAMPLES_BEYOND = 10


def samples_beyond(n: int, p: float) -> float:
    return n * (100.0 - p) / 100.0


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """The p-th percentile (linear interpolation), or None when fewer
    than ``MIN_SAMPLES_BEYOND`` samples lie beyond it — a 99th percentile
    of 200 values is a maximum of two, not a tail."""
    n = len(values)
    if n == 0 or samples_beyond(n, p) < MIN_SAMPLES_BEYOND:
        return None
    return float(np.percentile(np.asarray(values, np.float64), p))


# -- the program's log-binned LatencyHistogram, read by difference ----------

def hist_diff(after: Dict, before: Optional[Dict]) -> Dict:
    """``snapshot()`` at the end of the window minus the one at its
    start: what was observed inside the window only."""
    if before is None:
        return {"counts": list(after["counts"]), "n": after["n"],
                "total_s": after["total_s"]}
    return {
        "counts": [a - b for a, b in zip(after["counts"], before["counts"])],
        "n": after["n"] - before["n"],
        "total_s": after["total_s"] - before["total_s"],
    }


def hist_mean_s(diff: Dict) -> Optional[float]:
    return diff["total_s"] / diff["n"] if diff["n"] > 0 else None


def hist_percentile_s(diff: Dict, p: float, *, lo_exp: float = -6.0,
                      bins_per_decade: int = 10) -> Optional[float]:
    """Percentile of a log-binned histogram (1 us upward, ten bins a
    decade: the layout of ``fmda_tpu.obs.registry.LatencyHistogram``),
    interpolated log-linearly inside the bin that holds it.  Accurate to
    a bin width (26 %), which is why nothing read this way has a bound.
    """
    n = diff["n"]
    if n <= 0:
        return None
    target = p / 100.0 * n
    seen = 0
    for i, c in enumerate(diff["counts"]):
        if c <= 0:
            continue
        if seen + c >= target:
            frac = (target - seen) / c
            lo = lo_exp + i / bins_per_decade
            return 10.0 ** (lo + frac / bins_per_decade)
        seen += c
    return None


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median — the driver's
    measure of run-to-run spread."""
    a = np.asarray(values, np.float64)
    q1, med, q3 = np.percentile(a, [25, 50, 75])
    return float((q3 - q1) / med) if med else math.inf
