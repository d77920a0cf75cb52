"""The knee sweep's rule for a sustained rate, on rows as the sweep makes
them (numpy scalars included: the rows are printed as JSON)."""

import json
import os
import sys

import numpy as np

from benchmark.harness import catalog

sys.path.insert(0, os.path.join(catalog.BENCH_DIR, "tools"))
import knee_sweep  # noqa: E402


def row(**kw):
    base = {"shed": 0, "unanswered": 0, "recompiles": 0, "depth_mid": 1.0,
            "depth_end": 1.0, "p50_ms": np.float64(9.0),
            "burst_drain_ms": [345.2, 300.6, 298.0]}
    base.update(kw)
    return base


def test_rule():
    assert knee_sweep.sustained(row(), 2.0) is True
    json.dumps({"sustained": knee_sweep.sustained(row(), 2.0)})
    # the median tick takes as long as a drain: never out of the drains
    assert knee_sweep.sustained(row(p50_ms=np.float64(505.0)), 2.0) is False
    assert knee_sweep.sustained(row(shed=3), 2.0) is False
    assert knee_sweep.sustained(row(unanswered=1), 2.0) is False
    # a burst not drained before the next is due
    assert knee_sweep.sustained(
        row(burst_drain_ms=[300.0, 2100.0]), 2.0) is False
    assert knee_sweep.sustained(
        row(burst_drain_ms=[300.0, float("inf")]), 2.0) is False
    # no bursts in the traffic: the queue must not grow
    assert knee_sweep.sustained(row(burst_drain_ms=[]), 0.0) is True
    assert knee_sweep.sustained(
        row(burst_drain_ms=[], depth_end=900.0), 0.0) is False
