"""The arithmetic behind the per-layer readers, shared by the files under
``layer_metrics/`` that differ only in the end-to-end metric they move.
Each function takes the run's record and returns a number or None."""

from __future__ import annotations

from typing import Callable, Dict, Optional

from benchmark.harness import flops
from benchmark.harness.device import peaks_for
from benchmark.harness.stats import (
    hist_mean_s, hist_percentile_s, percentile)

Reader = Callable[[Dict], Optional[float]]


def hist_mean_ms(stage: str) -> Reader:
    def read(rec):
        h = rec.get("hist", {}).get(stage)
        mean = hist_mean_s(h) if h else None
        return None if mean is None else mean * 1e3
    return read


def hist_p50_ms(stage: str) -> Reader:
    def read(rec):
        h = rec.get("hist", {}).get(stage)
        p = hist_percentile_s(h, 50.0) if h else None
        return None if p is None else p * 1e3
    return read


def flush_fill(rec):
    c = rec.get("counters")
    if not c or not c.get("flushes"):
        return None
    return c.get("ticks_served", 0) / c["flushes"]


def padded_lane_share(rec):
    c = rec.get("counters")
    if not c:
        return None
    total = c.get("padded_lanes", 0) + c.get("ticks_served", 0)
    return 100.0 * c.get("padded_lanes", 0) / total if total else None


def gen_lateness_p99_ms(rec):
    late = rec.get("gen_lateness_ms")
    return None if late is None else percentile(late, 99.0)


def pool_step_dev_us(rec):
    """Device-plane busy time in the traced slice over the ``pool_flush``
    annotations that started in it."""
    t = rec.get("trace")
    if not t or not t["steps"].get("pool_flush"):
        return None
    return t["busy_s"] / t["steps"]["pool_flush"] * 1e6


def pool_step_bounds_us(rec):
    """(compute-bound, bandwidth-bound) least time of the mean flush."""
    mc, rc = rec["model_cfg"], rec["runtime_cfg"]
    c = rec["counters"]
    lanes = (c.get("padded_lanes", 0) + c.get("ticks_served", 0)) \
        / c["flushes"]
    peak_flops, peak_bytes = peaks_for(rec["device"]["kind"])
    f = flops.pool_step_flops(mc.cell, lanes, mc.n_features,
                              mc.hidden_size, mc.output_size)
    b = flops.pool_step_bytes(mc.cell, lanes, mc.n_features, mc.hidden_size,
                              mc.output_size, rc.window)
    return f / peak_flops * 1e6, b / peak_bytes * 1e6


def _rehearsal(rec) -> bool:
    """Not on a TPU: there is no peak to hold a number against (a TPU of a
    kind the table lacks is refused at start-up instead)."""
    return rec["device"]["platform"] != "tpu"


def pool_step_roofline(rec):
    if _rehearsal(rec):
        return None
    dev_us = pool_step_dev_us(rec)
    if dev_us is None or not rec.get("counters", {}).get("flushes"):
        return None
    return 100.0 * max(pool_step_bounds_us(rec)) / dev_us


def train_step_dev_ms(rec):
    """Device-plane busy time in the traced slice over the trainer's step
    annotations (``train`` and ``eval``) that started in it."""
    t = rec.get("trace")
    if not t:
        return None
    steps = t["steps"].get("train", 0) + t["steps"].get("eval", 0)
    return t["busy_s"] / steps * 1e3 if steps else None


def train_mfu(rec):
    mc, tc = rec.get("model_cfg"), rec.get("train_cfg")
    rate = rec["end_to_end"].get("train_samples_per_s")
    if tc is None or not rate or _rehearsal(rec):
        return None
    per_window = flops.train_flops_per_window(
        tc.window, mc.n_features, mc.hidden_size, mc.output_size,
        bidirectional=mc.bidirectional, cell=mc.cell)
    peak_flops, _ = peaks_for(rec["device"]["kind"])
    return 100.0 * per_window * rate / (peak_flops * rec["device"]["count"])


def input_stall_share(rec):
    if "input_stall_s" not in rec:
        return None
    return 100.0 * rec["input_stall_s"] / rec["window_s"]


def device_idle_share(rec):
    t = rec.get("trace")
    return None if not t else 100.0 * t["idle_share"]


def peak_hbm_mb(rec):
    peak = rec["device"].get("memory_peak_bytes", 0)
    return peak / 1e6 if peak else None
