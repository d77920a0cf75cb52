"""The reduction from a trace to busy time, idle share, heaviest
operations and gaps by host span: against a hand-built trace, against a
brute-force reading of a random one, and against a small trace recorded
on the chip (``fixtures/``)."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import trace_reduce as tr

MS = 1e6  # ns
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def hand_built():
    # slice 0..100 ms; ops busy 10-20, 15-30 (overlap), 50-60, 95-110
    device = {"/device:TPU:0": [
        ("fusion.1", 10 * MS, 10 * MS), ("fusion.2", 15 * MS, 15 * MS),
        ("fusion.1", 50 * MS, 10 * MS), ("copy.3", 95 * MS, 15 * MS)]}
    host = [
        (tr.SLICE_SPAN, 0.0, 100 * MS),
        ("bench_submit", 0.0, 8 * MS),
        ("bench_pump", 8 * MS, 42 * MS),       # 8..50
        ("pool_flush", 9 * MS, 2 * MS),        # nested in the pump
        ("pool_flush", 45 * MS, 3 * MS),       # 45..48, nested too
        ("bench_pump", 60 * MS, 20 * MS),      # 60..80
        ("pool_flush", 120 * MS, 1 * MS),      # outside the slice
    ]
    return {"device": device, "host": host}


def test_busy_union_idle_share_and_steps():
    r = tr.reduce(hand_built())
    # union inside the slice: 10-30, 50-60, 95-100 = 35 ms
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["idle_share"] == pytest.approx(0.65)
    assert r["steps"]["pool_flush"] == 2
    assert r["n_gaps"] == 3
    assert r["longest_gap_s"] == pytest.approx(0.035)  # 60..95


def test_heaviest_operations_are_summed_by_name_and_clipped():
    ops = dict(map(tuple, tr.reduce(hand_built())["device_ops"]))
    assert ops["fusion.1"] == pytest.approx(0.020)
    assert ops["fusion.2"] == pytest.approx(0.015)
    assert ops["copy.3"] == pytest.approx(0.005)  # clipped at the slice


def test_gaps_go_to_the_innermost_host_span():
    idle = dict(map(tuple, tr.reduce(hand_built())["idle_by_span"]))
    # gap 0-10: submit 0-8, pump 8-9, flush 9-10
    # gap 30-50: pump 30-45 and 48-50, flush 45-48
    # gap 60-95: pump 60-80, nothing 80-95
    assert idle["bench_submit"] == pytest.approx(0.008)
    assert idle["pool_flush"] == pytest.approx(0.001 + 0.003)
    assert idle["bench_pump"] == pytest.approx(0.001 + 0.017 + 0.020)
    assert idle[tr.NO_SPAN] == pytest.approx(0.015)
    assert sum(idle.values()) == pytest.approx(0.065)


def test_without_a_slice_span_the_device_extent_is_the_window():
    t = hand_built()
    t["host"] = [e for e in t["host"] if e[0] != tr.SLICE_SPAN]
    r = tr.reduce(t)
    assert r["window_s"] == pytest.approx(0.100)  # 10..110
    assert r["busy_s"] == pytest.approx(0.045)


def test_no_device_event_reduces_to_nothing():
    assert tr.reduce({"device": {}, "host": hand_built()["host"]}) is None


def test_against_brute_force_on_a_random_trace():
    rng = np.random.default_rng(5)
    starts = np.sort(rng.uniform(0, 1000, 300))
    durs = rng.exponential(2.0, 300)
    device = {"/device:TPU:0": [
        (f"op{k % 7}", float(s) * 1e3, float(d) * 1e3)
        for k, (s, d) in enumerate(zip(starts, durs))]}
    host = [(tr.SLICE_SPAN, 100e3, 800e3)]
    t = 100.0
    while t < 900.0:
        d = float(rng.uniform(5, 40))
        host.append(("bench_pump", t * 1e3, d * 1e3))
        host.append(("pool_flush", (t + d / 4) * 1e3, d / 2 * 1e3))
        t += d + float(rng.uniform(0, 10))
    r = tr.reduce({"device": device, "host": host})
    # brute force on a 0.01-unit grid of the slice
    grid = np.arange(100.0, 900.0, 0.01) + 0.005
    busy = np.zeros(len(grid), bool)
    for s, d in zip(starts, durs):
        busy |= (grid >= s) & (grid < s + d)
    assert r["busy_s"] * 1e6 == pytest.approx(busy.sum() * 0.01, rel=2e-3)
    label = np.full(len(grid), 0)
    for name, s, d in host[1:]:
        inside = (grid >= s / 1e3) & (grid < (s + d) / 1e3)
        code = 2 if name == "pool_flush" else 1
        label[inside] = np.maximum(label[inside], code)
    idle = dict(map(tuple, r["idle_by_span"]))
    for code, name in ((0, tr.NO_SPAN), (1, "bench_pump"),
                       (2, "pool_flush")):
        want = ((label == code) & ~busy).sum() * 0.01 / 1e6
        assert idle.get(name, 0.0) == pytest.approx(want, rel=5e-3, abs=2e-8)


RECORDED = os.path.join(FIXTURES, "serve_slice.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in fixtures/")
def test_against_the_recorded_trace():
    """A few flushes recorded on the v5e; what the reduction must find in
    it was read off by hand (``jax.profiler.ProfileData``) and is kept
    beside it."""
    with open(os.path.join(FIXTURES, "serve_slice.expected.json")) as fh:
        want = json.load(fh)
    trace = tr.load(RECORDED)
    (events,) = trace["device"].values()
    assert len(events) == want["xla_ops_events"]
    r = tr.reduce(trace)
    # the union again, the slow way: paint a 1 ns grid of the slice
    lo, hi = tr.slice_bounds(trace)
    grid = np.zeros(int(hi - lo) + 1, bool)
    for _, s, d in events:
        a, b = int(max(s, lo) - lo), int(min(s + d, hi) - lo)
        if b > a:
            grid[a:b] = True
    assert r["busy_s"] * 1e9 == pytest.approx(grid.sum(), rel=1e-3)
    assert r["steps"]["pool_flush"] == want["pool_flush"]
    assert r["n_device_planes"] == want["n_device_planes"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert 0.0 < r["idle_share"] < 1.0
    assert r["device_ops"][0][0] == want["top_op"]
    total_idle = sum(t for _, t in r["idle_by_span"])
    assert total_idle <= (1 - r["busy_s"] / r["window_s"]) * r["window_s"] \
        * (1 + 1e-9)


def test_gaps_of_a_training_trace_go_to_the_programs_spans(tmp_path):
    """The five steps recorded on the v5e in PR 24 (three train, two
    eval; ``test_program_spans.py`` reads the same file by span): the
    device's idle time is named by the step loop's own spans, and only
    what ``fit`` does around its two passes is left under no host span."""
    import gzip

    path = tmp_path / "train_steps.xplane.pb"
    with gzip.open(os.path.join(FIXTURES, "train_steps.xplane.pb.gz")) as fh:
        path.write_bytes(fh.read())
    r = tr.reduce(tr.load(str(path)))
    assert r["steps"] == {"pool_flush": 0, "train": 3, "eval": 2}
    idle = dict(map(tuple, r["idle_by_span"]))
    assert {"train_fold", "eval_fold", "train", "eval",
            "train_pass_drain"} <= set(idle)
    assert set(idle) <= set(tr.HOST_SPANS) | {tr.NO_SPAN}
    named = sum(t for n, t in idle.items() if n != tr.NO_SPAN)
    assert idle["train_fold"] > 0.25 * named
    assert named > 3 * idle[tr.NO_SPAN]
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
