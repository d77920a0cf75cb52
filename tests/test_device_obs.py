"""Device & compiler observability (ISSUE 17): the tracked-jit compile
ledger, unexpected-recompile detection, the cost-analysis probe, the
memory watermark monitor, the host sampling profiler, and the SLO /
flight-recorder integration.

The acceptance test is the ISSUE's contract: a runtime bucket-set
change after warmup triggers the unexpected-recompile path end to end
— ledger event, counter, SLO burn-rate alert, and a flight-recorder
bundle carrying both the folded-stack profile and the ledger snapshot.
"""

import json
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fmda_tpu import compat
from fmda_tpu.config import ModelConfig, ProfilingConfig, SLOConfig
from fmda_tpu.obs import EventLog, FleetTelemetry, FlightRecorder
from fmda_tpu.obs.device import (
    LEDGER_SCHEMA,
    PROGRAM_SCHEMA,
    CompileLedger,
    DeviceMemoryMonitor,
    TrackedFunction,
    configure_device_obs,
    device_report,
    tracked_jit,
)
from fmda_tpu.obs.pyprof import HostProfiler, thread_stage
from fmda_tpu.obs.slo import SERIES_LEAK, SERIES_RECOMPILES
from fmda_tpu.runtime import SessionPool


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _setup(feats=6, hidden=5, window=4, seed=0):
    cfg = ModelConfig(hidden_size=hidden, n_features=feats, output_size=4,
                      dropout=0.0, bidirectional=False, use_pallas=False)
    from fmda_tpu.models import build_model

    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(seed)},
                        jnp.zeros((1, window, feats)))["params"]
    return cfg, params


def _slo_cfg(**over):
    base = dict(
        interval_s=1.0, retention_s=600.0, scrape_interval_s=1.0,
        fast_window_s=8.0, slow_window_s=24.0, burn_threshold=2.0,
        latency_p99_ms=100.0, latency_budget=0.05, loss_budget=0.01,
        journal_depth=100, journal_budget=0.1,
        degraded_feed_budget_minutes=0.05)
    base.update(over)
    return SLOConfig(**base)


# ---------------------------------------------------------------------------
# ledger basics + pinned schemas
# ---------------------------------------------------------------------------


def test_ledger_dump_schema_is_pinned():
    """The dump document is part of ``/device`` and a flight-recorder
    bundle member — its key set is part of the operational contract."""
    led = CompileLedger(enabled=True)
    f = tracked_jit(lambda x: x + 1.0, name="prog", ledger=led,
                    signature_of=lambda x: int(x.shape[0]))
    f(jnp.ones((2,)))
    dump = led.dump()
    assert tuple(sorted(dump)) == tuple(sorted(LEDGER_SCHEMA))
    assert dump["schema_version"] == 2
    assert len(dump["programs"]) == 1
    for prog in dump["programs"]:
        assert tuple(sorted(prog)) == tuple(sorted(PROGRAM_SCHEMA))
    assert dump["compiles_total"] == 1
    assert dump["compile_seconds_total"] > 0.0


def test_tracked_jit_counts_compiles_per_signature_not_per_call():
    led = CompileLedger(enabled=True)
    f = tracked_jit(lambda x: (x * 2.0).sum(), name="prog", ledger=led,
                    signature_of=lambda x: int(x.shape[0]))
    for _ in range(3):
        f(jnp.ones((4,)))
    f(jnp.ones((8,)))
    assert led.compiles_total == 2
    recs = {p["signature"]: p for p in led.dump()["programs"]}
    assert recs["4"]["calls"] == 3 and recs["4"]["compiles"] == 1
    assert recs["8"]["calls"] == 1 and recs["8"]["compiles"] == 1


def test_disabled_ledger_is_passthrough_and_records_nothing():
    led = CompileLedger(enabled=False)
    f = tracked_jit(lambda x: x + 1.0, name="prog", ledger=led)
    out = f(jnp.ones((2,)))
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert led.compiles_total == 0
    assert led.dump()["programs"] == []


def test_unexpected_recompile_counted_and_evented_after_mark_warm():
    led = CompileLedger(enabled=True)
    led.events = EventLog()
    f = tracked_jit(lambda x: x * 3.0, name="prog", ledger=led,
                    signature_of=lambda x: int(x.shape[0]))
    f(jnp.ones((2,)))
    led.mark_warm()
    assert led.recompiles_after_warmup == 0
    f(jnp.ones((2,)))  # same program: no compile, no event
    assert led.recompiles_after_warmup == 0
    f(jnp.ones((5,)))  # new shape after warmup: the alarm case
    assert led.recompiles_after_warmup == 1
    kinds = [e["kind"] for e in led.events.tail()]
    assert "device.compile" in kinds
    assert "device.unexpected_recompile" in kinds
    fired = [e for e in led.events.tail()
             if e["kind"] == "device.unexpected_recompile"]
    assert fired[0]["program"] == "prog"


def test_ledger_families_aggregate_same_named_programs():
    """Several pools in one process can track same-named programs (a
    multi-worker soak) — the exposition must stay one sample per label
    set, summed.  The workers stay live across the scrape (registration
    is weak: a dropped owner's programs leave the ledger with it)."""
    led = CompileLedger(enabled=True)
    fns = []
    for _ in range(2):
        f = tracked_jit(lambda x: x - 1.0, name="shared", ledger=led,
                        signature_of=lambda x: int(x.shape[0]))
        f(jnp.ones((3,)))
        fns.append(f)
    fams = led.families()
    compiles = [s for s in fams["counters"] if s["name"] == "compile_total"
                and s["labels"].get("program") == "shared"]
    assert len(compiles) == 1
    assert compiles[0]["value"] == 2


def test_ledger_registration_is_weak():
    """Registration must never be what keeps a dead owner alive: a
    trainer/pool that is dropped takes its tracked programs — and
    everything their jit closures captured (parameter trees, placed
    device batches) — off the ledger with it.  Before this pin, every
    Trainer ever constructed in a process leaked through the ledger."""
    import gc
    import weakref

    led = CompileLedger(enabled=True)
    f = tracked_jit(lambda x: x * 2.0, name="ephemeral", ledger=led,
                    signature_of=lambda x: int(x.shape[0]))
    f(jnp.ones((2,)))
    assert len(led.functions()) == 1
    ref = weakref.ref(f)
    del f
    gc.collect()
    assert ref() is None
    assert led.functions() == []


def test_ledger_thread_safety_sum_of_deltas_equals_cache_size():
    """Concurrent callers racing distinct shapes: every compile is
    claimed exactly once (sum of per-signature compiles == the jit
    cache's final size) and call counts are exact."""
    led = CompileLedger(enabled=True)
    f = tracked_jit(lambda x: (x + 1.0).sum(), name="prog", ledger=led,
                    signature_of=lambda x: int(x.shape[0]))
    n_threads, calls_each = 8, 25
    errors = []

    def hammer(tid):
        try:
            for i in range(calls_each):
                f(jnp.ones((1 + (tid * calls_each + i) % 5,)))
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    size = f.cache_size()
    if size is not None:
        assert led.compiles_total == size
    else:
        assert led.compiles_total == 5  # distinct-signature fallback
    assert sum(p["calls"] for p in led.dump()["programs"]) \
        == n_threads * calls_each


def test_cache_size_fallback_counts_distinct_signatures():
    """On a jax without the private cache probe the ledger degrades to
    distinct-signature counting instead of going blind."""
    led = CompileLedger(enabled=True)

    calls = []

    class NoProbeJit:
        def __call__(self, *a, **k):
            calls.append(a)
            return 0.0

    f = TrackedFunction(NoProbeJit(), name="prog", ledger=led,
                        signature_of=lambda x: int(x.shape[0]))
    led.track(f)
    assert f.cache_size() is None
    f(jnp.ones((2,)))
    f(jnp.ones((2,)))
    f(jnp.ones((6,)))
    assert led.compiles_total == 2
    assert len(calls) == 3


# ---------------------------------------------------------------------------
# cost-analysis probe (compat seam)
# ---------------------------------------------------------------------------


class _FakeCompiled:
    def __init__(self, cost):
        self._cost = cost

    def cost_analysis(self):
        return self._cost


class _FakeJit:
    def __init__(self, cost):
        self._cost = cost

    def lower(self, *a, **k):
        compiled = _FakeCompiled(self._cost)
        return type("L", (), {"compile": lambda self_: compiled})()


def test_cost_analysis_probe_returns_dict_or_none():
    cost = compat.cost_analysis(
        _FakeJit({"flops": 12.0, "bytes accessed": 34.0}),
        (jnp.ones((2, 3)),))
    assert cost == {"flops": 12.0, "bytes accessed": 34.0}
    # XLA reporting no costs for a program reads as None, not {}
    assert compat.cost_analysis(_FakeJit({}), (jnp.ones((2,)),)) is None


def test_cost_probe_failure_is_counted_never_raised():
    led = CompileLedger(enabled=True, cost_analysis=True)

    class BrokenJit:
        def __call__(self, *a, **k):
            return 0.0

        def lower(self, *a, **k):
            raise RuntimeError("no lowering on this build")

    f = TrackedFunction(BrokenJit(), name="prog", ledger=led,
                        signature_of=lambda x: int(x.shape[0]))
    led.track(f)
    f(jnp.ones((2,)))  # fallback compile detection + failing probe
    assert led.dump()["cost_probe_failures"] == 1
    assert led.compiles_total == 1


def test_cost_analysis_populates_flops_on_real_jax():
    led = CompileLedger(enabled=True, cost_analysis=True)
    f = tracked_jit(lambda x: x @ x.T, name="prog", ledger=led,
                    signature_of=lambda x: int(x.shape[0]))
    f(jnp.ones((8, 8)))
    progs = led.dump()["programs"]
    if led.dump()["cost_probe_failures"]:
        pytest.skip("installed jax exposes no cost_analysis")
    assert progs[0]["flops"] > 0.0


# ---------------------------------------------------------------------------
# memory watermarks + leak heuristic
# ---------------------------------------------------------------------------


def test_memory_monitor_attributes_owners_and_tracks_watermark():
    mon = DeviceMemoryMonitor(interval_s=100.0, leak_window=3)
    tree = {"w": jnp.ones((16, 4), jnp.float32)}
    mon.register_owner("pool:a", lambda: tree)
    doc = mon.sample()
    assert doc["by_owner"]["pool:a"] == 16 * 4 * 4
    assert doc["watermark_bytes"] >= doc["by_owner"]["pool:a"]
    assert mon.watermark_bytes == doc["watermark_bytes"]
    fams = mon.families()
    owners = {s["labels"]["owner"]: s["value"] for s in fams["gauges"]
              if s["name"] == "device_live_bytes"}
    assert owners["pool:a"] == 16 * 4 * 4
    assert "process" in owners


def test_memory_monitor_cadence_gate_and_leak_heuristic(monkeypatch):
    mon = DeviceMemoryMonitor(interval_s=5.0, leak_window=3)
    assert mon.maybe_sample(now=0.0) is True
    assert mon.maybe_sample(now=1.0) is False  # not due: one clock read
    assert mon.maybe_sample(now=5.1) is True
    # strictly monotonic growth across the full window => suspected
    grow = iter([10.0, 20.0, 30.0, 30.0])

    def fake_live():
        return [type("A", (), {"nbytes": next(grow)})()]

    monkeypatch.setattr(jax, "live_arrays", fake_live)
    mon2 = DeviceMemoryMonitor(interval_s=0.0, leak_window=3)
    mon2.sample()
    mon2.sample()
    assert mon2.leak_suspected is False  # window not full yet
    mon2.sample()
    assert mon2.leak_suspected is True
    mon2.sample()  # plateau breaks the strict-growth window
    assert mon2.leak_suspected is False


def test_configure_device_obs_applies_profiling_config():
    cfg = ProfilingConfig(enabled=False, cost_analysis=False,
                          memory_interval_s=9.0, memory_leak_window=5,
                          profile_interval_ms=25.0, profile_max_stacks=7)
    configure_device_obs(cfg)
    from fmda_tpu.obs.device import default_ledger, default_memory_monitor
    from fmda_tpu.obs.pyprof import default_profiler

    try:
        assert default_ledger().enabled is False
        assert default_memory_monitor().interval_s == 9.0
        assert default_memory_monitor().leak_window == 5
        assert default_profiler().interval_ms == 25.0
        assert default_profiler().max_stacks == 7
        assert not default_profiler().running
    finally:
        configure_device_obs(ProfilingConfig(cost_analysis=False))
    assert default_ledger().enabled is True


def test_configure_device_obs_starts_and_stops_host_profiler():
    from fmda_tpu.obs.pyprof import default_profiler

    try:
        configure_device_obs(ProfilingConfig(
            cost_analysis=False, host_profiler=True,
            profile_interval_ms=50.0))
        assert default_profiler().running
    finally:
        configure_device_obs(ProfilingConfig(cost_analysis=False))
    assert not default_profiler().running


# ---------------------------------------------------------------------------
# host sampling profiler
# ---------------------------------------------------------------------------


def test_profiler_folded_round_trip_and_stage_attribution():
    prof = HostProfiler(interval_ms=1000.0)
    ready = threading.Event()
    done = threading.Event()

    def busservice():
        ready.set()
        done.wait(timeout=10.0)

    t = threading.Thread(target=busservice, name="fmda-bus-server-0",
                         daemon=True)
    t.start()
    ready.wait(timeout=10.0)
    try:
        n = prof.sample_once()
        assert n >= 1
    finally:
        done.set()
        t.join(timeout=5.0)
    folded = prof.folded()
    parsed = HostProfiler.parse_folded(folded)
    assert parsed  # at least this test's threads
    assert sum(parsed.values()) == sum(
        int(line.rsplit(" ", 1)[1]) for line in folded.splitlines())
    bus_stacks = [s for s in parsed if s.startswith("fmda-bus-server-0;")]
    assert bus_stacks and "busservice" in bus_stacks[0]
    assert prof.stage_summary().get("bus", 0) >= 1
    assert thread_stage("fmda-bus-server-0") == "bus"
    assert thread_stage("totally-unrelated") == "other"


def test_profiler_start_stop_is_clean_and_families_export():
    prof = HostProfiler(interval_ms=2.0)
    prof.start()
    assert prof.running
    import time as _time

    deadline = _time.monotonic() + 5.0
    while _time.monotonic() < deadline:
        if HostProfiler.parse_folded(prof.folded()):
            break
        _time.sleep(0.01)
    prof.stop()
    assert not prof.running
    fams = prof.families()
    samples = [s for s in fams["counters"]
               if s["name"] == "profile_samples_total"]
    assert samples and samples[0]["value"] >= 1


def test_profiler_overflow_folds_into_other_bucket():
    prof = HostProfiler(max_stacks=1)
    prof.sample_once()
    parsed = HostProfiler.parse_folded(prof.folded())
    assert len(parsed) <= 2  # the one stack + the <other> bucket


# ---------------------------------------------------------------------------
# flight recorder + /device report
# ---------------------------------------------------------------------------


def _bundle_with_device_report(tmp_path, reason):
    """One program compiled once on its own ledger, frozen into a bundle."""
    led = CompileLedger(enabled=True)
    f = tracked_jit(lambda x: x + 1.0, name="prog", ledger=led,
                    signature_of=lambda x: int(x.shape[0]))
    f(jnp.ones((2,)))
    rec = FlightRecorder(
        str(tmp_path), keep=2, min_interval_s=0.0,
        profile_fn=lambda: "MainThread;mod:fn 7\n",
        device_fn=lambda: device_report(ledger=led))
    return rec.trigger(reason)


def test_recorder_bundles_profile_and_device_report(tmp_path):
    path = _bundle_with_device_report(tmp_path, "slo-recompile")
    files = set(os.listdir(path))
    assert {"profile.folded", "device.json"} <= files
    assert HostProfiler.parse_folded(
        open(os.path.join(path, "profile.folded")).read()) \
        == {"MainThread;mod:fn": 7}
    device = json.load(open(os.path.join(path, "device.json")))
    assert tuple(sorted(device["ledger"])) == tuple(sorted(LEDGER_SCHEMA))
    assert device["ledger"]["compiles_total"] == 1
    assert "memory" in device and "kernel_fallbacks" in device


def test_device_report_shape():
    doc = device_report(ledger=CompileLedger(enabled=True),
                        memory=DeviceMemoryMonitor())
    assert set(doc) == {"ledger", "memory", "kernel_fallbacks",
                        "recompiles_after_warmup", "mfu"}
    # the cpu has no published peak: the MFU is absent, not estimated
    assert doc["mfu"] is None


def test_no_mfu_gauge_for_a_device_kind_without_a_published_peak():
    led = CompileLedger(enabled=True)
    for _ in range(2):  # two scrapes: the interval the gauge derives over
        names = {g["name"] for g in led.families()["gauges"]}
    assert "device_arithmetic_intensity" in names
    assert "device_mfu" not in names
    assert led.mfu() is None
    # with a published peak the same scrape derives the gauge
    led._backend, led._device_kind = "tpu", "TPU v5 lite"
    led.families()
    gauge = [g for g in led.families()["gauges"] if g["name"] == "device_mfu"]
    assert gauge and gauge[0]["labels"] == {
        "backend": "tpu", "device_kind": "TPU v5 lite"}
    assert gauge[0]["value"] == led.mfu() == 0.0


class _FlopsOnly:
    """A tracked function's face towards ``CompileLedger.families``."""

    name = "prog"

    def __init__(self):
        self.flops = 0.0

    def _totals(self):
        return 0, 0.0, 0, self.flops, 0.0

    def cache_size(self):
        return 1

    def snapshot(self):
        return []


def _mfu_gauge_after(monkeypatch, device_kind, flops, seconds):
    """(gauge values, ``mfu()``) of a ledger on ``device_kind`` whose
    programs did ``flops`` in the ``seconds`` between two scrapes."""
    from fmda_tpu.obs import device as device_mod

    led = CompileLedger(enabled=True)
    led._backend, led._device_kind = "tpu", device_kind
    fn = _FlopsOnly()
    monkeypatch.setattr(led, "functions", lambda: [fn])
    clock = FakeClock()
    monkeypatch.setattr(device_mod.time, "monotonic", lambda: clock.t)
    led.families()
    clock.t += seconds
    fn.flops = flops
    gauges = [g["value"] for g in led.families()["gauges"]
              if g["name"] == "device_mfu"]
    return gauges, led.mfu()


def test_the_peak_table_is_the_published_v5e_row_and_the_gauge_reads_it(
        monkeypatch):
    from fmda_tpu.obs.device import DEVICE_PEAKS

    # keyed by jax device_kind; no cpu / interpreter / "tpu" backend rows
    assert DEVICE_PEAKS == {"TPU v5 lite": (197e12, 819e9)}
    # two seconds at the published peak
    assert _mfu_gauge_after(
        monkeypatch, "TPU v5 lite", 2.0 * 197e12, 2.0) == ([1.0], 1.0)


def test_unknown_device_kind_has_no_mfu_not_an_assumed_one(monkeypatch):
    from fmda_tpu.obs.device import DEVICE_PEAKS

    for kind in ("TPU v9 imaginary", "cpu", "tpu", "", None):
        assert DEVICE_PEAKS.get(kind) is None
        assert _mfu_gauge_after(monkeypatch, kind, 1e12, 1.0) == ([], None)


def test_cmd_perf_renders_a_bundles_device_report(tmp_path, capsys):
    from fmda_tpu.cli import main

    bundle = _bundle_with_device_report(tmp_path, "manual")
    device_json = os.path.join(bundle, "device.json")
    assert main(["perf", "--input", device_json, "--profile",
                 os.path.join(bundle, "profile.folded")]) == 0
    out = capsys.readouterr().out
    assert "compiles 1" in out and "prog" in out
    assert "hottest host stacks (7 samples)" in out
    # --json passes the device report through, the profile beside it
    assert main(["perf", "--input", device_json, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert tuple(sorted(doc["ledger"])) == tuple(sorted(LEDGER_SCHEMA))


def test_cmd_perf_usage_names_only_the_inputs_that_exist(tmp_path, capsys):
    from fmda_tpu.cli import main

    assert main(["perf"]) == 2  # no input selected: usage error
    err = capsys.readouterr().err
    assert "--endpoint" in err and "--input" in err and "device.json" in err
    assert "bench" not in err and "artifact" not in err
    assert main(["perf", "--input", str(tmp_path / "absent.json")]) == 2
    assert "cannot read" in capsys.readouterr().err


def test_device_plane_on_a_warmed_pool_recompiles_nothing_and_changes_nothing():
    """Steady serving under the whole device plane (ledger accounting a
    call, the memory monitor's cadence check a step, the host profiler
    sampling) adds no compile after warm-up, and the same steps with
    the plane off give the same outputs and record nothing."""
    from fmda_tpu.obs.device import default_ledger, default_memory_monitor

    led, memory = default_ledger(), default_memory_monitor()
    was = led.enabled, memory.enabled
    led.reset()
    led.enabled = memory.enabled = True
    try:
        cfg, params = _setup()
        pool = SessionPool(cfg, params, capacity=4, window=4)
        slots = np.arange(4, dtype=np.int32)
        rows = np.random.default_rng(0).standard_normal(
            (8, 4, 6)).astype(np.float32)
        pool.step(np.full(4, pool.padding_slot, np.int32), rows[0])
        pool.mark_warm()
        profiler = HostProfiler()
        profiler.start()
        on = []
        for r in rows:
            on.append(np.asarray(pool.step(slots, r)))
            memory.maybe_sample()
        profiler.stop()
        dump = led.dump()
        assert dump["unexpected_recompiles_total"] == 0
        assert pool.recompiles_after_warmup == 0
        calls = sum(p["calls"] for p in dump["programs"])
        assert calls >= len(rows) + 1

        led.enabled = memory.enabled = False
        twin = SessionPool(cfg, params, capacity=4, window=4)
        off = [np.asarray(twin.step(slots, r)) for r in rows]
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a, b)
        assert sum(p["calls"] for p in led.dump()["programs"]) == calls
    finally:
        led.enabled, memory.enabled = was
        led.reset()


# ---------------------------------------------------------------------------
# SLO integration
# ---------------------------------------------------------------------------


def test_recompile_objective_fires_on_one_recompile_and_needs_data():
    clock = FakeClock()
    from fmda_tpu.obs import SLOEngine, TimeSeriesStore

    cfg = _slo_cfg(recompile_budget=0.5)
    store = TimeSeriesStore(interval_s=1.0, capacity=64, clock=clock)
    slo = SLOEngine(cfg, store, clock=clock)
    # no data => no alert (a fleet without the device plane is not
    # perpetually healthy-zero OR alerting)
    assert slo.evaluate()["recompile"]["state"] == "ok"
    total = 0
    saw_firing = False
    for step in range(20):
        clock.t = float(step)
        if step == 10:
            total += 1  # ONE post-warmup recompile
        store.record_counter(SERIES_RECOMPILES, float(total), process="w0")
        slo.evaluate()
        if "recompile" in slo.firing():
            saw_firing = True
            assert slo.alerts()["alerts"]["recompile"]["state"] == "firing"
    assert saw_firing
    # and once the event rolls out of both windows the alert resolves —
    # a single historic recompile must not page forever
    assert slo.alerts()["alerts"]["recompile"]["state"] == "ok"


def test_memory_leak_objective_reads_worker_gauges():
    clock = FakeClock()
    from fmda_tpu.obs import SLOEngine, TimeSeriesStore

    cfg = _slo_cfg(memory_leak_budget=0.05)
    store = TimeSeriesStore(interval_s=1.0, capacity=64, clock=clock)
    slo = SLOEngine(cfg, store, clock=clock)
    for step in range(30):
        clock.t = float(step)
        store.record_gauge(SERIES_LEAK, 1.0 if step >= 10 else 0.0,
                           process="w0")
        slo.evaluate()
    assert slo.alerts()["alerts"]["memory_leak"]["state"] == "firing"


# ---------------------------------------------------------------------------
# acceptance: bucket-set change -> recompile -> alert -> bundle
# ---------------------------------------------------------------------------


def test_bucket_change_recompile_alerts_and_bundles_end_to_end(tmp_path):
    """The ISSUE 17 contract.  A SessionPool precompiled on its bucket
    set and marked warm hits an off-bucket batch: the ledger records
    the unexpected recompile (event + counter), the landed worker
    series burns the recompile SLO, the firing alert triggers a
    flight-recorder bundle, and the bundle carries both the host
    profile and the ledger snapshot."""
    from fmda_tpu.obs.device import default_ledger

    led = default_ledger()
    led.reset()
    led.enabled = True
    events = EventLog()
    led.events = events

    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=8, window=4)
    # precompile the declared bucket set, then declare warmup over
    pool.step(np.full(4, pool.padding_slot, np.int32),
              np.zeros((4, 6), np.float32))
    pool.mark_warm()
    assert pool.recompiles_after_warmup == 0
    # the fault: an off-bucket batch size reaches the step seam
    pool.step(np.full(6, pool.padding_slot, np.int32),
              np.zeros((6, 6), np.float32))
    assert pool.recompiles_after_warmup == 1
    assert led.recompiles_after_warmup == 1
    kinds = [e["kind"] for e in events.tail()]
    assert "device.unexpected_recompile" in kinds

    # the worker heartbeat ships the count; the aggregator lands it;
    # the SLO engine burns through the zero-recompile budget and the
    # firing alert freezes a postmortem bundle
    clock = FakeClock()
    telemetry = FleetTelemetry(
        _slo_cfg(recompile_budget=0.5, postmortem_dir=str(tmp_path),
                 postmortem_min_interval_s=0.0),
        clock=clock)
    saw_firing = False
    for step in range(20):
        clock.t = float(step)
        n = led.recompiles_after_warmup if step >= 10 else 0
        telemetry.store.record_counter(
            SERIES_RECOMPILES, float(n), process="w0")
        telemetry.slo.evaluate(now=clock.t)
        if "recompile" in telemetry.slo.firing():
            saw_firing = True
    assert saw_firing
    bundles = telemetry.recorder.bundles()
    assert bundles
    newest = bundles[-1]
    files = set(os.listdir(newest))
    assert {"profile.folded", "device.json"} <= files
    device = json.load(open(os.path.join(newest, "device.json")))
    assert device["recompiles_after_warmup"] >= 1
    programs = {p["program"] for p in device["ledger"]["programs"]}
    assert any(p.startswith("session_pool_step") for p in programs)
    telemetry.close()
    led.reset()
    led.events = None


# ---------------------------------------------------------------------------
# what a compile was made of, and what a program holds (PR 51)
# ---------------------------------------------------------------------------

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def compile_events():
    """Every duration event jax fires while the test runs, as
    ``(event, fun_name, seconds)``: a listener of the test's own beside
    the ledger's."""
    seen = []

    def listen(event, duration, fun_name=None, **_kw):
        seen.append((event, fun_name, duration))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@pytest.fixture
def persistent_cache(tmp_path):
    """jax's persistent compilation cache in ``tmp_path``, holding every
    program whatever its size (the suite runs with it off: conftest
    pins the CPU)."""
    from jax.experimental.compilation_cache import compilation_cache

    # (the flag's name in two pieces: tests/test_env_utils.py holds that
    # only utils/env.py carries it whole, which is about the program)
    keys = {"jax_compilation_" "cache_dir": str(tmp_path / "jax_cache"),
            "jax_enable_compilation_cache": True,
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    was = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def _slow_to_trace(x):
    """A function whose trace takes milliseconds: were its events to
    leak out of a tracked call, the ``(untracked)`` table would keep
    them by name."""
    for k in range(60):
        x = jnp.tanh(x) * (1.0 + k) + jnp.sin(x)
    return x


def test_a_tracked_programs_trace_holds_its_nested_jits_and_untracked_none(
        compile_events):
    led = CompileLedger(enabled=True)
    nested_probe = jax.jit(_slow_to_trace)
    nested_probe.__wrapped__.__name__ = "nested_probe"

    def outer(x):
        for _ in range(20):
            x = nested_probe(x) + 1.0
        return x

    f = tracked_jit(outer, name="nesting_prog", ledger=led)
    f(jnp.ones((4,)))
    nested = [s for e, name, s in compile_events
              if e == TRACE_EVENT and name == "nested_probe"]
    own = [s for e, name, s in compile_events
           if e == TRACE_EVENT and name == "nesting_prog"]
    assert len(nested) == 20 and len(own) == 1
    (prog,) = led.dump()["programs"]
    assert prog["trace_s"] == pytest.approx(own[0], abs=1e-5)
    assert prog["trace_s"] >= sum(nested) > 1e-3
    lower = [s for e, name, s in compile_events
             if e == LOWER_EVENT and name == "jit(nesting_prog)"]
    backend = [s for e, name, s in compile_events
               if e == BACKEND_EVENT and name == "jit(nesting_prog)"]
    assert prog["lower_s"] == pytest.approx(sum(lower), abs=1e-5)
    assert prog["backend_compile_s"] == pytest.approx(sum(backend), abs=1e-5)
    assert prog["rest_s"] >= 0.0
    assert (prog["trace_s"] + prog["lower_s"] + prog["backend_compile_s"]
            + prog["rest_s"]) == pytest.approx(prog["compile_seconds"],
                                               abs=1e-5)
    # nothing of the tracked call is outside it: the only untracked
    # events are the eager ``jnp.ones`` above
    untracked = led.untracked()
    assert "nested_probe" not in untracked["by_name"]
    assert "nesting_prog" not in untracked["by_name"]
    assert untracked["trace_s"] < nested[0]
    # the same function traced outside any tracked call is kept by name,
    # once: its own event holds what it calls
    nested_probe(jnp.ones((5,)))
    row = led.untracked()["by_name"]["nested_probe"]
    assert row["events"] == 3 and row["trace_s"] > 1e-3
    total = led.untracked()["trace_s"] - untracked["trace_s"]
    assert total == pytest.approx(row["trace_s"], rel=0.05)


def test_the_persistent_caches_answer_is_on_the_compile_it_served(
        persistent_cache):
    led = CompileLedger(enabled=True)
    led.events = EventLog()

    def body(x):
        return jnp.tanh(x) @ x.T

    first = tracked_jit(body, name="cached_prog", ledger=led)
    first(jnp.ones((8, 8)))
    (miss,) = led.compile_records()
    assert miss["cache"] == "miss" and miss["cache_retrieval_s"] == 0.0
    jax.clear_caches()
    second = tracked_jit(body, name="cached_prog", ledger=led)
    second(jnp.ones((8, 8)))
    _, hit = led.compile_records()
    assert hit["cache"] == "hit" and hit["cache_retrieval_s"] > 0.0
    assert hit["backend_compile_s"] >= hit["cache_retrieval_s"]
    by_cache = {p["cache"]: p for p in led.dump()["programs"]}
    assert set(by_cache) == {"hit", "miss"}
    # the mirror on /events carries the same fields
    mirrored = [e for e in led.events.tail() if e["kind"] == "device.compile"]
    assert [e["cache"] for e in mirrored] == ["miss", "hit"]
    assert {"trace_s", "lower_s", "backend_compile_s", "rest_s",
            "cache_retrieval_s", "compile_time_saved_s"} <= set(mirrored[0])
    # the running totals hold the tracked program's two answers and
    # those of the eager ``jnp.ones`` beside it
    hits, misses = led.compile_parts_total()[3:5]
    eager = led.untracked()
    assert (hits, misses) == (eager["cache_hits"] + 1,
                              eager["cache_misses"] + 1)


def test_a_compile_with_no_persistent_cache_reads_no_answer():
    led = CompileLedger(enabled=True)
    f = tracked_jit(lambda x: x - 1.0, name="prog", ledger=led)
    f(jnp.ones((3,)))
    (rec,) = led.compile_records()
    assert rec["cache"] is None
    assert led.dump()["programs"][0]["cache"] is None


def _no_compile_happened(events):
    return not [e for e, _, _ in events if e in (LOWER_EVENT, BACKEND_EVENT)]


def test_memory_is_asked_once_and_compiles_nothing(compile_events):
    led = CompileLedger(enabled=True)
    f = tracked_jit(lambda s, b: (s @ b + 1.0, b.sum()), name="holds",
                    ledger=led, donate_argnums=(0,))
    state = jax.device_put(jnp.ones((16, 16)), jax.devices()[0])
    state, _ = f(state, jnp.ones((16, 16)))
    f.mark_warm()
    assert led.compile_records()[0]["memory"] is None  # nobody asked yet
    del compile_events[:]
    size = f.cache_size()
    got = f.memory()
    assert _no_compile_happened(compile_events)
    assert got is f.memory()
    assert (f.cache_size(), f.unexpected_recompiles) == (size, 0)
    assert got["argument_bytes"] == 2 * 16 * 16 * 4
    assert got["alias_bytes"] == 16 * 16 * 4  # the donated state
    assert got["reserved_bytes"] == (
        got["argument_bytes"] + got["output_bytes"] - got["alias_bytes"]
        + got["temp_bytes"] + got["code_bytes"])
    assert got["asked"]["lowerings"] == got["asked"]["backend_compiles"] == 0
    # the ledger's own record of the compile holds the answer: it
    # outlives the function
    assert led.compile_records()[0]["memory"] is got
    assert led.dump()["programs"][0]["memory"] is got
    del f
    assert led.compile_records()[0]["memory"] is got
    # the step goes on as it was
    assert led.dump()["unexpected_recompiles_total"] == 0


def test_memory_of_a_donated_sharded_step_on_a_one_device_mesh(
        compile_events):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    replicated = NamedSharding(mesh, P())
    batched = NamedSharding(mesh, P("dp"))
    led = CompileLedger(enabled=True)

    def step(state, batch, rng):
        noise = jax.random.normal(rng, batch.shape)
        return {"w": state["w"] + (batch + noise).mean(0)}

    f = tracked_jit(step, name="sharded_step", ledger=led,
                    donate_argnums=(0,),
                    in_shardings=(replicated, batched, replicated),
                    out_shardings=replicated)
    state = {"w": jax.device_put(jnp.zeros((8,)), replicated)}
    rng = jax.random.PRNGKey(0)
    for _ in range(2):
        state = f(state, np.ones((4, 8), np.float32), rng)
    f.mark_warm()
    del compile_events[:]
    size = f.cache_size()
    got = f.memory()
    assert got is not None and got is f.memory()
    assert _no_compile_happened(compile_events)
    assert (f.cache_size(), f.unexpected_recompiles) == (size, 0)
    state = f(state, np.ones((4, 8), np.float32), rng)
    assert (f.cache_size(), f.unexpected_recompiles) == (size, 0)


def test_the_cost_probe_rides_the_calls_own_lowering(compile_events):
    """``[profiling] cost_analysis``: cost and memory from one
    ``lower().compile()`` at the compile event, which finds the call's
    own executable (it was a second lowering and a second compile of
    every program before the signature carried the committed leaves'
    shardings)."""
    led = CompileLedger(enabled=True, cost_analysis=True)
    f = tracked_jit(lambda s, b: s @ b, name="probed", ledger=led,
                    donate_argnums=(0,))
    state = jax.device_put(jnp.ones((8, 8)), jax.devices()[0])
    f(state, jnp.ones((8, 8)))
    lowerings = [n for e, n, _ in compile_events
                 if e == LOWER_EVENT and n == "jit(probed)"]
    backends = [n for e, n, _ in compile_events
                if e == BACKEND_EVENT and n == "jit(probed)"]
    assert (len(lowerings), len(backends)) == (1, 1)
    (prog,) = led.dump()["programs"]
    assert prog["flops"] > 0.0 and prog["memory"]["argument_bytes"] == 512
    assert f.cache_size() == 1


def test_a_disabled_ledger_registers_no_listener_and_records_nothing(
        monkeypatch):
    from fmda_tpu.obs import device

    registered = []
    monkeypatch.setattr(device, "_LISTENING", False)
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        registered.append)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        registered.append)
    led = CompileLedger(enabled=False)
    f = tracked_jit(lambda x: x * 5.0, name="prog", ledger=led)
    f(jnp.ones((2,)))
    assert registered == [] and led not in device._armed()
    assert led.compile_records() == []
    assert led.compile_parts_total() == (0.0, 0.0, 0.0, 0, 0, 0.0)
    assert f.memory() is None
    assert led.dump()["untracked"]["by_name"] == {}
    # an enabled one registers the pair, once a process
    on = CompileLedger(enabled=True)
    g = tracked_jit(lambda x: x * 6.0, name="prog", ledger=on)
    h = tracked_jit(lambda x: x * 7.0, name="prog", ledger=on)
    assert registered == [device._on_event, device._on_duration]
    assert on in device._armed() and g is not h


def test_the_untracked_table_is_bounded(monkeypatch):
    from fmda_tpu.obs import device

    led = CompileLedger(enabled=True)
    for k in range(device.UNTRACKED_NAMES + 8):
        led._untracked_part(0, 0.002, 0.002, f"fn_{k}", None)
    led._untracked_part(0, 0.0005, 0.0005, "too_short_to_name", None)
    table = led.untracked()
    assert len(table["by_name"]) == device.UNTRACKED_NAMES + 1
    assert table["by_name"]["(other)"]["events"] == 8
    assert "too_short_to_name" not in table["by_name"]
    assert table["trace_s"] == pytest.approx(
        0.002 * (device.UNTRACKED_NAMES + 8) + 0.0005)


def test_the_compile_ring_keeps_the_newest_records():
    from fmda_tpu.obs import device

    led = CompileLedger(enabled=True)
    f = tracked_jit(lambda x: x.sum(), name="prog", ledger=led,
                    signature_of=lambda x: int(x.shape[0]))
    for n in range(1, 6):
        f(jnp.ones((n,)))
    assert [r["signature"] for r in led.compile_records()] == list("12345")
    assert device.COMPILE_RECORDS == 256
    assert led._compile_ring.maxlen == device.COMPILE_RECORDS
    led.reset()
    assert led.compile_records() == []
    assert led.compile_parts_total() == (0.0, 0.0, 0.0, 0, 0, 0.0)


def test_the_schemas_pin_at_version_two():
    from fmda_tpu.obs.device import LEDGER_SCHEMA_VERSION

    assert LEDGER_SCHEMA_VERSION == 2
    assert "untracked" in LEDGER_SCHEMA
    assert {"trace_s", "lower_s", "backend_compile_s", "rest_s", "cache",
            "cache_retrieval_s", "compile_time_saved_s",
            "memory"} <= set(PROGRAM_SCHEMA)
    led = CompileLedger(enabled=True)
    doc = device_report(ledger=led, memory=DeviceMemoryMonitor())
    assert set(doc["ledger"]["untracked"]) == {
        "trace_s", "lower_s", "backend_compile_s", "cache_hits",
        "cache_misses", "cache_retrieval_s", "compile_time_saved_s",
        "by_name"}


def test_device_report_asks_every_live_program_what_it_holds():
    led = CompileLedger(enabled=True)
    f = tracked_jit(lambda x: x * 2.0, name="prog", ledger=led,
                    signature_of=lambda x: int(x.shape[0]))
    f(jnp.ones((2,)))
    f(jnp.ones((3,)))
    assert [p["memory"] for p in led.dump()["programs"]] == [None, None]
    doc = device_report(ledger=led, memory=DeviceMemoryMonitor())
    held = [p["memory"] for p in doc["ledger"]["programs"]]
    assert [m["argument_bytes"] for m in held] == [8, 12]
    assert f.cache_size() == 2 and f.memory(3) is held[1]
    json.dumps(doc)
