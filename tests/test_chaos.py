"""fmda_tpu.chaos — deterministic fault injection (ISSUE 7).

The fast tier-1 surface: seeded plans are pure functions of their seed
(two runs of one plan observe the identical event sequence — a chaos
run is a reproduction recipe), the wrappers degrade components the way
real transport failures do, the compiled-in injection points drive the
REAL link-failure machinery in the router, and the configured-off state
is indistinguishable from no chaos at all.  The full spawned-process
soak is the slow-marked test at the bottom.
"""

import json

import numpy as np
import pytest

from fmda_tpu.chaos import (
    ChaosBus,
    ChaosFault,
    ChaosRuntime,
    ChaosWarehouse,
    FaultEvent,
    FaultPlan,
    chaos_families,
)
from fmda_tpu.stream.bus import InProcessBus

# ---------------------------------------------------------------------------
# the plan: seeded, serializable, deterministic
# ---------------------------------------------------------------------------


def test_plan_generation_is_a_pure_function_of_the_seed():
    kw = dict(workers=["w0", "w1", "w2"], worker_kills=2,
              router_restarts=1, link_partitions=2, bus_blips=1,
              delays=3)
    a = FaultPlan.generate(7, 50, **kw)
    b = FaultPlan.generate(7, 50, **kw)
    assert a == b
    assert a != FaultPlan.generate(8, 50, **kw)
    # events land inside the settle window at both ends
    settle = 5
    for e in a.events:
        assert e.step >= settle
        assert e.step + 1 <= 50 - settle + max(
            ev.duration for ev in a.events)


def test_generated_plans_have_disjoint_windows_and_distinct_victims():
    """No two generated fault windows may overlap (one-step gap): a
    router takeover coinciding with a dead control bus would wedge the
    soak driver (its virtual clock is frozen mid-step), and compound
    windows make a failing seed irreproducible fault by fault.  Worker
    kills also pick distinct victims — two overlapping kills of one
    worker would silently under-inject."""
    for seed in range(30):
        plan = FaultPlan.generate(
            seed, 60, workers=["w0", "w1", "w2"], worker_kills=3,
            revive_after=6, router_restarts=2, link_partitions=2,
            bus_blips=2, delays=2, corrupts=1)
        spans = sorted((e.step, e.step + e.duration) for e in plan.events)
        for (a0, a1), (b0, b1) in zip(spans, spans[1:]):
            assert a1 < b0, (seed, plan.events)
        kills = [e.target for e in plan.events
                 if e.kind == "kill" and e.target.startswith("worker:")]
        assert len(kills) == len(set(kills)), (seed, kills)


def test_plan_round_trips_through_json_and_files(tmp_path):
    plan = FaultPlan.generate(3, 40, workers=["w0"], corrupts=1,
                              warehouse_kills=1)
    assert FaultPlan.from_wire(
        json.loads(json.dumps(plan.to_wire()))) == plan
    path = str(tmp_path / "plan.json")
    plan.save(path)
    assert FaultPlan.load(path) == plan


def test_plan_rejects_bad_events():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent(0, "meteor", "bus")
    with pytest.raises(ValueError, match="duration"):
        FaultEvent(1, "kill", "bus", duration=0)


def test_runtime_observes_identical_sequences_across_two_runs():
    """The headline determinism contract: one plan, two runs, the same
    scripted probe schedule → bit-identical observed event sequences
    (raise/sleep/pass per probe) and identical counters."""
    plan = FaultPlan.generate(11, 30, workers=["w0", "w1"],
                              worker_kills=0, router_restarts=0,
                              link_partitions=2, bus_blips=2, delays=3)
    points = ("wire.request", "router.pump", "worker.step", "bus",
              "link:w0", "link:w1")

    def observe():
        seq = []
        sleeps = []
        rt = ChaosRuntime().configure(
            enabled=True, plan=plan, sleep_fn=sleeps.append)
        for step in range(plan.n_steps):
            rt.advance(step)
            for point in points:
                try:
                    rt.check(point)
                    seq.append((step, point, "pass"))
                except ChaosFault:
                    seq.append((step, point, "raise"))
        return seq, sleeps, dict(rt.counters)

    a = observe()
    b = observe()
    assert a == b
    # and something actually fired (the plan is not vacuous)
    assert any(kind != "pass" for _, _, kind in a[0]) or a[1]


def test_disabled_runtime_is_inert_through_the_wrappers():
    """The enabled flag gates every instrumented surface: with chaos
    off, a wrapped bus carrying an armed plan behaves exactly like the
    raw bus and nothing is ever recorded."""
    rt = ChaosRuntime().configure(
        enabled=True,
        plan=FaultPlan(5, (FaultEvent(0, "kill", "bus", duration=5),)))
    rt.configure(enabled=False)
    bus = ChaosBus(InProcessBus(["t"]), "bus", chaos=rt)
    rt.advance(0)
    assert bus.publish("t", {"x": 1}) == 0  # armed plan, no effect
    assert [r.value["x"] for r in bus.read("t", 0)] == [1]
    assert rt.counters == {}


def test_chaos_families_snapshot_shape():
    rt = ChaosRuntime().configure(
        enabled=True,
        plan=FaultPlan(5, (FaultEvent(1, "kill", "bus", duration=2),)))
    rt.advance(1)
    with pytest.raises(ChaosFault):
        rt.check("bus")
    fam = chaos_families(rt)
    counters = {(s["labels"]["point"], s["labels"]["kind"]): s["value"]
                for s in fam["counters"]}
    assert counters[("bus", "kill")] == 1
    gauges = {s["name"]: s["value"] for s in fam["gauges"]}
    assert gauges["chaos_enabled"] == 1
    assert gauges["chaos_active_faults"] == 1
    assert gauges["chaos_step"] == 1


# ---------------------------------------------------------------------------
# the wrappers: bus + warehouse degrade like real transport failures
# ---------------------------------------------------------------------------


def test_chaos_bus_kill_window_then_revive():
    rt = ChaosRuntime().configure(
        enabled=True,
        plan=FaultPlan(10, (FaultEvent(2, "kill", "bus", duration=3),)))
    bus = ChaosBus(InProcessBus(["t"]), "bus", chaos=rt)
    assert bus.publish("t", {"x": 1}) == 0
    rt.advance(2)
    with pytest.raises(ChaosFault):
        bus.publish("t", {"x": 2})
    with pytest.raises(ChaosFault):
        bus.read("t", 0)
    assert isinstance(ChaosFault("x"), ConnectionError)  # the handler
    # contract: every existing transport-failure path applies unchanged
    rt.advance(5)  # window closed: the bus "revives" with its log intact
    assert bus.publish("t", {"x": 3}) == 1
    assert [r.value["x"] for r in bus.consumer("t").poll()] == [1, 3]


def test_chaos_bus_corrupt_window_produces_counted_markers():
    rt = ChaosRuntime().configure(
        enabled=True,
        plan=FaultPlan(4, (FaultEvent(1, "corrupt", "bus"),)))
    bus = ChaosBus(InProcessBus(["t"]), "bus", chaos=rt)
    rt.advance(1)
    bus.publish_many("t", [{"x": 1}, {"x": 2}])
    vals = [r.value for r in bus.read("t", 0)]
    assert all(v.get("chaos_corrupted") for v in vals)
    assert rt.counters[("bus", "corrupt")] >= 2
    rt.advance(2)
    bus.publish("t", {"x": 3})
    assert bus.read("t", 0)[-1].value == {"x": 3}


def test_chaos_warehouse_guards_every_public_method():
    class FakeWarehouse:
        def __init__(self):
            self.rows = [1, 2, 3]

        def timestamps(self):
            return [10, 20, 30]

        def __len__(self):
            return len(self.rows)

    rt = ChaosRuntime().configure(
        enabled=True,
        plan=FaultPlan(5, (FaultEvent(1, "kill", "warehouse",
                                      duration=2),)))
    wh = ChaosWarehouse(FakeWarehouse(), chaos=rt)
    assert wh.timestamps() == [10, 20, 30]
    assert len(wh) == 3
    rt.advance(1)
    with pytest.raises(ChaosFault):
        wh.timestamps()
    with pytest.raises(ChaosFault):
        len(wh)
    rt.advance(3)
    assert wh.timestamps() == [10, 20, 30]  # revived, data intact


# ---------------------------------------------------------------------------
# injection points drive the REAL fleet failure machinery
# ---------------------------------------------------------------------------


def test_link_partition_injection_exercises_router_link_machinery():
    """A ``partition link:w0`` window makes the router's per-link
    exchange raise through the compiled-in injection point; the
    EXISTING failure handling must fire — link dropped + counted, ticks
    in the frame counted lost, idempotent control messages requeued —
    and the post-window heartbeat re-link must resume cleanly."""
    from fmda_tpu.chaos import configure_chaos
    from fmda_tpu.config import DEFAULT_TOPICS, FleetTopologyConfig, \
        fleet_topics
    from fmda_tpu.fleet.router import FleetRouter
    from fmda_tpu.stream.bus import Record

    class RecordingLinkBus:
        def __init__(self):
            self.published = []
            self.results = []

        def publish_many(self, topic, values):
            self.published.extend((topic, v) for v in values)

        def read(self, topic, offset):
            return [Record(topic, o, v) for o, v in self.results
                    if o >= offset]

        def end_offset(self, topic):
            return len(self.results)

        def close(self):
            pass

    plan = FaultPlan(
        20, (FaultEvent(5, "partition", "link:w0", duration=1),))
    rt = configure_chaos(enabled=True, plan=plan)
    try:
        link_bus = RecordingLinkBus()
        bus = InProcessBus(tuple(DEFAULT_TOPICS) + fleet_topics(["w0"]))
        clock = [0.0]
        router = FleetRouter(
            bus, FleetTopologyConfig(heartbeat_timeout_s=500.0),
            n_features=4, clock=lambda: clock[0],
            connect_fn=lambda addr: link_bus)
        bus.publish("fleet_control", {
            "kind": "hello", "worker": "w0", "address": "addr:1"})
        router.pump()
        router.open_session("S")
        router.pump()  # the open reaches w0 cleanly
        n_open = sum(1 for _t, v in link_bus.published
                     if v["kind"] == "open")
        assert n_open == 1

        rt.advance(5)  # the partition window opens
        router.submit("S", np.zeros(4, np.float32))
        # enqueue a drain-ish control message alongside the tick so the
        # requeue path has something idempotent to preserve
        router._enqueue("w0", {"kind": "close", "session": "ghost"})
        router.pump()
        c = router.metrics.counters
        assert c["link_errors"] == 1
        assert c["routed_ticks_lost"] == 1
        assert c["control_requeued"] == 1
        assert "w0" not in router._links
        # the control message is HELD for the re-link, never dumped on
        # the shared bus (w0's inbox lives on w0's bus)
        assert [m["kind"] for m in router._outgoing["w0"]] == ["close"]

        rt.advance(7)  # window closed; the worker's next beat re-links
        bus.publish("fleet_control", {
            "kind": "heartbeat", "worker": "w0", "address": "addr:1"})
        router.pump()
        assert "w0" in router._links
        delivered = [v["kind"] for _t, v in link_bus.published]
        assert delivered.count("close") == 1  # requeued exactly once
        # the lost tick ages into results_missing (counted, identity
        # preserved: submitted == served + missing)
        clock[0] += router.cfg.result_timeout_s + 1
        router.pump()
        assert c["results_missing"] == 1
    finally:
        configure_chaos(enabled=False)


def test_injected_worker_step_delay_uses_plan_sleep(monkeypatch):
    """The worker.step injection point stalls via the runtime's sleep
    hook — deterministic, no real wall-clock dependence in tests."""
    from fmda_tpu.chaos import configure_chaos, default_chaos

    sleeps = []
    plan = FaultPlan(
        5, (FaultEvent(2, "delay", "worker.step", delay_s=0.5),))
    configure_chaos(enabled=True, plan=plan, sleep_fn=sleeps.append)
    try:
        rt = default_chaos()
        rt.advance(2)
        rt.check("worker.step")
        assert sleeps == [0.5]
    finally:
        configure_chaos(enabled=False)


# ---------------------------------------------------------------------------
# the full spawned-process soak (slow)
# ---------------------------------------------------------------------------


def _spawn_ok():
    import subprocess
    import sys

    try:
        return subprocess.run(
            [sys.executable, "-c", "pass"], timeout=60,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        ).returncode == 0
    except Exception:
        return False


@pytest.mark.slow
@pytest.mark.parametrize("cell", ["gru", "ssm"])
def test_chaos_soak_never_abort_gates(cell):
    """The end-to-end never-abort contract under a real kill/revive
    plan: spawned workers, a SIGKILLed worker revived mid-run, a router
    takeover rebuilding the registry from worker session reports, a
    control-bus outage — every gate must hold (zero uncounted losses,
    no orphaned session, post-chaos serving, clean sessions
    bit-identical to an unfaulted replay).

    Parametrized over the GRU reference AND the SSM cell family
    (ISSUE 14): the identity gates must stay green with the O(1)-cache
    state riding the whole drain/export/replay machinery (the soak
    ships [model] cell to every spawned worker via the config file)."""
    if not _spawn_ok():
        pytest.skip("subprocess spawn unavailable")
    import dataclasses

    from fmda_tpu.chaos.soak import run_chaos_soak
    from fmda_tpu.config import FrameworkConfig

    cfg = FrameworkConfig()
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, cell=cell))
    workers = ["w0", "w1"]
    plan = FaultPlan.generate(
        1, 40, workers=workers, worker_kills=1, revive_after=8,
        router_restarts=1, link_partitions=1, bus_blips=1, delays=1,
        settle_steps=8)
    out = run_chaos_soak(
        plan, n_workers=len(workers), n_sessions=8, hidden=8, seed=1,
        round_sleep_s=0.04, compare_unfaulted=True, config=cfg)
    assert out["gates_ok"], json.dumps(
        {k: v for k, v in out.items() if k != "worker_stats"},
        indent=2, default=str)
    assert out["takeovers"] and all(
        t["rebuilt_in_time"] for t in out["takeovers"])
    assert out["unaccounted"] == 0
