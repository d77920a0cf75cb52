"""fmda_tpu.control — the adaptive control plane (ISSUE 16).

Deterministic fake-clock coverage of the three loops and their wiring:

- :class:`BatchingController` — the shrink/grow ladders, the hysteresis
  deadband (no oscillation), the bounded steps, idle freeze;
- :class:`QosPolicy` — classification, quotas, and the WFQ victim pick's
  starvation-freedom property;
- :class:`Autoscaler` — sustain windows, cooldown, bounds, and regime
  resets over a ~20-line fake actuator;
- :class:`ControlPlane` — cadence, signal injection, retune actuation,
  the ``/control`` status document, per-tenant counter folding;
- the gateway's QoS integration (quota shed, WFQ overflow victim, exact
  per-class bookkeeping through ``take_batch``, tenant export/import);
- the capacity-model artifact (schema + keys pinned, fake gateway);
- the in-process elastic loop: a latency spike scales the fleet up
  through the actuator, idle drains it back down through
  ``request_leave`` live migration, with zero session loss and outputs
  bit-identical to an unscaled reference run (the fast tier-1 version
  of the spawned ``run_elastic_soak``, which is marked ``slow``).
"""

import dataclasses
import json
import urllib.request
from argparse import Namespace
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fmda_tpu.config import (
    DEFAULT_TOPICS,
    ControlConfig,
    FleetTopologyConfig,
    FrameworkConfig,
    ModelConfig,
    RuntimeConfig,
    fleet_topics,
    load_config,
    save_config,
)
from fmda_tpu.control import (
    Autoscaler,
    BatchingController,
    ControlPlane,
    QosPolicy,
)
from fmda_tpu.control.capacity import (
    CAPACITY_KEYS,
    CAPACITY_SCHEMA,
    CELL_KEYS,
    run_capacity_model,
)
from fmda_tpu.data.normalize import NormParams
from fmda_tpu.fleet.router import FleetRouter
from fmda_tpu.fleet.worker import FleetWorker
from fmda_tpu.runtime import BatcherConfig, FleetGateway, SessionPool
from fmda_tpu.runtime.loadgen import (
    FleetLoadConfig,
    assign_tenants,
    run_fleet_load,
)
from fmda_tpu.runtime.metrics import RuntimeMetrics
from fmda_tpu.stream.bus import InProcessBus


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _setup(feats=6, hidden=5, window=4, seed=0):
    cfg = ModelConfig(hidden_size=hidden, n_features=feats, output_size=4,
                      dropout=0.0, bidirectional=False, use_pallas=False)
    from fmda_tpu.models import build_model

    params = build_model(cfg).init(
        {"params": jax.random.PRNGKey(seed)},
        jnp.zeros((1, window, feats)))["params"]
    return cfg, params


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


def test_control_config_defaults_and_round_trip(tmp_path):
    cfg = FrameworkConfig()
    assert cfg.control.enabled
    assert cfg.control.batching and cfg.control.autoscale
    assert cfg.control.tenant_classes == ()  # QoS off by default
    tuned = dataclasses.replace(
        cfg, control=dataclasses.replace(
            cfg.control,
            target_p99_ms=42.0, hysteresis=0.1,
            tenant_classes=("gold", "standard"),
            tenant_weights=(3.0, 1.0),
            tenant_quota_frac=(1.0, 0.5),
            max_workers=4, cooldown_s=2.5))
    path = save_config(tuned, str(tmp_path / "fmda.toml"))
    loaded = load_config(path)
    assert loaded.control == tuned.control


# ---------------------------------------------------------------------------
# BatchingController
# ---------------------------------------------------------------------------


def _controller(**kw):
    kw.setdefault("target_p99_ms", 10.0)
    kw.setdefault("linger_ms", 0.75)
    kw.setdefault("bucket_sizes", (8, 16))
    kw.setdefault("hysteresis", 0.25)
    kw.setdefault("linger_step_ms", 0.25)
    kw.setdefault("min_linger_ms", 0.0)
    kw.setdefault("max_linger_ms", 1.5)
    return BatchingController(**kw)


def test_batching_shrink_ladder_linger_first_then_bucket():
    ctrl = _controller()
    actions = []
    for t in range(6):
        d = ctrl.decide(100.0, float(t))  # far above target: shrink
        actions.append(d["action"] if d else None)
    # 0.75 -> 0.5 -> 0.25 -> 0.0 (three bounded steps), then the bucket
    # ladder 16 -> 8, then pinned at the floor (hold, not an error)
    assert actions == ["linger_down", "linger_down", "linger_down",
                       "bucket_down", None, None]
    assert ctrl.linger_ms == 0.0 and ctrl.bucket_cap == 8
    assert ctrl.mode == "shrink"


def test_batching_grow_ladder_bucket_first_then_linger():
    ctrl = _controller()
    for t in range(4):
        ctrl.decide(100.0, float(t))  # drive to the floor: cap 8
    actions = []
    for t in range(6):
        d = ctrl.decide(1.0, float(10 + t))  # far below target: grow
        actions.append(d["action"] if d else None)
    # cap 8 -> uncapped (16 is the top of the ladder => None), then the
    # linger climbs 0.25/step to the 1.5 ceiling, then pinned
    assert actions[0] == "bucket_up"
    assert ctrl.bucket_cap is None
    assert actions[1:] == ["linger_up"] * 5
    assert ctrl.linger_ms == pytest.approx(1.25)


def test_batching_deadband_holds_and_idle_freezes():
    ctrl = _controller()
    before = (ctrl.linger_ms, ctrl.bucket_cap)
    # anywhere inside [7.5, 12.5] (hysteresis 0.25 around 10): hold
    for p99 in (7.6, 10.0, 12.4):
        assert ctrl.decide(p99, 0.0) is None
        assert ctrl.mode == "hold"
    # idle window (no served ticks): the knobs must not creep
    assert ctrl.decide(None, 1.0) is None
    assert ctrl.mode == "idle"
    assert (ctrl.linger_ms, ctrl.bucket_cap) == before


def test_batching_bounded_steps_never_jump():
    ctrl = _controller(linger_ms=1.0)
    d = ctrl.decide(1000.0, 0.0)  # 100x over target: still ONE step
    assert d["action"] == "linger_down"
    assert ctrl.linger_ms == pytest.approx(0.75)


def test_batching_decision_record_shape():
    ctrl = _controller()
    d = ctrl.decide(50.0, 3.25)
    assert d["loop"] == "batching" and d["t"] == 3.25
    assert {"action", "p99_ms", "target_p99_ms", "linger_ms",
            "bucket_cap"} <= set(d)
    status = ctrl.status()
    assert status["mode"] == "shrink"
    assert status["deadband_ms"] == [7.5, 12.5]


def test_batching_rejects_nonpositive_target():
    with pytest.raises(ValueError):
        _controller(target_p99_ms=0.0)


# ---------------------------------------------------------------------------
# QosPolicy
# ---------------------------------------------------------------------------


def _policy():
    return QosPolicy(("gold", "standard", "bronze"), (3.0, 2.0, 1.0),
                     (1.0, 0.75, 0.5))


def test_qos_classify_and_quota():
    pol = _policy()
    assert pol.classify("gold") == "gold"
    assert pol.classify(None) == "standard"
    assert pol.classify("unheard-of") == "standard"
    assert pol.quota("gold", 100) == 100
    assert pol.quota("bronze", 100) == 50
    assert pol.quota("bronze", 1) == 1  # never statically locked out


def test_qos_missing_default_class_gets_a_lane():
    pol = QosPolicy(("gold",), (3.0,), (1.0,), default_class="standard")
    assert "standard" in pol.classes
    assert pol.classify(None) == "standard"
    assert pol.quota("standard", 10) == 10


def test_qos_victim_is_most_over_normalized_share():
    pol = _policy()
    # bronze 2/1 = 2.0 vs gold 3/3 = 1.0: bronze loses
    assert pol.pick_victim({"gold": 3, "bronze": 2}) == "bronze"
    # exact tie on shares: lower priority sheds first
    assert pol.pick_victim({"gold": 3, "bronze": 1}) == "bronze"
    assert pol.pick_victim({}) is None
    assert pol.pick_victim({"gold": 0}) is None


def test_qos_starvation_freedom_property():
    """A class at or under its fair share is never the victim while any
    class sits strictly over its share — across random queue states."""
    pol = _policy()
    rng = np.random.default_rng(7)
    for _ in range(200):
        queued = {c: int(n) for c, n in zip(
            pol.classes, rng.integers(0, 12, size=len(pol.classes)))}
        victim = pol.pick_victim(queued)
        if victim is None:
            assert all(n <= 0 for n in queued.values())
            continue
        vshare = queued[victim] / pol.weight(victim)
        for cls, n in queued.items():
            if n > 0:
                assert queued[victim] > 0
                assert vshare >= n / pol.weight(cls) - 1e-12, (
                    queued, victim)


def test_qos_validation():
    with pytest.raises(ValueError):
        QosPolicy(("a", "b"), (1.0,), (1.0, 1.0))  # not parallel
    with pytest.raises(ValueError):
        QosPolicy((), (), ())
    with pytest.raises(ValueError):
        QosPolicy(("a", "a"), (1.0, 1.0), (1.0, 1.0))  # duplicate
    with pytest.raises(ValueError):
        QosPolicy(("a",), (0.0,), (1.0,))  # weight must be positive
    with pytest.raises(ValueError):
        QosPolicy(("a",), (1.0,), (0.0,))  # quota in (0, 1]


def test_qos_from_config():
    assert QosPolicy.from_config(ControlConfig()) is None
    cfg = ControlConfig(tenant_classes=("gold",), tenant_weights=(2.0,),
                        tenant_quota_frac=(1.0,))
    pol = QosPolicy.from_config(cfg)
    assert pol.classify("gold") == "gold"
    snap = pol.snapshot()
    assert snap["default_class"] == "standard"
    assert {c["name"] for c in snap["classes"]} == {"gold", "standard"}


# ---------------------------------------------------------------------------
# Autoscaler
# ---------------------------------------------------------------------------


class FakeActuator:
    """The ~20-line in-memory actuator the protocol docstring promises."""

    def __init__(self, n=1, can_spawn=True):
        self.n = n
        self.can_spawn = can_spawn
        self.spawns = []
        self.retires = []

    def n_workers(self):
        return self.n

    def spawn_worker(self):
        if not self.can_spawn:
            return None
        self.n += 1
        wid = f"w{self.n - 1}"
        self.spawns.append(wid)
        return wid

    def retire_worker(self):
        if self.n <= 1:
            return None
        self.n -= 1
        wid = f"w{self.n}"
        self.retires.append(wid)
        return wid


def _scaler(act, **kw):
    kw.setdefault("min_workers", 1)
    kw.setdefault("max_workers", 3)
    kw.setdefault("target_p99_ms", 100.0)
    kw.setdefault("scale_up_burn", 1.0)
    kw.setdefault("up_sustain_s", 3.0)
    kw.setdefault("scale_down_frac", 0.3)
    kw.setdefault("down_sustain_s", 10.0)
    kw.setdefault("cooldown_s", 5.0)
    return Autoscaler(act, **kw)


HIGH = {"burn_fast": 2.0, "p99_ms": 400.0}
MID = {"burn_fast": 0.0, "p99_ms": 50.0}    # between the thresholds
LOW = {"burn_fast": 0.0, "p99_ms": 5.0}
IDLE = {"burn_fast": 0.0, "p99_ms": None}


def test_autoscaler_scales_up_only_after_sustained_burn():
    act = FakeActuator()
    sc = _scaler(act)
    assert sc.decide(HIGH, 0.0) is None
    assert sc.decide(HIGH, 2.9) is None          # not sustained yet
    d = sc.decide(HIGH, 3.0)
    assert d["action"] == "scale_up" and d["worker"] == "w1"
    assert act.n == 2 and sc.mode == "high"


def test_autoscaler_cooldown_blocks_back_to_back_moves():
    act = FakeActuator()
    sc = _scaler(act)
    sc.decide(HIGH, 0.0)
    assert sc.decide(HIGH, 3.0)["action"] == "scale_up"
    # the move reset the sustain window; it restarts at the first
    # post-move high sample (t=3.5)
    assert sc.decide(HIGH, 3.5) is None
    assert sc.decide(HIGH, 7.9) is None          # sustained, but cooling
    d = sc.decide(HIGH, 8.5)                     # cooldown over at t=8
    assert d["action"] == "scale_up" and act.n == 3


def test_autoscaler_regime_exit_resets_the_sustain_window():
    act = FakeActuator()
    sc = _scaler(act)
    sc.decide(HIGH, 0.0)
    sc.decide(MID, 2.0)                           # dip: window resets
    assert sc.mode == "hold"
    sc.decide(HIGH, 2.5)
    assert sc.decide(HIGH, 5.0) is None           # only 2.5s sustained
    assert sc.decide(HIGH, 5.5)["action"] == "scale_up"


def test_autoscaler_scales_down_on_sustained_idle_and_respects_min():
    act = FakeActuator(n=2)
    sc = _scaler(act)
    assert sc.decide(IDLE, 0.0) is None
    assert sc.decide(LOW, 9.9) is None
    d = sc.decide(IDLE, 10.0)
    assert d["action"] == "scale_down" and act.n == 1
    # at min_workers: sustained idle never drops below the floor
    for t in (16.0, 30.0, 60.0):
        assert sc.decide(IDLE, t) is None
    assert act.n == 1


def test_autoscaler_max_workers_bound():
    act = FakeActuator(n=3)
    sc = _scaler(act)
    sc.decide(HIGH, 0.0)
    assert sc.decide(HIGH, 10.0) is None
    assert act.spawns == []


def test_autoscaler_failed_spawn_is_not_a_move():
    act = FakeActuator(can_spawn=False)
    sc = _scaler(act)
    sc.decide(HIGH, 0.0)
    assert sc.decide(HIGH, 3.0) is None
    act.can_spawn = True
    # no cooldown was engaged by the failed attempt
    assert sc.decide(HIGH, 3.5)["action"] == "scale_up"


def test_autoscaler_rejects_bad_bounds():
    with pytest.raises(ValueError):
        _scaler(FakeActuator(), min_workers=0)
    with pytest.raises(ValueError):
        _scaler(FakeActuator(), min_workers=4, max_workers=2)


# ---------------------------------------------------------------------------
# ControlPlane
# ---------------------------------------------------------------------------


class FakeRouter:
    def __init__(self, stats=None):
        self.retunes = []
        self._stats = stats or {}

    def broadcast_retune(self, **kw):
        self.retunes.append(kw)
        return 1

    def worker_stats(self):
        return self._stats


def _plane_cfg(**kw):
    kw.setdefault("interval_s", 1.0)
    kw.setdefault("target_p99_ms", 10.0)
    kw.setdefault("autoscale", False)
    return ControlConfig(**kw)


def test_plane_cadence_and_retune_broadcast():
    clock = FakeClock()
    router = FakeRouter()
    plane = ControlPlane(
        _plane_cfg(), router=router, initial_linger_ms=1.0,
        bucket_sizes=(8, 16),
        signals_fn=lambda now: {"p99_ms": 100.0, "burn_fast": 0.0},
        clock=clock)
    assert plane.maybe_tick()
    assert not plane.maybe_tick()            # same instant: not due
    clock.advance(0.5)
    assert not plane.maybe_tick()            # half an interval
    clock.advance(0.6)
    assert plane.maybe_tick()
    # every shrink decision pushed a retune with the controller's knobs
    assert len(router.retunes) == 2
    assert router.retunes[-1] == {
        "max_linger_ms": plane.batching.linger_ms,
        "bucket_cap": plane.batching.bucket_cap,
    }
    assert len(plane.decisions) == 2


def test_plane_target_resolution_chain():
    slo = SimpleNamespace(latency_p99_ms=120.0)
    plane = ControlPlane(_plane_cfg(target_p99_ms=None), slo_cfg=slo)
    assert plane.target_p99_ms == 120.0
    plane = ControlPlane(_plane_cfg(target_p99_ms=33.0), slo_cfg=slo)
    assert plane.target_p99_ms == 33.0
    plane = ControlPlane(_plane_cfg(target_p99_ms=None))
    assert plane.target_p99_ms == 250.0     # never targetless


def test_plane_decision_ring_is_bounded():
    clock = FakeClock()
    plane = ControlPlane(
        _plane_cfg(decisions_keep=4, interval_s=0.0),
        initial_linger_ms=8.0, bucket_sizes=(),
        signals_fn=lambda now: {"p99_ms": 1000.0, "burn_fast": 0.0},
        clock=clock)
    for _ in range(40):
        clock.advance(1.0)
        plane.tick()
    assert len(plane.decisions) <= 4


def test_plane_status_folds_tenant_counters_fleet_wide():
    router = FakeRouter(stats={
        "w0": {"tenant_counters": {"admitted_class_gold": 3,
                                   "shed_class_bronze": 1}},
        "w1": {"tenant_counters": {"admitted_class_gold": 2}},
        "w2": {},                             # a worker with no tenants
    })
    plane = ControlPlane(
        _plane_cfg(tenant_classes=("gold", "bronze"),
                   tenant_weights=(3.0, 1.0),
                   tenant_quota_frac=(1.0, 0.5)),
        router=router)
    doc = plane.status()
    assert doc["enabled"] and doc["target_p99_ms"] == 10.0
    assert doc["batching"]["mode"] == "hold"
    assert doc["qos"]["default_class"] == "standard"
    assert doc["tenants"] == {"admitted_class_gold": 5,
                              "shed_class_bronze": 1}
    # round-trips through the scrape endpoint's json.dumps
    json.dumps(doc)


# ---------------------------------------------------------------------------
# capacity model (fake gateway: jax-free, deterministic)
# ---------------------------------------------------------------------------


class FakeCapGateway:
    """Latency = base + linger: retuning the linger down visibly cuts
    p99, so the A/B verdict is deterministic."""

    n_features = 4

    def __init__(self, base_ms=1.0, shed_over=None):
        self.metrics = RuntimeMetrics()
        self.batcher = SimpleNamespace(config=BatcherConfig(
            bucket_sizes=(4, 8), max_linger_s=0.002))
        self.linger_ms = 2.0
        self.base_ms = base_ms
        self.shed_over = shed_over
        self._queued = 0

    def open_session(self, sid, *a, **k):
        pass

    def close_session(self, sid):
        pass

    def submit(self, sid, row):
        if self.shed_over is not None and self._queued >= self.shed_over:
            self.metrics.count("shed_oldest")
            return
        self._queued += 1
        self.metrics.count("ticks_served")
        self.metrics.observe(
            "total", (self.base_ms + self.linger_ms) / 1e3)

    def pump(self):
        self._queued = 0
        return []

    def drain(self):
        return []

    def retune(self, *, max_linger_ms=None, bucket_cap=None):
        if max_linger_ms is not None:
            self.linger_ms = max_linger_ms


def test_capacity_artifact_schema_and_keys_pinned():
    out = run_capacity_model(
        lambda n: FakeCapGateway(), slo_p99_ms=10.0,
        session_grid=(2, 4), duty_grid=(0.5, 1.0), rounds=10)
    assert CAPACITY_SCHEMA == "fmda.control.capacity/1"
    assert out["schema"] == CAPACITY_SCHEMA
    assert tuple(out) == CAPACITY_KEYS
    assert len(out["grid"]) == 4
    for cell in out["grid"]:
        assert tuple(cell) == CELL_KEYS
        assert cell["served"] + cell["shed"] == cell["submitted"]
        assert cell["ok"]
    best = out["max_sustainable"]
    assert best["ticks_per_s"] == max(
        c["ticks_per_s"] for c in out["grid"])
    json.dumps(out)


def test_capacity_controller_ab_improves_when_linger_dominates():
    out = run_capacity_model(
        lambda n: FakeCapGateway(), slo_p99_ms=10.0,
        session_grid=(2, 4), duty_grid=(1.0,), rounds=20)
    ab = out["controller_ab"]
    assert ab["fixed_p99_ms"] == pytest.approx(3.0)
    assert ab["decisions"] > 0
    assert ab["adaptive_p99_ms"] < ab["fixed_p99_ms"]
    assert ab["improved"]
    assert ab["converged"]["linger_ms"] < 2.0


def test_capacity_unsustainable_cells_flagged():
    out = run_capacity_model(
        lambda n: FakeCapGateway(shed_over=1), slo_p99_ms=10.0,
        session_grid=(4,), duty_grid=(1.0,), rounds=5,
        controller_ab=False)
    cell = out["grid"][0]
    assert cell["shed"] > 0 and not cell["ok"]
    assert out["max_sustainable"] is None
    assert out["controller_ab"] is None


# ---------------------------------------------------------------------------
# gateway QoS integration (real pool)
# ---------------------------------------------------------------------------


def _qos_gateway(queue_bound=4, feats=6, window=4):
    cfg, params = _setup(feats=feats, window=window)
    pool = SessionPool(cfg, params, capacity=8, window=window)
    gw = FleetGateway(
        pool, None,
        batcher_config=BatcherConfig(bucket_sizes=(1, 2, 4, 8),
                                     max_linger_s=10.0),
        queue_bound=queue_bound, pipeline_depth=0)
    gw.attach_qos(QosPolicy(("gold", "bronze"), (3.0, 1.0), (1.0, 0.5)))
    return gw, feats


def test_gateway_quota_shed_hits_the_offender_only():
    gw, feats = _qos_gateway()
    rng = np.random.default_rng(0)
    for i, ten in enumerate(["gold", "gold", "bronze", "bronze"]):
        gw.open_session(f"s{i}", tenant=ten)
    row = lambda: rng.normal(size=feats).astype(np.float32)  # noqa: E731
    # bronze quota = max(1, int(0.5 * 4)) = 2: the third bronze tick
    # sheds bronze's own oldest, never touching gold
    gw.submit("s2", row())
    gw.submit("s3", row())
    gw.submit("s2", row())
    c = gw.metrics.counters
    assert c["quota_shed"] == 1
    assert c["shed_class_bronze"] == 1
    assert "shed_class_gold" not in c
    assert c.get("shed_oldest", 0) == 0     # quota shed is NOT oldest-drop
    assert gw._queued_by_class == {"bronze": 2}


def test_gateway_overflow_victim_is_wfq_not_global_oldest():
    gw, feats = _qos_gateway()
    rng = np.random.default_rng(0)
    for i, ten in enumerate(["gold", "gold", "bronze", "bronze"]):
        gw.open_session(f"s{i}", tenant=ten)
    row = lambda: rng.normal(size=feats).astype(np.float32)  # noqa: E731
    # bronze submits FIRST (global-oldest would evict gold later);
    # queue fills to bound=4 with 2 bronze + 2 gold
    gw.submit("s2", row())
    gw.submit("s3", row())
    gw.submit("s0", row())
    gw.submit("s1", row())
    assert gw.saturated
    gw.submit("s0", row())   # overflow: WFQ picks bronze (1/1 > 3/3)
    c = gw.metrics.counters
    assert c["shed_oldest"] == 1            # counted-loss vocab name
    assert c["shed_class_bronze"] == 1
    assert gw._queued_by_class == {"bronze": 1, "gold": 3}
    # conservation: admitted - shed == queued, exactly, per class
    assert c["admitted_class_bronze"] - c["shed_class_bronze"] == 1
    assert c["admitted_class_gold"] == 3


def test_gateway_class_bookkeeping_zeroes_through_drain():
    gw, feats = _qos_gateway(queue_bound=64)
    rng = np.random.default_rng(1)
    for i, ten in enumerate(["gold", "bronze"]):
        gw.open_session(f"s{i}", tenant=ten)
    for _ in range(5):
        gw.submit("s0", rng.normal(size=feats).astype(np.float32))
        gw.submit("s1", rng.normal(size=feats).astype(np.float32))
    assert sum(gw._queued_by_class.values()) == 10
    res = gw.drain()
    assert len(res) == 10
    assert gw._queued_by_class == {}        # every exit decremented


def test_gateway_tenant_survives_export_import_and_close():
    gw, feats = _qos_gateway()
    gw.open_session("s", tenant="bronze")
    assert gw.session_tenant("s") == "bronze"
    state = gw.export_session("s")
    assert state["tenant"] == "bronze"
    gw.close_session("s")
    assert gw.session_tenant("s") is None
    gw.import_session("s", state)
    assert gw.session_tenant("s") == "bronze"


def test_gateway_retune_swaps_linger_and_caps_buckets():
    gw, _ = _qos_gateway()
    gw.retune(max_linger_ms=2.5, bucket_cap=3)
    assert gw.batcher.config.max_linger_s == pytest.approx(0.0025)
    # cap 3 undercuts bucket 4: effective cap falls to the largest
    # compiled bucket at or under it
    assert gw.batcher.effective_cap() == 2
    gw.retune(bucket_cap=None)              # None is authoritative: uncap
    assert gw.batcher.effective_cap() == 8
    assert gw.metrics.counters["retunes_applied"] == 2


def test_retune_on_a_warm_pool_compiles_nothing():
    """Linger and the bucket cap are host-side knobs: a pool precompiled
    on its bucket set and declared warm serves through any retune with
    the programs it had (the cap falls to a compiled bucket, never to a
    new size)."""
    gw, feats = _qos_gateway(queue_bound=64)
    pool = gw.pool
    for b in gw.batcher.config.bucket_sizes:
        pool.step(np.full(b, pool.padding_slot, np.int32),
                  np.zeros((b, feats), np.float32))
    pool.mark_warm()
    compiled = pool.compile_count
    rng = np.random.default_rng(0)
    for i in range(8):
        gw.open_session(f"s{i}", tenant="gold" if i % 2 else "bronze")

    def serve_round():
        for i in range(8):
            gw.submit(f"s{i}", rng.normal(size=feats).astype(np.float32))
        return len(gw.drain())

    served = serve_round()
    for linger_ms, cap in ((0.0, 3), (5.0, 1), (0.5, None), (0.0, 7)):
        gw.retune(max_linger_ms=linger_ms, bucket_cap=cap)
        served += serve_round()
    assert served == 8 * 5
    assert gw.metrics.counters["retunes_applied"] == 4
    assert pool.compile_count == compiled
    assert pool.recompiles_after_warmup == 0


# ---------------------------------------------------------------------------
# loadgen tenant mixes
# ---------------------------------------------------------------------------


def test_assign_tenants_deterministic_and_proportional():
    load = FleetLoadConfig(n_sessions=400, tenant_classes=("a", "b"),
                           tenant_weights=(3.0, 1.0))
    got = assign_tenants(load, np.random.default_rng(0))
    again = assign_tenants(load, np.random.default_rng(0))
    assert got == again
    frac_a = got.count("a") / 400
    assert 0.65 < frac_a < 0.85             # ~0.75 by weight
    assert assign_tenants(FleetLoadConfig(), np.random.default_rng(0)) \
        is None


def test_fleet_load_config_rejects_ragged_mix():
    with pytest.raises(ValueError):
        FleetLoadConfig(tenant_classes=("a", "b"), tenant_weights=(1.0,))


def test_run_fleet_load_labels_sessions_and_counts_by_class():
    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=8, window=4)
    gw = FleetGateway(
        pool, None,
        batcher_config=BatcherConfig(bucket_sizes=(1, 8),
                                     max_linger_s=0.0),
        pipeline_depth=0)
    out = run_fleet_load(gw, FleetLoadConfig(
        n_sessions=6, n_ticks=5, duty=1.0, seed=3,
        tenant_classes=("gold", "standard"), tenant_weights=(1.0, 1.0)))
    by_class = out["submitted_by_class"]
    assert sum(by_class.values()) == out["ticks_submitted"]
    assert out["ticks_served"] == out["ticks_submitted"]
    labels = {gw.session_tenant(f"T{i:04d}") for i in range(6)}
    assert labels <= {"gold", "standard"}


# ---------------------------------------------------------------------------
# fleet wiring: retune broadcast, tenant reports, in-process elastic loop
# ---------------------------------------------------------------------------


def _mini_topology(worker_ids, *, all_ids=None, qos=None, feats=6,
                   window=4, bucket_sizes=(1,)):
    cfg, params = _setup(feats=feats, window=window)
    clock = FakeClock()
    bus = InProcessBus(
        tuple(DEFAULT_TOPICS) + fleet_topics(all_ids or worker_ids))
    fleet_cfg = FleetTopologyConfig(
        heartbeat_interval_s=0.0, heartbeat_timeout_s=50.0)
    rc = RuntimeConfig(capacity=8, window=window,
                       bucket_sizes=bucket_sizes, max_linger_ms=0.0,
                       pipeline_depth=0)
    workers = {
        w: FleetWorker(w, bus, cfg, params, config=fleet_cfg, runtime=rc,
                       clock=clock, precompile=False, qos=qos)
        for w in worker_ids
    }
    router = FleetRouter(bus, fleet_cfg, n_features=feats, clock=clock)
    for w in workers.values():
        w.start()
    router.pump()
    return router, workers, bus, clock, (cfg, params, rc, fleet_cfg)


def _cycle(router, workers, got):
    router.pump()
    for w in workers:
        if not w.stopped:
            w.step()
    for res in router.pump():
        got.setdefault(res.session_id, []).append(res)


def test_retune_broadcast_reaches_every_worker_gateway():
    router, workers, _bus, _clock, _ = _mini_topology(
        ["w0", "w1"], bucket_sizes=(1, 4))
    n = router.broadcast_retune(max_linger_ms=3.0, bucket_cap=1)
    assert n == 2
    router.pump()                           # flush the enqueued retunes
    for w in workers.values():
        w.step()
    for w in workers.values():
        assert w.gateway.batcher.config.max_linger_s == pytest.approx(
            0.003)
        assert w.gateway.batcher.effective_cap() == 1
    assert router.metrics.counters["retunes_broadcast"] == 1


def test_worker_reports_carry_tenant_and_class_counters():
    qos = QosPolicy(("gold", "bronze"), (3.0, 1.0), (1.0, 0.5))
    router, workers, _bus, _clock, _ = _mini_topology(["w0"], qos=qos)
    router.open_session("S0", tenant="gold")
    router.open_session("S1")                # unlabeled
    rng = np.random.default_rng(0)
    got = {}
    for _ in range(3):
        router.submit("S0", rng.normal(size=6).astype(np.float32))
        _cycle(router, workers.values(), got)
    w = workers["w0"]
    assert w.gateway.session_tenant("S0") == "gold"
    report = w.session_report()
    assert report["S0"]["tenant"] == "gold"
    assert "tenant" not in report["S1"]
    stats = w.stats()
    assert stats["tenant_counters"]["admitted_class_gold"] == 3
    # the router sees the same counters via heartbeat-carried stats
    # (one more cycle so a post-admission heartbeat lands)
    _cycle(router, workers.values(), got)
    assert router.worker_stats()["w0"]["tenant_counters"][
        "admitted_class_gold"] == 3
    assert router.session_tenant("S0") == "gold"
    assert router.session_tenant("S1") is None


def test_inprocess_elastic_loop_scales_up_and_down_losslessly():
    """The fast tier-1 elastic soak: a forced latency spike drives the
    plane's autoscaler to spawn a second in-process worker (sessions
    rebalance onto it via live migration), sustained idle retires it
    through ``request_leave``, and the whole elastic episode serves
    every tick bit-identically to a never-scaled reference gateway."""
    feats, window, n_rounds = 6, 4, 12
    tenants = {"E0": "gold", "E1": "standard", "E2": "bronze",
               "E3": "gold"}
    sids = list(tenants)
    rng = np.random.default_rng(5)
    norms = {}
    rows = {}
    for sid in sids:
        mn = rng.normal(size=feats).astype(np.float32)
        norms[sid] = NormParams(mn, mn + 2.0)
        rows[sid] = rng.normal(size=(n_rounds, feats)).astype(np.float32)

    # reference: one gateway, never scaled, bucket 1
    cfg, params = _setup(feats=feats, window=window)
    pool = SessionPool(cfg, params, capacity=8, window=window)
    gw = FleetGateway(
        pool, None,
        batcher_config=BatcherConfig(bucket_sizes=(1,), max_linger_s=0.0),
        pipeline_depth=0)
    ref = {sid: [] for sid in sids}
    for sid in sids:
        gw.open_session(sid, norms[sid])
    for r in range(n_rounds):
        for sid in sids:
            gw.submit(sid, rows[sid][r])
            for res in gw.drain():
                ref[res.session_id].append(res.probabilities)

    router, workers, bus, clock, (mcfg, mparams, rc, fleet_cfg) = \
        _mini_topology(["w0"], all_ids=["w0", "w1"])
    live = list(workers.values())

    class InProcessActuator:
        def n_workers(self):
            return len(router.membership.live())

        def spawn_worker(self):
            w1 = FleetWorker("w1", bus, mcfg, mparams, config=fleet_cfg,
                             runtime=rc, clock=clock, precompile=False)
            workers["w1"] = w1
            live.append(w1)
            w1.start()
            return "w1"

        def retire_worker(self):
            alive = router.membership.live()
            if len(alive) < 2:
                return None
            wid = alive[-1]
            return wid if router.request_leave(wid) else None

    signal = {"p99_ms": None, "burn_fast": 0.0}
    plane = ControlPlane(
        ControlConfig(batching=False, autoscale=True, target_p99_ms=100.0,
                      min_workers=1, max_workers=2, scale_up_burn=1.0,
                      up_sustain_s=0.5, scale_down_frac=0.5,
                      down_sustain_s=1.0, cooldown_s=0.5, interval_s=0.0),
        router=router, actuator=InProcessActuator(),
        signals_fn=lambda now: dict(signal), clock=clock)

    got = {}
    for sid in sids:
        router.open_session(sid, norms[sid], tenant=tenants[sid])
    for r in range(n_rounds):
        if r == 4:
            # market-open spike: the latency objective burns
            signal.update(p99_ms=400.0, burn_fast=4.0)
        if r == 8:
            # spike over: the fleet idles far under target
            signal.update(p99_ms=5.0, burn_fast=0.0)
        for sid in sids:
            router.submit(sid, rows[sid][r])
        for _ in range(4):
            _cycle(router, live, got)
        clock.advance(0.4)
        plane.tick()
    for _ in range(10):
        _cycle(router, live, got)
        clock.advance(0.4)
        plane.tick()

    actions = [d["action"] for d in plane.decisions]
    assert "scale_up" in actions and "scale_down" in actions
    assert "w1" in workers                   # the spawn really happened
    assert workers["w1"].stopped             # ...and the retire drained it
    assert router.membership.live() == ["w0"]
    counters = router.metrics.counters
    assert counters["migrations_completed"] >= 1
    assert counters.get("sessions_lost_state", 0) == 0
    # every tick served exactly once, in order, bit-identical to the
    # never-scaled reference — elasticity moves sessions, never changes
    # them
    for sid in sids:
        assert [r_.seq for r_ in got[sid]] == list(range(n_rounds)), sid
        for r in range(n_rounds):
            np.testing.assert_array_equal(
                got[sid][r].probabilities, ref[sid][r],
                err_msg=f"{sid} tick {r} diverged across scaling")
        assert router.session_tenant(sid) == tenants[sid]


# ---------------------------------------------------------------------------
# observability surface
# ---------------------------------------------------------------------------


def test_control_endpoint_serves_the_plane_document():
    from fmda_tpu.obs.registry import MetricsRegistry
    from fmda_tpu.obs.server import MetricsServer

    plane = ControlPlane(_plane_cfg())
    server = MetricsServer(
        MetricsRegistry(), control_fn=plane.status).start()
    try:
        with urllib.request.urlopen(f"{server.url}/control") as resp:
            doc = json.loads(resp.read())
        assert doc["enabled"] and doc["target_p99_ms"] == 10.0
    finally:
        server.stop()
    # without a control_fn the route 404s instead of lying
    bare = MetricsServer(MetricsRegistry()).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"{bare.url}/control")
        assert err.value.code == 404
    finally:
        bare.stop()


def test_telemetry_attach_controller():
    from fmda_tpu.obs.aggregate import FleetTelemetry

    telemetry = FleetTelemetry(FrameworkConfig().slo)
    assert telemetry.control() == {"enabled": False}
    plane = ControlPlane(_plane_cfg())
    telemetry.attach_controller(plane)
    assert telemetry.control()["enabled"]


def test_cli_tenant_mix_parser():
    from fmda_tpu.cli import _tenant_mix

    classes, weights = _tenant_mix(
        Namespace(tenant_mix="gold:3,standard:1,bronze"))
    assert classes == ("gold", "standard", "bronze")
    assert weights == (3.0, 1.0, 1.0)       # weight defaults to 1
    assert _tenant_mix(Namespace(tenant_mix=None)) == ((), ())
    with pytest.raises(SystemExit):
        _tenant_mix(Namespace(tenant_mix="gold:three"))


def test_cli_print_control_renders_the_status_document(capsys):
    from fmda_tpu.cli import _print_control

    router = FakeRouter(stats={
        "w0": {"tenant_counters": {"admitted_class_gold": 5,
                                   "shed_class_gold": 1}}})
    plane = ControlPlane(
        _plane_cfg(tenant_classes=("gold",), tenant_weights=(2.0,),
                   tenant_quota_frac=(1.0,)),
        router=router, initial_linger_ms=1.0, bucket_sizes=(8,),
        signals_fn=lambda now: {"p99_ms": 100.0, "burn_fast": 0.0})
    plane.tick(now=0.0)
    _print_control(plane.status())
    out = capsys.readouterr().out
    assert "target p99" in out and "gold" in out
    assert "linger" in out


# ---------------------------------------------------------------------------
# the spawned-topology elastic soak (wide; tier-2)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_elastic_soak_spawned_topology_gates_green():
    from fmda_tpu.control.elastic import run_elastic_soak
    from fmda_tpu.fleet.launcher import spawn_supported

    if not spawn_supported():
        pytest.skip("subprocess spawn unavailable on this host")
    report = run_elastic_soak(
        n_sessions=6, warmup_rounds=20, spike_timeout_s=90.0,
        drop_timeout_s=120.0)
    assert report["gates_ok"], report
