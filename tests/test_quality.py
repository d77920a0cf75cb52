"""The online model-quality plane (ISSUE 19).

Deterministic coverage for the label-join evaluator and its edges:

- the capture ledger's conservation identity
  ``captured == joined + expired + shed + pending`` under ring
  overflow, duplicate keys, round-counted expiry, and backend blips;
- target-materialization timing on BOTH warehouse backends (embedded
  sqlite and the protocol-faithful fake MySQL): a prediction joins the
  round its row's targets turn final (``pos + max_lead <= len``),
  including the exact partial-window boundary;
- the quality SLO objectives firing off the published series;
- the acceptance end-to-end: serve v1 through the real replay/serving
  path, hot-swap a deliberately degraded checkpoint, watch per-version
  metrics split, the accuracy SLO fire, and the flight-recorder bundle
  freeze the quality window — then the ``require_eval`` guardrail
  refuse an equally-bad candidate while a good one passes.  No
  wall-clock sleeps anywhere: joins ride fake/virtual clocks.

The flat-price warehouse trick makes quality *constructively*
deterministic: constant OHLC rows give ATR = 0, so every movement
threshold sits exactly at the close and all four targets are 1 for any
row whose leads are in range — an all-ones predictor scores accuracy
1.0 and an all-zeros predictor 0.0, by arithmetic, not by seed luck.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import fake_mysql  # noqa: E402

from fmda_tpu.config import (  # noqa: E402
    FeatureConfig,
    ModelConfig,
    QualityConfig,
    SLOConfig,
    WarehouseConfig,
)
from fmda_tpu.obs.quality import QualityEvaluator  # noqa: E402
from fmda_tpu.obs.slo import SLOEngine  # noqa: E402
from fmda_tpu.obs.tsdb import TimeSeriesStore  # noqa: E402
from fmda_tpu.stream.warehouse import Warehouse  # noqa: E402


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _ts(i: int) -> str:
    return f"2020-01-02 09:{30 + i // 60:02d}:{i % 60:02d}"


def _flat_rows(n: int, start: int = 0):
    """Constant-price rows: ATR 0, so materialized targets are all ones
    for every row whose lead-15 window is in range."""
    fc = FeatureConfig()
    return [
        {"Timestamp": _ts(start + i),
         **{f: (100.0 if f in ("1_open", "2_high", "3_low", "4_close")
                else 1.0)
            for f in fc.table_columns()}}
        for i in range(n)]


def _flat_warehouse(n: int) -> Warehouse:
    wh = Warehouse(FeatureConfig(), WarehouseConfig(path=":memory:"))
    wh.insert_rows(_flat_rows(n))
    return wh


@pytest.fixture
def mysql_env(monkeypatch):
    fake_mysql.SERVER = fake_mysql.FakeServer()
    monkeypatch.setitem(sys.modules, "mysql", fake_mysql)
    monkeypatch.setitem(sys.modules, "mysql.connector",
                        fake_mysql.connector)
    yield fake_mysql.SERVER


def _conservation_holds(evaluator) -> bool:
    c = evaluator.conservation()
    return c["captured"] == (
        c["joined"] + c["expired"] + c["shed"] + c["pending"])


# ---------------------------------------------------------------------------
# capture ledger: the conservation identity under every loss edge
# ---------------------------------------------------------------------------


def test_ring_overflow_evicts_oldest_as_counted_shed():
    ev = QualityEvaluator(QualityConfig(capture_capacity=4),
                          clock=FakeClock())
    for i in range(6):
        ev.capture("T0", _ts(i), np.full(4, 0.9, np.float32))
    c = ev.conservation()
    assert c == {"captured": 6, "joined": 0, "expired": 0,
                 "shed": 2, "pending": 4}
    assert ev.metrics.counters["quality_captures_shed"] == 2
    # the oldest two are gone: the survivors are the newest four
    assert sorted(k[1] for k in ev._ring) == [_ts(i) for i in range(2, 6)]
    assert _conservation_holds(ev)


def test_duplicate_key_capture_counts_replaced_entry_as_shed():
    ev = QualityEvaluator(QualityConfig(), clock=FakeClock())
    ev.capture("T0", _ts(0), np.zeros(4, np.float32), weights_version=1)
    ev.capture("T0", _ts(0), np.ones(4, np.float32), weights_version=1)
    c = ev.conservation()
    assert c["captured"] == 2 and c["shed"] == 1 and c["pending"] == 1
    assert _conservation_holds(ev)
    # the replay-duplicate keeps the NEWEST probabilities
    assert float(np.asarray(
        ev._ring[("T0", _ts(0), 1)].probs)[0]) == 1.0


def test_unjoinable_capture_expires_after_max_attempts_round_counted():
    wh = _flat_warehouse(17)
    ev = QualityEvaluator(
        QualityConfig(max_join_attempts=3), warehouse=wh, max_lead=15,
        clock=FakeClock())
    ev.capture("T0", "2031-01-01 00:00:00",  # never lands
               np.ones(4, np.float32))
    for round_no in range(3):
        ev.join(now=float(round_no))
        expected_pending = 1 if round_no < 2 else 0
        assert ev.conservation()["pending"] == expected_pending
    c = ev.conservation()
    assert c["expired"] == 1 and c["joined"] == 0
    assert ev.metrics.counters["quality_join_expired"] == 1
    assert _conservation_holds(ev)


def test_backend_blip_degrades_the_round_not_the_caller():
    class FlakyWarehouse:
        def ids_for_timestamps(self, ts):
            raise ConnectionError("backend down")

        def __len__(self):
            return 0

    ev = QualityEvaluator(QualityConfig(max_join_attempts=2),
                          warehouse=FlakyWarehouse(), max_lead=15,
                          clock=FakeClock())
    ev.capture("T0", _ts(0), np.ones(4, np.float32))
    assert ev.join(now=0.0) == 0  # degraded round, no raise
    c = ev.conservation()
    # the blip round must NOT age the capture toward expiry
    assert c["pending"] == 1 and c["expired"] == 0
    assert ev.metrics.counters["quality_join_errors"] == 1
    assert _conservation_holds(ev)


def test_maybe_join_is_cadence_gated_on_the_callers_clock():
    wh = _flat_warehouse(17)
    clock = FakeClock()
    ev = QualityEvaluator(QualityConfig(join_interval_s=5.0),
                          warehouse=wh, max_lead=15, clock=clock)
    ev.capture("T0", _ts(1), np.ones(4, np.float32))
    assert ev.maybe_join() == 1  # first call always joins
    ev.capture("T0", _ts(0), np.ones(4, np.float32))
    clock.advance(4.9)
    assert ev.maybe_join() == 0  # within the interval: one clock read
    clock.advance(0.2)
    assert ev.maybe_join() == 1


# ---------------------------------------------------------------------------
# ids_for_timestamps: embedded vs MySQL backend parity
# ---------------------------------------------------------------------------


def _both_warehouses(mysql_env, n=17):
    from fmda_tpu.stream.mysql_warehouse import MySQLWarehouse

    fc = FeatureConfig()
    emb = Warehouse(fc, WarehouseConfig(path=":memory:"))
    myw = MySQLWarehouse(fc, WarehouseConfig(backend="mysql"))
    rows = _flat_rows(n)
    emb.insert_rows(rows)
    myw.insert_rows(rows)
    # the fake serves COUNT from the seeded join view and targets from
    # the seeded target view: mirror the landed rows into both
    mysql_env.seed({i: (0.0,) for i in range(1, n + 1)},
                   {i: (1.0, 1.0, 1.0, 1.0) for i in range(1, n + 1)})
    return emb, myw


def test_ids_for_timestamps_backend_parity(mysql_env):
    emb, myw = _both_warehouses(mysql_env)
    wanted = [_ts(5), "2031-01-01 00:00:00", _ts(0), _ts(16), _ts(5)]
    expect = [6, None, 1, 17, 6]
    assert emb.ids_for_timestamps(wanted) == expect
    assert myw.ids_for_timestamps(wanted) == expect
    assert emb.ids_for_timestamps([]) == myw.ids_for_timestamps([]) == []


def test_ids_for_timestamps_duplicate_landing_resolves_newest(mysql_env):
    emb, myw = _both_warehouses(mysql_env)
    dup = _flat_rows(1, start=3)  # _ts(3) lands AGAIN (backfill overlap)
    emb.insert_rows(dup)
    myw.insert_rows(dup)
    assert emb.ids_for_timestamps([_ts(3)]) == [18]
    assert myw.ids_for_timestamps([_ts(3)]) == [18]


# ---------------------------------------------------------------------------
# target materialization timing, both backends (satellite 3)
# ---------------------------------------------------------------------------


def _timing_case(evaluator, insert_more):
    """Drive the partial-window boundary: with 17 rows and max_lead 15,
    position 2 is exactly final (2 + 15 == 17) and position 3 is one
    row short — until one more row lands."""
    evaluator.capture("T0", _ts(1), np.ones(4, np.float32))   # pos 2
    evaluator.capture("T0", _ts(2), np.ones(4, np.float32))   # pos 3
    assert evaluator.join(now=0.0) == 1
    c = evaluator.conservation()
    assert c["joined"] == 1 and c["pending"] == 1
    insert_more()  # row 18 lands: pos 3 turns final (3 + 15 <= 18)
    assert evaluator.join(now=1.0) == 1
    c = evaluator.conservation()
    assert c["joined"] == 2 and c["pending"] == 0 and c["expired"] == 0
    # flat-price targets are all ones; the all-ones prediction is exact
    assert evaluator.summary()["overall"]["subset_accuracy"] == 1.0
    assert _conservation_holds(evaluator)


def test_target_timing_embedded_backend():
    wh = _flat_warehouse(17)
    ev = QualityEvaluator(QualityConfig(max_join_attempts=10),
                          warehouse=wh, max_lead=15, clock=FakeClock())
    _timing_case(ev, lambda: wh.insert_rows(_flat_rows(1, start=17)))


def test_target_timing_mysql_backend(mysql_env):
    _, myw = _both_warehouses(mysql_env)
    ev = QualityEvaluator(QualityConfig(max_join_attempts=10),
                          warehouse=myw, max_lead=15, clock=FakeClock())

    def insert_more():
        myw.insert_rows(_flat_rows(1, start=17))
        mysql_env.seed({i: (0.0,) for i in range(1, 19)},
                       {i: (1.0, 1.0, 1.0, 1.0) for i in range(1, 19)})

    _timing_case(ev, insert_more)


def test_joined_metrics_split_per_weights_version():
    wh = _flat_warehouse(20)  # positions 1..5 final
    ev = QualityEvaluator(QualityConfig(), warehouse=wh, max_lead=15,
                          clock=FakeClock())
    for i in range(3):  # v1 predicts the truth (all ones)
        ev.capture("T0", _ts(i), np.ones(4, np.float32),
                   weights_version=1)
    for i in range(3, 5):  # v2 predicts all zeros: always wrong
        ev.capture("T0", _ts(i), np.zeros(4, np.float32),
                   weights_version=2)
    assert ev.join(now=0.0) == 5
    doc = ev.summary()
    assert doc["versions"]["1"]["subset_accuracy"] == 1.0
    assert doc["versions"]["1"]["n"] == 3
    assert doc["versions"]["2"]["subset_accuracy"] == 0.0
    assert doc["versions"]["2"]["hamming_loss"] == 1.0
    assert doc["overall"]["n"] == 5
    names = {g["name"] for g in ev.families()["gauges"]}
    assert {"quality_subset_accuracy", "quality_hamming_loss",
            "quality_fbeta", "quality_pending"} <= names


# ---------------------------------------------------------------------------
# drift rides the join cadence
# ---------------------------------------------------------------------------


def test_drift_monitor_scores_at_join_time_and_exports():
    from fmda_tpu.eval.drift import DriftMonitor, build_profile

    rng = np.random.default_rng(0)
    ref = rng.normal(size=(256, 6))
    profile = build_profile(ref, rng.uniform(size=(256, 4)) > 0.7, bins=8)
    wh = _flat_warehouse(17)
    store = TimeSeriesStore(interval_s=1.0, capacity=64, clock=FakeClock())
    ev = QualityEvaluator(
        QualityConfig(), warehouse=wh, max_lead=15, store=store,
        drift=DriftMonitor(profile, min_samples=32), clock=FakeClock())
    for i in range(40):
        ev.capture("T0", _ts(i % 17), np.ones(4, np.float32),
                   features=rng.normal(size=6) + 3.0)  # gross shift
    ev.join(now=1.0)
    doc = ev.summary()
    assert doc["drift"] is not None and doc["drift"]["max_psi"] > 0.25
    assert store.points("quality_drift_score")[-1][1] > 0.25
    assert {g["name"] for g in ev.families()["gauges"]} >= {
        "quality_drift_score"}


# ---------------------------------------------------------------------------
# the quality SLO objectives fire off the published series
# ---------------------------------------------------------------------------


def _slo_cfg(**over):
    base = dict(
        interval_s=1.0, retention_s=600.0, scrape_interval_s=1.0,
        fast_window_s=8.0, slow_window_s=24.0, burn_threshold=2.0)
    base.update(over)
    return SLOConfig(**base)


def test_quality_accuracy_objective_fires_on_sustained_misses():
    wh = _flat_warehouse(64)
    clock = FakeClock()
    store = TimeSeriesStore(interval_s=1.0, capacity=128, clock=clock)
    slo = SLOEngine(_slo_cfg(quality_accuracy_budget=0.35), store,
                    clock=clock)
    ev = QualityEvaluator(QualityConfig(), warehouse=wh, max_lead=15,
                          store=store, clock=clock)
    fired = False
    for step in range(40):
        clock.t = float(step)
        if step < 40:  # two wrong (all-zero) predictions join per step
            for k in range(2):
                i = (2 * step + k) % 49
                ev.capture(f"T{step}", _ts(i), np.zeros(4, np.float32))
        ev.join(now=clock.t)
        slo.evaluate()
        fired = fired or (
            slo.alerts()["alerts"]["quality_accuracy"]["state"] == "firing")
    assert fired
    assert slo.alerts()["alerts"]["quality_accuracy"]["burn_fast"] >= 2.0


def test_quality_objectives_stay_silent_without_the_plane():
    clock = FakeClock()
    slo = SLOEngine(_slo_cfg(), TimeSeriesStore(
        interval_s=1.0, capacity=16, clock=clock), clock=clock)
    for step in range(30):
        clock.t = float(step)
        slo.evaluate()
    alerts = slo.alerts()["alerts"]
    for objective in ("quality_accuracy", "quality_fbeta", "quality_drift"):
        assert alerts[objective]["state"] == "ok"
        assert alerts[objective]["burn_fast"] == 0.0


# ---------------------------------------------------------------------------
# /quality endpoint + status line + CLI report
# ---------------------------------------------------------------------------


def test_quality_endpoint_serves_the_evaluator_document():
    import urllib.request

    from fmda_tpu.obs import FleetTelemetry

    wh = _flat_warehouse(17)
    telemetry = FleetTelemetry(_slo_cfg())
    ev = QualityEvaluator(QualityConfig(), warehouse=wh, max_lead=15)
    ev.capture("T0", _ts(1), np.ones(4, np.float32))
    ev.join(now=0.0)
    telemetry.attach_quality(ev)
    assert ev.store is telemetry.store  # the SLO series wire-up
    server = telemetry.start_server(port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/quality", timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["conservation"]["joined"] == 1
        assert doc["overall"]["subset_accuracy"] == 1.0
    finally:
        server.stop()


def test_status_quality_line_renders_from_snapshot(capsys):
    from fmda_tpu.cli import _print_quality_summary, _quality_summary

    snapshot = {
        "gauges": [
            {"name": "quality_subset_accuracy",
             "labels": {"version": "1"}, "value": 0.875},
            {"name": "quality_hamming_loss",
             "labels": {"version": "1"}, "value": 0.05},
            {"name": "quality_pending", "labels": {}, "value": 3.0},
            {"name": "quality_drift_score", "labels": {}, "value": 0.31},
        ],
        "counters": [
            {"name": "quality_joined_total", "labels": {}, "value": 40.0},
            {"name": "quality_join_expired_total", "labels": {},
             "value": 2.0},
        ],
    }
    quality = _quality_summary(snapshot)
    assert quality["versions"]["1"]["accuracy"] == 0.875
    _print_quality_summary(quality)
    out = capsys.readouterr().out
    assert out.startswith("quality: joined 40")
    assert "v1 acc 0.875" in out and "drift psi 0.310" in out
    assert "lost 2 expired" in out
    # no quality series at all -> no section in `status`
    assert _quality_summary({"gauges": [], "counters": []}) == {}


def test_cmd_quality_renders_bundle(tmp_path, capsys):
    from fmda_tpu.cli import main

    wh = _flat_warehouse(17)
    ev = QualityEvaluator(QualityConfig(), warehouse=wh, max_lead=15)
    ev.capture("T0", _ts(1), np.ones(4, np.float32), weights_version=2)
    ev.join(now=0.0)
    bundle = tmp_path / "bundle"
    bundle.mkdir()
    (bundle / "quality.json").write_text(json.dumps(ev.summary()))
    assert main(["quality", "--bundle", str(bundle)]) == 0
    out = capsys.readouterr().out
    assert "captured 1 = joined 1" in out
    assert "v2" in out

    # --json passes the document through verbatim
    assert main(["quality", "--bundle", str(bundle), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["conservation"]["joined"] == 1
    assert main(["quality"]) == 2  # no input selected: usage error


def test_cmd_quality_has_no_artifact_input(tmp_path, capsys):
    """`quality` reads the evaluator's own document only: the flag
    that read another program's file is a usage error from argparse."""
    from fmda_tpu.cli import main

    with pytest.raises(SystemExit) as exc:
        main(["quality", "--artifact", str(tmp_path / "x.json")])
    assert exc.value.code == 2
    assert "unrecognized arguments: --artifact" in capsys.readouterr().err


def test_cmd_quality_usage_names_only_the_inputs_that_exist(tmp_path, capsys):
    from fmda_tpu.cli import main

    assert main(["quality"]) == 2
    err = capsys.readouterr().err
    assert "--endpoint" in err and "--bundle" in err
    assert "--artifact" not in err and "bench" not in err
    # a bundle without the evaluator's document is a read error, not a report
    assert main(["quality", "--bundle", str(tmp_path)]) == 2
    assert "quality.json" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# acceptance end-to-end: serve -> degrade -> SLO -> bundle -> guardrail
# ---------------------------------------------------------------------------


def _params_with_bias(cfg, bias, seed=0):
    """A checkpoint whose head bias saturates the sigmoid: +50 predicts
    all ones (the flat warehouse's truth), -50 all zeros (always
    wrong) — quality separation by construction, not seed luck."""
    import jax
    import jax.numpy as jnp

    from fmda_tpu.models import build_model

    params = build_model(cfg).init(
        {"params": jax.random.PRNGKey(seed)},
        jnp.zeros((1, 4, cfg.n_features)))["params"]
    params = jax.tree.map(np.asarray, params)
    params["linear"]["bias"] = np.full(
        cfg.output_size, float(bias), np.float32)
    return params


def _serving_model_cfg():
    fc = FeatureConfig()
    # WarehouseHistory streams RAW landed rows: the model width is the
    # landed table width, not the derived x_fields view
    return ModelConfig(
        hidden_size=5, n_features=len(fc.table_columns()), output_size=4,
        dropout=0.0, bidirectional=False, use_pallas=False)


@pytest.mark.slow
def test_e2e_hot_swap_regression_fires_slo_and_freezes_bundle(tmp_path):
    from fmda_tpu.obs import FleetTelemetry
    from fmda_tpu.replay import ReplayDriver, WarehouseHistory
    from fmda_tpu.runtime import BatcherConfig, FleetGateway, SessionPool

    wh = _flat_warehouse(40)  # positions 1..25 have final targets
    cfg = _serving_model_cfg()
    clock = FakeClock()
    telemetry = FleetTelemetry(
        _slo_cfg(quality_accuracy_budget=0.35,
                 postmortem_dir=str(tmp_path / "postmortem")),
        clock=clock)
    evaluator = QualityEvaluator(
        # joins are driven explicitly below (deterministic schedule);
        # expiry settles the tail pending rows within the test window
        QualityConfig(join_interval_s=1e9, max_join_attempts=4),
        warehouse=wh, max_lead=15, clock=clock)

    pool = SessionPool(cfg, _params_with_bias(cfg, +50.0),
                       capacity=2, window=4)
    gateway = FleetGateway(pool, None, batcher_config=BatcherConfig(
        bucket_sizes=(2,), max_linger_s=0.0))

    # serve v1 (the good checkpoint) over the first 10 rows
    ReplayDriver(
        gateway,
        WarehouseHistory(wh, 2, n_features=cfg.n_features, end_ts=_ts(9)),
        quality=evaluator).run()
    telemetry.attach_quality(evaluator)
    for step in range(5):
        clock.t = float(step)
        evaluator.join(now=clock.t)
        telemetry.collect_gateway(gateway, now=clock.t)
    alerts = telemetry.slo.alerts()["alerts"]
    assert alerts["quality_accuracy"]["state"] == "ok"
    assert evaluator.summary()["versions"]["0"]["subset_accuracy"] == 1.0

    # hot-swap a deliberately degraded checkpoint, keep serving
    for sid in ("T0000", "T0001"):
        gateway.close_session(sid)
    assert gateway.hot_swap(_params_with_bias(cfg, -50.0, seed=1)) == 1
    ReplayDriver(
        gateway,
        WarehouseHistory(wh, 2, n_features=cfg.n_features,
                         start_ts=_ts(10)),
        quality=evaluator).run()
    assert set(gateway.version_ticks) == {0, 1}

    fired_at = None
    for step in range(25, 40):
        clock.t = float(step)
        evaluator.join(now=clock.t)
        telemetry.collect_gateway(gateway, now=clock.t)
        state = telemetry.slo.alerts()["alerts"]["quality_accuracy"]
        if fired_at is None and state["state"] == "firing":
            fired_at = step
    assert fired_at is not None, "accuracy SLO never fired post-swap"

    # per-version split: the regression is attributed to v1's stamp
    doc = evaluator.summary()
    assert doc["versions"]["0"]["subset_accuracy"] == 1.0
    assert doc["versions"]["1"]["subset_accuracy"] == 0.0
    # all 40 captures accounted: 25 joined, the 15 beyond the final-
    # target frontier expired round-counted (no wall clock anywhere)
    assert doc["conservation"]["joined"] == 25
    assert doc["conservation"]["expired"] == 15
    assert doc["conservation"]["pending"] == 0
    assert _conservation_holds(evaluator)

    # the alert froze a postmortem bundle with the quality window in it
    bundles = telemetry.recorder.bundles()
    assert bundles, "SLO fire did not trigger a flight-recorder bundle"
    with open(os.path.join(bundles[-1], "quality.json")) as fh:
        frozen = json.load(fh)
    assert frozen["versions"]["1"]["subset_accuracy"] == 0.0
    assert frozen["versions"]["0"]["subset_accuracy"] == 1.0
    telemetry.close()


@pytest.mark.slow
def test_broadcast_hot_swap_guardrail_refuses_regression(mysql_env):
    """The acceptance guardrail: ``broadcast_hot_swap(require_eval=...)``
    shadow-scores the candidate against the incumbent over warehoused
    history and refuses the regression — counted, announced, zero
    workers told — while an equally-good candidate passes."""
    import jax

    from test_fleet import _cycle, _topology

    from fmda_tpu.eval.shadow import ShadowEvaluator

    wh = _flat_warehouse(40)
    cfg = _serving_model_cfg()
    incumbent = _params_with_bias(cfg, +50.0)
    degraded = _params_with_bias(cfg, -50.0, seed=1)
    good = _params_with_bias(cfg, +50.0, seed=2)

    shadow = ShadowEvaluator(
        incumbent, model_config=cfg, warehouse=wh,
        quality_config=QualityConfig(
            swap_eval_rounds=10, swap_eval_sessions=2, swap_margin=0.02),
        max_lead=15, window=4)

    router, workers, bus, _clock, _ = _topology(
        ["w0"], feats=cfg.n_features, window=4)
    refusals = bus.consumer(router.control_topic, from_end=True)

    told = router.broadcast_hot_swap(
        jax.tree.map(np.asarray, degraded), require_eval=shadow)
    assert told == 0
    assert router.metrics.counters["hot_swaps_refused"] == 1
    announced = [r.value for r in refusals.poll()
                 if r.value.get("kind") == "hot_swap_refused"]
    assert len(announced) == 1
    detail = announced[0]["detail"]
    assert detail["scored"] is True
    assert detail["candidate_accuracy"] == 0.0
    assert detail["incumbent_accuracy"] == 1.0
    got = {}
    for _ in range(3):
        _cycle(router, workers.values(), got)
    # the fleet keeps serving the incumbent: no worker saw a swap
    assert all(w.gateway.weights_version is None for w in workers.values())

    told = router.broadcast_hot_swap(
        jax.tree.map(np.asarray, good), require_eval=shadow)
    assert told == 1
    for _ in range(3):
        _cycle(router, workers.values(), got)
    assert all(w.gateway.weights_version == 1 for w in workers.values())
    assert router.metrics.counters["hot_swaps_refused"] == 1  # unchanged


def test_shadow_evaluator_passes_unscored_on_a_young_warehouse():
    """A warehouse with no materialized targets cannot refuse: blocking
    every swap on an empty history would deadlock a fresh deployment."""
    from fmda_tpu.eval.shadow import ShadowEvaluator

    wh = _flat_warehouse(8)  # < max_lead + 1: nothing final yet
    cfg = _serving_model_cfg()
    shadow = ShadowEvaluator(
        _params_with_bias(cfg, +50.0), model_config=cfg, warehouse=wh,
        quality_config=QualityConfig(
            swap_eval_rounds=3, swap_eval_sessions=2),
        max_lead=15, window=4)
    ok, detail = shadow.gate(_params_with_bias(cfg, -50.0, seed=1))
    assert ok
    assert detail["scored"] is False and detail["joined"] == 0
