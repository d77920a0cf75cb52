"""The unified observability plane (fmda_tpu.obs): registry vocabulary,
Prometheus/JSONL export, scrape endpoint, health checks, and the
pipeline-wide instrumentation the plane aggregates."""

import json
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from fmda_tpu.config import (
    FrameworkConfig,
    ModelConfig,
    ObservabilityConfig,
    TrainConfig,
    WarehouseConfig,
)
from fmda_tpu.obs import (
    EventLog,
    LatencyHistogram,
    MetricsRegistry,
    MetricsServer,
    Observability,
    default_registry,
    render_prometheus,
)

from test_stream import _small_features


# ---------------------------------------------------------------------------
# LatencyHistogram edge cases (ISSUE 2 satellite)
# ---------------------------------------------------------------------------


def test_histogram_empty_percentile_is_zero():
    h = LatencyHistogram()
    assert h.percentile(50) == 0.0
    assert h.percentile(99) == 0.0
    s = h.summary()
    assert s["count"] == 0 and s["mean_ms"] == 0.0 and s["max_ms"] == 0.0


def test_histogram_single_observation():
    h = LatencyHistogram()
    h.observe(0.005)
    assert h.n == 1
    # every percentile lands in the one occupied bin, clamped to the max
    assert h.percentile(1) == h.percentile(50) == h.percentile(99) == 0.005
    assert h.summary()["count"] == 1
    assert h.summary()["mean_ms"] == pytest.approx(5.0)


def test_histogram_sub_microsecond_clamps_to_bin_0():
    h = LatencyHistogram()
    h.observe(1e-9)   # below the 1 µs floor
    h.observe(0.0)    # zero must not log10-crash
    h.observe(-1.0)   # a clock going backwards must not crash either
    assert h.counts[0] == 3
    assert all(c == 0 for c in h.counts[1:])


def test_histogram_p99_clamped_to_observed_max():
    h = LatencyHistogram()
    for _ in range(100):
        h.observe(0.00123)
    # the bin's upper edge (~1.259 ms) overshoots the true max; the
    # percentile must report the observed max instead
    assert h.percentile(99) == pytest.approx(0.00123)
    assert h.summary()["p99_ms"] == pytest.approx(1.23, abs=1e-6)


def test_histogram_snapshot_merge_round_trip():
    a, b = LatencyHistogram(), LatencyHistogram()
    for v in (1e-5, 3e-4, 0.002, 0.05):
        a.observe(v)
    for v in (2e-4, 0.9, 0.002):
        b.observe(v)
    merged = LatencyHistogram()
    merged.merge(a.snapshot())
    merged.merge(b)  # accepts a live histogram too
    assert merged.n == a.n + b.n
    assert merged.total_s == pytest.approx(a.total_s + b.total_s)
    assert merged.max_s == pytest.approx(0.9)
    # bin-exact: merging is addition of counts
    assert merged.counts == [x + y for x, y in zip(a.counts, b.counts)]
    # distribution queries agree with observing everything in one histogram
    direct = LatencyHistogram()
    for v in (1e-5, 3e-4, 0.002, 0.05, 2e-4, 0.9, 0.002):
        direct.observe(v)
    assert merged.percentile(50) == direct.percentile(50)
    assert merged.percentile(99) == direct.percentile(99)


def test_histogram_merge_rejects_mismatched_bins():
    h = LatencyHistogram()
    with pytest.raises(ValueError, match="bins"):
        h.merge({"counts": [1, 2], "n": 3, "total_s": 0.1, "max_s": 0.1})


def test_histogram_concurrent_observe_keeps_totals_consistent():
    h = LatencyHistogram()
    n_threads, per_thread = 8, 2000

    def hammer():
        for _ in range(per_thread):
            h.observe(0.001)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.n == n_threads * per_thread
    assert sum(h.counts) == h.n


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_registry_get_or_create_and_labels():
    reg = MetricsRegistry()
    c1 = reg.counter("requests_total", topic="deep")
    c2 = reg.counter("requests_total", topic="deep")
    c3 = reg.counter("requests_total", topic="vix")
    assert c1 is c2 and c1 is not c3
    c1.inc()
    c1.inc(2)
    c3.inc()
    snap = reg.snapshot()
    by_label = {
        s["labels"]["topic"]: s["value"] for s in snap["counters"]
    }
    assert by_label == {"deep": 3, "vix": 1}


def test_registry_gauge_and_histogram_snapshot():
    reg = MetricsRegistry()
    reg.gauge("depth").set(7)
    reg.histogram("lat", stage="device").observe(0.01)
    snap = reg.snapshot()
    assert snap["gauges"][0]["value"] == 7
    (h,) = snap["histograms"]
    assert h["name"] == "lat" and h["labels"] == {"stage": "device"}
    assert h["count"] == 1 and h["sum_s"] == pytest.approx(0.01)


def test_registry_collectors_and_include():
    inner = MetricsRegistry()
    inner.counter("inner_total").inc(5)
    reg = MetricsRegistry()
    reg.include(inner)
    reg.register_collector("x", lambda: {
        "gauges": [{"name": "sampled", "labels": {}, "value": 42}]})
    # same-name re-registration replaces (no double-reporting)
    reg.register_collector("x", lambda: {
        "gauges": [{"name": "sampled", "labels": {}, "value": 43}]})
    snap = reg.snapshot()
    assert [s["value"] for s in snap["gauges"]] == [43]
    assert {s["name"]: s["value"] for s in snap["counters"]} == {
        "inner_total": 5}


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("x")
    c.inc(100)
    reg.gauge("y").set(5)
    reg.histogram("z").observe(1.0)
    reg.register_collector("c", lambda: 1 / 0)  # never sampled
    assert reg.snapshot() == {"counters": [], "gauges": [], "histograms": []}


# ---------------------------------------------------------------------------
# Prometheus text exposition (promtool-style validation)
# ---------------------------------------------------------------------------

#: text exposition v0.0.4 grammar, one regex per line kind
_PROM_COMMENT = re.compile(r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
                           r"(counter|gauge|summary|histogram)$")
_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{" + _LABEL + r"(," + _LABEL + r")*\})?"
    r" (NaN|[+-]?Inf|[-+]?[0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?)$"
)


def _assert_valid_exposition(text: str) -> None:
    assert text.endswith("\n")
    for line in text.splitlines():
        if line.startswith("#"):
            assert _PROM_COMMENT.match(line), f"bad comment line: {line!r}"
        else:
            assert _PROM_SAMPLE.match(line), f"bad sample line: {line!r}"


def test_render_prometheus_valid_and_escaped():
    reg = MetricsRegistry()
    reg.counter("req_total", topic="deep").inc(3)
    reg.gauge("queue depth!").set(1.5)  # bad chars get sanitised
    reg.histogram("lat_seconds", stage='we"ird\nstage').observe(0.01)
    text = render_prometheus(reg.snapshot())
    _assert_valid_exposition(text)
    assert 'fmda_req_total{topic="deep"} 3\n' in text
    assert "fmda_queue_depth_" in text  # sanitised name
    assert "fmda_lat_seconds_count" in text and "quantile=" in text


def test_render_prometheus_empty_snapshot():
    assert render_prometheus(
        {"counters": [], "gauges": [], "histograms": []}) == ""


# ---------------------------------------------------------------------------
# EventLog (bounded JSONL ring)
# ---------------------------------------------------------------------------


def test_event_log_ring_bound_and_schema(tmp_path):
    path = str(tmp_path / "events.jsonl")
    logbuf = EventLog(capacity=3, path=path, clock=lambda: 123.5)
    for i in range(5):
        logbuf.emit("test.tick", i=i)
    assert len(logbuf) == 3
    assert [e["i"] for e in logbuf.tail()] == [2, 3, 4]
    assert logbuf.emitted == 5
    assert logbuf.tail(1)[0] == {"ts": 123.5, "kind": "test.tick", "i": 4}
    # every line in the ring serialises back; the file sink kept ALL 5
    for line in logbuf.to_jsonl().strip().splitlines():
        event = json.loads(line)
        assert set(event) >= {"ts", "kind"}
    logbuf.close()
    with open(path) as fh:
        assert len(fh.readlines()) == 5


def test_event_log_rejects_unserialisable_payload():
    logbuf = EventLog(capacity=4)
    with pytest.raises(TypeError):
        logbuf.emit("bad", payload=object())
    assert len(logbuf) == 0  # nothing half-recorded


# ---------------------------------------------------------------------------
# StageTimer thread safety (ISSUE 2 satellite)
# ---------------------------------------------------------------------------


def test_stage_timer_concurrent_observe_and_summary():
    from fmda_tpu.utils.tracing import StageTimer

    timer = StageTimer()
    stop = threading.Event()
    errors = []

    def writer(name):
        while not stop.is_set():
            with timer.stage(name):
                pass

    def reader():
        try:
            for _ in range(300):
                for stats in timer.summary().values():
                    assert stats["count"] >= 0
        except Exception as e:  # noqa: BLE001 — the race we guard against
            errors.append(e)

    writers = [
        threading.Thread(target=writer, args=(f"s{i}",)) for i in range(4)
    ]
    for t in writers:
        t.start()
    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in readers:
        t.start()
    for t in readers:
        t.join()
    stop.set()
    for t in writers:
        t.join()
    assert not errors
    summary = timer.summary()
    assert set(summary) == {"s0", "s1", "s2", "s3"}
    for stats in summary.values():
        assert stats["count"] > 0


def test_stage_timer_observe_records_measured_duration():
    from fmda_tpu.utils.tracing import StageTimer

    timer = StageTimer()
    timer.observe("x", 0.5)
    timer.observe("x", 0.25)
    s = timer.summary()["x"]
    assert s["count"] == 2 and s["total_s"] == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# End-to-end: instrumented Application + scrape endpoint
# ---------------------------------------------------------------------------


def _obs_app(tmp_path=None, **obs_kw):
    from fmda_tpu.app import Application
    from fmda_tpu.stream.bus import InProcessBus

    fc = _small_features(get_cot=False)
    cfg = FrameworkConfig(
        features=fc,
        warehouse=WarehouseConfig(path=":memory:"),
        model=ModelConfig(hidden_size=4, dropout=0.0),
        train=TrainConfig(batch_size=8, window=3, chunk_size=20, epochs=1),
        observability=ObservabilityConfig(**obs_kw),
    )
    bus = InProcessBus(cfg.bus.topics, capacity=cfg.bus.capacity)
    return Application(cfg, bus=bus)


def _feed_synthetic(app, n_days=2, seed=0):
    from fmda_tpu.data.synthetic import (
        SyntheticMarketConfig,
        synthetic_session_messages,
    )

    for topic, msg in synthetic_session_messages(
            app.config.features, SyntheticMarketConfig(
                seed=seed, n_days=n_days)):
        app.bus.publish(topic, msg)
    app.run_tick()


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def test_scrape_endpoint_covers_pipeline_vocabulary():
    """The acceptance check: /metrics off a running app + fleet is valid
    Prometheus exposition covering ingest, bus, engine, and runtime."""
    import jax
    import jax.numpy as jnp

    import dataclasses

    from fmda_tpu.models import build_model

    app = _obs_app()
    _feed_synthetic(app)

    # attach a fleet and push a few ticks through it
    model_cfg = dataclasses.replace(
        app.config.model, bidirectional=False,
        n_features=app.config.features.n_features)
    model = build_model(model_cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, app.config.runtime.window, model_cfg.n_features)),
    )["params"]
    gateway = app.attach_fleet(model_cfg, params)
    gateway.open_session("s0")
    row = np.zeros(model_cfg.n_features, np.float32)
    gateway.submit("s0", row)
    gateway.drain()

    server = app.observability.start_server(port=0)
    try:
        status, body = _get(server.url + "/metrics")
        assert status == 200
        _assert_valid_exposition(body)
        for series in (
            # ingest vocabulary (declared even before any live request)
            "fmda_ingest_requests_total",
            "fmda_ingest_request_seconds",
            # bus
            'fmda_bus_published_total{topic="deep"}',
            'fmda_bus_consumed_total{topic="deep"}',
            # engine
            "fmda_engine_emitted_total",
            "fmda_engine_step_seconds",
            'fmda_engine_stage_seconds_total{stage="join"}',
            "fmda_engine_consumer_lag",
            # warehouse
            "fmda_warehouse_rows_written_total",
            # runtime (fleet)
            'fmda_runtime_latency_seconds_count{stage="total"}',
            "fmda_runtime_ticks_served_total",
            "fmda_runtime_active_sessions",
        ):
            assert series in body, f"missing series: {series}"
        # the engine actually landed rows and the fleet actually served
        m = re.search(r"fmda_engine_emitted_total (\d+)", body)
        assert int(m.group(1)) > 0
        m = re.search(r"fmda_runtime_ticks_served_total (\d+)", body)
        assert int(m.group(1)) == 1

        # JSON snapshot endpoint serves the same registry
        status, snap_body = _get(server.url + "/snapshot")
        assert status == 200
        snap = json.loads(snap_body)
        assert any(
            s["name"] == "engine_emitted_total" for s in snap["counters"])

        # events endpoint: fleet attach + server start were recorded
        status, events_body = _get(server.url + "/events")
        kinds = [json.loads(l)["kind"]
                 for l in events_body.strip().splitlines()]
        assert "fleet.attached" in kinds
        assert "obs.server_started" in kinds
    finally:
        app.observability.close()


def test_healthz_ok_then_flips_on_induced_failures():
    app = _obs_app()
    _feed_synthetic(app)
    server = app.observability.start_server(port=0)
    try:
        status, body = _get(server.url + "/healthz")
        assert status == 200
        health = json.loads(body)
        assert health["status"] == "ok"
        assert set(health["checks"]) == {
            "bus", "warehouse", "feed_degraded", "last_tick", "chaos"}
        assert all(c["ok"] for c in health["checks"].values())

        # induced bus failure: the transport stops answering
        def broken_topics():
            raise RuntimeError("bus gone")

        app.bus.topics = broken_topics
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            _get(server.url + "/healthz")
        assert exc_info.value.code == 503
        health = json.loads(exc_info.value.read())
        assert health["status"] == "degraded"
        assert not health["checks"]["bus"]["ok"]
        assert "bus gone" in health["checks"]["bus"]["detail"]
        assert health["checks"]["warehouse"]["ok"]

        # heal the bus, kill the warehouse: flips the other way
        del app.bus.topics
        app.warehouse.close()
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            _get(server.url + "/healthz")
        health = json.loads(exc_info.value.read())
        assert health["checks"]["bus"]["ok"]
        assert not health["checks"]["warehouse"]["ok"]
    finally:
        app.observability.close()


def test_healthz_last_tick_age_gate():
    clock = {"now": 0.0}
    obs = Observability(
        ObservabilityConfig(max_tick_age_s=10.0),
        clock=lambda: clock["now"],
    )
    obs.checks["last_tick"] = obs._check_last_tick
    assert obs.health()["status"] == "ok"  # startup grace
    obs.tick()
    clock["now"] = 5.0
    assert obs.health()["status"] == "ok"
    clock["now"] = 20.0
    health = obs.health()
    assert health["status"] == "degraded"
    assert "age 20.0s" in health["checks"]["last_tick"]["detail"]
    obs.tick()
    assert obs.health()["status"] == "ok"


def test_chaos_fault_events_land_in_the_latest_observability():
    """The process-default chaos runtime's ``on_fault`` hook must follow
    the LATEST Observability instance (same discipline as its scrape
    collectors): a first-one-wins guard would pin a discarded instance's
    event log — and the whole instance with it — for the process
    lifetime, silently dropping fault events from the live surface."""
    from fmda_tpu.chaos import ChaosFault, FaultEvent, FaultPlan
    from fmda_tpu.chaos.inject import configure_chaos

    first = Observability(ObservabilityConfig(enabled=True))
    second = Observability(ObservabilityConfig(enabled=True))
    rt = configure_chaos(
        enabled=True, plan=FaultPlan(3, (FaultEvent(1, "kill", "bus"),)))
    try:
        rt.advance(1)
        with pytest.raises(ChaosFault):
            rt.check("bus")
        assert "chaos_fault" in [e["kind"] for e in second.events.tail()]
        assert "chaos_fault" not in [e["kind"] for e in first.events.tail()]
    finally:
        configure_chaos(enabled=False)
        rt.on_fault = None


def test_fleet_queue_health_check_reports_saturation():
    import jax
    import jax.numpy as jnp

    import dataclasses

    from fmda_tpu.models import build_model

    app = _obs_app()
    model_cfg = dataclasses.replace(
        app.config.model, bidirectional=False,
        n_features=app.config.features.n_features)
    model = build_model(model_cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, app.config.runtime.window, model_cfg.n_features)),
    )["params"]
    gateway = app.attach_fleet(model_cfg, params, queue_bound=2)
    health = app.observability.health()
    assert health["checks"]["fleet_queue"]["ok"]
    gateway.open_session("s0")
    row = np.zeros(model_cfg.n_features, np.float32)
    gateway.submit("s0", row)
    gateway.submit("s0", row)  # queue now at bound: next submit sheds
    health = app.observability.health()
    assert not health["checks"]["fleet_queue"]["ok"]
    assert "2/2" in health["checks"]["fleet_queue"]["detail"]
    gateway.drain()
    assert app.observability.health()["checks"]["fleet_queue"]["ok"]


def test_disabled_observability_keeps_app_working():
    app = _obs_app(enabled=False)
    _feed_synthetic(app)
    assert app.stats["emitted"] > 0
    assert app.observability.snapshot() == {
        "counters": [], "gauges": [], "histograms": []}
    assert app.observability.health()["status"] == "ok"  # no checks


def test_app_stats_and_stage_timings_surface_fleet():
    """ISSUE 2 satellite: fleet counters visible from the app handle."""
    import jax
    import jax.numpy as jnp

    import dataclasses

    from fmda_tpu.models import build_model

    app = _obs_app()
    assert "fleet" not in app.stats  # no fleet attached yet
    model_cfg = dataclasses.replace(
        app.config.model, bidirectional=False,
        n_features=app.config.features.n_features)
    model = build_model(model_cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, app.config.runtime.window, model_cfg.n_features)),
    )["params"]
    gateway = app.attach_fleet(model_cfg, params)
    gateway.open_session("s0")
    gateway.submit("s0", np.zeros(model_cfg.n_features, np.float32))
    gateway.drain()
    fleet = app.stats["fleet"]
    assert fleet["counters"]["ticks_served"] == 1
    assert fleet["gauges"]["active_sessions"] == 1
    assert "total" in fleet["latency"]
    # gateway host stages land in stage_timings under the fleet. prefix
    assert any(k.startswith("fleet.") for k in app.stage_timings)


# ---------------------------------------------------------------------------
# Transport + trainer instrumentation reaches a registry
# ---------------------------------------------------------------------------


def test_transport_instrumentation_counts_retries_and_waits():
    from fmda_tpu.ingest.transport import (
        RateLimitTransport,
        ReplayTransport,
        RetryTransport,
        TransportError,
    )

    reg = MetricsRegistry()

    class Flaky:
        def __init__(self):
            self.calls = 0

        def get(self, url, headers=None):
            self.calls += 1
            if self.calls < 3:
                raise TransportError("boom")
            return b"ok"

    t = RetryTransport(Flaky(), attempts=3, sleep_fn=lambda s: None,
                       metrics=reg)
    assert t.get("http://x/") == b"ok"
    assert reg.counter("ingest_retries_total").value == 2

    clock = {"now": 0.0}
    waits = []

    def fake_sleep(s):
        waits.append(s)
        clock["now"] += s

    rl = RateLimitTransport(
        ReplayTransport({"http://h/": b"hi"}), min_interval_s=1.0,
        clock=lambda: clock["now"], sleep_fn=fake_sleep, metrics=reg)
    rl.get("http://h/")
    rl.get("http://h/")  # must wait ~1 s
    assert reg.counter("ingest_ratelimit_waits_total").value == 1
    assert reg.counter(
        "ingest_ratelimit_wait_seconds_total").value == pytest.approx(
        sum(waits))


def test_instrumented_engine_replay_lands_the_bare_replays_rows():
    """The plane observes the hot loop and does not steer it: the same
    session replayed in small steps with the registry wired (per-step
    histogram, bus and warehouse counters, the engine's scrape-time
    collector, a scrape mid-load) lands the rows a bare engine lands."""
    from fmda_tpu.config import DEFAULT_TOPICS
    from fmda_tpu.obs import engine_families
    from fmda_tpu.stream import InProcessBus, StreamEngine, Warehouse

    from test_stream import _session_messages

    fc = _small_features(get_cot=False)
    msgs = _session_messages(12)

    def replay(reg):
        bus = InProcessBus(DEFAULT_TOPICS)
        wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
        eng = StreamEngine(bus, wh, fc, metrics=reg)
        if reg is not None:
            reg.register_collector("engine", lambda: engine_families(eng))
            bus.bind_metrics(reg)
            wh.bind_metrics(reg)
        steps = 0
        for i in range(0, len(msgs), 4):  # one tick's four messages a step
            for topic, m in msgs[i:i + 4]:
                bus.publish(topic, m)
            eng.step()
            steps += 1
            if reg is not None and steps == 6:
                reg.snapshot()
        return wh.fetch(list(range(1, len(wh) + 1))), steps

    bare, steps = replay(None)
    reg = MetricsRegistry()
    wired, _ = replay(reg)
    assert bare.shape[0] == 12
    np.testing.assert_array_equal(wired, bare)
    snap = reg.snapshot()
    step_hist = [h for h in snap["histograms"]
                 if h["name"] == "engine_step_seconds"]
    assert step_hist and step_hist[0]["count"] == steps
    published = sum(c["value"] for c in snap["counters"]
                    if c["name"] == "bus_published_total")
    assert published >= len(msgs)


def test_trainer_reports_step_and_epoch_timings():
    from fmda_tpu.data.synthetic import SyntheticMarketConfig, build_corpus

    fc = _small_features(get_cot=False)
    wh, _ = build_corpus(fc, SyntheticMarketConfig(seed=0, n_days=2))
    cfg = FrameworkConfig(
        features=fc,
        model=ModelConfig(hidden_size=4, dropout=0.0),
        train=TrainConfig(batch_size=8, window=3, chunk_size=20, epochs=1),
    )
    reg = default_registry()
    steps_before = reg.counter("train_steps_total", phase="train").value
    epochs_before = reg.counter("train_epochs_total").value

    from fmda_tpu.train.trainer import Trainer

    trainer = Trainer(cfg.model, cfg.train)
    trainer.fit(wh, bid_levels=fc.bid_levels, ask_levels=fc.ask_levels)
    assert reg.counter("train_steps_total",
                       phase="train").value > steps_before
    assert reg.counter("train_epochs_total").value == epochs_before + 1
    assert reg.histogram("train_epoch_seconds").n >= 1


# ---------------------------------------------------------------------------
# status CLI
# ---------------------------------------------------------------------------


def test_status_cli_local_snapshot(capsys):
    from fmda_tpu.cli import main

    assert main(["status"]) == 0
    out = capsys.readouterr().out
    assert "status: ok" in out
    assert "warehouse" in out and "bus" in out
    assert "engine_emitted_total" in out


def test_status_cli_down_endpoint_fails_cleanly(capsys):
    from fmda_tpu.cli import main

    # nothing listens on a fresh ephemeral port: clean error, exit 2
    import socket

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    assert main(["status", "--endpoint", f"127.0.0.1:{port}"]) == 2
    err = capsys.readouterr().err
    assert "cannot scrape" in err


def test_status_cli_scrapes_running_endpoint(capsys):
    from fmda_tpu.cli import main

    app = _obs_app()
    _feed_synthetic(app)
    server = app.observability.start_server(port=0)
    try:
        assert main(["status", "--endpoint",
                     f"127.0.0.1:{server.port}"]) == 0
        out = capsys.readouterr().out
        assert "status: ok" in out
        assert "engine_emitted_total" in out
        # degraded endpoint -> nonzero exit, detail still printed
        app.warehouse.close()
        assert main(["status", "--endpoint",
                     f"127.0.0.1:{server.port}"]) == 1
        out = capsys.readouterr().out
        assert "status: degraded" in out
        assert "FAIL" in out
    finally:
        app.observability.close()


def test_bus_publish_counter_created_on_first_touch():
    """A topic that misses both the bind_metrics snapshot and
    add_topic's counter creation (the concurrent-join race) must be
    counted on first publish, never KeyError the hot path."""
    from fmda_tpu.obs import MetricsRegistry
    from fmda_tpu.stream.bus import InProcessBus

    reg = MetricsRegistry()
    bus = InProcessBus(("a",))
    bus.bind_metrics(reg)
    bus.add_topic("late")
    # simulate the lost-counter interleaving (bind_metrics snapshot
    # taken before add_topic, add_topic seeing no counter dict yet)
    bus._publish_counters.pop("late")
    bus.publish("late", {"x": 1})
    bus.publish_many("late", [{"x": 2}, {"x": 3}])
    assert reg.counter("bus_published_total", topic="late").value == 3
