"""fmda_tpu.obs.trace — end-to-end tick tracing (ISSUE 4).

Covers the acceptance surface: trace-context round-trip through every
bus backend (including ``publish_many``), Perfetto ``trace_event``
schema validity (``ph``/``ts``/``dur``/``pid``/``tid``, monotonic
timestamps), span-ring eviction under overflow, the zero-allocation
no-op path with tracing disabled, the fleet gateway's ≥5-stage traces
with tiling children (stage breakdown sums to e2e), engine/serve trace
propagation, EventLog ``trace_id`` stamping + ``/events?trace_id=``
filtering, the ``/trace`` endpoint, the MetricsServer 500-with-JSON
regression, and the persistent cross-pump overlap pipeline.
"""

import json
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fmda_tpu.config import (
    DEFAULT_TOPICS,
    FeatureConfig,
    ModelConfig,
    TOPIC_DEEP,
    TOPIC_FLEET_PREDICTION,
    TOPIC_IND,
    TOPIC_PREDICT_TIMESTAMP,
    TOPIC_VIX,
    TOPIC_VOLUME,
)
from fmda_tpu.obs import EventLog, MetricsRegistry, MetricsServer
from fmda_tpu.obs import trace as trace_mod
from fmda_tpu.obs.trace import (
    Tracer,
    chrome_trace,
    configure_tracing,
    default_tracer,
    format_trace,
    group_chrome_traces,
    parse_wire,
    stamp_message,
)
from fmda_tpu.runtime import BatcherConfig, FleetGateway, SessionPool
from fmda_tpu.stream import InProcessBus


@pytest.fixture
def tracer():
    """Enable the process-default tracer for one test, restore after."""
    tr = configure_tracing(enabled=True, sample_rate=1.0, capacity=4096)
    tr.clear()
    yield tr
    configure_tracing(enabled=False)
    tr.clear()


def _setup_model(feats=6, hidden=5, window=4, seed=0):
    cfg = ModelConfig(hidden_size=hidden, n_features=feats, output_size=4,
                      dropout=0.0, bidirectional=False, use_pallas=False)
    from fmda_tpu.models import build_model

    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(seed)},
                        jnp.zeros((1, window, feats)))["params"]
    return cfg, params


def _fleet(n=4, bucket=4, bus=None, **gw_kwargs):
    cfg, params = _setup_model()
    pool = SessionPool(cfg, params, capacity=n, window=4)
    gw = FleetGateway(
        pool, bus,
        batcher_config=BatcherConfig(bucket_sizes=(bucket,),
                                     max_linger_s=0.0),
        **gw_kwargs)
    for i in range(n):
        gw.open_session(f"T{i}")
    return cfg, gw


# ---------------------------------------------------------------------------
# in-band context round-trip through every bus backend
# ---------------------------------------------------------------------------


def test_trace_context_round_trips_through_inprocess_bus(tracer):
    bus = InProcessBus(("t",))
    with tracer.root("session_tick", "ingest") as root:
        bus.publish("t", {"x": 1})
        bus.publish_many("t", [{"x": 2}, {"x": 3, "trace": "own:ctx"}])
    recs = bus.consumer("t").poll()
    assert len(recs) == 3
    wire = recs[0].value["trace"]
    assert parse_wire(wire) == (root.trace_id, root.span_id)
    # publish_many: unstamped messages inherit the active context,
    # pre-stamped ones (the gateway's per-tick contexts) keep their own
    assert recs[1].value["trace"] == wire
    assert recs[2].value["trace"] == "own:ctx"


def test_trace_context_round_trips_through_native_bus(tracer):
    from fmda_tpu.stream.native_bus import NativeBus, native_available

    if not native_available():
        pytest.skip("native toolchain unavailable")
    bus = NativeBus(("t",))
    with tracer.root("session_tick", "ingest") as root:
        bus.publish("t", {"x": 1})
        bus.publish_many("t", [{"x": 2}])
    recs = bus.consumer("t").poll()
    want = f"{root.trace_id}:{root.span_id}"
    assert [r.value["trace"] for r in recs] == [want, want]


def test_trace_context_round_trips_through_kafka_bus(tracer, monkeypatch):
    import fake_kafka

    fake_kafka.reset()
    monkeypatch.setitem(sys.modules, "kafka", fake_kafka)
    from fmda_tpu.stream.kafka_bus import KafkaBus

    bus = KafkaBus(("t",))
    with tracer.root("session_tick", "ingest") as root:
        bus.publish("t", {"x": 1})
        bus.publish_many("t", [{"x": 2}, {"x": 3}])
    recs = bus.read("t", 0)
    want = f"{root.trace_id}:{root.span_id}"
    assert [r.value["trace"] for r in recs] == [want] * 3


# ---------------------------------------------------------------------------
# the no-op path: disabled tracing is one branch, zero allocation
# ---------------------------------------------------------------------------


def test_disabled_tracer_is_zero_allocation_noop():
    tr = Tracer(enabled=False)
    # the refs/context-managers handed out are shared singletons
    assert tr.maybe_trace() is None
    assert tr.root("a", "ingest") is tr.root("b", "bus")
    assert tr.span("a", "ingest") is tr.span("b", "bus")
    with tr.span("a", "ingest"):
        pass  # enter/exit are no-ops
    assert tr.spans() == []
    assert tr.recorded == 0
    assert tr.families() == {"counters": [], "gauges": [], "histograms": []}


def test_disabled_tracing_stamp_returns_caller_dict_unchanged():
    configure_tracing(enabled=False)
    msg = {"x": 1}
    assert stamp_message(msg) is msg  # no copy on the disabled path


def test_unsampled_ticks_are_not_traced(tracer):
    tracer.configure(sample_rate=0.0)
    assert tracer.maybe_trace() is None
    assert tracer.root("t", "ingest") is tracer.root("t", "ingest")
    assert tracer.recorded == 0


# ---------------------------------------------------------------------------
# span ring: bounded, oldest-evicting
# ---------------------------------------------------------------------------


def test_span_ring_evicts_oldest_under_overflow():
    tr = Tracer(enabled=True, sample_rate=1.0, capacity=8)
    for i in range(20):
        tr.add_span(f"trace{i}", None, f"s{i}", "engine", 0, 10)
    spans = tr.spans()
    assert len(spans) == 8
    assert [s.name for s in spans] == [f"s{i}" for i in range(12, 20)]
    assert tr.recorded == 20  # total ever recorded still counted


# ---------------------------------------------------------------------------
# Perfetto trace_event schema
# ---------------------------------------------------------------------------


def test_chrome_export_schema_and_monotonic_ts(tracer):
    with tracer.root("tick", "ingest"):
        with tracer.span("inner", "bus"):
            pass
    doc = json.loads(json.dumps(tracer.chrome()))  # JSON-serialisable
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert events, "no complete events exported"
    for e in events:
        for field in ("name", "cat", "ph", "ts", "dur", "pid", "tid"):
            assert field in e, f"missing {field}"
        assert e["ph"] == "X"
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["args"]["trace_id"] and e["args"]["span_id"]
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts), "ts must be monotonic"
    # metadata names the per-stage lanes
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} >= {"stage:ingest", "stage:bus"}


# ---------------------------------------------------------------------------
# fleet gateway traces: >=5 stages, tiling children, sum == e2e
# ---------------------------------------------------------------------------


def test_fleet_trace_has_five_stages_nested_and_summing(tracer):
    bus = InProcessBus(DEFAULT_TOPICS)
    cfg, gw = _fleet(n=4, bucket=4, bus=bus)
    rng = np.random.default_rng(0)
    for k in range(3):
        for i in range(4):
            gw.submit(f"T{i}", rng.normal(size=cfg.n_features))
        gw.pump()
    gw.drain()

    traces = group_chrome_traces(tracer.chrome())
    assert len(traces) == 12  # every tick sampled at 100%
    by_trace = tracer.traces()
    for t in traces:
        spans = by_trace[t["trace_id"]]
        stages = {s.stage for s in spans}
        assert stages >= {"ingest", "gateway", "engine", "publish", "bus"}
        root = next(s for s in spans if s.parent_id is None)
        assert root.name == "tick"
        # parent-child nesting is consistent: every child sits inside
        # its parent's interval
        by_id = {s.span_id: s for s in spans}
        for s in spans:
            if s.parent_id is None:
                continue
            parent = by_id[s.parent_id]
            assert s.t0_ns >= parent.t0_ns - 1
            assert s.t0_ns + s.dur_ns <= parent.t0_ns + parent.dur_ns + 1
        # the root's direct children tile it: breakdown sums to e2e
        child_sum = sum(dur for _, _, _, dur in t["stages"])
        assert child_sum == pytest.approx(t["e2e_ms"], rel=0.05)
    # the result messages carry each tick's own context in-band
    msgs = bus.consumer(TOPIC_FLEET_PREDICTION).poll()
    assert len(msgs) == 12
    trace_ids = {parse_wire(m.value["trace"])[0] for m in msgs}
    assert trace_ids == {t["trace_id"] for t in traces}


def test_trace_cli_reports_slowest_breakdown(tracer, tmp_path, capsys):
    from fmda_tpu.cli import main

    cfg, gw = _fleet(n=2, bucket=2)
    rng = np.random.default_rng(1)
    for i in range(2):
        gw.submit(f"T{i}", rng.normal(size=cfg.n_features))
    gw.drain()
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(tracer.chrome()))
    assert main(["trace", "--input", str(path),
                 "--slowest", "1"]) == 0
    out = capsys.readouterr().out
    assert "root=tick" in out
    assert "queued" in out and "dispatch" in out and "publish" in out
    # the printed per-stage sum is within +-5% of e2e
    pct = float(out.rsplit("= ", 1)[1].split("%")[0])
    assert 95.0 <= pct <= 105.0


def test_format_trace_share_column_sums(tracer):
    tr_id = "t" * 16
    root = tracer.add_span(tr_id, None, "tick", "ingest", 0, 10_000_000)
    tracer.add_span(tr_id, root, "queued", "gateway", 0, 4_000_000)
    tracer.add_span(tr_id, root, "publish", "publish", 4_000_000, 10_000_000)
    t = group_chrome_traces(tracer.chrome())[0]
    text = format_trace(t)
    assert "e2e=10.000ms" in text
    assert "stages sum 10.000ms = 100.0% of e2e" in text


# ---------------------------------------------------------------------------
# engine + serve: the app-path journey stitches into the producer's trace
# ---------------------------------------------------------------------------


def _minimal_features():
    return FeatureConfig(get_cot=False, get_vix=True, get_stock_volume=None)


def _feed_messages(fc, ts="2020-02-07 10:00:00"):
    deep = {"Timestamp": ts}
    for i in range(fc.bid_levels):
        deep[f"bids_{i}"] = {f"bid_{i}": 100.0 + i, f"bid_{i}_size": 5.0}
    for i in range(fc.ask_levels):
        deep[f"asks_{i}"] = {f"ask_{i}": 101.0 + i, f"ask_{i}_size": 4.0}
    vix = {"Timestamp": ts, "VIX": 15.0}
    ind = {"Timestamp": ts}
    for event in fc.event_list_repl:
        ind[event] = {v: 0.0 for v in
                      ("Actual", "Prev_actual_diff", "Forc_actual_diff")}
    return deep, vix, ind


def test_engine_propagates_trace_to_signal_and_serve(tracer):
    from fmda_tpu.stream import StreamEngine, Warehouse
    from fmda_tpu.stream.warehouse import WarehouseConfig

    fc = _minimal_features()
    bus = InProcessBus(DEFAULT_TOPICS)
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    engine = StreamEngine(bus, wh, fc)
    deep, vix, ind = _feed_messages(fc)
    with tracer.root("session_tick", "ingest") as root:
        bus.publish(TOPIC_DEEP, deep)
        bus.publish(TOPIC_VIX, vix)
        bus.publish(TOPIC_IND, ind)
    assert engine.step() == 1
    # the signal carries the producer's context onward
    sig = bus.consumer(TOPIC_PREDICT_TIMESTAMP).poll()
    assert len(sig) == 1
    assert parse_wire(sig[0].value["trace"]) == (root.trace_id, root.span_id)
    # engine stages landed as spans on the producer's trace
    spans = tracer.traces()[root.trace_id]
    names = {s.name: s.stage for s in spans}
    assert names["join"] == "engine"
    assert names["land"] == "warehouse"
    assert names["signal"] == "bus"
    assert "http_get" not in names  # no transport in this test
    assert {s.name for s in spans} >= {
        "session_tick", "bus_publish", "join", "land", "signal"}


def test_engine_trace_survives_checkpoint_restore(tracer, tmp_path):
    """A polled-but-unjoined traced book row keeps its context across a
    checkpoint/restore cycle (the trace stitches even through a crash)."""
    from fmda_tpu.stream import StreamEngine, Warehouse
    from fmda_tpu.stream.warehouse import WarehouseConfig

    fc = _minimal_features()
    ckpt = str(tmp_path / "engine.json")
    bus = InProcessBus(DEFAULT_TOPICS)
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    engine = StreamEngine(bus, wh, fc, checkpoint_path=ckpt)
    deep, vix, ind = _feed_messages(fc)
    with tracer.root("session_tick", "ingest") as root:
        bus.publish(TOPIC_DEEP, deep)  # book row only: join must wait
    assert engine.step() == 0
    engine.checkpoint()
    engine2 = StreamEngine(bus, wh, fc, checkpoint_path=ckpt)
    bus.publish(TOPIC_VIX, vix)
    bus.publish(TOPIC_IND, ind)
    assert engine2.step() == 1
    sig = bus.consumer(TOPIC_PREDICT_TIMESTAMP).poll()
    assert parse_wire(sig[0].value["trace"]) == (root.trace_id, root.span_id)


# ---------------------------------------------------------------------------
# EventLog stamping + /events filter + /trace endpoint + 500 JSON body
# ---------------------------------------------------------------------------


def test_event_log_stamps_active_trace_id(tracer):
    events = EventLog(capacity=16)
    events.emit("before.any_trace")
    with tracer.root("tick", "ingest") as root:
        events.emit("inside.trace", detail=1)
    events.emit("after.trace")
    ring = events.tail()
    assert "trace_id" not in ring[0] and "trace_id" not in ring[2]
    assert ring[1]["trace_id"] == root.trace_id
    assert events.tail(trace_id=root.trace_id) == [ring[1]]
    assert events.to_jsonl(trace_id="nope") == ""


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read()


def test_server_trace_endpoint_and_events_filter(tracer):
    events = EventLog(capacity=16)
    with tracer.root("tick", "ingest") as root:
        events.emit("traced.event")
    events.emit("untraced.event")
    server = MetricsServer(
        MetricsRegistry(), events=events, tracer=tracer).start()
    try:
        status, body = _get(server.url + "/trace")
        assert status == 200
        doc = json.loads(body)
        assert any(
            e.get("args", {}).get("trace_id") == root.trace_id
            for e in doc["traceEvents"] if e["ph"] == "X")
        status, body = _get(
            server.url + f"/events?trace_id={root.trace_id}")
        lines = [json.loads(x) for x in body.decode().splitlines()]
        assert [e["kind"] for e in lines] == ["traced.event"]
        status, body = _get(server.url + "/events")
        assert len(body.decode().splitlines()) == 2
    finally:
        server.stop()


def test_server_returns_json_500_on_collector_exception():
    """Regression (ISSUE 4 satellite): a snapshot that cannot be
    serialised must yield a clean HTTP 500 with a JSON error body — not
    a half-written response — and the serving thread survives."""
    reg = MetricsRegistry()
    # a collector returning an unserialisable value: registry.snapshot()
    # keeps it (collectors may legally return any Sample fields), then
    # json.dumps inside the handler blows up
    reg.register_collector(
        "broken",
        lambda: {"gauges": [
            {"name": "bad", "labels": {}, "value": object()}]},
    )
    server = MetricsServer(reg).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            _get(server.url + "/snapshot")
        err = exc_info.value
        assert err.code == 500
        assert err.headers.get("Content-Type") == "application/json"
        body = json.loads(err.read())
        assert "error" in body and body["path"] == "/snapshot"
        # the thread survives: a good route still answers
        status, _ = _get(server.url + "/healthz")
        assert status == 200
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# attribution table + e2e histogram on the snapshot surface
# ---------------------------------------------------------------------------


def test_tracer_families_surface_attribution_and_e2e(tracer):
    cfg, gw = _fleet(n=2, bucket=2)
    rng = np.random.default_rng(2)
    for i in range(2):
        gw.submit(f"T{i}", rng.normal(size=cfg.n_features))
    gw.drain()
    fam = tracer.families()
    hists = {h["name"] for h in fam["histograms"]}
    assert "e2e_tick_seconds" in hists
    stages = {c["labels"]["stage"] for c in fam["counters"]
              if c["name"] == "trace_stage_seconds_total"}
    assert stages >= {"tick", "queued", "dispatch", "device", "publish"}
    assert tracer.e2e.n == 2


def test_app_snapshot_includes_tracing_collector(tracer):
    from fmda_tpu.app import Application
    from fmda_tpu.config import FrameworkConfig

    from fmda_tpu.obs.trace import TraceRef

    app = Application(FrameworkConfig())
    try:
        tracer.finish_root(
            TraceRef("t" * 16, "s" * 16, 0), "tick", "ingest", 1_000_000)
        snap = app.observability.snapshot()
        names = {c["name"] for c in snap["counters"]}
        assert "trace_stage_seconds_total" in names
        assert any(h["name"] == "e2e_tick_seconds"
                   for h in snap["histograms"])
    finally:
        app.close()


# ---------------------------------------------------------------------------
# persistent cross-pump overlap pipeline (ROADMAP runtime follow-up)
# ---------------------------------------------------------------------------


def test_overlap_pipeline_persists_across_pumps():
    """Single-flush-per-pump traffic (the steady-state serving loop)
    overlaps too: round k's pump dispatches flush k and completes flush
    k-1 — overlapped_flushes counts every consecutive round."""
    cfg, gw = _fleet(n=3, bucket=4)
    rng = np.random.default_rng(3)
    rounds, served = 5, []
    for k in range(rounds):
        for i in range(3):
            gw.submit(f"T{i}", rng.normal(size=cfg.n_features))
        served.append(len(gw.pump()))
    served.append(len(gw.drain()))
    # first pump only dispatches; each later pump returns the previous
    # round's results; drain returns the final round's
    assert served == [0, 3, 3, 3, 3, 3]
    assert gw.metrics.counters["overlapped_flushes"] == rounds - 1
    assert gw.metrics.counters["ticks_served"] == 3 * rounds


def test_serial_gateway_keeps_same_call_results():
    """pipeline_depth=0 (--serial) stays the strict same-call reference."""
    cfg, gw = _fleet(n=3, bucket=4, pipeline_depth=0)
    rng = np.random.default_rng(4)
    for k in range(3):
        for i in range(3):
            gw.submit(f"T{i}", rng.normal(size=cfg.n_features))
        assert len(gw.pump()) == 3
    assert gw.metrics.counters.get("overlapped_flushes", 0) == 0


def test_close_while_in_flight_across_pumps_drops_stale_result():
    """The persistent pipeline opens a close_session window between
    dispatch and completion; a session closed (and even reopened — seq
    restarts at 0) in that window must not have the dead incarnation's
    result published with a colliding (session, seq)."""
    bus = InProcessBus(DEFAULT_TOPICS)
    cfg, gw = _fleet(n=2, bucket=2, bus=bus)
    rng = np.random.default_rng(6)
    for i in range(2):
        gw.submit(f"T{i}", rng.normal(size=cfg.n_features))
    assert gw.pump() == []          # flush dispatched, in flight
    gw.close_session("T1")          # ...and closed mid-flight
    gw.open_session("T1")           # same id reopened: seq restarts
    res = gw.pump()                 # idle pump completes the flush
    assert [r.session_id for r in res] == ["T0"]
    assert gw.metrics.counters["stale_results_dropped"] == 1
    assert gw.metrics.counters["ticks_served"] == 1
    msgs = bus.consumer(TOPIC_FLEET_PREDICTION).poll()
    assert [m.value["session"] for m in msgs] == ["T0"]
    # the new incarnation's stream starts cleanly at seq 0
    assert gw.submit("T1", rng.normal(
        size=cfg.n_features).astype(np.float32)) == 0


def test_e2e_histogram_counts_only_journey_closing_roots(tracer):
    """Context-manager roots (session_tick) close before downstream
    stages attach, so they must NOT feed e2e_tick_seconds — only
    finish_root-closed journeys (fleet ticks) do; and the grouped
    trace's e2e covers the late-attached spans (journey extent)."""
    with tracer.root("session_tick", "ingest") as root:
        pass
    assert tracer.e2e.n == 0  # ingest root alone: no e2e sample
    # a downstream stage attaches 5ms of work 10ms after the root closed
    spans = tracer.spans()
    root_span = next(s for s in spans if s.parent_id is None)
    tracer.add_span(root_span.trace_id, root_span.span_id, "join",
                    "engine", root_span.t0_ns + 10_000_000,
                    root_span.t0_ns + 15_000_000)
    t = group_chrome_traces(tracer.chrome())[0]
    assert t["e2e_ms"] == pytest.approx(15.0, rel=0.05)  # extent, not
    # the (sub-ms) root duration — shares in the report stay <= 100%
    for _, _, offset_ms, dur_ms in t["stages"]:
        assert offset_ms + dur_ms <= t["e2e_ms"] * 1.01


def test_idle_pump_flushes_the_persistent_pipeline():
    """A pump with nothing to dispatch completes the leftover in-flight
    flush: result latency is bounded by the pump cadence, not by the
    arrival of more traffic."""
    cfg, gw = _fleet(n=2, bucket=2)
    rng = np.random.default_rng(5)
    for i in range(2):
        gw.submit(f"T{i}", rng.normal(size=cfg.n_features))
    assert gw.pump() == []          # dispatched, in flight
    assert len(gw.pump()) == 2      # idle pump -> pipeline flushed
    assert gw.pump() == []          # nothing left


# ---------------------------------------------------------------------------
# cross-process trace merge (ISSUE 5: ROADMAP trace follow-up)
# ---------------------------------------------------------------------------


def _doc_with(trace_id, spans, pid=1):
    """A minimal per-process trace_event doc: spans = [(name, parent_id
    or None, span_id, ts_us, dur_us)]."""
    return {"traceEvents": [
        {"name": n, "cat": "serve", "ph": "X", "ts": ts, "dur": dur,
         "pid": pid, "tid": 1,
         "args": {"trace_id": trace_id, "span_id": sid, "parent_id": par}}
        for n, par, sid, ts, dur in spans
    ], "displayTimeUnit": "ms"}


def test_merge_chrome_traces_aligns_shared_journeys():
    """Two processes' span rings (each on its own perf_counter epoch)
    stitch into one trace per trace id: the consumer process's spans
    land under the producer's root after the timeline alignment."""
    from fmda_tpu.obs.trace import merge_chrome_traces

    tid = "a" * 16
    # producer: root at ts=1000, publish child
    producer = _doc_with(tid, [
        ("tick", None, "root1", 1000.0, 500.0),
        ("bus_publish", "root1", "p1", 1200.0, 100.0),
    ], pid=1)
    # consumer process: serve span on the SAME trace, its epoch wildly
    # different (its perf_counter started elsewhere)
    consumer = _doc_with(tid, [
        ("serve", "root1", "s1", 9_000_000.0, 200.0),
    ], pid=2)
    merged = merge_chrome_traces([producer, consumer])
    evs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert len(evs) == 3
    # alignment: the consumer's earliest span for the shared trace now
    # starts at the producer's earliest (offset = 1000 - 9_000_000)
    serve = next(e for e in evs if e["name"] == "serve")
    assert serve["ts"] == 1000.0
    # and the grouped view shows one journey with the serve stage
    traces = group_chrome_traces(merged)
    assert len(traces) == 1
    assert traces[0]["root"] == "tick"
    assert {s[0] for s in traces[0]["stages"]} == {"bus_publish", "serve"}


def test_merge_without_shared_traces_concatenates():
    from fmda_tpu.obs.trace import merge_chrome_traces

    a = _doc_with("a" * 16, [("tick", None, "r1", 100.0, 10.0)], pid=1)
    b = _doc_with("b" * 16, [("tick", None, "r2", 777.0, 10.0)], pid=2)
    merged = merge_chrome_traces([a, b])
    evs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert {e["ts"] for e in evs} == {100.0, 777.0}  # unshifted
    assert len(group_chrome_traces(merged)) == 2


def test_trace_cli_merge_writes_and_reports(tmp_path, capsys):
    from fmda_tpu.cli import main

    tid = "c" * 16
    p1 = tmp_path / "proc1.json"
    p2 = tmp_path / "proc2.json"
    p1.write_text(json.dumps(_doc_with(tid, [
        ("tick", None, "r1", 1000.0, 400.0)], pid=1)))
    p2.write_text(json.dumps(_doc_with(tid, [
        ("serve", "r1", "s1", 5_000.0, 100.0)], pid=2)))
    out = tmp_path / "merged.json"
    assert main(["trace", "--merge", str(p1), str(p2),
                 "--out", str(out)]) == 0
    merged = json.loads(out.read_text())
    assert len(group_chrome_traces(merged)) == 1
    # without --out: attribution display over the merged doc
    assert main(["trace", "--merge", str(p1), str(p2), "--json"]) == 0
    shown = json.loads(capsys.readouterr().out)
    assert shown[0]["trace_id"] == tid
    assert {s[0] for s in shown[0]["stages"]} == {"serve"}


# ---------------------------------------------------------------------------
# sample-linked exemplars (ISSUE 5: ROADMAP trace follow-up)
# ---------------------------------------------------------------------------


def test_e2e_exemplars_on_snapshot_and_metrics(tracer):
    """finish_root records the last trace id per e2e_tick_seconds
    bucket; /snapshot carries them on the histogram sample and /metrics
    renders OpenMetrics exemplar syntax on the bucketed exposition."""
    from fmda_tpu.obs.prometheus import render_prometheus
    from fmda_tpu.obs.trace import TraceRef, tracer_families

    slow_tid = "f" * 16
    tracer.finish_root(  # ~1 ms journey
        TraceRef("a" * 16, "s1", 0), "tick", "ingest", 1_000_000)
    tracer.finish_root(  # ~100 ms journey — a different bucket
        TraceRef(slow_tid, "s2", 0), "tick", "ingest", 100_000_000)
    fam = tracer_families(tracer)
    e2e = next(h for h in fam["histograms"]
               if h["name"] == "e2e_tick_seconds")
    buckets = e2e["buckets"]
    assert buckets[-1] == {"le": "+Inf", "count": 2}
    with_ex = [b for b in buckets if "exemplar" in b]
    assert {b["exemplar"]["trace_id"] for b in with_ex} == \
        {"a" * 16, slow_tid}
    # cumulative counts are monotone and end at n
    counts = [b["count"] for b in buckets]
    assert counts == sorted(counts) and counts[-1] == 2
    # the slow exemplar's bucket bound brackets its value
    slow = next(b for b in with_ex
                if b["exemplar"]["trace_id"] == slow_tid)
    assert slow["exemplar"]["value_s"] <= slow["le"]

    snap = {"counters": [], "gauges": [], "histograms": [e2e]}
    text = render_prometheus(snap, exemplars=True)
    assert "# TYPE fmda_e2e_tick_seconds histogram" in text
    assert f'# {{trace_id="{slow_tid}"}} 0.1' in text
    assert 'le="+Inf"' in text
    # the DEFAULT (0.0.4) rendering must stay parseable by the legacy
    # text parser: buckets yes, exemplar suffix no
    legacy = render_prometheus(snap)
    assert "_bucket" in legacy and "trace_id" not in legacy
    # summary-form histograms (no exemplars) render unchanged
    plain = render_prometheus({"counters": [], "gauges": [], "histograms": [
        {"name": "x_seconds", "labels": {}, "count": 1, "sum_s": 0.5,
         "max_s": 0.5, "p50_s": 0.5, "p99_s": 0.5}]})
    assert 'quantile="0.5"' in plain and "_bucket" not in plain


def test_predictor_gateway_traces_ride_the_signal_journey(tracer):
    """A signal arriving with in-band context gets its batched serving
    spans stitched under a ``serve`` span on the SIGNAL's trace (the
    engine→serve journey); the stage breakdown tiles the serve span."""
    from fmda_tpu.config import WarehouseConfig
    from fmda_tpu.data.normalize import NormParams
    from fmda_tpu.models import build_model
    from fmda_tpu.runtime import PredictorGateway, PredictorPool
    from fmda_tpu.stream import StreamEngine, Warehouse

    sys.path.insert(0, "tests")
    from test_stream import _session_messages, _small_features

    fc = _small_features(get_cot=False)
    bus = InProcessBus(DEFAULT_TOPICS)
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    eng = StreamEngine(bus, wh, fc)
    cfg = ModelConfig(hidden_size=4, n_features=len(wh.x_fields),
                      output_size=4, dropout=0.0, use_pallas=False)
    params = build_model(cfg).init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, 3, cfg.n_features)))["params"]
    norm = NormParams(np.zeros(cfg.n_features, np.float32),
                      np.ones(cfg.n_features, np.float32))
    pool = PredictorPool(cfg, params, norm, window=3)
    gw = PredictorGateway(pool, bus, wh, from_end=False,
                          max_staleness_s=None,
                          batcher_config=BatcherConfig(
                              bucket_sizes=(8,), max_linger_s=0.0))
    for topic, msg in _session_messages(5):
        # each published feed message inside its own root: the book
        # tick's context rides the join and lands on the signal
        with tracer.root("session_tick", "ingest"):
            bus.publish(topic, msg)
    eng.step()  # engine stamps trace context onto the signals
    preds = gw.poll()
    assert len(preds) == 3
    # each served signal's trace now holds a serve span whose children
    # tile it: queued/gather/dispatch/device/publish (+ bus_publish)
    by_trace = tracer.traces()
    served = [spans for spans in by_trace.values()
              if any(s.name == "serve" for s in spans)]
    assert len(served) == 3
    for spans in served:
        serve = next(s for s in spans if s.name == "serve")
        children = [s for s in spans if s.parent_id == serve.span_id]
        names = [s.name for s in children]
        assert names == ["queued", "gather", "dispatch", "device",
                         "publish"]
        tiled = sum(s.dur_ns for s in children)
        assert abs(tiled - serve.dur_ns) <= 0.05 * serve.dur_ns + 10_000
    # the prediction messages carry the signal's context onward
    out = bus.consumer("prediction").poll()
    assert all("trace" in m.value for m in out)


def test_predictor_gateway_bare_signal_gets_own_root(tracer):
    """Signals without in-band context become their own sampled roots,
    closed via finish_root — they feed e2e_tick_seconds."""
    from fmda_tpu.config import WarehouseConfig
    from fmda_tpu.data.normalize import NormParams
    from fmda_tpu.models import build_model
    from fmda_tpu.runtime import PredictorGateway, PredictorPool
    from fmda_tpu.stream import StreamEngine, Warehouse

    sys.path.insert(0, "tests")
    from test_stream import _session_messages, _small_features

    fc = _small_features(get_cot=False)
    bus = InProcessBus(DEFAULT_TOPICS)
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    eng = StreamEngine(bus, wh, fc)
    configure_tracing(enabled=False)
    for topic, msg in _session_messages(5):
        bus.publish(topic, msg)
    eng.step()  # untraced: signals carry no context
    configure_tracing(enabled=True, sample_rate=1.0)
    cfg = ModelConfig(hidden_size=4, n_features=len(wh.x_fields),
                      output_size=4, dropout=0.0, use_pallas=False)
    params = build_model(cfg).init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, 3, cfg.n_features)))["params"]
    norm = NormParams(np.zeros(cfg.n_features, np.float32),
                      np.ones(cfg.n_features, np.float32))
    pool = PredictorPool(cfg, params, norm, window=3)
    gw = PredictorGateway(pool, bus, wh, from_end=False,
                          max_staleness_s=None,
                          batcher_config=BatcherConfig(
                              bucket_sizes=(8,), max_linger_s=0.0))
    before = tracer.e2e.n
    preds = gw.poll()
    assert len(preds) == 3
    assert tracer.e2e.n == before + 3
    roots = [s for s in tracer.spans()
             if s.parent_id is None and s.name == "predict"]
    assert len(roots) == 3


def test_metrics_endpoint_negotiates_openmetrics_exemplars(tracer):
    """/metrics stays 0.0.4-clean by default (the legacy parser fails a
    whole scrape on exemplar syntax); clients that Accept OpenMetrics
    get the exemplar-bearing exposition + EOF terminator."""
    from fmda_tpu.obs.trace import TraceRef, tracer_families

    tracer.finish_root(
        TraceRef("d" * 16, "s1", 0), "tick", "ingest", 2_000_000)
    reg = MetricsRegistry()
    reg.register_collector("tracing", lambda: tracer_families(tracer))
    server = MetricsServer(reg, port=0).start()
    try:
        plain = urllib.request.urlopen(
            f"{server.url}/metrics", timeout=10)
        body = plain.read().decode()
        assert "version=0.0.4" in plain.headers["Content-Type"]
        assert "trace_id" not in body and "# EOF" not in body
        assert "_bucket" in body  # the bucketed form itself is legal

        req = urllib.request.Request(
            f"{server.url}/metrics",
            headers={"Accept": "application/openmetrics-text"})
        om = urllib.request.urlopen(req, timeout=10)
        om_body = om.read().decode()
        assert "openmetrics-text" in om.headers["Content-Type"]
        assert f'# {{trace_id="{"d" * 16}"}}' in om_body
        assert om_body.endswith("# EOF\n")
    finally:
        server.stop()
