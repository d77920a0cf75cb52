"""The real cross-process fleet topology (ISSUE 6 acceptance, small).

Spawns actual worker processes via the local launcher — worker-hosted
data buses, SocketBus control — runs a synthetic load through the
router, and checks the acceptance surface end to end: every tick
answered in per-session order, per-worker compile counts stable, and
the per-process trace files stitching into single cross-process
journeys via ``trace --merge`` on the topology's trace directory.
Kept deliberately small (one worker, short load): how the topology
scales is not measured (local workers are forced onto the CPU).
"""

import json
import subprocess
import sys

import pytest


def _spawn_ok():
    try:
        return subprocess.run(
            [sys.executable, "-c", "pass"], timeout=60,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        ).returncode == 0
    except Exception:
        return False


pytestmark = pytest.mark.skipif(
    not _spawn_ok(), reason="subprocess spawn unavailable")


def test_local_topology_end_to_end_with_trace_merge(tmp_path):
    from fmda_tpu.cli import main
    from fmda_tpu.fleet.launcher import launch_local_fleet
    from fmda_tpu.obs.trace import configure_tracing, default_tracer
    from fmda_tpu.runtime import FleetLoadConfig, run_fleet_load

    trace_dir = tmp_path / "traces"
    configure_tracing(enabled=True, sample_rate=1.0)
    try:
        topo = launch_local_fleet(
            n_workers=1, hidden=8, capacity_per_worker=16,
            bucket_sizes=(4, 16), seed=0, trace_dir=str(trace_dir),
            wait_timeout_s=240.0)
        try:
            out = run_fleet_load(topo.router, FleetLoadConfig(
                n_sessions=8, n_ticks=12, seed=0))
        finally:
            stats = topo.shutdown()
        # router-side trace file completes the per-process set
        with open(trace_dir / "router.json", "w") as fh:
            json.dump(default_tracer().chrome(), fh)
    finally:
        configure_tracing(enabled=False)

    # every tick answered, exactly once, across the process boundary
    assert out["ticks_served"] == out["ticks_submitted"] == 96
    counters = out["counters"]
    assert counters.get("results_missing", 0) == 0
    assert counters.get("results_unmatched", 0) == 0
    # worker stats rode the goodbye; no recompiles happened mid-load
    assert stats["w0"]["ticks_served"] == 96
    assert stats["w0"]["compile_count"] == 2

    # the topology's trace directory merges in ONE command (satellite):
    # point --merge at the DIRECTORY, not an explicit file list
    merged = tmp_path / "merged.json"
    rc = main(["trace", "--merge", str(trace_dir),
               "--out", str(merged)])
    assert rc == 0
    doc = json.loads(merged.read_text())
    # cross-process journeys: one trace id carries the router's root +
    # route span AND the worker's serve/queued/dispatch/... spans
    by_trace = {}
    for ev in doc["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        tid = ev["args"]["trace_id"]
        by_trace.setdefault(tid, set()).add(ev["name"])
    stitched = [
        names for names in by_trace.values()
        if "tick" in names and "serve" in names and "route" in names
    ]
    assert stitched, "no cross-process journey stitched"
    assert {"queued", "dispatch", "device", "publish"} <= stitched[0]


def test_worker_role_cli_requires_connect_args(capsys):
    from fmda_tpu.cli import main

    rc = main(["serve-fleet", "--role", "worker"])
    assert rc == 2
    assert "--worker-id" in capsys.readouterr().err


def test_shared_broker_kafka_topology_end_to_end(monkeypatch):
    """ROADMAP (d): the `--shared-bus` topology over KafkaBus, end to
    end through open/tick/migrate/close — router and both workers each
    hold their OWN KafkaBus client against one (fake, protocol-faithful)
    broker, exactly the external-broker deployment shape.  The late
    worker's inbox topic is created dynamically (`add_topic` — ROADMAP
    (c) on the Kafka side), migration state crosses the broker, and the
    per-session streams stay complete and ordered."""
    import numpy as np

    import fake_kafka

    fake_kafka.reset()
    monkeypatch.setitem(sys.modules, "kafka", fake_kafka)
    try:
        from fmda_tpu.config import DEFAULT_TOPICS, FleetTopologyConfig, \
            RuntimeConfig, fleet_topics
        from fmda_tpu.fleet.router import FleetRouter
        from fmda_tpu.fleet.worker import FleetWorker
        from fmda_tpu.stream.kafka_bus import KafkaBus
        from test_fleet import FakeClock, _setup

        clock = FakeClock()
        feats, window = 6, 4
        cfg, params = _setup(feats=feats, window=window)
        fleet_cfg = FleetTopologyConfig(
            heartbeat_interval_s=0.0, heartbeat_timeout_s=50.0)
        rc = RuntimeConfig(capacity=8, window=window, bucket_sizes=(1,),
                           max_linger_ms=0.0, pipeline_depth=0)
        # launch-time topics cover only w0 — w1 joins beyond the set
        topics = tuple(DEFAULT_TOPICS) + fleet_topics(["w0"])
        servers = ("broker:9092",)

        def bus():
            return KafkaBus(topics, servers=servers)

        router = FleetRouter(bus(), fleet_cfg, n_features=feats,
                             clock=clock)
        w0 = FleetWorker("w0", bus(), cfg, params, config=fleet_cfg,
                         runtime=rc, clock=clock, precompile=False)
        w0.start()
        router.pump()
        assert router.membership.live() == ["w0"]

        rng = np.random.default_rng(0)
        sids = [f"T{i}" for i in range(4)]
        got = {}

        def cycle(workers):
            router.pump()
            for w in workers:
                if not w.stopped:
                    w.step()
            for res in router.pump():
                got.setdefault(res.session_id, []).append(res)

        for sid in sids:
            router.open_session(sid)
        n_rounds = 10
        live = [w0]
        for r in range(n_rounds):
            if r == 4:
                # w1 joins mid-run: its inbox topic is NOT in the
                # launch-time set — FleetWorker/router create it via
                # add_topic (Kafka brokers auto-create; the adapter
                # widens its configured set)
                w1 = FleetWorker("w1", bus(), cfg, params,
                                 config=fleet_cfg, runtime=rc,
                                 clock=clock, precompile=False)
                live.append(w1)
                w1.start()
                router.pump()  # join -> rebalance -> drains enqueued
            for sid in sids:
                router.submit(sid, rng.normal(size=feats).astype(
                    np.float32))
            cycle(live)
        for _ in range(8):
            cycle(live)

        counters = router.metrics.counters
        assert counters["migrations_completed"] >= 1
        assert counters.get("sessions_lost_state", 0) == 0
        assert counters.get("results_missing", 0) == 0
        moved = [s for s in sids if router.table.owner_of(s) == "w1"]
        assert moved  # the rebalance actually used the new worker
        for sid in sids:
            seqs = [r_.seq for r_ in got[sid]]
            assert seqs == list(range(n_rounds)), (sid, seqs)

        # close everything; the workers release their slots
        for sid in sids:
            router.close_session(sid)
        for _ in range(3):
            cycle(live)
        assert all(w.pool.n_active == 0 for w in live)
    finally:
        fake_kafka.reset()
