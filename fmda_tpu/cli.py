"""Command-line entry points: ``python -m fmda_tpu <command>``.

The reference is operated by hand-running five scripts in order
(producer.py, spark_consumer.py, create_database.py, the training
notebook, predict.py — reference README.md:186-292); here the same
operations are subcommands over one file-backed warehouse:

- ``demo``      synthetic end-to-end proof: corpus → warehouse → train →
                backtest (no network, no accelerator requirements);
- ``ingest``    replay or live-feed a session into a warehouse file;
- ``train``     chunked training over a warehouse file → Orbax checkpoint;
- ``backtest``  serving-equivalent scoring + signal-quality table;
- ``serve``     the prediction daemon (push-triggered, no sleep-15);
- ``status``    pretty-print an observability snapshot (metrics registry
                + health checks), either from a locally built app or
                scraped from a running ``/snapshot`` endpoint;
- ``trace``     inspect recorded tick traces (per-stage latency
                attribution) from a ``--trace-out`` file or a running
                ``/trace`` endpoint;
- ``lint``      framework-aware static analysis over the package
                (lock discipline, jit purity, JAX API drift as a
                zero-baseline hard gate, compat-shim confinement,
                topic cross-checks, hygiene rules); exit 0 = clean
                against the baseline, 1 = new findings, 2 = usage
                error.

Every command is a thin composition of the public library API — anything
the CLI does is one import away in a notebook.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _config(args):
    """FrameworkConfig from --config (JSON), or the defaults."""
    from fmda_tpu.config import FrameworkConfig, load_config

    path = getattr(args, "config", None)
    return load_config(path) if path else FrameworkConfig()


def _select_backend(args) -> None:
    """Apply the one backend rule (:func:`fmda_tpu.utils.env
    .select_backend`) before the command's first jax call: ``--platform
    cpu`` forces the host, a platform pinned from outside is respected,
    anything else requires a TPU and exits non-zero without one.  Also
    places the persistent compile cache."""
    from fmda_tpu.utils.env import select_backend

    select_backend(force_cpu=getattr(args, "platform", None) == "cpu")


def _ckpt_dir(args, cfg) -> str:
    """--checkpoint-dir if passed, else the config's train.checkpoint_dir."""
    return (args.checkpoint_dir if args.checkpoint_dir is not None
            else cfg.train.checkpoint_dir)


def _warehouse(path: str, cfg):
    import dataclasses

    from fmda_tpu.stream import Warehouse

    return Warehouse(
        cfg.features, dataclasses.replace(cfg.warehouse, path=path))


def cmd_demo(args) -> int:
    _select_backend(args)
    from fmda_tpu.data.synthetic import SyntheticMarketConfig, build_corpus

    cfg = _config(args)
    # absent flags fall back to the config file when one is given, else to
    # quick demo defaults
    epochs = args.epochs if args.epochs is not None else (
        cfg.train.epochs if args.config else 2)
    batch_size = args.batch_size if args.batch_size is not None else (
        cfg.train.batch_size if args.config else 32)
    seed = args.seed if args.seed is not None else cfg.train.seed
    wh, stats = build_corpus(
        cfg.features, SyntheticMarketConfig(seed=seed, n_days=args.days))
    print(f"corpus: {len(wh)} rows ({stats})")
    ckpt = _train(wh, cfg, epochs=epochs, batch_size=batch_size,
                  checkpoint_dir=_ckpt_dir(args, cfg), seed=seed)
    if ckpt is None:
        return 2
    # score exactly the checkpoint this demo just trained, never whatever
    # happens to be newest in a shared checkpoint dir
    return _backtest(wh, cfg, ckpt, window=cfg.train.window,
                     threshold=cfg.train.prob_threshold)


def cmd_ingest(args) -> int:
    import dataclasses

    from fmda_tpu.app import Application
    from fmda_tpu.data.synthetic import (
        SyntheticMarketConfig, synthetic_session_messages,
    )

    cfg = _config(args)
    # CLI overrides fold into the config; one composition root builds
    # bus + warehouse + engine exactly as the library API would
    engine_overrides = {
        k: v for k, v in dict(
            checkpoint_path=args.engine_checkpoint,
            checkpoint_every=args.checkpoint_every,
        ).items() if v is not None
    }
    cfg = dataclasses.replace(
        cfg,
        warehouse=dataclasses.replace(cfg.warehouse, path=args.warehouse),
        engine=dataclasses.replace(cfg.engine, **engine_overrides),
    )
    fc = cfg.features
    app = Application(cfg)
    wh, bus, engine = app.warehouse, app.bus, app.engine
    if args.synthetic_days:
        for topic, msg in synthetic_session_messages(
                fc, SyntheticMarketConfig(seed=args.seed,
                                          n_days=args.synthetic_days)):
            bus.publish(topic, msg)
        engine.step()
    elif args.replay:
        ticks = _replay_session(args, cfg, bus)
        print(f"replayed {ticks} session tick(s)", file=sys.stderr)
        if ticks == 0:
            print("0 ticks replayed — check --replay-start against the "
                  "recording's market-calendar date", file=sys.stderr)
            return 2
        engine.step()
    else:
        print("pass --synthetic-days or --replay (a RecordingTransport "
              "fixture file); live ingestion attaches a SessionDriver via "
              "the Application API (docs/OPERATIONS.md §2)", file=sys.stderr)
        return 2
    print(f"warehouse {args.warehouse}: {len(wh)} rows; engine {engine.stats}")
    return 0


def _replay_session(args, cfg, bus) -> int:
    """Re-run a recorded session (RecordingTransport file) through the real
    acquisition layer: same clients/scrapers, responses served back in
    recorded order, clock simulated at the configured cadence."""
    import datetime as dt

    from fmda_tpu.ingest import (
        AlphaVantageClient, COTScraper, EconomicCalendarScraper, IEXClient,
        RecordingTransport, SessionDriver, SessionReplayTransport,
        TradierCalendarClient, VIXScraper,
    )

    transport = SessionReplayTransport(
        RecordingTransport.load_fixtures(args.replay))
    clock = {"now": dt.datetime.strptime(
        args.replay_start, "%Y-%m-%d %H:%M:%S")}

    def now_fn():
        return clock["now"]

    def fast_sleep(s):
        clock["now"] += dt.timedelta(seconds=s)

    sc = cfg.session
    driver = SessionDriver(
        bus, sc,
        iex=IEXClient("replay", transport),
        alpha_vantage=AlphaVantageClient("replay", transport),
        calendar=TradierCalendarClient("replay", transport),
        indicator_scraper=EconomicCalendarScraper(
            cfg.features, transport=transport),
        vix_scraper=VIXScraper(transport),
        cot_scraper=COTScraper(sc.cot_subject, transport),
        now_fn=now_fn, sleep_fn=fast_sleep,
    )
    ticks = driver.run_session(max_ticks=args.ticks or None)
    if transport.misses:
        # the replay ran under a config whose feeds/cadence differ from
        # the recording — the per-feed warnings above say which ticks,
        # this says which endpoints
        print("recording has no responses for: "
              + ", ".join(sorted(set(transport.misses))), file=sys.stderr)
    return ticks


def _save_quality_profile(wh, cfg, ckpt, *, max_rows: int = 4096) -> None:
    """Persist the training-time reference profile beside the checkpoint
    so the live drift monitor (fmda_tpu.obs.quality) has a baseline to
    PSI-score production traffic against.  Best-effort: a profile that
    cannot be built (degenerate data) must not fail training."""
    from fmda_tpu.eval.drift import (
        build_profile, profile_path_for, save_profile)

    try:
        n = len(wh)
        ids = list(range(max(1, n - max_rows + 1), n + 1))
        rows = wh.fetch(ids)
        targets = wh.fetch_targets(ids) if n > cfg.features.max_lead else None
        profile = build_profile(
            rows, targets, bins=cfg.quality.drift_bins,
            columns=list(wh.x_fields))
        path = save_profile(profile_path_for(ckpt), profile)
        print(f"drift reference profile: {path}")
    except (ValueError, IndexError, OSError) as e:
        print(f"drift reference profile not written: {e}", file=sys.stderr)


def _token_source(path: str, cfg):
    """``train --tokens``: a ``.npy`` file of token ids, one packed
    stream, for the families trained on next-token prediction."""
    import numpy as np

    from fmda_tpu.data.source import TokenArraySource

    return TokenArraySource(np.load(path), cfg.model.vocab_size)


def _train(wh, cfg, *, epochs, batch_size, checkpoint_dir, seed):
    """Shared by ``train`` and ``demo``; returns the checkpoint path, or
    None (after printing why) when training cannot run.  ``wh`` is the
    warehouse, or a token source (``train --tokens``): what a family is
    trained on is its task's business (train/tasks.py), and the source
    brings class weights, normalisation and a drift profile only where
    it is a feature table."""
    import dataclasses

    from fmda_tpu.train import Trainer, save_checkpoint
    from fmda_tpu.train.tasks import task_class
    from fmda_tpu.train.trainer import imbalance_weights_from_source
    from fmda_tpu.utils.env import device_report

    if len(wh) == 0:
        print("warehouse is empty — run ingest first", file=sys.stderr)
        return None
    fc = cfg.features
    features = task_class(cfg.model).feature_windows
    model_cfg = (dataclasses.replace(cfg.model, n_features=len(wh.x_fields))
                 if features else cfg.model)
    # explicitly-passed CLI flags override the config file; absent flags
    # (None) leave the config's values in force
    overrides = {k: v for k, v in
                 dict(batch_size=batch_size, epochs=epochs, seed=seed).items()
                 if v is not None}
    train_cfg = dataclasses.replace(cfg.train, **overrides)
    weight, pos_weight = (imbalance_weights_from_source(wh) if features
                          else (None, None))
    trainer = Trainer(model_cfg, train_cfg, weight=weight,
                      pos_weight=pos_weight)
    state, history, dataset = trainer.fit(
        wh, bid_levels=fc.bid_levels, ask_levels=fc.ask_levels)
    ckpt = save_checkpoint(checkpoint_dir, state,
                           trainer.task.norm_params(dataset))
    if features:
        _save_quality_profile(wh, cfg, ckpt)
    last = history["train"][-1]
    dev = device_report()
    print(f"trained {len(history['train'])} epochs: "
          f"loss={last.loss:.4f} acc={last.accuracy:.4f} "
          f"(backend={dev['backend']} device_kind={dev['device_kind']} "
          f"n_devices={dev['n_devices']})")
    print(f"checkpoint: {ckpt}")
    return ckpt


def _continuous_train(wh, cfg, *, checkpoint_dir, max_rounds, seed):
    """``train --continuous``: the standalone continuous fine-tuning
    loop — tail the warehouse, fine-tune on the sliding window, write
    versioned checkpoints (+ drift profiles).  No fleet attached here;
    ``serve-fleet --continuous-train`` is the in-process serving
    variant that also hot-swaps."""
    import dataclasses

    from fmda_tpu.train.continuous import ContinuousTrainer

    if len(wh) == 0:
        print("warehouse is empty — run ingest first", file=sys.stderr)
        return None
    fc = cfg.features
    model_cfg = dataclasses.replace(cfg.model, n_features=len(wh.x_fields))
    train_cfg = (dataclasses.replace(cfg.train, seed=seed)
                 if seed is not None else cfg.train)
    ct = ContinuousTrainer(
        wh, model_cfg, train_cfg,
        checkpoint_dir=checkpoint_dir,
        bid_levels=fc.bid_levels, ask_levels=fc.ask_levels,
        drift_bins=cfg.quality.drift_bins, target_lead=fc.max_lead,
    )
    out = ct.run(max_rounds=max_rounds)
    print(f"continuous train: {out['rounds']} round(s), "
          f"{out['rows_seen']} rows seen, "
          f"{len(out['checkpoints'])} checkpoint(s), "
          f"recompiles={out['trainer_unexpected_recompiles']}")
    for ckpt in out["checkpoints"]:
        print(f"checkpoint: {ckpt}")
    return out


def cmd_train(args) -> int:
    _select_backend(args)
    cfg = _config(args)
    import contextlib

    profile = contextlib.nullcontext()
    if args.jax_profile:
        # the trainer's host spans and named scopes are always compiled
        # in; a capture is what makes them visible (docs/training.md
        # "Profiling a run").  Whole-run capture: keep --epochs small.
        from fmda_tpu.utils.tracing import device_trace

        profile = device_trace(args.jax_profile)
    if bool(args.tokens) == bool(args.warehouse) or (
            args.tokens and args.continuous):
        print("train needs one of --warehouse and --tokens (--continuous "
              "tails a warehouse)", file=sys.stderr)
        return 2
    with profile:
        if args.continuous:
            out = _continuous_train(
                _warehouse(args.warehouse, cfg), cfg,
                checkpoint_dir=_ckpt_dir(args, cfg),
                max_rounds=args.max_rounds, seed=args.seed,
            )
            ok = bool(out and out["rounds"] > 0)
        else:
            source = (_token_source(args.tokens, cfg) if args.tokens
                      else _warehouse(args.warehouse, cfg))
            ok = bool(_train(
                source, cfg, epochs=args.epochs,
                batch_size=args.batch_size,
                checkpoint_dir=_ckpt_dir(args, cfg), seed=args.seed,
            ))
    if args.jax_profile:
        print(f"jax profile captured to {args.jax_profile} (tensorboard "
              "--logdir, or benchmark/tools/span_report.py on its "
              ".xplane.pb)", file=sys.stderr)
    return 0 if ok else 2


def _backtest(wh, cfg, ckpt: str, *, window: int, threshold: float) -> int:
    import dataclasses

    from fmda_tpu.serve import backtest_from_checkpoint, trading_summary

    result = backtest_from_checkpoint(
        wh, ckpt, dataclasses.replace(cfg.model, n_features=len(wh.x_fields)),
        window=window, threshold=threshold)
    m = result.metrics
    print(f"backtest over {len(result.probabilities)} rows: "
          f"accuracy={float(m.accuracy):.3f} hamming={float(m.hamming):.3f}")
    print(f"{'label':>8} {'signals':>8} {'hits':>6} {'precision':>10} "
          f"{'recall':>7} {'edge':>7}")
    for label, s in trading_summary(result).items():
        print(f"{label:>8} {s.signals:>8} {s.hits:>6} {s.precision:>10.3f} "
              f"{s.recall:>7.3f} {s.edge:>+7.3f}")
    return 0


def cmd_backtest(args) -> int:
    _select_backend(args)
    from fmda_tpu.train.checkpoint import latest_checkpoint

    cfg = _config(args)
    ckpt = args.checkpoint or latest_checkpoint(_ckpt_dir(args, cfg))
    if ckpt is None:
        print("no checkpoint found", file=sys.stderr)
        return 2
    return _backtest(
        _warehouse(args.warehouse, cfg), cfg, ckpt,
        window=(args.window if args.window is not None
                else cfg.train.window),
        threshold=(args.threshold if args.threshold is not None
                   else cfg.train.prob_threshold),
    )


def cmd_serve(args) -> int:
    """Tail-follow the warehouse file: another process (ingest) appends
    rows to the same SQLite file; each new row is served through the
    push-triggered predictor (signals synthesised locally — the shared
    medium between processes is the warehouse, like the reference's
    MariaDB between Spark and predict.py, minus the sleep-15 race)."""
    _select_backend(args)
    import time

    import dataclasses

    from fmda_tpu.app import default_bus
    from fmda_tpu.config import TOPIC_PREDICT_TIMESTAMP
    from fmda_tpu.serve import Predictor
    from fmda_tpu.train.checkpoint import latest_checkpoint

    cfg = _config(args)
    window = args.window if args.window is not None else cfg.train.window
    threshold = (args.threshold if args.threshold is not None
                 else cfg.train.prob_threshold)
    wh = _warehouse(args.warehouse, cfg)
    ckpt = args.checkpoint or latest_checkpoint(_ckpt_dir(args, cfg))
    if ckpt is None:
        print("no checkpoint found", file=sys.stderr)
        return 2
    bus = default_bus(cfg)
    predictor = Predictor.from_checkpoint(
        ckpt, bus, wh,
        dataclasses.replace(cfg.model, n_features=len(wh.x_fields)),
        window=window, threshold=threshold,
        from_end=False, max_staleness_s=None)
    served = 0
    last_pos = window - 1 if args.from_start else len(wh)
    deadline = time.monotonic() + args.duration_s if args.duration_s else None
    while True:
        # the cursor is the last row *position* fetched (dense ordinals,
        # immune to autoincrement gaps — warehouse.timestamps_after); a
        # concurrent ingest commit between reads can only appear in the
        # NEXT poll, never twice (rows are append-only)
        new_rows = wh.timestamps_after(last_pos)
        if new_rows:
            for _, ts in new_rows:
                bus.publish(TOPIC_PREDICT_TIMESTAMP, {"Timestamp": ts})
            last_pos = new_rows[-1][0]
            for p in predictor.poll():
                served += 1
                print(json.dumps({
                    "timestamp": p.timestamp,
                    "probabilities": [
                        round(float(v), 4) for v in p.probabilities],
                    "labels": list(p.labels),
                }), flush=True)
        if args.once or (deadline is not None
                         and time.monotonic() >= deadline):
            break
        time.sleep(args.poll_interval_s)
    print(f"served {served} predictions", file=sys.stderr)
    return 0


def _fleet_worker_model(args, cfg):
    """The worker-role model stack: a randomly-initialised
    unidirectional carrier from the shared seed — deterministic, so
    every worker process of one topology serves identical params."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from fmda_tpu.models import build_model

    model_cfg = dataclasses.replace(
        cfg.model, bidirectional=False, dropout=0.0,
        hidden_size=args.hidden, n_features=cfg.features.n_features,
        cell=cfg.model.cell if cfg.model.cell != "attn" else "gru")
    window = args.window if args.window is not None else cfg.runtime.window
    params = build_model(model_cfg).init(
        {"params": jax.random.PRNGKey(args.seed)},
        jnp.zeros((1, window, model_cfg.n_features)))["params"]
    return model_cfg, params


def _fleet_wire_override(args, cfg):
    """Fold the cross-role serve-fleet switches into cfg: binary-wire
    rollback (``--wire-format`` -> [fleet]) and the carried-state cell
    family A/B knob (``--cell``, falling back to ``FMDA_FLEET_CELL`` ->
    [model] cell) — both must work from the command line alone, on
    every role, so a GRU-vs-SSM ticks/s comparison at equal --hidden
    is two invocations of the same command."""
    import dataclasses

    if getattr(args, "wire_format", None):
        cfg = dataclasses.replace(
            cfg, fleet=dataclasses.replace(
                cfg.fleet, wire_format=args.wire_format))
    cell = getattr(args, "cell", None) or os.environ.get("FMDA_FLEET_CELL")
    if cell:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, cell=cell))
    return cfg


def _fleet_runtime_overrides(args, cfg):
    """Fold the shared serve-fleet batching flags into cfg.runtime."""
    import dataclasses

    cfg = _fleet_wire_override(args, cfg)

    bucket_sizes = (tuple(int(b) for b in args.bucket_sizes.split(","))
                    if args.bucket_sizes else None)
    overrides = {
        k: v for k, v in dict(
            capacity=max(args.sessions, cfg.runtime.capacity),
            max_linger_ms=args.max_linger_ms,
            queue_bound=args.queue_bound,
            window=args.window,
            bucket_sizes=bucket_sizes,
            pipeline_depth=(0 if args.serial else None),
            slo_p99_ms=args.slo_p99_ms,
        ).items() if v is not None
    }
    return dataclasses.replace(
        cfg, runtime=dataclasses.replace(cfg.runtime, **overrides))


def _maybe_write_trace(args, out: dict) -> None:
    """Shared --trace/--trace-out tail for every serve-fleet role."""
    if not (args.trace or args.trace_out):
        return
    from fmda_tpu.obs.trace import default_tracer

    tracer = default_tracer()
    out["tracing"] = {
        "traces_finished": tracer.traces_finished,
        "spans_buffered": len(tracer.spans()),
        "e2e": tracer.e2e.summary(),
    }
    if args.trace_out:
        with open(args.trace_out, "w") as fh:
            json.dump(tracer.chrome(), fh)
        out["tracing"]["file"] = args.trace_out


def _cmd_fleet_worker(args) -> int:
    """serve-fleet --role worker: one slot-range owner in a multi-host
    topology (docs/multihost.md).  Connects a SocketBus to the router's
    bus server, joins via hello, and serves its inbox until the router
    says stop (or the --duration-s safety valve fires)."""
    if not args.worker_id or not args.connect:
        print("--role worker needs --worker-id and --connect HOST:PORT",
              file=sys.stderr)
        return 2
    _select_backend(args)
    cfg = _fleet_runtime_overrides(args, _config(args))
    if args.trace or args.trace_out:
        from fmda_tpu.obs.trace import configure_tracing

        configure_tracing(enabled=True, sample_rate=args.trace_sample)
    # apply [profiling] BEFORE the worker builds its pools, so the
    # precompile burst is ledger-tracked under the deployment's
    # cost-analysis setting and the host profiler (if opted in) covers
    # the whole serve
    from fmda_tpu.obs.device import configure_device_obs

    configure_device_obs(cfg.profiling)
    from fmda_tpu.config import TOPIC_FLEET_PREDICTION, fleet_worker_topic
    from fmda_tpu.fleet.wire import BusServer, SocketBus
    from fmda_tpu.fleet.worker import FleetWorker
    from fmda_tpu.obs import Observability
    from fmda_tpu.stream.bus import InProcessBus

    model_cfg, params = _fleet_worker_model(args, cfg)
    wire_format = cfg.fleet.wire_format
    bus = SocketBus.connect(args.connect, wire_format=wire_format)
    data_bus = None
    data_server = None
    data_address = None
    if not args.shared_bus:
        # worker-hosted data plane (default): this process serves its
        # own inbox + results bus; the router links to it directly, so
        # the serving hot loop never crosses a socket
        data_bus = InProcessBus(
            (fleet_worker_topic(args.worker_id), TOPIC_FLEET_PREDICTION))
        data_server = BusServer(
            data_bus, host=cfg.fleet.host,
            wire_format=wire_format).start()
        data_address = data_server.address
    # split-topology workers re-dial the control bus after a router/
    # broker restart (the data plane is local, serving never stops);
    # shared-bus workers exit cleanly after the grace instead — their
    # whole transport is the one broker
    reconnect = (None if args.shared_bus
                 else (lambda: SocketBus.connect(
                     args.connect, wire_format=wire_format)))
    qos = None
    if cfg.control.enabled and cfg.control.tenant_classes:
        from fmda_tpu.control.qos import QosPolicy

        qos = QosPolicy.from_config(cfg.control)
    worker = FleetWorker(
        args.worker_id, bus, model_cfg, params,
        config=cfg.fleet, runtime=cfg.runtime, capacity=args.sessions,
        data_bus=data_bus, data_address=data_address,
        reconnect_fn=reconnect, qos=qos)
    # per-process observability: every series this worker exports
    # carries a `process` label, so a fleet-wide scrape never collides
    obs = Observability(cfg.observability, process=args.worker_id)
    obs.track_fleet(worker.gateway)
    bus.bind_metrics(obs.registry)
    if args.metrics_port is not None:
        server = obs.start_server(port=args.metrics_port)
        # announce the scrape endpoint in every liveness message: the
        # router's fleet aggregator (fmda_tpu.obs.aggregate) scrapes
        # exactly the addresses heartbeats carry
        worker.heartbeater.announce["metrics"] = server.url
        print(f"worker {args.worker_id} metrics: {server.url}/metrics",
              file=sys.stderr)
    try:
        stats = worker.run(
            duration_s=args.duration_s if args.duration_s else None)
    finally:
        obs.close()
        if data_server is not None:
            data_server.stop()
        bus.close()
    out = {"worker": args.worker_id, "stats": stats,
           **worker.metrics.summary()}
    _maybe_write_trace(args, out)
    print(json.dumps(out, indent=2))
    return 0


def _cmd_fleet_broker(args) -> int:
    """serve-fleet --role broker: host the topology's bus + bus server
    and nothing else — the local stand-in for a Kafka broker.  The
    router, workers, and loadgen each keep their own process (and GIL);
    every bus op crosses a socket to here.  Runs until killed or
    --duration-s elapses."""
    import time

    from fmda_tpu.config import DEFAULT_TOPICS, fleet_topics
    from fmda_tpu.fleet.launcher import _build_local_bus
    from fmda_tpu.fleet.wire import BusServer

    # the broker is one connection-serving thread per client, all doing
    # short JSON/frame work: the default 5ms GIL switch interval turns
    # every request into multi-ms queueing delay under concurrency —
    # drop it so round-trip latency tracks actual work
    sys.setswitchinterval(0.0005)
    cfg = _fleet_wire_override(args, _config(args))
    n = args.workers if args.workers is not None else cfg.fleet.n_workers
    worker_ids = [f"{cfg.fleet.worker_prefix}{i}" for i in range(n)]
    topics = tuple(DEFAULT_TOPICS) + fleet_topics(worker_ids)
    bus = _build_local_bus(cfg, topics)
    port = args.listen if args.listen is not None else cfg.fleet.port
    server = BusServer(bus, host=cfg.fleet.host, port=port,
                       wire_format=cfg.fleet.wire_format).start()
    # the one line launchers parse to find the ephemeral port
    print(f"BROKER {server.address}", flush=True)
    deadline = (time.monotonic() + args.duration_s
                if args.duration_s else None)
    try:
        while deadline is None or time.monotonic() < deadline:
            time.sleep(0.2)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
    return 0


def _fleet_telemetry(args, cfg):
    """Router-side fleet telemetry (store + aggregator + SLO engine +
    flight recorder — fmda_tpu.obs.aggregate) for --role router/local,
    or None when the ``[slo]`` section disables it.  ``--postmortem-dir``
    overrides the config so the flight recorder works from the command
    line alone."""
    if not cfg.slo.enabled:
        return None
    import dataclasses

    from fmda_tpu.obs.aggregate import FleetTelemetry

    slo_cfg = cfg.slo
    postmortem = getattr(args, "postmortem_dir", None)
    if postmortem:
        slo_cfg = dataclasses.replace(slo_cfg, postmortem_dir=postmortem)
    return FleetTelemetry(slo_cfg)


def _control_plane(args, cfg, telemetry, *, router=None, actuator=None,
                   initial_linger_ms=None, bucket_sizes=None):
    """The adaptive control plane for --role router/local (fmda_tpu
    .control; docs/control.md): on whenever fleet telemetry is — the
    loops read its signals — unless the ``[control]`` section or
    ``--no-controller`` opts out.  Attached to the telemetry so its
    decision ring serves on ``/control``."""
    if telemetry is None or not cfg.control.enabled:
        return None
    if getattr(args, "no_controller", False):
        return None
    from fmda_tpu.control import ControlPlane

    plane = ControlPlane(
        cfg.control, telemetry=telemetry, router=router,
        actuator=actuator, slo_cfg=cfg.slo,
        initial_linger_ms=(initial_linger_ms if initial_linger_ms
                           is not None else cfg.runtime.max_linger_ms),
        bucket_sizes=tuple(bucket_sizes if bucket_sizes is not None
                           else cfg.runtime.bucket_sizes))
    telemetry.attach_controller(plane)
    return plane


def _tenant_mix(args):
    """Parse ``--tenant-mix gold:1,standard:4`` into the loadgen's
    parallel (classes, weights) tuples; ((), ()) when unset."""
    spec = getattr(args, "tenant_mix", None)
    if not spec:
        return (), ()
    classes, weights = [], []
    for part in spec.split(","):
        name, _, w = part.partition(":")
        if not name.strip():
            raise SystemExit(f"bad --tenant-mix entry: {part!r}")
        classes.append(name.strip())
        try:
            weights.append(float(w) if w else 1.0)
        except ValueError:
            raise SystemExit(
                f"bad --tenant-mix weight in {part!r} "
                "(want CLASS or CLASS:WEIGHT)") from None
    return tuple(classes), tuple(weights)


def _cmd_fleet_router(args) -> int:
    """serve-fleet --role router: the routing/membership/migration
    control loop on a bus-only host (no jax on this code path).  With
    ``--connect`` it joins an existing broker's bus (the production
    shape: broker, router, and workers each their own process/host);
    with ``--listen`` it hosts the bus + bus server itself (a two-tier
    topology for small fleets)."""
    import time

    from fmda_tpu.fleet.router import FleetRouter

    cfg = _fleet_wire_override(args, _config(args))
    if args.trace or args.trace_out:
        from fmda_tpu.obs.trace import configure_tracing

        configure_tracing(enabled=True, sample_rate=args.trace_sample)
    server = None
    if args.connect:
        from fmda_tpu.fleet.wire import SocketBus

        bus = SocketBus.connect(
            args.connect, wire_format=cfg.fleet.wire_format)
        fleet_cfg = cfg.fleet
    else:
        import dataclasses

        from fmda_tpu.config import DEFAULT_TOPICS, fleet_topics
        from fmda_tpu.fleet.launcher import _build_local_bus
        from fmda_tpu.fleet.wire import BusServer

        n = (args.workers if args.workers is not None
             else cfg.fleet.n_workers)
        worker_ids = [f"{cfg.fleet.worker_prefix}{i}" for i in range(n)]
        topics = tuple(DEFAULT_TOPICS) + fleet_topics(worker_ids)
        bus = _build_local_bus(cfg, topics)
        fleet_cfg = dataclasses.replace(
            cfg.fleet,
            port=args.listen if args.listen is not None
            else cfg.fleet.port)
        server = BusServer(bus, host=fleet_cfg.host,
                           port=fleet_cfg.port,
                           wire_format=fleet_cfg.wire_format).start()
        print(f"router bus server on {server.address}; start workers "
              f"with: python -m fmda_tpu serve-fleet --role worker "
              f"--connect {server.address} --worker-id w<N>",
              file=sys.stderr)
    router = FleetRouter(bus, fleet_cfg, n_features=cfg.features.n_features)
    telemetry = _fleet_telemetry(args, cfg)
    plane = _control_plane(args, cfg, telemetry, router=router)
    tele_server = None
    if telemetry is not None and args.metrics_port is not None:
        # the router's OWN scrape surface: fleet-level series
        # (/query?series=&window=), the SLO alert document (/alerts),
        # and an SLO-aware /healthz `status --endpoint` exits 1 on
        tele_server = telemetry.start_server(port=args.metrics_port)
        print(f"router telemetry: {tele_server.url}/metrics "
              f"(query, alerts, healthz)", file=sys.stderr)
    deadline = (time.monotonic() + args.duration_s
                if args.duration_s else None)
    try:
        while deadline is None or time.monotonic() < deadline:
            router.pump()
            if telemetry is not None:
                # cadence-gated fold (one clock read when not due) —
                # aggregation stays off the routing hot path
                telemetry.maybe_collect(router)
            if plane is not None:
                plane.maybe_tick()
            time.sleep(0.005)
    except KeyboardInterrupt:
        pass
    finally:
        router.stop_workers()
        # keep pumping briefly so the workers' drain + goodbye (final
        # stats) make it into the printed summary — stop_workers only
        # SENDS the stop; the goodbyes land on the control topic after
        # the workers drain (LocalFleet.shutdown does the same)
        grace = time.monotonic() + 5.0
        try:
            while router.membership.workers and time.monotonic() < grace:
                router.pump()
                time.sleep(0.02)
        except (ConnectionError, OSError):
            pass
        if telemetry is not None:
            telemetry.close()
        if tele_server is not None:
            tele_server.stop()
        if server is not None:
            server.stop()
    out = router.summary()
    out["n_features"] = router.n_features
    if telemetry is not None:
        out["alerts"] = telemetry.alerts()["firing"]
    if plane is not None:
        out["control"] = plane.status()
    _maybe_write_trace(args, out)
    print(json.dumps(out, indent=2, default=str))
    return 0


def _cmd_fleet_chaos(args, cfg) -> int:
    """serve-fleet --role local --chaos-plan: run the chaos soak — the
    full topology under a fault plan (kill/revive workers, router
    takeover, bus blips, link partitions), hard-gating the never-abort
    contract (docs/chaos.md).  Exit 1 iff a gate fails."""
    from fmda_tpu.chaos.plan import FaultPlan, plan_from_config
    from fmda_tpu.chaos.soak import run_chaos_soak

    n = args.workers if args.workers is not None else cfg.fleet.n_workers
    worker_ids = [f"{cfg.fleet.worker_prefix}{i}" for i in range(n)]
    if args.chaos_plan == "generate":
        plan = plan_from_config(
            cfg.chaos, worker_ids, n_steps=args.ticks)
    else:
        plan = FaultPlan.load(args.chaos_plan)
    out = run_chaos_soak(
        plan,
        n_workers=n,
        n_sessions=args.sessions,
        hidden=args.hidden,
        seed=args.seed,
        duty=args.duty,
        slow_fraction=args.slow_fraction,
        slow_duty=args.slow_duty,
        burst_every=args.burst_every,
        compare_unfaulted=not args.chaos_no_reference,
        config=cfg,
    )
    print(json.dumps(out, indent=2, default=str))
    return 0 if out["gates_ok"] else 1


def cmd_chaos_pipeline(args) -> int:
    """chaos-pipeline: the data-plane chaos soak — synthetic feeds →
    join engine → journaled warehouse → predictor, in-process, under a
    seeded fault plan (feed outage, warehouse outage, engine kill),
    hard-gating the never-abort contract for the whole pipeline
    (docs/chaos.md "Data-plane faults").  Exit 1 iff a gate fails."""
    from fmda_tpu.chaos.pipeline import (
        generate_pipeline_plan,
        run_pipeline_soak,
    )
    from fmda_tpu.chaos.plan import FaultPlan

    if not args.no_predictor:
        _select_backend(args)  # the predictor stage is jitted
    cfg = _config(args)
    cc = cfg.chaos
    seed = args.seed if args.seed is not None else cc.seed
    if args.plan:
        plan = FaultPlan.load(args.plan)
    else:
        plan = generate_pipeline_plan(
            seed, args.rounds,
            feed_outages=cc.feed_outages,
            feed_outage_steps=cc.feed_outage_steps,
            warehouse_outages=cc.warehouse_outages,
            warehouse_outage_steps=cc.warehouse_outage_steps,
            engine_kills=cc.engine_kills,
            engine_kill_steps=cc.engine_kill_steps,
            settle_steps=cc.settle_steps)
    out = run_pipeline_soak(
        plan,
        seed=seed,
        rounds=args.rounds,
        predictor=not args.no_predictor,
        compare_unfaulted=not args.no_reference,
    )
    print(json.dumps(out, indent=2, default=str))
    return 0 if out["gates_ok"] else 1


def _replay_width(cfg) -> int:
    """The feature width a replay run actually serves: a
    warehouse-source backfill streams the RAW landed table
    (``table_columns()`` wide, docs/replay.md), not the derived
    x_fields view — the serving model must be sized to the rows it
    will see."""
    if cfg.replay.source == "warehouse":
        return len(cfg.features.table_columns())
    return cfg.features.n_features


def _replay_swap_params(args, cfg):
    """The --hot-swap checkpoint: the worker-model stack re-initialised
    from a shifted seed — same tree structure and leaf shapes (a hot
    swap must not change the compiled program), observably different
    weights (post-swap probes prove the new checkpoint serves)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from fmda_tpu.models import build_model

    model_cfg = dataclasses.replace(
        cfg.model, bidirectional=False, dropout=0.0,
        hidden_size=args.hidden, n_features=_replay_width(cfg),
        cell=cfg.model.cell if cfg.model.cell != "attn" else "gru")
    window = args.window if args.window is not None else cfg.runtime.window
    return build_model(model_cfg).init(
        {"params": jax.random.PRNGKey(args.seed + 1)},
        jnp.zeros((1, window, model_cfg.n_features)))["params"]


def _run_replay(target, cfg, args, *, warehouse=None, swap_params=None,
                is_router=False, extra_on_round=None):
    """The --replay load: a max-speed virtual-clock backfill through
    the target's unmodified submit/pump surface (fmda_tpu.replay;
    docs/replay.md) instead of the cadence-shaped synthetic load.  With
    ``swap_params`` the checkpoint lands halfway through the backfill —
    straight into a solo gateway, or broadcast to every live worker
    through the router — without dropping a session."""
    from fmda_tpu.replay import (
        ReplayDriver, SyntheticHistory, WarehouseHistory,
    )

    rc = cfg.replay
    n_features = _replay_width(cfg)
    if rc.source == "warehouse":
        if warehouse is None:
            from fmda_tpu.stream.warehouse import Warehouse

            warehouse = Warehouse(cfg.features, cfg.warehouse)
        source = WarehouseHistory(
            warehouse, rc.n_tickers, n_features=n_features,
            start_ts=rc.start_ts, end_ts=rc.end_ts, chunk=rc.chunk)
    else:
        source = SyntheticHistory(
            rc.n_tickers, rc.n_rounds, n_features,
            seed=rc.seed, duty=rc.duty, step_s=rc.step_s)
    quality = None
    if cfg.quality.enabled and rc.source == "warehouse":
        # warehoused backfills have joinable labels: ride the replay
        # through the label-join evaluator so the run reports live
        # per-version quality alongside throughput
        from fmda_tpu.obs.quality import QualityEvaluator

        quality = QualityEvaluator(
            cfg.quality, warehouse=warehouse,
            max_lead=cfg.features.max_lead)
    # halfway for the synthetic source; best effort for a warehouse
    # backfill (its round count is only known once the rows stream)
    swap_at = max(1, rc.n_rounds // 2)
    tenant_classes, tenant_weights = _tenant_mix(args)
    swapped: dict = {}

    def on_round(r):
        if swap_params is not None and not swapped and r + 1 >= swap_at:
            if is_router:
                told = target.broadcast_hot_swap(swap_params)
                swapped.update({"round": r + 1, "workers_told": told})
            else:
                version = target.hot_swap(swap_params)
                swapped.update({"round": r + 1,
                                "weights_version": version})
        if extra_on_round is not None:
            extra_on_round(r)

    driver = ReplayDriver(
        target, source,
        tenant_classes=tenant_classes, tenant_weights=tenant_weights,
        seed=rc.seed,
        # a router encodes per link itself; the dialect round-trip is
        # the solo gateway's stand-in for those bytes
        wire_dialect=(None if is_router else rc.wire_dialect),
        on_round=on_round, quality=quality)
    out = driver.run()
    out["replay"] = {"source": rc.source, "n_tickers": rc.n_tickers}
    if swapped:
        out["hot_swap"] = swapped
    if quality is not None:
        quality.join()  # final join: drain whatever already has labels
        q = quality.summary()
        out["quality"] = {
            "conservation": q["conservation"],
            "overall": q["overall"],
            "versions": q["versions"],
        }
    return out


def _cmd_fleet_local(args) -> int:
    """serve-fleet --role local: the single-command topology — spawn
    router (inline) + N worker processes, drive the synthetic fleet
    load through the router, print aggregate + per-worker stats."""
    from fmda_tpu.fleet.launcher import (
        WORKER_PLATFORM, launch_local_fleet, spawn_supported,
    )
    from fmda_tpu.runtime.loadgen import FleetLoadConfig, run_fleet_load

    cfg = _fleet_wire_override(args, _config(args))
    if not spawn_supported():
        print("--role local spawns worker processes and this host "
              "cannot spawn any", file=sys.stderr)
        return 2
    if args.chaos_plan:
        return _cmd_fleet_chaos(args, cfg)
    if args.trace or args.trace_out or args.trace_dir:
        from fmda_tpu.obs.trace import configure_tracing

        configure_tracing(enabled=True, sample_rate=args.trace_sample)
    n = args.workers if args.workers is not None else cfg.fleet.n_workers
    bucket_sizes = (tuple(int(b) for b in args.bucket_sizes.split(","))
                    if args.bucket_sizes else None)
    topo = launch_local_fleet(
        n_workers=n,
        config=cfg,
        hidden=args.hidden,
        seed=args.seed,
        capacity_per_worker=args.sessions,
        bucket_sizes=bucket_sizes,
        max_linger_ms=args.max_linger_ms,
        window=args.window,
        trace_dir=args.trace_dir,
    )
    telemetry = _fleet_telemetry(args, cfg)
    plane = None
    if telemetry is not None:
        from fmda_tpu.control import LocalFleetActuator

        plane = _control_plane(
            args, cfg, telemetry, router=topo.router,
            actuator=LocalFleetActuator(topo),
            initial_linger_ms=args.max_linger_ms,
            bucket_sizes=bucket_sizes)
    tele_server = None
    if telemetry is not None and args.metrics_port is not None:
        tele_server = telemetry.start_server(port=args.metrics_port)
        print(f"fleet telemetry: {tele_server.url}/metrics "
              f"(query, alerts, healthz)", file=sys.stderr)

    def on_round(r):
        if telemetry is not None:
            telemetry.maybe_collect(topo.router)
        if plane is not None:
            plane.maybe_tick()

    tenant_classes, tenant_weights = _tenant_mix(args)
    try:
        if args.replay:
            out = _run_replay(
                topo.router, cfg, args,
                swap_params=(_replay_swap_params(args, cfg)
                             if args.hot_swap else None),
                is_router=True,
                extra_on_round=(on_round if telemetry is not None
                                or plane is not None else None))
            if args.hot_swap:
                # the router's view of who acked which version — the
                # zero-downtime proof is spread == 0 with sessions intact
                fleet = topo.router.summary()
                out.setdefault("hot_swap", {})
                out["hot_swap"]["weights_versions"] = fleet.get(
                    "weights_versions")
                out["hot_swap"]["weights_version_spread"] = fleet.get(
                    "weights_version_spread")
        else:
            out = run_fleet_load(topo.router, FleetLoadConfig(
                n_sessions=args.sessions, n_ticks=args.ticks,
                duty=args.duty, seed=args.seed,
                storm_every=args.storm_every,
                storm_fraction=args.storm_fraction,
                burst_every=args.burst_every,
                burst_rounds=args.burst_rounds,
                slow_fraction=args.slow_fraction,
                slow_duty=args.slow_duty,
                tenant_classes=tenant_classes,
                tenant_weights=tenant_weights),
                on_round=(on_round if telemetry is not None
                          or plane is not None else None))
        if telemetry is not None:
            telemetry.collect(topo.router)  # final fold before teardown
    finally:
        worker_stats = topo.shutdown()
        if telemetry is not None:
            telemetry.close()
        if tele_server is not None and args.metrics_hold_s <= 0:
            # with --metrics-hold-s the endpoint outlives the load (the
            # curl/promtool demo workflow) and stops after the hold below
            tele_server.stop()
    out["workers"] = n
    # the multi-worker topology is a host-side topology today: every
    # spawned worker is forced onto the CPU (one process per chip; R6
    # gives each worker its own device) — say so in the report
    out["worker_platform"] = WORKER_PLATFORM
    out["worker_stats"] = worker_stats
    out["table_version"] = topo.router.table.version
    if telemetry is not None:
        out["alerts"] = telemetry.alerts()["firing"]
        out["fleet"] = {
            g["name"]: g["value"] for g in telemetry.fleet_gauges()}
    if plane is not None:
        out["control"] = plane.status()
    if args.trace_dir:
        from fmda_tpu.obs.trace import default_tracer

        router_trace = os.path.join(args.trace_dir, "router.json")
        with open(router_trace, "w") as fh:
            json.dump(default_tracer().chrome(), fh)
        out["trace_dir"] = args.trace_dir
        print(f"per-process traces in {args.trace_dir}; merge with "
              f"`python -m fmda_tpu trace --merge {args.trace_dir}`",
              file=sys.stderr)
    _maybe_write_trace(args, out)
    print(json.dumps(out, indent=2, default=str))
    if tele_server is not None and args.metrics_hold_s > 0:
        # the endpoint outlives the load so an operator can curl
        # /alerts + /query against the run's final state (same contract
        # as the solo role's --metrics-hold-s)
        import time

        print(f"holding fleet telemetry endpoint for "
              f"{args.metrics_hold_s:.0f}s", file=sys.stderr)
        time.sleep(args.metrics_hold_s)
        tele_server.stop()
    return 0


def cmd_serve_fleet(args) -> int:
    """Multi-tenant serving proof: N concurrent ticker sessions through
    the dynamic micro-batching runtime (fmda_tpu.runtime; docs/runtime.md)
    against a synthetic multi-ticker load — one fused jit step per flush
    serves every active session.  Prints the runtime metrics (per-stage
    latency histograms, shed/queue counters, compiled-bucket count) as
    one JSON object.

    ``--role router|worker|local`` runs the multi-host topology instead
    (fmda_tpu.fleet; docs/multihost.md): a router fronting N worker
    processes over the cross-process bus, with session routing,
    membership, and live migration."""
    if args.replay and args.role not in ("solo", "local"):
        print("--replay drives a solo gateway or the local topology; "
              "use --role solo or --role local", file=sys.stderr)
        return 2
    if args.replay and args.role == "local" and _config(
            args).replay.source == "warehouse":
        # spawned workers size their models from the live feature
        # schema; a warehouse backfill streams raw landed rows
        # (narrower) — only the solo gateway sizes itself to them
        print("[replay] source=warehouse backfills run solo "
              "(landed-row width); drop --role local", file=sys.stderr)
        return 2
    if args.hot_swap and not args.replay:
        print("--hot-swap lands mid-backfill; it needs --replay",
              file=sys.stderr)
        return 2
    if args.replay and args.predictor:
        print("--replay serves carried-state sessions; it composes "
              "with --cell, not --predictor", file=sys.stderr)
        return 2
    if args.continuous_train and args.role != "solo":
        print("--continuous-train runs beside the solo gateway; "
              "use --role solo (fleet-wide: run `train --continuous` "
              "against the shared warehouse and let the router "
              "broadcast)", file=sys.stderr)
        return 2
    if args.continuous_train and (args.replay or args.predictor):
        print("--continuous-train is its own load shape; drop "
              "--replay/--predictor", file=sys.stderr)
        return 2
    if args.swap_guard and not args.continuous_train:
        print("--swap-guard gates --continuous-train swaps; add "
              "--continuous-train", file=sys.stderr)
        return 2
    if args.role == "worker":
        return _cmd_fleet_worker(args)
    if args.role == "broker":
        return _cmd_fleet_broker(args)
    if args.role == "router":
        return _cmd_fleet_router(args)
    if args.role == "local":
        return _cmd_fleet_local(args)
    _select_backend(args)
    import dataclasses

    import jax

    from fmda_tpu.app import Application
    from fmda_tpu.runtime import FleetLoadConfig, run_fleet_load

    cfg = _fleet_wire_override(args, _config(args))
    bucket_sizes = (tuple(int(b) for b in args.bucket_sizes.split(","))
                    if args.bucket_sizes else None)
    if args.predictor:
        # the window-re-scan Predictor path: the batching knobs land on
        # the predictor_* half of RuntimeConfig
        overrides = {
            k: v for k, v in dict(
                predictor_max_linger_ms=args.max_linger_ms,
                predictor_queue_bound=args.queue_bound,
                predictor_window=args.window,
                predictor_bucket_sizes=bucket_sizes,
                predictor_ring=(True if args.ring else None),
                pipeline_depth=(0 if args.serial else None),
                slo_p99_ms=args.slo_p99_ms,
            ).items() if v is not None
        }
    else:
        overrides = {
            k: v for k, v in dict(
                capacity=max(args.sessions, cfg.runtime.capacity,
                             cfg.replay.n_tickers if args.replay else 0),
                max_linger_ms=args.max_linger_ms,
                queue_bound=args.queue_bound,
                window=args.window,
                bucket_sizes=bucket_sizes,
                pipeline_depth=(0 if args.serial else None),
                shard_pool=args.shard_pool,
                slo_p99_ms=args.slo_p99_ms,
            ).items() if v is not None
        }
    cfg = dataclasses.replace(
        cfg, runtime=dataclasses.replace(cfg.runtime, **overrides))
    if args.trace or args.trace_out:
        # enable BEFORE the Application builds, so every captured
        # default-tracer handle (bus, gateway) sees the switch
        from fmda_tpu.obs.trace import configure_tracing

        configure_tracing(enabled=True, sample_rate=args.trace_sample)
    # [profiling] applies before any pool compiles (ledger settings,
    # memory cadence, optional continuous host profiler)
    from fmda_tpu.obs.device import configure_device_obs

    configure_device_obs(cfg.profiling)

    from fmda_tpu.models import build_model
    import jax.numpy as jnp

    if args.predictor:
        # batched-Predictor proof run: synthetic corpus warehouse, a
        # randomly-initialised flagship bidirectional model (the serving
        # math is checkpoint-independent), every servable timestamp
        # signalled in bursts through the PredictorGateway
        from fmda_tpu.data.normalize import NormParams
        from fmda_tpu.data.synthetic import (
            SyntheticMarketConfig, build_corpus,
        )
        from fmda_tpu.runtime import PredictorLoadConfig, run_predictor_load
        import numpy as np

        wh, _ = build_corpus(
            cfg.features,
            SyntheticMarketConfig(seed=args.seed,
                                  n_days=args.predictor_days))
        app = Application(cfg, warehouse=wh)
        model_cfg = dataclasses.replace(
            cfg.model, dropout=0.0, hidden_size=args.hidden,
            n_features=len(wh.x_fields))
        window = (cfg.runtime.predictor_window
                  if cfg.runtime.predictor_window is not None
                  else cfg.runtime.window)
        params = build_model(model_cfg).init(
            {"params": jax.random.PRNGKey(args.seed)},
            jnp.zeros((1, window, model_cfg.n_features)))["params"]
        norm = NormParams(
            np.zeros(model_cfg.n_features, np.float32),
            np.ones(model_cfg.n_features, np.float32))
        gateway = app.attach_predictor_fleet(
            model_cfg, params, norm, max_staleness_s=None)
        timestamps = wh.timestamps()[window - 1:]
        load_cfg = PredictorLoadConfig(
            n_signals=args.signals, burst=args.burst)

        def run_load():
            return run_predictor_load(gateway, timestamps, load_cfg)
    else:
        if args.continuous_train:
            # the continuous-train proof run tails a real warehouse:
            # build the synthetic corpus through the production
            # streaming stack and size the serving model to its joined
            # feature width (the trainer must train the SAME param tree
            # the pool serves, or the hot swap would rebind wrong)
            from fmda_tpu.data.synthetic import (
                SyntheticMarketConfig, build_corpus,
            )

            wh, _ = build_corpus(
                cfg.features,
                SyntheticMarketConfig(seed=args.seed,
                                      n_days=args.continuous_days))
            app = Application(cfg, warehouse=wh)
        else:
            app = Application(cfg)

        # synthetic proof run: a randomly-initialised unidirectional
        # carrier (the serving math is checkpoint-independent; --hidden
        # sizes it)
        model_cfg = dataclasses.replace(
            cfg.model, bidirectional=False, dropout=0.0,
            hidden_size=args.hidden,
            n_features=(len(app.warehouse.x_fields)
                        if args.continuous_train
                        else _replay_width(cfg) if args.replay
                        else cfg.features.n_features),
            cell=cfg.model.cell if cfg.model.cell != "attn" else "gru")
        model = build_model(model_cfg)

        params = model.init(
            {"params": jax.random.PRNGKey(args.seed)},
            jnp.zeros((1, cfg.runtime.window,
                       model_cfg.n_features)))["params"]

        gateway = app.attach_fleet(model_cfg, params)
        if args.replay:
            swap_params = (_replay_swap_params(args, cfg)
                           if args.hot_swap else None)

            def run_load():
                return _run_replay(gateway, cfg, args,
                                   warehouse=app.warehouse,
                                   swap_params=swap_params)
        else:
            load_cfg = FleetLoadConfig(
                n_sessions=args.sessions,
                n_ticks=args.ticks, duty=args.duty, seed=args.seed,
                storm_every=args.storm_every,
                storm_fraction=args.storm_fraction,
                burst_every=args.burst_every,
                burst_rounds=args.burst_rounds,
                slow_fraction=args.slow_fraction,
                slow_duty=args.slow_duty)

            def run_load():
                return run_fleet_load(gateway, load_cfg)
    continuous = None
    continuous_thread = None
    if args.continuous_train:
        # the trainer tails the corpus warehouse beside the serving
        # load; every accepted round hot-swaps the live pool (host-side
        # rebind — serving never recompiles; docs/training.md)
        import threading

        from fmda_tpu.train.continuous import (
            ContinuousTrainer, gateway_publisher)

        require_eval = None
        if args.swap_guard:
            from fmda_tpu.eval.shadow import ShadowEvaluator

            require_eval = ShadowEvaluator(
                params, model_config=model_cfg, warehouse=app.warehouse,
                quality_config=cfg.quality, max_lead=cfg.features.max_lead,
                window=cfg.runtime.window,
                # the model is sized to the joined x_fields view; the
                # shadow replay streams raw landed chunks and must map
                # them through the derived views
                row_transform=app.warehouse.joined_row_transform)
        continuous = ContinuousTrainer(
            app.warehouse, model_cfg, cfg.train,
            checkpoint_dir=(args.train_checkpoint_dir
                            or cfg.train.checkpoint_dir),
            publish=gateway_publisher(gateway, require_eval=require_eval),
            bid_levels=cfg.features.bid_levels,
            ask_levels=cfg.features.ask_levels,
            drift_bins=cfg.quality.drift_bins,
            target_lead=cfg.features.max_lead)
        continuous_thread = threading.Thread(
            target=lambda: continuous.run(max_rounds=args.train_rounds),
            daemon=True, name="fmda-continuous-train")
        continuous_thread.start()
    if args.metrics_port is not None:
        server = app.observability.start_server(port=args.metrics_port)
        print(f"metrics endpoint: {server.url}/metrics "
              f"(healthz, snapshot, events, trace)", file=sys.stderr)
    if args.jax_profile:
        # device-side work joins the host spans: a TensorBoard/XProf
        # capture of the whole load; carried-state pool flushes are
        # annotated as numbered StepTraceAnnotation steps
        from fmda_tpu.utils.tracing import device_trace

        if not args.predictor:
            gateway.annotate_device_steps = True
        with device_trace(args.jax_profile):
            out = run_load()
        print(f"jax profile captured to {args.jax_profile} "
              f"(tensorboard --logdir)", file=sys.stderr)
    else:
        out = run_load()
    if args.predictor:
        out["ring"] = gateway.pool.use_ring
    else:
        out["cell"] = model_cfg.cell
    if continuous is not None:
        # let the tail quiesce on its own (bounded follow: at most
        # continuous_follow_polls empty polls) so the backlog's drain
        # round lands; stop() is the backstop, not the happy path
        continuous_thread.join(timeout=120.0)
        if continuous_thread.is_alive():
            continuous.stop()
            continuous_thread.join(timeout=120.0)
        summary = continuous.summary()
        summary["weights_version"] = gateway.weights_version
        summary["pool_compile_count"] = gateway.pool.compile_count
        out["continuous_train"] = summary
    from fmda_tpu.obs.device import default_ledger
    from fmda_tpu.utils.env import device_report

    out.update(device_report())
    # what this run compiled and what that cost (cold vs warm cache)
    out["compile_ledger"] = default_ledger().dump()
    if args.trace or args.trace_out:
        from fmda_tpu.obs.trace import default_tracer

        tracer = default_tracer()
        out["tracing"] = {
            "traces_finished": tracer.traces_finished,
            "spans_buffered": len(tracer.spans()),
            "e2e": tracer.e2e.summary(),
        }
        if args.trace_out:
            with open(args.trace_out, "w") as fh:
                json.dump(tracer.chrome(), fh)
            out["tracing"]["file"] = args.trace_out
            print(f"perfetto trace written to {args.trace_out} "
                  f"(load at https://ui.perfetto.dev, or "
                  f"`python -m fmda_tpu trace --input {args.trace_out}`)",
                  file=sys.stderr)
    slo_ok = True
    # args.slo_p99_ms already merged into cfg.runtime via `overrides`
    slo_ms = cfg.runtime.slo_p99_ms
    if slo_ms is not None:
        p99 = out.get("latency", {}).get("total", {}).get("p99_ms")
        slo_ok = p99 is not None and p99 <= slo_ms
        out["slo"] = {
            "p99_ms_bound": slo_ms,
            "p99_ms": p99,
            "ok": slo_ok,
            "soft": bool(args.slo_soft),
        }
    print(json.dumps(out, indent=2))
    if args.metrics_port is not None and args.metrics_hold_s > 0:
        # keep the endpoint scrapeable after the load (curl/promtool
        # demos; the load itself is finite) — BEFORE the SLO verdict
        # exits, so a violating run's histograms stay inspectable
        import time

        print(f"holding metrics endpoint for {args.metrics_hold_s:.0f}s",
              file=sys.stderr)
        time.sleep(args.metrics_hold_s)
    if slo_ms is not None and not slo_ok and not args.slo_soft:
        p99 = out["slo"]["p99_ms"]
        print("SLO gate failed: "
              + (f"total p99 {p99}ms > {slo_ms}ms bound"
                 if p99 is not None else
                 "no latency data collected (zero ticks served)")
              + " (--slo-soft reports without failing)", file=sys.stderr)
        return 1
    return 0


def _print_status(snapshot: dict, health: dict,
                  alerts: dict = None, control: dict = None) -> None:
    """Human-readable registry snapshot + health verdict (+ the SLO
    alert table when the endpoint serves ``/alerts``, + the control
    plane's loop state when it serves ``/control``)."""

    def key(s):
        labels = ",".join(f"{k}={v}" for k, v in
                          sorted(s.get("labels", {}).items()))
        return f"{s['name']}{{{labels}}}" if labels else s["name"]

    print(f"status: {health['status']}")
    for name, check in sorted(health.get("checks", {}).items()):
        mark = "ok  " if check["ok"] else "FAIL"
        print(f"  {mark} {name:<14} {check['detail']}")
    if alerts and alerts.get("alerts"):
        print(f"slo alerts (burn threshold "
              f"{alerts.get('burn_threshold')}x):")
        for name, a in sorted(alerts["alerts"].items()):
            mark = "FIRE" if a.get("state") == "firing" else "ok  "
            print(f"  {mark} {name:<16} "
                  f"fast {a.get('burn_fast', 0):>8.2f}x  "
                  f"slow {a.get('burn_slow', 0):>8.2f}x  "
                  f"{a.get('detail', '')}")
    if control and control.get("enabled"):
        _print_control(control)
    perf = _perf_summary(snapshot)
    if perf:
        _print_perf_summary(perf)
    replay = _replay_summary(snapshot)
    if replay:
        _print_replay_summary(replay)
    quality = _quality_summary(snapshot)
    if quality:
        _print_quality_summary(quality)
    for kind in ("counters", "gauges"):
        samples = sorted(snapshot.get(kind, []), key=key)
        if samples:
            print(f"{kind}:")
            for s in samples:
                v = s["value"]
                v = int(v) if float(v) == int(v) else round(float(v), 6)
                print(f"  {key(s):<52} {v}")
    hists = sorted(snapshot.get("histograms", []), key=key)
    if hists:
        print("latency:")
        print(f"  {'series':<52} {'count':>8} {'p50_ms':>9} "
              f"{'p99_ms':>9} {'mean_ms':>9}")
        for s in hists:
            n = s["count"]
            mean_ms = (s["sum_s"] / n * 1e3) if n else 0.0
            print(f"  {key(s):<52} {n:>8} {s['p50_s'] * 1e3:>9.3f} "
                  f"{s['p99_s'] * 1e3:>9.3f} {mean_ms:>9.3f}")


def _fmt_bytes(n: float) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0:
            return (f"{int(n)}B" if unit == "B" else f"{n:.1f}{unit}")
        n /= 1024.0
    return f"{n:.1f}TiB"


def _perf_summary(snapshot: dict) -> dict:
    """The device/compiler facts inside ``status`` (ISSUE 17): MFU,
    post-warmup recompiles, memory watermark + leak verdict.  Reads
    both vocabularies — a process registry's device collector
    (``device_mfu``, ``compile_unexpected_total``, ...) and a fleet
    telemetry's landed worker series (``worker_device_mfu``, ...) —
    and returns {} when neither is present (older endpoints)."""
    by_name: dict = {}
    for kind in ("counters", "gauges"):
        for s in snapshot.get(kind, []):
            by_name.setdefault(s["name"], []).append(float(s["value"]))

    def agg(fn, *names):
        vals = [v for n in names for v in by_name.get(n, [])]
        return fn(vals) if vals else None

    out = {}
    mfu = agg(max, "device_mfu", "worker_device_mfu")
    if mfu is not None:
        out["mfu"] = mfu
    intensity = agg(max, "device_arithmetic_intensity")
    if intensity is not None:
        out["arithmetic_intensity"] = intensity
    recompiles = agg(sum, "compile_unexpected_total",
                     "worker_recompiles_total")
    if recompiles is not None:
        out["recompiles_after_warmup"] = int(recompiles)
    compile_s = agg(sum, "compile_seconds_total",
                    "worker_compile_seconds_total")
    if compile_s is not None:
        out["compile_seconds"] = compile_s
    watermark = agg(max, "device_memory_watermark_bytes",
                    "worker_memory_watermark_bytes")
    if watermark is not None:
        out["memory_watermark_bytes"] = watermark
    leak = agg(max, "device_memory_leak_suspected",
               "worker_memory_leak_suspected")
    if leak is not None:
        out["memory_leak_suspected"] = bool(leak)
    return out


def _print_perf_summary(perf: dict) -> None:
    parts = []
    if "mfu" in perf:
        parts.append(f"mfu {perf['mfu'] * 100:.2f}%")
    if "compile_seconds" in perf:
        parts.append(f"compile {perf['compile_seconds']:.3f}s")
    if "recompiles_after_warmup" in perf:
        n = perf["recompiles_after_warmup"]
        parts.append(f"post-warmup recompiles {n}"
                     + (" !!" if n else ""))
    if "memory_watermark_bytes" in perf:
        parts.append(
            f"mem watermark {_fmt_bytes(perf['memory_watermark_bytes'])}")
    if perf.get("memory_leak_suspected"):
        parts.append("LEAK SUSPECTED")
    print("perf: " + " | ".join(parts))


def _replay_summary(snapshot: dict) -> dict:
    """The replay section of ``status`` — present only while a backfill
    is active (the driver's ``replay_active`` gauge).  Reads any prefix
    vocabulary (``runtime_``/``router_``/``worker_``), like
    :func:`_perf_summary`."""
    out: dict = {}
    for s in snapshot.get("gauges", []):
        name = s["name"]
        for base in ("replay_active", "replay_rows_per_s",
                     "replay_virtual_watermark",
                     "replay_max_ticker_lag_s"):
            if name == base or name.endswith("_" + base):
                out[base] = max(float(s["value"]), out.get(base, 0.0))
    if out.get("replay_active", 0.0) <= 0.0:
        return {}
    return out


def _print_replay_summary(replay: dict) -> None:
    from datetime import datetime, timezone

    parts = ["backfill active"]
    if "replay_rows_per_s" in replay:
        parts.append(f"{replay['replay_rows_per_s']:,.0f} rows/s")
    wm = replay.get("replay_virtual_watermark")
    if wm:
        stamp = datetime.fromtimestamp(
            wm, tz=timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ")
        parts.append(f"virtual watermark {stamp}")
    if "replay_max_ticker_lag_s" in replay:
        parts.append(
            f"max ticker lag {replay['replay_max_ticker_lag_s']:.0f}s")
    print("replay: " + " | ".join(parts))


def _quality_summary(snapshot: dict) -> dict:
    """The model-quality section of ``status`` — present once the
    label-join evaluator has published at least one joined window
    (docs/observability.md "Model quality")."""
    out: dict = {"versions": {}}
    for s in snapshot.get("gauges", []):
        name, labels = s["name"], s.get("labels", {})
        if name == "quality_subset_accuracy":
            v = labels.get("version", "?")
            out["versions"].setdefault(v, {})["accuracy"] = float(s["value"])
        elif name == "quality_hamming_loss":
            v = labels.get("version", "?")
            out["versions"].setdefault(v, {})["hamming"] = float(s["value"])
        elif name == "quality_pending":
            out["pending"] = float(s["value"])
        elif name == "quality_drift_score":
            out["drift"] = float(s["value"])
    for s in snapshot.get("counters", []):
        if s["name"] in ("quality_joined_total", "quality_join_expired_total",
                         "quality_captures_shed_total"):
            out[s["name"]] = out.get(s["name"], 0.0) + float(s["value"])
    if not out["versions"] and "quality_joined_total" not in out:
        return {}
    return out


def _print_quality_summary(quality: dict) -> None:
    parts = []
    joined = quality.get("quality_joined_total")
    if joined is not None:
        parts.append(f"joined {int(joined)}")
    for v, m in sorted(quality.get("versions", {}).items()):
        acc = m.get("accuracy")
        ham = m.get("hamming")
        seg = f"v{v} acc {acc:.3f}" if acc is not None else f"v{v}"
        if ham is not None:
            seg += f" hamming {ham:.3f}"
        parts.append(seg)
    if "drift" in quality:
        parts.append(f"drift psi {quality['drift']:.3f}")
    if quality.get("pending"):
        parts.append(f"pending {int(quality['pending'])}")
    expired = quality.get("quality_join_expired_total", 0.0)
    shed = quality.get("quality_captures_shed_total", 0.0)
    if expired or shed:
        parts.append(f"lost {int(expired)} expired / {int(shed)} shed")
    print("quality: " + " | ".join(parts))


def _print_control(control: dict) -> None:
    """The controller section of ``status``: loop modes + knobs, the
    per-tenant admit/shed aggregates, and the last few decisions."""
    batching = control.get("batching") or {}
    autoscale = control.get("autoscale") or {}
    line = f"control: target p99 {control.get('target_p99_ms')}ms"
    if batching:
        cap = batching.get("bucket_cap")
        line += (f" | batching {batching.get('mode')} "
                 f"linger {batching.get('linger_ms'):.2f}ms "
                 f"cap {'-' if cap is None else cap}")
    if autoscale:
        line += (f" | autoscale {autoscale.get('mode')} "
                 f"workers {autoscale.get('workers')} "
                 f"[{autoscale.get('min_workers')}.."
                 f"{autoscale.get('max_workers')}]")
    print(line)
    tenants = control.get("tenants") or {}
    if tenants:
        print("  tenants:")
        for name, v in sorted(tenants.items()):
            print(f"    {name:<36} {v}")
    decisions = control.get("decisions") or []
    if decisions:
        print(f"  decisions (last {min(len(decisions), 5)}):")
        for d in decisions[-5:]:
            extra = (f"worker {d.get('worker')}"
                     if d.get("loop") == "autoscale"
                     else f"linger {d.get('linger_ms')}ms "
                          f"cap {d.get('bucket_cap')}")
            print(f"    t+{d.get('t', 0):.1f}s {d.get('loop'):<9} "
                  f"{d.get('action'):<12} {extra}")


def _scrape_endpoint(endpoint: str):
    """GET /snapshot + /healthz (+ /alerts and /control, absent on
    older endpoints) off one endpoint; raises on transport failure
    (callers decide whether one dead worker fails the probe)."""
    import urllib.error
    import urllib.request

    base = (endpoint if "://" in endpoint
            else f"http://{endpoint}").rstrip("/")
    with urllib.request.urlopen(base + "/snapshot", timeout=10) as r:
        snapshot = json.loads(r.read())
    try:
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            health = json.loads(r.read())
    except urllib.error.HTTPError as e:
        # 503 = degraded; the body still carries the check detail
        health = json.loads(e.read())

    def _optional(path: str):
        # absent on worker endpoints (no telemetry) and on older
        # routers — the snapshot and health verdict still stand alone
        try:
            with urllib.request.urlopen(base + path, timeout=10) as r:
                return json.loads(r.read())
        except (urllib.error.URLError, OSError, json.JSONDecodeError):
            return None

    return snapshot, health, _optional("/alerts"), _optional("/control")


def _status_multi(endpoints) -> int:
    """Fleet-wide status: scrape every endpoint (one per worker/router
    process), print per-process health, then the aggregate verdict.
    Exit 0 iff every endpoint answered ok; an unreachable process is a
    degraded fleet, not a CLI crash."""
    import urllib.error

    per = {}
    for ep in endpoints:
        try:
            per[ep] = _scrape_endpoint(ep)
        except (urllib.error.URLError, OSError,
                json.JSONDecodeError) as e:
            per[ep] = (None, {
                "status": "unreachable",
                "checks": {},
                "error": str(e),
            }, None, None)
    n_ok = 0
    for ep, (snapshot, health, alerts, control) in per.items():
        status = health.get("status")
        print(f"===== {ep}: {status} =====")
        if status == "unreachable":
            print(f"  {health.get('error')}")
            continue
        if status == "ok":
            n_ok += 1
        _print_status(snapshot, health, alerts, control)
    aggregate = "ok" if n_ok == len(endpoints) else "degraded"
    print(f"aggregate: {aggregate} ({n_ok}/{len(endpoints)} endpoints ok)")
    return 0 if aggregate == "ok" else 1


def cmd_status(args) -> int:
    """Observability snapshot: local (build the app, sample its registry)
    or remote (GET /snapshot + /healthz + /alerts off running
    endpoints).  Several ``--endpoint`` values — one per fleet worker —
    report per-worker health plus the aggregate verdict.  ``--watch N``
    re-scrapes every N seconds, redrawing in place, until Ctrl-C (clean
    exit 0) — watching a soak without a shell loop."""
    if args.watch:
        return _status_watch(args)
    return _status_once(args)


def _status_watch(args) -> int:
    import time

    try:
        while True:
            if sys.stdout.isatty():
                # clear + home: redraw in place like `watch(1)`
                print("\x1b[2J\x1b[H", end="")
            _status_once(args)
            print(f"-- every {args.watch:g}s (Ctrl-C to exit) --",
                  flush=True)
            time.sleep(args.watch)
    except KeyboardInterrupt:
        # the operator closed the watch — a clean exit, not an error
        # (the per-refresh verdicts were already printed)
        return 0


def _status_once(args) -> int:
    alerts = None
    control = None
    if args.endpoint:
        import urllib.error

        if len(args.endpoint) > 1:
            return _status_multi(args.endpoint)
        try:
            snapshot, health, alerts, control = \
                _scrape_endpoint(args.endpoint[0])
        except (urllib.error.URLError, OSError,
                json.JSONDecodeError) as e:
            # a down daemon is the most common reason to run this probe
            # — report it cleanly, don't traceback
            print(f"cannot scrape {args.endpoint[0]}: {e}",
                  file=sys.stderr)
            return 2
    else:
        import dataclasses

        from fmda_tpu.app import Application

        cfg = _config(args)
        if args.warehouse:
            cfg = dataclasses.replace(
                cfg,
                warehouse=dataclasses.replace(
                    cfg.warehouse, path=args.warehouse),
            )
        # never bind the scrape port here: a config with
        # endpoint_enabled=true belongs to the daemon this command is
        # most likely being run to inspect (use --endpoint for that)
        cfg = dataclasses.replace(
            cfg,
            observability=dataclasses.replace(
                cfg.observability, endpoint_enabled=False),
        )
        app = Application(cfg)
        snapshot = app.observability.snapshot()
        health = app.observability.health()
    _print_status(snapshot, health, alerts, control)
    firing = bool(alerts and alerts.get("firing"))
    return 0 if health.get("status") == "ok" and not firing else 1


def cmd_trace(args) -> int:
    """Per-stage latency attribution for recorded tick traces — the
    "where did tick T spend its 38 ms" tool (docs/OPERATIONS.md §4d).
    Input is Chrome/Perfetto trace_event JSON: a ``serve-fleet
    --trace-out`` file, a running endpoint's ``/trace``, or several
    per-process files stitched by trace id (``--merge``)."""
    from fmda_tpu.obs.trace import (
        format_trace, group_chrome_traces, merge_chrome_traces,
    )

    if args.merge:
        import glob as _glob

        # each --merge arg may be a file, a directory of per-process
        # --trace-out files (a topology's --trace-dir merges in one
        # command), or a glob pattern
        paths = []
        for arg in args.merge:
            if os.path.isdir(arg):
                expanded = sorted(_glob.glob(os.path.join(arg, "*.json")))
                if not expanded:
                    print(f"no *.json trace files in directory {arg}",
                          file=sys.stderr)
                    return 2
            elif _glob.has_magic(arg):
                expanded = sorted(_glob.glob(arg))
                if not expanded:
                    print(f"glob {arg!r} matched nothing", file=sys.stderr)
                    return 2
            else:
                expanded = [arg]
            paths.extend(expanded)
        docs = []
        for path in paths:
            try:
                with open(path) as fh:
                    docs.append(json.load(fh))
            except (OSError, json.JSONDecodeError) as e:
                print(f"cannot read {path}: {e}", file=sys.stderr)
                return 2
        doc = merge_chrome_traces(docs)
        if args.out:
            try:
                with open(args.out, "w") as fh:
                    json.dump(doc, fh)
            except OSError as e:
                print(f"cannot write {args.out}: {e}", file=sys.stderr)
                return 2
            n_traces = len(group_chrome_traces(doc))
            print(f"merged {len(paths)} trace files "
                  f"({n_traces} traces) -> {args.out} "
                  "(load at https://ui.perfetto.dev)", file=sys.stderr)
            return 0
        # no --out: fall through to the attribution display over the
        # merged document (cross-process journeys group by trace id)
    elif args.endpoint:
        import urllib.error
        import urllib.request

        base = (args.endpoint if "://" in args.endpoint
                else f"http://{args.endpoint}").rstrip("/")
        try:
            with urllib.request.urlopen(base + "/trace", timeout=10) as r:
                doc = json.loads(r.read())
        except (urllib.error.URLError, OSError, json.JSONDecodeError) as e:
            print(f"cannot scrape {base}/trace: {e}", file=sys.stderr)
            return 2
    elif args.input:
        try:
            with open(args.input) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"cannot read {args.input}: {e}", file=sys.stderr)
            return 2
    else:
        print("pass --input FILE (a serve-fleet --trace-out file), "
              "--endpoint HOST:PORT (a running /trace endpoint), or "
              "--merge FILE FILE... (stitch per-process trace files)",
              file=sys.stderr)
        return 2
    traces = group_chrome_traces(doc)
    if args.min_ms is not None:
        traces = [t for t in traces if t["e2e_ms"] >= args.min_ms]
    if args.slowest is not None:
        traces = sorted(
            traces, key=lambda t: t["e2e_ms"], reverse=True)[:args.slowest]
    else:
        traces = traces[-args.last:]
    if not traces:
        print("no traces matched (is tracing enabled and sampled?)",
              file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(traces, indent=2))
    else:
        print("\n".join(format_trace(t) for t in traces))
    return 0


def cmd_perf(args) -> int:
    """The device/compiler performance report (docs/observability.md
    §device): compile ledger, top programs by compile time, MFU +
    roofline position, memory watermarks, kernel fallbacks, and the
    host profiler's hottest stacks.  Input is a running endpoint's
    ``/device`` (+ ``/profile``) or a saved device report — a
    flight-recorder bundle's ``device.json``."""
    profile_text = None
    if args.endpoint:
        import urllib.error
        import urllib.request

        base = (args.endpoint if "://" in args.endpoint
                else f"http://{args.endpoint}").rstrip("/")
        try:
            with urllib.request.urlopen(base + "/device", timeout=10) as r:
                doc = json.loads(r.read())
        except (urllib.error.URLError, OSError, json.JSONDecodeError) as e:
            print(f"cannot scrape {base}/device: {e}", file=sys.stderr)
            return 2
        try:
            with urllib.request.urlopen(base + "/profile", timeout=10) as r:
                profile_text = r.read().decode("utf-8", "replace")
        except (urllib.error.URLError, OSError):
            # older endpoints / profiler not attached: the device
            # report still stands alone
            profile_text = None
    elif args.input:
        try:
            with open(args.input) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"cannot read {args.input}: {e}", file=sys.stderr)
            return 2
    else:
        print("pass --endpoint HOST:PORT (a running /device endpoint) "
              "or --input FILE (a flight-recorder bundle's device.json)",
              file=sys.stderr)
        return 2
    if args.profile:
        try:
            with open(args.profile) as fh:
                profile_text = fh.read()
        except OSError as e:
            print(f"cannot read {args.profile}: {e}", file=sys.stderr)
            return 2
    if args.json:
        if profile_text is not None:
            doc = {**doc, "profile_folded": profile_text}
        print(json.dumps(doc, indent=2))
        return 0
    _print_perf_report(doc, profile_text, top=args.top)
    return 0


def cmd_quality(args) -> int:
    """The model-quality report (docs/observability.md "Model
    quality"): per-weights-version live accuracy/F-beta off the
    label-join evaluator, drift scores vs the training-time reference
    profile, and the capture/join conservation ledger.  Input is a
    running endpoint's ``/quality`` or a flight-recorder bundle
    directory (its ``quality.json``)."""
    if args.endpoint:
        import urllib.error
        import urllib.request

        base = (args.endpoint if "://" in args.endpoint
                else f"http://{args.endpoint}").rstrip("/")
        try:
            with urllib.request.urlopen(base + "/quality", timeout=10) as r:
                doc = json.loads(r.read())
        except (urllib.error.URLError, OSError, json.JSONDecodeError) as e:
            print(f"cannot scrape {base}/quality: {e}", file=sys.stderr)
            return 2
    elif args.bundle:
        path = os.path.join(args.bundle, "quality.json")
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"cannot read {path}: {e}", file=sys.stderr)
            return 2
    else:
        print("pass --endpoint HOST:PORT (a running /quality endpoint) "
              "or --bundle DIR (a flight-recorder postmortem bundle)",
              file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(doc, indent=2))
        return 0
    _print_quality_report(doc)
    return 0


def _print_quality_report(doc: dict) -> None:
    if not doc.get("enabled", True):
        print("quality evaluation disabled ([quality] enabled=false "
              "or no evaluator attached)")
        return
    labels = doc.get("labels") or []
    overall = doc.get("overall") or {}
    beta = doc.get("beta", 0.5)
    print(f"model quality (threshold {doc.get('threshold')}, "
          f"F-beta beta={beta:g}, label lag {doc.get('max_lead')} rows):")
    cons = doc.get("conservation") or {}
    print(f"  captured {cons.get('captured', 0)} = "
          f"joined {cons.get('joined', 0)} + expired {cons.get('expired', 0)}"
          f" + shed {cons.get('shed', 0)} + pending {cons.get('pending', 0)}"
          f" (join errors: {doc.get('join_errors', 0)})")
    rows = [("overall", overall)]
    rows += [(f"v{v}", s) for v, s in sorted(
        (doc.get("versions") or {}).items())]
    print(f"  {'version':<10} {'n':>7} {'accuracy':>9} {'hamming':>9} "
          + " ".join(f"F:{label}" for label in labels))
    for name, s in rows:
        if not s or not s.get("n"):
            print(f"  {name:<10} {'0':>7} {'-':>9} {'-':>9}")
            continue
        fbeta = " ".join(
            f"{f:>8.3f}" for f in (s.get("fbeta") or []))
        print(f"  {name:<10} {s['n']:>7} {s['subset_accuracy']:>9.4f} "
              f"{s['hamming_loss']:>9.4f} {fbeta}")
    drift = doc.get("drift")
    if drift:
        print(f"  drift: max PSI {drift.get('max_psi', 0.0):.4f} over "
              f"{drift.get('rows', 0)} sampled rows "
              f"(prediction PSI {drift.get('prediction_psi')})")


def _print_perf_report(doc: dict, profile_text, *, top: int) -> None:
    ledger = doc.get("ledger") or {}
    programs = list(ledger.get("programs") or [])
    print("compile ledger"
          + (f" (backend {ledger['backend']})"
             if ledger.get("backend") else "") + ":")
    print(f"  compiles {ledger.get('compiles_total', 0)}"
          f" | compile time {ledger.get('compile_seconds_total', 0.0):.3f}s"
          f" | post-warmup recompiles"
          f" {ledger.get('unexpected_recompiles_total', 0)}"
          f" | cost-probe failures {ledger.get('cost_probe_failures', 0)}")
    if doc.get("mfu") is not None:
        print(f"  mfu {float(doc['mfu']) * 100:.2f}%")
    if programs:
        programs.sort(key=lambda p: -float(p.get("compile_seconds", 0.0)))
        print(f"  top {min(top, len(programs))} programs "
              f"by compile time:")
        # trace / lower / backend / cache / held: schema 2 (a bundle
        # written before it prints zeros and dashes)
        print(f"    {'program':<32} {'signature':<18} {'compiles':>8} "
              f"{'calls':>8} {'compile_s':>10} {'trace_s':>8} "
              f"{'lower_s':>8} {'backend_s':>9} {'cache':>5} "
              f"{'gflops':>9} {'held':>10}")
        for p in programs[:top]:
            held = (p.get("memory") or {}).get("reserved_bytes")
            print(f"    {str(p.get('program', '')):<32} "
                  f"{str(p.get('signature', ''))[:18]:<18} "
                  f"{p.get('compiles', 0):>8} {p.get('calls', 0):>8} "
                  f"{float(p.get('compile_seconds', 0.0)):>10.3f} "
                  f"{float(p.get('trace_s', 0.0)):>8.3f} "
                  f"{float(p.get('lower_s', 0.0)):>8.3f} "
                  f"{float(p.get('backend_compile_s', 0.0)):>9.3f} "
                  f"{str(p.get('cache') or '-'):>5} "
                  f"{float(p.get('flops', 0.0)) / 1e9:>9.3f} "
                  f"{_fmt_bytes(held) if held is not None else '-':>10}")
    untracked = ledger.get("untracked") or {}
    if untracked.get("by_name"):
        rows = sorted(
            untracked["by_name"].items(),
            key=lambda kv: -(kv[1]["trace_s"] + kv[1]["lower_s"]
                             + kv[1]["backend_compile_s"]))
        print(f"  compiled outside any tracked program: trace "
              f"{untracked['trace_s']:.3f}s | lower "
              f"{untracked['lower_s']:.3f}s | backend "
              f"{untracked['backend_compile_s']:.3f}s; by name:")
        for name, row in rows[:top]:
            print(f"    {name:<32} trace {row['trace_s']:>8.3f} "
                  f"lower {row['lower_s']:>8.3f} "
                  f"backend {row['backend_compile_s']:>8.3f} "
                  f"events {row['events']:>5}")
    memory = doc.get("memory") or {}
    if memory.get("samples"):
        leak = " | LEAK SUSPECTED" if memory.get("leak_suspected") else ""
        print("device memory:")
        print(f"  live {_fmt_bytes(memory.get('live_bytes', 0))}"
              f" | watermark {_fmt_bytes(memory.get('watermark_bytes', 0))}"
              f" | samples {memory.get('samples', 0)}{leak}")
        for owner, nbytes in sorted((memory.get("by_owner") or {}).items()):
            print(f"    {owner:<44} {_fmt_bytes(nbytes)}")
    fallbacks = doc.get("kernel_fallbacks") or {}
    if fallbacks:
        print("kernel fallbacks:")
        for key, n in sorted(fallbacks.items()):
            print(f"    {key:<44} {n}")
    if profile_text:
        from fmda_tpu.obs.pyprof import HostProfiler

        stacks = sorted(HostProfiler.parse_folded(profile_text).items(),
                        key=lambda kv: -kv[1])
        if stacks:
            total = sum(n for _, n in stacks)
            print(f"hottest host stacks ({total} samples):")
            for stack, n in stacks[:top]:
                frames = stack.split(";")
                leaf = frames[-1] if frames else stack
                root = frames[0] if frames else ""
                print(f"  {n:>7}  {root} ... {leaf}"
                      if len(frames) > 2 else f"  {n:>7}  {stack}")


def cmd_lint(args) -> int:
    """The static-analysis gate (docs/analysis.md).  Exit-code contract
    mirrors the serve-fleet gates: 0 = clean against the baseline,
    1 = new findings, 2 = usage error — CI scripts can gate on it
    directly and parse ``--json`` for the details."""
    import pathlib

    from fmda_tpu.analysis import default_rules, run_lint

    if not args.no_drift:
        import importlib.util

        if importlib.util.find_spec("jax") is None:
            print(
                "jax is not installed on this host — the jax-api-drift "
                "rule has nothing to resolve against; re-run with "
                "--no-drift",
                file=sys.stderr)
            return 2
    rules = default_rules(drift=not args.no_drift)
    if args.rule:
        by_id = {r.id: r for r in rules}
        unknown = [rid for rid in args.rule if rid not in by_id]
        if unknown:
            print(
                f"unknown rule(s): {', '.join(unknown)} "
                f"(available: {', '.join(sorted(by_id))})",
                file=sys.stderr)
            return 2
        rules = [by_id[rid] for rid in args.rule]
    baseline = pathlib.Path(args.baseline) if args.baseline else None
    if baseline is not None and not baseline.is_file():
        # only the *default* baseline may be absent (fresh tree); an
        # explicit path that resolves to nothing is a typo, and gating
        # against an empty register silently would defeat the gate
        print(f"baseline file not found: {baseline}", file=sys.stderr)
        return 2
    try:
        result = run_lint(rules, baseline_path=baseline)
    except ValueError as exc:  # malformed baseline (no justification, …)
        print(str(exc), file=sys.stderr)
        return 2
    if args.drift_report:
        if "jax_api_drift" not in result.reports:
            # --no-drift or a --rule filter excluded the drift rule:
            # silently leaving a stale inventory on disk would be worse
            # than refusing
            print(
                "--drift-report needs the jax-api-drift rule in the "
                "run (drop --no-drift / include --rule jax-api-drift)",
                file=sys.stderr)
            return 2
        out = pathlib.Path(args.drift_report)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(
            json.dumps(result.reports["jax_api_drift"], indent=2) + "\n")
    if args.sarif:
        from fmda_tpu.analysis import to_sarif

        out = pathlib.Path(args.sarif)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(
            json.dumps(to_sarif(result, rules), indent=2) + "\n")
    if args.json:
        print(json.dumps(result.as_dict(), indent=2))
        return 0 if result.ok else 1
    for f in result.new:
        print(f.format())
    for e in result.stale_baseline:
        print(
            f"stale baseline entry (debt paid — prune it): "
            f"[{e['rule']}] {e['path']}: {e['message']}",
            file=sys.stderr)
    for e in result.forbidden_baseline:
        print(
            f"forbidden baseline entry ([{e['rule']}] is a zero-baseline "
            f"hard gate — fix the code, never grandfather it): "
            f"{e['path']}: {e['message']}",
            file=sys.stderr)
    print(f"{result.n_modules} modules: {len(result.new)} new finding(s), "
          f"{len(result.baselined)} baselined, "
          f"{result.suppressed} suppressed, "
          f"{len(result.stale_baseline)} stale baseline entr"
          f"{'y' if len(result.stale_baseline) == 1 else 'ies'}, "
          f"{len(result.forbidden_baseline)} forbidden")
    return 0 if result.ok else 1


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="fmda_tpu", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    common = argparse.ArgumentParser(add_help=False)
    common.add_argument(
        "--config", default=None, metavar="JSON",
        help="FrameworkConfig overrides as JSON "
             "(fmda_tpu.config.save_config writes the full schema; "
             "partial files override sections). The CLI honors features/"
             "warehouse/bus/model/train; session and mesh apply to the "
             "library Application/Trainer APIs")
    common.add_argument(
        "--platform", choices=("cpu",), default=None,
        help="'cpu' forces the host platform. Without it a platform "
             "pinned from outside (JAX_PLATFORMS) is respected; with "
             "nothing pinned the command requires a TPU and exits "
             "non-zero when there is none — it never falls back to the "
             "CPU on its own")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("demo", parents=[common], help="synthetic end-to-end proof run")
    p.add_argument("--days", type=int, default=8)
    p.add_argument("--epochs", type=int, default=None,
                   help="default: config's train.epochs, or 2 standalone")
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--checkpoint-dir", default=None)
    p.set_defaults(fn=cmd_demo)

    p = sub.add_parser("ingest", parents=[common], help="fill a warehouse file")
    p.add_argument("--warehouse", required=True, help="sqlite file path")
    p.add_argument("--synthetic-days", type=int, default=0)
    p.add_argument("--replay", default=None, metavar="FIXTURES",
                   help="re-run a recorded session (RecordingTransport "
                        "file) through the real acquisition layer")
    p.add_argument("--replay-start", default="2020-02-07 09:30:00",
                   help="simulated clock start for --replay")
    p.add_argument("--ticks", type=int, default=0,
                   help="cap on --replay session ticks (0 = until close)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--engine-checkpoint", default=None)
    p.add_argument("--checkpoint-every", type=int, default=None)
    p.set_defaults(fn=cmd_ingest)

    p = sub.add_parser("train", parents=[common],
                       help="train over a warehouse file, or a token file")
    p.add_argument("--warehouse", default=None)
    p.add_argument("--tokens", default=None, metavar="FILE.npy",
                   help="train a token family (model.cell=decoder) over "
                        "one packed stream of int token ids")
    p.add_argument("--checkpoint-dir", default=None,
                   help="override config train.checkpoint_dir")
    p.add_argument("--epochs", type=int, default=None,
                   help="override config train.epochs (default 25)")
    p.add_argument("--batch-size", type=int, default=None,
                   help="override config train.batch_size (default 2)")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--continuous", action="store_true",
                   help="tail the warehouse and fine-tune continuously "
                        "([train] continuous_* knobs; versioned "
                        "checkpoints + drift profiles per round)")
    p.add_argument("--max-rounds", type=int, default=None,
                   help="bound --continuous fine-tune rounds "
                        "(default: until the warehouse quiesces)")
    p.add_argument("--jax-profile", default=None, metavar="DIR",
                   help="capture a jax device profile of the run "
                        "(TensorBoard/XProf): the step loop's host "
                        "spans beside the compiled steps' named scopes")
    p.set_defaults(fn=cmd_train)

    p = sub.add_parser("backtest", parents=[common], help="score a checkpoint over history")
    p.add_argument("--warehouse", required=True)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--window", type=int, default=None,
                   help="override config train.window (default 30)")
    p.add_argument("--threshold", type=float, default=None)
    p.set_defaults(fn=cmd_backtest)

    p = sub.add_parser("serve", parents=[common], help="prediction daemon over a warehouse")
    p.add_argument("--warehouse", required=True)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--window", type=int, default=None,
                   help="override config train.window (default 30)")
    p.add_argument("--threshold", type=float, default=None,
                   help="label decision threshold (match your backtest)")
    p.add_argument("--poll-interval-s", type=float, default=0.5)
    p.add_argument("--duration-s", type=float, default=0.0)
    p.add_argument("--once", action="store_true",
                   help="one poll pass, then exit")
    p.add_argument("--from-start", action="store_true",
                   help="serve existing history too, not just new rows")
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser(
        "serve-fleet", parents=[common],
        help="multi-tenant micro-batching runtime vs a synthetic fleet")
    p.add_argument("--role",
                   choices=("solo", "broker", "router", "worker", "local"),
                   default="solo",
                   help="'solo' (default) runs the single-process fleet "
                        "runtime; the multi-host topology "
                        "(fmda_tpu.fleet, docs/multihost.md) splits into "
                        "'broker' (bus + bus server only — the local "
                        "Kafka stand-in), 'router' (session routing + "
                        "membership + migration, jax-free), 'worker' "
                        "(one slot-range owner), and 'local' (one "
                        "command: broker + N workers spawned, router "
                        "inline, synthetic load)")
    p.add_argument("--workers", type=int, default=None,
                   help="worker-process count for --role local/router "
                        "(default: config fleet.n_workers)")
    p.add_argument("--listen", type=int, default=None,
                   help="bus-server port for --role router (0 = "
                        "ephemeral; default: config fleet.port)")
    p.add_argument("--connect", default=None, metavar="HOST:PORT",
                   help="router bus-server address for --role worker")
    p.add_argument("--worker-id", default=None,
                   help="this worker's id (--role worker); the router "
                        "routes its slot-range to fleet_ticks_<id>")
    p.add_argument("--shared-bus", action="store_true",
                   help="--role worker: do the data plane on the shared "
                        "--connect bus too (an external broker topology, "
                        "e.g. Kafka-shaped) instead of hosting this "
                        "worker's own inbox/results bus")
    p.add_argument("--wire-format", default=None,
                   choices=["auto", "binary", "json"],
                   help="frame encoding on every SocketBus link "
                        "(overrides [fleet] wire_format; json = the "
                        "rollback format, docs/multihost.md)")
    p.add_argument("--duration-s", type=float, default=0.0,
                   help="safety-valve runtime bound for --role "
                        "worker/router (0 = until stopped)")
    p.add_argument("--storm-every", type=int, default=0,
                   help="adversarial reconnect storm: every N load "
                        "rounds, close + instantly reopen a burst of "
                        "sessions (0 = off)")
    p.add_argument("--storm-fraction", type=float, default=0.25,
                   help="fraction of sessions hit per reconnect storm")
    p.add_argument("--burst-every", type=int, default=0,
                   help="synchronized burst (market-open spike): every "
                        "N rounds EVERY session ticks for "
                        "--burst-rounds consecutive rounds (0 = off)")
    p.add_argument("--burst-rounds", type=int, default=1,
                   help="consecutive all-tick rounds per burst")
    p.add_argument("--slow-fraction", type=float, default=0.0,
                   help="fraction of sessions that are slow-drip "
                        "stragglers ticking at --slow-duty instead of "
                        "--duty (long-lived barely-ticking clients)")
    p.add_argument("--slow-duty", type=float, default=0.05,
                   help="tick probability per round for the slow-drip "
                        "straggler set")
    p.add_argument("--no-controller", action="store_true",
                   help="--role router/local: disable the adaptive "
                        "control plane (fmda_tpu.control; on by default "
                        "whenever fleet telemetry is) — fixed linger, "
                        "no autoscaling, global oldest-drop shedding")
    p.add_argument("--tenant-mix", default=None,
                   metavar="CLASS:WEIGHT,...",
                   help="--role local: tenant-labeled traffic mix, e.g. "
                        "'gold:1,standard:4' — sessions are assigned a "
                        "priority class weight-proportionally and opened "
                        "labeled (per-tenant QoS applies when [control] "
                        "tenant_classes configures the policy); "
                        "composable with --burst-every/--storm-every/"
                        "--slow-fraction")
    p.add_argument("--replay", action="store_true",
                   help="--role solo/local: historical backfill — serve "
                        "the [replay] config section's history source "
                        "(seeded synthetic or warehouse bulk reads) "
                        "through the unmodified serving path at max "
                        "speed on a virtual clock (the rows' own "
                        "timestamps; no wall-clock pacing), instead of "
                        "the cadence-shaped synthetic load "
                        "(docs/replay.md)")
    p.add_argument("--hot-swap", action="store_true",
                   help="with --replay: land a fresh-seed checkpoint "
                        "into the live fleet halfway through the "
                        "backfill — zero dropped sessions, zero "
                        "recompiles; results carry weights_version "
                        "from the swap barrier on")
    p.add_argument("--continuous-train", action="store_true",
                   help="--role solo: run the continuous fine-tuning "
                        "loop beside the serving gateway — a synthetic "
                        "corpus warehouse is tailed, fine-tuned on a "
                        "sliding window, and every round's checkpoint "
                        "hot-swaps into the live pool (zero serving "
                        "recompiles; [train] continuous_* knobs, "
                        "docs/training.md)")
    p.add_argument("--swap-guard", action="store_true",
                   help="with --continuous-train: shadow-score every "
                        "candidate against the incumbent before the "
                        "swap (fmda_tpu.eval.shadow; refusals keep the "
                        "incumbent serving and are counted)")
    p.add_argument("--continuous-days", type=int, default=2,
                   help="synthetic corpus size (trading days) for the "
                        "--continuous-train warehouse")
    p.add_argument("--train-rounds", type=int, default=None,
                   help="bound --continuous-train fine-tune rounds "
                        "(default: until the backlog quiesces)")
    p.add_argument("--train-checkpoint-dir", default=None,
                   help="--continuous-train checkpoint directory "
                        "(default: config train.checkpoint_dir)")
    p.add_argument("--chaos-plan", default=None, metavar="FILE",
                   help="--role local: run the chaos soak under this "
                        "fault-plan JSON (fmda_tpu.chaos.FaultPlan; "
                        "docs/chaos.md) instead of the plain load; "
                        "'generate' derives a plan from the config's "
                        "[chaos] knobs + seed.  Exits 1 iff a "
                        "never-abort gate fails")
    p.add_argument("--chaos-no-reference", action="store_true",
                   help="skip the unfaulted reference run (and with it "
                        "the bit-identity gate) — faster soak, "
                        "accounting + failover gates only")
    p.add_argument("--trace-dir", default=None, metavar="DIR",
                   help="--role local: enable tracing in every process "
                        "and write one trace file per process into DIR "
                        "(merge: `python -m fmda_tpu trace --merge DIR`)")
    p.add_argument("--sessions", type=int, default=64,
                   help="concurrent ticker sessions (pool capacity grows "
                        "to fit when the config's is smaller)")
    p.add_argument("--ticks", type=int, default=100,
                   help="submission rounds over the fleet")
    p.add_argument("--duty", type=float, default=1.0,
                   help="fraction of sessions ticking per round")
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--cell", default=None, choices=["gru", "lstm", "ssm"],
                   help="carried-state cell family for the serving "
                        "pool (overrides [model] cell; default env "
                        "FMDA_FLEET_CELL, else the config).  'ssm' is "
                        "the O(1)-cache family — GRU-vs-SSM ticks/s at "
                        "equal --hidden is two runs of this command "
                        "(docs/runtime.md 'The SSM cell family')")
    p.add_argument("--window", type=int, default=None,
                   help="override config runtime.window (default 30)")
    p.add_argument("--bucket-sizes", default=None, metavar="N,N,...",
                   help="override config runtime.bucket_sizes "
                        "(ascending; each is one compiled program)")
    p.add_argument("--max-linger-ms", type=float, default=None,
                   help="override config runtime.max_linger_ms")
    p.add_argument("--queue-bound", type=int, default=None,
                   help="override config runtime.queue_bound")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--predictor", action="store_true",
                   help="serve the window-re-scan Predictor path "
                        "instead of carried-state sessions: "
                        "predict-timestamp signals over a synthetic "
                        "corpus, batched into bucketed (B, window, F) "
                        "forwards (runtime.predictor_* knobs; "
                        "docs/runtime.md 'Batched Predictor path')")
    p.add_argument("--predictor-days", type=int, default=3,
                   help="synthetic corpus size for --predictor (days)")
    p.add_argument("--signals", type=int, default=0,
                   help="signal count for --predictor (0 = every "
                        "servable warehouse timestamp)")
    p.add_argument("--burst", type=int, default=32,
                   help="signals published per poll for --predictor "
                        "(the engine's signal-after-commit burst shape)")
    p.add_argument("--ring", action="store_true", default=None,
                   help="enable the device-resident window ring for "
                        "--predictor (runtime.predictor_ring: "
                        "consecutive signals re-send only new rows)")
    p.add_argument("--serial", action="store_true", default=None,
                   help="disable the one-deep flush overlap pipeline "
                        "(runtime.pipeline_depth=0; bit-identical A/B "
                        "reference for the default overlapped path)")
    p.add_argument("--shard-pool", action="store_true", default=None,
                   help="shard the session pool's slot axis across the "
                        "configured device mesh (runtime.shard_pool; "
                        "1-device meshes degrade to the unsharded pool)")
    p.add_argument("--slo-p99-ms", type=float, default=None,
                   help="latency-SLO gate: exit 1 unless p99 of "
                        "submit->publish stays under this bound "
                        "(overrides config runtime.slo_p99_ms)")
    p.add_argument("--slo-soft", action="store_true",
                   help="report the SLO verdict in the JSON but never "
                        "fail the run (loaded-host escape hatch)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve /metrics + /healthz + /snapshot on this "
                        "port during the run (0 = ephemeral); for "
                        "--role router/local this is the fleet "
                        "telemetry endpoint (+ /query + /alerts)")
    p.add_argument("--postmortem-dir", default=None, metavar="DIR",
                   help="--role router/local: flight-recorder bundle "
                        "directory (overrides [slo] postmortem_dir) — "
                        "an SLO alert firing or an injected chaos fault "
                        "dumps a rotated postmortem bundle there")
    p.add_argument("--metrics-hold-s", type=float, default=0.0,
                   help="keep the metrics endpoint up this long after "
                        "the load finishes (curl/promtool demos)")
    p.add_argument("--trace", action="store_true",
                   help="enable end-to-end tick tracing for the run "
                        "(fmda_tpu.obs.trace; spans also served on "
                        "/trace when --metrics-port is up)")
    p.add_argument("--trace-sample", type=float, default=1.0,
                   help="trace sampling rate in [0,1] (default 1.0 — "
                        "every tick; production fleets run ~0.01)")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="write the span ring as Chrome/Perfetto "
                        "trace_event JSON after the load (implies "
                        "--trace; inspect with `python -m fmda_tpu "
                        "trace --input FILE` or ui.perfetto.dev)")
    p.add_argument("--jax-profile", default=None, metavar="DIR",
                   help="capture a jax device profile of the load "
                        "(TensorBoard/XProf), pool flushes annotated "
                        "as numbered steps")
    p.set_defaults(fn=cmd_serve_fleet)

    p = sub.add_parser(
        "status", parents=[common],
        help="pretty-print an observability snapshot + health verdict")
    p.add_argument("--endpoint", default=None, metavar="HOST:PORT",
                   nargs="+",
                   help="scrape running endpoints' /snapshot + /healthz "
                        "instead of building a local app; several "
                        "endpoints (one per fleet worker) report "
                        "per-worker + aggregate health")
    p.add_argument("--warehouse", default=None,
                   help="warehouse file for the local snapshot (default: "
                        "config's path)")
    p.add_argument("--watch", type=float, default=None, metavar="N",
                   help="live-refresh mode: re-scrape and redraw every "
                        "N seconds until Ctrl-C (clean exit 0) — watch "
                        "a soak without a shell loop")
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser(
        "trace", parents=[common],
        help="per-stage latency attribution for recorded tick traces")
    p.add_argument("--input", default=None, metavar="FILE",
                   help="Chrome/Perfetto trace_event JSON file "
                        "(serve-fleet --trace-out)")
    p.add_argument("--endpoint", default=None, metavar="HOST:PORT",
                   help="scrape a running endpoint's /trace instead")
    p.add_argument("--merge", nargs="+", default=None, metavar="PATH",
                   help="stitch per-process --trace-out files into one "
                        "trace by trace id (timelines aligned on shared "
                        "journeys); each PATH may be a file, a glob, or "
                        "a directory of *.json trace files (a topology's "
                        "--trace-dir merges in one command); with --out "
                        "writes the merged Perfetto JSON, without it "
                        "shows the attribution over the merged document")
    p.add_argument("--out", default=None, metavar="FILE",
                   help="write the --merge result to this file")
    p.add_argument("--last", type=int, default=10,
                   help="show the newest N traces (default 10)")
    p.add_argument("--slowest", type=int, default=None, metavar="N",
                   help="show the N slowest traces by e2e duration "
                        "instead of the newest")
    p.add_argument("--min-ms", type=float, default=None,
                   help="only traces with e2e duration >= this (ms)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output (grouped trace dicts)")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser(
        "perf", parents=[common],
        help="device/compiler performance report: compile ledger, "
             "MFU, memory watermarks, hottest host stacks")
    p.add_argument("--endpoint", default=None, metavar="HOST:PORT",
                   help="scrape a running endpoint's /device (+ "
                        "/profile) — a serve-fleet worker or the "
                        "fleet telemetry endpoint")
    p.add_argument("--input", default=None, metavar="FILE",
                   help="saved device report JSON instead: a "
                        "flight-recorder bundle's device.json")
    p.add_argument("--profile", default=None, metavar="FILE",
                   help="folded-stack profile text to report hottest "
                        "stacks from (a bundle's profile.folded); "
                        "--endpoint fetches /profile automatically")
    p.add_argument("--top", type=int, default=10,
                   help="rows per table: top programs, hottest "
                        "stacks (default 10)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report (the device report "
                        "document, plus profile_folded when present)")
    p.set_defaults(fn=cmd_perf)

    p = sub.add_parser(
        "quality", parents=[common],
        help="model-quality report: per-weights-version live "
             "accuracy/F-beta, drift vs the training profile, "
             "capture/join conservation")
    p.add_argument("--endpoint", default=None, metavar="HOST:PORT",
                   help="scrape a running endpoint's /quality (the "
                        "fleet telemetry endpoint)")
    p.add_argument("--bundle", default=None, metavar="DIR",
                   help="read a flight-recorder postmortem bundle's "
                        "quality.json instead")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report (the /quality "
                        "document verbatim)")
    p.set_defaults(fn=cmd_quality)

    p = sub.add_parser(
        "chaos-pipeline", parents=[common],
        help="data-plane chaos soak: feeds -> engine -> journaled "
             "warehouse -> predictor under a seeded fault plan "
             "(docs/chaos.md); exit 1 iff a never-abort gate fails")
    p.add_argument("--seed", type=int, default=None,
                   help="plan + market seed (default: [chaos] seed)")
    p.add_argument("--rounds", type=int, default=30,
                   help="virtual steps the plan schedules over")
    p.add_argument("--plan", default=None, metavar="FILE",
                   help="explicit fault-plan JSON instead of the "
                        "seeded data-plane schedule (the reproduction "
                        "path)")
    p.add_argument("--no-predictor", action="store_true",
                   help="skip the jitted Predictor stage (jax-free, "
                        "faster; drops the probes-served gate)")
    p.add_argument("--no-reference", action="store_true",
                   help="skip the unfaulted reference replay (faster; "
                        "drops the bit-identity gate)")
    p.set_defaults(fn=cmd_chaos_pipeline)

    p = sub.add_parser(
        "lint",
        help="framework-aware static analysis gate (docs/analysis.md)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable result document (schema "
                        "covered by tests/test_analysis.py)")
    p.add_argument("--rule", action="append", default=None, metavar="ID",
                   help="run only this rule (repeatable); baseline "
                        "entries for other rules are ignored, not stale")
    p.add_argument("--baseline", default=None, metavar="FILE",
                   help="baseline JSON of grandfathered findings "
                        "(default: fmda_tpu/analysis/baseline.json)")
    p.add_argument("--no-drift", action="store_true",
                   help="skip the JAX API-drift resolver — the one rule "
                        "that imports jax (fast editor loops, jax-free "
                        "hosts)")
    p.add_argument("--drift-report", default=None, metavar="FILE",
                   help="write the machine-readable jax drift inventory "
                        "(the porting work-list artifact: "
                        "artifacts/jax_api_drift.json in this repo)")
    p.add_argument("--sarif", default=None, metavar="FILE",
                   help="write the run as a SARIF 2.1.0 document (new "
                        "findings as results, baselined ones suppressed) "
                        "— what CI uploads to render findings as diff "
                        "annotations")
    p.set_defaults(fn=cmd_lint)
    return parser


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    try:
        return args.fn(args)
    except BrokenPipeError:
        # stdout consumer (head, a closed pager) went away mid-print —
        # normal unix behavior, not an error
        try:
            sys.stdout.close()
        except BrokenPipeError:
            pass
        return 0


if __name__ == "__main__":
    raise SystemExit(main())
