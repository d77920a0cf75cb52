"""Benchmark: biGRU training throughput, TPU (fmda_tpu) vs CPU (torch ref).

Prints ONE JSON line (always — even when every phase fails):

  {"metric": ..., "value": N, "unit": "seq/s", "vs_baseline": N,
   "backend": ..., "device_kind": ..., "fallback": bool,
   "phases": {name: {...} | {"error": ...}, ...}}

- value: sequences/second/chip of the full fmda_tpu training step (forward +
  weighted BCE + backward + global-norm clip + Adam + all four metrics) on
  the flagship config (108 features, hidden 32, window 30) at batch 256.
- vs_baseline: ratio against the same training step implemented with torch
  on CPU — the reference's actual execution mode (its CUDA dispatch never
  moves the inputs, biGRU_model.py:195-196; BASELINE.md), scaled to the
  same batch size for fairness.
- phases: per-config results — flagship with/without the Pallas kernel,
  the long-context north-star (seq 1024, 10 book levels, remat) and the
  50-ticker batched config (BASELINE.json configs[1-3]), each with
  step-time and an analytic model-FLOPs/MFU estimate.

Every phase runs in its OWN subprocess with a hard timeout, and the final
JSON line is printed no matter which phases died.  The parent never
touches jax — a chip belongs to one process, and each phase child takes
it in turn.  ``main()`` measures the TPU: where the platform is pinned to
anything else it exits non-zero before any phase, and every device phase
child runs with ``JAX_PLATFORMS=tpu`` so none can land on the CPU.
``--phase NAME`` runs one phase wherever jax lands, labelled with the
backend, device kind and device count it ran on.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))
if _REPO_DIR not in sys.path:
    sys.path.insert(0, _REPO_DIR)

from fmda_tpu.utils.env import cpu_forced_env, device_report  # noqa: E402

BATCH = 256
WINDOW = 30
FEATURES = 108
HIDDEN = 32
CLASSES = 4

GLOBAL_BUDGET_S = 1500.0


def model_flops_per_step(batch: int, seq: int, features: int, hidden: int) -> float:
    """Analytic FLOPs of one train step of the bidirectional GRU.

    Matmul-only (gates/head elementwise work is VPU noise): per direction,
    input projection ``x @ W_ih^T`` is 2*B*T*F*3H and the recurrence is
    T * 2*B*H*3H; the head is 2*B*3H*C.  Train step ~= 3x forward
    (backward ~= 2x forward).
    """
    fwd = 2 * (2 * batch * seq * features * 3 * hidden
               + seq * 2 * batch * hidden * 3 * hidden) \
        + 2 * batch * 3 * hidden * CLASSES
    return 3.0 * fwd


def attn_flops_per_step(batch: int, seq: int, features: int, hidden: int,
                        n_layers: int = 1) -> float:
    """Analytic matmul FLOPs of one temporal-transformer train step:
    embed + per-layer (qkv, QK^T, AV, proj, 4x MLP) + head; train ~= 3x
    forward.  The T^2 terms are the attention scores/values (all heads
    together contract to 2*B*T*T*H each)."""
    per_layer = (2 * batch * seq * hidden * 3 * hidden
                 + 2 * batch * seq * seq * hidden * 2
                 + 2 * batch * seq * hidden * hidden
                 + 2 * batch * seq * hidden * 4 * hidden * 2)
    fwd = (2 * batch * seq * features * hidden
           + n_layers * per_layer
           + 2 * batch * 3 * hidden * CLASSES)
    return 3.0 * fwd


def _mfu(flops_per_step: float, step_time_s: float, device_kind: str):
    """Analytic model FLOP/s over the device kind's published peak
    (``fmda_tpu.obs.device.DEVICE_PEAKS``); ``None`` — not an estimate —
    for a kind that has no entry there."""
    from fmda_tpu.obs.device import DEVICE_PEAKS

    peaks = DEVICE_PEAKS.get(device_kind)
    if peaks is None:
        return None
    return round(flops_per_step / step_time_s / peaks[0], 4)


# ---------------------------------------------------------------------------
# Phases (each runs in its own subprocess; prints one JSON line on stdout)
# ---------------------------------------------------------------------------


def _slope_time(window_fn, *, target_s: float = 2.5, repeats: int = 3) -> float:
    """Steady-state per-step seconds from two window sizes.

    ``window_fn(n)`` must run n steps and end with a **host fetch** of some
    step output — a value on the host is a completion barrier by
    construction.  Two window sizes are timed (best of ``repeats`` each)
    and the per-step cost is the slope ``(t_hi - t_lo) / (hi - lo)``: the
    constant per-window cost (the closing fetch, dispatch tails) cancels
    in the difference.  Whether ``jax.block_until_ready`` alone is barrier
    enough on the chip is measured by ``chip_smoke.py`` (chained pool
    steps ended either way); the benchmark of record decides from that
    whether this method stays.
    Window sizes adapt so the large window covers ~``target_s`` of compute
    without wasting minutes on slow backends.
    A non-positive slope (jitter larger than the window delta) retries with
    4x the window; if it persists, RuntimeError — never a silently absurd
    throughput number.
    """
    # size the windows from a *slope* estimate too: window_fn(8)/8 alone
    # carries the constant per-window cost and would undersize hi
    t8, t24 = window_fn(8), window_fn(24)
    t1 = (t24 - t8) / 16 if t24 > t8 else max(t24 / 24, 1e-9)
    hi = int(min(512, max(44, target_s / max(t1, 1e-9))))
    if t1 > 0.25:
        # slow (CPU) backend: jitter is negligible relative to the
        # step itself, so shrink the windows/repeats instead of spending
        # minutes inside a phase-subprocess budget
        hi, repeats = 24, 1
    tried = None
    for _ in range(2):
        lo = max(4, hi // 11)
        t_lo = t_hi = float("inf")
        for _ in range(max(1, repeats)):
            t_lo = min(t_lo, window_fn(lo))
            t_hi = min(t_hi, window_fn(hi))
        if t_hi > t_lo:
            return (t_hi - t_lo) / (hi - lo)
        tried = (lo, hi, t_lo, t_hi)
        hi = min(4096, hi * 4)  # noise-dominated: widen and retry once
    lo, hi, t_lo, t_hi = tried
    raise RuntimeError(
        f"slope timing noise-dominated: t_lo={t_lo:.4f}s t_hi={t_hi:.4f}s "
        f"at windows ({lo}, {hi})")


def _bench_train_step(
    *,
    batch: int,
    window: int,
    features: int,
    use_pallas: bool,
    dtype: str = "float32",
    remat: bool = False,
    warmup: int = 3,
    repeats: int = 3,
    hidden: int = HIDDEN,
    cell: str = "gru",
) -> dict:
    import jax
    import jax.numpy as jnp

    from fmda_tpu.config import ModelConfig, TrainConfig
    from fmda_tpu.data.pipeline import Batch
    from fmda_tpu.train.trainer import Trainer

    model_cfg = ModelConfig(
        hidden_size=hidden, n_features=features, output_size=CLASSES,
        dropout=0.5, spatial_dropout=True, use_pallas=use_pallas,
        dtype=dtype, remat=remat, cell=cell,
    )
    train_cfg = TrainConfig(batch_size=batch, window=window)
    weight = np.full(CLASSES, 2.0, np.float32)
    pos_weight = np.full(CLASSES, 3.0, np.float32)
    trainer = Trainer(model_cfg, train_cfg, weight=weight, pos_weight=pos_weight)
    state = trainer.init_state(jax.random.PRNGKey(0))

    r = np.random.default_rng(0)
    b = Batch(
        x=jnp.asarray(r.normal(size=(batch, window, features)).astype(np.float32)),
        y=jnp.asarray((r.uniform(size=(batch, CLASSES)) > 0.7).astype(np.float32)),
        mask=jnp.ones(batch, np.float32),
    )
    rng = jax.random.PRNGKey(1)

    for _ in range(warmup):
        state, vals = trainer.single_step(state, b, rng)
    float(vals.loss)

    # Slope timing (see _slope_time): two window sizes, each ended by a
    # host fetch; the constant per-window cost cancels in the difference.
    holder = {"state": state}

    def window_fn(n: int) -> float:
        # as the step loop runs it: the pass's totals ride through the step
        st, totals = holder["state"], trainer.zero_totals()
        t0 = time.perf_counter()
        for _ in range(n):
            st, totals = trainer._train_step(st, totals, b, rng)
        float(totals.loss)  # host fetch: the window's completion barrier
        holder["state"] = st
        return time.perf_counter() - t0

    step_s = _slope_time(window_fn, repeats=max(1, repeats))

    # optional device profile (XProf trace) of a few post-measurement
    # steps: FMDA_PROFILE_DIR=/path python bench.py
    profile_dir = os.environ.get("FMDA_PROFILE_DIR")
    if profile_dir:
        from fmda_tpu.utils.tracing import device_trace, step_annotation

        state = holder["state"]  # the pre-timing state's buffers were donated
        with device_trace(profile_dir):
            for i in range(3):
                with step_annotation("bench_train_step", i):
                    state, vals = trainer.single_step(state, b, rng)
            float(vals.loss)  # host fetch barrier

    dev = jax.devices()[0]
    if cell == "attn":
        flops = attn_flops_per_step(batch, window, features, hidden,
                                    n_layers=model_cfg.n_layers)
    else:
        flops = model_flops_per_step(batch, window, features, hidden)
    mfu_est = _mfu(flops, step_s, dev.device_kind)
    # what actually ran: availability AND the per-shape VMEM gate —
    # at MXU-wide H the GRU/LSTM families auto-select lax.scan
    # (fmda_tpu.ops.gru.select_scan_fn) and this reports that
    # truthfully; the attn family's dispatch is internal to ops.mha
    # (flash kernel on TPU when the shape fits, jnp online softmax
    # elsewhere)
    itemsize = jnp.dtype(dtype).itemsize
    if cell == "attn":
        from fmda_tpu.ops.attention import flash_dispatch

        # the model's apply passes no attention mask for fully-valid
        # batches (models/attn.py), which is what this bench feeds
        kernel_active = flash_dispatch(
            window, window, hidden // model_cfg.n_heads,
            use_flash=use_pallas)
        path = "pallas-flash" if kernel_active else "jnp-online-softmax"
    elif cell == "lstm":
        from fmda_tpu.ops.lstm import lstm_scan, select_lstm_scan_fn

        kernel_active = select_lstm_scan_fn(
            use_pallas, shape=(batch, window, hidden), itemsize=itemsize,
        ) is not lstm_scan
        path = "pallas" if kernel_active else "lax.scan"
    else:
        from fmda_tpu.ops.gru import gru_scan, select_scan_fn

        kernel_active = select_scan_fn(
            use_pallas, shape=(batch, window, hidden), itemsize=itemsize,
        ) is not gru_scan
        path = "pallas" if kernel_active else "lax.scan"
    result = {
        "seq_s": round(batch / step_s, 1),
        "step_ms": round(step_s * 1e3, 3),
        **device_report(),
        "pallas_active": kernel_active,
        "scan_path": path,
        "dtype": dtype,
        "tflops_per_step": round(flops / 1e12, 4),
        "mfu_est": mfu_est,
        "shape": {"B": batch, "T": window, "F": features, "H": hidden},
        "cell": cell,
    }
    if profile_dir:
        result["profile_dir"] = profile_dir
    return result


def phase_flagship(use_pallas: bool, dtype: str = "float32") -> dict:
    return _bench_train_step(
        batch=BATCH, window=WINDOW, features=FEATURES, use_pallas=use_pallas,
        dtype=dtype,
    )


def phase_flagship_wide() -> dict:
    """MXU-utilization probe: the flagship protocol scaled to hidden=1024
    (bf16, batch 512).  The flagship's H=32 gates are too small to light up
    the 128x128 systolic array, so its MFU is structurally tiny; this phase
    shows what the same train step does when the matmuls are MXU-shaped —
    the number that speaks to the framework's performance ceiling rather
    than the reference's model size."""
    import jax

    if jax.default_backend() == "cpu":
        # a CPU H=1024 step would just burn the whole subprocess
        # timeout.  "skipped", not "error": an accelerator-only phase
        # sitting out a `--phase` run on the CPU is designed, not a failure
        return {"skipped": "cpu backend; the MXU-wide step needs an "
                           "accelerator"}
    # use_pallas=True here is the *auto* path: at H=1024 the kernel's
    # VMEM working set fails fmda_tpu.ops.pallas_gru.kernel_supported, so
    # select_scan_fn picks lax.scan — whose per-step (B,H)x(H,3H) matmul
    # is MXU-shaped at this width.  The result's scan_path/pallas_active
    # fields record the decision; kernel_sweep carries the measured
    # kernel-vs-scan crossover in H.
    return _bench_train_step(
        batch=512, window=WINDOW, features=FEATURES,
        use_pallas=True, dtype="bfloat16", hidden=1024,
        warmup=2,
    )


def phase_longctx() -> dict:
    """North-star long-context config: seq 1024, 10 book levels, remat."""
    from fmda_tpu.config import FeatureConfig

    features = len(FeatureConfig(bid_levels=10, ask_levels=10).x_fields())
    return _bench_train_step(
        batch=16, window=1024, features=features,
        use_pallas=True, remat=True, warmup=2,
    )


def phase_longctx_attn(dtype: str = "float32") -> dict:
    """Long-context via the attention family (cell="attn"): same
    seq-1024 windows as phase_longctx but through the temporal
    transformer — all batched matmuls, no serial scan; the single-device
    twin of the ring-attention sp path.  The bf16 variant is the MXU
    dtype the flash kernel is built for (bf16 operands, f32
    accumulators in VMEM)."""
    from fmda_tpu.config import FeatureConfig

    features = len(FeatureConfig(bid_levels=10, ask_levels=10).x_fields())
    # use_pallas opts the attn family into the flash kernel on TPU
    # (T=1024 is in-envelope; jnp online softmax elsewhere)
    return _bench_train_step(
        batch=16, window=1024, features=features,
        use_pallas=True, remat=True, warmup=2, cell="attn", dtype=dtype,
    )


def phase_multiticker() -> dict:
    """North-star 50-ticker config at the REAL composition: mixed batches
    of 16 windows from each of 50 tickers (800 rows/step) composed by
    MultiTickerDataset.mixed_batches, per-ticker normalization included —
    not a synthetic monolithic batch."""
    import jax

    from fmda_tpu.config import ModelConfig, TrainConfig
    from fmda_tpu.data import ArraySource
    from fmda_tpu.train.multiticker import MultiTickerDataset
    from fmda_tpu.train.trainer import Trainer

    n_tickers, per_ticker = 50, 16
    rows_per_ticker = 260
    r = np.random.default_rng(0)
    fields = tuple(f"f{i}" for i in range(FEATURES))
    sources = {
        f"T{i:02d}": ArraySource(
            r.normal(size=(rows_per_ticker, FEATURES)).astype(np.float32),
            (r.uniform(size=(rows_per_ticker, CLASSES)) > 0.7).astype(
                np.float32),
            fields,
        )
        for i in range(n_tickers)
    }
    mtd = MultiTickerDataset(sources, chunk_size=100, window=WINDOW)
    train_chunks, _, _ = mtd.splits(0.1, 0.1)
    round0 = mtd.rounds(train_chunks)[0]

    model_cfg = ModelConfig(
        hidden_size=HIDDEN, n_features=FEATURES, output_size=CLASSES,
        dropout=0.5, spatial_dropout=True, use_pallas=True,
    )
    batch = n_tickers * per_ticker
    trainer = Trainer(model_cfg, TrainConfig(batch_size=batch, window=WINDOW))
    state = trainer.init_state(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)

    # host-side composition cost, measured separately from the step
    t0 = time.perf_counter()
    staged = list(mtd.mixed_batches(round0, per_ticker))
    compose_s = time.perf_counter() - t0

    # device-resident copies: the step number must measure compute, not
    # the per-step ~10 MB host->device transfer a host-resident numpy
    # batch smuggles into _train_step.  Only a small rotating subset is
    # staged: the slope loop needs enough distinct batches to dodge
    # cache effects, not the whole round resident in HBM.
    staged_dev = [jax.device_put(b) for b in staged[:3]]

    for b in staged_dev[:2]:
        state, vals = trainer.single_step(state, b, rng)
    float(vals.loss)

    # slope-timed device step over the staged batches
    holder = {"state": state}

    def window_fn(n: int) -> float:
        st, totals = holder["state"], trainer.zero_totals()
        t0 = time.perf_counter()
        for i in range(n):
            st, totals = trainer._train_step(
                st, totals, staged_dev[i % len(staged_dev)], rng)
        float(totals.loss)
        holder["state"] = st
        return time.perf_counter() - t0

    step_s = _slope_time(window_fn)

    # the production path (Trainer.fit_multi): background-thread
    # composition + double-buffered device transfer — steady state is
    # max(compose, step), not their sum
    from fmda_tpu.data.pipeline import background_compose, prefetch_to_device

    state, totals = holder["state"], trainer.zero_totals()
    for b in prefetch_to_device(background_compose(
            mtd.mixed_batches(round0, per_ticker))):
        state, totals = trainer._train_step(state, totals, b, rng)
    float(totals.loss)  # warm the overlapped path
    totals = trainer.zero_totals()
    t0 = time.perf_counter()
    pipeline_steps = 0
    for _ in range(3):
        for b in prefetch_to_device(background_compose(
                mtd.mixed_batches(round0, per_ticker))):
            state, totals = trainer._train_step(state, totals, b, rng)
            pipeline_steps += 1
    float(totals.loss)  # host fetch: completion barrier
    pipeline_s = (time.perf_counter() - t0) / pipeline_steps

    dev = jax.devices()[0]
    flops = model_flops_per_step(batch, WINDOW, FEATURES, HIDDEN)
    mfu_est = _mfu(flops, step_s, dev.device_kind)
    # the overlap claim ("steady state is max(compose, step)") only holds
    # when the step runs on an accelerator — on a CPU backend the compose
    # thread and the XLA step compete for the same cores, so pipeline >=
    # plain is EXPECTED there, not a regression.
    # On an accelerator the bar is the real overlap target max(step,
    # compose); on CPU merely not regressing past the serial sum.
    on_accel = jax.default_backend() != "cpu"
    compose_per = compose_s / len(staged)
    if on_accel:
        overlap_effective = pipeline_s <= max(step_s, compose_per) * 1.25
    else:
        overlap_effective = pipeline_s <= (step_s + compose_per) * 1.1
    return {
        "seq_s": round(batch / step_s, 1),
        "step_ms": round(step_s * 1e3, 3),
        "pipeline_step_ms": round(pipeline_s * 1e3, 3),
        "pipeline_seq_s": round(batch / pipeline_s, 1),
        "compose_ms_per_batch": round(compose_s / len(staged) * 1e3, 3),
        "overlap_effective": bool(overlap_effective),
        "overlap_note": (
            "pipeline overlap is host-vs-device; on a cpu backend compose "
            "and step share cores, so pipeline_step_ms ~ step_ms + "
            "compose is expected" if not on_accel else
            "accelerator backend: pipeline_step_ms should approach "
            "max(step_ms, compose_ms_per_batch)"),
        **device_report(),
        "composition": f"{n_tickers} tickers x {per_ticker} windows, "
                       "per-ticker norm (MultiTickerDataset.mixed_batches; "
                       "pipeline_* = background compose + prefetch overlap)",
        "dtype": "float32",
        "tflops_per_step": round(flops / 1e12, 4),
        "mfu_est": mfu_est,
        "shape": {"B": batch, "T": WINDOW, "F": FEATURES, "H": HIDDEN},
    }


def phase_train_e2e() -> dict:
    """Compact end-to-end training on whatever backend jax selects: synthetic
    session replayed through bus -> engine -> warehouse, then the
    reference protocol's chunked/normalized windows through the jitted
    trainer (fit + test eval).  This is the 'trained on device' artifact
    — the pipeline the accuracy-parity experiment runs for 25 epochs,
    here at a bench-sized corpus/epoch count with throughput reported."""
    import jax

    from fmda_tpu.config import FeatureConfig, ModelConfig, TrainConfig
    from fmda_tpu.data.synthetic import SyntheticMarketConfig, build_corpus
    from fmda_tpu.train.trainer import Trainer, imbalance_weights_from_source

    fc = FeatureConfig()
    t0 = time.perf_counter()
    wh, _ = build_corpus(fc, SyntheticMarketConfig(seed=0, n_days=10))
    corpus_s = time.perf_counter() - t0

    model_cfg = ModelConfig(
        hidden_size=HIDDEN, n_features=len(wh.x_fields), output_size=CLASSES,
        dropout=0.5, spatial_dropout=True, use_pallas=True,
    )
    train_cfg = TrainConfig(
        batch_size=32, window=WINDOW, chunk_size=100, learning_rate=1e-3,
        epochs=4, clip=50.0, val_size=0.1, test_size=0.1, seed=0,
    )
    weight, pos_weight = imbalance_weights_from_source(wh)
    trainer = Trainer(model_cfg, train_cfg, weight=weight,
                      pos_weight=pos_weight)
    t0 = time.perf_counter()
    state, history, dataset = trainer.fit(
        wh, bid_levels=fc.bid_levels, ask_levels=fc.ask_levels)
    fit_s = time.perf_counter() - t0
    _, _, test_chunks = dataset.split(train_cfg.val_size, train_cfg.test_size)
    test_m, _ = trainer.evaluate(state, dataset, test_chunks)

    dev = jax.devices()[0]
    tr = history["train"]
    return {
        **device_report(),
        "corpus_rows": len(wh),
        "corpus_build_s": round(corpus_s, 1),
        "fit_wall_s": round(fit_s, 1),
        "epochs": train_cfg.epochs,
        "train_loss_first_last": [round(tr[0].loss, 4),
                                  round(tr[-1].loss, 4)],
        "final_train_accuracy": round(tr[-1].accuracy, 4),
        "test_accuracy": round(float(test_m.accuracy), 4),
        "test_hamming": round(float(test_m.hamming), 4),
    }


TRAIN_THROUGHPUT_SCHEMA = (
    "rows", "window", "features", "batch_size", "epochs", "backend",
    "device_kind", "n_devices", "quiet_host", "cells", "speedup_vs_seed", "accum_speed_ratio",
    "continuous", "compile_ok",
)


def _train_cell_run(source, model_cfg, train_cfg, epochs: int) -> dict:
    """One trainer configuration timed over ``epochs`` steady-state
    epochs on a fresh Trainer; samples/s counts real (unpadded) windows.

    One warm-up epoch runs untimed first: it carries the XLA compile
    (identical across cells — the A/B measures the input pipeline, not
    the compiler) and the allocator warm-up.  The timed ``fit`` resumes
    from the warm-up state ON the warm-up's dataset, so its shapes hit
    the already-compiled step and every cache tier the cell's config
    enables (host windows, placed device batches) is warm — i.e. the
    timed epochs are the loop's steady state.  The compile pin below
    proves the warm-up epoch was the only compile either fit
    triggered."""
    from fmda_tpu.train.trainer import Trainer

    trainer = Trainer(model_cfg, train_cfg)
    state, _, dataset = trainer.fit(source, epochs=1)
    t0 = time.perf_counter()
    state, history, dataset = trainer.fit(
        source, epochs=epochs, initial_state=state, dataset=dataset)
    wall = time.perf_counter() - t0
    window = train_cfg.window
    per_epoch = sum(max(0, len(r) - window + 1) for r in dataset.ranges)
    samples = epochs * per_epoch
    return {
        "wall_s": round(wall, 3),
        "samples": samples,
        "samples_per_s": round(samples / wall, 1) if wall > 0 else None,
        "train_step_compiles": trainer.compile_counts["train_step"],
        "unexpected_recompiles": trainer.unexpected_recompiles,
        "final_loss": round(float(history["train"][-1].loss), 4),
    }


def _continuous_train_cell() -> dict:
    """Continuous fine-tuning beside a warm solo serving gateway: a
    2-day backlog round plus a fresh-day round, every accepted round
    hot-swapped into the pool.  The pins: the serving step never
    recompiles across the swaps, and the trainer's compiled step carries
    the whole loop (recompiles after round-1 warm-up == 0)."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from fmda_tpu.config import (
        DEFAULT_TOPICS, FeatureConfig, ModelConfig, TrainConfig,
        WarehouseConfig)
    from fmda_tpu.data.synthetic import (
        SyntheticMarketConfig, synthetic_session_messages)
    from fmda_tpu.models import build_model
    from fmda_tpu.runtime import BatcherConfig, FleetGateway, SessionPool
    from fmda_tpu.stream import InProcessBus, StreamEngine, Warehouse
    from fmda_tpu.train.continuous import (
        ContinuousTrainer, gateway_publisher)

    fc = FeatureConfig()
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    bus = InProcessBus(DEFAULT_TOPICS)
    engine = StreamEngine(bus, wh, fc)
    msgs = synthetic_session_messages(
        fc, SyntheticMarketConfig(seed=1, n_days=8))
    per_day = 5 * 78  # five feed messages per 5-minute bar

    def feed_day() -> None:
        n = 0
        for topic, msg in msgs:
            bus.publish(topic, msg)
            n += 1
            if n >= per_day:
                break
        if n:
            engine.step()

    feed_day()
    feed_day()  # the 2-day backlog the first round trains on

    serve_window = 16
    model_cfg = ModelConfig(
        hidden_size=8, n_features=len(wh.x_fields), output_size=CLASSES,
        dropout=0.0, bidirectional=False, use_pallas=False)
    model = build_model(model_cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, serve_window, model_cfg.n_features)))["params"]
    pool = SessionPool(model_cfg, params, capacity=4, window=serve_window)
    gateway = FleetGateway(
        pool, batcher_config=BatcherConfig(
            bucket_sizes=(4,), max_linger_s=0.0))
    pool.step(np.full(4, pool.padding_slot, np.int32),
              np.zeros((4, model_cfg.n_features), np.float32))
    assert pool.compile_count == 1
    pool.mark_warm()

    train_cfg = TrainConfig(
        batch_size=32, window=serve_window, chunk_size=96,
        learning_rate=1e-3, epochs=1, clip=50.0,
        val_size=0.0, test_size=0.0, seed=0,
        prefetch_depth=2, cache_chunks=8,
        continuous_min_rows=64, continuous_window_rows=448,
        continuous_epochs=1, continuous_follow_polls=3,
        continuous_poll_s=0.01)
    continuous = ContinuousTrainer(
        wh, model_cfg, train_cfg,
        checkpoint_dir=tempfile.mkdtemp(prefix="bench_cts_"),
        publish=gateway_publisher(gateway),
        target_lead=fc.max_lead,
        wait_fn=feed_day, chunk=512)
    summary = continuous.run(max_rounds=2)

    # serving survived the swaps: same program, post-swap steps included
    pool.step(np.full(4, pool.padding_slot, np.int32),
              np.zeros((4, model_cfg.n_features), np.float32))
    return {
        "rounds": summary["rounds"],
        "rows_seen": summary["rows_seen"],
        "swaps_accepted": summary["swaps_accepted"],
        "swaps_refused": summary["swaps_refused"],
        "checkpoints": len(summary["checkpoints"]),
        "pool_compile_count": pool.compile_count,
        "pool_recompiles_after_warmup": pool.recompiles_after_warmup,
        "trainer_unexpected_recompiles":
            summary["trainer_unexpected_recompiles"],
        "trainer_train_step_compiles":
            continuous.trainer.compile_counts["train_step"],
    }


def phase_train_throughput() -> dict:
    """The continuous-training tentpole's hard numbers (ISSUE 20): the
    sharded/pipelined/prefetch-overlapped train step vs the seed's
    synchronous loop, plus the live-loop recompile pins.

    Three A/B cells over one in-memory source (identical model, epochs,
    and batch schedule — only the input pipeline differs):

    * **seed_sync** — the seed behavior: no window cache (every epoch
      re-fetches, re-normalizes, and re-gathers every chunk) and no
      prefetch (per-batch synchronous placement);
    * **pipelined** — ``cache_chunks`` + depth-2 prefetch: the epoch-1
      gather is overlapped with device compute, epochs 2+ replay cached
      windows;
    * **pipelined_accum** — the same plus ``accum_steps=4`` microbatch
      gradient accumulation (reported, not speed-gated: accumulation
      buys memory headroom, not wall clock).

    Hard gates:

    * **speed** (quiet hosts only, else ``gate_inert``): pipelined
      samples/s >= 2x seed_sync samples/s;
    * **compile pins** (always): every cell compiles its train step
      exactly once (batches are padded to ``batch_size``) with zero
      unexpected recompiles, and the continuous cell's serving pool
      sees ZERO recompiles after warm-up across live hot swaps while
      the trainer's step survives round 2 without recompiling.

    Artifact: ``artifacts/train_throughput.json`` with the
    ``TRAIN_THROUGHPUT_SCHEMA`` top level."""
    import dataclasses

    import jax

    from fmda_tpu.config import ModelConfig, TrainConfig
    from fmda_tpu.data.source import ArraySource

    # ambient load, sampled BEFORE the cells run — the phase's own
    # minute of compute pushes load1 past any sane threshold, so
    # sampling after would read the bench's own footprint as "loaded
    # host" and permanently inert the gate
    try:
        load1 = os.getloadavg()[0]
    except OSError:
        load1 = None
    quiet = load1 is not None and load1 < 0.5 * (os.cpu_count() or 1)

    rows, window, features = 8192, 64, 256
    batch_size, epochs = 256, 4
    rng = np.random.default_rng(0)
    source = ArraySource(
        rng.normal(size=(rows, features)).astype(np.float32),
        (rng.random(size=(rows, CLASSES)) < 0.25).astype(np.float32),
        [f"f{i}" for i in range(features)])
    # hidden_size 2: the A/B measures the INPUT pipeline, so the model
    # is sized to keep device FLOPs below the host-side window
    # gather/normalize/placement cost the pipelined path hides (GRU
    # FLOPs scale with hidden, the host bytes don't — this is the one
    # knob that separates the two)
    model_cfg = ModelConfig(
        hidden_size=2, n_features=features, output_size=CLASSES,
        dropout=0.0, bidirectional=False, use_pallas=False)
    base = TrainConfig(
        batch_size=batch_size, window=window, chunk_size=1024,
        learning_rate=1e-3, epochs=epochs, clip=50.0,
        val_size=0.0, test_size=0.0, seed=0)
    cells = {
        "seed_sync": _train_cell_run(
            source, model_cfg,
            dataclasses.replace(base, prefetch_depth=0, cache_chunks=0),
            epochs),
        "pipelined": _train_cell_run(
            source, model_cfg,
            dataclasses.replace(base, prefetch_depth=2, cache_chunks=16),
            epochs),
        "pipelined_accum": _train_cell_run(
            source, model_cfg,
            dataclasses.replace(
                base, prefetch_depth=2, cache_chunks=16, accum_steps=4),
            epochs),
    }
    continuous = _continuous_train_cell()

    def _per_s(cell: str):
        return cells[cell]["samples_per_s"]

    speedup = (round(_per_s("pipelined") / _per_s("seed_sync"), 2)
               if _per_s("pipelined") and _per_s("seed_sync") else None)
    accum_ratio = (round(_per_s("pipelined_accum") / _per_s("pipelined"), 2)
                   if _per_s("pipelined_accum") and _per_s("pipelined")
                   else None)
    compile_ok = all(
        (c["train_step_compiles"] in (None, 1))
        and c["unexpected_recompiles"] == 0
        for c in cells.values()
    ) and (continuous["pool_recompiles_after_warmup"] == 0
           and continuous["trainer_unexpected_recompiles"] == 0
           and continuous["pool_compile_count"] == 1
           and continuous["trainer_train_step_compiles"] in (None, 1))

    result = {
        "rows": rows,
        "window": window,
        "features": features,
        "batch_size": batch_size,
        "epochs": epochs,
        **device_report(),
        "quiet_host": quiet,
        "cells": cells,
        "speedup_vs_seed": speedup,
        "accum_speed_ratio": accum_ratio,
        "continuous": continuous,
        "compile_ok": compile_ok,
    }
    assert tuple(sorted(result)) == tuple(sorted(TRAIN_THROUGHPUT_SCHEMA))
    artifact_dir = os.path.join(_REPO_DIR, "artifacts")
    os.makedirs(artifact_dir, exist_ok=True)
    artifact = os.path.join(artifact_dir, "train_throughput.json")
    with open(artifact, "w") as fh:
        json.dump(result, fh, indent=2, default=str)
    result["artifact"] = os.path.relpath(artifact, _REPO_DIR)

    errors = []
    if not compile_ok:
        errors.append(
            "compile pins failed: expected exactly one train-step "
            "program per cell, zero unexpected recompiles, and a "
            "recompile-free serving pool across continuous hot swaps "
            f"(cells={cells}, continuous={continuous})")
    if continuous["rounds"] < 2 or continuous["swaps_accepted"] < 2:
        errors.append(
            f"continuous loop under-delivered: {continuous}")
    if quiet:
        if speedup is None or speedup < 2.0:
            errors.append(
                "pipelined input path did not clear 2x the seed's "
                f"synchronous loop on a quiet host: {speedup}")
    else:
        result["speed_gate"] = "gate_inert: loaded host"
    if errors:
        result["error"] = "; ".join(errors)
    return result


def phase_kernel_sweep() -> dict:
    """Fused Pallas GRU kernel vs lax.scan across shapes, fwd+bwd through
    jax.grad, best-of-3 windows — where does the kernel win and by how
    much.  The H axis spans overhead-bound (32) through MXU-shaped
    (512/1024) widths so the sweep *measures the crossover* that
    ``kernel_supported`` + ``select_scan_fn`` encode: each shape records
    the predicate's verdict alongside the actual attempt (the kernel is
    tried even where the predicate says no, so a spuriously conservative
    gate would show up as a working kernel marked unsupported, and a
    VMEM overflow as a recorded compile error).  Off-TPU the sweep runs
    the kernel in INTERPRET mode over a reduced shape set — no timing
    headline (the interpreter is orders slower by construction), but the
    whole fused fwd+bwd path executes end-to-end on every backend, the
    coverage the compat port bought back (PR 9).

    Since ISSUE 14 the sweep covers ``KERNEL_SWEEP_FAMILIES``: the GRU
    scan kernel above plus the SSM family's fused O(1) serve-step
    kernel (``ssm_step`` — jnp step vs fmda_tpu.ops.pallas_ssm over
    (B, H) tick shapes; interpret-mode smoke on CPU, real timings on
    hardware)."""
    import jax
    import jax.numpy as jnp

    from fmda_tpu.ops.gru import gru_scan, pallas_scan_available
    from fmda_tpu.ops.pallas_gru import gru_scan_pallas, kernel_supported

    interpret = not pallas_scan_available()

    if interpret:
        # interpret mode: correctness/coverage smoke, not a race — small
        # shapes, one timed window (slope timing would take minutes)
        shapes = [(8, 16, 32), (4, 32, 64)]
    else:
        shapes = [
            # (batch, seq, hidden): the flagship + longctx protocol shapes...
            (256, 30, 32), (256, 128, 64), (64, 256, 128), (16, 1024, 128),
            # ...and the H ladder at flagship batch/seq — where is the
            # kernel-vs-scan crossover as the matmul becomes MXU food?
            (256, 30, 128), (256, 30, 256), (64, 30, 512), (64, 30, 1024),
        ]
    out: dict = {**device_report(),
                 "interpret": interpret, "shapes": {}}
    if interpret:
        out["note"] = ("Mosaic unavailable on this backend: fused kernel "
                       "run in pallas interpret mode — parity smoke, "
                       "timings not comparable to hardware")

    def timed(fn, args):
        r = fn(*args)
        float(r[0][(0,) * r[0].ndim])  # compile + warm; host fetch barrier
        if interpret:  # one window: smoke timing, not a headline
            t0 = time.perf_counter()
            r = fn(*args)
            float(r[0][(0,) * r[0].ndim])
            return time.perf_counter() - t0

        def window_fn(n):
            t0 = time.perf_counter()
            for _ in range(n):
                r = fn(*args)
            # scalar host fetch: the device queue is FIFO, so fetching the
            # last dispatch's value completes every prior one too (see
            # _slope_time)
            float(r[0][(0,) * r[0].ndim])
            return time.perf_counter() - t0

        return _slope_time(window_fn, target_s=1.5)

    for batch, seq, hidden in shapes:
        r = np.random.default_rng(0)
        xp = jnp.asarray(
            r.normal(size=(batch, seq, 3 * hidden)).astype(np.float32))
        h0 = jnp.zeros((batch, hidden), jnp.float32)
        w_hh = jnp.asarray(
            r.normal(size=(3 * hidden, hidden)).astype(np.float32) * 0.1)
        b_hh = jnp.zeros((3 * hidden,), jnp.float32)

        def make(fn):
            def loss(xp_, h0_, w, b):
                h_last, hs = fn(xp_, h0_, w, b)
                return jnp.sum(h_last**2) + jnp.sum(hs**2)

            return jax.jit(jax.grad(loss, argnums=(0, 2)))

        def pallas_fn(xp_, h0_, w, b):
            return gru_scan_pallas(xp_, h0_, w, b, interpret=interpret)

        key = f"B{batch}_T{seq}_H{hidden}"
        entry: dict = {
            "kernel_supported": kernel_supported(batch, seq, hidden, 4),
        }
        # scan baseline first and in its own try: a kernel failure for a
        # shape must not cost us that shape's reference number
        try:
            t_scan = timed(make(gru_scan), (xp, h0, w_hh, b_hh))
            entry["scan_ms"] = round(t_scan * 1e3, 3)
        except Exception as e:  # noqa: BLE001 - record, keep sweeping
            entry["scan_error"] = str(e)[:300]
        try:
            t_pal = timed(make(pallas_fn), (xp, h0, w_hh, b_hh))
            entry["pallas_ms"] = round(t_pal * 1e3, 3)
            if "scan_ms" in entry and not interpret:
                entry["speedup"] = round(t_scan / t_pal, 3)
        except Exception as e:  # noqa: BLE001 - record, keep sweeping
            entry["pallas_error"] = str(e)[:300]
        out["shapes"][key] = entry

    # --- the SSM family's O(1) serve-step kernel (ISSUE 14) ------------
    # serve-step shapes are (B, H) — one tick, no time axis: B spans the
    # fleet bucket sizes, H the family ladder.  Off-TPU the kernel runs
    # in interpret mode (parity smoke, no timing headline), exactly like
    # the scan kernels above.
    from fmda_tpu.ops.pallas_ssm import (
        kernel_supported as ssm_kernel_supported)
    from fmda_tpu.ops.pallas_ssm import ssm_cell_step_pallas
    from fmda_tpu.ops.ssm import SSMWeights, ssm_cell_step

    out["families"] = list(KERNEL_SWEEP_FAMILIES)
    step_shapes = ([(8, 32)] if interpret
                   else [(16, 32), (64, 32), (256, 32),
                         (64, 128), (256, 128), (256, 256)])
    out["ssm_step"] = {}
    for batch, hidden in step_shapes:
        r = np.random.default_rng(1)
        w = SSMWeights(
            w_ih=jnp.zeros((3 * hidden, 1)),  # projection outside, unused
            b_ih=jnp.zeros((3 * hidden,)),
            a_base=jnp.asarray(
                r.uniform(1.0, 3.0, hidden).astype(np.float32)),
            d=jnp.asarray(r.normal(size=hidden).astype(np.float32) * 0.1),
            rho_f=jnp.zeros((hidden,)),
            rho_s=jnp.full((hidden,), 3.0),
        )
        xp = jnp.asarray(
            r.normal(size=(batch, 3 * hidden)).astype(np.float32))
        carry = tuple(jnp.zeros((batch, hidden)) for _ in range(3))

        def jnp_step(xp_, s, ef, es):
            return ssm_cell_step(xp_, (s, ef, es), w)

        def pal_step(xp_, s, ef, es):
            return ssm_cell_step_pallas(
                xp_, (s, ef, es), w, interpret=interpret)

        key = f"B{batch}_H{hidden}"
        entry = {
            "kernel_supported": ssm_kernel_supported(batch, hidden, 4),
        }
        try:
            t_ref = timed(jax.jit(jnp_step), (xp,) + carry)
            entry["step_ms"] = round(t_ref * 1e3, 4)
        except Exception as e:  # noqa: BLE001 - record, keep sweeping
            entry["step_error"] = str(e)[:300]
        try:
            t_pal = timed(jax.jit(pal_step), (xp,) + carry)
            entry["pallas_ms"] = round(t_pal * 1e3, 4)
            if "step_ms" in entry and not interpret:
                entry["speedup"] = round(t_ref / t_pal, 3)
        except Exception as e:  # noqa: BLE001 - record, keep sweeping
            entry["pallas_error"] = str(e)[:300]
        out["ssm_step"][key] = entry
    return out


def phase_attn_sweep() -> dict:
    """Fused flash-attention kernel vs the jnp online-softmax path across
    sequence lengths, fwd+bwd through jax.grad — the per-shape evidence
    behind the attn family's use_pallas opt-in AND the ring fold's
    per-step win (each sp ring step at T=1024, sp=4 runs exactly the
    T=256 row's shape per device).  Off-TPU the fused kernel runs in
    INTERPRET mode over a reduced shape set — coverage smoke for the
    full fwd+bwd custom-vjp path, timings not comparable (PR 9)."""
    import jax
    import jax.numpy as jnp

    from fmda_tpu.ops.attention import flash_available, mha
    from fmda_tpu.ops.pallas_attention import flash_attention, flash_supported

    interpret = not flash_available()

    if interpret:
        shapes = [(1, 2, 128, 8), (1, 1, 256, 8)]
    else:
        # (B, N, T, D): longctx protocol head shapes (H=32, 4 heads -> D=8)
        # at the ring-step ladder T=128..1024; plus a D=64 row for the
        # MXU-wide head the wide probe implies
        shapes = [
            (16, 4, 128, 8), (16, 4, 256, 8), (16, 4, 512, 8),
            (16, 4, 1024, 8), (16, 4, 1024, 64),
        ]
    out: dict = {**device_report(),
                 "interpret": interpret, "shapes": {},
                 "note": "T=256 row = one ring step per device at the "
                         "sp=4 longctx config; grad-of-sum-of-squares, "
                         "slope-timed"}
    if interpret:
        out["note"] = ("Mosaic unavailable on this backend: flash kernel "
                       "run in pallas interpret mode — parity smoke, "
                       "timings not comparable to hardware")

    def timed(fn, args):
        g = fn(*args)
        float(g[0][(0,) * g[0].ndim])  # compile + warm; host fetch barrier
        if interpret:  # one window: smoke timing, not a headline
            t0 = time.perf_counter()
            g = fn(*args)
            float(g[0][(0,) * g[0].ndim])
            return time.perf_counter() - t0

        def window_fn(n):
            t0 = time.perf_counter()
            for _ in range(n):
                g = fn(*args)
            float(g[0][(0,) * g[0].ndim])
            return time.perf_counter() - t0

        return _slope_time(window_fn, target_s=1.5)

    for b, n, t, d in shapes:
        r = np.random.default_rng(0)
        q, k, v = (
            jnp.asarray(r.normal(size=(b, n, t, d)).astype(np.float32))
            for _ in range(3))

        def make(attn_fn):
            def loss(q_, k_, v_):
                return jnp.sum(attn_fn(q_, k_, v_) ** 2)

            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        key = f"B{b}_N{n}_T{t}_D{d}"
        entry: dict = {"flash_supported": flash_supported(t, t, d)}
        try:
            t_jnp = timed(make(lambda a, b_, c: mha(a, b_, c)), (q, k, v))
            entry["jnp_ms"] = round(t_jnp * 1e3, 3)
        except Exception as e:  # noqa: BLE001 - record, keep sweeping
            entry["jnp_error"] = str(e)[:300]
        try:
            t_pal = timed(
                make(lambda a, b_, c: flash_attention(
                    a, b_, c, interpret=interpret)), (q, k, v))
            entry["flash_ms"] = round(t_pal * 1e3, 3)
            if "jnp_ms" in entry and not interpret:
                entry["speedup"] = round(t_jnp / t_pal, 3)
        except Exception as e:  # noqa: BLE001 - record, keep sweeping
            entry["flash_error"] = str(e)[:300]
        out["shapes"][key] = entry
    return out


def phase_serving() -> dict:
    """Tick latency of the carried-state streaming cores on the flagship
    bidirectional model (north-star config 5: jit state-carry p50 tick
    latency; the reference's floor is the hard-coded sleep(15) + retry,
    predict.py:141-157)."""
    import jax

    from fmda_tpu.config import ModelConfig
    from fmda_tpu.data.normalize import NormParams
    from fmda_tpu.models.bigru import BiGRU
    from fmda_tpu.serve.streaming import StreamingBiGRUBidirectional

    ticks, warmup = 200, 10
    cfg = ModelConfig(
        hidden_size=HIDDEN, n_features=FEATURES, output_size=CLASSES,
        dropout=0.0, use_pallas=False,
    )
    model = BiGRU(cfg)
    import jax.numpy as jnp

    params = model.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, WINDOW, FEATURES)),
    )["params"]
    norm = NormParams(np.zeros(FEATURES, np.float32),
                      np.ones(FEATURES, np.float32))
    core = StreamingBiGRUBidirectional(cfg, params, norm, window=WINDOW)
    r = np.random.default_rng(0)
    rows = r.normal(size=(warmup + ticks, FEATURES)).astype(np.float32)
    for t in range(warmup):
        core.step(rows[t])
    lat = np.empty(ticks)
    for t in range(ticks):
        t0 = time.perf_counter()
        core.step(rows[warmup + t])
        lat[t] = time.perf_counter() - t0
    # Device-isolated tick cost: the end-to-end percentiles above include
    # the host round-trip of every step().  Chain N ticks device-side
    # through the core's jitted step (device-resident rows, state carried,
    # ONE host fetch at the end) and slope-time them the way the train
    # phases do, so the per-window cost cancels.
    import jax.numpy as jnp

    dev_rows = jnp.asarray(rows[warmup:])  # (ticks, F) on device
    core.reset()
    state0 = (core._h, core._hs_ring, core._xpb_ring, core._pos)

    def window_fn(n: int) -> float:
        h, hs, xpb, pos = state0
        t0 = time.perf_counter()
        logits = None
        for i in range(n):
            logits, h, hs, xpb, pos = core._step(
                core._params, h, hs, xpb, pos, dev_rows[i % ticks][None])
        float(logits[0, 0])  # host fetch: the only trusted barrier
        return time.perf_counter() - t0

    window_fn(4)  # warm the loop
    try:
        device_tick_s = _slope_time(window_fn, target_s=1.0)
        device_tick_ms = round(device_tick_s * 1e3, 4)
    except RuntimeError:
        device_tick_ms = None  # noisy host: report end-to-end only

    # The OTHER serving mode (round-4 verdict next #5 asks for both): the
    # window-re-scan Predictor — warehouse row lookup + window fetch +
    # normalize + jitted bidirectional apply + sigmoid, per signal, on a
    # real sqlite warehouse.  Training-exact semantics, O(window x F)
    # per tick vs the carried core's O(window x H).
    from fmda_tpu.config import (
        DEFAULT_TOPICS, FeatureConfig, WarehouseConfig)
    from fmda_tpu.data.synthetic import SyntheticMarketConfig, build_corpus
    from fmda_tpu.serve.predictor import Predictor
    from fmda_tpu.stream import InProcessBus

    fc = FeatureConfig()
    wh, _ = build_corpus(
        fc, SyntheticMarketConfig(seed=1, n_days=3),
        warehouse_config=WarehouseConfig(path=":memory:"))
    pred_core = Predictor(
        InProcessBus(DEFAULT_TOPICS), wh, cfg, params,
        NormParams(np.zeros(len(wh.x_fields), np.float32),
                   np.ones(len(wh.x_fields), np.float32)),
        window=WINDOW, max_staleness_s=None)
    ts_all = [t for t in wh.recent_timestamps(len(wh))]
    servable = sorted(ts_all)[WINDOW + 1:]
    for ts in servable[:5]:
        pred_core.predict_for_timestamp(ts)  # warm compile + sqlite cache
    pl = np.empty(len(servable))
    for i, ts in enumerate(servable):
        t0 = time.perf_counter()
        pred_core.predict_for_timestamp(ts)
        pl[i] = time.perf_counter() - t0
    predictor_p50 = round(float(np.percentile(pl, 50)) * 1e3, 3)
    predictor_p99 = round(float(np.percentile(pl, 99)) * 1e3, 3)

    # device-isolated predictor forward (slope-timed): the
    # jitted normalize+apply+sigmoid on a device-resident window
    xw = jnp.asarray(
        np.random.default_rng(1).normal(
            size=(1, WINDOW, len(wh.x_fields))).astype(np.float32))

    def pred_window_fn(n: int) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            probs = pred_core._forward(
                pred_core._params, pred_core._x_min, pred_core._x_range, xw)
        float(probs[0, 0])
        return time.perf_counter() - t0

    pred_window_fn(4)
    try:
        predictor_device_ms = round(
            _slope_time(pred_window_fn, target_s=1.0) * 1e3, 4)
    except RuntimeError:
        predictor_device_ms = None

    return {
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
        "device_tick_ms": device_tick_ms,
        "predictor_p50_ms": predictor_p50,
        "predictor_p99_ms": predictor_p99,
        "predictor_device_ms": predictor_device_ms,
        "predictor_ticks": len(servable),
        **device_report(),
        "model": "bidirectional carried-state + window-re-scan Predictor",
        "timing_note": "p50/p99 = end-to-end step() incl. host round-trip;"
                       " device_tick_ms = slope-timed chained device steps;"
                       " predictor_* = warehouse->window->device per"
                       " signal (training-exact re-scan mode)",
        "reference_floor_ms": 15000.0,
    }


def phase_torch() -> dict:
    """The reference stack's training step (torch CPU), same shapes."""
    import torch

    steps = 5
    torch.manual_seed(0)
    gru = torch.nn.GRU(FEATURES, HIDDEN, num_layers=1, batch_first=True,
                       bidirectional=True)
    linear = torch.nn.Linear(HIDDEN * 3, CLASSES)
    drop = torch.nn.Dropout2d(0.5)
    params = list(gru.parameters()) + list(linear.parameters())
    optimizer = torch.optim.Adam(params, lr=1e-3)
    loss_fn = torch.nn.BCEWithLogitsLoss(
        weight=torch.full((CLASSES,), 2.0),
        pos_weight=torch.full((CLASSES,), 3.0),
    )
    x = torch.randn(BATCH, WINDOW, FEATURES)
    y = (torch.rand(BATCH, CLASSES) > 0.7).float()

    def step():
        optimizer.zero_grad()
        xd = drop(x.permute(0, 2, 1)).permute(0, 2, 1)
        gru_out, hidden = gru(xd)
        last_hidden = hidden.view(1, 2, BATCH, HIDDEN)[-1].sum(dim=0)
        summed = gru_out[:, :, :HIDDEN] + gru_out[:, :, HIDDEN:]
        max_pool = summed.max(dim=1).values
        avg_pool = summed.sum(dim=1) / WINDOW
        logits = linear(torch.cat([last_hidden, max_pool, avg_pool], dim=1))
        loss = loss_fn(logits, y)
        loss.backward()
        torch.nn.utils.clip_grad_norm_(params, 50.0)
        optimizer.step()
        # the reference computes sklearn metrics per batch on the host
        # (biGRU_model.py:215-222); charge a threshold pass at least
        (torch.sigmoid(logits) > 0.5).float().mean().item()

    step()  # warmup
    t0 = time.perf_counter()
    for _ in range(steps):
        step()
    elapsed = time.perf_counter() - t0
    return {
        "seq_s": round(BATCH * steps / elapsed, 1),
        "step_ms": round(elapsed / steps * 1e3, 3),
        "backend": "torch-cpu",
    }


def phase_longctx_sp() -> dict:
    """The long-context config ACTUALLY sequence-sharded (round-2 verdict
    next #5): full train step at seq=1024 over a (dp=2, sp=4) mesh, remat
    on, plus the pipelined scan's bubble-filling at M in {1, 2, 4}.

    Runs on the virtual CPU mesh (the phase env forces 8 host devices);
    under SPMD every device executes every stage, so wall-clock tracks
    total executed work and the measured M-speedups should match the
    ``sp*M/(sp+M-1)`` useful-work model within noise.
    """
    import jax
    import numpy as np
    import optax

    from fmda_tpu.config import FeatureConfig, MeshConfig, ModelConfig
    from fmda_tpu.models.bigru import BiGRU
    from fmda_tpu.parallel import build_mesh
    from fmda_tpu.parallel.sp_train import (
        make_sp_train_step, shard_train_inputs)

    # batch sized so the M=4 microbatch (batch/dp/M = 8 sequences) stays
    # compute-bound — the useful-work model assumes scan time ∝ batch,
    # which breaks when microbatches hit per-step launch overhead
    dp, sp, seq, batch = 2, 4, 1024, 64
    features = len(FeatureConfig(bid_levels=10, ask_levels=10).x_fields())
    devices = jax.devices()
    if len(devices) < dp * sp:
        return {"error": f"need {dp * sp} devices, have {len(devices)} "
                         f"({jax.default_backend()})"}
    mesh = build_mesh(MeshConfig(dp=dp, sp=sp), devices[: dp * sp])
    cfg = ModelConfig(
        hidden_size=HIDDEN, n_features=features, output_size=CLASSES,
        dropout=0.0, use_pallas=False, remat=True,
    )
    import jax.numpy as jnp

    r = np.random.default_rng(0)
    x_host = r.normal(size=(batch, seq, features)).astype(np.float32)
    y_host = (r.uniform(size=(batch, CLASSES)) > 0.7).astype(np.float32)
    model = BiGRU(cfg)
    params0 = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.asarray(x_host[:1]))["params"]
    optimizer = optax.chain(optax.clip_by_global_norm(50.0), optax.adam(1e-3))

    out: dict = {
        "mesh": f"dp={dp} sp={sp}", "remat": True,
        "shape": {"B": batch, "T": seq, "F": features, "H": HIDDEN},
    }
    steps = 4

    def time_step(step, params0, warmup=1):
        # one shared timing discipline for every program in this phase:
        # warmup, fetch barrier, timed steps, fetch barrier (plain window
        # timing: the steps on the CPU mesh dwarf the closing fetch)
        opt_state = optimizer.init(params0)
        x, y, p, o = shard_train_inputs(
            mesh, x_host, y_host, params0, opt_state)
        for _ in range(warmup):
            # the step donates p/o — always carry the returned tree
            p, o, loss = step(p, o, x, y)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            p, o, loss = step(p, o, x, y)
        float(loss)
        return (time.perf_counter() - t0) / steps, float(loss)

    t_m1 = None
    for m in (1, 2, 4):
        step_s, loss = time_step(
            make_sp_train_step(mesh, cfg, seq, optimizer, n_microbatches=m),
            params0)
        if m == 1:
            t_m1 = step_s
        out[f"M{m}"] = {
            "step_ms": round(step_s * 1e3, 1),
            "seq_s": round(batch / step_s, 1),
            "speedup_vs_M1": round(t_m1 / step_s, 3),
            # plain (M=1) runs sp full-batch scan stages; pipelined runs
            # (sp+M-1) stages at batch/M each -> predicted speedup
            # sp*M/(sp+M-1) over M=1 (the scan only; the projection and
            # backward dilute it in the full-step number)
            "model_speedup": round(sp * m / (sp + m - 1), 3),
            "loss": round(loss, 4),
        }

    # the ring-attention program on the same mesh/shapes: no serial carry,
    # so its step time is the comparison point for the recurrent pipeline
    from fmda_tpu.models import build_model

    attn_cfg = ModelConfig(
        hidden_size=HIDDEN, n_features=features, output_size=CLASSES,
        dropout=0.0, spatial_dropout=False, cell="attn", remat=True,
    )
    attn_params0 = build_model(attn_cfg).init(
        {"params": jax.random.PRNGKey(1)}, jnp.asarray(x_host[:1]))["params"]
    step_s, loss = time_step(
        make_sp_train_step(mesh, attn_cfg, seq, optimizer), attn_params0)
    out["ring_attn"] = {
        "step_ms": round(step_s * 1e3, 1),
        "seq_s": round(batch / step_s, 1),
        "loss": round(loss, 4),
    }

    # honest denominator for the ring number (round-4 verdict weak #3
    # compared B=64 ring steps against the B=16 longctx_attn phase): the
    # SAME attn model/loss/optimizer at the same global (B, T) shape,
    # UNSHARDED on one device.  On the serialised virtual CPU mesh
    # wall-clock tracks total executed work, so ring/single ratios near
    # 1.0 mean the ring program adds little overhead beyond the model's
    # own FLOPs; the flash-fold win is a TPU-capture number, not a CPU
    # one (kernels are gated off the CPU backend).
    from fmda_tpu.train.losses import weighted_bce_with_logits

    attn_model = build_model(attn_cfg)

    @jax.jit
    def single_step(p, o, xb, yb):
        def loss_fn(pp):
            logits = attn_model.apply({"params": pp}, xb)
            return weighted_bce_with_logits(logits, yb)

        loss_v, grads = jax.value_and_grad(loss_fn)(p)
        updates, o_new = optimizer.update(grads, o, p)
        return optax.apply_updates(p, updates), o_new, loss_v

    dev0 = devices[0]
    xd = jax.device_put(jnp.asarray(x_host), dev0)
    yd = jax.device_put(jnp.asarray(y_host), dev0)
    p = jax.device_put(attn_params0, dev0)
    o_state = jax.device_put(optimizer.init(attn_params0), dev0)
    p, o_state, loss_v = single_step(p, o_state, xd, yd)
    float(loss_v)
    t0 = time.perf_counter()
    for _ in range(steps):
        p, o_state, loss_v = single_step(p, o_state, xd, yd)
    float(loss_v)
    single_s = (time.perf_counter() - t0) / steps
    out["attn_single_device"] = {
        "step_ms": round(single_s * 1e3, 1),
        "seq_s": round(batch / single_s, 1),
        "shape_note": f"same global shape (B={batch}, T={seq}) as ring_attn",
    }
    out["ring_attn"]["vs_single_device"] = round(step_s / single_s, 3)
    return out


def phase_tpu_export() -> dict:
    """Prove the Pallas kernel pair lowers for TPU (Mosaic) at every bench
    shape — hardware-independent compile-readiness evidence (round-2 verdict
    next #7).  Mirrors tests/test_pallas_gru.py::test_pallas_kernel_lowers_for_tpu
    but lands the result in the driver artifact."""
    import jax
    import jax.numpy as jnp

    from fmda_tpu.ops.pallas_gru import gru_scan_pallas

    # one export per bench shape (f32) + the MXU dtype on the flagship;
    # the full shape x dtype x direction matrix stays in the test suite
    # (test_pallas_gru.py::test_pallas_kernel_lowers_for_tpu)
    cases = [
        ("flagship_B256_T30_H32", (256, 30, 32), "float32"),
        ("flagship_B256_T30_H32", (256, 30, 32), "bfloat16"),
        ("longctx_B16_T1024_H32", (16, 1024, 32), "float32"),
        ("multiticker_B800_T30_H32", (800, 30, 32), "float32"),
    ]
    out: dict = {"tpu_export_ok": {}}
    for name, (batch, seq, hidden), dtype in cases:
        dt = jnp.dtype(dtype)
        xp = jnp.zeros((batch, seq, 3 * hidden), dt)
        h0 = jnp.zeros((batch, hidden), dt)
        w_hh = jnp.zeros((3 * hidden, hidden), dt)
        b_hh = jnp.zeros((3 * hidden,), dt)

        def train_like(xp, h0, w_hh, b_hh):
            def loss(*args):
                h_last, hs = gru_scan_pallas(*args)
                return (jnp.sum(h_last.astype(jnp.float32))
                        + jnp.sum(hs.astype(jnp.float32) ** 2))

            return jax.grad(loss, argnums=(0, 1, 2, 3))(xp, h0, w_hh, b_hh)

        key = f"{name}_{dtype}"
        try:
            exported = jax.export.export(
                jax.jit(train_like), platforms=["tpu"])(xp, h0, w_hh, b_hh)
            out["tpu_export_ok"][key] = "tpu" in exported.platforms
        except Exception as e:  # noqa: BLE001 - report, don't crash phase
            out["tpu_export_ok"][key] = False
            out.setdefault("errors", {})[key] = repr(e)[:200]
    out["all_ok"] = all(out["tpu_export_ok"].values())
    return out


def phase_replay() -> dict:
    """Engine bulk-replay throughput, python vs native (C++) join scheduler
    (round-2 verdict next #8): ~100k warehouse rows (1,283 synthetic days,
    ~500k bus messages) through the full bus->engine->warehouse path.
    The reference analogue is the Spark micro-batch scheduler
    (spark_consumer.py:434-477), whose floor is its 5-min trigger cadence."""
    import time as _time

    from fmda_tpu.config import DEFAULT_TOPICS, FeatureConfig
    from fmda_tpu.data.synthetic import (
        SyntheticMarketConfig, synthetic_session_messages)
    from fmda_tpu.stream import InProcessBus, StreamEngine, Warehouse
    from fmda_tpu.stream.warehouse import WarehouseConfig

    fc = FeatureConfig()
    n_days = 1283  # 78 joined rows/day -> 100,074 rows
    msgs = list(synthetic_session_messages(
        fc, SyntheticMarketConfig(seed=3, n_days=n_days)))
    out: dict = {"n_messages": len(msgs)}
    rows = {}
    for backend in ("python", "native"):
        # default bus retention (1<<16/topic, Kafka drop-oldest) is smaller
        # than this backlog; raise it so the replay measures the engine,
        # not the retention policy
        bus = InProcessBus(DEFAULT_TOPICS, capacity=1 << 18)
        wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
        try:
            eng = StreamEngine(bus, wh, fc, join_backend=backend)
        except Exception as e:  # native toolchain absent
            out[backend] = {"error": repr(e)[:200]}
            continue
        for topic, m in msgs:
            bus.publish(topic, m)
        t0 = _time.monotonic()
        eng.step()
        elapsed = _time.monotonic() - t0
        rows[backend] = len(wh)
        out[backend] = {
            "rows": len(wh),
            "rows_s": round(len(wh) / elapsed, 1),
            "msgs_s": round(len(msgs) / elapsed, 1),
            "wall_s": round(elapsed, 2),
        }
    if len(rows) == 2:
        out["identical_rows"] = rows["python"] == rows["native"]
    return out


#: Carried-state cell families the fleet smoke races (equal H, same
#: load) and the kernel sweep covers — pinned by test_bench_helpers.
FLEET_AB_CELLS = ("gru", "ssm")
KERNEL_SWEEP_FAMILIES = ("gru", "ssm")


def _fleet_cell_run(cell: str, sessions: int, rounds: int,
                    buckets: tuple) -> dict:
    """One fleet-smoke measurement for one carried-state cell family:
    build pool + gateway at the flagship width, precompile every
    bucket, drive the synthetic load.  Shared by the per-cell A/B of
    ``phase_runtime_fleet``."""
    import jax
    import jax.numpy as jnp

    from fmda_tpu.config import ModelConfig
    from fmda_tpu.models import build_model
    from fmda_tpu.runtime import (
        BatcherConfig, FleetGateway, FleetLoadConfig, SessionPool,
        run_fleet_load)

    cfg = ModelConfig(hidden_size=HIDDEN, n_features=FEATURES,
                      output_size=CLASSES, dropout=0.0,
                      bidirectional=False, use_pallas=False, cell=cell)
    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, WINDOW, FEATURES)))["params"]
    pool = SessionPool(cfg, params, capacity=sessions, window=WINDOW)
    gateway = FleetGateway(
        pool,
        batcher_config=BatcherConfig(bucket_sizes=buckets,
                                     max_linger_s=0.002))
    # compile every bucket up front on padding-only flushes (touching
    # only the trash slot), so the measured latencies are steady-state
    for b in buckets:
        pool.step(np.full(b, pool.padding_slot, np.int32),
                  np.zeros((b, FEATURES), np.float32))
    assert pool.compile_count == len(buckets)
    out = run_fleet_load(gateway, FleetLoadConfig(
        n_sessions=sessions, n_ticks=rounds, duty=0.9, seed=0))
    out["cell"] = cell
    # per-session migration payload size at this H/window — the state
    # the fleet moves on every drain/export (ssm's O(1) cache vs the
    # ring-carrying families); the loadgen leaves its sessions open
    state = pool.export_slot(pool.handle_for("T0000"))
    out["export_bytes"] = int(
        sum(a.nbytes for layer in state["carry"] for a in layer)
        + state["ring"].nbytes)
    return out


def phase_runtime_fleet() -> dict:
    """Fleet-serving smoke + latency-SLO gate + cell-family A/B: the
    dynamic micro-batching runtime (fmda_tpu.runtime, docs/runtime.md)
    vs a synthetic 64-session multi-ticker load on the flagship feature
    width — p50/p99 tick latency + throughput, the serving-trajectory
    baseline later PRs regress against.  CPU-friendly by design (one
    small batched step per flush).

    ``FMDA_FLEET_CELL`` picks the family the headline numbers measure
    (default gru — the historical baseline series); the phase ALWAYS
    additionally races gru vs ssm at equal H under ``cells`` and gates
    the O(1)-cache family's claim (ISSUE 14): on a quiet host the SSM
    cell must sustain **strictly higher ticks/s than the GRU core**
    (its per-tick step is matmul-free and ring-free), with
    compile_count still 1/bucket for both; on a loaded host the
    comparison is reported ``gate_inert`` — the same quietness rule
    every perf gate here uses.

    The SLO gate (ROADMAP open item): total (submit→publish) p99 must
    stay under ``FMDA_FLEET_SLO_P99_MS`` (default 50 — ~6x quiet-host
    headroom over the measured ~7.5ms, tight enough to catch an
    order-of-magnitude serving regression).  Violations on a quiet host
    put an ``error`` in the phase result (→ ``phases_error``, the CI
    signal); a loaded host (1-min loadavg over half the cores) or
    ``--slo-soft`` / ``FMDA_FLEET_SLO_SOFT=1`` downgrades the verdict to
    a reported-but-non-failing ``slo_ok: false``."""
    import jax

    sessions, rounds = 64, 50
    buckets = (16, 64)
    primary = os.environ.get("FMDA_FLEET_CELL", "gru")
    cells = {}
    for cell in dict.fromkeys((primary,) + FLEET_AB_CELLS):
        cells[cell] = _fleet_cell_run(cell, sessions, rounds, buckets)
    out = cells[primary]
    lat = out["latency"]
    p99_ms = lat["total"]["p99_ms"]
    slo_ms = float(os.environ.get("FMDA_FLEET_SLO_P99_MS", "50"))
    soft = os.environ.get("FMDA_FLEET_SLO_SOFT", "") == "1"
    try:
        load1 = os.getloadavg()[0]
    except OSError:
        load1 = None
    quiet = load1 is not None and load1 < 0.5 * (os.cpu_count() or 1)
    result = {
        "cell": primary,
        "sessions": sessions,
        "rounds": rounds,
        "ticks_served": out["ticks_served"],
        "ticks_per_s": out["ticks_per_s"],
        "tick_p50_ms": lat["total"]["p50_ms"],
        "tick_p99_ms": p99_ms,
        "device_p50_ms": lat["device"]["p50_ms"],
        "dispatch_p50_ms": lat["dispatch"]["p50_ms"],
        "overlapped_flushes": out["counters"].get("overlapped_flushes", 0),
        "compile_count": out["compile_count"],
        "shed": out["counters"].get("shed_oldest", 0),
        "bucket_sizes": list(buckets),
        **device_report(),
        "slo_p99_ms": slo_ms,
        "slo_ok": p99_ms <= slo_ms,
        "slo_quiet_host": quiet,
        "cells": {
            c: {
                "ticks_per_s": r["ticks_per_s"],
                "tick_p50_ms": r["latency"]["total"]["p50_ms"],
                "tick_p99_ms": r["latency"]["total"]["p99_ms"],
                "compile_count": r["compile_count"],
                "export_bytes": r["export_bytes"],
            }
            for c, r in cells.items()
        },
        "timing_note": "total = submit->published per tick (incl. "
                       "micro-batch linger); dispatch = assembly + async "
                       "step enqueue; device = host-transfer block in "
                       "completion (overlapped work hides elsewhere); "
                       "buckets precompiled, so steady-state",
    }
    gru_tps = cells["gru"]["ticks_per_s"]
    ssm_tps = cells["ssm"]["ticks_per_s"]
    result["ssm_speedup_vs_gru"] = (
        round(ssm_tps / gru_tps, 3) if gru_tps else None)
    result["ssm_export_shrink"] = (
        round(cells["gru"]["export_bytes"]
              / max(cells["ssm"]["export_bytes"], 1), 2))
    errors = []
    if quiet:
        if ssm_tps <= gru_tps:
            errors.append(
                f"SSM cell did not beat the GRU core on a quiet host: "
                f"{ssm_tps:.0f} <= {gru_tps:.0f} ticks/s at equal "
                f"H={HIDDEN} (the O(1)-cache family's headline claim)")
    else:
        result["ssm_gate"] = "gate_inert: loaded host"
    if p99_ms > slo_ms and quiet and not soft:
        errors.append(
            f"latency SLO violated: total p99 {p99_ms}ms > {slo_ms}ms "
            "bound on a quiet host (FMDA_FLEET_SLO_P99_MS to retune, "
            "--slo-soft / FMDA_FLEET_SLO_SOFT=1 to report-only)")
    if errors:
        # both gates can fail in one run; neither message may eat the
        # other (phases_error shows exactly what regressed)
        result["error"] = "; ".join(errors)
    return result


#: pinned top-level schema of artifacts/replay_throughput.json — the
#: per-cell rows/s evidence, the bit-identity verdict, and the hot-swap
#: zero-downtime accounting (test_bench_helpers pins this tuple)
REPLAY_THROUGHPUT_SCHEMA = (
    "tickers", "rounds", "buckets", "cadence_s", "quiet_host",
    "cells", "identity_ok", "hot_swap",
)


def _replay_cell_run(cell: str, tickers: int, rounds: int,
                     buckets: tuple, cadence_s: float) -> dict:
    """One replay-vs-live A/B for one carried-state cell family, plus
    the mid-backfill hot swap, all at the flagship feature width.

    Three gateway builds off ONE params tree: (a) the max-speed replay
    backfill, (b) a fresh gateway serving the same history cadence-
    paced per-tick (the live baseline replay deletes), (c) a fresh
    gateway replaying again with a shifted-seed checkpoint hot-swapped
    in halfway.  (a) vs (b) sorted by (session, seq) is the in-phase
    bit-identity check; (a) vs (c) proves the swap barrier — pre-swap
    results byte-equal, post-swap results from the NEW weights — while
    the seq/served accounting proves zero dropped sessions and zero
    downtime rounds."""
    import jax
    import jax.numpy as jnp

    from fmda_tpu.config import ModelConfig
    from fmda_tpu.models import build_model
    from fmda_tpu.replay import (
        ReplayDriver, SyntheticHistory, run_live_reference)
    from fmda_tpu.runtime import BatcherConfig, FleetGateway, SessionPool

    cfg = ModelConfig(hidden_size=HIDDEN, n_features=FEATURES,
                      output_size=CLASSES, dropout=0.0,
                      bidirectional=False, use_pallas=False, cell=cell)
    model = build_model(cfg)

    def init_params(seed: int):
        return model.init({"params": jax.random.PRNGKey(seed)},
                          jnp.zeros((1, WINDOW, FEATURES)))["params"]

    params = init_params(0)

    def fresh_gateway():
        pool = SessionPool(cfg, params, capacity=tickers, window=WINDOW)
        gw = FleetGateway(
            pool,
            batcher_config=BatcherConfig(bucket_sizes=buckets,
                                         max_linger_s=0.002))
        for b in buckets:
            pool.step(np.full(b, pool.padding_slot, np.int32),
                      np.zeros((b, FEATURES), np.float32))
        assert pool.compile_count == len(buckets)
        pool.mark_warm()
        return gw, pool

    source = SyntheticHistory(tickers, rounds, FEATURES, seed=0)

    # (a) the backfill under test
    gw_a, pool_a = fresh_gateway()
    drv = ReplayDriver(gw_a, source, collect=True)
    rep = drv.run()

    # (b) the cadence-paced live baseline over the same rows
    gw_b, pool_b = fresh_gateway()
    live = run_live_reference(gw_b, source, cadence_s=cadence_s,
                              collect=True)

    def by_key(results):
        return sorted(results, key=lambda r: (r.session_id, r.seq))

    a, b = by_key(drv.results), by_key(live["results"])
    identity_ok = (
        len(a) == len(b)
        and all(x.session_id == y.session_id and x.seq == y.seq
                and np.array_equal(x.probabilities, y.probabilities)
                for x, y in zip(a, b)))

    # (c) the same backfill with a checkpoint landing halfway through
    gw_c, pool_c = fresh_gateway()
    swap_at = rounds // 2
    swapped: dict = {}

    def on_round(r):
        if not swapped and r + 1 >= swap_at:
            swapped["version"] = gw_c.hot_swap(init_params(1))
            swapped["round"] = r + 1

    drv_c = ReplayDriver(gw_c, source, collect=True, on_round=on_round)
    swap_run = drv_c.run()
    c = by_key(drv_c.results)
    # seq == round index under lockstep duty=1.0, so the swap round
    # splits the result stream exactly
    seqs_ok = all(
        [r.seq for r in c if r.session_id == f"T{i:04d}"]
        == list(range(rounds)) for i in range(tickers))
    pre = [(x, y) for x, y in zip(a, c) if y.seq < swapped.get("round", 0)]
    post = [(x, y) for x, y in zip(a, c)
            if y.seq >= swapped.get("round", 0)]
    pre_identical = all(
        np.array_equal(x.probabilities, y.probabilities) for x, y in pre)
    post_new_weights = any(
        not np.array_equal(x.probabilities, y.probabilities)
        for x, y in post)

    return {
        "replay_rows_per_s": rep["rows_per_s"],
        "replay_ticks_per_s": rep["ticks_per_s"],
        "live_ticks_per_s": live["ticks_per_s"],
        "speedup_vs_live": (
            round(rep["ticks_per_s"] / live["ticks_per_s"], 2)
            if live["ticks_per_s"] else None),
        "compile_count": rep["compile_count"],
        "identity_ok": identity_ok,
        "hot_swap": {
            "round": swapped.get("round"),
            "weights_version": swapped.get("version"),
            "dropped_sessions": tickers - swap_run["sessions"],
            "downtime_rounds": rounds - swap_run["rounds"],
            "ticks_lost": tickers * rounds - swap_run["ticks_served"],
            "seqs_contiguous": seqs_ok,
            "recompiles_after_warmup": pool_c.recompiles_after_warmup,
            "pre_swap_identical": pre_identical,
            "post_swap_new_weights": post_new_weights,
        },
    }


def phase_replay_throughput() -> dict:
    """Fleet-scale historical replay (docs/replay.md): the virtual-clock
    max-speed backfill vs the cadence-paced live loop, per carried-state
    cell family, with the mid-backfill checkpoint hot swap.

    Three hard gates on a quiet host, two of them host-load-independent:

    * **speed** (quiet hosts only, else ``gate_inert``): replay ticks/s
      must be >= 3x the cadence-paced live loop for every cell.  The
      cadence here (25 ms/round) is the market's 60 s bar cadence
      compressed ~2400x so the phase fits CI — the gate measures the
      pacing deletion, which is cadence-scale-free at >=3x.
    * **identity** (always): replay results sorted by (session, seq)
      are byte-equal to the live loop's over the same row sequence —
      the backfill serves through the UNMODIFIED path or this fails.
    * **hot swap** (always): the halfway checkpoint swap drops zero
      sessions, loses zero ticks, recompiles nothing after warmup, and
      post-swap results come from the NEW weights while pre-swap
      results stay byte-equal to a swap-free run (the barrier).

    compile_count is pinned to len(buckets) per gateway (asserted in
    the cell run).  Artifact: ``artifacts/replay_throughput.json`` with
    the ``REPLAY_THROUGHPUT_SCHEMA`` top level."""
    tickers, rounds = 16, 96
    buckets = (16,)
    cadence_s = 0.025
    cells = {}
    for cell in FLEET_AB_CELLS:
        cells[cell] = _replay_cell_run(
            cell, tickers, rounds, buckets, cadence_s)
    try:
        load1 = os.getloadavg()[0]
    except OSError:
        load1 = None
    quiet = load1 is not None and load1 < 0.5 * (os.cpu_count() or 1)

    identity_ok = all(c["identity_ok"] for c in cells.values())
    swap_ok = all(
        c["hot_swap"]["dropped_sessions"] == 0
        and c["hot_swap"]["downtime_rounds"] == 0
        and c["hot_swap"]["ticks_lost"] == 0
        and c["hot_swap"]["seqs_contiguous"]
        and c["hot_swap"]["recompiles_after_warmup"] == 0
        and c["hot_swap"]["pre_swap_identical"]
        and c["hot_swap"]["post_swap_new_weights"]
        for c in cells.values())
    result = {
        "tickers": tickers,
        "rounds": rounds,
        "buckets": list(buckets),
        "cadence_s": cadence_s,
        "quiet_host": quiet,
        "cells": cells,
        "identity_ok": identity_ok,
        "hot_swap": {cell: c["hot_swap"] for cell, c in cells.items()},
    }
    assert tuple(sorted(result)) == tuple(sorted(REPLAY_THROUGHPUT_SCHEMA))
    artifact_dir = os.path.join(_REPO_DIR, "artifacts")
    os.makedirs(artifact_dir, exist_ok=True)
    artifact = os.path.join(artifact_dir, "replay_throughput.json")
    with open(artifact, "w") as fh:
        json.dump(result, fh, indent=2, default=str)
    result["artifact"] = os.path.relpath(artifact, _REPO_DIR)

    errors = []
    if not identity_ok:
        errors.append(
            "replay-vs-live bit-identity failed: the backfill's "
            "published probabilities diverge from the cadence-paced "
            "live loop over the same row sequence")
    if not swap_ok:
        bad = {cell: c["hot_swap"] for cell, c in cells.items()}
        errors.append(f"hot-swap zero-downtime gate failed: {bad}")
    if quiet:
        slow = {
            cell: c["speedup_vs_live"] for cell, c in cells.items()
            if c["speedup_vs_live"] is None or c["speedup_vs_live"] < 3.0}
        if slow:
            errors.append(
                f"replay did not beat the cadence-paced live loop 3x "
                f"on a quiet host: {slow}")
    else:
        result["speed_gate"] = "gate_inert: loaded host"
    if errors:
        result["error"] = "; ".join(errors)
    return result


def phase_predictor_fleet() -> dict:
    """Batched-Predictor smoke + latency-SLO gate (ISSUE 5): the
    window-re-scan serving path multiplexed onto the fleet runtime
    (fmda_tpu.runtime.predictor_pool) vs the serial solo Predictor loop
    over the same warehouse, model, and signals — signals/s both ways,
    the speedup headline (acceptance: >= 2x on a quiet host), and
    compile_count == len(buckets).

    The SLO gate mirrors runtime_fleet_smoke's: total (submit→publish)
    p99 must stay under ``FMDA_PREDICTOR_SLO_P99_MS`` (default 250 —
    the batched window forward is O(window·F) device work per signal,
    an order heavier than the carried-state tick).  Violations on a
    quiet host error the phase; a loaded host or ``--slo-soft`` /
    ``FMDA_FLEET_SLO_SOFT=1`` downgrades to report-only."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from fmda_tpu.config import (
        DEFAULT_TOPICS, FeatureConfig, ModelConfig, WarehouseConfig)
    from fmda_tpu.data.normalize import NormParams
    from fmda_tpu.data.synthetic import SyntheticMarketConfig, build_corpus
    from fmda_tpu.models import build_model
    from fmda_tpu.runtime import (
        BatcherConfig, PredictorGateway, PredictorLoadConfig, PredictorPool,
        run_predictor_load)
    from fmda_tpu.serve.predictor import Predictor
    from fmda_tpu.stream import InProcessBus

    buckets = (8, 32)
    fc = FeatureConfig()
    wh, _ = build_corpus(
        fc, SyntheticMarketConfig(seed=1, n_days=4),
        warehouse_config=WarehouseConfig(path=":memory:"))
    feats = len(wh.x_fields)
    cfg = ModelConfig(hidden_size=HIDDEN, n_features=feats,
                      output_size=CLASSES, dropout=0.0,
                      bidirectional=True, use_pallas=False)
    params = build_model(cfg).init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, WINDOW, feats)))["params"]
    norm = NormParams(np.zeros(feats, np.float32),
                      np.ones(feats, np.float32))
    timestamps = wh.timestamps()[WINDOW - 1:]

    # serial reference: the solo Predictor loop, one signal at a time
    solo = Predictor(
        InProcessBus(DEFAULT_TOPICS), wh, cfg, params, norm,
        window=WINDOW, max_staleness_s=None)
    for ts in timestamps[:3]:
        solo.predict_for_timestamp(ts)  # warm compile + sqlite cache
    t0 = _time.perf_counter()
    for ts in timestamps:
        solo.predict_for_timestamp(ts)
    serial_wall = _time.perf_counter() - t0
    serial_per_s = len(timestamps) / serial_wall if serial_wall > 0 else 0.0

    # batched gateway over the SAME warehouse/model/signals
    pool = PredictorPool(cfg, params, norm, window=WINDOW)
    gateway = PredictorGateway(
        pool, InProcessBus(DEFAULT_TOPICS), wh,
        batcher_config=BatcherConfig(bucket_sizes=buckets,
                                     max_linger_s=0.002),
        max_staleness_s=None)
    for b in buckets:  # precompile: the loop prices the steady state
        pool.forward(np.zeros((b, WINDOW, feats), np.float32))
    assert pool.compile_count == len(buckets)
    out = run_predictor_load(
        gateway, timestamps, PredictorLoadConfig(burst=max(buckets)))

    lat = out["latency"]
    p99_ms = lat["total"]["p99_ms"]
    slo_ms = float(os.environ.get("FMDA_PREDICTOR_SLO_P99_MS", "250"))
    soft = os.environ.get("FMDA_FLEET_SLO_SOFT", "") == "1"
    try:
        load1 = os.getloadavg()[0]
    except OSError:
        load1 = None
    quiet = load1 is not None and load1 < 0.5 * (os.cpu_count() or 1)
    batched_per_s = out["signals_per_s"] or 0.0
    speedup = (batched_per_s / serial_per_s) if serial_per_s else None
    result = {
        "signals": len(timestamps),
        "signals_served": out["signals_served"],
        "serial_signals_per_s": round(serial_per_s, 1),
        "batched_signals_per_s": round(batched_per_s, 1),
        "speedup_vs_serial": round(speedup, 2) if speedup else None,
        "tick_p50_ms": lat["total"]["p50_ms"],
        "tick_p99_ms": p99_ms,
        "gather_p50_ms": lat["gather"]["p50_ms"],
        "device_p50_ms": lat["device"]["p50_ms"],
        "compile_count": out["compile_count"],
        "bucket_sizes": list(buckets),
        **device_report(),
        "slo_p99_ms": slo_ms,
        "slo_ok": p99_ms <= slo_ms,
        "slo_quiet_host": quiet,
        "timing_note": "serial = solo Predictor.predict_for_timestamp "
                       "loop (per-signal SQL lookup + window fetch + "
                       "(1,W,F) forward); batched = PredictorGateway "
                       "(one id-lookup query + one vectorized window "
                       "gather + one bucketed forward per flush); same "
                       "warehouse, model, signals; buckets precompiled",
    }
    if out["compile_count"] != len(buckets):
        result["error"] = (
            f"compile_count {out['compile_count']} != {len(buckets)} "
            "buckets: something recompiled on the signal path")
    elif speedup is not None and speedup < 2.0 and quiet and not soft:
        result["error"] = (
            f"batched Predictor speedup {speedup:.2f}x < 2x over the "
            "serial loop on a quiet host (ISSUE 5 acceptance; "
            "--slo-soft / FMDA_FLEET_SLO_SOFT=1 to report-only)")
    elif p99_ms > slo_ms and quiet and not soft:
        result["error"] = (
            f"latency SLO violated: total p99 {p99_ms}ms > {slo_ms}ms "
            "bound on a quiet host (FMDA_PREDICTOR_SLO_P99_MS to "
            "retune, --slo-soft / FMDA_FLEET_SLO_SOFT=1 to report-only)")
    return result


def phase_runtime_multihost() -> dict:
    """Multi-host fleet smoke (ISSUE 6): the distributed serving tier
    (fmda_tpu.fleet, docs/multihost.md) as a real local topology —
    router inline, N worker processes spawned, each hosting its own
    data-plane bus — under the same synthetic multi-ticker load as
    runtime_fleet_smoke, at 1 worker and at 4.

    The scaling measure is **weak scaling** (sessions per worker held
    constant, aggregate ticks/s compared): a session's ticks advance a
    recurrence, so one session's flushes can never parallelise — fleets
    scale by hosting MORE sessions, and that is what the gate prices.
    Acceptance: >= FMDA_MULTIHOST_SCALING_MIN (default 2.5) aggregate
    ticks/s at 4 workers vs 1.  The gate hard-fails only on a quiet
    host with enough cores to actually run 4 workers + router in
    parallel (>= 6); fewer cores physically cap process parallelism,
    so the phase reports the measured scaling with ``gate_inert``
    instead (same philosophy as the SLO gates' quiet-host guard).
    Always gated hard: per-worker compile_count == len(buckets) in
    BOTH topologies (no recompiles on the tick path, no matter how the
    sessions shard), and zero lost/missing ticks.
    """
    from fmda_tpu.fleet.launcher import launch_local_fleet, spawn_supported
    from fmda_tpu.runtime import FleetLoadConfig, run_fleet_load

    if not spawn_supported():
        return {"skipped": "subprocess spawn unavailable on this host"}
    buckets = (8, 32, 64)
    sessions_per_worker, rounds = 64, 100
    per: dict = {}
    loss_counters = ("results_missing", "routed_ticks_lost",
                     "migration_buffer_shed")
    # FMDA_WIRE_FORMAT=json|binary|auto: A/B the ISSUE-12 binary data
    # plane against the JSON rollback format on the same topology
    wire_format = os.environ.get("FMDA_WIRE_FORMAT")
    config = None
    if wire_format:
        import dataclasses

        from fmda_tpu.config import FrameworkConfig

        base = FrameworkConfig()
        config = dataclasses.replace(
            base, fleet=dataclasses.replace(
                base.fleet, wire_format=wire_format))
    for n in (1, 4):
        topo = launch_local_fleet(
            n_workers=n, hidden=HIDDEN, config=config,
            capacity_per_worker=sessions_per_worker * 2,
            bucket_sizes=buckets, seed=0)
        try:
            out = run_fleet_load(topo.router, FleetLoadConfig(
                n_sessions=sessions_per_worker * n, n_ticks=rounds,
                duty=1.0, seed=0))
        finally:
            worker_stats = topo.shutdown()
        counters = out.get("counters", {})
        per[n] = {
            "sessions": sessions_per_worker * n,
            "rounds": rounds,
            "ticks_served": out["ticks_served"],
            "ticks_submitted": out["ticks_submitted"],
            "ticks_per_s": out["ticks_per_s"],
            "route_p50_ms": out["latency"].get("route", {}).get("p50_ms"),
            "total_p99_ms": out["latency"].get("total", {}).get("p99_ms"),
            "compile_counts": {
                w: s.get("compile_count") for w, s in worker_stats.items()},
            "losses": {
                # router-side loss counters + worker-side inbox
                # overruns (those ride the heartbeat stats — the
                # counter never appears in the router's own metrics)
                **{k: counters.get(k, 0) for k in loss_counters
                   if counters.get(k, 0)},
                **{f"{w}.inbox_records_lost": s.get(
                       "inbox_records_lost", 0)
                   for w, s in worker_stats.items()
                   if s.get("inbox_records_lost", 0)},
            },
        }
    t1 = per[1]["ticks_per_s"] or 0.0
    t4 = per[4]["ticks_per_s"] or 0.0
    scaling = round(t4 / t1, 2) if t1 else None
    scaling_min = float(os.environ.get("FMDA_MULTIHOST_SCALING_MIN", "2.5"))
    soft = os.environ.get("FMDA_FLEET_SLO_SOFT", "") == "1"
    try:
        load1 = os.getloadavg()[0]
    except OSError:
        load1 = None
    cores = os.cpu_count() or 1
    quiet = load1 is not None and load1 < 0.5 * cores
    enough_cores = cores >= 6  # 4 workers + router + slack
    result = {
        "workers_1": per[1],
        "workers_4": per[4],
        "scaling_4x": scaling,
        "scaling_min": scaling_min,
        "scaling_mode": "weak (sessions per worker constant)",
        "cpu_count": cores,
        "quiet_host": quiet,
        "bucket_sizes": list(buckets),
        "wire_format": wire_format or "auto",
    }
    bad_compile = {
        f"{n}w/{w}": c
        for n in (1, 4)
        for w, c in per[n]["compile_counts"].items()
        if c != len(buckets)
    }
    losses = {n: per[n]["losses"] for n in (1, 4) if per[n]["losses"]}
    if bad_compile:
        result["error"] = (
            f"compile_count != {len(buckets)} buckets on {bad_compile}: "
            "something recompiled on the tick path")
    elif per[1]["ticks_served"] != per[1]["ticks_submitted"] or \
            per[4]["ticks_served"] != per[4]["ticks_submitted"] or losses:
        result["error"] = (
            f"ticks went missing (served != submitted or loss counters "
            f"fired: {losses}) — the no-drop contract broke")
    elif scaling is not None and scaling < scaling_min \
            and quiet and enough_cores and not soft:
        result["error"] = (
            f"aggregate scaling {scaling}x < {scaling_min}x at 4 workers "
            "on a quiet multi-core host (FMDA_MULTIHOST_SCALING_MIN to "
            "retune, FMDA_FLEET_SLO_SOFT=1 to report-only)")
    elif scaling is not None and scaling < scaling_min:
        result["gate_inert"] = (
            f"scaling {scaling}x below {scaling_min}x but the gate needs "
            f"a quiet host with >= 6 cores (have {cores}, quiet={quiet}) "
            "— processes cannot run in parallel here")
    return result


def phase_control_capacity_model() -> dict:
    """Capacity model (ISSUE 16): the control plane's empirical sizing
    sweep (fmda_tpu.control.capacity, docs/control.md) on a real
    gateway — sessions × arrival-rate grid, each cell a fresh pool +
    gateway serving a seeded load, sustainable when p99 meets the SLO
    with zero sheds and served == submitted.  The phase result IS the
    pinned-schema artifact (``fmda.control.capacity/1``) plus the gate
    verdicts, so a bench run leaves the sizing table downstream tooling
    parses.

    Always gated hard: schema intact, every cell conserving ticks
    (served + shed == submitted — a leak here is a gateway bug, not a
    perf matter), and per-cell compile_count == len(buckets).  The
    fixed-vs-adaptive linger A/B (the batching controller steering the
    heaviest cell toward half the fixed-linger p99) hard-gates
    ``improved`` only on a quiet host with >= 6 cores — same quietness
    rule as the multihost scaling gate; elsewhere it reports
    ``gate_inert`` (timer-resolution noise on a starved host can hide a
    sub-millisecond win).  ``FMDA_FLEET_SLO_SOFT=1`` downgrades to
    report-only either way.
    """
    import jax
    import jax.numpy as jnp

    from fmda_tpu.config import ModelConfig
    from fmda_tpu.control.capacity import run_capacity_model
    from fmda_tpu.models import build_model
    from fmda_tpu.runtime import BatcherConfig, FleetGateway, SessionPool

    buckets = (8, 32)
    cfg = ModelConfig(hidden_size=HIDDEN, n_features=FEATURES,
                      output_size=CLASSES, dropout=0.0,
                      bidirectional=False, use_pallas=False)
    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, WINDOW, FEATURES)))["params"]
    pools: list = []

    def gateway_factory(n_sessions: int) -> FleetGateway:
        pool = SessionPool(cfg, params, capacity=n_sessions,
                          window=WINDOW)
        # steady-state cells: compile every bucket up front on
        # padding-only flushes so no cell's p99 pays compile time
        for b in buckets:
            pool.step(np.full(b, pool.padding_slot, np.int32),
                      np.zeros((b, FEATURES), np.float32))
        pools.append(pool)
        return FleetGateway(
            pool, batcher_config=BatcherConfig(
                bucket_sizes=buckets, max_linger_s=0.002))

    slo_ms = float(os.environ.get("FMDA_FLEET_SLO_P99_MS", "50"))
    artifact = run_capacity_model(
        gateway_factory, slo_p99_ms=slo_ms,
        session_grid=(8, 16, 32), duty_grid=(0.25, 0.5, 1.0),
        rounds=60, seed=0)
    soft = os.environ.get("FMDA_FLEET_SLO_SOFT", "") == "1"
    try:
        load1 = os.getloadavg()[0]
    except OSError:
        load1 = None
    cores = os.cpu_count() or 1
    quiet = load1 is not None and load1 < 0.5 * cores
    result = dict(artifact)
    result.update({
        "bucket_sizes": list(buckets),
        "cpu_count": cores,
        "quiet_host": quiet,
        "compile_counts": [p.compile_count for p in pools],
    })
    leaks = [
        {"sessions": c["sessions"], "duty": c["duty"],
         "submitted": c["submitted"],
         "served": c["served"], "shed": c["shed"]}
        for c in artifact["grid"]
        if c["served"] + c["shed"] != c["submitted"]
    ]
    bad_compile = [p.compile_count for p in pools
                   if p.compile_count != len(buckets)]
    ab = artifact.get("controller_ab") or {}
    if leaks:
        result["error"] = (
            f"ticks leaked in {len(leaks)} cell(s) (served + shed != "
            f"submitted: {leaks[:3]}) — the gateway's conservation "
            "contract broke")
    elif bad_compile:
        result["error"] = (
            f"compile_count != {len(buckets)} buckets ({bad_compile}): "
            "something recompiled on the capacity sweep's tick path")
    elif ab and ab.get("fixed_p99_ms") and not ab.get("improved") \
            and quiet and cores >= 6 and not soft:
        result["error"] = (
            f"batching controller A/B did not improve p99 "
            f"(fixed {ab.get('fixed_p99_ms')}ms vs adaptive "
            f"{ab.get('adaptive_p99_ms')}ms after {ab.get('decisions')} "
            "decisions) on a quiet multi-core host "
            "(FMDA_FLEET_SLO_SOFT=1 to report-only)")
    elif ab and ab.get("fixed_p99_ms") and not ab.get("improved"):
        result["gate_inert"] = (
            f"controller A/B not improved (fixed {ab.get('fixed_p99_ms')}"
            f"ms vs adaptive {ab.get('adaptive_p99_ms')}ms) but the gate "
            f"needs a quiet host with >= 6 cores (have {cores}, "
            f"quiet={quiet})")
    return result


def phase_runtime_chaos_soak() -> dict:
    """Chaos soak (ISSUE 7): the full local multi-host topology under a
    seeded fault plan — a worker SIGKILLed and revived, a router
    takeover (registry rebuilt from worker session reports), a
    control-bus outage, a data-link partition, injected delays — while
    a burst + slow-drip loadgen mix runs, hard-gating the never-abort
    contract:

    - this phase's subprocess exiting 0 is gate zero (nothing may
      abort under the plan);
    - zero uncounted losses: submitted == served + the loss counters
      (every drop/reopen/replay appears in a metric);
    - router failover rebuilds the registry with no orphaned session,
      and every session serves ticks again after the last fault;
    - surviving (untouched) sessions are bit-identical to an unfaulted
      run of the same tick schedule (bucket 1 — composition cannot
      perturb reduction order).

    The plan is a pure function of the seed (FMDA_CHAOS_SEED) — a
    failing soak is a reproduction recipe, not an anecdote.
    """
    from fmda_tpu.chaos.plan import FaultPlan
    from fmda_tpu.chaos.soak import run_chaos_soak
    from fmda_tpu.fleet.launcher import spawn_supported

    if not spawn_supported():
        return {"skipped": "subprocess spawn unavailable on this host"}
    seed = int(os.environ.get("FMDA_CHAOS_SEED", "0"))
    workers = ["w0", "w1"]
    rounds = 60
    plan = FaultPlan.generate(
        seed, rounds, workers=workers,
        worker_kills=1, revive_after=10, router_restarts=1,
        link_partitions=1, bus_blips=1, delays=2, delay_s=0.02,
        settle_steps=12)
    out = run_chaos_soak(
        plan, n_workers=len(workers), n_sessions=12, hidden=HIDDEN,
        seed=seed, compare_unfaulted=True)
    result = {
        "seed": seed,
        "rounds": rounds,
        "plan": out["plan"],
        "chaos_injected": out["chaos_injected"],
        "ticks_submitted": out["ticks_submitted"],
        "ticks_served": out["ticks_served"],
        "losses": out["losses"],
        "unaccounted": out["unaccounted"],
        "takeovers": out["takeovers"],
        "tainted_sessions": out["tainted_sessions"],
        "identity": {k: v for k, v in out.get("identity", {}).items()},
        "gates": out["gates"],
        "degradation_counters": out["degradation_counters"],
    }
    failed = [g for g, ok in out["gates"].items() if not ok]
    if failed:
        result["error"] = (
            f"never-abort gates failed: {failed} (seed {seed} "
            "reproduces the plan; see degradation_counters and "
            "docs/chaos.md)")
    return result


def phase_pipeline_chaos_soak() -> dict:
    """Data-plane chaos soak (ISSUE 10): synthetic feeds → join engine →
    write-ahead-journaled warehouse → solo Predictor, in-process, under
    a seeded plan that takes one side feed down (degraded-mode joins),
    makes the warehouse unreachable (journal spill + backfill), and
    kills the engine mid-stream (checkpoint restore + crash-replay
    dedupe).  Hard gates (docs/chaos.md "Data-plane faults"):

    - exit 0 with ``ingested == landed + Σ loss counters`` across the
      engine kill/restore (zero unaccounted rows);
    - degraded-mode entered AND exited (rows emitted with last-known
      side features during the outage, clean joins after recovery);
    - journal spilled AND drained to zero;
    - post-chaos probe bars land through the recovered pipeline and are
      served by the predictor;
    - clean-path rows bit-identical to an unfaulted replay (raw landed
      bytes).

    The plan replays from FMDA_CHAOS_SEED.
    """
    from fmda_tpu.chaos.pipeline import (
        generate_pipeline_plan, run_pipeline_soak)

    seed = int(os.environ.get("FMDA_CHAOS_SEED", "0"))
    rounds = 30
    plan = generate_pipeline_plan(seed, rounds)
    out = run_pipeline_soak(
        plan, seed=seed, rounds=rounds, predictor=True,
        compare_unfaulted=True)
    result = {
        "seed": seed,
        "rounds": rounds,
        "plan": out["plan"],
        "chaos_injected": out["chaos_injected"],
        "ingested": out["ingested"],
        "landed": out["landed"],
        "losses": out["losses"],
        "unaccounted": out["unaccounted"],
        "degraded_rows": out["degraded_rows"],
        "journal": out["journal"],
        "engine_restarts": out["engine_restarts"],
        "served": out["served"],
        "identity": out.get("identity", {}),
        "gates": out["gates"],
    }
    failed = [g for g, ok in out["gates"].items() if not ok]
    if failed:
        result["error"] = (
            f"data-plane never-abort gates failed: {failed} (seed "
            f"{seed} reproduces the plan; see docs/chaos.md)")
    return result


def phase_obs_overhead() -> dict:
    """Observability-plane cost on the engine.step hot loop: the same
    synthetic replay driven twice per repetition — once with the obs
    registry fully wired (per-step histogram, bus publish/consume
    counters, warehouse write timing, scrape-time collectors
    registered), once bare — interleaved, min-of-reps, overhead as a
    percentage.  The plane's contract is <2% (docs/observability.md);
    ``ok`` asserts it."""
    import time as _time

    from fmda_tpu.config import DEFAULT_TOPICS, FeatureConfig
    from fmda_tpu.data.synthetic import (
        SyntheticMarketConfig, synthetic_session_messages)
    from fmda_tpu.obs import MetricsRegistry, engine_families
    from fmda_tpu.stream import InProcessBus, StreamEngine, Warehouse
    from fmda_tpu.stream.warehouse import WarehouseConfig

    fc = FeatureConfig()
    n_days, reps = 80, 3
    msgs = list(synthetic_session_messages(
        fc, SyntheticMarketConfig(seed=5, n_days=n_days)))
    # many small steps (not one bulk step): the per-step instrumentation
    # is what this phase prices
    chunk = max(1, len(msgs) // 400)

    def run_once(instrumented: bool) -> float:
        bus = InProcessBus(DEFAULT_TOPICS, capacity=1 << 18)
        wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
        reg = MetricsRegistry() if instrumented else None
        eng = StreamEngine(bus, wh, fc, metrics=reg)
        if reg is not None:
            reg.register_collector(
                "engine", lambda eng=eng: engine_families(eng))
            bus.bind_metrics(reg)
            wh.bind_metrics(reg)
        t0 = _time.monotonic()
        for i in range(0, len(msgs), chunk):
            for topic, m in msgs[i:i + chunk]:
                bus.publish(topic, m)
            eng.step()
        elapsed = _time.monotonic() - t0
        if reg is not None:
            # a scrape mid-load must not distort the loop measurably
            reg.snapshot()
        return elapsed

    run_once(False)  # warm caches (sqlite pages, numpy, parser paths)
    bare, wired = [], []
    for _ in range(reps):
        bare.append(run_once(False))
        wired.append(run_once(True))
    base, inst = min(bare), min(wired)
    overhead_pct = (inst - base) / base * 100.0
    return {
        "n_messages": len(msgs),
        "steps": (len(msgs) + chunk - 1) // chunk,
        "reps": reps,
        "bare_wall_s": round(base, 3),
        "instrumented_wall_s": round(inst, 3),
        "overhead_pct": round(overhead_pct, 2),
        "budget_pct": 2.0,
        "ok": overhead_pct < 2.0,
    }


def phase_trace_overhead() -> dict:
    """Tracing cost on the fleet-serving hot loop (ISSUE 4): the same
    synthetic fleet load run with the tracer (a) compiled in but
    disabled — the default state, pricing the one-branch contract — and
    (b) enabled at 1% sampling — the documented production setting —
    interleaved, min-of-reps, overhead as a percentage of the disabled
    baseline.  The contract is <2% for the sampled path
    (docs/observability.md); ``ok`` asserts it on a quiet host only
    (the measurement is sub-noise-floor on a loaded one)."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from fmda_tpu.config import DEFAULT_TOPICS, ModelConfig
    from fmda_tpu.models import build_model
    from fmda_tpu.obs.trace import configure_tracing
    from fmda_tpu.runtime import (
        BatcherConfig, FleetGateway, FleetLoadConfig, SessionPool,
        run_fleet_load)
    from fmda_tpu.stream import InProcessBus

    sessions, rounds, reps = 32, 150, 5
    bucket = 32
    cfg = ModelConfig(hidden_size=16, n_features=FEATURES,
                      output_size=CLASSES, dropout=0.0,
                      bidirectional=False, use_pallas=False)
    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, WINDOW, FEATURES)))["params"]

    def run_once(sample_rate) -> float:
        configure_tracing(
            enabled=sample_rate is not None,
            sample_rate=sample_rate if sample_rate is not None else 1.0,
        )
        try:
            pool = SessionPool(cfg, params, capacity=sessions,
                               window=WINDOW)
            bus = InProcessBus(DEFAULT_TOPICS)
            gateway = FleetGateway(
                pool, bus,
                batcher_config=BatcherConfig(bucket_sizes=(bucket,),
                                             max_linger_s=0.002))
            # precompile so the loop prices the steady state, not XLA
            pool.step(np.full(bucket, pool.padding_slot, np.int32),
                      np.zeros((bucket, FEATURES), np.float32))
            t0 = _time.monotonic()
            run_fleet_load(gateway, FleetLoadConfig(
                n_sessions=sessions, n_ticks=rounds, duty=1.0, seed=0))
            return _time.monotonic() - t0
        finally:
            configure_tracing(enabled=False)

    run_once(None)  # warm caches
    disabled, sampled = [], []
    for _ in range(reps):
        disabled.append(run_once(None))
        sampled.append(run_once(0.01))
    base, inst = min(disabled), min(sampled)
    overhead_pct = (inst - base) / base * 100.0
    try:
        load1 = os.getloadavg()[0]
    except OSError:
        load1 = None
    quiet = load1 is not None and load1 < 0.5 * (os.cpu_count() or 1)
    return {
        "sessions": sessions,
        "rounds": rounds,
        "reps": reps,
        "disabled_wall_s": round(base, 3),
        "sampled_1pct_wall_s": round(inst, 3),
        "overhead_pct": round(overhead_pct, 2),
        "budget_pct": 2.0,
        "quiet_host": quiet,
        "ok": overhead_pct < 2.0 or not quiet,
    }


QUALITY_EVAL_SCHEMA = (
    "sessions", "rounds", "reps", "disabled_wall_s", "enabled_wall_s",
    "overhead_pct", "budget_pct", "quiet_host", "joined", "join_wall_s",
    "conservation_ok", "ok",
)


def phase_quality_overhead() -> dict:
    """Label-join evaluator cost on the replay serving loop (ISSUE 19):
    the same warehoused backfill run with the quality plane off vs on,
    interleaved, min-of-reps.  What rides the tick path is ONLY the
    per-result capture (lock + bounded-ring insert); the label join is
    cadence-gated onto the telemetry collection cadence, exactly like
    SLO evaluation — so the <2% budget gates the capture overhead, and
    the join round (one batched ``ids_for_timestamps`` +
    ``fetch_targets`` query) is timed separately as ``join_wall_s``,
    outside the serving loop it never runs on.  The enabled run must
    also join predictions and close the capture conservation identity
    (``captured == joined + expired + shed + pending``).  Artifact:
    ``artifacts/quality_eval.json`` (``QUALITY_EVAL_SCHEMA`` top
    level) — feed it to ``python -m fmda_tpu quality --artifact``."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from fmda_tpu.config import FeatureConfig, ModelConfig, QualityConfig
    from fmda_tpu.data.synthetic import SyntheticMarketConfig, build_corpus
    from fmda_tpu.models import build_model
    from fmda_tpu.obs.quality import QualityEvaluator
    from fmda_tpu.replay import ReplayDriver, WarehouseHistory
    from fmda_tpu.runtime import BatcherConfig, FleetGateway, SessionPool

    sessions, reps = 8, 9
    fc = FeatureConfig()
    wh, _ = build_corpus(fc, SyntheticMarketConfig(seed=2, n_days=3))
    # landed table width (raw columns), not the derived x_fields view —
    # WarehouseHistory streams raw landed rows
    feats = len(fc.table_columns())
    rounds = len(wh) // sessions
    # flagship-ish serving dims: the budget is relative to a REAL tick's
    # device+dispatch cost, not a toy cell that makes any fixed
    # per-capture cost look enormous
    cfg = ModelConfig(hidden_size=4 * HIDDEN, n_features=feats,
                      output_size=CLASSES, dropout=0.0,
                      bidirectional=False, use_pallas=False)
    params = build_model(cfg).init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, WINDOW, feats)))["params"]
    # the join NEVER fires inside the timed serving loop: production
    # joins ride the telemetry collection cadence (a wall-clock
    # interval the compressed virtual clock here would fire every
    # round), so the loop pays only capture and the join round is
    # priced separately below
    qcfg = QualityConfig(join_interval_s=1e12)

    state = {}

    def run_once(with_quality: bool) -> float:
        pool = SessionPool(cfg, params, capacity=sessions, window=WINDOW)
        gateway = FleetGateway(
            pool, None,
            batcher_config=BatcherConfig(bucket_sizes=(sessions,),
                                         max_linger_s=0.0))
        pool.step(np.full(sessions, pool.padding_slot, np.int32),
                  np.zeros((sessions, feats), np.float32))
        pool.mark_warm()
        quality = (QualityEvaluator(qcfg, warehouse=wh,
                                    max_lead=fc.max_lead)
                   if with_quality else None)
        source = WarehouseHistory(wh, sessions, n_features=feats)
        driver = ReplayDriver(gateway, source, seed=0, quality=quality)
        t0 = _time.monotonic()
        driver.run()
        wall = _time.monotonic() - t0
        if quality is not None:
            t0 = _time.monotonic()
            quality.join()  # the cadence path, timed on its own
            state["join_wall_s"] = _time.monotonic() - t0
            state["conservation"] = quality.conservation()
        return wall

    run_once(False)  # warm caches, both variants
    run_once(True)
    disabled, enabled = [], []
    for _ in range(reps):
        disabled.append(run_once(False))
        enabled.append(run_once(True))
    base, inst = min(disabled), min(enabled)
    overhead_pct = (inst - base) / base * 100.0
    cons = state["conservation"]
    conservation_ok = (
        cons["captured"]
        == cons["joined"] + cons["expired"] + cons["shed"] + cons["pending"])
    try:
        load1 = os.getloadavg()[0]
    except OSError:
        load1 = None
    quiet = load1 is not None and load1 < 0.5 * (os.cpu_count() or 1)
    result = {
        "sessions": sessions,
        "rounds": rounds,
        "reps": reps,
        "disabled_wall_s": round(base, 3),
        "enabled_wall_s": round(inst, 3),
        "overhead_pct": round(overhead_pct, 2),
        "budget_pct": 2.0,
        "quiet_host": quiet,
        "joined": cons["joined"],
        "join_wall_s": round(state["join_wall_s"], 4),
        "conservation_ok": conservation_ok,
        "ok": (conservation_ok and cons["joined"] > 0
               and (overhead_pct < 2.0 or not quiet)),
    }
    assert tuple(sorted(result)) == tuple(sorted(QUALITY_EVAL_SCHEMA))
    artifact_dir = os.path.join(_REPO_DIR, "artifacts")
    os.makedirs(artifact_dir, exist_ok=True)
    artifact = os.path.join(artifact_dir, "quality_eval.json")
    with open(artifact, "w") as fh:
        json.dump(result, fh, indent=2, default=str)
    result["artifact"] = os.path.relpath(artifact, _REPO_DIR)
    errors = []
    if not conservation_ok:
        errors.append(f"capture conservation identity broken: {cons}")
    if cons["joined"] <= 0:
        errors.append("label join produced zero joined predictions — "
                      "the evaluator never scored anything")
    if quiet and overhead_pct >= 2.0:
        errors.append(
            f"quality plane costs {overhead_pct:.2f}% of the replay "
            "loop on a quiet host (budget 2%)")
    if errors:
        result["error"] = "; ".join(errors)
    return result


def phase_device_obs_overhead() -> dict:
    """Device-observability cost on the serving step seam (ISSUE 17):
    the same warmed SessionPool stepped with the whole device plane
    on — tracked-jit ledger accounting per call, the memory watermark
    monitor's cadence check per step (the worker-loop seam), and the
    continuous host sampling profiler — vs fully disabled,
    interleaved, min-of-reps.  Budget <2% on a quiet host, the
    tracer's contract.  The step loop is driven directly (not through
    the gateway) because the batcher's linger scheduling noise is an
    order of magnitude above the cost being priced.  The enabled
    run's compile ledger (pinned LEDGER_SCHEMA, cost-analysis FLOPs
    populated at precompile) lands at ``artifacts/device_ledger.json``
    — feed it to ``python -m fmda_tpu perf --input``."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from fmda_tpu.config import ModelConfig
    from fmda_tpu.models import build_model
    from fmda_tpu.obs.device import (
        LEDGER_SCHEMA, default_ledger, default_memory_monitor)
    from fmda_tpu.obs.pyprof import HostProfiler
    from fmda_tpu.runtime import SessionPool

    sessions, steps, reps = 32, 300, 6
    cfg = ModelConfig(hidden_size=16, n_features=FEATURES,
                      output_size=CLASSES, dropout=0.0,
                      bidirectional=False, use_pallas=False)
    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, WINDOW, FEATURES)))["params"]
    ledger = default_ledger()
    memory = default_memory_monitor()
    ledger.reset()
    ledger.enabled = True
    ledger.cost_analysis = True  # FLOPs land at the precompile below
    memory.enabled = True
    pool = SessionPool(cfg, params, capacity=sessions, window=WINDOW)
    memory.register_owner("session_pool:bench", pool.live_tree)
    slots = np.full(sessions, pool.padding_slot, np.int32)
    feats = np.zeros((sessions, FEATURES), np.float32)
    # precompile (and pay the one cost probe) OUTSIDE every timed
    # region, then declare warmup over: the loop prices the
    # steady-state tracking cost a warmed serving host pays
    pool.step(slots, feats)
    pool.mark_warm()
    for _ in range(200):  # warm caches/allocator before any timing
        pool.step(slots, feats)
    profile_samples = 0

    def run_once(enabled: bool) -> float:
        nonlocal profile_samples
        ledger.enabled = enabled
        memory.enabled = enabled
        profiler = HostProfiler() if enabled else None
        try:
            if profiler is not None:
                profiler.start()
            t0 = _time.perf_counter()
            for _ in range(steps):
                pool.step(slots, feats)
                memory.maybe_sample()  # the worker-loop seam: one
                #                        clock read when not due
            return _time.perf_counter() - t0
        finally:
            if profiler is not None:
                profiler.stop()
                profile_samples = max(
                    profile_samples,
                    sum(profiler.parse_folded(profiler.folded())
                        .values()))
            ledger.enabled = True
            memory.enabled = True

    disabled, instrumented = [], []
    for _ in range(reps):
        disabled.append(run_once(False))
        instrumented.append(run_once(True))
    base, inst = min(disabled), min(instrumented)
    overhead_pct = (inst - base) / base * 100.0
    memory.sample()  # populate the artifact's memory doc
    dump = ledger.dump()
    ledger.cost_analysis = False
    assert tuple(sorted(dump)) == tuple(sorted(LEDGER_SCHEMA))
    artifact_dir = os.path.join(_REPO_DIR, "artifacts")
    os.makedirs(artifact_dir, exist_ok=True)
    artifact = os.path.join(artifact_dir, "device_ledger.json")
    with open(artifact, "w") as fh:
        json.dump(dump, fh, indent=2, default=str)
    try:
        load1 = os.getloadavg()[0]
    except OSError:
        load1 = None
    quiet = load1 is not None and load1 < 0.5 * (os.cpu_count() or 1)
    return {
        "sessions": sessions,
        "steps": steps,
        "reps": reps,
        "disabled_wall_s": round(base, 3),
        "enabled_wall_s": round(inst, 3),
        "overhead_pct": round(overhead_pct, 2),
        "budget_pct": 2.0,
        "quiet_host": quiet,
        "compiles": dump["compiles_total"],
        "profile_samples": profile_samples,
        "recompiles_after_warmup": dump["unexpected_recompiles_total"],
        "cost_probe_failures": dump["cost_probe_failures"],
        "artifact": os.path.relpath(artifact, _REPO_DIR),
        "ok": ((overhead_pct < 2.0 or not quiet)
               and dump["unexpected_recompiles_total"] == 0),
    }


def phase_obs_aggregate_overhead() -> dict:
    """Fleet-telemetry cost on the serving hot loop (ISSUE 13): the same
    synthetic fleet load run (a) bare and (b) with the full aggregation
    + SLO-evaluation path folding on a tight cadence — histogram
    snapshots into the time-series store, counter rates, burn-rate
    evaluation over both windows — interleaved, min-of-reps, overhead as
    a percentage.  The aggregation path's contract is pull-based
    scrape-time work only (<2% of the loop, docs/observability.md);
    ``ok`` asserts it on a quiet host (noise floor otherwise).  The
    cadence here (20 ms) is ~250x denser than the shipped 5 s default —
    a deliberate worst case."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from fmda_tpu.config import DEFAULT_TOPICS, ModelConfig, SLOConfig
    from fmda_tpu.models import build_model
    from fmda_tpu.obs.aggregate import FleetTelemetry
    from fmda_tpu.runtime import (
        BatcherConfig, FleetGateway, FleetLoadConfig, SessionPool,
        run_fleet_load)
    from fmda_tpu.stream import InProcessBus

    sessions, rounds, reps = 32, 150, 5
    bucket = 32
    fold_every_s = 0.02
    cfg = ModelConfig(hidden_size=16, n_features=FEATURES,
                      output_size=CLASSES, dropout=0.0,
                      bidirectional=False, use_pallas=False)
    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, WINDOW, FEATURES)))["params"]

    def run_once(instrumented: bool) -> float:
        pool = SessionPool(cfg, params, capacity=sessions, window=WINDOW)
        bus = InProcessBus(DEFAULT_TOPICS)
        gateway = FleetGateway(
            pool, bus,
            batcher_config=BatcherConfig(bucket_sizes=(bucket,),
                                         max_linger_s=0.002))
        pool.step(np.full(bucket, pool.padding_slot, np.int32),
                  np.zeros((bucket, FEATURES), np.float32))
        on_round = None
        if instrumented:
            telemetry = FleetTelemetry(SLOConfig(
                interval_s=fold_every_s, retention_s=60.0,
                fast_window_s=0.5, slow_window_s=2.0))
            state = {"last": 0.0}

            def on_round(r):
                now = _time.monotonic()
                if now - state["last"] >= fold_every_s:
                    state["last"] = now
                    telemetry.collect_gateway(gateway)

        t0 = _time.monotonic()
        run_fleet_load(gateway, FleetLoadConfig(
            n_sessions=sessions, n_ticks=rounds, duty=1.0, seed=0),
            on_round=on_round)
        return _time.monotonic() - t0

    run_once(False)  # warm caches
    bare, wired = [], []
    for _ in range(reps):
        bare.append(run_once(False))
        wired.append(run_once(True))
    base, inst = min(bare), min(wired)
    overhead_pct = (inst - base) / base * 100.0
    try:
        load1 = os.getloadavg()[0]
    except OSError:
        load1 = None
    quiet = load1 is not None and load1 < 0.5 * (os.cpu_count() or 1)
    return {
        "sessions": sessions,
        "rounds": rounds,
        "reps": reps,
        "fold_every_s": fold_every_s,
        "bare_wall_s": round(base, 3),
        "aggregated_wall_s": round(inst, 3),
        "overhead_pct": round(overhead_pct, 2),
        "budget_pct": 2.0,
        "quiet_host": quiet,
        "ok": overhead_pct < 2.0 or not quiet,
    }


#: the ISSUE-15 never-abort analyzers: held at ZERO findings outright
#: (new, baselined, anything) — deliberate exceptions annotate in place,
#: never in the baseline.  Pinned by test_bench_helpers.
NEVER_ABORT_RULES = ("counted-loss", "wire-protocol", "thread-lifecycle")


def phase_analysis_lint() -> dict:
    """Cost guard for the static-analysis gate (ISSUE 8): the whole rule
    suite — drift resolver included — over the parsed-module cache must
    stay a single-digit-seconds affair, or nobody runs it pre-commit and
    tier-1 eats the slowdown.  Also re-asserts the gate itself: zero
    non-baselined findings (`ok` covers both), and — since ISSUE 15 —
    ZERO findings of any kind for the never-abort rules (not merely
    zero new: those contracts admit no grandfathered debt).  Budget is
    generous (10 s) because the drift rule imports jax submodules on
    first resolution; the second run prices the warm path the pytest
    wrapper pays."""
    import time as _time

    from fmda_tpu.analysis import (
        collect_modules,
        default_rules,
        load_baseline,
        run_lint,
    )

    t0 = _time.monotonic()
    result = run_lint(default_rules())
    cold_s = _time.monotonic() - t0
    # warm: jax imports + resolution cache primed; re-parse dominates
    t0 = _time.monotonic()
    ctx = collect_modules()
    result2 = run_lint(default_rules(), ctx=ctx)
    warm_s = _time.monotonic() - t0
    budget_s = 10.0
    # the drift rule is a zero-baseline hard gate (PR 9): the kernel
    # surface carries zero unresolved jax refs AND the baseline holds no
    # drift entries — both asserted here so the bench agrees with lint
    # and the tier-1 test
    drift_symbols = result.reports.get("jax_api_drift", {}).get("n_symbols")
    drift_baseline_entries = len(
        [e for e in load_baseline() if e["rule"] == "jax-api-drift"])
    never_abort_findings = len(
        [f for f in result.new + result.baselined
         if f.rule in NEVER_ABORT_RULES])
    never_abort_baseline_entries = len(
        [e for e in load_baseline() if e["rule"] in NEVER_ABORT_RULES])
    return {
        "n_modules": result.n_modules,
        "n_rules": len(default_rules()),
        "new_findings": len(result.new),
        "baselined": len(result.baselined),
        "drift_symbols": drift_symbols,
        "drift_baseline_entries": drift_baseline_entries,
        "never_abort_findings": never_abort_findings,
        "never_abort_baseline_entries": never_abort_baseline_entries,
        "cold_wall_s": round(cold_s, 3),
        "warm_wall_s": round(warm_s, 3),
        "budget_s": budget_s,
        "ok": (result.ok and result2.ok
               and drift_symbols == 0 and drift_baseline_entries == 0
               and never_abort_findings == 0
               and never_abort_baseline_entries == 0
               and cold_s < budget_s and warm_s < budget_s),
    }


def phase_wire_codec() -> dict:
    """ISSUE 12 satellite: the binary data plane's win as a tracked
    number, not a claim — JSON (the pre-v2 wire: per-tick dicts with
    base64 rows inside a JSON frame) vs the binary codec (columnar tick
    blocks: one contiguous (B, F) f32 array + dictionary-encoded
    session ids) on a fixed synthetic batch, encode+decode rows/s.
    Acceptance: >= 3x.  Pure CPU, no jax — runs identically anywhere,
    and it IS the serialize/parse pass every fleet tick pays."""
    import base64 as _b64
    import json as _json
    import time as _time

    import numpy as np

    from fmda_tpu.stream import codec

    B, F, POOL = 256, 108, 64
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((B, F)).astype(np.float32)
    msgs = [{"kind": "tick", "session": f"T{i % POOL}",
             "row": rows[i], "seq": i} for i in range(B)]

    def run_json():
        wire = [{
            "kind": "tick", "session": m["session"], "seq": m["seq"],
            "row": _b64.b64encode(
                np.ascontiguousarray(m["row"]).tobytes()).decode("ascii"),
        } for m in msgs]
        payload = _json.dumps(
            {"op": "publish_many", "topic": "t", "values": wire}).encode()
        out = _json.loads(payload)
        return [np.frombuffer(_b64.b64decode(m["row"]), np.float32)
                for m in out["values"]]

    def run_binary():
        values = codec.coalesce_ticks(msgs)
        payload = codec.encode(
            {"op": "publish_many", "topic": "t", "values": values})
        out = codec.decode(payload)
        return [np.asarray(b["rows"], np.float32) for b in out["values"]]

    # both paths must hand back the identical rows bit-exact before any
    # timing means anything
    got_j = np.stack(run_json())
    got_b = np.vstack(run_binary())
    assert np.array_equal(got_j, rows) and np.array_equal(got_b, rows)

    def rate(fn) -> float:
        iters = 8
        while True:  # calibrate to a ~0.2s window
            t0 = _time.perf_counter()
            for _ in range(iters):
                fn()
            dt = _time.perf_counter() - t0
            if dt > 0.2 or iters >= 4096:
                break
            iters *= 2
        best = dt / iters
        for _ in range(2):  # min-of-reps rides out scheduler noise
            t0 = _time.perf_counter()
            for _ in range(iters):
                fn()
            best = min(best, (_time.perf_counter() - t0) / iters)
        return B / best

    json_rps = rate(run_json)
    binary_rps = rate(run_binary)
    speedup = binary_rps / json_rps
    return {
        "batch_rows": B,
        "n_features": F,
        "session_pool": POOL,
        "json_rows_per_s": round(json_rps),
        "binary_rows_per_s": round(binary_rps),
        "speedup_x": round(speedup, 2),
        "acceptance_x": 3.0,
        "ok": bool(speedup >= 3.0),
    }


_PHASES = {
    "flagship_pallas": lambda: phase_flagship(use_pallas=True),
    "flagship_scan": lambda: phase_flagship(use_pallas=False),
    # bf16 compute / f32 params — the MXU's native dtype; reported as its
    # own phase (the headline stays the reference-matching f32 protocol)
    "flagship_bf16": lambda: phase_flagship(use_pallas=True, dtype="bfloat16"),
    "flagship_wide": phase_flagship_wide,
    "train_e2e": phase_train_e2e,
    "train_throughput": phase_train_throughput,
    "kernel_sweep": phase_kernel_sweep,
    "attn_sweep": phase_attn_sweep,
    "longctx": phase_longctx,
    "longctx_attn": phase_longctx_attn,
    "longctx_attn_bf16": lambda: phase_longctx_attn(dtype="bfloat16"),
    "multiticker": phase_multiticker,
    "serving": phase_serving,
    "torch": phase_torch,
    "tpu_export": phase_tpu_export,
    "replay": phase_replay,
    "replay_throughput": phase_replay_throughput,
    "longctx_sp": phase_longctx_sp,
    "runtime_fleet_smoke": phase_runtime_fleet,
    "predictor_fleet_smoke": phase_predictor_fleet,
    "runtime_multihost_smoke": phase_runtime_multihost,
    "control_capacity_model": phase_control_capacity_model,
    "runtime_chaos_soak": phase_runtime_chaos_soak,
    "pipeline_chaos_soak": phase_pipeline_chaos_soak,
    "obs_overhead": phase_obs_overhead,
    "obs_aggregate_overhead": phase_obs_aggregate_overhead,
    "trace_overhead": phase_trace_overhead,
    "quality_overhead": phase_quality_overhead,
    "device_obs_overhead": phase_device_obs_overhead,
    "analysis_lint": phase_analysis_lint,
    "wire_codec_bench": phase_wire_codec,
}


# ---------------------------------------------------------------------------
# Orchestration (parent process)
# ---------------------------------------------------------------------------


def _run_phase_subprocess(name: str, env: dict, timeout_s: float) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name]
    env = dict(env)
    env["PYTHONPATH"] = _REPO_DIR + os.pathsep + env.get("PYTHONPATH", "")
    try:
        proc = subprocess.run(
            cmd, env=env, cwd=_REPO_DIR, timeout=timeout_s,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout_s:.0f}s"}
    err_tail = proc.stderr.decode(errors="replace")[-800:]
    if proc.returncode != 0:
        return {"error": f"rc={proc.returncode}: {err_tail}"}
    try:
        line = proc.stdout.decode(errors="replace").strip().splitlines()[-1]
        return json.loads(line)
    except (IndexError, json.JSONDecodeError):
        return {"error": f"unparseable phase output; stderr: {err_tail}"}


def main() -> None:
    from fmda_tpu.utils.env import NO_TPU_MESSAGE

    # This parent stays off jax (a process that initialises a backend
    # holds the chip, and every phase child needs it), so it decides from
    # the environment alone: a platform pinned to anything but the TPU
    # ends the run here, before any phase.  Nothing pinned means "require
    # a TPU": the device children get JAX_PLATFORMS=tpu, under which jax
    # itself fails at start-up without one — see the check after the
    # first phase below.
    pinned = os.environ.get("JAX_PLATFORMS", "").lower()
    if pinned and "tpu" not in pinned.split(","):
        sys.exit(f"bench.py measures the TPU and JAX_PLATFORMS={pinned!r} "
                 "pins this run elsewhere; it never falls back to the CPU. "
                 "A single phase runs wherever jax lands: "
                 "bench.py --phase NAME.")
    deadline = time.monotonic() + GLOBAL_BUDGET_S
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "tpu"

    # priority order under GLOBAL_BUDGET_S: the headline + baseline first,
    # then the cheap evidence phases (compile-readiness proof, replay
    # throughput), then the north-star configs; later phases are the ones
    # a slow run budget-skips
    plan = [
        ("flagship_pallas", 420.0),
        ("flagship_scan", 420.0),
        ("torch", 300.0),
        ("tpu_export", 180.0),
        ("replay", 300.0),
        ("longctx", 600.0),
        ("longctx_attn", 600.0),
        ("longctx_sp", 600.0),
        ("multiticker", 420.0),
        ("serving", 300.0),
        ("runtime_fleet_smoke", 240.0),
        ("replay_throughput", 300.0),
        ("train_throughput", 420.0),
        ("predictor_fleet_smoke", 300.0),
        ("runtime_multihost_smoke", 420.0),
        ("runtime_chaos_soak", 600.0),
        ("pipeline_chaos_soak", 420.0),
        ("obs_overhead", 300.0),
        ("trace_overhead", 300.0),
        ("quality_overhead", 300.0),
        ("flagship_bf16", 300.0),
        ("flagship_wide", 300.0),
        ("train_e2e", 600.0),
        ("kernel_sweep", 600.0),
        ("attn_sweep", 600.0),
    ]
    # phases that never run on the chip: torch is the CPU baseline by
    # definition; longctx_sp runs on the 8-device virtual CPU mesh
    special_envs = {
        "torch": lambda: cpu_forced_env(repo_dir=_REPO_DIR),
        "longctx_sp": lambda: cpu_forced_env(n_devices=8, repo_dir=_REPO_DIR),
    }
    phases: dict = {}
    for name, budget in plan:
        remaining = deadline - time.monotonic()
        if remaining < 60.0:
            phases[name] = {"skipped": "global budget exhausted"}
            continue
        phase_env = special_envs[name]() if name in special_envs else env
        t0 = time.monotonic()
        result = _run_phase_subprocess(name, phase_env, min(budget, remaining))
        if not phases and "Unable to initialize backend 'tpu'" in result.get(
                "error", ""):
            # the first device child could not take a TPU: there is none
            # (or another process holds it) — stop before any phase ran
            sys.exit(f"{NO_TPU_MESSAGE}\n{result['error']}")
        phases[name] = result
        phases[name]["wall_s"] = round(time.monotonic() - t0, 1)
        print(f"phase {name}: {phases[name]}", file=sys.stderr)

    pallas_res = phases.get("flagship_pallas", {})
    scan_res = phases.get("flagship_scan", {})
    if "seq_s" in pallas_res and "seq_s" in scan_res:
        headline = max((pallas_res, scan_res), key=lambda r: r["seq_s"])
    elif "seq_s" in pallas_res:
        headline = pallas_res
    elif "seq_s" in scan_res:
        headline = scan_res
    else:
        headline = {}
    value = headline.get("seq_s", 0.0)
    torch_seq_s = phases.get("torch", {}).get("seq_s")
    vs_baseline = (
        round(value / torch_seq_s, 2) if torch_seq_s and value else None
    )

    try:
        loadavg = [round(v, 2) for v in os.getloadavg()]
    except OSError:
        loadavg = None

    record = {
        "metric": (
            "seq/sec/chip (biGRU train step, "
            f"B={BATCH} T={WINDOW} F={FEATURES} H={HIDDEN})"
        ),
        "value": value,
        "unit": "seq/s",
        "vs_baseline": vs_baseline,
        "backend": headline.get("backend"),
        "device_kind": headline.get("device_kind"),
        "n_devices": headline.get("n_devices"),
        "loadavg": loadavg,
        "phases": phases,
    }
    record["utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    # full record -> BENCH_DETAIL.json (git-ignored run output); stdout
    # gets a COMPACT line a bounded-tail reader can always parse
    detail_path = os.path.join(_REPO_DIR, "BENCH_DETAIL.json")
    try:
        with open(detail_path, "w") as f:
            json.dump(record, f, indent=1)
    except OSError:
        detail_path = None
    compact = {k: record[k] for k in (
        "metric", "value", "unit", "vs_baseline", "backend", "device_kind",
        "n_devices", "loadavg", "utc")}
    compact["detail"] = "BENCH_DETAIL.json" if detail_path else "(unwritable)"
    compact["phases_ok"] = sorted(
        n for n, p in phases.items()
        if isinstance(p, dict) and "error" not in p and "skipped" not in p)
    compact["phases_skipped"] = sorted(
        n for n, p in phases.items()
        if isinstance(p, dict) and "skipped" in p and "error" not in p)
    compact["phases_error"] = sorted(
        n for n, p in phases.items()
        if not isinstance(p, dict) or "error" in p)
    print(json.dumps(compact))


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--phase", choices=sorted(_PHASES))
    parser.add_argument("--slo-soft", action="store_true",
                        help="report the runtime_fleet_smoke and "
                             "predictor_fleet_smoke SLO/speedup "
                             "verdicts without failing the phases "
                             "(loaded-host escape hatch; also "
                             "FMDA_FLEET_SLO_SOFT=1)")
    args = parser.parse_args()
    if args.slo_soft:
        # phases run in subprocesses that inherit our env
        os.environ["FMDA_FLEET_SLO_SOFT"] = "1"
    if args.phase:
        from fmda_tpu.utils.env import enable_compile_cache

        enable_compile_cache()
        print(json.dumps(_PHASES[args.phase]()))
    else:
        main()
