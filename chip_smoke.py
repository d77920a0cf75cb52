#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
types — ``fmda_tpu.cli.main([...])`` and ``Application`` — at the published
width (108 features, hidden 32, window 30, float32) with seeded random
weights: the serving pool answers a fleet, the trainer takes ten steps and
resumes from its checkpoint, the continuous-train loop hot-swaps the live
pool, and the five Pallas kernels (gru, lstm, ssm, flash attention, attention
over picked keys) run compiled against their ``jnp``
references.  Every check is the repo's own: counts that must balance,
finite probabilities, agreement with a float32 reference.

Contract:

- no flags, no sizes, no mode in which a CPU run passes.  Under
  ``JAX_PLATFORMS=cpu`` every section still runs (the dry run before chip
  time is spent) and the script then exits non-zero;
- any section that raises makes the exit code non-zero, and the result
  line is not printed;
- nothing is written into the checkout except the compile cache
  (``fmda_tpu.utils.env.enable_compile_cache``) and ``native/build/``;
- the last line of stdout, on success only, is
  ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

It prints no utilization and claims no speed: times shown are set-up facts
(compile seconds, a barrier check), not a benchmark.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib.metadata
import io
import json
import logging
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))
if _REPO_DIR not in sys.path:
    sys.path.insert(0, _REPO_DIR)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from fmda_tpu import cli  # noqa: E402
from fmda_tpu.config import (  # noqa: E402
    TOPIC_FLEET_PREDICTION,
    FrameworkConfig,
    MeshConfig,
)
from fmda_tpu.utils.env import select_backend  # noqa: E402

# The published width (source paper's biGRU) and the fleet the issue names.
HIDDEN = 32
WINDOW = 30
SESSIONS = 64
TICKS = 30
FLEET_ARGS = ["--sessions", str(SESSIONS), "--ticks", str(TICKS),
              "--hidden", str(HIDDEN)]
DEMO_ARGS = ["--days", "8", "--epochs", "2", "--batch-size", "256"]

# Tolerances, each with its reason.  "highest" = the plain float32
# reference under jax.default_matmul_precision("highest").  A float32
# matmul on the TPU runs, by default, as one bf16 pass with float32
# accumulation (2^-9 relative per operand) — in XLA's programs and in the
# Mosaic kernels alike — so "float32" agrees with `highest` to about three
# digits, and computing in bfloat16 throughout is an order worse.
#: pool vs the float32 reference over 30 carried ticks.  Measured on the
#: v5e: gru 3.7e-4, ssm 7.7e-5.
TOL_POOL_VS_HIGHEST = 2e-3
#: pool vs a solo carrier whose program multiplies the same way (batch 8,
#: default precision): the same math in two compiled programs.
TOL_POOL_VS_SOLO = 1e-5
#: float32 kernels as they are served (Mosaic's default dot precision, the
#: same bf16 pass) vs `highest`, normalised by the reference's largest
#: magnitude: 2^-9 per operand through 30 recurrent steps, or through the
#: flash backward's four chained matmuls.  Measured on the v5e: gru/lstm
#: <= 4.0e-3, flash forward 4.9e-3, flash gradients 1.0e-2.
TOL_KERNEL_F32 = 2e-2
#: the same float32 kernels traced under `highest` (Mosaic then contracts
#: in full float32) vs `highest`: what is left is accumulation order and
#: the transcendental approximations.  Measured on the v5e: gru 3.4e-6,
#: lstm 3.8e-6, ssm 0, flash forward 1.2e-7, flash gradients 6.6e-5 — an
#: order below anything a bf16 pass produces, so a kernel computing in
#: lower precision than it is asked to, or wrongly, fails here.
TOL_KERNEL_F32_FULL = 5e-4
#: bfloat16 kernels vs `highest`: state and outputs are rounded to 8
#: mantissa bits at every one of 30 steps.  Measured: <= 7.4e-3.
TOL_KERNEL_BF16 = 5e-2
#: demo loss with the GRU kernel vs the lax.scan demo: same data, seed and
#: matmul precision; ten optimizer steps amplify rounding differences.
#: Measured: 3.1e-7.
TOL_PALLAS_LOSS = 1e-3
#: dp=4 vs dp=1 loss after ten steps: the gradient all-reduce
#: re-associates a float32 sum.
TOL_DP_LOSS = 1e-3

_failed: list = []
_facts: dict = {}


def say(msg: str) -> None:
    print(msg, flush=True)


def section(name: str, fn) -> None:
    """Run one section; a raise fails the run (and the remaining sections
    still run, so one chip call shows every failure)."""
    say(f"== {name}")
    t0 = time.perf_counter()
    try:
        fn()
    except Exception:  # noqa: BLE001 — reported, counted, exit code != 0
        traceback.print_exc(file=sys.stdout)
        _failed.append(name)
        say(f"!! {name} FAILED after {time.perf_counter() - t0:.1f}s")
    else:
        say(f"ok {name} ({time.perf_counter() - t0:.1f}s)")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def run_cli(argv: list) -> str:
    """``python -m fmda_tpu <argv>`` in this process; returns its stdout."""
    gc.collect()  # drop the previous run's pools from the weak ledger
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(argv)
    check(rc == 0, f"fmda_tpu {' '.join(argv)} exited {rc}")
    return buf.getvalue()


# ---------------------------------------------------------------------------
# device + host path
# ---------------------------------------------------------------------------


def device_line() -> None:
    dev = jax.devices()[0]
    _facts["device"] = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }

    def version(dist):
        try:
            return importlib.metadata.version(dist)
        except importlib.metadata.PackageNotFoundError:
            return None

    say(json.dumps({
        **_facts["device"],
        "jax": jax.__version__,
        "jaxlib": version("jaxlib"),
        "libtpu": version("libtpu"),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }))


class _CacheCounter:
    """Counts jax's own persistent-cache events for the whole run."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        self.backend_compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += duration

    def close(self) -> None:
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)


def host_path() -> None:
    from fmda_tpu.app import Application
    from fmda_tpu.stream import _native
    from fmda_tpu.stream.native_join import native_join_available

    app = Application(FrameworkConfig())
    native_join = native_join_available()
    say(json.dumps({
        "bus": type(app.bus).__name__,
        "join_scheduler": app.engine.join_scheduler,
        "native_join_available": native_join,
        "so_built_this_run": dict(_native.built_this_run),
    }))
    app.close()


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def _serve_report(argv: list) -> dict:
    out = json.loads(run_cli(["serve-fleet", *argv]))
    buckets = sorted(
        int(k.rsplit("_", 1)[1]) for k in out["counters"]
        if k.startswith("flushes_bucket_"))
    led = out["compile_ledger"]
    steps = [p for p in led["programs"]
             if p["program"].startswith("session_pool_step")]
    say(f"   serve-fleet {' '.join(argv)}: served "
        f"{out['ticks_served']}/{out['ticks_submitted']} buckets={buckets} "
        f"compile_count={out['compile_count']} "
        f"compile_s={led['compile_seconds_total']:.2f} "
        f"backend={out['backend']}/{out['device_kind']}/{out['n_devices']}")
    check(out["ticks_submitted"] > 0, "no ticks submitted")
    check(out["ticks_served"] == out["ticks_submitted"],
          f"served {out['ticks_served']} != submitted "
          f"{out['ticks_submitted']}")
    check(out["compile_count"] == len(buckets),
          f"compile_count {out['compile_count']} != buckets dispatched "
          f"{buckets}")
    check(led["unexpected_recompiles_total"] == 0
          and all(p["compiles"] == 1 for p in steps),
          f"pool step recompiled: {steps}")
    check(out["backend"] == _facts["device"]["platform"],
          f"report says backend {out['backend']}")
    _facts["ledger_compile_s"] = (
        _facts.get("ledger_compile_s", 0.0) + led["compile_seconds_total"])
    out["buckets"] = buckets
    return out


def _fleet_model(cfg: FrameworkConfig, cell: str, seed: int = 0):
    """The unidirectional carrier ``serve-fleet`` builds, from a seed."""
    from fmda_tpu.models import build_model

    model_cfg = dataclasses.replace(
        cfg.model, bidirectional=False, dropout=0.0, hidden_size=HIDDEN,
        n_features=cfg.features.n_features, cell=cell)
    params = build_model(model_cfg).init(
        {"params": jax.random.PRNGKey(seed)},
        jnp.zeros((1, cfg.runtime.window, model_cfg.n_features)))["params"]
    return model_cfg, params


def _seeded_load(n_features: int):
    """Rows, per-round tick masks and per-session norms, from one seed.
    Even rounds are lockstep (bucket 64), odd rounds ragged (smaller
    buckets), so one load dispatches several compiled programs."""
    rng = np.random.default_rng(7)
    mins = rng.normal(size=(SESSIONS, n_features)).astype(np.float32)
    maxs = mins + rng.uniform(
        1.0, 5.0, size=(SESSIONS, n_features)).astype(np.float32)
    walk = np.cumsum(
        rng.normal(scale=0.1, size=(TICKS, SESSIONS, n_features)),
        axis=0).astype(np.float32)
    mask = rng.random((TICKS, SESSIONS)) < 0.4
    mask[::2] = True
    mask[:, 0] = True  # session 0 ticks every round: the compared stream
    return walk, mask, mins, maxs


def _drive_fleet(cell: str, *, serial: bool):
    """One seeded load through Application.attach_fleet; returns every
    published probability keyed (session, seq), the rows session 0 sent,
    and the live objects for follow-up checks."""
    from fmda_tpu.app import Application
    from fmda_tpu.data.normalize import NormParams

    cfg = FrameworkConfig()
    cfg = dataclasses.replace(cfg, runtime=dataclasses.replace(
        cfg.runtime, pipeline_depth=0 if serial else 1))
    app = Application(cfg)
    model_cfg, params = _fleet_model(cfg, cell)
    gateway = app.attach_fleet(model_cfg, params)
    consumer = app.bus.consumer(TOPIC_FLEET_PREDICTION)
    walk, mask, mins, maxs = _seeded_load(model_cfg.n_features)
    ids = [f"S{i:03d}" for i in range(SESSIONS)]
    for i, sid in enumerate(ids):
        gateway.open_session(sid, NormParams(mins[i], maxs[i]))
    for r in range(TICKS):
        for i in np.flatnonzero(mask[r]):
            gateway.submit(ids[i], walk[r, i])
        gateway.pump()
    gateway.drain()
    probs = {}
    for rec in consumer.poll():
        v = rec.value
        probs[(v["session"], v["seq"])] = np.asarray(
            v["probabilities"], np.float32)
    check(len(probs) == int(mask.sum()),
          f"{len(probs)} results published for {int(mask.sum())} ticks")
    return probs, (app, gateway, model_cfg, params,
                   NormParams(mins[0], maxs[0]), walk[:, 0])


def _solo_stream(model_cfg, params, norm, rows, precision, batch=1):
    """Session 0's stream through a solo StreamingBiGRU — the plain
    float32 reference when ``precision`` is "highest".  ``batch`` > 1
    feeds the same row in every lane: lane 0 is still session 0, through
    a program that multiplies matrices the way the pool's buckets do."""
    from fmda_tpu.serve.streaming import StreamingBiGRU

    with jax.default_matmul_precision(precision):
        core = StreamingBiGRU(
            model_cfg, params, norm, window=WINDOW, batch=batch)
        return np.stack(
            [core.step(np.tile(row, (batch, 1)))[0] for row in rows])


def serving(cell: str) -> None:
    lock = _serve_report(["--cell", cell, *FLEET_ARGS])
    check(lock["buckets"] == [SESSIONS], f"lockstep fleet: {lock['buckets']}")
    ragged = _serve_report(["--cell", cell, *FLEET_ARGS, "--duty", "0.5"])
    check(len(ragged["buckets"]) > 1, f"one bucket only: {ragged['buckets']}")
    serial = _serve_report(
        ["--cell", cell, *FLEET_ARGS, "--duty", "0.5", "--serial"])
    check(serial["counters"].get("overlapped_flushes", 0) == 0,
          "--serial overlapped a flush")
    check(serial["ticks_served"] == ragged["ticks_served"],
          "--serial served a different count from the same seeded load")

    overlapped, live = _drive_fleet(cell, serial=False)
    in_order, _ = _drive_fleet(cell, serial=True)
    check(overlapped.keys() == in_order.keys(), "result keys differ")
    stacked = np.stack(list(overlapped.values()))
    check(bool(np.all(np.isfinite(stacked))), "non-finite probability")
    check(bool(np.all((stacked > 0) & (stacked < 1))),
          "probability outside (0, 1)")
    # the gateway reuses two host staging buffers per bucket on the
    # argument that an earlier dispatch has finished reading them — which
    # is only known to hold where host-to-device is a memcpy
    n_diff = sum(
        not np.array_equal(overlapped[k], in_order[k]) for k in overlapped)
    check(n_diff == 0, f"{n_diff} results differ overlapped vs --serial")

    app, gateway, model_cfg, params, norm, rows = live
    if cell == "gru":
        _barrier_fact(gateway.pool)
    app.close()
    stream = np.stack([overlapped[("S000", t)] for t in range(TICKS)])

    def diff(precision, batch):
        return float(np.max(np.abs(stream - _solo_stream(
            model_cfg, params, norm, rows, precision, batch))))

    d_high = diff("highest", 1)
    d_solo1 = diff("default", 1)
    d_solo8 = diff("default", 8)
    say(f"   {cell}: {len(overlapped)} results, overlapped == serial bit "
        f"for bit; session 0 over {TICKS} ticks, max|diff| of the pool vs "
        f"the float32 reference (solo, highest) {d_high:.3e} (tol "
        f"{TOL_POOL_VS_HIGHEST:.0e}); vs solo batch 8 at default precision "
        f"{d_solo8:.3e} (tol {TOL_POOL_VS_SOLO:.0e}); vs solo batch 1 at "
        f"default precision {d_solo1:.3e} (reported)")
    _facts[f"pool_{cell}"] = {
        "vs_reference_highest": d_high, "vs_solo_b8_default": d_solo8,
        "vs_solo_b1_default": d_solo1}
    check(d_high <= TOL_POOL_VS_HIGHEST, "pool disagrees with the reference")
    check(d_solo8 <= TOL_POOL_VS_SOLO, "pool disagrees with the solo carrier")


def _barrier_fact(pool) -> None:
    """Fact (a) for the benchmark of record: does ``block_until_ready``
    wait for the device?  N chained pool steps ended by it, against the
    same N ended by a host fetch.  If they agree it is a barrier."""
    slots = np.arange(SESSIONS, dtype=np.int32)
    rows = np.zeros((SESSIONS, pool.cfg.n_features), np.float32)
    np.asarray(pool.step_device(slots, rows))  # bucket 64 is compiled
    out = {}
    for n in (50, 500):
        for name, finish in (("block_until_ready", jax.block_until_ready),
                             ("host_fetch", np.asarray)):
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(n):
                    probs = pool.step_device(slots, rows)
                finish(probs)
                best = min(best, time.perf_counter() - t0)
            out[f"{name}_{n}_steps_ms"] = round(best * 1e3, 3)
    _facts["barrier"] = out
    say(f"   chained pool steps, best of 3: {json.dumps(out)}")


def profile_fact(tmp: str) -> None:
    """Fact (b): a ``--jax-profile`` capture of a few flushes opens with
    jax.profiler.ProfileData.  Fatal if there is no capture to open; what
    the planes are called is reported."""
    from jax.profiler import ProfileData

    prof = os.path.join(tmp, "profile")
    _serve_report(["--cell", "gru", "--sessions", str(SESSIONS), "--ticks",
                   "5", "--hidden", str(HIDDEN), "--jax-profile", prof])
    files = glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                      recursive=True)
    check(len(files) == 1, f"expected one .xplane.pb, found {files}")
    data = ProfileData.from_file(files[0])
    planes = {}
    flushes = 0
    for plane in data.planes:
        n = 0
        for line in plane.lines:
            for ev in line.events:
                n += 1
                flushes += "pool_flush" in ev.name
        planes[plane.name] = n
    _facts["profile"] = {
        "xplane_bytes": os.path.getsize(files[0]),
        "planes": planes,
        "tpu_device_plane": any("TPU" in name for name in planes),
        "pool_flush_events": flushes,
    }
    say(f"   {json.dumps(_facts['profile'])}")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


class _EpochLosses(logging.Handler):
    """Collects the trainer's per-epoch log line (``epoch N: train
    loss=...``) — the CLI prints only the last epoch."""

    def __init__(self) -> None:
        super().__init__(level=logging.INFO)
        self.losses: list = []

    def emit(self, record: logging.LogRecord) -> None:
        if str(record.msg).startswith("epoch %d: train loss"):
            self.losses.append(float(record.args[1]))


def _demo(tmp: str, name: str, extra: list) -> tuple:
    """``python -m fmda_tpu demo`` → (per-epoch losses, checkpoint path)."""
    log = logging.getLogger("fmda_tpu.train")
    handler = _EpochLosses()
    level = log.level
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    try:
        text = run_cli(["demo", *DEMO_ARGS, "--checkpoint-dir",
                        os.path.join(tmp, name), *extra])
    finally:
        log.removeHandler(handler)
        log.setLevel(level)
    ckpt = [ln.split(": ", 1)[1] for ln in text.splitlines()
            if ln.startswith("checkpoint: ")][0]
    check("backtest over" in text, "demo printed no backtest")
    losses = handler.losses
    check(len(losses) == 2 and all(np.isfinite(losses)),
          f"per-epoch losses {losses}")
    say(f"   demo {name}: epoch losses {losses}, {os.path.basename(ckpt)}")
    return losses, ckpt


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _demo_trainer(cfg: FrameworkConfig, mesh=None):
    """The corpus and Trainer ``demo`` builds (cli._train), in process."""
    from fmda_tpu.data.synthetic import SyntheticMarketConfig, build_corpus
    from fmda_tpu.train import Trainer
    from fmda_tpu.train.trainer import imbalance_weights_from_source

    wh, _ = build_corpus(
        cfg.features, SyntheticMarketConfig(seed=cfg.train.seed, n_days=8))
    model_cfg = dataclasses.replace(
        cfg.model, n_features=len(wh.x_fields))
    train_cfg = dataclasses.replace(cfg.train, batch_size=256, epochs=2)
    weight, pos_weight = imbalance_weights_from_source(wh)
    trainer = Trainer(model_cfg, train_cfg, weight=weight,
                      pos_weight=pos_weight, mesh=mesh)
    return wh, trainer


def training(tmp: str) -> None:
    cfg = FrameworkConfig()
    losses, ckpt = _demo(tmp, "scan", [])
    check(os.path.basename(ckpt) == "step_00000010",
          f"expected ten steps, got {ckpt}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    _facts["demo_losses"] = losses

    # the same ten steps through the library, state kept in memory: the
    # run the checkpoint must be able to stand in for
    wh, trainer = _demo_trainer(cfg)
    fit_kw = dict(bid_levels=cfg.features.bid_levels,
                  ask_levels=cfg.features.ask_levels)
    state, history, dataset = trainer.fit(wh, **fit_kw)
    lib_losses = [m.loss for m in history["train"]]
    check(lib_losses == losses,
          f"library run {lib_losses} != CLI run {losses}")
    _facts["dp1_losses"] = lib_losses
    restored = trainer.restore_state(ckpt)
    check(int(restored.step) == 10 and int(state.step) == 10, "step != 10")
    check(_tree_equal(state.params, restored.params),
          "checkpoint params differ from the in-memory run")
    # one more epoch from each: the restored steps must equal the
    # continued steps (the train step donates its state — continue first)
    cont, h_cont, _ = trainer.fit(
        wh, epochs=1, initial_state=state, dataset=dataset, **fit_kw)
    rest, h_rest, _ = trainer.fit(
        wh, epochs=1, initial_state=restored, dataset=dataset, **fit_kw)
    l_cont, l_rest = h_cont["train"][0].loss, h_rest["train"][0].loss
    say(f"   resume: continued loss {l_cont:.6f}, restored loss "
        f"{l_rest:.6f}, step {int(rest.step)}")
    check(np.isfinite(l_cont) and l_cont == l_rest,
          "restored step != continued step (loss)")
    check(_tree_equal(cont.params, rest.params),
          "restored step != continued step (params)")
    check(trainer.compile_counts == {"train_step": 1, "eval_step": 1},
          f"trainer recompiled: {trainer.compile_counts}")


def the_loop(tmp: str) -> None:
    out = json.loads(run_cli([
        "serve-fleet", "--continuous-train", "--swap-guard",
        "--continuous-days", "8", *FLEET_ARGS,
        "--train-checkpoint-dir", os.path.join(tmp, "continuous")]))
    ct = out["continuous_train"]
    say(f"   {json.dumps({k: ct[k] for k in ct if k != 'checkpoints'})}")
    check(out["ticks_served"] == out["ticks_submitted"], "ticks lost")
    check(ct["rounds"] >= 1, "no fine-tune round ran")
    check(ct["swaps_accepted"] >= 1 and ct["weights_version"] >= 1,
          "no swap accepted")
    check(ct["pool_compile_count"] == out["compile_count"],
          f"swap changed the pool's compile count: {out['compile_count']} "
          f"-> {ct['pool_compile_count']}")
    check(ct["trainer_unexpected_recompiles"] == 0, "trainer recompiled")
    check(np.isfinite(ct["last_metrics"]["loss"]), "non-finite loss")


# ---------------------------------------------------------------------------
# kernels — compiled, never interpret=True
# ---------------------------------------------------------------------------


def _err(got, want) -> float:
    """Max abs difference over every leaf, normalised per leaf by the
    reference's largest magnitude (floored at 1)."""
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        check(bool(np.all(np.isfinite(g))), "non-finite kernel output")
        worst = max(worst, float(
            np.max(np.abs(g - w)) / max(1.0, float(np.max(np.abs(w))))))
    return worst


def _value_and_grads(fn, n_args, precision="default", float32=False):
    """jit of (outputs, d sum(outputs**2) / d args): forward and backward
    in one compiled call, traced under ``precision`` — the whole of it,
    because a custom-vjp kernel's backward is traced after the forward
    has returned.  ``float32`` computes on float32 copies of the
    arguments (the reference for the bfloat16 runs)."""
    def loss(*args):
        if float32:
            args = [a.astype(jnp.float32) for a in args]
        out = fn(*args)
        return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                   for x in jax.tree.leaves(out)), out

    def both(*args):
        with jax.default_matmul_precision(precision):
            (_, out), grads = jax.value_and_grad(
                loss, argnums=tuple(range(n_args)), has_aux=True)(*args)
        return out, grads

    return jax.jit(both)


def _kernel_errs(got, want, tol, suffix="") -> dict:
    return {f"fwd{suffix}": (_err(got[0], want[0]), tol),
            f"grad{suffix}": (_err(got[1], want[1]), tol)}


def _f32_kernel_errs(run, want, *args) -> dict:
    """A float32 kernel as served and traced under `highest`."""
    n = len(args)
    errs = _kernel_errs(_value_and_grads(run, n)(*args), want, TOL_KERNEL_F32)
    errs.update(_kernel_errs(_value_and_grads(run, n, "highest")(*args),
                             want, TOL_KERNEL_F32_FULL, "_full_f32"))
    return errs


class _KernelTable:
    """Prints every comparison first and fails afterwards, so one chip
    run shows the whole table."""

    def __init__(self) -> None:
        self.over: list = []
        _facts.setdefault("kernel_err", {})

    def row(self, key: str, what: str, errs: dict) -> None:
        """``errs``: label -> (measured, tolerance or None = reported)."""
        parts = []
        for label, (err, tol) in errs.items():
            worst = _facts["kernel_err"].setdefault(f"{key}_{label}", 0.0)
            _facts["kernel_err"][f"{key}_{label}"] = max(worst, err)
            parts.append(f"{label} {err:.2e}" + (
                f" (tol {tol:.0e})" if tol is not None else " (reported)"))
            if tol is not None and not err <= tol:
                self.over.append(f"{what}: {label} {err:.2e} > {tol:.0e}")
        say(f"   {what}: " + ", ".join(parts))

    def close(self) -> None:
        check(not self.over, "kernel disagrees with its reference: "
              + "; ".join(self.over))


def _scan_kernels(table: _KernelTable) -> None:
    from fmda_tpu.ops.gru import gru_scan
    from fmda_tpu.ops.lstm import lstm_scan
    from fmda_tpu.ops.pallas_gru import gru_scan_pallas
    from fmda_tpu.ops.pallas_lstm import lstm_scan_pallas

    for name, gates, kernel, ref in (
            ("gru", 3, gru_scan_pallas, gru_scan),
            ("lstm", 4, lstm_scan_pallas, lstm_scan)):
        for batch in (256, 32):
            for dtype in (jnp.float32, jnp.bfloat16):
                for reverse in (False, True):
                    r = np.random.default_rng(batch + gates)
                    scale = 1.0 / np.sqrt(HIDDEN)

                    def arr(*shape, s=1.0):
                        return jnp.asarray(
                            r.normal(scale=s, size=shape), dtype)

                    args = [arr(batch, WINDOW, gates * HIDDEN),
                            arr(batch, HIDDEN, s=0.5)]
                    if name == "lstm":
                        args.append(arr(batch, HIDDEN, s=0.5))
                    args += [arr(gates * HIDDEN, HIDDEN, s=scale),
                             arr(gates * HIDDEN, s=scale)]
                    n = len(args)

                    def run(*a):
                        return kernel(*a, reverse=reverse)

                    want = _value_and_grads(
                        lambda *a: ref(*a, reverse=reverse), n,
                        "highest", float32=True)(*args)
                    f32 = dtype == jnp.float32
                    errs = _kernel_errs(
                        _value_and_grads(run, n)(*args), want,
                        TOL_KERNEL_F32 if f32 else TOL_KERNEL_BF16)
                    if f32:
                        errs.update(_kernel_errs(
                            _value_and_grads(run, n, "highest")(*args),
                            want, TOL_KERNEL_F32_FULL, "_full_f32"))
                    table.row(
                        f"{name}_{jnp.dtype(dtype).name}",
                        f"{name} B={batch} T={WINDOW} H={HIDDEN} "
                        f"{jnp.dtype(dtype).name} reverse={reverse}", errs)


def _ssm_kernel(table: _KernelTable) -> None:
    from fmda_tpu.ops.pallas_ssm import ssm_cell_step_pallas
    from fmda_tpu.ops.ssm import SSMWeights, ssm_cell_step

    for batch in (8, 32, 128):
        r = np.random.default_rng(batch)

        def arr(*shape):
            return jnp.asarray(r.normal(size=shape), jnp.float32)

        xp = arr(batch, 3 * HIDDEN)
        carry = tuple(arr(batch, HIDDEN) for _ in range(3))
        w = SSMWeights(None, None, arr(HIDDEN), arr(HIDDEN), arr(HIDDEN),
                       arr(HIDDEN))
        got = jax.jit(ssm_cell_step_pallas)(xp, carry, w)
        # the step has no matmul: `highest` and default are one reference
        want = jax.jit(ssm_cell_step)(xp, carry, w)
        table.row("ssm_float32", f"ssm step B={batch} H={HIDDEN} float32",
                  {"fwd": (_err(got, want), TOL_KERNEL_F32_FULL)})


def _flash_kernel(table: _KernelTable) -> None:
    from fmda_tpu.ops import attention as A
    from fmda_tpu.ops.pallas_attention import flash_attention, flash_supported

    b, n, t, d = 2, 4, 128, HIDDEN // 4  # the attn family's heads at H=32
    check(flash_supported(t, t, d), "T=128 is off the flash gate")
    r = np.random.default_rng(0)
    q, k, v = (jnp.asarray(r.normal(size=(b, n, t, d)), jnp.float32)
               for _ in range(3))
    for causal in (False, True):
        def run(*a):
            return flash_attention(*a, causal=causal)

        # mha without use_flash is the jnp online-softmax reference
        want = _value_and_grads(
            lambda *a: A.mha(*a, causal=causal), 3, "highest")(q, k, v)
        table.row(
            "flash_float32",
            f"flash B={b} N={n} T={t} D={d} float32 causal={causal}",
            _f32_kernel_errs(run, want, q, k, v))


def _sparse_kernel(table: _KernelTable) -> None:
    """Attention over picked keys, forward and gradient, with the block
    pairs that follow from T (the forward's key block is the wider)."""
    from fmda_tpu.ops import pallas_sparse_attention as kernels
    from fmda_tpu.ops import sparse_attention as sa

    b, n, g, t, d, topk = 1, 8, 2, 4096, 128, 512
    check(kernels.sparse_supported(t, n // g, d),
          "T=4096 is off the learned-sparse gate")
    check(kernels.fwd_blocks_for(t)[1] > kernels.blocks_for(t)[1],
          "the forward's key block is no wider than the backward's")
    r = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(r.normal(size=shape), jnp.float32)

    q, k, v = arr(b, n, t, d), arr(b, g, t, d), arr(b, g, t, d)
    picked, kept = sa.select_keys(arr(b, 4, t, 32), arr(b, t, 32),
                                  arr(b, t, 4), topk, use_kernels=True)
    check(int(kept.sum()) == int(np.minimum(np.arange(t) + 1, topk).sum()),
          "the selection kernels kept another count than min(t + 1, topk)")

    def run(*a):
        return kernels.sparse_attention(*a, picked)

    # sparse_mha without use_kernels is the jnp path under the same mask
    want = _value_and_grads(
        lambda *a: sa.sparse_mha(*a, picked), 3, "highest")(q, k, v)
    table.row("sparse_float32",
              f"sparse B={b} N={n}/{g} T={t} D={d} top-{topk} float32",
              _f32_kernel_errs(run, want, q, k, v))


def kernels(tmp: str) -> None:
    from fmda_tpu.ops import gru, pallas_gru, pallas_ssm, ssm
    from fmda_tpu.ops.dispatch import kernel_fallbacks, reset_kernel_fallbacks

    table = _KernelTable()
    _scan_kernels(table)
    _ssm_kernel(table)
    _flash_kernel(table)
    _sparse_kernel(table)

    # the run that faulted in the one old capture: the float32 B=256
    # train step with the GRU kernel — ten steps, through `demo`
    reset_kernel_fallbacks()
    # there is no flag for the kernels: a partial --config carries it
    pallas_cfg = os.path.join(tmp, "pallas.json")
    with open(pallas_cfg, "w") as fh:
        json.dump({"model": {"use_pallas": True}}, fh)
    check(gru.select_scan_fn(True, shape=(256, WINDOW, HIDDEN), itemsize=4)
          is pallas_gru.gru_scan_pallas,
          "select_scan_fn refuses the kernel at B=256 T=30 H=32 float32")
    losses, ckpt = _demo(tmp, "pallas", ["--config", pallas_cfg])
    check(os.path.basename(ckpt) == "step_00000010", f"not ten steps: {ckpt}")
    scan = _facts.get("demo_losses")
    if scan is not None:
        rel = abs(losses[-1] - scan[-1]) / abs(scan[-1])
        say(f"   kernel demo loss {losses[-1]} vs lax.scan demo "
            f"{scan[-1]}: rel {rel:.2e} (tol {TOL_PALLAS_LOSS:.0e})")
        check(rel <= TOL_PALLAS_LOSS, "kernel demo loss is off the scan's")

    check(ssm.select_ssm_step_fn(True, shape=(SESSIONS, HIDDEN), itemsize=4)
          is pallas_ssm.ssm_cell_step_pallas,
          "select_ssm_step_fn refuses the kernel at B=64 H=32 float32")
    _serve_report(["--cell", "ssm", "--config", pallas_cfg, *FLEET_ARGS,
                   "--duty", "0.5"])
    fallbacks = kernel_fallbacks()
    say(f"   kernel_fallbacks after the use_pallas runs: {fallbacks}")
    # every use_pallas=True request a selector turns down is counted, so
    # an empty map says the kernel is what ran in both programs
    check(not fallbacks, f"a selector fell back: {fallbacks}")
    table.close()


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chips() -> None:
    from fmda_tpu.app import Application
    from fmda_tpu.parallel.mesh import build_mesh

    n = len(jax.devices())
    if n != 4:
        say(f"   not run: {n} device{'s' if n != 1 else ''}")
        return
    devices = set(jax.devices())
    cfg = FrameworkConfig()
    wh, trainer = _demo_trainer(cfg, mesh=build_mesh(MeshConfig(dp=4)))
    state, history, _ = trainer.fit(
        wh, bid_levels=cfg.features.bid_levels,
        ask_levels=cfg.features.ask_levels)
    check(int(state.step) == 10, f"step {int(state.step)}")
    # the cache holds what one call takes: a group of stacked batches,
    # split over dp along each batch's own leading axis
    batch = next(iter(trainer._placed_cache.values()))[1][0].batches
    check(batch.x.sharding.device_set == devices
          and len({s.index for s in batch.x.addressable_shards}) == 4,
          f"batch is not split over four devices: {batch.x.sharding}")
    losses = [m.loss for m in history["train"]]
    dp1 = _facts.get("dp1_losses")
    check(dp1 is not None, "no dp=1 run to compare with")
    rel = abs(losses[-1] - dp1[-1]) / abs(dp1[-1])
    say(f"   dp=4 losses {losses} vs dp=1 {dp1}: rel {rel:.2e} "
        f"(tol {TOL_DP_LOSS:.0e})")
    check(rel <= TOL_DP_LOSS, "dp=4 loss differs from dp=1")

    out = _serve_report(["--cell", "gru", "--shard-pool", *FLEET_ARGS])
    check(out["n_devices"] == 4, f"report says {out['n_devices']} devices")
    cfg = dataclasses.replace(cfg, runtime=dataclasses.replace(
        cfg.runtime, shard_pool=True))
    app = Application(cfg)
    gateway = app.attach_fleet(*_fleet_model(cfg, "gru"))
    check(gateway.pool.n_shards == 4, f"n_shards {gateway.pool.n_shards}")
    _params, carry, ring, pos, _x_min, _x_range = gateway.pool.live_tree()
    for leaf in (*jax.tree.leaves(carry), ring, pos):
        check(leaf.sharding.device_set == devices
              and not leaf.sharding.is_fully_replicated,
              f"pool state leaf not sharded over four: {leaf.sharding}")
    in_use = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
    say(f"   bytes_in_use per device: {in_use}")
    check(all(b > 0 for b in in_use), "a device holds nothing")
    _facts["four_chips"] = {"dp4_vs_dp1_rel": rel, "bytes_in_use": in_use}
    app.close()


# ---------------------------------------------------------------------------


def main() -> int:
    select_backend()  # exits non-zero: nothing pinned and no TPU
    cache = _CacheCounter()
    tmp = tempfile.mkdtemp(prefix="fmda_chip_smoke_")
    try:
        device_line()  # the first line of stdout says what this ran on
        section("host path", host_path)
        section("serving gru", lambda: serving("gru"))
        section("serving ssm", lambda: serving("ssm"))
        section("jax profile", lambda: profile_fact(tmp))
        section("training", lambda: training(tmp))
        section("the loop", lambda: the_loop(tmp))
        section("kernels", lambda: kernels(tmp))
        section("four chips", four_chips)
    finally:
        cache.close()
        shutil.rmtree(tmp, ignore_errors=True)
    say("== compile")
    say(json.dumps({
        "ledger_compile_seconds_serve_fleet": round(
            _facts.get("ledger_compile_s", 0.0), 3),
        "jax_backend_compile_seconds": round(cache.backend_compile_s, 3),
        "persistent_cache_hits": cache.hits,
        "persistent_cache_misses": cache.misses,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }))
    say(json.dumps({"facts": {k: v for k, v in _facts.items()
                              if k != "device"}}))
    if _failed:
        say(f"FAILED sections: {_failed}")
        return 1
    device = _facts["device"]
    if device["platform"] != "tpu":
        say(f"FAILED: platform is {device['platform']!r}, not 'tpu' — this "
            "was a dry run; the smoke passes only on the chip")
        return 1
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
