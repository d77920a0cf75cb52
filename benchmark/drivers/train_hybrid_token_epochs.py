"""Whole training epochs of a hybrid token family (state-space layers
and attention layers in one stack, a dense MLP, a tied head) through
``Trainer.fit``, the placed-batch cache warm.

The run has the shape of ``drivers/train_token_epochs.py`` and uses its
pieces (the seeded token corpus, :class:`~benchmark.drivers.
train_token_epochs.OpeningTracer`, :class:`~benchmark.drivers.
train_token_epochs.DeviceStepCount`, ``count_split``): set-up makes the
corpus from ``--seed``, runs ``setup_epochs`` epochs (compile and
placement, then the exact call the window repeats); the window runs
``fit(epochs=1, initial_state=..., dataset=...)`` until ``--seconds``
have passed; ``train_samples_per_s`` is the valid sequences of the whole
epochs over their wall time.  A traced run then traces ``trace_steps``
train steps of one training pass, counted where the device finishes
them.

``correct`` is decided after all that, outside the window and outside
``setup_s``, at the timed sizes, on what the timed path produced, as
``train_token_epochs.py`` decides it but for the routing this family has
none of (the limits and the readings behind each are at
:data:`VAL_LOSS_ATOL` .. :data:`CHANGE_BAND`):

- the trainer's validation loss on each validation sequence, from the
  compiled eval step the window ran, against the plain float32 reference
  (``reference/hybrid_decoder.py``, whose recurrence is stepwise) on the
  same ids and final parameters;
- the first train step, from ``Trainer.single_step`` on fresh
  parameters, against the reference on the same batch and parameters:
  its loss; **its gradient**, read from Adam's first moment after the
  step, leaf by leaf against the reference's clipped gradient, the worst
  leaf of each group of :data:`GRAD_GROUP`; **its change to the
  parameters** against the reference's plain Adam step;
- the scans walked every position of every sequence of the window's
  training passes (``ssd_positions_total``, ``ssd_chunks_total``);
- every loss finite, the training loss below the first epoch's, no
  recompile after warm-up (``run.py`` adds: no compile inside the
  window).

Traffic parameters: as ``train_token_epochs.py``'s, and
``setup_epochs``.  The record carries ``hybrid`` (sizes and the scans'
counts a train step) and neither ``moe`` nor ``sparse``: the readers
keyed to those stay silent here, and this cell's utilization is
``hybrid_train_mfu`` over ``harness/hybrid_decoder_flops.py``.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from benchmark.drivers.train_token_epochs import (
    ADAM_B1, DeviceStepCount, OpeningTracer, _leaf_name, count_split)
from benchmark.harness.device import memory_peak_bytes
from benchmark.harness.token_corpus import make_token_stream
from benchmark.harness.tracing import StepSlice, span

END_TO_END = {"train_samples_per_s": "samples/s"}

#: ``layer_layout``'s value for a state-space layer.
SSM_LAYOUT = 3

#: The limits below and the readings behind them.  All readings are the
#: builder's on the chip at the published widths (PERF.md section 6,
#: PR 34; sixteen runs of the program, seeds 34001234xx, 34002000xx
#: and 34004000xx, the wrong references at seed 3400123403, the state
#: in bfloat16 at 3400400201 too).  Each limit
#: lies between the largest reading the program gave and the reading of
#: the wrong reference nearest to it; each deliberately wrong reference
#: has to fail by one of the limits, not by each.
#:
#: Absolute tolerance, in nats, on a step's loss (a mean over 8,192
#: tokens).  The program computes its products in bfloat16 with float32
#: accumulation, its scan in chunks of 256; the reference is float32
#: ``highest`` throughout and its recurrence stepwise.  The program:
#: **2.9e-6 to 6.0e-5** on the validation sequence.  The reference with
#: every product's operands in float8 e5m2: 2.9e-3; with the carried
#: state dropped every 256 positions 4.2e-3; the convolution a position
#: ahead 3.4e-3; the skip left out 4.2e-3; the gate 1.5e-2; a multiplier
#: 6.5e-3 to 13.8 (the attention multiplier 3.0e-4, which the gradient
#: catches).  The limit is 6.7x the program's largest and 7x under the
#: float8 run's.
VAL_LOSS_ATOL = 4e-4
#: The first train step's loss, on fresh parameters (a loss of ln 12,544
#: whatever the layers do, so it says less): the program **9.5e-7 to
#: 4.9e-5**; float8 operands 3.4e-4, the convolution ahead 2.6e-3, the
#: embedding or the residual multiplier left out 1.5e-2 to 1.7e-2.  3x
#: the program's largest, 2.3x under the float8 run's.
FIRST_LOSS_ATOL = 1.5e-4
#: The first train step's gradient against the reference's, leaf by
#: leaf: ``|g - g_ref| / |g_ref|`` (Frobenius norms, both clipped to the
#: configuration's global norm), the worst leaf of each group against
#: the group's limit.  ``mixer``: a state-space layer's two projections;
#: ``ssm``: its small leaves (rates, step-size bias, skip, taps);
#: ``attention``: the attention layer's four projections; ``mlp``: the
#: dense MLP's three; ``norms``: every norm scale; ``embed``: the tied
#: embedding/head.  Worst leaf of a group, program / float8 operands /
#: **the state carried in bfloat16** / the carry dropped every 256:
#: mixer **2.32 to 2.39 %** / 31 % / 3.9 % / 17 %; ssm **2.9 to 4.8 %**
#: (a ``dt_bias`` every time: 64 numbers, each a sum over 8,192 x 64
#: that mostly cancels) / 42 % / **15.9 % and 27.1 %** (two seeds; the
#: program 3.6 % and 4.6 % on the same steps) / 84 %; attention **1.67 to
#: 1.81 %** / 22 % / 2.5 % / 12 % (the attention multiplier left out:
#: 89 %); mlp **2.34 to 2.40 %** / 33 % / 4.0 % / 17 %; norms **2.32 to
#: 2.46 %** / 31 % / 3.9 % / 17 %; embed **1.82 to 1.93 %** / 24 % /
#: 3.1 % / 14 %.  The program's 2.3 % is even over the ten layers and is
#: bfloat16's (the same program at a quarter of the widths reads 1.1 %
#: on the CPU, 0.8 % with a float32 residual stream).  A state in
#: bfloat16 moves the large leaves by less than twice the program's own
#: distance; it is the step-size biases that show it, so the ``ssm``
#: limit is the tight one: 1.7x the program's largest reading and 2.0x
#: under that run's.  The others are 3.2x to 3.4x the program's largest
#: and 3.7x to 4.1x under the float8 run's.
GRAD_GROUP = {
    "w_in": "mixer", "w_out": "mixer",
    "a_log": "ssm", "dt_bias": "ssm", "d_skip": "ssm", "conv_w": "ssm",
    "conv_b": "ssm",
    "wq": "attention", "wk": "attention", "wv": "attention",
    "wo": "attention",
    "w_gate": "mlp", "w_up": "mlp", "w_down": "mlp",
    "ln_attn": "norms", "ln_mlp": "norms", "ln_gate": "norms",
    "ln_final": "norms",
    "embed": "embed",
}
GRAD_REL_DIFF = {"mixer": 0.08, "ssm": 0.08, "attention": 0.06,
                 "mlp": 0.08, "norms": 0.08, "embed": 0.065}
#: ``sum |change| / sum |the reference's change|`` of each leaf over the
#: first step (the reference's: plain Adam on its own clipped gradient).
#: Adam's first step moves every element with a gradient by the learning
#: rate, so a leaf the step never updates reads 0 and a rate applied
#: twice 2.  The program: **0.974 to 1.015** over all 128 leaves, the
#: rates, the step-size biases and the skips at both ends: 64 elements a
#: leaf, some with a clipped gradient small enough to stand beside
#: Adam's eps of 1e-8 (an element's first step is ``lr * g / (|g| +
#: eps)``, which shows a rounding of ``g`` only there; over 324 such
#: leaves the distance from 1 is 0.14 % at the median, 1.5 % at the 99th
#: percentile and 2.6 % at most); every other leaf reads 0.9985 to
#: 1.0017.  The wrong references nearest to 1: the logits' scaling left
#: out 0.87, the attention multiplier 0.78 (both on ``wq``); the gate
#: 1.93; the skip 1e27 (its gradient is 0 in that reference).  The band
#: is 2.3x the program's widest reading and 2.2x inside the nearest
#: wrong one; no wrong reference is left to this check alone.
CHANGE_BAND = (0.94, 1.06)


def require_hybrid_program() -> None:
    """Fail at once, with a message, on a program without the layer."""
    import dataclasses

    from fmda_tpu.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    if not {"ssm_heads", "ssm_state", "ffn_size", "tie_embeddings"} <= fields:
        raise SystemExit(
            "this program has no state-space layer and no dense MLP "
            "(ModelConfig lacks ssm_heads / ssm_state / ffn_size / "
            "tie_embeddings): the cell cannot run on it")


def scan_counters(reg, layouts) -> Dict[str, List[float]]:
    """The training passes' scan counters so far, per state-space layer."""
    labels = [dict(layer=str(i), phase="train")
              for i, layout in enumerate(layouts) if layout == SSM_LAYOUT]
    return {
        "chunks": [reg.counter("ssd_chunks_total", **lb).value
                   for lb in labels],
        "positions": [reg.counter("ssd_positions_total", **lb).value
                      for lb in labels],
    }


def run(ctx) -> Dict:
    t0 = time.perf_counter()
    require_hybrid_program()
    import jax

    from fmda_tpu.config import config_from_dict
    from fmda_tpu.data.source import TokenArraySource
    from fmda_tpu.obs.registry import default_registry
    from fmda_tpu.train.trainer import Trainer

    traffic, seconds, parts = ctx.traffic, ctx.seconds, ctx.parts
    cfg = config_from_dict(ctx.config["framework"])
    mc, tc = cfg.model, cfg.train
    seq = int(traffic["seq_len"])
    per_epoch = sum(int(traffic[k]) for k in (
        "train_sequences", "val_sequences", "test_sequences"))
    if (tc.window, tc.batch_size) != (seq, int(traffic["sequences_per_step"])):
        raise SystemExit(
            f"traffic asks for {traffic['sequences_per_step']} x {seq} "
            f"tokens a step, the configuration trains batch_size="
            f"{tc.batch_size} x window={tc.window}")
    stream = make_token_stream(
        per_epoch * seq + 1, mc.vocab_size, ctx.seed,
        zipf_exponent=float(traffic["zipf_exponent"]),
        doc_median_tokens=float(traffic["doc_median_tokens"]),
        doc_sigma=float(traffic["doc_sigma"]), eod_id=int(traffic["eod_id"]))
    source = TokenArraySource(stream, mc.vocab_size)
    parts["corpus"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    reg = default_registry()
    trainer = Trainer(mc, tc)
    rng = jax.random.PRNGKey(ctx.seed)
    state, hist0, dataset = trainer.fit(source, rng=rng, epochs=1)
    parts["first_epoch_compile_place"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    setup_losses = [hist0["train"][0].loss]
    for _ in range(int(traffic.get("setup_epochs", 2)) - 1):
        state, hist, _ = trainer.fit(source, rng=rng, epochs=1,
                                     initial_state=state, dataset=dataset)
        setup_losses.append(hist["train"][0].loss)
    trainer.mark_warm()
    parts["further_setup_epochs_cached"] = time.perf_counter() - t0
    train_chunks, val_chunks, test_chunks, n_valid = count_split(dataset, tc)
    got = tuple(len(c) * dataset.per_chunk
                for c in (train_chunks, val_chunks, test_chunks))
    want = tuple(int(traffic[k]) for k in (
        "train_sequences", "val_sequences", "test_sequences"))
    if got != want:
        raise SystemExit(f"the configuration's split gives {got} "
                         f"sequences, the traffic asks for {want}")
    train_steps = sum(len(trainer.task.batches(dataset, i))
                      for i in train_chunks)
    eval_steps = sum(len(trainer.task.batches(dataset, i))
                     for i in val_chunks)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(state.params))
    ctx.say({"train_loss_after_setup_epochs": setup_losses,
             "valid_sequences_per_epoch": n_valid,
             "train_steps_per_epoch": train_steps,
             "eval_steps_per_epoch": eval_steps,
             "parameters": n_params,
             "eod_share": float(np.mean(stream == int(traffic["eod_id"])))})

    counted = reg.counter("train_steps_total", phase="train")
    done = DeviceStepCount(lambda: counted.value) if ctx.trace else None
    stall = reg.histogram("train_input_stall_seconds")
    clock = time.perf_counter
    ctx.window_begins()
    stall_before = stall.snapshot()
    scans_before = scan_counters(reg, mc.layer_layout)
    t_start = clock()
    epochs = 0
    train_losses, val_losses = [], []
    while clock() - t_start < seconds:
        with span("bench_epoch"):
            state, h, _ = trainer.fit(source, rng=rng, epochs=1,
                                      initial_state=state, dataset=dataset)
        train_losses.append(h["train"][0].loss)
        val_losses.append(h["val"][0].loss)
        epochs += 1
    elapsed = clock() - t_start
    peak_at_window_end = memory_peak_bytes(jax.devices())
    stall_after = stall.snapshot()
    scans_after = scan_counters(reg, mc.layer_layout)
    ctx.window_ended()
    steps_per_epoch = train_steps + eval_steps

    # a traced run: more epochs, the first steps of one training pass
    # traced; the profiler starts and stops on a thread of its own
    tracer = OpeningTracer(ctx.trace, ctx.trace_dir)
    tail = {}
    if ctx.trace:
        done.start()
        piece = StepSlice(tracer, lambda: done.value,
                          int(traffic["trace_steps"]), train_steps)

        def one_epoch():
            nonlocal state
            # the traced pass begins once the slice is open: every one
            # of its `train` annotations then begins inside the slice
            tracer.opened.wait(timeout=60.0)
            with span("bench_epoch"):
                state, _, _ = trainer.fit(
                    source, rng=rng, epochs=1, initial_state=state,
                    dataset=dataset)

        tail_epochs = piece.drive(one_epoch)
        done.stop()
        tail = {
            "tail_epochs": tail_epochs,
            "trace_steps": piece.n_steps,
            "traced_steps": piece.traced_steps,
            "trace_slice_s": tracer.slice_s,
            "trace_slice_fits_margins": piece.fits,
            "trace_slice_opened_at_step": piece.opened_at,
            "trace_slice_closed_at_step": piece.closed_at,
            "trace_slice_inside_training_pass": bool(
                piece.closed_at is not None
                and piece.closed_at <= train_steps),
        }

    first_batch = next(iter(trainer._chunk_batches(dataset, train_chunks[0])))
    step_bytes = compiled_step_bytes(trainer, state, first_batch, rng)
    # the optimizer's moments are let go first: the reference's float32
    # activations need the room they take
    final_params = [state.params]
    del state
    # read before the comparisons: where a pass runs as groups of steps
    # (a tiny rehearsal cell), they are the first to call the single
    # programs, and that compile is theirs, not the window's
    recompiles = trainer.unexpected_recompiles
    checks = reference_checks(
        ctx, trainer, final_params, dataset, val_chunks, train_chunks[0],
        rng)
    finite = [bool(np.isfinite(v)) for v in train_losses + val_losses]
    bad_epochs = sum(1 for a, b in zip(train_losses, val_losses)
                     if not (np.isfinite(a) and np.isfinite(b)))
    # what the scans walked in the window's training passes, a layer
    window_steps = max(epochs * train_steps, 1)
    walked = {k: [(a - b) / window_steps
                  for a, b in zip(scans_after[k], scans_before[k])]
              for k in scans_after}
    sequences = tc.batch_size
    checks.update({
        "losses_finite": all(finite),
        "loss_fell": bool(train_losses
                          and train_losses[-1] < hist0["train"][0].loss),
        "scan_positions_per_train_step": walked["positions"],
        "scan_chunks_per_train_step": walked["chunks"],
        "scans_ok": bool(walked["positions"]) and all(
            p == sequences * seq for p in walked["positions"]) and all(
            c == sequences * -(-seq // mc.ssm_chunk)
            for c in walked["chunks"]),
        "unexpected_recompiles": recompiles,
        "compiles_by_the_comparisons": (
            trainer.unexpected_recompiles - recompiles),
        "compile_counts": trainer.compile_counts,
        "train_losses": train_losses,
        "val_losses": val_losses,
    })
    correct = bool(
        all(checks[name] for name in REFERENCE_DECIDES)
        and checks["scans_ok"] and all(finite) and checks["loss_fell"]
        and recompiles == 0)

    hybrid = {
        "seq_len": seq,
        "sequences_per_step": tc.batch_size,
        "scan_positions_per_train_step": walked["positions"],
        "scan_chunks_per_train_step": walked["chunks"],
    }
    return {
        "attempted": epochs * steps_per_epoch,
        "failed": bad_epochs * steps_per_epoch,
        "correct": correct,
        "checks": checks,
        "end_to_end": {
            "train_samples_per_s": epochs * n_valid / elapsed},
        # no "train_cfg": readers.train_mfu counts a recurrent classifier
        # from it; this cell's utilization is hybrid_train_mfu
        "record": {
            "window_s": elapsed,
            "input_stall_s": stall_after["total_s"] - stall_before["total_s"],
            "model_cfg": mc,
            "hybrid": hybrid,
            "valid_sequences_per_epoch": n_valid,
            "tracer": tracer,
        },
        "notes": {
            "epochs": epochs,
            "valid_sequences_per_epoch": n_valid,
            "tokens_per_s": epochs * n_valid * seq / elapsed,
            "train_steps": epochs * train_steps,
            "eval_steps": epochs * eval_steps,
            "window_elapsed_s": elapsed,
            # train_peak_hbm_mb reads the process's peak after the
            # comparisons with the reference: they stay under this
            "device_peak_bytes_at_window_end": peak_at_window_end,
            "compiled_step_bytes": step_bytes,
            "hybrid": hybrid,
            "trace_start_cost_s": tracer.start_cost_s,
            "trace_stop_cost_s": tracer.stop_cost_s,
            **tail,
        },
    }


def compiled_step_bytes(trainer, state, batch, rng
                        ) -> Optional[Dict[str, int]]:
    """What the compiled train step reserves on the device, from the
    compiler's own analysis of the program the window ran (compiled once
    more here, after the window, out of the persistent cache where there
    is one): its arguments (the state: parameters and moments) and the
    temporaries beside them (gradients and activations).  None where the
    backend does not say."""
    try:
        stats = trainer._train_step._jit.lower(
            state, trainer.zero_totals(), batch, rng
        ).compile().memory_analysis()
    except Exception:  # noqa: BLE001 - a backend without the analysis
        return None
    if stats is None:
        return None
    return {"argument": int(stats.argument_size_in_bytes),
            "temp": int(stats.temp_size_in_bytes),
            "output": int(stats.output_size_in_bytes),
            "alias": int(stats.alias_size_in_bytes)}


#: What of :func:`reference_checks` decides ``correct``.
REFERENCE_DECIDES = ("val_loss_ok", "first_loss_ok", "grad_ok", "change_ok")


def reference_checks(ctx, trainer, final_params: list, dataset,
                     val_chunks, first_chunk, rng,
                     reference_kw: Optional[Dict] = None) -> Dict:
    """The comparisons with the plain reference, at the timed sizes.
    ``final_params`` is a one-element list that is emptied here, so that
    the parameters the run ended on are freed before fresh ones are
    made.  ``reference_kw`` makes the *reference* deliberately wrong (the
    ``wrong`` keywords of ``reference/hybrid_decoder.py``): the selftest
    and the builder's readings put those runs through the limits above,
    and each has to come out as not correct."""
    checks = validation_checks(ctx, trainer, final_params.pop(), dataset,
                               val_chunks, reference_kw)
    checks.update(first_step_checks(ctx, trainer, dataset, first_chunk, rng,
                                    reference_kw))
    return checks


def validation_checks(ctx, trainer, params, dataset, val_chunks,
                      reference_kw: Optional[Dict] = None) -> Dict:
    """The validation sequences, a step at a time through the compiled
    eval step the window ran, against the reference on the same ids and
    the parameters the run ended on."""
    import jax

    from benchmark.reference import hybrid_decoder as ref

    t0 = time.perf_counter()
    mc, kw = trainer.model_cfg, dict(reference_kw or {})
    ref_loss = jax.jit(
        lambda p, x, y, m: ref.batch_loss(p, x, y, m, mc, **kw))
    program, wanted = [], []
    for idx in val_chunks:
        for batch in trainer._chunk_batches(dataset, idx):
            totals = trainer._eval_step(params, trainer.zero_totals(), batch)
            program.append(float(totals.loss))
            wanted.append(float(ref_loss(params, batch.x, batch.y,
                                         batch.mask)))
    err = [abs(a - b) for a, b in zip(program, wanted)]
    ctx.say({"reference_check_s": {"validation": time.perf_counter() - t0}})
    return {
        "val_loss_program": program,
        "val_loss_reference": wanted,
        "val_loss_abs_err": err,
        "val_loss_atol": VAL_LOSS_ATOL,
        "val_loss_ok": bool(err and max(err) <= VAL_LOSS_ATOL),
    }


def first_step_checks(ctx, trainer, dataset, first_chunk, rng,
                      reference_kw: Optional[Dict] = None) -> Dict:
    """The first train step, from fresh parameters as ``fit()`` makes
    them, on the first training batch, through the compiled train step
    the window ran: its loss, the gradient it computed and the change it
    made to the parameters, against the reference's on the same batch
    and parameters.

    The step's gradient is read from what the step left behind: Adam's
    moments start at zero, so the first moment after one step is
    ``(1 - b1)`` times the gradient as the optimizer saw it, clipped."""
    import jax
    import optax

    from benchmark.reference import hybrid_decoder as ref

    t0 = time.perf_counter()
    mc, tc = trainer.model_cfg, trainer.train_cfg
    init_rng, step_rng = jax.random.split(rng)
    batch = next(iter(trainer._chunk_batches(dataset, first_chunk)))

    # the program's step; what it made goes to the host and the device
    # is cleared for the reference's float32 backward
    fresh = trainer.init_state(init_rng)
    before = jax.device_get(fresh.params)
    after, totals = trainer.single_step(fresh, batch, step_rng)
    got_loss = float(totals.loss)
    got_grads = jax.tree.map(
        lambda m: m / (1.0 - ADAM_B1),
        jax.device_get(optax.tree_utils.tree_get(after.opt_state, "mu")))
    got_change = jax.tree.map(lambda a, b: a - b,
                              jax.device_get(after.params), before)
    del fresh, after, totals
    t_program = time.perf_counter() - t0

    # the reference: its forward, its backward a layer at a time
    # (gradients to the host as they come), its plain clip and Adam step
    # on the host
    t0 = time.perf_counter()
    params = jax.device_put(before)
    del before
    want_loss, grads = ref.loss_and_grads_by_layer(
        params, batch.x, batch.y, batch.mask, mc, **(reference_kw or {}))
    del params
    want_grads, want_change = ref.first_adam_step(
        grads, learning_rate=tc.learning_rate, clip=tc.clip)
    del grads

    grad_diff, change_ratio = {}, {}
    want = jax.tree_util.tree_leaves_with_path(want_grads)
    for (path, want_g), got_g, got_d, want_d in zip(
            want, jax.tree.leaves(got_grads), jax.tree.leaves(got_change),
            jax.tree.leaves(want_change)):
        name = _leaf_name(path)
        grad_diff[name] = float(
            np.linalg.norm((got_g - want_g).ravel())
            / max(np.linalg.norm(want_g.ravel()), 1e-30))
        change_ratio[name] = float(
            np.abs(got_d).sum() / max(np.abs(want_d).sum(), 1e-30))
    del want_grads, want_change, want
    worst = {group: 0.0 for group in GRAD_REL_DIFF}
    for name, value in grad_diff.items():
        group = GRAD_GROUP[name.rsplit("/", 1)[-1]]
        worst[group] = max(worst[group], value)
    ctx.say({"reference_check_s": {
        "first_step_program": t_program,
        "first_step_reference": time.perf_counter() - t0}})
    return {
        "first_loss_program": got_loss,
        "first_loss_reference": want_loss,
        "first_loss_abs_err": abs(got_loss - want_loss),
        "first_loss_atol": FIRST_LOSS_ATOL,
        "first_loss_ok": bool(abs(got_loss - want_loss) <= FIRST_LOSS_ATOL),
        "grad_rel_diff": grad_diff,
        "grad_rel_diff_worst": worst,
        "grad_rel_diff_limit": GRAD_REL_DIFF,
        "grad_ok": all(worst[g] <= GRAD_REL_DIFF[g] for g in GRAD_REL_DIFF),
        "change_over_reference": change_ratio,
        "change_over_reference_band": list(CHANGE_BAND),
        "change_ok": all(CHANGE_BAND[0] <= v <= CHANGE_BAND[1]
                         for v in change_ratio.values()),
    }
