"""Whole training epochs through ``Trainer.fit`` with the caches warm.

Set-up makes the corpus, runs one epoch from fresh parameters (compile,
window gather, placement) and one more through the exact call the window
repeats; the window then runs ``fit(epochs=1, initial_state=...,
dataset=...)`` until ``--seconds`` have passed, each epoch with its
validation pass and ended by the trainer's own device fetch — what
epochs 2..25 of ``python -m fmda_tpu train`` are.

A traced run then runs further epochs and traces ``trace_steps`` train
steps of one of them, counted by the program's ``train_steps_total``
and wholly inside one training pass where the pass is long enough
(``harness/tracing.py`` ``StepSlice``): no eval step, drain or epoch
end in the slice, at whatever speed the trainer runs.

Traffic parameters: ``rows`` (length of the one-ticker corpus),
``lead`` (bars ahead the labels look), ``trace_steps`` (train steps in a
traced run's slice).  Batch, chunk and cache sizes are the
configuration's ``framework.train``.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from benchmark.harness.corpus import make_corpus
from benchmark.harness.tracing import StepSlice, TailTracer, span

END_TO_END = {"train_samples_per_s": "samples/s"}
#: The trainer's validation loss (default matmul precision: one bf16 MXU
#: pass, f32 accumulation) against the float32 ``highest`` reference on
#: the same batches and parameters, relative.  bf16 rounding of 108- and
#: 32-term dot products moves a logit by ~1e-3 relative, and the loss is
#: a mean over 4e4 windows, so the errors average down; a forward pass in
#: bf16 throughout moves it by more than 1e-2.
EVAL_LOSS_RTOL = 3e-3


def valid_windows(dataset, chunk_indices, batch_size: int):
    """(valid windows, lanes, steps) over the chunks: the mask sum, never
    the padded lanes."""
    from fmda_tpu.data.pipeline import WindowBatches

    valid = lanes = steps = 0
    for idx in chunk_indices:
        for b in WindowBatches(dataset, idx, batch_size):
            valid += int(b.mask.sum())
            lanes += len(b.mask)
            steps += 1
    return valid, lanes, steps


def run(ctx) -> Dict:
    t0 = time.perf_counter()
    import jax

    from fmda_tpu.config import config_from_dict
    from fmda_tpu.data.pipeline import WindowBatches
    from fmda_tpu.data.source import ArraySource
    from fmda_tpu.obs.registry import default_registry
    from fmda_tpu.train.trainer import Trainer, imbalance_weights_from_source

    traffic, seconds, parts = ctx.traffic, ctx.seconds, ctx.parts
    cfg = config_from_dict(ctx.config["framework"])
    mc, tc = cfg.model, cfg.train
    x, y = make_corpus(int(traffic["rows"]), mc.n_features, ctx.seed,
                       lead=int(traffic.get("lead", 6)))
    source = ArraySource(x, y, [f"f{j}" for j in range(mc.n_features)])
    weight, pos_weight = imbalance_weights_from_source(source)
    parts["corpus"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    trainer = Trainer(mc, tc, weight=weight, pos_weight=pos_weight)
    rng = jax.random.PRNGKey(ctx.seed)
    state, hist0, dataset = trainer.fit(source, rng=rng, epochs=1)
    parts["first_epoch_compile_gather_place"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, hist1, _ = trainer.fit(source, rng=rng, epochs=1,
                                  initial_state=state, dataset=dataset)
    trainer.mark_warm()
    parts["second_epoch_cached"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    train_chunks, val_chunks, _ = dataset.split(tc.val_size, tc.test_size)
    n_valid, n_lanes, train_steps = valid_windows(
        dataset, train_chunks, tc.batch_size)
    _, _, eval_steps = valid_windows(dataset, val_chunks, tc.batch_size)
    parts["count_valid_windows"] = time.perf_counter() - t0
    ctx.say({"train_loss_after_setup_epochs": [
        hist0["train"][0].loss, hist1["train"][0].loss],
        "valid_windows_per_epoch": n_valid, "lanes_per_epoch": n_lanes,
        "train_steps_per_epoch": train_steps,
        "eval_steps_per_epoch": eval_steps})

    stall = default_registry().histogram("train_input_stall_seconds")
    clock = time.perf_counter
    ctx.window_begins()
    stall_before = stall.snapshot()
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
    stall_after = stall.snapshot()
    ctx.window_ended()

    # the reference: plain forward and loss on the validation batches,
    # with the parameters the run ended on
    from benchmark.reference.training import eval_loss

    def val_batches():
        for idx in val_chunks:
            yield from WindowBatches(dataset, idx, tc.batch_size)

    ref_loss = eval_loss(state.params, val_batches(),
                         np.asarray(weight), np.asarray(pos_weight),
                         cell=mc.cell)
    rel = abs(ref_loss - val_losses[-1]) / max(abs(ref_loss), 1e-12)
    finite = [bool(np.isfinite(v)) for v in train_losses + val_losses]
    bad_epochs = sum(1 for a, b in zip(train_losses, val_losses)
                     if not (np.isfinite(a) and np.isfinite(b)))
    checks = {
        "val_loss_program": val_losses[-1],
        "val_loss_reference": ref_loss,
        "val_loss_rel_err": rel,
        "val_loss_rtol": EVAL_LOSS_RTOL,
        "losses_finite": all(finite),
        "loss_fell": train_losses[-1] < hist0["train"][0].loss,
        "unexpected_recompiles": trainer.unexpected_recompiles,
        "compile_counts": trainer.compile_counts,
        "train_losses": train_losses,
    }
    correct = bool(rel <= EVAL_LOSS_RTOL and all(finite)
                   and checks["loss_fell"]
                   and trainer.unexpected_recompiles == 0)
    steps_per_epoch = train_steps + eval_steps

    # more epochs, some train steps of one of them traced: the profiler
    # starts and stops on a thread of its own, outside the window
    tracer = TailTracer(ctx.trace, ctx.trace_dir)
    tail = {}
    if ctx.trace:
        counted = default_registry().counter("train_steps_total",
                                             phase="train")
        piece = StepSlice(tracer, lambda: counted.value,
                          int(traffic["trace_steps"]), train_steps)

        def one_epoch():
            nonlocal state
            with span("bench_epoch"):
                state, _, _ = trainer.fit(
                    source, rng=rng, epochs=1, initial_state=state,
                    dataset=dataset)

        tail_epochs = piece.drive(one_epoch)
        tail = {
            "tail_epochs": tail_epochs,
            "trace_steps": piece.n_steps,
            "traced_steps": piece.traced_steps,
            "trace_slice_s": tracer.slice_s,
            "trace_slice_in_one_pass": piece.in_one_pass,
            "trace_slice_opened_at_step": piece.opened_at,
            "trace_slice_closed_at_step": piece.closed_at,
        }
    return {
        "attempted": epochs * steps_per_epoch,
        "failed": bad_epochs * steps_per_epoch,
        "correct": correct,
        "checks": checks,
        "end_to_end": {
            "train_samples_per_s": epochs * n_valid / elapsed},
        "record": {
            "window_s": elapsed,
            "input_stall_s": stall_after["total_s"] - stall_before["total_s"],
            "model_cfg": mc,
            "train_cfg": tc,
            "valid_windows_per_epoch": n_valid,
            "tracer": tracer,
        },
        "notes": {
            "epochs": epochs,
            "valid_windows_per_epoch": n_valid,
            "padded_lanes_per_epoch": n_lanes - n_valid,
            "train_steps": epochs * train_steps,
            "eval_steps": epochs * eval_steps,
            "window_elapsed_s": elapsed,
            "trace_start_cost_s": tracer.start_cost_s,
            "trace_stop_cost_s": tracer.stop_cost_s,
            **tail,
        },
    }
