"""Whole training epochs of a latent-attention token family under a plain
residual whose expert layers declare a loss term (Moonlight-16B-A3B:
latent attention with a direct query, two shared experts beside the
routed ones, a sigmoid router with a selection bias and the family's
per-sequence balance term) through ``Trainer.fit``, the placed-batch
cache warm.

The run has the shape of ``drivers/train_latent_token_epochs.py`` and is
built from the accepted token drivers' parts (the seeded token corpus,
:class:`~benchmark.drivers.train_token_epochs.OpeningTracer`,
:class:`~benchmark.drivers.train_token_epochs.DeviceStepCount`,
``count_split``, ``flip_shares``, ``moe_counters``,
``compiled_step_bytes``): set-up makes the corpus from ``--seed``, runs
``setup_epochs`` epochs (compile and placement, then the exact call the
window repeats); the window runs ``fit(epochs=1, initial_state=...,
dataset=...)`` until ``--seconds`` have passed; ``train_samples_per_s``
is the valid sequences of the whole epochs over their wall time.  A
traced run then traces ``trace_steps`` train steps of one training pass,
counted where the device finishes them.

``correct`` is decided after all that, outside the window and outside
``setup_s``, at the timed sizes, on what the timed path produced (the
limits and the readings behind each are at :data:`VAL_LOSS_ATOL` ..
:data:`HELD_PAIRS_BAND`):

- the trainer's validation loss (the next-token loss alone: a validation
  pass leaves the balance term out of its loss), per-expert pairs and
  **the balance term of every expert layer** on each validation
  sequence, from the compiled eval step the window ran, against the
  plain float32 reference (``reference/mla_decoder.py``) on the same ids
  and final parameters;
- the first train step, from ``Trainer.single_step`` on fresh
  parameters, against the reference on the same batch and parameters:
  its reported loss against the reference's next-token loss; **each
  expert layer's balance term**; the pairs each held expert received;
  **its gradient** (of the objective: next-token loss plus the terms),
  read from Adam's first moment after the step, leaf by leaf against the
  reference's clipped gradient, the worst leaf of each group of
  :data:`GRAD_GROUP`, the router's leaves in a group of their own;
  **its change to the parameters** against the reference's plain Adam
  step; **the selection bias after the step** equal to the reference's
  rule on the reference's own load over all the router's experts;
- the held pairs of a train step in every pass of the window inside a
  stated band; ``moe_pairs_dropped_total`` 0; every loss finite, the
  training loss below the first epoch's, no recompile after warm-up
  (``run.py`` adds: no compile inside the window).

The record carries ``latent`` (sizes and the window's counts a train
step: what the accepted ``mla_*`` / ``moe_*`` / ``attention_*`` readers
ask for) and ``mla`` (the same, for this cell's own ``mla_train_mfu``
over ``harness/mla_decoder_flops.py``), and none of ``moe`` / ``sparse``
/ ``hybrid``.  ``latent_train_mfu`` finds ``latent`` and reads here too,
low by the query's product (its count takes the query through a latent
of width 0): ``BENCHMARK.json`` does not list this cell for it.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from benchmark.drivers.train_hybrid_token_epochs import compiled_step_bytes
from benchmark.drivers.train_token_epochs import (
    ADAM_B1, DeviceStepCount, OpeningTracer, _leaf_name, count_split,
    flip_shares, moe_counters)
from benchmark.harness.device import memory_peak_bytes
from benchmark.harness.token_corpus import make_token_stream
from benchmark.harness.tracing import StepSlice, span

END_TO_END = {"train_samples_per_s": "samples/s"}

#: The limits below and the readings behind them.  All readings are the
#: builder's on the chip at the published widths (PERF.md section 6,
#: PR 46): the program on twenty seeds (4100046001 ..02 ..03 ..04,
#: 977046005, 4100046011 ..12, 3100046013, 2100046014, 1100046015, 46016,
#: 4100046017, 146018, 3946000777, 3946001001, 2946001002, 1946001003,
#: 946001004, 46001005, 4294001006: first steps and validation sequences
#: alike), the deliberately wrong references at seed
#: 4100046003 (after eight epochs; the right one beside them).  Each
#: limit lies between the largest reading the program gave and the
#: reading of the wrong reference nearest to it that the limit is there
#: to refuse; each deliberately wrong reference has to fail by one of
#: the limits, not by each.  Two wrong references fail by none, and no
#: limit could make them without refusing the program on some seeds
#: (PERF.md section 7): **a softmax rounded to bfloat16** reads as the
#: right reference does in every number (latent 2.774 / 2.774 %: the
#: kernels' ``p @ v`` takes ``p`` in bfloat16 already, and a score of
#: size 0.6 rounded to bfloat16 moves by 1e-3, under the bfloat16
#: products' own noise), and **the query rounded to float8 (e5m2)** moves
#: only the first layers' attention leaves, by 1.4 times at most (layer
#: 0's ``wkv_a`` 1.04 -> 1.47 %, all its key-value leaves 1.14 -> 1.61 %,
#: ``wq`` 2.27 -> 2.81 %) where the program's own reading runs from 0.85
#: to 1.17 % over the seeds and the deeper layers' from 0.58 to 1.22 %.
#:
#: Absolute tolerance, in nats, on the validation sequence's loss (the
#: next-token loss alone, a mean over 8,192 tokens, 9.6 nats after ten
#: epochs).  The program computes its products in bfloat16 with float32
#: accumulation and keeps the stream in bfloat16 between sublayers; the
#: reference is float32 ``highest`` throughout.  The program: **2.8e-5 to 7.7e-4**;
#: the reference with every product's operands in float8 e5m2 2.24e-2,
#: the shared experts left out 2.76e-1 (with every operand in bfloat16
#: 1.1e-5: the program's distance is its products').  The limit is 3.2x
#: the program's largest and 9x under float8's.
VAL_LOSS_ATOL = 2.5e-3
#: The first train step's reported loss against the reference's
#: next-token loss, on fresh parameters (9.9 nats whatever the layers
#: do, so it says less): the program **1.2e-5 to 5.1e-4** (eleven seeds
#: under 2.1e-4, two at 4.1e-4 and 5.1e-4: a limit of 4e-4 set from the
#: first five refused those two runs); float8 operands 1.05e-3 (inside:
#: that run is left to the validation loss and the gradient), the shared
#: experts left out 4.2e-3.  3x the program's largest, 2.8x under that.
FIRST_LOSS_ATOL = 1.5e-3
#: Share of a layer's held (token, expert) pairs that may sit on another
#: held expert, or on none, than in the reference's routing (half the
#: sum over the held experts of the absolute difference in pairs, over
#: the pairs held; the largest over the expert layers).  The router's
#: scores come from bfloat16 products, and where a token's sixth and
#: seventh largest differ by less than that rounding the top-6 flips;
#: with Zipf ids the flips come in lumps (``train_token_epochs.py`` has
#: the arithmetic: one tie of each of the two most frequent ids is 9 %).
#: Readings: the program 0.34 to 0.95 % on first steps and 0.38 to 3.0 %
#: on validation sequences; float8 operands 8.1 % / 8.1 % (inside: it
#: fails by the loss's and the gradient's limits), the shared experts
#: left out **13.3 % / 11.0 %**.
PAIR_FLIP_SHARE = 0.10
VAL_PAIR_FLIP_SHARE = 0.10
#: Each expert layer's balance term against the reference's, ``|got -
#: want|`` over the larger of the two, the worst layer: on the first
#: step (the step's own value) and on each validation sequence (the eval
#: step folds the term though its loss leaves it out).  The term is
#: ``alpha sum_e f_e P_e``, 1.003e-3 to 1.018e-3 at alpha 1e-3: a flipped
#: pair moves two ``f_e`` by 1/768 against ``P_e`` that differ by a few
#: per cent, so the program's distance is second order.  Readings: the
#: program **4.1e-5 to 2.8e-4** (first steps) and **1.9e-4 to 9.3e-4**
#: (validation); float8 operands 1.1e-3 / 2.1e-3, the shared experts
#: left out 1.9e-3 / 7.7e-3 (both fail by other limits); **the term left
#: out 1.0 / 1.0; ``s`` in place of ``s'`` 0.969 / 0.969** (the sigmoid
#: scores sum to about 32, not 1).  The limit is 5.4x the
#: program's largest and 190 times under the unnormalised term's.
TERM_REL_DIFF = 5e-3
VAL_TERM_REL_DIFF = 5e-3
#: The first train step's gradient (of the objective: next-token loss
#: plus the balance terms) against the reference's, leaf by leaf, both
#: clipped to the configuration's global norm, ``|g - g_ref| /
#: |g_ref|`` in Frobenius norms, the worst leaf of each group against
#: the group's limit.  ``latent`` (the four attention products and the
#: latent norm; the direct query's ``wq`` is the worst every time, 1.6 to
#: 2.8 %, the key-value leaves 0.58 to 1.22 %), ``dense`` (what every
#: token reaches the same way: the dense MLP, both shared experts,
#: embedding, head, the layers' attention and MLP norms), ``routed``
#: (what a token reaches through its top-6: the held experts' matrices
#: and the norm in front of them) and ``router`` (the routers' leaves
#: alone: the one leaf the balance term's gradient reaches directly,
#: and the leaf a flipped pair moves most).  Readings, program /
#: float8 operands / the shared experts left out: latent **1.88 to 2.88 %**
#: / 31.3 % / 96 %; dense **1.80 to 2.56 %** / 24.5 % / 87 %; routed
#: **7.8 to 15.1 %** / 83.5 % / 467 %; router **12.7 to 27.3 %** / 105 % /
#: 116 %, and with ``s`` in place of ``s'`` **91.1 %** (the term's
#: gradient is then 32 times as large; with the term left out 14.9 %
#: against 14.7 %: at alpha 1e-3 the term is 1.5 % of the router's
#: gradient, and it is the term's own limit that refuses that run).
#: The latent and dense limits are 2.1x / 2.3x the program's largest and
#: 5x / 4x under float8's; the routed 2.3x and 2.4x under float8's; the
#: router's 2.0x and 1.7x under the unnormalised term's.
GRAD_GROUP = {
    "wq": "latent", "wkv_a": "latent", "wkv_b": "latent", "wo": "latent",
    "kv_norm": "latent",
    "embed": "dense", "head": "dense", "ln_final": "dense",
    "ln_attn": "dense", "ln_mlp": "dense", "ws_gate": "dense",
    "ws_up": "dense", "ws_down": "dense",
    "ln_moe": "routed", "w_gate": "routed", "w_up": "routed",
    "w_down": "routed",
    "router": "router",
}
GRAD_REL_DIFF = {"latent": 0.06, "dense": 0.06, "routed": 0.35,
                 "router": 0.55}
#: As ``train_token_epochs.py``: a routed leaf's distance grows as the
#: root of the share of pairs routed elsewhere, so the routed and the
#: router limits are the larger of the ones above and these times the
#: root of the first step's largest pair-flip share (the program's
#: readings lie at 1.0 to 2.4 and 1.4 to 4.3 times that root; they come
#: into play over flips of 0.49 % and 0.47 %; the unnormalised term's
#: router reading, 91.1 % at flips of 0.54 %, stays over its 58.8 %).
GRAD_PER_ROOT_FLIP = {"routed": 5.0, "router": 8.0}
#: A leaf whose reference gradient is under this share of the whole
#: gradient's norm is *quiet*: zero but for rounding.  Here those are the
#: five selection biases alone (no gradient here, none there: they read
#: exactly 0 of the whole; the smallest share among the other 83 leaves is
#: 0.16 %), held to :data:`QUIET_ABS` of the whole gradient's norm.
QUIET_SHARE = 1e-5
QUIET_ABS = 3e-6
#: ``sum |change| / sum |the reference's change|`` of each leaf that is
#: not quiet over the first step (``train_token_epochs.py`` has the
#: arithmetic: a leaf the step never updates reads 0, a rate applied
#: twice 2).  The program: **0.9799 to 1.0075** (the norm scales at the low
#: end: they start at 1.0, where float32 rounds a step of 1e-6 to 8 or
#: 17 units in the last place).
CHANGE_BAND = (0.96, 1.04)
#: Held pairs of a train step, summed over the expert layers, in every
#: training pass of the window, as a multiple of what an even router
#: gives (seq_len x top-6 x held / all experts, 6,144 a layer): readings
#: over 84 passes 0.94 to 1.08 of the expected 30,720 (layers alone 0.86
#: to 1.31).
#: No expert layer under HELD_PAIRS_LAYER_FLOOR of its expected pairs.
HELD_PAIRS_BAND = (0.6, 1.6)
HELD_PAIRS_LAYER_FLOOR = 0.25


def require_mla_program() -> None:
    """Fail at once, with a message, on a program without the layer."""
    import dataclasses

    from fmda_tpu.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    if not {"kv_lora_rank", "moe_shared_experts", "first_dense_layers",
            "moe_seq_aux_alpha"} <= fields:
        raise SystemExit(
            "this program has no latent attention with a direct query under "
            "a declared balance term (ModelConfig lacks kv_lora_rank / "
            "moe_shared_experts / first_dense_layers / moe_seq_aux_alpha): "
            "the cell cannot run on it")


def term_gauges(reg, layers: List[int], phase: str = "train") -> List[float]:
    """``moe_seq_aux_loss`` of the last pass of ``phase``, a layer."""
    return [reg.gauge("moe_seq_aux_loss", layer=str(i), phase=phase).value
            for i in layers]


def run(ctx) -> Dict:
    t0 = time.perf_counter()
    require_mla_program()
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
    n_layers = len(mc.layer_layout)
    expert_layers = list(range(mc.first_dense_layers, n_layers))
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
    moe_before = moe_counters(reg, n_layers)
    t_start = clock()
    epochs = 0
    train_losses, val_losses, held_by_pass, terms_by_pass = [], [], [], []
    while clock() - t_start < seconds:
        with span("bench_epoch"):
            state, h, _ = trainer.fit(source, rng=rng, epochs=1,
                                      initial_state=state, dataset=dataset)
        train_losses.append(h["train"][0].loss)
        val_losses.append(h["val"][0].loss)
        held_by_pass.append(moe_counters(reg, n_layers)["held"])
        terms_by_pass.append(term_gauges(reg, expert_layers))
        epochs += 1
    elapsed = clock() - t_start
    peak_at_window_end = memory_peak_bytes(jax.devices())
    stall_after = stall.snapshot()
    moe_after = moe_counters(reg, n_layers)
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
    dropped = reg.counter("moe_pairs_dropped_total").value
    # the held pairs of a train step, expert layer by expert layer, in
    # each training pass of the window
    pairs_by_pass = [
        [(after[i] - before[i]) / train_steps for i in expert_layers]
        for before, after in zip([moe_before["held"]] + held_by_pass,
                                 held_by_pass)]
    expected = seq * tc.batch_size * mc.moe_top_k * mc.experts_held[1] \
        / mc.moe_experts
    band = [b * expected * len(expert_layers) for b in HELD_PAIRS_BAND]
    checks.update({
        "losses_finite": all(finite),
        "loss_fell": bool(train_losses
                          and train_losses[-1] < hist0["train"][0].loss),
        "moe_pairs_dropped_total": dropped,
        "held_pairs_per_step_by_pass": pairs_by_pass,
        "held_pairs_per_step_band": band,
        "held_pairs_layer_floor": HELD_PAIRS_LAYER_FLOOR * expected,
        "held_pairs_ok": bool(pairs_by_pass) and all(
            band[0] <= sum(layers) <= band[1]
            and min(layers) >= HELD_PAIRS_LAYER_FLOOR * expected
            for layers in pairs_by_pass),
        "seq_aux_loss_by_pass": terms_by_pass,
        "unexpected_recompiles": recompiles,
        "compiles_by_the_comparisons": (
            trainer.unexpected_recompiles - recompiles),
        "compile_counts": trainer.compile_counts,
        "train_losses": train_losses,
        "val_losses": val_losses,
    })
    correct = bool(
        all(checks[name] for name in REFERENCE_DECIDES)
        and checks["held_pairs_ok"]
        and all(finite) and checks["loss_fell"] and dropped == 0
        and recompiles == 0)

    window_steps = max(epochs * train_steps, 1)
    latent = {
        "seq_len": seq,
        "sequences_per_step": tc.batch_size,
        "experts_held": mc.experts_held[1],
        "pairs_per_train_step": [
            (moe_after["held"][i] - moe_before["held"][i]) / window_steps
            for i in expert_layers],
        "seq_aux_loss_last_pass": terms_by_pass[-1] if terms_by_pass else None,
    }
    return {
        "attempted": epochs * steps_per_epoch,
        "failed": bad_epochs * steps_per_epoch,
        "correct": correct,
        "checks": checks,
        "end_to_end": {
            "train_samples_per_s": epochs * n_valid / elapsed},
        # no "train_cfg": readers.train_mfu counts a recurrent classifier
        # from it; this cell's utilization is mla_train_mfu
        "record": {
            "window_s": elapsed,
            "input_stall_s": stall_after["total_s"] - stall_before["total_s"],
            "model_cfg": mc,
            "latent": latent,
            "mla": latent,
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
            "latent": latent,
            "trace_start_cost_s": tracer.start_cost_s,
            "trace_stop_cost_s": tracer.stop_cost_s,
            **tail,
        },
    }


#: What of :func:`reference_checks` decides ``correct``.
REFERENCE_DECIDES = ("val_loss_ok", "val_pairs_ok", "val_terms_ok",
                     "first_loss_ok", "terms_ok", "pairs_ok", "grad_ok",
                     "change_ok", "bias_ok")


def reference_checks(ctx, trainer, final_params: list, dataset,
                     val_chunks, first_chunk, rng,
                     reference_kw: Optional[Dict] = None) -> Dict:
    """The comparisons with the plain reference, at the timed sizes.
    ``final_params`` is a one-element list that is emptied here, so that
    the parameters the run ended on are freed before fresh ones are
    made.  ``reference_kw`` makes the *reference* deliberately wrong (the
    ``wrong`` keywords of ``reference/mla_decoder.py``): the selftest
    and the builder's readings put those runs through the limits above,
    and each has to come out as not correct."""
    checks = validation_checks(ctx, trainer, final_params.pop(), dataset,
                               val_chunks, reference_kw)
    checks.update(first_step_checks(ctx, trainer, dataset, first_chunk, rng,
                                    reference_kw))
    return checks


def _term_diffs(got, want, layers) -> List[float]:
    """``|got - want|`` of each expert layer's balance term over the
    larger of the two (a reference that leaves the term out reads 1)."""
    return [abs(float(got[i]) - float(want[i]))
            / max(abs(float(want[i])), abs(float(got[i])), 1e-30)
            for i in layers]


def _reference_loss(trainer, reference_kw: Optional[Dict]):
    """``(params, batch) -> (the next-token loss, held pairs, load, the
    balance terms a layer)`` as the reference has them: the loss a mean
    over the batch's counted tokens, the terms a mean over its sequences,
    a sequence at a time."""
    import jax

    from benchmark.reference import mla_decoder as ref

    mc, kw = trainer.model_cfg, dict(reference_kw or {})
    ref_loss = jax.jit(
        lambda p, x, y, m: ref.loss_and_counts(p, x, y, m, mc, **kw))

    def reference(params, batch):
        total, count, pairs, load, terms, seqs = 0.0, 0, 0, 0, 0.0, 0
        for i in range(batch.x.shape[0]):
            n_i = int((np.asarray(batch.mask[i]) > 0).sum())
            if n_i == 0:  # a padded sequence: the program masks it
                continue
            loss_i, (pairs_i, load_i, terms_i) = ref_loss(
                params, batch.x[i], batch.y[i], batch.mask[i])
            total, count = total + float(loss_i) * n_i, count + n_i
            pairs = pairs + np.asarray(pairs_i)
            load = load + np.asarray(load_i)
            terms, seqs = terms + np.asarray(terms_i, np.float64), seqs + 1
        return total / max(count, 1), pairs, load, terms / max(seqs, 1)

    return reference


def validation_checks(ctx, trainer, params, dataset, val_chunks,
                      reference_kw: Optional[Dict] = None) -> Dict:
    """The validation sequences, a step at a time through the compiled
    eval step the window ran, against the reference on the same ids and
    the parameters the run ended on."""
    t0 = time.perf_counter()
    mc = trainer.model_cfg
    expert_layers = range(mc.first_dense_layers, len(mc.layer_layout))
    reference = _reference_loss(trainer, reference_kw)
    program, wanted, flips, term_diffs, terms = [], [], [], [], []
    for idx in val_chunks:
        for batch in trainer._chunk_batches(dataset, idx):
            totals = trainer._eval_step(params, trainer.zero_totals(), batch)
            want_loss, want_pairs, _, want_terms = reference(params, batch)
            program.append(float(totals.loss))
            wanted.append(want_loss)
            got_terms = np.asarray(totals.seq_aux_loss)
            terms.append([got_terms.tolist(), want_terms.tolist()])
            term_diffs.append(max(_term_diffs(got_terms, want_terms,
                                              expert_layers)))
            if (np.asarray(batch.mask) > 0).all():
                flips.append(max(flip_shares(
                    np.asarray(totals.expert_pairs)[mc.first_dense_layers:],
                    want_pairs[mc.first_dense_layers:])))
    err = [abs(a - b) for a, b in zip(program, wanted)]
    ctx.say({"reference_check_s": {"validation": time.perf_counter() - t0}})
    return {
        "val_loss_program": program,
        "val_loss_reference": wanted,
        "val_loss_abs_err": err,
        "val_loss_atol": VAL_LOSS_ATOL,
        "val_loss_ok": bool(err and max(err) <= VAL_LOSS_ATOL),
        "val_pair_flip_share": flips,
        "val_pair_flip_share_limit": VAL_PAIR_FLIP_SHARE,
        "val_pairs_ok": bool(flips and max(flips) <= VAL_PAIR_FLIP_SHARE),
        "val_terms_program_reference": terms,
        "val_term_rel_diff": term_diffs,
        "val_term_rel_diff_limit": VAL_TERM_REL_DIFF,
        "val_terms_ok": bool(term_diffs
                             and max(term_diffs) <= VAL_TERM_REL_DIFF),
    }


def _group(name: str, dense_blocks: int = 0) -> str:
    """A leaf's group; the MLP of a leading dense block is ``dense``
    (its three leaves have the routed experts' names)."""
    block, _, leaf = name.rpartition("/")
    if block.startswith("block_") and int(block[6:]) < dense_blocks \
            and leaf in ("w_gate", "w_up", "w_down"):
        return "dense"
    return GRAD_GROUP[leaf]


def first_step_checks(ctx, trainer, dataset, first_chunk, rng,
                      reference_kw: Optional[Dict] = None) -> Dict:
    """The first train step, from fresh parameters as ``fit()`` makes
    them, on the first training batch, through the compiled train step
    the window ran: its loss, balance terms and routing, the gradient it
    computed, the change it made to the parameters and the selection
    biases it left, against the reference's on the same batch and
    parameters.

    The step's gradient is read from what the step left behind: Adam's
    moments start at zero, so the first moment after one step is
    ``(1 - b1)`` times the gradient as the optimizer saw it, clipped."""
    import jax
    import optax

    from benchmark.reference import mla_decoder as ref

    t0 = time.perf_counter()
    mc, tc = trainer.model_cfg, trainer.train_cfg
    dense = mc.first_dense_layers
    expert_layers = range(dense, len(mc.layer_layout))
    init_rng, step_rng = jax.random.split(rng)
    batch = next(iter(trainer._chunk_batches(dataset, first_chunk)))
    full = bool((np.asarray(batch.mask) > 0).all())

    # the program's step; what it made goes to the host and the device
    # is cleared for the reference's float32 backward
    fresh = trainer.init_state(init_rng)
    before = jax.device_get(fresh.params)
    after, totals = trainer.single_step(fresh, batch, step_rng)
    got_loss = float(totals.loss)
    got_terms = np.asarray(totals.seq_aux_loss)
    got_pairs = np.asarray(totals.expert_pairs)
    got_load = np.asarray(totals.router_load)
    got_grads = jax.tree.map(
        lambda m: m / (1.0 - ADAM_B1),
        jax.device_get(optax.tree_utils.tree_get(after.opt_state, "mu")))
    got_change = jax.tree.map(lambda a, b: a - b,
                              jax.device_get(after.params), before)
    del fresh, after, totals
    t_program = time.perf_counter() - t0

    # the reference: its forward for the routing, its objective's
    # backward a block at a time (gradients to the host as they come),
    # its plain clip and Adam step and its bias rule on the host
    t0 = time.perf_counter()
    params = jax.device_put(before)
    del before
    _, want_pairs, want_load, _ = _reference_loss(
        trainer, reference_kw)(params, batch)
    _, want_loss, want_terms, grads = ref.objective_and_grads_by_layer(
        params, batch.x, batch.y, batch.mask, mc, **(reference_kw or {}))
    del params
    want_grads, want_change = ref.first_adam_step(
        grads, learning_rate=tc.learning_rate, clip=tc.clip)
    del grads
    want_bias = ref.bias_step(want_load, mc.moe_bias_rate)
    flips = flip_shares(got_pairs[dense:], want_pairs[dense:]) if full else []
    term_diffs = _term_diffs(got_terms, want_terms, expert_layers)

    whole = sum(float((g.astype(np.float64) ** 2).sum())
                for g in jax.tree.leaves(want_grads)) ** 0.5
    grad_diff, quiet_diff, change_ratio, bias_wrong = {}, {}, {}, {}
    over_whole, share_of_whole = {}, {}
    want = jax.tree_util.tree_leaves_with_path(want_grads)
    for (path, want_g), got_g, got_d, want_d in zip(
            want, jax.tree.leaves(got_grads), jax.tree.leaves(got_change),
            jax.tree.leaves(want_change)):
        name = _leaf_name(path)
        apart = float(np.linalg.norm((got_g - want_g).ravel()))
        over_whole[name] = apart / max(whole, 1e-30)
        share_of_whole[name] = float(
            np.linalg.norm(want_g.ravel())) / max(whole, 1e-30)
        if name.endswith("router_bias"):
            # no gradient; its change is the bias rule's: elements that
            # differ from the reference's (an expert whose load sits on
            # the mean, on one side here and on the other there)
            layer = int(name.split("/")[0].rsplit("_", 1)[1])
            quiet_diff[name] = over_whole[name]
            bias_wrong[name] = int((got_d != want_bias[layer]).sum())
            continue
        if share_of_whole[name] < QUIET_SHARE:
            quiet_diff[name] = over_whole[name]
            continue
        grad_diff[name] = apart / float(np.linalg.norm(want_g.ravel()))
        change_ratio[name] = float(
            np.abs(got_d).sum() / max(np.abs(want_d).sum(), 1e-30))
    del want_grads, want_change, want
    root_flip = max(flips, default=0.0) ** 0.5
    limits = dict(GRAD_REL_DIFF, **{
        group: max(GRAD_REL_DIFF[group], per_root * root_flip)
        for group, per_root in GRAD_PER_ROOT_FLIP.items()})
    # a group's worst leaf: its distance over its own norm
    worst = {group: 0.0 for group in GRAD_REL_DIFF}
    for name, value in grad_diff.items():
        group = _group(name, dense)
        worst[group] = max(worst[group], value)
    # a bias element may differ only where the loads differ (a flipped
    # pair moves an expert across the mean): at most the experts whose
    # load differs between program and reference
    load_differs = int((got_load != want_load).sum())
    ctx.say({"reference_check_s": {
        "first_step_program": t_program,
        "first_step_reference": time.perf_counter() - t0}})
    return {
        "first_loss_program": got_loss,
        "first_loss_reference": want_loss,
        "first_loss_abs_err": abs(got_loss - want_loss),
        "first_loss_atol": FIRST_LOSS_ATOL,
        "first_loss_ok": bool(abs(got_loss - want_loss) <= FIRST_LOSS_ATOL),
        "first_step_terms_program": got_terms.tolist(),
        "first_step_terms_reference": np.asarray(want_terms).tolist(),
        "term_rel_diff": term_diffs,
        "term_rel_diff_limit": TERM_REL_DIFF,
        "terms_ok": bool(term_diffs and max(term_diffs) <= TERM_REL_DIFF),
        "first_step_pairs_program": got_pairs.tolist(),
        "first_step_pairs_reference": np.asarray(want_pairs).tolist(),
        "pair_flip_share": flips,
        "pair_flip_share_limit": PAIR_FLIP_SHARE,
        "pairs_ok": bool(flips and max(flips) <= PAIR_FLIP_SHARE),
        "grad_rel_diff": grad_diff,
        "grad_diff_over_whole": over_whole,
        "grad_reference_share_of_whole": share_of_whole,
        "grad_rel_diff_worst": worst,
        "grad_rel_diff_limit": limits,
        "grad_quiet_leaves_over_whole": quiet_diff,
        "grad_quiet_limit": QUIET_ABS,
        "grad_ok": all(worst[g] <= limits[g] for g in limits) and all(
            v <= QUIET_ABS for v in quiet_diff.values()),
        "change_over_reference": change_ratio,
        "change_over_reference_band": list(CHANGE_BAND),
        "change_ok": all(CHANGE_BAND[0] <= v <= CHANGE_BAND[1]
                         for v in change_ratio.values()),
        "bias_elements_off_the_reference": bias_wrong,
        "router_load_elements_off_the_reference": load_differs,
        "bias_ok": bool(bias_wrong) and sum(bias_wrong.values())
        <= load_differs,
    }
