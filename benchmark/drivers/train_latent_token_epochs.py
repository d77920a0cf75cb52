"""Whole training epochs of a latent-attention token family (latent
attention, a hyper-connected residual stream of four lanes, a sigmoid
router with a shared expert, dense and expert layers in one model)
through ``Trainer.fit``, the placed-batch cache warm.

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
``setup_s``, at the timed sizes, on what the timed path produced (the
limits and the readings behind each are at :data:`VAL_LOSS_ATOL` ..
:data:`HC_SUM_BOUND`):

- the trainer's validation loss and per-expert pairs on each validation
  sequence, from the compiled eval step the window ran, against the
  plain float32 reference (``reference/latent_decoder.py``) on the same
  ids and final parameters;
- the first train step, from ``Trainer.single_step`` on fresh
  parameters, against the reference on the same batch and parameters:
  its loss; the pairs each held expert received; **its gradient**, read
  from Adam's first moment after the step, leaf by leaf against the
  reference's clipped gradient, the worst leaf of each group of
  :data:`GRAD_GROUP` (a leaf whose gradient is zero but for rounding is
  held to the whole gradient's size: :data:`QUIET_SHARE`); **its change
  to the parameters** against the reference's plain Adam step; **the
  selection bias after the step** equal to the reference's rule on the
  reference's own load over all the router's experts;
- every row and column sum of every residual mixing matrix within
  :data:`HC_SUM_BOUND` of one, in the window's passes (the program's
  ``hc_res_sum_error_max``) and in the reference;
- the held pairs of a train step in every pass of the window inside a
  stated band; ``moe_pairs_dropped_total`` 0; every loss finite, the
  training loss below the first epoch's, no recompile after warm-up
  (``run.py`` adds: no compile inside the window).

The record carries ``latent`` (sizes and the window's counts a train
step) and none of ``moe`` / ``sparse`` / ``hybrid``: the readers keyed
to those stay silent here, and this cell's utilization is
``latent_train_mfu`` over ``harness/latent_decoder_flops.py``.
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
#: PR 41): the program on twenty seeds (4100000001, ...0201, ...0302,
#: ...0777, ...0301, ...0402, ...9037, ...9074, ...9111, ...9999,
#: ...12053, ...12106, ...12159, and the last call's seven between
#: 977000333 and 4100020101: nineteen validation sequences, twenty
#: first steps), the deliberately wrong references at seeds 4100000301
#: and 4100000402.  Each limit lies between the largest reading the
#: program gave and the reading of the wrong reference nearest to it;
#: each deliberately wrong reference has to fail by one of the limits,
#: not by each.
#:
#: Absolute tolerance, in nats, on the validation sequence's loss (a
#: mean over 4,096 tokens, 9.5 to 9.7 nats after ten epochs).  The
#: program computes its products in bfloat16 with float32 accumulation
#: and **keeps the four lanes in bfloat16 between sublayers**; the
#: reference is float32 ``highest`` throughout.  The program: **2.02e-3
#: to 3.36e-3**, always above the reference.  That distance is the
#: stream's: a fresh block writes lanes 1..3 with weight 2 sigmoid(-6) =
#: 0.005, and a write of 0.005 y beside a lane of size one falls under
#: bfloat16's half step, so the program's lanes 1..3 keep the embedding
#: row where the reference's gather 1.5 % of every sublayer's output; the
#: reference with the stream rounded to bfloat16 at the entry and after
#: every write (``stream_as``) reads **6.0e-5** from the program, with
#: every product operand rounded to bfloat16 and a float32 stream 3.1e-3.
#: The reference with every product's operands in float8 e5m2: 1.87e-2;
#: a softmax router 1.15e-2; the shared expert left out 1.96e-1; a
#: Sinkhorn or a softmax rounded to bfloat16 3.3e-3, as the right one
#: (below).  The limit is 1.8x the program's largest and 1.9x under the
#: softmax router's.
VAL_LOSS_ATOL = 6e-3
#: The first train step's loss, on fresh parameters (10.4 nats whatever
#: the layers do, so it says less): the program **6e-5 to 4.8e-4**;
#: float8 operands 3.3e-3 and 9.5e-3 (two seeds), a softmax router 7.1e-4
#: and 2.9e-3, the shared expert left out 1.0e-3.  3.1x the program's
#: largest, 2.2x under float8's smallest; the softmax router is left to
#: the gradient's limits.
FIRST_LOSS_ATOL = 1.5e-3
#: Share of a layer's held (token, expert) pairs that may sit on another
#: held expert, or on none, than in the reference's routing (half the
#: sum over the held experts of the absolute difference in pairs, over
#: the pairs held; the largest over the expert layers).  The router's
#: scores come from bfloat16 products, and where a token's fourth and
#: fifth largest differ by less than that rounding the top-4 flips; with
#: Zipf ids the flips come in lumps (``train_token_epochs.py`` has the
#: arithmetic: one tie of each of the two most frequent ids is 9 %).
#: Readings: the program 0.15 to 0.95 % on twenty first steps and 0.43 to
#: 1.78 % on nineteen validation sequences; float8 3.3 and 6.9 % (first
#: step), 11.5 % (validation); the shared expert left out 14.9 % and
#: 24.7 %; a softmax router 1.0 to 4.3 % on a first step (at a zero bias
#: both routers rank the experts alike, and its gates change what the
#: deeper layers see) and **79 %** on the validation sequence (a trained
#: bias weighs differently against softmax scores).
PAIR_FLIP_SHARE = 0.10
VAL_PAIR_FLIP_SHARE = 0.10
#: The first train step's gradient against the reference's, leaf by
#: leaf, both clipped to the configuration's global norm, the worst leaf
#: of each group against the group's limit.  ``latent`` (the five
#: attention products, the two latent norms), ``dense`` (what every
#: token reaches the same way: the dense MLP, the shared expert,
#: embedding, head, the layers' norms) and ``routed`` (what a token
#: reaches through its top-4): ``|g - g_ref| / |g_ref|``, Frobenius
#: norms.  ``mixing`` (the hyper-connections' eighteen leaves a block):
#: ``|g - g_ref|`` over the norm of the WHOLE gradient, because most of
#: those leaves have next to no gradient of their own at a fresh model
#: (below) and the few numbers of a gain or an offset (1, 4 or 16) are
#: sums over 4,096 tokens that mostly cancel: one gain read 3.3 times its
#: own size and 1.9e-4 of the whole, and a leaf that is loud on one seed
#: is quiet on the next (one offset's reference gradient fell to 3.8e-6
#: of the whole on one seed, where the program's stood 2.6e-5 from it).
#: Readings, program (twenty first steps) / float8 operands (two) / a
#: softmax router (two): latent **2.4 to 3.7 %** / 34 % / 17 and 28 %;
#: dense **2.1 to 3.1 %** / 25 % / 17 and 22 %; routed **14 to 28 %** (a
#: router every time; the held experts' matrices 5 to 21 %, the dense
#: block's MLP 2.2 to 2.9 %: a held expert sees 256 pairs a step, so one
#: flipped pair is a larger share of its gradient than in the other two
#: expert cells) / 75 and 79 % / 94 and 95 %; mixing **7.3e-5 to
#: 2.4e-4** of the whole / 2.4e-3 / 1.1e-3.  A Sinkhorn or a softmax
#: rounded to bfloat16, or a
#: Sinkhorn turn fewer, read as the right reference does in every group
#: (latent 2.94 / 2.95 / 2.94 % against 2.94 %): from a fresh model the
#: residual mixing is the identity to 6e-6 after its first turn and the
#: scores are near zero, so those three move nothing a bfloat16 program
#: can be told from (PERF.md section 7); the first shows in
#: :data:`HC_SUM_BOUND`.  The latent and dense limits are 2.1x / 2.4x the
#: program's largest and 2.1x / 2.3x under the softmax router's smallest;
#: the routed 1.6x and 1.7x under float8's; the mixing 2.1x and 2.2x.
GRAD_GROUP = {
    "wq_a": "latent", "wq_b": "latent", "wkv_a": "latent",
    "wkv_b": "latent", "wo": "latent", "q_norm": "latent",
    "kv_norm": "latent",
    "embed": "dense", "head": "dense", "ln_final": "dense",
    "ln_attn": "dense", "ln_mlp": "dense", "ws_gate": "dense",
    "ws_up": "dense", "ws_down": "dense",
    "router": "routed", "ln_moe": "routed", "w_gate": "routed",
    "w_up": "routed", "w_down": "routed",
}
GRAD_REL_DIFF = {"latent": 0.08, "dense": 0.075, "routed": 0.45,
                 "mixing": 5e-4}
#: As ``train_token_epochs.py``: the routed limit is the larger of the
#: one above and this times the root of the first step's largest
#: pair-flip share (the program's routed reading lies at 2.1 to 3.8
#: times that root; it comes into play over flips of 0.81 %).
GRAD_ROUTED_PER_ROOT_FLIP = 5.0
#: A leaf whose reference gradient is under this share of the whole
#: gradient's norm is *quiet*: zero but for rounding.  There are such
#: leaves by construction: the first sublayer reads four equal lanes and
#: the norm behind it forgets the size of the mix, so its read's
#: parameters have no gradient; the last sublayer's remix has columns
#: that sum to one and the exit sums the lanes, so its parameters have
#: none either; the selection biases have none; and at a fresh model
#: every read and remix is saturated (59 to 64 of the 189 leaves are
#: quiet on the chip, every one a ``pre`` or ``res`` leaf of the mixing
#: or a bias; the largest share among them 8.6e-6, the smallest among the
#: rest 1.2e-5).  A quiet leaf is left out of the change's band, where
#: Adam turns its rounding into steps.  A quiet leaf of the mixing is
#: held with the mixing's other leaves (above); a quiet leaf elsewhere
#: (the selection biases: no gradient here, none there) is held to
#: :data:`QUIET_ABS` of the whole gradient's norm and reads exactly 0.
QUIET_SHARE = 1e-5
QUIET_ABS = 3e-6
#: ``sum |change| / sum |the reference's change|`` of each leaf that is
#: not quiet over the first step (``train_token_epochs.py`` has the
#: arithmetic: a leaf the step never updates reads 0, a rate applied
#: twice 2).  The program: **0.9655 to 1.036** over the leaves of 64
#: numbers or more (the norm scales at the low end: they start at 1.0,
#: where float32 rounds a step of 1e-6 to 8 or 17 units in the last
#: place); the shared expert left out 1.288.  A gain or an offset of the
#: mixing is 1, 4 or 16 numbers, some with a clipped gradient beside
#: Adam's eps of 1e-8, where a step shows the gradient's rounding: one
#: offset read **1.262** (and 1.290 against the softmax router's
#: reference), another **0.622**, over twenty first steps; such leaves get
#: the wider band, which still tells a leaf never updated or updated
#: twice.
CHANGE_BAND = (0.94, 1.06)
CHANGE_BAND_FEW = (0.25, 1.75)
FEW_NUMBERS = 64
#: Held pairs of a train step, summed over the expert layers, in every
#: training pass of the window, as a multiple of what an even router
#: gives (seq_len x top-4 x held / all experts, 2,048 a layer): readings
#: over 144 passes 0.92 to 1.15 of the expected 8,192 (layers alone 0.84
#: to 1.33).  No expert layer under HELD_PAIRS_LAYER_FLOOR of its
#: expected pairs.
HELD_PAIRS_BAND = (0.6, 1.6)
HELD_PAIRS_LAYER_FLOOR = 0.25
#: The largest distance of a row or column sum of a residual mixing
#: matrix from one, over the window's passes (program) and on the first
#: batch (reference).  20 float32 turns from a fresh block's logits
#: leave ``hc_eps`` and rounding: the program **3.4e-6 to 4.2e-6** (144
#: passes and twenty first steps), the reference 3.3e-6 to 4.1e-6; a
#: Sinkhorn whose turns are rounded to bfloat16 **2.06e-5** (the
#: identity survives the rounding, what is off it does not).  2.4x the
#: program's largest, 2.1x under that run's.
HC_SUM_BOUND = 1e-5


def require_latent_program() -> None:
    """Fail at once, with a message, on a program without the layer."""
    import dataclasses

    from fmda_tpu.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    if not {"q_lora_rank", "kv_lora_rank", "hc_streams",
            "moe_shared_experts", "first_dense_layers"} <= fields:
        raise SystemExit(
            "this program has no latent attention, no hyper-connected "
            "residual and no shared expert (ModelConfig lacks q_lora_rank "
            "/ kv_lora_rank / hc_streams / moe_shared_experts / "
            "first_dense_layers): the cell cannot run on it")


def sum_error_gauges(reg, n_layers: int) -> List[float]:
    """``hc_res_sum_error_max`` of the last training pass, a layer."""
    return [reg.gauge("hc_res_sum_error_max", layer=str(i),
                      phase="train").value for i in range(n_layers)]


def run(ctx) -> Dict:
    t0 = time.perf_counter()
    require_latent_program()
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
    train_losses, val_losses, held_by_pass, sum_errors = [], [], [], []
    while clock() - t_start < seconds:
        with span("bench_epoch"):
            state, h, _ = trainer.fit(source, rng=rng, epochs=1,
                                      initial_state=state, dataset=dataset)
        train_losses.append(h["train"][0].loss)
        val_losses.append(h["val"][0].loss)
        held_by_pass.append(moe_counters(reg, n_layers)["held"])
        sum_errors.append(max(sum_error_gauges(reg, n_layers)))
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
        "hc_sum_error_by_pass": sum_errors,
        "hc_sum_bound": HC_SUM_BOUND,
        "hc_sums_ok": bool(sum_errors) and max(sum_errors) <= HC_SUM_BOUND
        and checks["hc_sum_error_reference"] <= HC_SUM_BOUND,
        "unexpected_recompiles": recompiles,
        "compiles_by_the_comparisons": (
            trainer.unexpected_recompiles - recompiles),
        "compile_counts": trainer.compile_counts,
        "train_losses": train_losses,
        "val_losses": val_losses,
    })
    correct = bool(
        all(checks[name] for name in REFERENCE_DECIDES)
        and checks["held_pairs_ok"] and checks["hc_sums_ok"]
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
        "hc_sum_error_max": max(sum_errors) if sum_errors else None,
    }
    return {
        "attempted": epochs * steps_per_epoch,
        "failed": bad_epochs * steps_per_epoch,
        "correct": correct,
        "checks": checks,
        "end_to_end": {
            "train_samples_per_s": epochs * n_valid / elapsed},
        # no "train_cfg": readers.train_mfu counts a recurrent classifier
        # from it; this cell's utilization is latent_train_mfu
        "record": {
            "window_s": elapsed,
            "input_stall_s": stall_after["total_s"] - stall_before["total_s"],
            "model_cfg": mc,
            "latent": latent,
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
REFERENCE_DECIDES = ("val_loss_ok", "val_pairs_ok", "first_loss_ok",
                     "pairs_ok", "grad_ok", "change_ok", "bias_ok")


def reference_checks(ctx, trainer, final_params: list, dataset,
                     val_chunks, first_chunk, rng,
                     reference_kw: Optional[Dict] = None) -> Dict:
    """The comparisons with the plain reference, at the timed sizes.
    ``final_params`` is a one-element list that is emptied here, so that
    the parameters the run ended on are freed before fresh ones are
    made.  ``reference_kw`` makes the *reference* deliberately wrong (the
    ``wrong`` keywords of ``reference/latent_decoder.py``): the selftest
    and the builder's readings put those runs through the limits above,
    and each has to come out as not correct."""
    checks = validation_checks(ctx, trainer, final_params.pop(), dataset,
                               val_chunks, reference_kw)
    checks.update(first_step_checks(ctx, trainer, dataset, first_chunk, rng,
                                    reference_kw))
    return checks


def _reference_loss(trainer, reference_kw: Optional[Dict]):
    """``(params, batch) -> (the step's loss, held pairs, load, the
    mixing matrices' largest sum error)`` as the reference has them: a
    mean over the batch's counted tokens, a sequence at a time."""
    import jax

    from benchmark.reference import latent_decoder as ref

    mc, kw = trainer.model_cfg, dict(reference_kw or {})
    ref_loss = jax.jit(
        lambda p, x, y, m: ref.loss_and_counts(p, x, y, m, mc, **kw))

    def reference(params, batch):
        total, count, pairs, load, worst = 0.0, 0, 0, 0, 0.0
        for i in range(batch.x.shape[0]):
            n_i = int((np.asarray(batch.mask[i]) > 0).sum())
            if n_i == 0:  # a padded sequence: the program masks it
                continue
            loss_i, (pairs_i, load_i, err_i) = ref_loss(
                params, batch.x[i], batch.y[i], batch.mask[i])
            total, count = total + float(loss_i) * n_i, count + n_i
            pairs = pairs + np.asarray(pairs_i)
            load = load + np.asarray(load_i)
            worst = max(worst, float(np.max(err_i)))
        return total / max(count, 1), pairs, load, worst

    return reference


def validation_checks(ctx, trainer, params, dataset, val_chunks,
                      reference_kw: Optional[Dict] = None) -> Dict:
    """The validation sequences, a step at a time through the compiled
    eval step the window ran, against the reference on the same ids and
    the parameters the run ended on."""
    t0 = time.perf_counter()
    mc = trainer.model_cfg
    reference = _reference_loss(trainer, reference_kw)
    program, wanted, flips = [], [], []
    for idx in val_chunks:
        for batch in trainer._chunk_batches(dataset, idx):
            totals = trainer._eval_step(params, trainer.zero_totals(), batch)
            want_loss, want_pairs, _, _ = reference(params, batch)
            program.append(float(totals.loss))
            wanted.append(want_loss)
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
    }


def _group(name: str, dense_blocks: int = 0) -> str:
    """A leaf's group; the MLP of a leading dense block is ``dense``
    (its three leaves have the routed experts' names)."""
    block, _, leaf = name.rpartition("/")
    if leaf.startswith("hc_"):
        return "mixing"
    if block.startswith("block_") and int(block[6:]) < dense_blocks \
            and leaf in ("w_gate", "w_up", "w_down"):
        return "dense"
    return GRAD_GROUP[leaf]


def first_step_checks(ctx, trainer, dataset, first_chunk, rng,
                      reference_kw: Optional[Dict] = None) -> Dict:
    """The first train step, from fresh parameters as ``fit()`` makes
    them, on the first training batch, through the compiled train step
    the window ran: its loss and routing, the gradient it computed, the
    change it made to the parameters and the selection biases it left,
    against the reference's on the same batch and parameters.

    The step's gradient is read from what the step left behind: Adam's
    moments start at zero, so the first moment after one step is
    ``(1 - b1)`` times the gradient as the optimizer saw it, clipped."""
    import jax
    import optax

    from benchmark.reference import latent_decoder as ref

    t0 = time.perf_counter()
    mc, tc = trainer.model_cfg, trainer.train_cfg
    dense = mc.first_dense_layers
    init_rng, step_rng = jax.random.split(rng)
    batch = next(iter(trainer._chunk_batches(dataset, first_chunk)))
    full = bool((np.asarray(batch.mask) > 0).all())

    # the program's step; what it made goes to the host and the device
    # is cleared for the reference's float32 backward
    fresh = trainer.init_state(init_rng)
    before = jax.device_get(fresh.params)
    after, totals = trainer.single_step(fresh, batch, step_rng)
    got_loss = float(totals.loss)
    got_pairs = np.asarray(totals.expert_pairs)
    got_load = np.asarray(totals.router_load)
    got_sum_error = float(np.max(np.asarray(totals.hc_sum_error))) \
        if totals.hc_sum_error is not None else 0.0
    got_grads = jax.tree.map(
        lambda m: m / (1.0 - ADAM_B1),
        jax.device_get(optax.tree_utils.tree_get(after.opt_state, "mu")))
    got_change = jax.tree.map(lambda a, b: a - b,
                              jax.device_get(after.params), before)
    del fresh, after, totals
    t_program = time.perf_counter() - t0

    # the reference: its forward for the loss and the routing, its
    # backward a block at a time (gradients to the host as they come),
    # its plain clip and Adam step and its bias rule on the host
    t0 = time.perf_counter()
    params = jax.device_put(before)
    del before
    want_loss, want_pairs, want_load, want_sum_error = _reference_loss(
        trainer, reference_kw)(params, batch)
    _, grads = ref.loss_and_grads_by_layer(
        params, batch.x, batch.y, batch.mask, mc, **(reference_kw or {}))
    del params
    want_grads, want_change = ref.first_adam_step(
        grads, learning_rate=tc.learning_rate, clip=tc.clip)
    del grads
    want_bias = ref.bias_step(want_load, mc.moe_bias_rate)
    flips = flip_shares(got_pairs[dense:], want_pairs[dense:]) if full else []

    whole = sum(float((g.astype(np.float64) ** 2).sum())
                for g in jax.tree.leaves(want_grads)) ** 0.5
    grad_diff, quiet_diff, change_ratio, bias_wrong = {}, {}, {}, {}
    over_whole, share_of_whole, few, mixing_diff = {}, {}, set(), {}
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
            quiet_diff[name] = apart / max(whole, 1e-30)
            bias_wrong[name] = int((got_d != want_bias[layer]).sum())
            continue
        if _group(name, dense) == "mixing":
            mixing_diff[name] = over_whole[name]
        if share_of_whole[name] < QUIET_SHARE:
            quiet_diff[name] = over_whole[name]
            continue
        grad_diff[name] = apart / float(np.linalg.norm(want_g.ravel()))
        change_ratio[name] = float(
            np.abs(got_d).sum() / max(np.abs(want_d).sum(), 1e-30))
        if want_g.size < FEW_NUMBERS:
            few.add(name)
    del want_grads, want_change, want
    routed_limit = max(GRAD_REL_DIFF["routed"],
                       GRAD_ROUTED_PER_ROOT_FLIP * max(flips, default=0.0)
                       ** 0.5)
    # a group's worst leaf: its distance over its own norm, but for the
    # mixing's leaves, quiet or not, whose distance is taken over the
    # whole gradient's
    worst = {group: 0.0 for group in GRAD_REL_DIFF}
    for name, value in grad_diff.items():
        group = _group(name, dense)
        if group != "mixing":
            worst[group] = max(worst[group], value)
    worst["mixing"] = max(mixing_diff.values(), default=0.0)
    limits = dict(GRAD_REL_DIFF, routed=routed_limit)
    quiet_elsewhere = [v for name, v in quiet_diff.items()
                       if name not in mixing_diff]
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
            v <= QUIET_ABS for v in quiet_elsewhere),
        "change_over_reference": change_ratio,
        "change_over_reference_band": list(CHANGE_BAND),
        "change_over_reference_band_few_numbers": list(CHANGE_BAND_FEW),
        "change_ok": all(
            band[0] <= v <= band[1] for name, v in change_ratio.items()
            for band in [CHANGE_BAND_FEW if name in few else CHANGE_BAND]),
        "bias_elements_off_the_reference": bias_wrong,
        "router_load_elements_off_the_reference": load_differs,
        "bias_ok": bool(bias_wrong) and sum(bias_wrong.values())
        <= load_differs,
        "hc_sum_error_first_step": got_sum_error,
        "hc_sum_error_reference": want_sum_error,
    }
