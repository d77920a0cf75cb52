"""Whole training epochs of a token family whose layers mix a gated delta
rule (one decay a head, a correction that may overshoot, keys and values
of different widths) with unrotated full attention under whole-width q/k
norms, a dense MLP in every layer and the block's norms on the
sublayers' outputs (Olmo-Hybrid-7B) through ``Trainer.fit``, the
placed-batch cache warm.

The run has the shape of ``drivers/train_hybrid_token_epochs.py`` (the
same corpus, the same split, no routing) and is built from the accepted
token drivers' parts (the seeded token corpus,
:class:`~benchmark.drivers.train_token_epochs.OpeningTracer`,
:class:`~benchmark.drivers.train_token_epochs.DeviceStepCount`,
``count_split``, ``compiled_step_bytes``): set-up makes the corpus from
``--seed``, runs ``setup_epochs`` epochs (compile and placement, then the
exact call the window repeats); the window runs ``fit(epochs=1,
initial_state=..., dataset=...)`` until ``--seconds`` have passed;
``train_samples_per_s`` is the valid sequences of the whole epochs over
their wall time.  A traced run then traces ``trace_steps`` train steps of
one training pass, counted where the device finishes them.

``correct`` is decided after all that, outside the window and outside
``setup_s``, at the timed sizes, on what the timed path produced (the
limits and the readings behind each are at :data:`VAL_LOSS_ATOL` ..
:data:`CHANGE_BAND`):

- the trainer's validation loss on each validation sequence, from the
  compiled eval step the window ran, against the plain float32 reference
  (``reference/gdn_decoder.py``, whose recurrence is stepwise) on the
  same ids and final parameters;
- the first train step, from ``Trainer.single_step`` on fresh
  parameters, against the reference on the same batch and parameters:
  its loss; **its gradient**, read from Adam's first moment after the
  step, leaf by leaf against the reference's clipped gradient, the worst
  leaf of each group of :func:`_group` (the delta-rule mixers' leaves in
  three groups of their own: the query and key products, the other wide
  products, and the small leaves, ``a_log``, ``dt_bias``, ``wa``, ``wb``
  among them); **its change to the
  parameters** against the reference's plain Adam step;
- the walks took every position of every sequence of the window's
  training passes (``gdn_positions_total``, ``gdn_chunks_total``);
- every loss finite, the training loss below the first epoch's, no
  recompile after warm-up (``run.py`` adds: no compile inside the
  window).

A run that is not correct says which comparisons failed and by how much
on standard error (``failed_comparisons``: the reading beside its limit;
the result line is ``run.py``'s and carries ``correct`` alone).

The record carries ``gdn`` (sizes and the window's counts a train step:
what this cell's own ``gdn_*`` readers ask for) and none of ``moe`` /
``sparse`` / ``hybrid`` / ``latent`` / ``mla`` / ``kda``: the readers
keyed to those stay silent here; the scope readers
(``attention_dev_share``, ``dense_mlp_dev_share``, ``lm_head_dev_share``)
need no record and read here.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from benchmark.drivers.train_hybrid_token_epochs import compiled_step_bytes
from benchmark.drivers.train_token_epochs import (
    ADAM_B1, DeviceStepCount, OpeningTracer, _leaf_name, count_split)
from benchmark.harness.device import memory_peak_bytes
from benchmark.harness.token_corpus import make_token_stream
from benchmark.harness.tracing import StepSlice, span

END_TO_END = {"train_samples_per_s": "samples/s"}

#: ``layer_layout``'s value for a gated-delta-rule layer.
GDN_LAYOUT = 6

#: The limits below and the readings behind them.  All readings are the
#: builder's on the chip at the published widths (PERF.md section 6,
#: PR 54): the program on seventeen seeds (call 1: 4900054002 traced,
#: 4900054003, 3900054004, 2900054005, 1900054006, 900054007; call 2, from
#: the ``git archive`` of the tree: 4900054102, 3900054103, 2900054104,
#: 1900054105, 900054106, 4294054107, 4900054108 traced and 77, a seed
#: never used while the change was written; call 3, from the tree with
#: the limits as they stand: 4900054201, 3900054202, 1234567, which read
#: inside every range below; first steps and validation sequences
#: alike); the deliberately wrong references at seed 4900054003 after
#: four epochs, the right one beside them, and the three nearest the
#: limits again at seed 4900054201 under the groups as they stand.  Each limit stands at 1.5
#: times or more over the largest reading the program gave and under the
#: reading of the wrong reference nearest to it that the limit is there
#: to refuse; each deliberately wrong reference has to fail by one of the
#: limits, not by each.  One wrong reference fails by none, and no limit
#: could make it without refusing the program on some seed (PERF.md
#: section 7): **the delta rule's state and log-decays rounded to
#: bfloat16** moves the validation loss by 2.1e-4 and 4.9e-4 (two seeds)
#: where the program's own distance runs to 4.4e-4, and the q/k leaves'
#: gradient by 12.5 % and 13.0 % where the program's runs from 8.7 to
#: 15.8 % (12.8 % on the second of those steps).
#:
#: Absolute tolerance, in nats, on the validation sequence's loss (a
#: mean over 8,192 tokens, 7.7 nats after nine epochs).  The program
#: computes its products in bfloat16 with float32 accumulation, its walk
#: in chunks of 64, and keeps the stream in bfloat16 between sublayers;
#: the reference is float32 ``highest`` throughout and its recurrence
#: stepwise.  The program: **2.4e-6 to 4.4e-4**; rotary in the attention
#: layer 2.75e-3 and 1.39e-3 (two seeds), the reference with every
#: product's operands in float8 e5m2 9.4e-3 and 2.9e-4 (after nine epochs
#: the loss says little of the products' precision on some seeds: float8
#: and rotary fail by the first step's loss and gradient), ``b =
#: sigmoid`` 3.0e-2, the gate under sigmoid 4.9e-2,
#: the correction left out 5.0e-2, the decay left out 0.20, the q/k
#: RMSNorm left out 0.77, the block pre-norm 1.8, q and k not normalised
#: nan (with every operand in bfloat16 2.4e-6: the program's distance is
#: its products').  The limit is 3.4x the program's largest and 20x
#: under the nearest wrong run that only this limit and the gradient's
#: refuse (``b = sigmoid``).
VAL_LOSS_ATOL = 1.5e-3
#: The first train step's loss against the reference's, on fresh
#: parameters (10.2 nats whatever the layers do, so it says less): the
#: program **2.5e-5 to 6.2e-4**; rotary 4.1e-3 and 1.2e-3, the
#: correction left out 7.3e-3, float8 operands **1.04e-2 and 1.30e-2**,
#: the gate 1.3e-2 (``b = sigmoid``
#: 6.5e-4 and the decay left out 3.6e-4 are inside and fail by the
#: validation loss and the gradient).  3.2x the program's largest and 5x
#: under float8's.
FIRST_LOSS_ATOL = 2.0e-3
#: The first train step's gradient against the reference's, leaf by
#: leaf, both clipped to the configuration's global norm, ``|g - g_ref|
#: / |g_ref|`` in Frobenius norms, the worst leaf of each group against
#: the group's limit.  ``gdn_qk``: a delta-rule mixer's query and key
#: products and their taps (what reaches the walk through the L2 norm);
#: ``gdn``: its value, gate and output products; ``gdn_small``: its small
#: leaves (the decay's and the correction's weights ``wa``, ``wb``,
#: ``a_log``, ``dt_bias``, the values' taps, the head norm);
#: ``attention``: the attention layer's four products and its two
#: whole-width norms; ``mlp``: the dense MLP's three; ``norms``: the
#: blocks' output norms and the final norm; ``embed``: embedding and
#: head.  Readings, program / float8 operands / the nearest other wrong
#: run: gdn_qk **8.7 to 15.8 %** / 93 and 100 % / rotary 21 and 23 %,
#: the state in bfloat16 12.5 and 13.0 % (both inside), ``b = sigmoid``
#: 111 %; gdn **1.5 to 5.8 %** / 63 % / rotary 18.5 %; gdn_small **2.4 to
#: 6.6 %** / 92 % / rotary 21 %; attention **1.7 to 2.0 %** / 39 and
#: 40 % / rotary 182 and 178 %; mlp **3.6 to 5.1 %** / 57 % / rotary
#: 17 %; norms **3.2 to 4.6 %** / 53 % / rotary 16 %; embed **4.0 to
#: 5.7 %** / 60 % / rotary 18 % (the state in bfloat16 reads 5.1, 6.2,
#: 2.0, 4.6, 4.1 and 4.9 % in those six groups: inside, beside the
#: program's 4.9, 5.7, 1.8, 4.4, 3.9, 4.7 on the same step).  The
#: distance grows from the last layer to the first (the attention layer's
#: leaves 0.4 to 2.0 %, the first layer's 4 to 7 %) and is largest on the
#: query and key leaves of the deepest delta-rule layers; the same
#: program in float32 reads 3e-4 on every leaf (tests/test_gdn_decoder.py)
#: and the reference with every operand in bfloat16 reads as the right
#: one does: it is the products' rounding (PERF.md section 6, PR 54, has
#: what was tried to place it).  Each limit is 2.2x to 2.5x the largest
#: of the program's seventeen readings and 2.7x (gdn_qk) to 7.8x under
#: float8's; rotary reads over six of the seven.
GRAD_GROUP = {
    "embed": "embed", "head": "embed",
    "ln_final": "norms", "ln_attn": "norms", "ln_mlp": "norms",
    "w_gate": "mlp", "w_up": "mlp", "w_down": "mlp",
}
#: ... and the mixers' leaves, by the kind of the block they are in.
GDN_LEAVES = {
    "wq": "gdn_qk", "wk": "gdn_qk", "conv_q": "gdn_qk", "conv_k": "gdn_qk",
    "wv": "gdn", "wg": "gdn", "wo": "gdn",
    "wa": "gdn_small", "wb": "gdn_small", "a_log": "gdn_small",
    "dt_bias": "gdn_small", "conv_v": "gdn_small", "o_norm": "gdn_small",
}
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
GRAD_REL_DIFF = {"gdn_qk": 0.35, "gdn": 0.14, "gdn_small": 0.16,
                 "attention": 0.05, "mlp": 0.125, "norms": 0.115,
                 "embed": 0.14}
#: ``sum |change| / sum |the reference's change|`` of each leaf over the
#: first step (``train_hybrid_token_epochs.py`` has the arithmetic: a
#: leaf the step never updates reads 0, a rate applied twice 2).  The
#: program: **0.9951 to 1.0038** over all 68 leaves (the low end a
#: ``dt_bias`` or ``a_log``, 15 numbers of size 1 to 8; the wide matrices
#: 0.9995 to 1.0018).  The wrong references nearest to 1: the block
#: pre-norm 0.990 to 1.022, float8 operands 0.9988 to 1.0047 (inside);
#: the decay left out 1e30 (``a_log``'s gradient is 0 in that
#: reference).  The band is 8x the program's widest reading; no wrong
#: reference is left to this check alone.
CHANGE_BAND = (0.96, 1.04)


def require_gdn_program() -> None:
    """Fail at once, with a message, on a program without the layer."""
    import dataclasses

    from fmda_tpu.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    if not {"gdn_heads", "gdn_key_dim", "gdn_value_dim", "gdn_beta_scale",
            "post_norm", "qk_norm_whole"} <= fields:
        raise SystemExit(
            "this program has no gated-delta-rule layer (one decay a head, "
            "b in 0..2, keys and values of different widths) under output "
            "norms (ModelConfig lacks gdn_heads / gdn_key_dim / "
            "gdn_value_dim / gdn_beta_scale / post_norm / qk_norm_whole): "
            "the cell cannot run on it")


def gdn_counters(reg, layers: List[int]) -> Dict[str, List[float]]:
    """The training passes' walk counters so far, a delta-rule layer."""
    labels = [dict(layer=str(i), phase="train") for i in layers]
    return {
        "positions": [reg.counter("gdn_positions_total", **lb).value
                      for lb in labels],
        "chunks": [reg.counter("gdn_chunks_total", **lb).value
                   for lb in labels],
        "log_decay_absmax": [reg.gauge("gdn_log_decay_absmax", **lb).value
                             for lb in labels],
        "beta_max": [reg.gauge("gdn_beta_max", **lb).value for lb in labels],
    }


def run(ctx) -> Dict:
    t0 = time.perf_counter()
    require_gdn_program()
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
    gdn_layers = [i for i, v in enumerate(mc.layer_layout)
                  if v == GDN_LAYOUT]
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
    gdn_before = gdn_counters(reg, gdn_layers)
    t_start = clock()
    epochs = 0
    train_losses, val_losses, decay_by_pass, beta_by_pass = [], [], [], []
    while clock() - t_start < seconds:
        with span("bench_epoch"):
            state, h, _ = trainer.fit(source, rng=rng, epochs=1,
                                      initial_state=state, dataset=dataset)
        train_losses.append(h["train"][0].loss)
        val_losses.append(h["val"][0].loss)
        seen = gdn_counters(reg, gdn_layers)
        decay_by_pass.append(seen["log_decay_absmax"])
        beta_by_pass.append(seen["beta_max"])
        epochs += 1
    elapsed = clock() - t_start
    peak_at_window_end = memory_peak_bytes(jax.devices())
    stall_after = stall.snapshot()
    gdn_after = gdn_counters(reg, gdn_layers)
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
    # every position of every training sequence of the window, in whole
    # chunks, through every delta-rule layer
    window_steps = max(epochs * train_steps, 1)
    positions = [(a - b) / window_steps for a, b in zip(
        gdn_after["positions"], gdn_before["positions"])]
    chunks = [(a - b) / window_steps for a, b in zip(
        gdn_after["chunks"], gdn_before["chunks"])]
    checks.update({
        "losses_finite": all(finite),
        "loss_fell": bool(train_losses
                          and train_losses[-1] < hist0["train"][0].loss),
        "gdn_positions_per_train_step": positions,
        "gdn_chunks_per_train_step": chunks,
        "gdn_log_decay_absmax_by_pass": decay_by_pass,
        "gdn_beta_max_by_pass": beta_by_pass,
        "walk_ok": bool(epochs and positions) and all(
            p == seq * tc.batch_size for p in positions) and all(
            c == -(-seq // mc.gdn_chunk) * tc.batch_size for c in chunks),
        "unexpected_recompiles": recompiles,
        "compiles_by_the_comparisons": (
            trainer.unexpected_recompiles - recompiles),
        "compile_counts": trainer.compile_counts,
        "train_losses": train_losses,
        "val_losses": val_losses,
    })
    decides = {name: bool(checks[name]) for name in REFERENCE_DECIDES}
    decides.update(walk_ok=checks["walk_ok"], losses_finite=all(finite),
                   loss_fell=checks["loss_fell"],
                   no_recompile=recompiles == 0)
    correct = all(decides.values())
    if not correct:
        ctx.say({"failed_comparisons": {
            name: checks.get("readings_beside_limits", {}).get(name)
            for name, ok in decides.items() if not ok}})

    gdn = {
        "seq_len": seq,
        "sequences_per_step": tc.batch_size,
        "scan_positions_per_train_step": positions,
        "scan_chunks_per_train_step": chunks,
        "log_decay_absmax_last_pass": (
            decay_by_pass[-1] if decay_by_pass else None),
        "beta_max_last_pass": beta_by_pass[-1] if beta_by_pass else None,
    }
    return {
        "attempted": epochs * steps_per_epoch,
        "failed": bad_epochs * steps_per_epoch,
        "correct": correct,
        "checks": checks,
        "end_to_end": {
            "train_samples_per_s": epochs * n_valid / elapsed},
        # no "train_cfg": readers.train_mfu counts a recurrent classifier
        # from it; this cell's utilization is gdn_train_mfu
        "record": {
            "window_s": elapsed,
            "input_stall_s": stall_after["total_s"] - stall_before["total_s"],
            "model_cfg": mc,
            "gdn": gdn,
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
            "gdn": gdn,
            "trace_start_cost_s": tracer.start_cost_s,
            "trace_stop_cost_s": tracer.stop_cost_s,
            **tail,
        },
    }


#: What of :func:`reference_checks` decides ``correct``.
REFERENCE_DECIDES = ("val_loss_ok", "first_loss_ok", "grad_ok", "change_ok")


def reference_checks(ctx, trainer, final_params: list, dataset,
                     val_chunks, first_chunk, rng,
                     reference_kw: Optional[Dict] = None) -> Dict:
    """The comparisons with the plain reference, at the timed sizes.
    ``final_params`` is a one-element list that is emptied here, so that
    the parameters the run ended on are freed before fresh ones are
    made.  ``reference_kw`` makes the *reference* deliberately wrong (the
    ``wrong`` keywords of ``reference/gdn_decoder.py``): the selftest
    and the builder's readings put those runs through the limits above,
    and each has to come out as not correct.  ``readings_beside_limits``
    has, for each comparison that decides, its worst reading and its
    limit."""
    checks = validation_checks(ctx, trainer, final_params.pop(), dataset,
                               val_chunks, reference_kw)
    checks.update(first_step_checks(ctx, trainer, dataset, first_chunk, rng,
                                    reference_kw))
    change = checks["change_over_reference"].values()
    checks["readings_beside_limits"] = {
        "val_loss_ok": [max(checks["val_loss_abs_err"], default=None),
                        VAL_LOSS_ATOL],
        "first_loss_ok": [checks["first_loss_abs_err"], FIRST_LOSS_ATOL],
        "grad_ok": {g: [checks["grad_rel_diff_worst"][g], GRAD_REL_DIFF[g]]
                    for g in GRAD_REL_DIFF},
        "change_ok": [[min(change, default=None), max(change, default=None)],
                      list(CHANGE_BAND)],
    }
    return checks


def validation_checks(ctx, trainer, params, dataset, val_chunks,
                      reference_kw: Optional[Dict] = None) -> Dict:
    """The validation sequences, a step at a time through the compiled
    eval step the window ran, against the reference on the same ids and
    the parameters the run ended on."""
    import jax

    from benchmark.reference import gdn_decoder as ref

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


def _group(name: str, layout) -> str:
    """A leaf's group: a mixer's leaf by the kind of its block (``wq``,
    ``wk``, ``wv`` and ``wo`` are both kinds')."""
    block, _, leaf = name.rpartition("/")
    if block.startswith("block_") and leaf not in GRAD_GROUP:
        if int(layout[int(block[6:])]) == GDN_LAYOUT:
            return GDN_LEAVES[leaf]
        if leaf in ATTENTION_LEAVES:
            return "attention"
    return GRAD_GROUP[leaf]


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

    from benchmark.reference import gdn_decoder as ref

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
    got_absmax = np.asarray(totals.gdn_log_decay_absmax)
    got_beta = np.asarray(totals.gdn_beta_max)
    got_grads = jax.tree.map(
        lambda m: m / (1.0 - ADAM_B1),
        jax.device_get(optax.tree_utils.tree_get(after.opt_state, "mu")))
    got_change = jax.tree.map(lambda a, b: a - b,
                              jax.device_get(after.params), before)
    del fresh, after, totals
    t_program = time.perf_counter() - t0

    # the reference: its forward, its backward a block at a time
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
        group = _group(name, mc.layer_layout)
        # a nan (a wrong run whose state overflows) is the worst there is
        worst[group] = (max(worst[group], value) if np.isfinite(value)
                        else float("inf"))
    ctx.say({"reference_check_s": {
        "first_step_program": t_program,
        "first_step_reference": time.perf_counter() - t0}})
    return {
        "first_loss_program": got_loss,
        "first_loss_reference": want_loss,
        "first_loss_abs_err": abs(got_loss - want_loss),
        "first_loss_atol": FIRST_LOSS_ATOL,
        "first_loss_ok": bool(abs(got_loss - want_loss) <= FIRST_LOSS_ATOL),
        "first_step_gdn_log_decay_absmax": got_absmax.tolist(),
        "first_step_gdn_beta_max": got_beta.tolist(),
        "grad_rel_diff": grad_diff,
        "grad_rel_diff_worst": worst,
        "grad_rel_diff_limit": GRAD_REL_DIFF,
        "grad_ok": all(worst[g] <= GRAD_REL_DIFF[g] for g in GRAD_REL_DIFF),
        "change_over_reference": change_ratio,
        "change_over_reference_band": list(CHANGE_BAND),
        "change_ok": all(CHANGE_BAND[0] <= v <= CHANGE_BAND[1]
                         for v in change_ratio.values()),
    }
