"""Whole training epochs of a token family with learned-sparse attention
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
``setup_s``, at the timed sizes, on what the timed path produced.  The
model chooses its keys by a top-k over scores made of bfloat16 products,
and a top-k is not continuous: a key whose score lies within rounding of
the last kept one may fall either way, and everything downstream then
differs by that key's whole contribution.  So the comparison has three
parts (the limits and the readings behind each are at
:data:`SELECTION_DIFFER_SHARE` .. :data:`HELD_PAIRS_BAND`):

1. **The selection**, a layer at a time, on the validation sequence (at
   the parameters the run ended on) and on the first train batch (fresh
   parameters): the keys the program's layers attended over
   (the model's own ``DecoderBlock`` a layer at a time, with its
   ``intermediates``: the same kernels as the timed step's) against the plain reference's float32
   ``lax.top_k`` (``reference/sparse_decoder.py``) on the same
   parameters and the reference's own input to that layer.  Exactly
   ``sum_t min(t + 1, topk)`` keys a layer and sequence; the keys one
   side took and the other did not are a small share, and each lies
   within a band of the reference's last kept score of its row.
2. **Everything else, tightly**, against the reference *given the
   program's own selection*: the validation loss and held pairs from
   the compiled eval step the window ran; the first train step from
   ``Trainer.single_step`` on fresh parameters: its loss, the pairs
   each held expert received, its gradient (read from Adam's first
   moment) leaf by leaf, its change to the parameters against the
   reference's plain Adam step.  The indexer's three matrices have no
   gradient and do not move, here and there.
3. **The loss against the reference under its own selection**, within a
   wider band: the selection's differences may not add up to more.

And what the SmallThinker cell holds: nothing dropped, the held pairs
of a step inside their band in every pass of the window, every loss
finite, the training loss below the first epoch's, no recompile after
warm-up (``run.py`` adds: no compile inside the window).

Traffic parameters: ``seq_len`` (the configuration's
``framework.train.window``), ``sequences_per_step`` (its
``batch_size``), ``train_sequences`` / ``val_sequences`` /
``test_sequences`` (the split its ``val_size`` / ``test_size`` must
give), ``zipf_exponent``, ``doc_median_tokens``, ``doc_sigma``,
``eod_id`` (the corpus), ``setup_epochs``, ``trace_steps``.

The facts the per-layer readers need go under ``record["sparse"]``, not
``record["moe"]``: ``moe_train_mfu`` and the experts' roofline count a
layer of layouts 0 and 1 (``harness/moe_decoder_flops.py``) and would
read a wrong share here; this cell's are ``sparse_train_mfu`` and the
``sparse_*`` readers over ``harness/sparse_decoder_flops.py``.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from benchmark.drivers.train_token_epochs import (
    ADAM_B1, DeviceStepCount, OpeningTracer, _leaf_name, count_split,
    flip_shares, moe_counters)
from benchmark.harness.device import memory_peak_bytes
from benchmark.harness.sparse_decoder_flops import picked_pairs
from benchmark.harness.token_corpus import make_token_stream
from benchmark.harness.tracing import StepSlice, span

END_TO_END = {"train_samples_per_s": "samples/s"}

#: The limits below and the readings behind them.  All readings are the
#: builder's on the chip at the published widths (PERF.md section 6,
#: PR 32): the program on eleven seeds (32000xx to 32003xx; validation
#: sequence at the final parameters and first batch at fresh ones), and
#: the reference made deliberately wrong five ways against the same
#: program on seed 3200277: ``topk`` 1024, ``dense_attention``,
#: ``indexer_relu=False``, ``skip_expert`` 7, ``products_as`` float8
#: e5m2 (the nearest precision below the configuration's bfloat16).
#: Each wrong run has to fail by one of the limits, not by each.
#:
#: Share of a layer's kept keys that the program took and the reference
#: did not (as many the other way: both keep the same count; with
#: another count, the larger of the two over the keys kept).  The
#: program's index scores are sums of bfloat16 products and, from the
#: second layer on, of a residual stream that went through bfloat16
#: layers; the reference's are float32 ``highest`` on its own float32
#: stream; a key whose score lies within that rounding of the row's
#: last kept one falls either way.  The program **0.29 to 0.56 %** a
#: layer (90,138 to 176,000 of 31,458,304 keys, growing with depth, the
#: same on every seed to a tenth).  Wrong: float8 **2.86 to 3.13 %**,
#: ``skip_expert`` 0.30 to 1.26 % (inside: it fails the gradient),
#: without the relu 23 to 25 %, ``topk`` 1024 48.3 %, dense 327 %.  The
#: limit is 2.7x the program's largest and 1.9x under float8's smallest.
SELECTION_DIFFER_SHARE = 0.015
#: How far, in units of the row's score spread (the standard deviation
#: of its causal index scores, float32 reference), the reference score of
#: any key that one side took and the other did not may lie from the
#: reference's last kept score of that row: the farthest of a layer's
#: ~100,000 to 176,000 such keys.  The program **0.031 to 0.046** in
#: layer 0 (the same float32 input on both sides: the bfloat16 products
#: alone) and **0.13 to 0.42** in layers 1 to 3 (the inputs differ too;
#: the largest of a layer's ~150,000 readings, so it creeps up with the
#: seeds tried: 0.34 after seven, 0.42 after eleven).
#: Wrong: ``topk`` 1024 5.4 to 6.0, without the relu 3.7 to 4.6,
#: ``skip_expert`` 0.44 to 0.73 from layer 1 on, float8 0.38 to 0.50
#: (inside: it fails the share).  The limit is 1.44x the program's
#: largest of 88 readings; it is what holds a selection to *the
#: reference's edge* where the share alone would pass one that swaps
#: 1 % of the keys for far ones.
SELECTION_GAP_BAND = 0.6
#: Absolute tolerance, in nats, on a step's loss against the reference
#: given the program's selection (a mean over 16,384 tokens, 9 to 10.3
#: nats).  The program at most **3.5e-4** (validation) and **3.6e-4**
#: (first step); float8 1.27e-3 and 1.50e-3; ``skip_expert`` 7.0e-4 (a
#: mean loss hardly sees one expert: the gradient catches it).  The
#: limit is 2.8x the program's largest and 1.27x under float8's.
VAL_LOSS_ATOL = 1e-3
FIRST_LOSS_ATOL = 1e-3
#: ... and against the reference under its own selection: the program
#: at most **4.2e-4** (the 0.5 % of keys that differ lie at the edge of
#: the kept set and carry little softmax weight).  A reference that
#: keeps another set by design moves the loss by more: no relu 4.3e-3,
#: every causal key 9.3e-3, 1,024 keys 1.24e-2.  The limit is 4.7x the
#: program's largest and 2.2x under the smallest of those.
OWN_SELECTION_LOSS_ATOL = 2e-3
#: Share of a layer's held (token, expert) pairs that may sit on another
#: held expert, or on none, than in the reference's routing (the first
#: step, and the validation sequence): the program 0.07 to 0.44 %; the
#: limit is ``drivers/train_token_epochs.py``'s, for its reason (one tie
#: of the most frequent id moves several per cent of a layer's pairs at
#: once; a router that kept seven experts a token moves 12 %).
PAIR_FLIP_SHARE = 0.10
VAL_PAIR_FLIP_SHARE = 0.10
#: The first train step's gradient against the reference's, leaf by
#: leaf: ``|g - g_ref| / |g_ref|`` (both clipped), the worst leaf of each
#: group against the group's limit.  ``dense``: leaves every token
#: reaches the same way.  ``qk``: the query and key projections and
#: their norms, behind the attention softmax.  ``routed``: what a token
#: reaches through its top-8.  ``held``: the indexer's, which must read
#: exactly zero on both sides.  The program, worst leaf of the group on
#: eleven first steps: **0.51 to 0.56 %** dense, **0.99 to 1.27 %** qk,
#: **3.8 to 8.4 %** routed (a router or an ``ln_moe`` of layer 1 to 3).
#: Wrong: float8 **1.16 / 1.93 / 14.9 %**; ``skip_expert`` 1.04 / 2.17 /
#: 32.4 %.  The dense limit is 1.52x the program's largest and 1.36x
#: under float8's; qk and routed are ``drivers/train_token_epochs.py``'s
#: (1.42x and 1.55x the program's largest here, 1.07x and 1.15x under
#: float8's, which fails by the dense limit, the share and the losses
#: with more room).
GRAD_GROUP = {
    "embed": "dense", "head": "dense", "ln_final": "dense",
    "ln_attn": "dense", "wo": "dense", "wv": "dense",
    "wq": "qk", "wk": "qk", "q_norm": "qk", "k_norm": "qk",
    "router": "routed", "ln_moe": "routed", "w_gate": "routed",
    "w_up": "routed", "w_down": "routed",
    "wq_idx": "held", "wk_idx": "held", "ww_idx": "held",
}
GRAD_REL_DIFF = {"dense": 0.0085, "qk": 0.018, "routed": 0.13, "held": 0.0}
#: As ``drivers/train_token_epochs.py``: a routed leaf's distance grows
#: as the root of the share of pairs routed elsewhere, so the routed
#: limit is the larger of the one above and this times the root of the
#: first step's largest pair-flip share (it comes into play over
#: 0.42 %, float8's own: 13.0 %, which its 14.9 % still fails).
GRAD_ROUTED_PER_ROOT_FLIP = 2.0
#: ``sum |change| / sum |the reference's change|`` of each leaf over the
#: first step (the reference's: plain Adam on its own clipped gradient;
#: Adam's first step moves every element with a gradient by the rate);
#: a leaf neither side moves (the indexer's) reads 1.  The program
#: 0.9999 to 1.0008 on the matrices; the norm scales start at 1.0, where
#: float32 rounds a step of 1e-6 to 8 units in the last place going up
#: (0.954) and 17 going down (1.013): **0.973 to 0.990** on the eighteen
#: scale leaves, the lowest on a 128-element ``q_norm`` whose steps
#: mostly went up, and 0.954 if all did.  So the scales get the wider
#: band; a leaf the step never updates reads 0 in either, and the
#: reference without one expert reads 1.072 on the ``w_*`` leaves.
CHANGE_BAND = (0.97, 1.03)
CHANGE_BAND_SCALES = (0.95, 1.03)
SCALE_LEAVES = ("ln_attn", "ln_moe", "ln_final", "q_norm", "k_norm")
#: Held pairs of a train step, summed over the layers, in every training
#: pass of the window, as a multiple of what an even router gives
#: (seq_len x top-8 x held / all experts, 16,384 a layer, 65,536 over the
#: four): the rate holds the routing about where initialisation put it.
#: Readings over eleven runs: 52,202 to 78,395 (0.80 to 1.20: the seed's
#: router decides, a pass moves it by 1 to 3 %), a layer alone at least
#: 9,228 (0.56).  A change that sheds (or gathers) pairs
#: to move the step's time leaves the band; no layer may fall under the
#: floor's share of its expected pairs.
HELD_PAIRS_BAND = (0.6, 1.6)
HELD_PAIRS_LAYER_FLOOR = 0.25

#: What of :func:`reference_checks` decides ``correct``.
REFERENCE_DECIDES = (
    "val_kept_ok", "val_selection_ok", "val_loss_ok", "val_pairs_ok",
    "val_own_selection_loss_ok", "first_kept_ok", "first_selection_ok",
    "first_loss_ok", "pairs_ok", "grad_ok", "change_ok")


def require_sparse_program() -> None:
    """Fail at once, with a message, on a program without the layer."""
    import dataclasses

    from fmda_tpu.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    if not {"indexer_topk", "indexer_heads", "hidden_act"} <= fields:
        raise SystemExit(
            "this program has no learned-sparse attention layer "
            "(ModelConfig lacks indexer_topk / indexer_heads / hidden_act): "
            "the cell cannot run on it")


def sparse_counters(reg, n_layers: int) -> Dict[str, List[float]]:
    """The training passes' selection counters so far, per layer."""
    labels = [dict(layer=str(i), phase="train") for i in range(n_layers)]
    return {
        "kept": [reg.counter("sparse_keys_kept_total", **lb).value
                 for lb in labels],
        "rows": [reg.counter("sparse_query_rows_total", **lb).value
                 for lb in labels],
    }


def run(ctx) -> Dict:
    t0 = time.perf_counter()
    require_sparse_program()
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
    sparse_before = sparse_counters(reg, n_layers)
    t_start = clock()
    epochs = 0
    train_losses, val_losses, held_by_pass = [], [], []
    while clock() - t_start < seconds:
        with span("bench_epoch"):
            state, h, _ = trainer.fit(source, rng=rng, epochs=1,
                                      initial_state=state, dataset=dataset)
        train_losses.append(h["train"][0].loss)
        val_losses.append(h["val"][0].loss)
        held_by_pass.append(moe_counters(reg, n_layers)["held"])
        epochs += 1
    elapsed = clock() - t_start
    peak_at_window_end = memory_peak_bytes(jax.devices())
    stall_after = stall.snapshot()
    moe_after = moe_counters(reg, n_layers)
    sparse_after = sparse_counters(reg, n_layers)
    ctx.window_ended()
    steps_per_epoch = train_steps + eval_steps

    tracer = OpeningTracer(ctx.trace, ctx.trace_dir)
    tail = {}
    if ctx.trace:
        done.start()
        piece = StepSlice(tracer, lambda: done.value,
                          int(traffic["trace_steps"]), train_steps)

        def one_epoch():
            nonlocal state
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
    pairs_by_pass = [
        [(a - b) / train_steps for a, b in zip(after, before)]
        for before, after in zip([moe_before["held"]] + held_by_pass,
                                 held_by_pass)]
    expected = seq * tc.batch_size * mc.moe_top_k * mc.experts_held[1] \
        / mc.moe_experts
    band = [HELD_PAIRS_BAND[0] * expected * n_layers,
            HELD_PAIRS_BAND[1] * expected * n_layers]
    window_steps = max(epochs * train_steps, 1)
    # what the selection counted over the window's training passes
    kept = [a - b for a, b in zip(sparse_after["kept"], sparse_before["kept"])]
    rows = [a - b for a, b in zip(sparse_after["rows"], sparse_before["rows"])]
    kept_want = picked_pairs(seq, mc.indexer_topk) * tc.batch_size \
        * window_steps
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
        "window_keys_kept": kept,
        "window_keys_kept_expected": kept_want,
        "window_kept_ok": all(k == kept_want for k in kept),
        "unexpected_recompiles": recompiles,
        "compiles_by_the_comparisons": (
            trainer.unexpected_recompiles - recompiles),
        "compile_counts": trainer.compile_counts,
        "train_losses": train_losses,
        "val_losses": val_losses,
    })
    correct = bool(
        all(checks[name] for name in REFERENCE_DECIDES)
        and checks["held_pairs_ok"] and checks["window_kept_ok"]
        and all(finite) and checks["loss_fell"]
        and dropped == 0 and recompiles == 0)

    sparse = {
        "seq_len": seq,
        "sequences_per_step": tc.batch_size,
        "experts_held": mc.experts_held[1],
        "pairs_per_train_step": [
            (a - b) / window_steps
            for a, b in zip(moe_after["held"], moe_before["held"])],
        "keys_kept_per_train_step": [k / window_steps for k in kept],
        "query_rows_per_train_step": [r / window_steps for r in rows],
        "first_pass_pairs_per_step": pairs_by_pass[0],
        "last_pass_pairs_per_step": pairs_by_pass[-1],
        "last_pass_pairs_max": moe_after["max"],
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
            "sparse": sparse,
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
            "parameters": n_params,
            # train_peak_hbm_mb reads the process's peak after the
            # comparisons with the reference: they stay under this
            "device_peak_bytes_at_window_end": peak_at_window_end,
            "sparse": sparse,
            "trace_start_cost_s": tracer.start_cost_s,
            "trace_stop_cost_s": tracer.stop_cost_s,
            **tail,
        },
    }


def reference_checks(ctx, trainer, final_params: list, dataset,
                     val_chunks, first_chunk, rng,
                     reference_kw: Optional[Dict] = None) -> Dict:
    """The comparisons with the plain reference, at the timed sizes.
    ``final_params`` is a one-element list that is emptied here, so that
    the parameters the run ended on are freed before fresh ones are
    made.  ``reference_kw`` makes the *reference* deliberately wrong
    (``products_as``, ``skip_expert``, ``topk``, ``dense_attention``,
    ``indexer_relu`` of ``reference/sparse_decoder.py``): the selftest
    and the builder's readings put those runs through the limits above,
    and each has to come out as not correct."""
    from benchmark.reference import sparse_decoder as ref

    # one set of compiled pieces for every comparison of the run
    layerwise = ref.Layerwise(trainer.model_cfg, **(reference_kw or {}))
    checks = validation_checks(ctx, trainer, final_params.pop(), dataset,
                               val_chunks, layerwise)
    checks.update(first_step_checks(ctx, trainer, dataset, first_chunk, rng,
                                    layerwise))
    return checks


def program_selection(trainer):
    """``(params, ids (B, T)) -> (B, layers, T, T // 8) uint8``: the keys
    the program's layers attend over, eight to a byte, from the model's
    own layer (``models/decoder.py`` ``DecoderBlock``: the kernels the
    timed step runs) applied a layer at a time to what the layer before
    it gave, so that one layer is compiled and not the model again.
    Packed, because beside the state and the step's temporaries the chip
    has no room for a (T, T) byte mask a layer."""
    import jax
    import jax.numpy as jnp

    from fmda_tpu.models.decoder import SPARSE_LAYOUT, DecoderBlock

    mc = trainer.model_cfg
    if set(mc.layer_layout) != {SPARSE_LAYOUT}:
        raise SystemExit("this driver compares a model whose layers are all "
                         f"learned-sparse; layer_layout is {mc.layer_layout}")
    block = DecoderBlock(mc, SPARSE_LAYOUT)

    @jax.jit
    def one_layer(p, x):
        (x2, _), inter = block.apply({"params": p}, x,
                                     mutable=["intermediates"])
        return x2, jnp.packbits(
            inter["intermediates"]["picked"][0] != 0, axis=-1)

    def picks(params, ids):
        x = jnp.take(params["embed"], ids, axis=0).astype(jnp.dtype(mc.dtype))
        packed = []
        for i in range(len(mc.layer_layout)):
            x, bits = one_layer(params[f"block_{i}"], x)
            packed.append(bits)
        return jnp.stack(packed, axis=1)

    return picks


class PackedPicks:
    """What :func:`program_selection` packed, indexed like the
    ``(..., T, T)`` masks it stands for: the last leading index unpacks
    one ``(T, T)`` uint8 mask, so that only the layer in use is ever
    held unpacked (a quarter of a gigabyte, not one a layer)."""

    def __init__(self, bits) -> None:
        self.bits = bits

    def __getitem__(self, i):
        import jax.numpy as jnp

        if self.bits.ndim == 3:
            return jnp.unpackbits(self.bits[i], axis=-1)
        return PackedPicks(self.bits[i])


def selection_facts(dist, seq: int, topk: int, prefix: str) -> Dict:
    """One sequence's :class:`SelectionDistance` against the limits."""
    kept = np.asarray(dist.kept).tolist()
    differ = [float(max(a, b)) / max(k, 1) for a, b, k in zip(
        np.asarray(dist.program_only), np.asarray(dist.reference_only), kept)]
    gap = np.asarray(dist.worst_gap).tolist()
    return {
        f"{prefix}_keys_kept": kept,
        f"{prefix}_keys_kept_expected": picked_pairs(seq, topk),
        f"{prefix}_kept_ok": all(
            k == picked_pairs(seq, topk) for k in kept),
        f"{prefix}_selection_program_only": np.asarray(
            dist.program_only).tolist(),
        f"{prefix}_selection_reference_only": np.asarray(
            dist.reference_only).tolist(),
        f"{prefix}_selection_differ_share": differ,
        f"{prefix}_selection_differ_share_limit": SELECTION_DIFFER_SHARE,
        f"{prefix}_selection_worst_gap": gap,
        f"{prefix}_selection_gap_band": SELECTION_GAP_BAND,
        f"{prefix}_selection_ok": bool(
            max(differ) <= SELECTION_DIFFER_SHARE
            and max(gap) <= SELECTION_GAP_BAND),
    }


def validation_checks(ctx, trainer, params, dataset, val_chunks,
                      layerwise) -> Dict:
    """The first validation sequence through the compiled eval step the
    window ran, against the reference on the same ids and the parameters
    the run ended on: given the program's selection, and under its
    own."""
    t0 = time.perf_counter()
    mc = trainer.model_cfg
    batch = next(iter(trainer._chunk_batches(dataset, val_chunks[0])))
    totals = trainer._eval_step(params, trainer.zero_totals(), batch)
    got_loss = float(totals.loss)
    got_pairs = np.asarray(totals.expert_pairs)
    picked = PackedPicks(program_selection(trainer)(params, batch.x))
    x, y, m = batch.x[0], batch.y[0], batch.mask[0]
    want_loss, want_pairs, dist = layerwise.loss(params, x, y, m, picked[0])
    own_loss, _, _ = layerwise.loss(params, x, y, m, picked[0],
                                    attend_given=False)
    del picked
    full = bool((np.asarray(batch.mask) > 0).all()) and batch.x.shape[0] == 1
    flips = flip_shares(got_pairs, want_pairs) if full else []
    err = abs(got_loss - float(want_loss))
    own_err = abs(got_loss - float(own_loss))
    ctx.say({"reference_check_s": {"validation": time.perf_counter() - t0}})
    return {
        **selection_facts(dist, batch.x.shape[1], mc.indexer_topk, "val"),
        "val_loss_program": got_loss,
        "val_loss_reference": float(want_loss),
        "val_loss_abs_err": err,
        "val_loss_atol": VAL_LOSS_ATOL,
        "val_loss_ok": bool(err <= VAL_LOSS_ATOL),
        "val_loss_reference_own_selection": float(own_loss),
        "val_own_selection_loss_abs_err": own_err,
        "val_own_selection_loss_atol": OWN_SELECTION_LOSS_ATOL,
        "val_own_selection_loss_ok": bool(own_err <= OWN_SELECTION_LOSS_ATOL),
        "val_pair_flip_share": flips,
        "val_pair_flip_share_limit": VAL_PAIR_FLIP_SHARE,
        "val_pairs_ok": bool(flips and max(flips) <= VAL_PAIR_FLIP_SHARE),
    }


def first_step_checks(ctx, trainer, dataset, first_chunk, rng,
                      layerwise) -> Dict:
    """The first train step, from fresh parameters as ``fit()`` makes
    them, on the first training batch, through the compiled train step
    the window ran: its selection, loss and routing, the gradient it
    computed (Adam's first moment after one step is ``(1 - b1)`` times
    the clipped gradient) and the change it made to the parameters,
    against the reference's on the same batch and parameters, given the
    program's selection."""
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark.reference import sparse_decoder as ref

    t0 = time.perf_counter()
    mc, tc = trainer.model_cfg, trainer.train_cfg
    init_rng, step_rng = jax.random.split(rng)
    batch = next(iter(trainer._chunk_batches(dataset, first_chunk)))
    full = bool((np.asarray(batch.mask) > 0).all())

    fresh = trainer.init_state(init_rng)
    # packed until the step is done: the step needs the room (its
    # temporaries and the state fill the chip)
    picked = program_selection(trainer)(fresh.params, batch.x)
    after, totals = trainer.single_step(fresh, batch, step_rng)
    got_loss, got_pairs = float(totals.loss), np.asarray(totals.expert_pairs)
    # everything stays on the device and only sums come back: three
    # trees of 1.86 GB through the host cost a traced run half a minute
    got_grads = jax.tree.map(
        lambda m: m / (1.0 - ADAM_B1),
        optax.tree_utils.tree_get(after.opt_state, "mu"))
    moved = after.params
    del fresh, after, totals
    # the parameters the step began from, made again from their key (the
    # step donated the first copy)
    params = trainer.init_state(init_rng).params
    moved_by = jax.tree.map(
        jax.jit(lambda a, b: jnp.sum(jnp.abs(a - b))), moved, params)
    del moved
    t_program = time.perf_counter() - t0

    # the reference: forward and backward a layer at a time under the
    # program's selection, then its plain clip and Adam step
    t0 = time.perf_counter()
    want_loss, grads, want_pairs, dist = layerwise.loss_and_grads(
        params, batch.x, batch.y, batch.mask, PackedPicks(picked))
    del params, picked
    flips = flip_shares(got_pairs, want_pairs) if full else []

    # leaf by leaf: the reference's clipped gradient and plain Adam
    # change, the program's distance from the one and size against the
    # other; a leaf is freed before the next is made
    scale = ref.clip_scale(grads, tc.clip)

    @jax.jit
    def leaf_facts(got, g):
        want, change = ref.first_adam_leaf(g, scale, tc.learning_rate)
        return (jnp.linalg.norm((got - want).ravel()),
                jnp.linalg.norm(want.ravel()), jnp.sum(jnp.abs(change)))

    grad_diff, change_ratio = {}, {}
    names = [_leaf_name(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(grads)]
    for name, g, got_g, got_sum in zip(
            names, jax.tree.leaves(grads), jax.tree.leaves(got_grads),
            jax.tree.leaves(moved_by)):
        apart, size, should = (float(v) for v in leaf_facts(got_g, g))
        grad_diff[name] = apart / max(size, 1e-30)
        moved = float(got_sum)
        change_ratio[name] = 1.0 if moved == should == 0.0 else (
            moved / max(should, 1e-30))
    del grads, got_grads
    routed_limit = max(GRAD_REL_DIFF["routed"],
                       GRAD_ROUTED_PER_ROOT_FLIP * max(flips, default=0.0)
                       ** 0.5)
    worst = {group: 0.0 for group in GRAD_REL_DIFF}
    for name, value in grad_diff.items():
        group = GRAD_GROUP[name.rsplit("/", 1)[-1]]
        worst[group] = max(worst[group], value)
    limits = dict(GRAD_REL_DIFF, routed=routed_limit)
    ctx.say({"reference_check_s": {
        "first_step_program": t_program,
        "first_step_reference": time.perf_counter() - t0}})
    return {
        **selection_facts(dist, batch.x.shape[1], mc.indexer_topk, "first"),
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
        "grad_rel_diff_worst": worst,
        "grad_rel_diff_limit": limits,
        "grad_ok": all(worst[g] <= limits[g] for g in limits),
        "change_over_reference": change_ratio,
        "change_over_reference_band": list(CHANGE_BAND),
        "change_over_reference_band_scales": list(CHANGE_BAND_SCALES),
        "change_ok": all(
            band[0] <= v <= band[1] for name, v in change_ratio.items()
            for band in [CHANGE_BAND_SCALES if name.rsplit("/", 1)[-1]
                         in SCALE_LEAVES else CHANGE_BAND]),
    }
