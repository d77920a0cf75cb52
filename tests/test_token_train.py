"""The token task on the trainer's one loop: a pass's totals are bit for
bit the fold over ``single_step`` (as tests/test_step_totals.py holds for
gru and ssm), ``fit`` on a token source lowers the loss, caches its
placed batches, checkpoints and resumes, and ``python -m fmda_tpu
train --tokens`` reaches it."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fmda_tpu.config import (
    FrameworkConfig, ModelConfig, TrainConfig, config_to_dict)
from fmda_tpu.data.pipeline import TokenBatches, TokenDataset
from fmda_tpu.data.source import TokenArraySource
from fmda_tpu.obs.registry import default_registry
from fmda_tpu.train.tasks import TokenTotals
from fmda_tpu.train.trainer import Trainer

SEQ, VOCAB = 32, 64


def _model(**over):
    return ModelConfig(**{**dict(
        cell="decoder", hidden_size=32, n_heads=4, n_kv_heads=2, head_dim=8,
        vocab_size=VOCAB, layer_layout=(0, 1), sliding_window=8,
        moe_experts=4, moe_top_k=2, moe_ffn_size=16, experts_held=(1, 2),
        loss_chunk=16, dtype="float32"), **over})


def _train(**over):
    return TrainConfig(**{**dict(
        batch_size=2, window=SEQ, chunk_size=2 * SEQ, learning_rate=1e-2,
        clip=1.0, val_size=0.1, test_size=0.1, cache_chunks=16, seed=0),
        **over})


def _source(n_seq=21, seed=0):
    rng = np.random.default_rng(seed)
    # a Zipf-like stream: a few ids carry most of the mass, so a unigram
    # fit alone lowers the loss
    ids = np.minimum(rng.zipf(1.3, size=n_seq * SEQ + 1) - 1, VOCAB - 1)
    return TokenArraySource(ids, VOCAB)


def _copy(state):
    return jax.tree.map(jnp.copy, state)


def test_token_dataset_cuts_shifted_sequences_and_pads_whole_ones():
    src = _source(n_seq=5)
    ds = TokenDataset(src, chunk_size=2 * SEQ, window=SEQ)
    assert ds.n_sequences == 5 and len(ds) == 3
    x, y = ds.sequences(1)
    stream = src.fetch_tokens(0, len(src))
    np.testing.assert_array_equal(x[0], stream[2 * SEQ:3 * SEQ])
    np.testing.assert_array_equal(y[0], stream[2 * SEQ + 1:3 * SEQ + 1])
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    (last,) = list(TokenBatches(ds, 2, batch_size=2))  # one sequence left
    assert last.x.dtype == np.int32 and last.x.shape == (2, SEQ)
    assert last.mask[0].all() and not last.mask[1].any()


def test_a_source_wider_than_the_model_is_refused():
    trainer = Trainer(_model(vocab_size=32), _train())
    with pytest.raises(ValueError, match="the model holds 32"):
        trainer.fit(_source(), epochs=1)


@pytest.mark.parametrize("train", [True, False])
def test_pass_totals_are_bit_for_bit_the_fold_over_single_step(train):
    trainer = Trainer(_model(), _train())
    ds = trainer.task.dataset(_source())
    batches = [b for c in (0, 1, 2) for b in trainer._chunk_batches(ds, c)]
    state = trainer.init_state(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1) if train else None

    folded, st = None, _copy(state)
    for b in batches:
        st, vals = trainer.single_step(st, b, rng)
        assert isinstance(vals, TokenTotals)
        folded = vals if folded is None else jax.tree.map(
            jnp.add, folded, vals)
    want, _ = trainer.task.epoch_metrics(jax.device_get(folded), len(batches))

    before = default_registry().counter("train_tokens_total").value
    _, got, _ = trainer._run_batches(_copy(state), (batches,), rng, train)
    assert got.loss == want.loss and got.accuracy == want.accuracy
    held = np.asarray(folded.expert_pairs)
    assert held.shape == (2, 2) and int(folded.dropped) == 0
    after = default_registry().counter("train_tokens_total").value
    assert after - before == (int(folded.tokens) if train else 0)
    phase = "train" if train else "eval"
    for layer in (0, 1):
        gauge = default_registry().gauge(
            "moe_expert_pairs_max", layer=str(layer), phase=phase)
        assert gauge.value == held[layer].max()
    assert trainer.compile_counts == {"train_step": int(train),
                                      "eval_step": int(not train)}


@pytest.mark.parametrize("train,accum", [(True, 1), (False, 1), (True, 2)])
def test_a_pass_publishes_the_row_tiles_its_layers_used(train, accum):
    """``TokenTotals.row_tiles_used`` is the sum over a pass of each
    layer's used row tiles (told here from the pairs each held expert
    got: a group fills whole tiles, an empty one keeps one), and the
    drain publishes it beside the tiles laid out, the rounds of the
    layout and the layer's calls: whole batches, or a train step's
    microbatches, each one round here."""
    from fmda_tpu.ops.moe import default_row_tile, layout_tiles

    trainer = Trainer(_model(), _train(accum_steps=accum))
    ds = trainer.task.dataset(_source())
    batches = [b for c in (0, 1, 2) for b in trainer._chunk_batches(ds, c)]
    state = trainer.init_state(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1) if train else None
    passes = accum if train else 1
    pairs = 2 * SEQ * 2 // passes                  # a forward pass's
    tile = default_row_tile(pairs)

    want, st = np.zeros(2, np.int64), _copy(state)
    for b in batches:
        st, vals = trainer.single_step(st, b, rng)
        if passes == 1:  # n_used from the group sizes, layer by layer
            sizes = np.asarray(vals.expert_pairs)
            np.testing.assert_array_equal(
                vals.row_tiles_used,
                np.maximum(-(-sizes // tile), 1).sum(axis=1))
        want += np.asarray(vals.row_tiles_used)

    phase = "train" if train else "eval"
    counters = [[default_registry().counter(name, layer=str(layer),
                                            phase=phase)
                 for layer in (0, 1)]
                for name in ("moe_row_tiles_used_total",
                             "moe_row_tiles_layout_total",
                             "moe_layout_rounds_total",
                             "moe_layer_calls_total")]
    before = [[c.value for c in row] for row in counters]
    trainer._run_batches(_copy(state), (batches,), rng, train)
    used, layout, rounds, calls = (
        [c.value - b for c, b in zip(row, was)]
        for row, was in zip(counters, before))
    assert used == want.tolist()
    assert rounds == calls == [len(batches) * passes] * 2
    assert layout == [calls[0] * layout_tiles(pairs, 2, 4)] * 2
    assert all(0 < u <= total for u, total in zip(used, layout))


@pytest.mark.parametrize("train", [True, False])
def test_grouped_token_pass_is_the_fold_over_single_step(train):
    """A decoder this small is grouped like any other family (the rule
    reads bytes, not names): seven batches ride one call, padded to 16,
    and read what seven single steps read."""
    trainer = Trainer(_model(), _train())
    ds = trainer.task.dataset(_source())
    host = [b for c in range(7) for b in trainer.task.batches(ds, c)]
    state = trainer.init_state(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1) if train else None
    (group,) = trainer._place_batches(host, state)
    assert group.n_live == 7 and group.batches.x.shape == (16, 1, 2, SEQ)

    folded, st = None, _copy(state)
    for b in trainer._place_batches(host):
        st, vals = trainer.single_step(st, b, rng)
        folded = vals if folded is None else jax.tree.map(
            jnp.add, folded, vals)
    want, _ = trainer.task.epoch_metrics(jax.device_get(folded), 7)
    out, got, _ = trainer._run_batches(_copy(state), ([group],), rng, train)
    assert got.loss == want.loss and got.accuracy == want.accuracy
    same = jax.tree.map(np.array_equal, jax.device_get(out),
                        jax.device_get(st))
    assert all(jax.tree.leaves(same))


#: sha256 (first 16 hex digits) of the lowered text of this file's tiny
#: decoder's single train and eval programs, jax 0.9.0: PR 29's parent's
#: (98d5033) until PR 31 changed the expert layer's row passes and the
#: totals' leaves, PR 31's until PR 48 put the layer's rounds in a
#: ``while`` under one ``custom_vjp`` (each direction a ``jax.jit``),
#: named its output and gave the totals ``layout_rounds``; PR 48's since.  Regenerate with the snippet
#: in the test below after a deliberate change to the decoder, its task
#: or the step function.
PARENT_STEP_TEXT = ("f298a8cc86996da6", "9a879c738316528e")


def test_a_solo_decoder_runs_the_parents_programs(monkeypatch):
    """At the published widths the decoder's 7.88 GB of state put it past
    ``SOLO_STEP_BYTES`` (tests/test_step_totals.py holds the rule to
    that); with the threshold brought down to this tiny one, ``fit`` is
    the parent's loop: one call a step through the single programs,
    whose lowered text is the parent's character for character."""
    import hashlib

    from fmda_tpu.train import trainer as trainer_module

    monkeypatch.setattr(trainer_module, "SOLO_STEP_BYTES", 1)
    trainer = Trainer(_model(), _train())
    state, _, ds = trainer.fit(_source(), epochs=1)
    assert trainer._train_group.cache_size() == 0
    assert trainer._eval_group.cache_size() == 0
    assert trainer._train_step.cache_size() == 1
    assert trainer._eval_step.cache_size() == 1
    batch = next(iter(trainer._chunk_batches(ds, 0)))
    totals = trainer.zero_totals()
    lowered = (
        trainer._train_step._jit.lower(
            state, totals, batch, jax.random.PRNGKey(1)),
        trainer._eval_step._jit.lower(state.params, totals, batch))
    got = tuple(hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
                for low in lowered)
    assert got == PARENT_STEP_TEXT
    # what the benchmark's decoder driver calls after the window are the
    # programs the loop ran: nothing more compiles
    trainer._eval_step(state.params, trainer.zero_totals(), batch)
    trainer.single_step(state, batch, jax.random.PRNGKey(1))  # donates
    assert trainer.compile_counts == {"train_step": 1, "eval_step": 1}


def test_accumulated_microbatches_give_the_full_batch_step():
    full = Trainer(_model(), _train())
    micro = Trainer(_model(), _train(accum_steps=2))
    ds = full.task.dataset(_source())
    (batch,) = list(full._chunk_batches(ds, 0))
    state = full.init_state(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)
    sa, a = full.single_step(_copy(state), batch, rng)
    sb, b = micro.single_step(_copy(state), batch, rng)
    np.testing.assert_allclose(a.loss, b.loss, rtol=1e-5)
    assert int(a.tokens) == int(b.tokens) == 2 * SEQ
    np.testing.assert_array_equal(a.expert_pairs, b.expert_pairs)
    for x, y in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
        np.testing.assert_allclose(x, y, rtol=2e-3, atol=2e-5)


def test_fit_lowers_the_loss_caches_checkpoints_and_resumes(tmp_path):
    from fmda_tpu.train.checkpoint import save_checkpoint

    trainer = Trainer(_model(remat=True), _train())
    src = _source()
    state, hist, ds = trainer.fit(src, epochs=3)
    losses = [m.loss for m in hist["train"]]
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert np.isfinite(hist["val"][-1].loss)
    assert 0.0 < hist["train"][-1].accuracy < 1.0
    assert hist["train"][-1].hamming == 1.0 - hist["train"][-1].accuracy
    assert trainer.compile_counts == {"train_step": 1, "eval_step": 1}
    assert len(trainer._placed_cache) == 2  # train and validation chunks

    ckpt = save_checkpoint(str(tmp_path), state)
    resumed = Trainer(_model(remat=True), _train())
    restored = resumed.restore_state(ckpt)
    assert int(restored.step) == int(state.step)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        np.testing.assert_array_equal(a, b)
    # the same further epoch from the live state and from the checkpoint
    _, h1, _ = trainer.fit(src, epochs=1, initial_state=_copy(state),
                           dataset=ds)
    _, h2, _ = resumed.fit(src, epochs=1, initial_state=restored)
    assert h1["train"][0].loss == h2["train"][0].loss
    assert h1["val"][0].loss == h2["val"][0].loss


def test_cli_train_reads_a_token_file(tmp_path, capsys):
    from fmda_tpu.cli import main

    cfg = FrameworkConfig(model=_model(), train=_train(epochs=1))
    cfg_path, tokens = tmp_path / "cfg.json", tmp_path / "tokens.npy"
    cfg_path.write_text(json.dumps(config_to_dict(cfg)))
    src = _source(n_seq=11)
    np.save(tokens, src.fetch_tokens(0, len(src)))
    rc = main(["train", "--config", str(cfg_path), "--platform", "cpu",
               "--tokens", str(tokens),
               "--checkpoint-dir", str(tmp_path / "ckpt")])
    out = capsys.readouterr()
    assert rc == 0, out.err
    assert "trained 1 epochs" in out.out and "checkpoint:" in out.out
    assert main(["train", "--config", str(cfg_path), "--platform", "cpu"]
                ) == 2


@pytest.mark.parametrize("clip", [1e-3, 1e3])  # clipping, and not
def test_the_first_step_leaves_the_reference_adam_step_behind(clip):
    """What the benchmark's ``correct`` reads a train step's gradient
    from: after one step from fresh state, Adam's first moment is
    ``(1 - b1)`` times the clipped gradient and the parameters moved as
    the plain reference's Adam step says
    (benchmark/reference/moe_decoder.py ``first_adam_step``)."""
    import optax

    from benchmark.reference import moe_decoder as ref

    mc, tc = _model(), _train(clip=clip, learning_rate=1e-3)
    trainer = Trainer(mc, tc)
    dataset = TokenDataset(_source(), tc.chunk_size, SEQ)
    batch = next(iter(TokenBatches(dataset, 0, tc.batch_size)))
    fresh = trainer.init_state(jax.random.PRNGKey(0))
    before = jax.device_get(fresh.params)
    with jax.default_matmul_precision("highest"):
        after, _ = trainer.single_step(fresh, batch, jax.random.PRNGKey(1))
    _, grads = ref.loss_and_grads(
        before, batch.x, batch.y, batch.mask, mc)
    want_grads, want_change = ref.first_adam_step(
        grads, learning_rate=tc.learning_rate, clip=clip)
    mu = optax.tree_utils.tree_get(after.opt_state, "mu")
    for got_mu, got, old, want_g, want_d in zip(*map(jax.tree.leaves, (
            mu, after.params, before, want_grads, want_change))):
        scale = float(jnp.abs(want_g).max()) + 1e-12
        np.testing.assert_allclose(got_mu / 0.1, want_g,
                                   atol=2e-4 * scale + 1e-9)
        # |change| is the rate wherever the gradient is not tiny
        np.testing.assert_allclose(
            np.abs(np.asarray(got) - old).sum(),
            np.abs(np.asarray(want_d)).sum(), rtol=2e-2)
