"""The decoder family's state-space layer (``layer_layout`` 3), dense MLP,
tied head and stated multipliers against their plain reference
(benchmark/reference/hybrid_decoder.py, whose recurrence is stepwise) at
a small size on the CPU, seeded random weights, float32: logits, loss
and every leaf's gradient; the tied leaf; each multiplier; the
reference's deliberately wrong runs; what a pass counts and publishes;
the configuration check and the CLI (the accepted configurations'
pinned programs are in tests/test_decoder.py).  The published widths are compared on the chip
(benchmark/drivers/train_hybrid_token_epochs.py)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import hybrid_decoder as ref  # noqa: E402
from fmda_tpu.config import (  # noqa: E402
    FrameworkConfig, ModelConfig, TrainConfig, config_to_dict)
from fmda_tpu.data.pipeline import Batch  # noqa: E402
from fmda_tpu.data.source import TokenArraySource  # noqa: E402
from fmda_tpu.models import build_model  # noqa: E402
from fmda_tpu.models.decoder import check_decoder_config  # noqa: E402
from fmda_tpu.train.tasks import NextToken  # noqa: E402

SEQ, VOCAB, CHUNK = 40, 96, 16


def small_cfg(**over):
    """A period of both layer kinds, three chunks a sequence (the last
    one short), every multiplier off its default."""
    return ModelConfig(**{**dict(
        cell="decoder", hidden_size=32, n_heads=4, n_kv_heads=2, head_dim=8,
        vocab_size=VOCAB, layer_layout=(3, 0, 3), rms_norm_eps=1e-5,
        moe_experts=0, ffn_size=48, hidden_act="silu", ssm_heads=4,
        ssm_head_dim=16, ssm_state=8, ssm_conv=4, ssm_chunk=CHUNK,
        tie_embeddings=True, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.25,
        logits_scaling=8.0, loss_chunk=16, dtype="float32", dropout=0.0),
        **over})


def _params(cfg, seed=0):
    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(seed)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # matrices wider than the family's N(0, 0.02), so that every path
    # matters at hidden 32; norm scales and the skip off one; rates, step
    # biases and taps as they are initialised
    keys = jax.random.split(jax.random.PRNGKey(seed + 1),
                            len(jax.tree.leaves(params)))
    flat = jax.tree_util.tree_leaves_with_path(params)
    tree = jax.tree.structure(params)
    wide = []
    for (path, leaf), key in zip(flat, keys):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("a_log", "dt_bias", "conv_w", "conv_b"):
            wide.append(leaf)
        elif leaf.ndim == 1:
            wide.append(1.0 + 0.1 * jax.random.normal(key, leaf.shape))
        else:
            wide.append(0.2 * jax.random.normal(key, leaf.shape))
    return model, jax.tree.unflatten(tree, wide)


def _ids(seed=3, n=SEQ + 1, batch=2):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, size=(batch, n)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def _program_loss(model, cfg, x, y, mask):
    task = NextToken(cfg, TrainConfig(batch_size=x.shape[0],
                                      window=x.shape[1]))
    batch = Batch(x, y, mask)

    def loss(p):
        with jax.default_matmul_precision("highest"):
            return task.loss(p, task.forward(model, p, batch, None), batch)[0]
    return loss


def test_the_parameter_tree_is_the_references():
    cfg = small_cfg()
    _, params = _params(cfg)
    assert "head" not in params and params["embed"].shape == (VOCAB, 32)
    assert set(params["block_0"]) == {
        "ln_attn", "w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
        "ln_gate", "w_out", "ln_mlp", "w_gate", "w_up", "w_down"}
    assert set(params["block_1"]) == {
        "ln_attn", "wq", "wk", "wv", "wo", "ln_mlp", "w_gate", "w_up",
        "w_down"}
    inner, n, heads = 64, 8, 4
    assert params["block_0"]["w_in"].shape == (32, 2 * inner + 2 * n + heads)
    assert params["block_0"]["conv_w"].shape == (inner + 2 * n, 4)
    # the state-space leaves start where the mechanism's code starts them
    _, fresh = build_model(cfg), build_model(cfg).init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, 8), jnp.int32))["params"]["block_0"]
    rates = np.exp(np.asarray(fresh["a_log"]))
    steps = np.asarray(jax.nn.softplus(fresh["dt_bias"]))
    assert (rates >= 1).all() and (rates <= 16).all()
    assert (steps >= 1e-3 * 0.999).all() and (steps <= 1e-1 * 1.001).all()
    assert (np.asarray(fresh["d_skip"]) == 1).all()
    assert np.abs(np.asarray(fresh["conv_w"])).max() <= 0.5


@pytest.mark.parametrize("layout", [(3, 0, 3), (3, 3), (0,)])
def test_logits_match_the_reference(layout):
    cfg = small_cfg(layer_layout=layout)
    model, params = _params(cfg)
    x, _ = _ids()
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, x)
    assert got.shape == (2, SEQ, VOCAB) and got.dtype == jnp.float32
    for b in range(x.shape[0]):
        want = ref.logits(params, x[b], cfg)
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-5)


# three chunks, the last one short; twelve chunks, which the scan walks
# as three groups of four carrying the state from one to the next; and
# thirteen with the last one short, thirteen groups of one chunk
@pytest.mark.parametrize("seq,chunk", [(SEQ, CHUNK), (96, 8), (100, 8)])
def test_loss_and_every_leafs_gradient_match_the_reference(seq, chunk):
    cfg = small_cfg(remat=True, ssm_chunk=chunk)
    model, params = _params(cfg)
    x, y = _ids(n=seq + 1)
    mask = jnp.ones(x.shape, jnp.float32).at[1, seq - 10:].set(0.0)
    got, got_grads = jax.jit(jax.value_and_grad(
        _program_loss(model, cfg, x, y, mask)))(params)
    want, want_grads = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        rel = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert rel < 2e-4, (path, rel)


def test_conv_silu_gives_the_logits_and_gradients_of_the_form_as_written(
        monkeypatch):
    """The mixer as it runs (``conv_silu``: the padding in the stream's
    dtype, a backward written out in float32) against the mixer with
    ``silu(causal_conv(...))`` as written and autodiff through it: the
    logits bit for bit, the loss and every leaf's gradient inside the
    limits the reference is held to above."""
    from fmda_tpu.models import decoder
    from fmda_tpu.ops import ssd

    cfg = small_cfg(remat=True, ssm_head_dim=24, ssm_state=16)
    model, params = _params(cfg)
    x, y = _ids()
    mask = jnp.ones(x.shape, jnp.float32).at[1, SEQ - 10:].set(0.0)

    def run():
        with jax.default_matmul_precision("highest"):
            logits = model.apply({"params": params}, x)
        return logits, jax.value_and_grad(
            _program_loss(model, cfg, x, y, mask))(params)

    got_logits, (got, got_grads) = run()
    calls = []

    def as_written(x, w, bias, *, dtype):
        calls.append(x.shape)
        return jax.nn.silu(ssd.causal_conv(x, w, bias)).astype(dtype)

    monkeypatch.setattr(decoder, "conv_silu", as_written)
    want_logits, (want, want_grads) = run()
    assert calls and all(shape == (2, SEQ, 128) for shape in calls)
    np.testing.assert_array_equal(got_logits, want_logits)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        rel = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert rel < 2e-4, (path, rel)


def test_the_references_layerwise_backward_is_the_whole_graphs():
    cfg = small_cfg()
    _, params = _params(cfg)
    x, y = _ids()
    mask = jnp.ones(x.shape, jnp.float32).at[1, 30:].set(0.0)
    want, want_grads = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg))(params)
    got, got_grads = ref.loss_and_grads_by_layer(params, x, y, mask, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(w).max()) + 1e-12
        assert float(np.abs(g - w).max()) <= 1e-5 * scale, path


def test_the_references_segmented_scan_is_the_plain_one(monkeypatch):
    cfg = small_cfg(layer_layout=(3,))
    _, params = _params(cfg)
    x, y = _ids(batch=1)
    mask = jnp.ones(x.shape, jnp.float32)
    want = ref.loss_and_grads(params, x, y, mask, cfg)
    monkeypatch.setattr(ref, "SEGMENT", 8)  # five segments of the 40
    got = ref.loss_and_grads(params, x, y, mask, cfg)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)


def test_the_tied_head_is_one_leaf_with_a_gradient_from_both_uses():
    cfg = small_cfg()
    model, params = _params(cfg)
    # ids from the lower half only: the upper rows are reached through
    # the head's use alone
    rng = np.random.default_rng(0)
    ids = rng.integers(0, VOCAB // 2, size=(1, SEQ + 1)).astype(np.int32)
    x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
    mask = jnp.ones(x.shape, jnp.float32)
    grads = jax.grad(_program_loss(model, cfg, x, y, mask))(params)
    assert "head" not in grads
    by_row = np.abs(np.asarray(grads["embed"])).max(axis=1)
    assert (by_row[VOCAB // 2:] > 0).all()          # the head's use
    # ... and an untied model's unused rows get none
    untied = small_cfg(tie_embeddings=False)
    model_u, params_u = _params(untied)
    assert params_u["head"].shape == (32, VOCAB)
    grads_u = jax.grad(_program_loss(model_u, untied, x, y, mask))(params_u)
    by_row_u = np.abs(np.asarray(grads_u["embed"])).max(axis=1)
    assert (by_row_u[VOCAB // 2:] == 0).all()
    assert (by_row_u[np.unique(np.asarray(x))] > 0).all()
    # the tied leaf's gradient is the sum of the two uses': the reference
    # computes them apart (the head's, then the rows')
    want = ref.loss_and_grads_by_layer(params, x, y, mask, cfg)[1]["embed"]
    np.testing.assert_allclose(grads["embed"], want, rtol=2e-3, atol=1e-6)


@pytest.mark.parametrize("field,neutral", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", None), ("logits_scaling", 1.0)])
def test_each_multiplier_changes_the_output(field, neutral):
    cfg = small_cfg()
    model, params = _params(cfg)
    x, _ = _ids(batch=1)
    base = model.apply({"params": params}, x)
    other_cfg = small_cfg(**{field: neutral})
    other = build_model(other_cfg).apply({"params": params}, x)
    assert float(jnp.abs(base - other).max()) > 1e-3
    # and the reference, told to leave it out, computes the other model
    want = ref.logits(params, x[0], cfg, leave_out=field)
    with jax.default_matmul_precision("highest"):
        other = build_model(other_cfg).apply({"params": params}, x)
    np.testing.assert_allclose(other[0], want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("wrong", [
    {"products_as": "float8_e5m2"}, {"state_as": "bfloat16"},
    {"drop_state_every": CHUNK}, {"conv_ahead": 1},
    {"leave_out": "d_skip"}, {"leave_out": "gate"}],
    ids=lambda w: "-".join(f"{k}={v}" for k, v in w.items()))
def test_the_references_wrong_runs_move_its_loss_and_gradient(wrong):
    cfg = small_cfg()
    _, params = _params(cfg)
    x, y = _ids(batch=1)
    mask = jnp.ones(x.shape, jnp.float32)
    loss, grads = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg))(params)
    wrong_loss, wrong_grads = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg, **wrong))(params)
    assert np.isfinite(float(wrong_loss)) and wrong_loss != loss
    g, w = grads["block_0"]["w_in"], wrong_grads["block_0"]["w_in"]
    rel = float(jnp.linalg.norm(w - g) / jnp.linalg.norm(g))
    # a rounded state's error grows with the positions it is carried
    # over: 40 here, 8,192 where the benchmark's limits have to catch it
    assert rel > (2e-5 if "state_as" in wrong else 1e-3), rel


def test_a_pass_publishes_what_the_scans_walked_and_no_routing_counter():
    from fmda_tpu.obs.registry import default_registry
    from fmda_tpu.train.trainer import Trainer

    cfg = small_cfg()
    tc = TrainConfig(batch_size=2, window=SEQ, chunk_size=2 * SEQ,
                     learning_rate=1e-3, val_size=0.2, test_size=0.2,
                     cache_chunks=8, seed=0)
    ids = np.random.default_rng(0).integers(0, VOCAB, 10 * SEQ + 1)
    reg = default_registry()

    def read(name, layer):
        return reg.counter(name, layer=str(layer), phase="train").value

    before = {(n, l): read(n, l) for l in (0, 1, 2) for n in (
        "ssd_chunks_total", "ssd_positions_total", "moe_pairs_held_total")}
    dropped = reg.counter("moe_pairs_dropped_total").value
    trainer = Trainer(cfg, tc)
    totals = trainer.zero_totals()
    assert totals.expert_pairs is None and totals.dropped is None
    assert totals.row_tiles_used is None and totals.sparse_keys_kept is None
    assert totals.ssd_chunks.shape == totals.ssd_positions.shape == (3,)
    _, hist, dataset = trainer.fit(TokenArraySource(ids, VOCAB), epochs=1)
    train, _, _ = dataset.split(tc.val_size, tc.test_size)
    n_rows = 2 * sum(len(trainer.task.batches(dataset, i)) for i in train)
    for layer in (0, 2):
        assert read("ssd_positions_total", layer) - before[
            "ssd_positions_total", layer] == n_rows * SEQ
        assert read("ssd_chunks_total", layer) - before[
            "ssd_chunks_total", layer] == n_rows * -(-SEQ // CHUNK)
    # the attention layer walks no scan, and no layer has experts
    assert read("ssd_positions_total", 1) == before["ssd_positions_total", 1]
    for layer in (0, 1, 2):
        assert read("moe_pairs_held_total", layer) == before[
            "moe_pairs_held_total", layer]
    assert reg.counter("moe_pairs_dropped_total").value == dropped
    assert reg.gauge("ssd_state_bytes").value == 3 * 4 * 16 * 8 * 4
    assert np.isfinite(hist["train"][0].loss)


@pytest.mark.parametrize("over,named", [
    (dict(ssm_heads=0), "ssm_heads"),
    (dict(ssm_head_dim=0), "ssm_head_dim"),
    (dict(ssm_state=0), "ssm_state"),
    (dict(ssm_conv=0), "ssm_conv"),
    (dict(ssm_chunk=0), "ssm_chunk"),
    (dict(ffn_size=0), "ffn_size"),
    (dict(residual_multiplier=0.0), "residual_multiplier"),
    (dict(attention_multiplier=-1.0), "attention_multiplier"),
    (dict(layer_layout=(3, 4)), "layer_layout"),
])
def test_config_errors_name_the_field(over, named):
    with pytest.raises(ValueError, match=named) as err:
        check_decoder_config(small_cfg(**over))
    # and nothing it does not need: a dense model is not asked for experts
    assert "moe_top_k" not in str(err.value)
    assert "experts_held" not in str(err.value)


def test_a_model_without_a_state_space_layer_needs_no_ssm_size():
    check_decoder_config(small_cfg(
        layer_layout=(0, 0), ssm_heads=0, ssm_head_dim=0, ssm_state=0,
        ssm_conv=0, ssm_chunk=0))


def test_cli_train_takes_the_benchmark_configurations_framework_block(
        tmp_path, capsys):
    """``python -m fmda_tpu train --tokens`` accepts the ``framework``
    block of benchmark/configs/granite_4_0_h_micro_pp4.json as written:
    the file's own keys parse, the published widths count 772,160,448
    parameters, and a copy cut to test size trains."""
    from fmda_tpu.cli import main
    from fmda_tpu.config import config_from_dict

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite_4_0_h_micro_pp4.json")) as fh:
        framework = json.load(fh)["framework"]
    full = config_from_dict(framework)
    check_decoder_config(full.model)
    assert full.model.layer_layout == (3, 3, 3, 3, 3, 0, 3, 3, 3, 3)
    shapes = jax.eval_shape(
        lambda key: build_model(full.model).init(
            {"params": key}, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    assert sum(int(np.prod(l.shape))
               for l in jax.tree.leaves(shapes)) == 772_160_448
    small = small_cfg()
    framework["model"].update({
        k: v for k, v in config_to_dict(FrameworkConfig(model=small))[
            "model"].items() if k in framework["model"]})
    framework["train"].update(window=SEQ, chunk_size=SEQ, epochs=1)
    cfg_path, tokens = tmp_path / "cfg.json", tmp_path / "tokens.npy"
    cfg_path.write_text(json.dumps(framework))
    np.save(tokens, np.random.default_rng(0).integers(0, VOCAB, 10 * SEQ + 1))
    rc = main(["train", "--config", str(cfg_path), "--platform", "cpu",
               "--tokens", str(tokens),
               "--checkpoint-dir", str(tmp_path / "ckpt")])
    out = capsys.readouterr()
    assert rc == 0, out.err
    assert "trained 1 epochs" in out.out
