"""Gated-delta-rule layers (one decay a head, a correction that may
overshoot, keys and values of different widths) beside unrotated full
attention under whole-width q/k norms, a dense MLP and the block's norms
on the sublayers' outputs (Olmo-Hybrid-7B's layers: ``layer_layout`` 6
and 0, ``post_norm``, ``qk_norm_whole``; models/decoder.py, ops/kda.py,
train/tasks.py) against its plain reference
(benchmark/reference/gdn_decoder.py, the recurrence stepwise), on the CPU
at small widths and seeded weights: logits, the loss, every leaf's
gradient, one optimizer step; the reference's wrong runs; the
configuration's rules; the two shares of a layer split by head; scopes,
counters and what a pass publishes.  (The chunked walk alone is in
tests/test_kda.py.)"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import gdn_decoder as ref  # noqa: E402
from test_kda_decoder import _leaf_names, _wide_params  # noqa: E402
from fmda_tpu.config import ModelConfig, TrainConfig  # noqa: E402
from fmda_tpu.data.pipeline import Batch  # noqa: E402
from fmda_tpu.data.source import TokenArraySource  # noqa: E402
from fmda_tpu.models import build_model  # noqa: E402
from fmda_tpu.models.decoder import (  # noqa: E402
    KINDS, check_decoder_config, model_counts)
from fmda_tpu.train.tasks import NextToken  # noqa: E402

SEQ, VOCAB, D = 24, 96, 32
HEADS, DK, DV, HD = 4, 6, 12, 8


def small_cfg(**over):
    """A delta-rule layer, the attention layer, a delta-rule layer; four
    heads of 6 | 12 and of 8; three chunks of eight."""
    return ModelConfig(**{**dict(
        cell="decoder", hidden_size=D, n_heads=HEADS, n_kv_heads=HEADS,
        head_dim=HD, vocab_size=VOCAB, layer_layout=(6, 0, 6),
        rms_norm_eps=1e-6, gdn_heads=HEADS, gdn_key_dim=DK,
        gdn_value_dim=DV, gdn_conv=4, gdn_chunk=8, gdn_beta_scale=2.0,
        post_norm=True, qk_norm_whole=True, moe_experts=0, ffn_size=48,
        hidden_act="silu", loss_chunk=16, dtype="float32", dropout=0.0),
        **over})


_MADE = {}


def _params(cfg, seed=0):
    model = build_model(cfg)
    if seed not in _MADE:  # the tree does not depend on what ``cfg`` holds
        _MADE[seed] = _wide_params(build_model(small_cfg()), seed)
    return model, _MADE[seed]


def _ids(seed=3, n=SEQ + 1, batch=2):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, size=(batch, n)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


@pytest.fixture(scope="module")
def right():
    """The right reference on two whole sequences."""
    cfg = small_cfg()
    _, params = _params(cfg)
    x, y = _ids()
    mask = jnp.ones(x.shape, jnp.float32)
    return (params, x, y, mask, cfg), jax.jit(
        lambda p: ref.loss_and_grads(p, x, y, mask, cfg))(params)


def test_the_parameter_tree_has_both_kinds_of_layer():
    cfg = small_cfg()
    _, params = _params(cfg)
    gdn, full = params["block_2"], params["block_1"]
    assert gdn["wq"].shape == gdn["wk"].shape == (D, HEADS * DK)
    assert gdn["wv"].shape == gdn["wg"].shape == (D, HEADS * DV)
    assert gdn["wo"].shape == (HEADS * DV, D)
    assert gdn["conv_q"].shape == gdn["conv_k"].shape == (HEADS * DK, 4)
    assert gdn["conv_v"].shape == (HEADS * DV, 4) and "conv_b" not in gdn
    # ONE decay and one step a head, straight from the stream
    assert gdn["wa"].shape == gdn["wb"].shape == (D, HEADS)
    assert gdn["dt_bias"].shape == gdn["a_log"].shape == (HEADS,)
    assert gdn["o_norm"].shape == (DV,)
    # the attention layer's norms over the whole width of q and of k
    assert full["wq"].shape == full["wk"].shape == (D, HEADS * HD)
    assert full["q_norm"].shape == full["k_norm"].shape == (HEADS * HD,)
    for block in (gdn, full):  # the same dense feed-forward under either
        assert block["w_gate"].shape == (D, 48) and "router" not in block
        assert block["ln_attn"].shape == block["ln_mlp"].shape == (D,)
    assert params["head"].shape == (D, VOCAB)  # untied


def test_logits_match_the_reference_and_not_a_rotated_or_pre_norm_one():
    cfg = small_cfg()
    model, params = _params(cfg)
    x, _ = _ids()
    apply = jax.jit(lambda p: model.apply({"params": p}, x))
    with jax.default_matmul_precision("highest"):
        got = apply(params)
        # the same leaves under input norms are another model: the flag
        pre = jax.jit(lambda p: build_model(small_cfg(post_norm=False)).apply(
            {"params": p}, x))(params)
    want = jax.jit(lambda p, ids: ref.logits(p, ids, cfg))
    turned = jax.jit(lambda p, ids: ref.logits(p, ids, cfg, rotary=True))
    pre_ref = jax.jit(lambda p, ids: ref.logits(p, ids, cfg, pre_norm=True))
    for b in range(2):
        np.testing.assert_allclose(got[b], want(params, x[b]), rtol=2e-4,
                                   atol=2e-5)
        assert float(jnp.abs(got[b] - turned(params, x[b])).max()) > 1e-2
        np.testing.assert_allclose(pre[b], pre_ref(params, x[b]), rtol=2e-4,
                                   atol=2e-5)
        assert float(jnp.abs(got[b] - pre[b]).max()) > 1e-2
    # no rotary in the attention layer: its lowered text has no such scope
    text = apply.lower(params).as_text(debug_info=True)
    assert "attention_full" in text and "/rope" not in text


@pytest.mark.parametrize("remat", [False, True])
def test_loss_counts_and_every_leafs_gradient_match_the_reference(
        right, remat):
    (params, x, y, mask, _), (want, want_grads) = right
    cfg = small_cfg(remat=remat)
    model = build_model(cfg)
    batch = Batch(x, y, mask)
    task = NextToken(cfg, TrainConfig(batch_size=2, window=SEQ))

    def loss(p):
        with jax.default_matmul_precision("highest"):
            value, aux = task.loss(
                p, task.forward(model, p, batch, None), batch)
            return value, task.step_values(value, aux, batch)

    (got, values), got_grads = jax.jit(
        jax.value_and_grad(loss, has_aux=True))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    for name, g, w in zip(_leaf_names(got_grads), jax.tree.leaves(got_grads),
                          jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(g - w)) < 3e-4 * float(
            jnp.linalg.norm(w)), name
    np.testing.assert_array_equal(values.gdn_chunks, [6, 0, 6])
    np.testing.assert_array_equal(values.gdn_positions, [48, 0, 48])
    assert float(values.gdn_log_decay_absmax[1]) == 0.0
    assert min(float(values.gdn_log_decay_absmax[i]) for i in (0, 2)) > 0.05
    # the correction overshoots somewhere in both delta-rule layers
    assert float(values.gdn_beta_max[1]) == 0.0
    assert all(1.0 < float(values.gdn_beta_max[i]) <= 2.0 for i in (0, 2))


WRONG = {
    "no_decay": dict(decay="none"),
    "no_correction": dict(correction=False),
    "no_overshoot": dict(overshoot=False),
    "qk_not_normalised": dict(qk_norm=False),
    "gate_under_sigmoid": dict(gate="sigmoid"),
    "block_pre_norm": dict(pre_norm=True),
    "no_qk_rms_norm": dict(qk_rms=False),
    "rotary": dict(rotary=True),
    "state_in_bfloat16": dict(state_as="bfloat16"),
    "products_in_float8": dict(products_as="float8_e5m2"),
}


@pytest.fixture(scope="module")
def wrong_losses(right):
    """Every wrong run's loss, one compiled program for all of them."""
    params, x, y, mask, cfg = right[0]
    return jax.jit(lambda p: {
        name: ref.batch_loss(p, x, y, mask, cfg, **kw)
        for name, kw in WRONG.items()})(params)


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_the_references_wrong_runs_move_its_loss(right, wrong_losses, wrong):
    loss, moved = right[1][0], wrong_losses[wrong]
    assert abs(float(moved) - float(loss)) > (
        2e-6 if wrong == "state_in_bfloat16" else 1e-4), (moved, loss)


def test_the_references_layerwise_backward_is_the_whole_graphs(right):
    given, (want, want_grads) = right
    got, got_grads = ref.loss_and_grads_by_layer(*given)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for name, g, w in zip(_leaf_names(got_grads), jax.tree.leaves(got_grads),
                          jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-6, err_msg=name)


def _trainer(cfg, **over):
    from fmda_tpu.train.trainer import Trainer

    tc = TrainConfig(**{**dict(
        batch_size=2, window=SEQ, chunk_size=2 * SEQ, learning_rate=1e-3,
        clip=1e9, val_size=0.2, test_size=0.2, cache_chunks=8, seed=0),
        **over})
    ids = np.random.default_rng(0).integers(0, VOCAB, 10 * SEQ + 1)
    trainer = Trainer(cfg, tc)
    dataset = trainer.task.dataset(TokenArraySource(ids, VOCAB))
    return trainer, dataset, next(iter(trainer._chunk_batches(dataset, 0)))


def test_one_step_is_the_references_adam_step_and_is_traced():
    """``Trainer.single_step`` on fresh parameters: Adam's first moment is
    the reference's gradient and every leaf moves by the reference's plain
    Adam step; the step's lowered text has the mixer's scopes, forward and
    backward, the walk's four under ``gdn_scan``; the pass publishes its
    four counts a layer."""
    import optax

    from fmda_tpu.obs.registry import default_registry

    trainer, _, batch = _trainer(small_cfg(remat=True))
    cfg, tc = trainer.model_cfg, trainer.train_cfg
    state = trainer.init_state(jax.random.PRNGKey(0))
    before = jax.device_get(state.params)
    # a fresh mixer: the rates in 1..16 and the steps in 1e-3..1e-1 a
    # head, the taps in +-1/sqrt(4)
    fresh = before["block_2"]
    rates, steps = np.exp(fresh["a_log"]), np.log1p(np.exp(fresh["dt_bias"]))
    assert 1.0 <= rates.min() and rates.max() <= 16.0
    assert 1e-3 <= steps.min() * 1.001 and steps.max() <= 1e-1 * 1.001
    assert np.abs(fresh["conv_k"]).max() <= 0.5
    with jax.default_matmul_precision("highest"):  # one trace for both
        text = trainer._train_step._jit.lower(
            state, trainer.zero_totals(), batch,
            jax.random.PRNGKey(1)).as_text(debug_info=True)
        after, totals = trainer.single_step(state, batch,
                                            jax.random.PRNGKey(1))
    loss, grads = ref.loss_and_grads_by_layer(
        before, batch.x, batch.y, batch.mask, cfg)
    np.testing.assert_allclose(float(totals.loss), loss, rtol=1e-5)
    want_g, want_change = ref.first_adam_step(
        grads, learning_rate=tc.learning_rate, clip=tc.clip)
    mu = optax.tree_utils.tree_get(after.opt_state, "mu")
    for name, m, g, a, b, d in zip(
            _leaf_names(mu), jax.tree.leaves(mu), jax.tree.leaves(want_g),
            jax.tree.leaves(jax.device_get(after.params)),
            jax.tree.leaves(before), jax.tree.leaves(want_change)):
        np.testing.assert_allclose(m / 0.1, g, rtol=2e-3, atol=2e-7,
                                   err_msg=name)
        live = np.abs(g) > 1e-5  # beside Adam's eps a step shows rounding
        np.testing.assert_allclose((a - b)[live], d[live], rtol=2e-2,
                                   err_msg=name)

    paths = set(re.findall(r'"(jit\([^"]*)"', text))
    for scope in ("gdn_proj", "gdn_conv", "gdn_gates", "gdn_out_norm",
                  "gdn_scan/kda_intra", "gdn_scan/kda_solve",
                  "gdn_scan/kda_carry", "gdn_scan/kda_out"):
        under = [p for p in paths if "/gdn_mixer/" in p and all(
            f"/{part}/" in p for part in scope.split("/"))]
        assert any("transpose(jvp(" in p for p in under), scope  # backward
        assert any("transpose(" not in p for p in under), scope  # forward
    assert not [p for p in paths if "/kda_mixer/" in p]

    reg = default_registry()
    trainer.task.publish(totals, "train", 1)
    for layer in (0, 2):
        labels = dict(layer=str(layer), phase="train")
        assert reg.counter("gdn_chunks_total", **labels).value == 6
        assert reg.counter("gdn_positions_total", **labels).value == 48
        assert reg.gauge("gdn_log_decay_absmax", **labels).value == (
            pytest.approx(float(totals.gdn_log_decay_absmax[layer])))
        assert reg.gauge("gdn_beta_max", **labels).value == (
            pytest.approx(float(totals.gdn_beta_max[layer])))
        assert float(totals.gdn_beta_max[layer]) > 1.0
    assert reg.counter("gdn_chunks_total", layer="1",
                       phase="train").value == 0


@pytest.mark.parametrize("over,named", [
    (dict(gdn_heads=0),
     "gdn_heads (layer_layout has a gated-delta-rule layer)"),
    (dict(gdn_key_dim=0), "gdn_key_dim"),
    (dict(gdn_value_dim=0), "gdn_value_dim"),
    (dict(gdn_conv=0), "gdn_conv"),
    (dict(gdn_chunk=0), "gdn_chunk"),
    (dict(gdn_beta_scale=0.0), "gdn_beta_scale"),
    (dict(layer_layout=(6, 4)), "layer_layout"),
    (dict(layer_layout=(5, 6)), "layer_layout"),
    (dict(layer_layout=(6, 6), qk_norm_whole=True), "qk_norm_whole"),
    (dict(head_dim=0), "head_dim"),
])
def test_config_errors_name_the_field(over, named):
    with pytest.raises(ValueError, match=re.escape(named)):
        check_decoder_config(small_cfg(**over))


def test_post_norm_is_a_plain_residuals():
    lanes = dict(layer_layout=(4, 4), hc_streams=4, kv_lora_rank=16,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
                 qk_norm_whole=False)
    with pytest.raises(ValueError, match="post_norm"):
        check_decoder_config(small_cfg(**lanes))
    check_decoder_config(small_cfg(**lanes, post_norm=False))


def test_kinds_0_and_6_in_one_model_are_accepted_and_declare_their_counts():
    for layout in ((6, 0, 6), (6, 6, 6, 0), (0, 6), (6, 1, 3)):
        check_decoder_config(small_cfg(
            layer_layout=layout, ssm_heads=2, ssm_head_dim=8, ssm_state=4,
            ssm_conv=4, ssm_chunk=8))
    declared = model_counts(small_cfg(layer_layout=(6, 6, 6, 0)))
    assert list(declared) == ["gdn_chunks", "gdn_positions",
                              "gdn_log_decay_absmax", "gdn_beta_max"]
    assert declared["gdn_chunks"].layers == (0, 1, 2)
    assert [declared[name].count.fold for name in declared] == [
        "sum", "sum", "max", "max"]
    # without a layer of the kind nothing of it is declared or read
    plain = small_cfg(layer_layout=(0, 0), gdn_heads=0)
    check_decoder_config(plain)
    assert not model_counts(plain)


def _columns(w, heads, first, count):
    """The columns (last axis) of ``w`` that belong to ``count`` heads
    from ``first``: a head's run of the projection's width."""
    width = w.shape[-1] // heads
    return w[..., first * width:(first + count) * width]


def _share(p, kind, heads, first, count):
    """Heads ``first .. first + count`` of a mixer's leaves: their columns
    of the products that make heads, their taps and scalars, their rows of
    ``wo``; a norm's scale over one head's width whole."""
    rows = lambda w: _columns(w.T, heads, first, count).T  # noqa: E731
    if kind == 6:
        by_column = ("wq", "wk", "wv", "wg", "wa", "wb")
        by_row = ("conv_q", "conv_k", "conv_v", "a_log", "dt_bias", "wo")
    else:
        by_column = ("wq", "wk", "wv", "q_norm", "k_norm")
        by_row = ("wo",)
    return {**p, **{k: _columns(p[k], heads, first, count)
                    for k in by_column},
            **{k: rows(p[k]) for k in by_row}}


def test_the_two_shares_by_head_add_up_to_the_uncut_mixers():
    """Two chips share a layer by head.  Taken BEFORE the block's norm,
    where the deployment's reduction sits, the mixer outputs of heads
    0..1 and 2..3 add up to the uncut reference's: for the delta-rule
    mixer as it stands (nothing in it crosses heads; the program's mixer,
    given a share, is that part too), and for the attention mixer once
    each share is handed the WHOLE width's mean squares of q and of k,
    the one thing the pair reduces before the core (over its own half a
    share's norm is another number, which is what the one-chip cell
    computes and the configuration's file says)."""
    import flax.linen as nn

    cfg = small_cfg()
    _, params = _params(cfg)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(SEQ, D)),
                    jnp.float32)
    half = small_cfg(gdn_heads=HEADS // 2, n_heads=HEADS // 2,
                     n_kv_heads=HEADS // 2)

    class Mixer(nn.Module):  # the program's mixer alone, no block norm
        cfg: ModelConfig
        kind: int

        @nn.compact
        def __call__(self, h):
            return KINDS[self.kind].mixer(self, self.cfg, h)[0]

    with jax.default_matmul_precision("highest"):
        p = params["block_2"]
        want = jax.jit(lambda q: ref.delta_mixer(q, x, cfg, False, {}))(p)
        parts = []
        for first in (0, HEADS // 2):
            held = _share(p, 6, HEADS, first, HEADS // 2)
            part = jax.jit(lambda q: ref.delta_mixer(
                q, x, half, False, {}))(held)
            mixer_leaves = {k: v for k, v in held.items()
                            if k not in ("ln_attn", "ln_mlp", "w_gate",
                                         "w_up", "w_down")}
            got = jax.jit(lambda q: Mixer(half, 6).apply(
                {"params": q}, x[None]))(mixer_leaves)
            np.testing.assert_allclose(got[0], part, rtol=2e-4, atol=2e-5)
            parts.append(part)
        np.testing.assert_allclose(parts[0] + parts[1], want, rtol=1e-5,
                                   atol=1e-6)
        assert float(jnp.abs(parts[0]).max()) > 1e-2  # both halves matter
        assert float(jnp.abs(parts[1]).max()) > 1e-2

        p = params["block_1"]
        want = jax.jit(lambda q: ref.attention(q, x, cfg, False, {}))(p)
        squares = tuple(jnp.mean(jnp.square(x @ p[k]), -1, keepdims=True)
                        for k in ("wq", "wk"))
        handed, alone = [], []
        for first in (0, HEADS // 2):
            held = _share(p, 0, HEADS, first, HEADS // 2)
            handed.append(jax.jit(lambda q: ref.attention(
                q, x, half, False, {}, squares=squares))(held))
            alone.append(jax.jit(lambda q: ref.attention(
                q, x, half, False, {}))(held))
        np.testing.assert_allclose(handed[0] + handed[1], want, rtol=1e-5,
                                   atol=1e-6)
        # without the reduction a share's norm is over its own half
        assert float(jnp.abs(alone[0] + alone[1] - want).max()) > 1e-3
