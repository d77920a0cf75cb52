"""The latent-attention decoder with a hyper-connected residual stream
(``layer_layout`` 4, models/decoder.py) against its plain reference
(benchmark/reference/latent_decoder.py), on the CPU at small widths and
seeded weights: logits, loss, every leaf's gradient, one optimizer step
and the selection bias's step; the share test; Sinkhorn's turns; a fresh
hyper-connection against the plain residual; and the configuration's
errors.  (The accepted configurations' pinned programs are in
tests/test_decoder.py.)"""

import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import latent_decoder as ref  # noqa: E402
from fmda_tpu.config import ModelConfig, TrainConfig  # noqa: E402
from fmda_tpu.data.pipeline import Batch  # noqa: E402
from fmda_tpu.data.source import TokenArraySource  # noqa: E402
from fmda_tpu.models import build_model  # noqa: E402
from fmda_tpu.models.decoder import (  # noqa: E402
    DecoderBlock, check_decoder_config, feed_forward, score_scale,
    yarn_inv_freq)
from fmda_tpu.ops import hyper_connection as hc  # noqa: E402
from fmda_tpu.train.tasks import NextToken  # noqa: E402

SEQ, VOCAB, EXPERTS = 40, 96, 8


def small_cfg(**over):
    """One dense and two expert layers, four lanes, three of eight
    experts held, every new field off its default."""
    return ModelConfig(**{**dict(
        cell="decoder", hidden_size=32, n_heads=4, vocab_size=VOCAB,
        layer_layout=(4, 4, 4), rms_norm_eps=1e-5, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12, rope_theta=10000.0, rope_factor=64.0,
        rope_original_max=64, moe_experts=EXPERTS, moe_top_k=2,
        moe_ffn_size=16, experts_held=(2, 3), hidden_act="silu", ffn_size=48,
        first_dense_layers=1, moe_shared_experts=1, moe_scoring="sigmoid",
        moe_routed_scaling=2.0, moe_bias_rate=1e-3, hc_streams=4,
        loss_chunk=16, dtype="float32", dropout=0.0), **over})


def _params(cfg, seed=0, offsets=0.7):
    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(seed)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # matrices wider than the family's N(0, 0.02), so that every path
    # matters at hidden 32; norm scales off one; the mixing's offsets
    # shrunk and its gains raised, so that every lane is read, written
    # and remixed and Sinkhorn has work to do; a selection bias that
    # decides some top-2
    keys = jax.random.split(jax.random.PRNGKey(seed + 1),
                            len(jax.tree.leaves(params)))
    wide = []
    for (path, leaf), key in zip(
            jax.tree_util.tree_leaves_with_path(params), keys):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "router_bias":
            wide.append(0.05 * jax.random.normal(key, leaf.shape))
        elif name.startswith("hc_") and "_b_" in name:
            wide.append(offsets / 6.0 * leaf
                        + 0.1 * jax.random.normal(key, leaf.shape))
        elif name.startswith("hc_") and "_a_" in name:
            wide.append(jnp.full((), 0.5))
        elif leaf.ndim == 1:
            wide.append(1.0 + 0.1 * jax.random.normal(key, leaf.shape))
        else:
            wide.append(0.2 * jax.random.normal(key, leaf.shape))
    return model, jax.tree.unflatten(jax.tree.structure(params), wide)


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


def test_the_parameter_tree_is_the_references_and_starts_as_stated():
    cfg = small_cfg()
    fresh = build_model(cfg).init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, 8), jnp.int32))["params"]
    mixing = {f"hc_{s}_{k}" for s in ("attn", "ffn") for k in (
        "p_pre", "p_post", "p_res", "a_pre", "a_post", "a_res", "b_pre",
        "b_post", "b_res")}
    attention = {"ln_attn", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
                 "wkv_b", "wo"}
    assert set(fresh["block_0"]) == mixing | attention | {
        "ln_mlp", "w_gate", "w_up", "w_down"}
    assert set(fresh["block_1"]) == mixing | attention | {
        "ln_moe", "router", "router_bias", "ws_gate", "ws_up", "ws_down",
        "w_gate", "w_up", "w_down"}
    b1 = fresh["block_1"]
    assert b1["wq_b"].shape == (24, 4 * (16 + 8))
    assert b1["wkv_a"].shape == (32, 16 + 8)
    assert b1["wkv_b"].shape == (16, 4 * (16 + 12))
    assert b1["wo"].shape == (4 * 12, 32)
    assert b1["w_gate"].shape == (3, 32, 16) and b1["router"].shape == (32, 8)
    assert fresh["block_0"]["w_gate"].shape == (32, 48)
    assert b1["hc_attn_p_res"].shape == (4 * 32, 16)
    # a fresh mixing reads and writes lane 0 and remixes by the identity
    assert (np.asarray(b1["router_bias"]) == 0).all()
    np.testing.assert_array_equal(b1["hc_ffn_b_pre"], [6, -6, -6, -6])
    np.testing.assert_array_equal(b1["hc_ffn_b_post"], [0, -6, -6, -6])
    np.testing.assert_array_equal(b1["hc_ffn_b_res"], 12 * np.eye(4) - 6)
    assert float(b1["hc_attn_a_res"]) == pytest.approx(0.01)


@pytest.mark.parametrize("over", [
    {}, dict(hc_streams=1), dict(first_dense_layers=0, moe_bias_rate=0.0),
    dict(moe_experts=0, moe_top_k=0, first_dense_layers=0,
         moe_shared_experts=0, moe_scoring="softmax", moe_routed_scaling=1.0,
         moe_bias_rate=0.0, rope_factor=1.0)],
    ids=["as_published", "one_lane", "no_dense_no_bias", "dense_plain_rope"])
def test_logits_match_the_reference(over):
    cfg = small_cfg(**over)
    model, params = _params(cfg)
    x, _ = _ids()
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, x)
    assert got.shape == (2, SEQ, VOCAB) and got.dtype == jnp.float32
    for b in range(x.shape[0]):
        want = ref.logits(params, x[b], cfg)
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("remat", [False, True])
def test_loss_counts_and_every_leafs_gradient_match_the_reference(remat):
    cfg = small_cfg(remat=remat)
    model, params = _params(cfg)
    x, y = _ids()
    mask = jnp.ones(x.shape, jnp.float32).at[1, SEQ - 10:].set(0.0)
    got, got_grads = jax.jit(jax.value_and_grad(
        _program_loss(model, cfg, x, y, mask)))(params)
    want, want_grads = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    whole = sum(float((w * w).sum()) for w in jax.tree.leaves(want_grads)) ** .5
    quiet = []
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.endswith("router_bias"):  # no gradient reaches it
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        # a leaf whose gradient is zero but for rounding is held to the
        # whole gradient's size, the others to their own
        apart = float(jnp.linalg.norm(g - w))
        if float(jnp.linalg.norm(w)) < 1e-5 * whole:
            quiet.append(name)
            assert apart < 1e-7 * whole, (name, apart / whole)
        else:
            assert apart < 3e-4 * float(jnp.linalg.norm(w)), name
    # those are: the first sublayer's read (every lane is still the
    # token's row, and the norm that follows forgets the mix's size) and
    # the last sublayer's remix (its columns sum to one, and the exit
    # sums the lanes)
    assert all("/hc_" in n for n in quiet), quiet
    assert "block_2/hc_ffn_p_res" in quiet and "block_0/hc_attn_b_pre" in quiet
    # what the layers counted: the reference's, a sequence at a time
    _, stats = model.apply({"params": params}, x, method="features")
    pairs = load = 0
    errors = []
    for b in range(2):
        _, (p_b, l_b, err) = ref.loss_and_counts(
            params, x[b], y[b], mask[b], cfg)
        pairs, load = pairs + p_b, load + l_b
        errors.append(err)
    # these parameters' mixing is far from a fresh block's: its sums are
    # off one by what 20 turns leave, the same here and there
    np.testing.assert_allclose(stats["hc_sum_error"], np.max(errors, axis=0),
                               rtol=1e-3)
    assert 1e-4 < float(stats["hc_sum_error"].max()) < 2e-2
    np.testing.assert_array_equal(stats["expert_pairs"], pairs)
    np.testing.assert_array_equal(stats["router_load"], load)
    assert stats["router_load"][1].sum() == 2 * SEQ * cfg.moe_top_k
    assert not np.asarray(stats["router_load"][0]).any()  # the dense layer
    assert int(stats["dropped"]) == 0
    np.testing.assert_array_equal(
        stats["latent_pairs"], [2 * SEQ * (SEQ + 1) // 2] * 3)
    # the held experts are 2..4 of the router's eight
    np.testing.assert_array_equal(stats["expert_pairs"],
                                  stats["router_load"][:, 2:5])


@pytest.mark.parametrize("clip", [1e9, 0.05])
def test_one_step_is_the_references_adam_step_and_bias_step(clip):
    """``Trainer.single_step`` on fresh parameters: Adam's first moment
    is the reference's clipped gradient, every leaf but the selection
    bias moves by the reference's plain Adam step, and the bias by its
    own rule on the step's load over all eight experts."""
    import optax

    from fmda_tpu.train.trainer import Trainer

    cfg = small_cfg()
    tc = TrainConfig(batch_size=2, window=SEQ, chunk_size=2 * SEQ,
                     learning_rate=1e-3, clip=clip, val_size=0.2,
                     test_size=0.2, cache_chunks=8, seed=0)
    ids = np.random.default_rng(0).integers(0, VOCAB, 10 * SEQ + 1)
    trainer = Trainer(cfg, tc)
    dataset = trainer.task.dataset(TokenArraySource(ids, VOCAB))
    batch = next(iter(trainer._chunk_batches(dataset, 0)))
    state = trainer.init_state(jax.random.PRNGKey(0))
    before = jax.device_get(state.params)
    with jax.default_matmul_precision("highest"):
        after, totals = trainer.single_step(state, batch,
                                            jax.random.PRNGKey(1))
    loss, grads = ref.loss_and_grads_by_layer(
        before, batch.x, batch.y, batch.mask, cfg)
    np.testing.assert_allclose(float(totals.loss), loss, rtol=1e-5)
    want_g, want_change = ref.first_adam_step(
        grads, learning_rate=tc.learning_rate, clip=clip)
    mu = optax.tree_utils.tree_get(after.opt_state, "mu")
    want_bias = ref.bias_step(totals.router_load, cfg.moe_bias_rate)
    assert np.abs(want_bias[1:]).max() == pytest.approx(cfg.moe_bias_rate)
    for (path, m), g, a, b, d in zip(
            jax.tree_util.tree_leaves_with_path(mu), jax.tree.leaves(want_g),
            jax.tree.leaves(jax.device_get(after.params)),
            jax.tree.leaves(before), jax.tree.leaves(want_change)):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.endswith("router_bias"):
            layer = int(name.split("/")[0].split("_")[1])
            assert not np.asarray(m).any()  # Adam saw a zero gradient
            np.testing.assert_array_equal(a - b, want_bias[layer])
            continue
        np.testing.assert_allclose(m / 0.1, g, rtol=2e-3, atol=1e-8,
                                   err_msg=name)
        live = np.abs(g) > 1e-5  # beside Adam's eps a step shows rounding
        np.testing.assert_allclose((a - b)[live], d[live], rtol=2e-2,
                                   err_msg=name)


class _OnlyExperts(nn.Module):
    """A layer's expert feed-forward alone, on a normalised stream."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, u):
        return feed_forward(self, self.cfg, u, dense=False, counted={},
                            load=True)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all the shares, the shared expert counted
    once, are the uncut reference layer: an expert layer's feed-forward
    under ``experts_held = (first, 1)`` for eight shares of one expert
    against ``(0, 8)``."""
    cfg = small_cfg()
    _, params = _params(cfg)
    whole = small_cfg(experts_held=(0, EXPERTS))
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(SEQ, 32)), jnp.float32)
    p = dict(params["block_1"])
    full = {k: jnp.asarray(rng.normal(size=(EXPERTS,) + p[k].shape[1:])
                           * 0.2, jnp.float32)
            for k in ("w_gate", "w_up", "w_down")}
    with jax.default_matmul_precision("highest"):
        want, pairs, load = ref.feed_forward(
            dict(p, **full), u, whole, False, {})
        shared, _, _ = ref.feed_forward(
            dict(p, **{k: v[:0] for k, v in full.items()}), u,
            small_cfg(experts_held=(0, 0)), False, {})
        routed = 0.0
        for first in range(EXPERTS):
            share = small_cfg(experts_held=(first, 1))
            part, part_pairs, part_load = ref.feed_forward(
                dict(p, **{k: v[first:first + 1] for k, v in full.items()}),
                u, share, False, {})
            routed = routed + (part - shared)
            np.testing.assert_array_equal(part_pairs,
                                          pairs[first:first + 1])
            np.testing.assert_array_equal(part_load, load)
            # ... and the program's block, given the same share, is that
            # part (it takes the normalised stream as the reference does)
            got, _ = _OnlyExperts(share).apply(
                {"params": dict(p, **{k: v[first:first + 1]
                                      for k, v in full.items()})}, u[None])
            np.testing.assert_allclose(got[0], part, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(routed + shared, want, rtol=2e-4, atol=2e-5)
    assert load.sum() == SEQ * cfg.moe_top_k


def test_twenty_turns_bring_clamped_logits_inside_the_bound():
    """Sinkhorn's 20 turns on matrices from logits clamped at +-30: the
    columns sum to one but for ``hc_eps`` whatever the matrix (the last
    half turn's doing); the rows within 1e-5 where one entry a row and
    column leads by e^12, as a fresh block's does whatever the input adds
    (gain 0.01), and within 2e-2 for logits anywhere in +-3.  A matrix
    from logits anywhere in +-30 may have no doubly stochastic scaling
    to converge to (entries of e^-60 beside the rest are a zero
    pattern), and its rows then stay far from one: the bound the
    benchmark holds the program to is the model's, not that one."""
    rng = np.random.default_rng(0)

    def errors(logits):
        res = hc.sinkhorn(
            jnp.exp(jnp.clip(logits, -30, 30)), 20, 1e-6)
        cols = np.abs(np.asarray(res.sum(axis=0)) - 1).max()
        rows = np.abs(np.asarray(res.sum(axis=1)) - 1).max()
        assert float(hc.sum_error(res)) == pytest.approx(max(cols, rows))
        return cols, rows, res

    wild = rng.uniform(-40, 40, size=(4, 4, 5000)).astype(np.float32)
    cols, rows, res = errors(wild)
    assert cols < 1e-5 and rows > 0.5, (cols, rows)
    cols, rows, _ = errors(rng.uniform(-3, 3, size=(4, 4, 5000)))
    assert cols < 1e-5 and rows < 2e-2, (cols, rows)
    lead = np.where(np.eye(4, dtype=bool)[:, :, None], 6.0, -6.0) \
        + 0.05 * rng.normal(size=(4, 4, 5000)).astype(np.float32)
    cols, rows, _ = errors(lead)
    assert cols < 1e-5 and rows < 1e-5, (cols, rows)
    # the reference's, with the matrix's axes last, is the same iteration
    want = ref.sinkhorn(jnp.exp(jnp.clip(jnp.moveaxis(wild, -1, 0), -30, 30)),
                        20, 1e-6)
    np.testing.assert_allclose(jnp.moveaxis(res, -1, 0), want, rtol=1e-5)


def test_every_turn_runs_where_the_matrix_is_slow_to_settle():
    """A matrix whose support is nearly triangular settles like 1 / turns:
    the 20th turn still moves it by 3e-3, so a program that stopped at 19
    (or tested for convergence) would differ from the reference's 20."""
    logits = np.where(np.tril(np.ones((4, 4), bool)), 30.0, -30.0)
    m = jnp.exp(jnp.asarray(logits, jnp.float32))[:, :, None]
    twenty, nineteen = hc.sinkhorn(m, 20, 1e-6), hc.sinkhorn(m, 19, 1e-6)
    assert float(jnp.abs(twenty - nineteen).max()) > 1e-3
    np.testing.assert_allclose(
        twenty[:, :, 0], ref.sinkhorn(m[:, :, 0], 20, 1e-6), rtol=1e-5)
    np.testing.assert_allclose(
        nineteen[:, :, 0], ref.sinkhorn(m[:, :, 0], 19, 1e-6), rtol=1e-5)


def test_a_fresh_hyper_connection_is_nearly_the_plain_residual():
    """A block with fresh mixing parameters reads lane 0, writes lane 0
    and leaves the other lanes as they were: lane 0 is the plain
    residual block's output within 1.5 % of its norm (sigmoid(6) is
    0.9975; the other lanes are read at 0.0025 each), the others move by
    under 1 % of theirs."""
    cfg = small_cfg(first_dense_layers=0)
    plain_cfg = small_cfg(first_dense_layers=0, hc_streams=1)
    block, plain = DecoderBlock(cfg, 4), DecoderBlock(plain_cfg, 4)
    rng = np.random.default_rng(2)
    lanes = jnp.asarray(rng.normal(size=(1, SEQ, 4, 32)), jnp.float32)
    params = block.init({"params": jax.random.PRNGKey(0)}, lanes)["params"]
    shared = {k: v for k, v in params.items() if not k.startswith("hc_")}
    with jax.default_matmul_precision("highest"):
        got, counts = block.apply({"params": params}, lanes)
        want, _ = plain.apply({"params": shared}, lanes[:, :, 0])
    norm = lambda a: float(jnp.linalg.norm(a))
    assert norm(got[:, :, 0] - want) < 0.015 * norm(want)
    assert norm(got[:, :, 1:] - lanes[:, :, 1:]) < 0.01 * norm(lanes[:, :, 1:])
    assert float(counts["hc_sum_error"]) < 1e-5


def test_yarn_stretches_the_slow_dims_and_keeps_the_fast_ones():
    cfg = small_cfg(qk_rope_head_dim=64, rope_original_max=4096)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    got = yarn_inv_freq(cfg)
    np.testing.assert_allclose(got, ref.yarn_inv_freq(cfg), rtol=1e-6)
    # beta 32 / 1 over 4,096 positions: dims 0..9 turn fast and keep
    # their frequency, dims from 23 on are slowed by the factor 64
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    assert (got[10:23] < plain[10:23]).all()
    assert (got[10:23] > plain[10:23] / 64).all()
    m = 0.1 * np.log(64.0) + 1
    assert score_scale(cfg) == pytest.approx((16 + 64) ** -0.5 * m * m)
    assert score_scale(small_cfg(rope_factor=1.0)) == pytest.approx(24 ** -0.5)


def test_a_pass_publishes_the_latent_layers_counters():
    from fmda_tpu.obs.registry import default_registry
    from fmda_tpu.train.trainer import Trainer

    cfg = small_cfg()
    tc = TrainConfig(batch_size=2, window=SEQ, chunk_size=2 * SEQ,
                     learning_rate=1e-3, val_size=0.2, test_size=0.2,
                     cache_chunks=8, seed=0)
    ids = np.random.default_rng(0).integers(0, VOCAB, 10 * SEQ + 1)
    reg = default_registry()

    def pairs(layer):
        return reg.counter("attention_latent_pairs_total", layer=str(layer),
                           phase="train").value

    def load(layer):
        return sum(reg.counter("moe_router_load_total", layer=str(layer),
                               phase="train", expert=str(e)).value
                   for e in range(EXPERTS))

    def held(layer):
        return reg.counter("moe_pairs_held_total", layer=str(layer),
                           phase="train").value

    before = [(pairs(l), load(l), held(l)) for l in range(3)]
    trainer = Trainer(cfg, tc)
    totals = trainer.zero_totals()
    assert totals.router_load.shape == (3, EXPERTS)
    assert totals.hc_sum_error.shape == totals.latent_pairs.shape == (3,)
    assert totals.sparse_keys_kept is None and totals.ssd_chunks is None
    state, hist, dataset = trainer.fit(TokenArraySource(ids, VOCAB), epochs=1)
    train, _, _ = dataset.split(tc.val_size, tc.test_size)
    n_rows = 2 * sum(len(trainer.task.batches(dataset, i)) for i in train)
    for layer in range(3):
        got = (pairs(layer) - before[layer][0], load(layer) - before[layer][1],
               held(layer) - before[layer][2])
        assert got[0] == n_rows * SEQ * (SEQ + 1) // 2
        # the dense layer routes nothing and publishes no routing counter
        assert got[1] == (n_rows * SEQ * cfg.moe_top_k if layer else 0)
        assert (got[2] > 0) == bool(layer)
        assert reg.gauge("hc_res_sum_error_max", layer=str(layer),
                         phase="train").value < 1e-5
    # a pass of steps moved every bias by the rate a step, at most
    steps = n_rows // 2
    size = reg.gauge("moe_router_bias_absmax", layer="1",
                     phase="train").value
    bias = np.asarray(state.params["block_1"]["router_bias"])
    assert 0 < size <= np.abs(bias).max() <= steps * cfg.moe_bias_rate * 1.001
    assert np.isfinite(hist["train"][0].loss)


@pytest.mark.parametrize("over,named", [
    (dict(q_lora_rank=-1), "q_lora_rank"),  # 0 is a direct query
    (dict(kv_lora_rank=0), "kv_lora_rank"),
    (dict(qk_nope_head_dim=0), "qk_nope_head_dim"),
    (dict(qk_rope_head_dim=7), "qk_rope_head_dim"),
    (dict(v_head_dim=0), "v_head_dim"),
    (dict(rope_factor=0.5), "rope_factor"),
    (dict(rope_original_max=0), "rope_original_max"),
    (dict(first_dense_layers=4), "first_dense_layers"),
    (dict(ffn_size=0), "ffn_size"),
    (dict(moe_shared_experts=-1), "moe_shared_experts"),
    (dict(moe_scoring="tanh"), "moe_scoring"),
    (dict(moe_routed_scaling=0.0), "moe_routed_scaling"),
    (dict(moe_bias_rate=-1.0), "moe_bias_rate"),
    (dict(hc_streams=0), "hc_streams"),
    (dict(hc_sinkhorn_iters=0), "hc_sinkhorn_iters"),
    (dict(hc_eps=0.0), "hc_eps"),
    (dict(hc_res_clamp=0.0), "hc_res_clamp"),
    (dict(attention_multiplier=0.1), "attention_multiplier"),
    (dict(layer_layout=(4, 0)), "layer_layout"),
    # what only a latent-attention model has, asked of another
    (dict(layer_layout=(0, 0), n_kv_heads=2, head_dim=8), "hc_streams"),
    (dict(layer_layout=(0, 0), n_kv_heads=2, head_dim=8, hc_streams=1),
     "moe_scoring"),
    (dict(layer_layout=(0, 0), n_kv_heads=2, head_dim=8, hc_streams=1,
          moe_scoring="softmax", moe_routed_scaling=1.0, moe_bias_rate=0.0),
     "first_dense_layers"),
])
def test_config_errors_name_the_field(over, named):
    with pytest.raises(ValueError, match=named) as err:
        check_decoder_config(small_cfg(**over))
    # and nothing it does not need: head_dim and n_kv_heads are not read
    if over.get("layer_layout", (4,))[0] == 4:
        assert "; head_dim" not in str(err.value)
        assert "n_kv_heads" not in str(err.value)


def test_the_small_configuration_is_accepted():
    check_decoder_config(small_cfg())
    check_decoder_config(small_cfg(hc_streams=1, moe_bias_rate=0.0))


def test_cli_train_takes_the_benchmark_configurations_framework_block(
        tmp_path, capsys):
    """``python -m fmda_tpu train --tokens`` accepts the ``framework``
    block of benchmark/configs/xing4_0_29b_a4b_ep8.json as written: the
    file's own keys parse, the published widths count 759,346,446
    parameters, and a copy cut to test size trains through
    ``Trainer.fit``."""
    from fmda_tpu.cli import main
    from fmda_tpu.config import (
        FrameworkConfig, config_from_dict, config_to_dict)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "xing4_0_29b_a4b_ep8.json")) as fh:
        framework = json.load(fh)["framework"]
    full = config_from_dict(framework)
    check_decoder_config(full.model)
    assert full.model.layer_layout == (4, 4, 4, 4, 4)
    assert (full.model.first_dense_layers, full.model.hc_streams,
            full.model.hc_sinkhorn_iters) == (1, 4, 20)
    shapes = jax.eval_shape(
        lambda key: build_model(full.model).init(
            {"params": key}, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    assert sum(int(np.prod(l.shape))
               for l in jax.tree.leaves(shapes)) == 759_346_446
    small = small_cfg(dtype="bfloat16", remat=True)
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
