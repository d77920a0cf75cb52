"""Delta-rule layers with a decay a channel beside unrotated latent
attention in one model, under a sigmoid router with a shared expert
(Kimi-Linear-48B-A3B's layers: ``layer_layout`` 5 and 4,
``mla_use_nope``; models/decoder.py, ops/kda.py, train/tasks.py) against
its plain reference (benchmark/reference/kda_decoder.py, the recurrence
stepwise), on the CPU at small widths and seeded weights: logits, the
loss, every leaf's gradient, one optimizer step and the selection bias's
step; the reference's wrong runs; the configuration's rules; no rotary;
the share test; scopes, counters and what a pass publishes.  (The
chunked walk alone is in tests/test_kda.py.)"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import kda_decoder as ref  # noqa: E402
from fmda_tpu.config import ModelConfig, TrainConfig  # noqa: E402
from fmda_tpu.data.pipeline import Batch  # noqa: E402
from fmda_tpu.data.source import TokenArraySource  # noqa: E402
from fmda_tpu.models import build_model  # noqa: E402
from fmda_tpu.models.decoder import (  # noqa: E402
    DecoderBlock, check_decoder_config, model_counts)
from fmda_tpu.train.tasks import NextToken  # noqa: E402

SEQ, VOCAB, EXPERTS = 24, 96, 8


def small_cfg(**over):
    """A dense delta-rule layer, then a latent and a delta-rule expert
    layer, three of eight experts held; three chunks of eight."""
    return ModelConfig(**{**dict(
        cell="decoder", hidden_size=32, n_heads=4, vocab_size=VOCAB,
        layer_layout=(5, 4, 5), rms_norm_eps=1e-5, q_lora_rank=0,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12, mla_use_nope=True, kda_heads=4, kda_head_dim=8,
        kda_conv=4, kda_chunk=8, moe_experts=EXPERTS, moe_top_k=2,
        moe_ffn_size=16, experts_held=(2, 3), hidden_act="silu", ffn_size=48,
        first_dense_layers=1, moe_shared_experts=1, moe_scoring="sigmoid",
        moe_routed_scaling=2.446, moe_bias_rate=1e-3, loss_chunk=16,
        dtype="float32", dropout=0.0), **over})


_MADE = {}


def _params(cfg, seed=0):
    model = build_model(cfg)
    if seed not in _MADE:  # the tree does not depend on what ``cfg`` holds
        _MADE[seed] = _wide_params(build_model(small_cfg()), seed)
    return model, _MADE[seed]


def _wide_params(model, seed):
    """Seeded values on the model's own tree (its shapes, without running
    its init): matrices wider than the family's N(0, 0.02), so that every
    path matters at hidden 32; norm scales off one; a selection bias that
    decides some top-2; the decay's rates in 1..16, its steps in
    1e-3..1e-1 and the taps in +-1/2, as a fresh model has them."""
    shapes = jax.eval_shape(lambda key: model.init(
        {"params": key}, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(seed))
    paths = jax.tree_util.tree_leaves_with_path(shapes)

    def draw_all(seed_key):  # one program, not one a leaf and operation
        wide = []
        for (path, leaf), key in zip(
                paths, jax.random.split(seed_key, len(paths))):
            name = str(getattr(path[-1], "key", path[-1]))
            draw = jax.random.normal(key, leaf.shape)
            if name == "router_bias":
                wide.append(0.05 * draw)
            elif name == "a_log":
                wide.append(jnp.log(jax.random.uniform(
                    key, leaf.shape, minval=1.0, maxval=16.0)))
            elif name == "dt_bias":
                step = jnp.exp(jax.random.uniform(
                    key, leaf.shape, minval=np.log(1e-3),
                    maxval=np.log(1e-1)))
                wide.append(step + jnp.log(-jnp.expm1(-step)))
            elif name.startswith("conv_"):
                wide.append(jax.random.uniform(
                    key, leaf.shape, minval=-0.5, maxval=0.5))
            elif leaf.ndim == 1:
                wide.append(1.0 + 0.1 * draw)
            else:
                wide.append(0.2 * draw)
        return wide

    return jax.tree.unflatten(jax.tree.structure(shapes), jax.jit(draw_all)(
        jax.random.PRNGKey(seed + 1)))


def _ids(seed=3, n=SEQ + 1, batch=2):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, size=(batch, n)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def _leaf_names(tree):
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


@pytest.fixture(scope="module")
def right():
    """The right reference on two whole sequences."""
    cfg = small_cfg()
    _, params = _params(cfg)
    x, y = _ids()
    mask = jnp.ones(x.shape, jnp.float32)
    return (params, x, y, mask, cfg), jax.jit(
        lambda p: ref.loss_and_grads(p, x, y, mask, cfg))(params)


@pytest.fixture(scope="module")
def right_counts(right):
    """What the reference's layers counted, a sequence at a time: the
    held pairs and the load over all experts, summed over the two."""
    params, x, y, mask, cfg = right[0]
    return jax.jit(lambda p: jax.tree.map(
        lambda *per_sequence: sum(per_sequence),
        *(ref.loss_and_counts(p, x[b], y[b], mask[b], cfg)[1]
          for b in range(2))))(params)


def test_the_parameter_tree_has_both_kinds_of_layer():
    cfg = small_cfg()
    _, params = _params(cfg)
    kda, latent = params["block_2"], params["block_1"]
    assert kda["wq"].shape == kda["wk"].shape == kda["wv"].shape == (32, 32)
    assert kda["conv_q"].shape == (32, 4) and "conv_b" not in kda
    assert kda["wf_a"].shape == kda["wg_a"].shape == (32, 8)
    assert kda["wf_b"].shape == kda["wg_b"].shape == (8, 32)
    assert kda["dt_bias"].shape == (32,) and kda["a_log"].shape == (4,)
    assert kda["wb"].shape == (32, 4) and kda["o_norm"].shape == (8,)
    assert latent["wq"].shape == (32, 4 * 24) and "wk" not in latent
    for block in (kda, latent):  # the same feed-forward under either
        assert block["router"].shape == (32, EXPERTS)
        assert block["router_bias"].shape == (EXPERTS,)
        assert block["ws_gate"].shape == (32, 16)
    assert "router" not in params["block_0"]


def test_logits_match_the_reference_and_not_a_rotated_one():
    cfg = small_cfg()
    model, params = _params(cfg)
    x, _ = _ids()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: model.apply({"params": p}, x))(params)
    want = jax.jit(lambda p, ids: ref.logits(p, ids, cfg))
    turned = jax.jit(lambda p, ids: ref.logits(p, ids, cfg, rotary=True))
    for b in range(2):
        np.testing.assert_allclose(got[b], want(params, x[b]), rtol=2e-4,
                                   atol=2e-5)
        assert float(jnp.abs(got[b] - turned(params, x[b])).max()) > 1e-2
    # no rotary in the latent layer: its lowered text has no such scope
    text = jax.jit(lambda p: model.apply({"params": p}, x)).lower(
        params).as_text(debug_info=True)
    assert "attention_latent" in text and "/rope" not in text
    rotated = build_model(small_cfg(mla_use_nope=False))
    assert "/rope" in jax.jit(lambda p: rotated.apply({"params": p}, x)
                              ).lower(params).as_text(debug_info=True)


@pytest.mark.parametrize("remat", [False, True])
def test_loss_counts_and_every_leafs_gradient_match_the_reference(
        right, right_counts, remat):
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
        if name.endswith("router_bias"):  # no gradient reaches it
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert float(jnp.linalg.norm(g - w)) < 3e-4 * float(
            jnp.linalg.norm(w)), name
    # what the layers counted: the reference's, a sequence at a time; an
    # expert layer under either mixer reports its load over all experts
    pairs, load = right_counts
    np.testing.assert_array_equal(values.expert_pairs, pairs)
    np.testing.assert_array_equal(values.router_load, load)
    for layer in (1, 2):
        assert int(values.router_load[layer].sum()) == 2 * SEQ * 2
    assert not np.asarray(values.router_load[0]).any()  # the dense layer
    np.testing.assert_array_equal(values.kda_chunks, [6, 0, 6])
    np.testing.assert_array_equal(values.kda_positions, [48, 0, 48])
    np.testing.assert_array_equal(values.latent_pairs, [0, 600, 0])
    assert float(values.kda_log_decay_absmax[1]) == 0.0
    assert min(float(values.kda_log_decay_absmax[i]) for i in (0, 2)) > 0.05


WRONG = {
    "no_decay": dict(decay="none"),
    "no_correction": dict(correction=False),
    "one_decay_a_head": dict(decay="head_mean"),
    "qk_not_normalised": dict(qk_norm=False),
    "rotary": dict(rotary=True),
    "state_in_bfloat16": dict(state_as="bfloat16"),
    "products_in_float8": dict(products_as="float8_e5m2"),
    "no_shared_expert": dict(skip_shared=True),
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


def test_one_step_is_the_references_adam_step_and_bias_step_and_is_traced():
    """``Trainer.single_step`` on fresh parameters: Adam's first moment is
    the reference's gradient, every leaf but the selection bias moves by
    the reference's plain Adam step, and the bias of EVERY expert layer,
    whatever its mixer, by its own rule on the step's load; the step's
    lowered text has the mixer's scopes, forward and backward, and the
    pass publishes its three counts a layer."""
    import optax

    from fmda_tpu.obs.registry import default_registry

    trainer, _, batch = _trainer(small_cfg(remat=True))
    cfg, tc = trainer.model_cfg, trainer.train_cfg
    state = trainer.init_state(jax.random.PRNGKey(0))
    before = jax.device_get(state.params)
    # a fresh mixer: the rates in 1..16 a head, the steps in 1e-3..1e-1 a
    # channel, the taps in +-1/sqrt(4)
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
    want_bias = ref.bias_step(totals.router_load, cfg.moe_bias_rate)
    assert np.abs(want_bias[1:]).max() == pytest.approx(cfg.moe_bias_rate)
    stepped = []
    for name, m, g, a, b, d in zip(
            _leaf_names(mu), jax.tree.leaves(mu), jax.tree.leaves(want_g),
            jax.tree.leaves(jax.device_get(after.params)),
            jax.tree.leaves(before), jax.tree.leaves(want_change)):
        if name.endswith("router_bias"):
            layer = int(name.split("/")[0].split("_")[1])
            assert not np.asarray(m).any()  # Adam saw a zero gradient
            np.testing.assert_array_equal(a - b, want_bias[layer])
            stepped.append(layer)
            continue
        np.testing.assert_allclose(m / 0.1, g, rtol=2e-3, atol=1e-8,
                                   err_msg=name)
        live = np.abs(g) > 1e-5  # beside Adam's eps a step shows rounding
        np.testing.assert_allclose((a - b)[live], d[live], rtol=2e-2,
                                   err_msg=name)
    assert stepped == [1, 2]  # the latent layer's and the delta-rule one's

    paths = set(re.findall(r'"(jit\([^"]*)"', text))
    for scope in ("kda_proj", "kda_conv", "kda_gates", "kda_out_norm",
                  "kda_scan/kda_intra", "kda_scan/kda_solve",
                  "kda_scan/kda_carry", "kda_scan/kda_out"):
        under = [p for p in paths if "/kda_mixer/" in p and all(
            f"/{part}/" in p for part in scope.split("/"))]
        assert any("transpose(jvp(" in p for p in under), scope  # backward
        assert any("transpose(" not in p for p in under), scope  # forward

    reg = default_registry()
    trainer.task.publish(totals, "train", 1)
    for layer in (0, 2):
        labels = dict(layer=str(layer), phase="train")
        assert reg.counter("kda_chunks_total", **labels).value == 6
        assert reg.counter("kda_positions_total", **labels).value == 48
        assert reg.gauge("kda_log_decay_absmax", **labels).value == (
            pytest.approx(float(totals.kda_log_decay_absmax[layer])))
    assert reg.counter("kda_chunks_total", layer="1",
                       phase="train").value == 0


@pytest.mark.parametrize("over,named", [
    (dict(kda_heads=0), "kda_heads (layer_layout has a delta-rule layer)"),
    (dict(kda_head_dim=0), "kda_head_dim"),
    (dict(kda_conv=0), "kda_conv"),
    (dict(kda_chunk=0), "kda_chunk"),
    (dict(layer_layout=(5, 0)), "layer_layout"),
    (dict(layer_layout=(3, 5)), "layer_layout"),
    (dict(hc_streams=4), "hc_streams"),
    (dict(kv_lora_rank=0), "kv_lora_rank"),
])
def test_config_errors_name_the_field(over, named):
    with pytest.raises(ValueError, match=re.escape(named)) as err:
        check_decoder_config(small_cfg(**over))
    # and nothing it does not need: head_dim and n_kv_heads are not read
    assert "; head_dim" not in str(err.value)
    assert "n_kv_heads" not in str(err.value)


def test_both_kinds_in_one_model_are_accepted_and_declare_their_counts():
    for layout in ((5, 4, 5), (4, 5, 5), (5, 5, 5, 4, 5), (5, 5)):
        check_decoder_config(small_cfg(layer_layout=layout))
    declared = model_counts(small_cfg(layer_layout=(5, 5, 5, 4, 5)))
    assert list(declared) == [
        "expert_pairs", "dropped", "row_tiles_used", "layout_rounds",
        "router_load", "router_bias_absmax", "latent_pairs", "kda_chunks",
        "kda_positions", "kda_log_decay_absmax"]
    # the load is the expert layers', whatever their mixer; a kind's own
    # counts are its layers'
    assert declared["router_load"].layers == (1, 2, 3, 4)
    assert declared["latent_pairs"].layers == (3,)
    assert declared["kda_chunks"].layers == (0, 1, 2, 4)
    assert "router_load" not in model_counts(small_cfg(
        moe_scoring="softmax", moe_routed_scaling=1.0, moe_bias_rate=0.0))


def test_the_shares_add_up_to_the_uncut_layer():
    """A whole delta-rule expert block: the routed parts of the four
    shares (each holding two of the eight experts), with the mixer and
    the shared expert counted once, are the uncut reference's layer
    output; every share's load is the uncut layer's."""
    cfg = small_cfg()
    _, params = _params(cfg)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(SEQ, 32)), jnp.float32)
    p = dict(params["block_2"])
    full = {k: jnp.asarray(rng.normal(size=(EXPERTS,) + p[k].shape[1:])
                           * 0.2, jnp.float32)
            for k in ("w_gate", "w_up", "w_down")}

    def reference(held_cfg, held):
        return jax.jit(lambda q: ref.block(
            q, x, held_cfg, 5, False, False, {}))(held)

    with jax.default_matmul_precision("highest"):
        want, pairs, load = reference(
            small_cfg(experts_held=(0, EXPERTS)), dict(p, **full))
        # what every chip computes alike: the mixer and the shared expert
        common, _, _ = reference(
            small_cfg(experts_held=(0, 0)),
            dict(p, **{k: v[:0] for k, v in full.items()}))
        routed = 0.0
        for first in range(0, EXPERTS, 2):
            share = small_cfg(experts_held=(first, 2))
            held = dict(p, **{k: v[first:first + 2]
                              for k, v in full.items()})
            part, part_pairs, part_load = reference(share, held)
            routed = routed + (part - common)
            np.testing.assert_array_equal(part_pairs, pairs[first:first + 2])
            np.testing.assert_array_equal(part_load, load)
            # ... and the program's block, given the same share, is that
            # part, with the same load
            got, counts = jax.jit(lambda q, _s=share: DecoderBlock(
                _s, 5).apply({"params": q}, x[None]))(held)
            np.testing.assert_allclose(got[0], part, rtol=2e-4, atol=2e-5)
            np.testing.assert_array_equal(counts["router_load"], load)
    assert int(pairs.sum()) > SEQ  # the routed experts do take part
    np.testing.assert_allclose(common + routed, want, rtol=2e-4, atol=2e-5)
