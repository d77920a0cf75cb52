"""Latent attention with a direct query under a plain residual, two
shared experts and the router's per-sequence balance term
(Moonlight-16B-A3B's layer: ``layer_layout`` 4, ``q_lora_rank`` 0,
``moe_seq_aux_alpha`` > 0; models/decoder.py, ops/moe.py
``seq_balance_term``, train/tasks.py) against its plain reference
(benchmark/reference/mla_decoder.py), on the CPU at small widths and
seeded weights, two sequences a step: logits, the objective, the
balance term by layer, every leaf's gradient, one optimizer step and
the selection bias's step; where the term's gradient goes; what a
validation pass leaves out; recomputation and microbatches; the share
test; what a pass publishes.  (The kind's pinned programs are in
tests/test_decoder.py.)"""

import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import mla_decoder as ref  # noqa: E402
from fmda_tpu.config import ModelConfig, TrainConfig  # noqa: E402
from fmda_tpu.data.pipeline import Batch  # noqa: E402
from fmda_tpu.data.source import TokenArraySource  # noqa: E402
from fmda_tpu.models import build_model  # noqa: E402
from fmda_tpu.models.decoder import (  # noqa: E402
    DecoderBlock, feed_forward, model_terms)
from fmda_tpu.ops.moe import route, seq_balance_term  # noqa: E402
from fmda_tpu.train.tasks import NextToken  # noqa: E402

SEQ, VOCAB, EXPERTS, ALPHA = 40, 96, 8, 0.05


def small_cfg(**over):
    """One dense and two expert layers, three of eight experts held, a
    direct query, two shared experts, a balance term large enough to
    read beside a loss of five nats."""
    return ModelConfig(**{**dict(
        cell="decoder", hidden_size=32, n_heads=4, vocab_size=VOCAB,
        layer_layout=(4, 4, 4), rms_norm_eps=1e-5, q_lora_rank=0,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12, rope_theta=50000.0, moe_experts=EXPERTS, moe_top_k=2,
        moe_ffn_size=16, experts_held=(2, 3), hidden_act="silu", ffn_size=48,
        first_dense_layers=1, moe_shared_experts=2, moe_scoring="sigmoid",
        moe_routed_scaling=2.446, moe_bias_rate=1e-3,
        moe_seq_aux_alpha=ALPHA, loss_chunk=16, dtype="float32",
        dropout=0.0), **over})


def _params(cfg, seed=0):
    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(seed)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # matrices wider than the family's N(0, 0.02), so that every path
    # matters at hidden 32; norm scales off one; a selection bias that
    # decides some top-2
    keys = jax.random.split(jax.random.PRNGKey(seed + 1),
                            len(jax.tree.leaves(params)))
    wide = []
    for (path, leaf), key in zip(
            jax.tree_util.tree_leaves_with_path(params), keys):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "router_bias":
            wide.append(0.05 * jax.random.normal(key, leaf.shape))
        elif leaf.ndim == 1:
            wide.append(1.0 + 0.1 * jax.random.normal(key, leaf.shape))
        else:
            wide.append(0.2 * jax.random.normal(key, leaf.shape))
    return model, jax.tree.unflatten(jax.tree.structure(params), wide)


def _ids(seed=3, n=SEQ + 1, batch=2):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, size=(batch, n)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def _leaf_names(tree):
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


def _objective(model, cfg, batch):
    """``p -> (objective, the step's values)`` as the train step has
    them."""
    task = NextToken(cfg, TrainConfig(batch_size=batch.x.shape[0],
                                      window=batch.x.shape[1]))

    def objective(p):
        with jax.default_matmul_precision("highest"):
            value, aux = task.loss(
                p, task.forward(model, p, batch, None), batch)
            return value, task.step_values(value, aux, batch)
    return objective


def test_the_parameter_tree_has_one_query_product_and_no_latent_for_it():
    cfg = small_cfg()
    _, params = _params(cfg)
    for i in range(3):
        block = params[f"block_{i}"]
        assert block["wq"].shape == (32, 4 * (16 + 8))
        assert not {"wq_a", "wq_b", "q_norm"} & set(block)
        assert block["wkv_a"].shape == (32, 16 + 8)
    assert params["block_1"]["ws_gate"].shape == (32, 2 * 16)
    assert "router_bias" in params["block_1"]
    assert "router" not in params["block_0"]
    assert model_terms(cfg) == {"seq_aux_loss": (1, 2)}
    assert model_terms(small_cfg(moe_seq_aux_alpha=0.0)) == {}


def test_logits_match_the_reference():
    cfg = small_cfg()
    model, params = _params(cfg)
    x, _ = _ids()
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, x)
    for b in range(2):
        want = ref.logits(params, x[b], cfg)
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-5)


@pytest.fixture(scope="module")
def right():
    """The right reference on two whole sequences and one of padding."""
    cfg = small_cfg()
    _, params = _params(cfg)
    x, y = _ids(batch=3)
    mask = jnp.ones(x.shape, jnp.float32).at[2].set(0.0)
    return (params, x, y, mask, cfg), jax.jit(
        lambda p: ref.objective_and_grads(p, x, y, mask, cfg))(params)


@pytest.mark.parametrize("remat", [False, True])
def test_objective_terms_and_every_leafs_gradient_match_the_reference(
        right, remat):
    (params, x, y, mask, _), ((want, (want_tokens, want_terms)),
                              want_grads) = right
    cfg = small_cfg(remat=remat)
    model = build_model(cfg)
    (got, values), got_grads = jax.jit(jax.value_and_grad(
        _objective(model, cfg, Batch(x, y, mask)), has_aux=True))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # the reported loss stays the next-token loss; the term is beside it,
    # a layer, the mean over the two sequences that count (the padded one
    # adds nothing: a term taken over the batch's rows would read 2/3)
    np.testing.assert_allclose(values.loss, want_tokens, rtol=1e-5)
    np.testing.assert_allclose(values.seq_aux_loss, want_terms, rtol=1e-5)
    assert float(want_terms[0]) == 0.0 and float(want_terms[1]) > ALPHA
    np.testing.assert_allclose(float(got) - float(values.loss),
                               float(want_terms.sum()), rtol=1e-4)
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    for name, g, w in zip(_leaf_names(got_grads), jax.tree.leaves(got_grads),
                          jax.tree.leaves(want_grads)):
        if name.endswith("router_bias"):  # no gradient reaches it
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert float(jnp.linalg.norm(g - w)) < 3e-4 * float(
            jnp.linalg.norm(w)), name
    # what the layers counted: the reference's, a sequence at a time
    _, stats = model.apply({"params": params}, x[:2], method="features")
    pairs = load = 0
    terms = []
    for b in range(2):
        _, (p_b, l_b, t_b) = ref.loss_and_counts(
            params, x[b], y[b], mask[b], cfg)
        pairs, load = pairs + p_b, load + l_b
        terms.append(t_b)
    np.testing.assert_array_equal(stats["expert_pairs"], pairs)
    np.testing.assert_array_equal(stats["router_load"], load)
    assert stats["seq_aux_loss"].shape == (3, 2)  # a layer, a sequence
    np.testing.assert_allclose(stats["seq_aux_loss"], np.stack(terms, 1),
                               rtol=1e-5)


@pytest.mark.parametrize("wrong", [
    dict(balance="none"), dict(balance="unnormalised"),
    dict(query_as="float8_e5m2"), dict(softmax_as="bfloat16"),
    dict(products_as="float8_e5m2"), dict(skip_shared=True)],
    ids=lambda w: "-".join(map(str, next(iter(w.items())))))
def test_the_references_wrong_runs_move_its_objective_and_gradient(
        right, wrong):
    given, ((objective, (_, terms)), grads) = right
    (moved, (_, wrong_terms)), wrong_grads = ref.objective_and_grads(
        *given, **wrong)
    assert abs(float(moved) - float(objective)) > 1e-5
    if "balance" in wrong:  # ... by the term alone: the router's gradient
        assert abs(float(wrong_terms.sum()) - float(terms.sum())) > ALPHA / 2
        leaf = "router"
    else:
        leaf = "wo"
    g, w = grads["block_1"][leaf], wrong_grads["block_1"][leaf]
    assert float(jnp.linalg.norm(w - g) / jnp.linalg.norm(g)) > 1e-3


def test_the_references_layerwise_backward_is_the_whole_graphs(right):
    given, ((want, (want_tokens, want_terms)), want_grads) = right
    got, tokens, terms, got_grads = ref.objective_and_grads_by_layer(*given)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(tokens, want_tokens, rtol=1e-6)
    np.testing.assert_allclose(terms, want_terms, rtol=1e-5)
    for name, g, w in zip(_leaf_names(got_grads), jax.tree.leaves(got_grads),
                          jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-6, err_msg=name)


def test_the_term_is_a_sequences_own_and_reads_alpha_at_an_even_router():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(2 * SEQ, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, EXPERTS)), jnp.float32)
    _, experts, scores = route(h, w, 2, scoring="sigmoid", with_scores=True)
    two = seq_balance_term(scores, experts, 2, ALPHA)
    one = seq_balance_term(scores, experts, 1, ALPHA)
    assert two.shape == (2,) and one.shape == (1,)
    # each sequence's is the reference's on its own tokens; the batch's
    # one term is another number than their mean
    cfg = small_cfg()
    for b in range(2):
        rows = slice(b * SEQ, (b + 1) * SEQ)
        np.testing.assert_allclose(two[b], ref.balance_term(
            scores[rows], experts[rows], cfg, {}), rtol=1e-6)
    assert abs(float(one[0]) - float(two.mean())) > 1e-3 * ALPHA
    # every expert chosen as often and scored alike: f_e = 1, sum P_e = 1
    even_scores = jnp.full((EXPERTS, EXPERTS), 0.3, jnp.float32)
    turn = jnp.arange(EXPERTS, dtype=jnp.int32)
    even_choice = jnp.stack([turn, (turn + 1) % EXPERTS], axis=1)
    np.testing.assert_allclose(
        seq_balance_term(even_scores, even_choice, 1, ALPHA), [ALPHA],
        rtol=1e-6)
    # route's two answers are what they were without the third
    gates, chosen = route(h, w, 2, scoring="sigmoid")
    np.testing.assert_array_equal(chosen, experts)


class _OnlyExperts(nn.Module):
    """A layer's expert feed-forward alone, on a normalised stream."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, u):
        return feed_forward(self, self.cfg, u, dense=False, counted={},
                            load=True)


def test_the_terms_gradient_reaches_the_router_and_nothing_else():
    """Of an expert layer's own leaves the term moves the router alone
    (the choice is a count, the bias has no gradient, no expert is on the
    path); through the layer's input it reaches what came before."""
    cfg = small_cfg()
    model, params = _params(cfg)
    u = jnp.asarray(np.random.default_rng(1).normal(size=(2, SEQ, 32)),
                    jnp.float32)
    layer = {k: v for k, v in params["block_1"].items() if k not in (
        "ln_attn", "ln_moe", "wq", "wkv_a", "kv_norm", "wkv_b", "wo")}

    def term(p):
        return jnp.sum(_OnlyExperts(cfg).apply({"params": p}, u)[1][
            "seq_aux_loss"])

    grads = jax.grad(term)(layer)
    assert float(jnp.abs(grads["router"]).max()) > 1e-6
    for name, g in grads.items():
        assert name == "router" or not np.asarray(g).any(), name
    # in the model: the last layer's experts, shared experts and bias get
    # nothing from the terms; its router, its norm and the layers before do
    x, _ = _ids()

    def terms(p):
        return jnp.sum(model.apply({"params": p}, x, method="features")[1][
            "seq_aux_loss"])

    grads = jax.grad(terms)(params)
    for name in ("w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down",
                 "router_bias"):
        assert not np.asarray(grads["block_2"][name]).any(), name
    for block, name in (("block_2", "router"), ("block_2", "ln_moe"),
                        ("block_2", "wq"), ("block_1", "w_down"),
                        ("block_0", "wq")):
        assert np.asarray(grads[block][name]).any(), (block, name)
    assert not np.asarray(grads["head"]).any()
    assert not np.asarray(grads["ln_final"]).any()


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


def test_an_eval_steps_loss_has_no_term_in_it():
    trainer, _, batch = _trainer(small_cfg())
    state = trainer.init_state(jax.random.PRNGKey(0))
    with jax.default_matmul_precision("highest"):
        totals = trainer._eval_step(state.params, trainer.zero_totals(),
                                    batch)
    params = jax.device_get(state.params)
    (want, (want_tokens, want_terms)) = ref.objective(
        params, batch.x, batch.y, batch.mask, trainer.model_cfg)
    np.testing.assert_allclose(float(totals.loss), want_tokens, rtol=1e-5)
    assert float(want) - float(want_tokens) > ALPHA  # a term would show
    # its value is still folded, for the pass to publish by phase
    np.testing.assert_allclose(totals.seq_aux_loss, want_terms, rtol=1e-5)


@pytest.mark.parametrize("clip", [1e9, 0.05])
def test_one_step_is_the_references_adam_step_and_bias_step(clip):
    """``Trainer.single_step`` on fresh parameters: the reported loss is
    the reference's next-token loss, the term beside it; Adam's first
    moment is the reference's clipped gradient OF THE OBJECTIVE, every
    leaf but the selection bias moves by the reference's plain Adam step,
    and the bias by its own rule on the step's load over all experts."""
    import optax

    trainer, _, batch = _trainer(small_cfg(), clip=clip)
    cfg, tc = trainer.model_cfg, trainer.train_cfg
    state = trainer.init_state(jax.random.PRNGKey(0))
    before = jax.device_get(state.params)
    with jax.default_matmul_precision("highest"):
        after, totals = trainer.single_step(state, batch,
                                            jax.random.PRNGKey(1))
    _, tokens, terms, grads = ref.objective_and_grads_by_layer(
        before, batch.x, batch.y, batch.mask, cfg)
    np.testing.assert_allclose(float(totals.loss), tokens, rtol=1e-5)
    np.testing.assert_allclose(totals.seq_aux_loss, terms, rtol=1e-5)
    want_g, want_change = ref.first_adam_step(
        grads, learning_rate=tc.learning_rate, clip=clip)
    mu = optax.tree_utils.tree_get(after.opt_state, "mu")
    want_bias = ref.bias_step(totals.router_load, cfg.moe_bias_rate)
    assert np.abs(want_bias[1:]).max() == pytest.approx(cfg.moe_bias_rate)
    for name, m, g, a, b, d in zip(
            _leaf_names(mu), jax.tree.leaves(mu), jax.tree.leaves(want_g),
            jax.tree.leaves(jax.device_get(after.params)),
            jax.tree.leaves(before), jax.tree.leaves(want_change)):
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
    # the router's gradient is not the next-token loss's alone: without
    # the term the reference's differs by more than the tolerance above
    _, _, _, bare = ref.objective_and_grads_by_layer(
        before, batch.x, batch.y, batch.mask, cfg, balance="none")
    g, w = grads["block_1"]["router"], bare["block_1"]["router"]
    assert np.linalg.norm(g - w) > 0.05 * np.linalg.norm(g)


def test_two_microbatches_give_the_whole_batchs_objective_and_gradients():
    import optax

    steps = {}
    for accum in (1, 2):
        trainer, _, batch = _trainer(small_cfg(remat=accum == 2),
                                     accum_steps=accum)
        state = trainer.init_state(jax.random.PRNGKey(0))
        with jax.default_matmul_precision("highest"):
            after, totals = trainer.single_step(state, batch,
                                                jax.random.PRNGKey(1))
        steps[accum] = (totals, jax.device_get(
            optax.tree_utils.tree_get(after.opt_state, "mu")))
    (whole, whole_mu), (micro, micro_mu) = steps[1], steps[2]
    np.testing.assert_allclose(micro.loss, whole.loss, rtol=1e-6)
    np.testing.assert_allclose(micro.seq_aux_loss, whole.seq_aux_loss,
                               rtol=1e-5)
    np.testing.assert_array_equal(micro.router_load, whole.router_load)
    for name, a, b in zip(_leaf_names(whole_mu), jax.tree.leaves(micro_mu),
                          jax.tree.leaves(whole_mu)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-9, err_msg=name)


def test_the_shares_add_up_to_the_uncut_layer():
    """A whole block of this model: the routed parts of the eight shares
    (each holding one expert), with attention and the two shared experts
    counted once, are the uncut reference's layer output; every share's
    routing and balance term are the uncut layer's (the term reads all
    eight experts, held or not)."""
    cfg = small_cfg()
    _, params = _params(cfg)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(SEQ, 32)), jnp.float32)
    p = dict(params["block_1"])
    full = {k: jnp.asarray(rng.normal(size=(EXPERTS,) + p[k].shape[1:])
                           * 0.2, jnp.float32)
            for k in ("w_gate", "w_up", "w_down")}
    with jax.default_matmul_precision("highest"):
        want, pairs, load, term = ref.block(
            dict(p, **full), x, small_cfg(experts_held=(0, EXPERTS)), False,
            False, {})
        # what every chip computes alike: attention and the shared experts
        common, _, _, _ = ref.block(
            dict(p, **{k: v[:0] for k, v in full.items()}), x,
            small_cfg(experts_held=(0, 0)), False, False, {})
        routed = 0.0
        for first in range(EXPERTS):
            share = small_cfg(experts_held=(first, 1))
            held = dict(p, **{k: v[first:first + 1]
                              for k, v in full.items()})
            part, part_pairs, part_load, part_term = ref.block(
                held, x, share, False, False, {})
            routed = routed + (part - common)
            np.testing.assert_array_equal(part_pairs, pairs[first:first + 1])
            np.testing.assert_array_equal(part_load, load)
            np.testing.assert_allclose(part_term, term, rtol=1e-6)
            # ... and the program's block, given the same share, is that
            # part, with the same term
            got, counts = DecoderBlock(share, 4).apply(
                {"params": held}, x[None])
            np.testing.assert_allclose(got[0], part, rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(counts["seq_aux_loss"], [term],
                                       rtol=1e-5)
            np.testing.assert_array_equal(counts["router_load"], load)
    assert float(term) > ALPHA / 2
    np.testing.assert_allclose(common + routed, want, rtol=2e-4, atol=2e-5)


def test_a_pass_publishes_the_term_by_layer_and_the_epochs_record_its_sum():
    from fmda_tpu.obs.events import default_epoch_log
    from fmda_tpu.obs.registry import default_registry

    trainer, dataset, _ = _trainer(small_cfg())
    source = dataset.source
    state, history, _ = trainer.fit(source, epochs=1, dataset=dataset)
    reg = default_registry()
    by_layer = [reg.gauge("moe_seq_aux_loss", layer=str(i),
                          phase="train").value for i in (1, 2)]
    # a fresh router is nearly even: each layer's term is near alpha
    assert all(0.8 * ALPHA < v < 1.5 * ALPHA for v in by_layer), by_layer
    assert all(0.8 * ALPHA < reg.gauge(
        "moe_seq_aux_loss", layer=str(i), phase="eval").value < 1.5 * ALPHA
        for i in (1, 2))
    record = default_epoch_log().tail(1)[-1]
    assert record["train"]["seq_aux_loss"] == pytest.approx(sum(by_layer))
    assert "seq_aux_loss" in record["eval"]
    # the history's losses are next-token losses: near ln(vocabulary) on
    # fresh parameters, with no 2 * alpha on top
    assert abs(history["val"][0].loss - np.log(VOCAB)) < 0.05


def test_the_step_has_the_terms_scope_and_the_querys_product_under_mla_proj():
    trainer, _, batch = _trainer(small_cfg(dtype="bfloat16", remat=True))
    state = trainer.init_state(jax.random.PRNGKey(0))
    text = trainer._train_step._jit.lower(
        state, trainer.zero_totals(), batch,
        jax.random.PRNGKey(1)).as_text(debug_info=True)
    import re

    paths = set(re.findall(r'"(jit\([^"]*)"', text))
    aux = [p for p in paths if "/moe_seq_aux/" in p or p.endswith(
        "/moe_seq_aux")]
    assert any("transpose(jvp(" in p for p in aux)   # backward
    assert any("transpose(" not in p for p in aux)   # forward
    # the direct query's product, (.., 32) x (32, 4 * 24): every such
    # dot of the forward pass is under attention/mla_proj
    named = dict(re.findall(r'(#loc\d+) = loc\("(jit\([^"]*)"', text))
    products = [named[loc] for loc in re.findall(
        r"dot_general.*tensor<2x40x32xbf16>, tensor<32x96xbf16>.*"
        r"loc\((#loc\d+)\)", text)]
    assert products and all(
        "/attention/mla_proj/" in name for name in products), products
