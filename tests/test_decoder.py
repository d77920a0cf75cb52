"""The token ``decoder`` family against its plain reference
(benchmark/reference/moe_decoder.py) at a small size on the CPU: logits,
loss and gradients on seeded random weights; window layers differ from
full layers past the window and agree inside it; rotary positions on
layout 1 only.  The published widths are compared on the chip
(benchmark/drivers/train_token_epochs.py)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import moe_decoder as ref  # noqa: E402
from fmda_tpu.config import ModelConfig, TrainConfig  # noqa: E402
from fmda_tpu.data.pipeline import Batch  # noqa: E402
from fmda_tpu.models import build_model  # noqa: E402
from fmda_tpu.models.decoder import check_decoder_config, rotary  # noqa: E402
from fmda_tpu.train.tasks import NextToken  # noqa: E402

SEQ, VOCAB, WINDOW = 64, 256, 16


def small_cfg(**over):
    return ModelConfig(**{**dict(
        cell="decoder", hidden_size=64, n_heads=4, n_kv_heads=2,
        head_dim=16, vocab_size=VOCAB, layer_layout=(0, 1, 1, 1),
        sliding_window=WINDOW, rope_theta=1.5e6, moe_experts=8,
        moe_top_k=2, moe_ffn_size=32, experts_held=(0, 8), loss_chunk=16,
        dtype="float32"), **over})


def _params(cfg, seed=0):
    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(seed)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # the family's init is N(0, 0.02): too flat for routing and attention
    # to matter at hidden 64, so the comparison is made on wider weights
    keys = jax.random.split(jax.random.PRNGKey(seed + 1),
                            len(jax.tree.leaves(params)))
    leaves, tree = jax.tree.flatten(params)
    wide = [l if l.ndim == 1 else 0.2 * jax.random.normal(k, l.shape)
            for l, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(tree, wide)


def _ids(seed=3, n=SEQ + 1, batch=2):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, size=(batch, n)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


@pytest.mark.parametrize("held", [(0, 8), (2, 4)])
def test_logits_match_the_reference(held):
    cfg = small_cfg(experts_held=held)
    model, params = _params(cfg)
    x, _ = _ids()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: model.apply({"params": p}, x))(params, x)
    reference = jax.jit(lambda p, ids: ref.logits(p, ids, cfg))
    for b in range(x.shape[0]):
        np.testing.assert_allclose(got[b], reference(params, x[b]),
                                   rtol=2e-4, atol=2e-4)


def _program_loss_and_grads(cfg, params, batch):
    model = build_model(cfg)
    task = NextToken(cfg, TrainConfig(batch_size=2, window=SEQ))

    def program_loss(p):
        return task.loss(p, task.forward(model, p, batch, None), batch)[0]

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(program_loss))(params)


@pytest.mark.parametrize("remat", [False, True, "replay"])
def test_loss_and_gradients_match_the_reference(remat):
    """``"replay"``: the reference here is the program itself without
    recomputation.  A block's replay keeps what it names and remakes the
    rest from the block's input: loss and every gradient leaf are the
    same bits with ``remat`` on and off."""
    layout = (0, 1) if remat == "replay" else small_cfg().layer_layout
    kw = dict(experts_held=(4, 4), layer_layout=layout)
    cfg = small_cfg(remat=bool(remat), **kw)
    _, params = _params(cfg)
    x, y = _ids()
    mask = jnp.ones(x.shape, jnp.float32).at[1, 40:].set(0.0)
    batch = Batch(x, y, mask)
    got, got_grads = _program_loss_and_grads(cfg, params, batch)
    if remat == "replay":
        want, want_grads = _program_loss_and_grads(
            small_cfg(remat=False, **kw), params, batch)
        np.testing.assert_array_equal(got, want)
        for (path, g), w in zip(
                jax.tree_util.tree_leaves_with_path(got_grads),
                jax.tree.leaves(want_grads)):
            np.testing.assert_array_equal(g, w, err_msg=str(path))
        return
    want, want_grads = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg, remat=remat))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
    flat_want = jax.tree.leaves(want_grads)
    for (path, g), w in zip(flat_got, flat_want):
        scale = float(jnp.abs(w).max()) + 1e-12
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-7, path


def test_expert_pair_counts_match_the_reference_routing():
    cfg = small_cfg(experts_held=(2, 4))
    model, params = _params(cfg)
    x, _ = _ids(batch=1)
    with jax.default_matmul_precision("highest"):
        _, stats = jax.jit(lambda p, x: model.apply(
            {"params": p}, x, method="features"))(params, x)
    _, want = jax.jit(lambda p, ids: ref.hidden_states(p, ids, cfg))(
        params, x[0])
    np.testing.assert_array_equal(stats.expert_pairs, want)
    assert int(stats.dropped) == 0


def test_window_layers_agree_inside_the_window_and_differ_past_it():
    """A window layer and a full layer of the same weights give the same
    rows while the whole past fits the window, and different ones after;
    the layout is the only difference (rotary off for both via theta
    that leaves positions where they are is not possible, so the full
    layer is given rotary too by comparing layouts (1,) with window
    >= T against (1,) with the small window)."""
    wide = small_cfg(layer_layout=(1,), sliding_window=SEQ)
    narrow = small_cfg(layer_layout=(1,), sliding_window=WINDOW)
    model_w, params = _params(wide)
    model_n = build_model(narrow)
    x, _ = _ids(batch=1)
    with jax.default_matmul_precision("highest"):
        a = model_w.apply({"params": params}, x)[0]
        b = model_n.apply({"params": params}, x)[0]
    np.testing.assert_allclose(a[:WINDOW], b[:WINDOW], rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(a[WINDOW:] - b[WINDOW:]).max()) > 1e-3


def test_rotary_on_layout_one_only():
    """Layout 0 has no positional encoding: with its causal mask the
    last row's logits do not change when the earlier tokens are
    permuted; layout 1 rotates q and k, so they do."""
    x, _ = _ids(batch=1)
    perm = jnp.concatenate([x[:, :SEQ - 1][:, ::-1], x[:, SEQ - 1:]], axis=1)
    for layout, moves in ((0, False), (1, True)):
        cfg = small_cfg(layer_layout=(layout,), sliding_window=SEQ)
        model, params = _params(cfg)
        with jax.default_matmul_precision("highest"):
            a = model.apply({"params": params}, x)[0, -1]
            b = model.apply({"params": params}, perm)[0, -1]
        gap = float(jnp.abs(a - b).max())
        assert (gap > 1e-3) == moves, (layout, gap)


def test_rotary_keeps_norms_and_is_identity_at_position_zero():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, 16))
    r = rotary(q, 1.5e6)
    np.testing.assert_allclose(r[:, :, 0], q[:, :, 0], rtol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(r, axis=-1),
                               jnp.linalg.norm(q, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(r[0], ref._rotary(q[0], 1.5e6), rtol=1e-6)


@pytest.mark.parametrize("field,value", [
    ("vocab_size", 0), ("n_kv_heads", 3), ("layer_layout", ()),
    ("experts_held", (6, 4)), ("moe_top_k", 9), ("head_dim", 0)])
def test_an_unset_size_is_refused_by_name(field, value):
    with pytest.raises(ValueError, match=field.split("_")[0]):
        check_decoder_config(small_cfg(**{field: value}))


def test_the_chunked_loss_is_the_whole_loss():
    from fmda_tpu.train.losses import chunked_next_token_loss

    k = jax.random.split(jax.random.PRNGKey(5), 3)
    hidden = jax.random.normal(k[0], (96, 16))
    head = jax.random.normal(k[1], (16, 50))
    y = jax.random.randint(k[2], (96,), 0, 50)
    mask = jnp.ones((96,)).at[90:].set(0.0)

    def whole(hidden, head):
        lg = hidden @ head
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, y[:, None], -1)[:, 0]
        return jnp.sum(nll * mask)

    for chunk in (96, 32, 40):  # 40 does not divide: lowered to 32
        f = lambda h, w: chunked_next_token_loss(h, w, y, mask,
                                                 chunk=chunk)[0]
        with jax.default_matmul_precision("highest"):
            got = jax.value_and_grad(f, (0, 1))(hidden, head)
            want = jax.value_and_grad(whole, (0, 1))(hidden, head)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)
    _, tokens, correct = chunked_next_token_loss(hidden, head, y, mask,
                                                 chunk=32)
    assert int(tokens) == 90
    assert int(correct) == int(jnp.sum(
        (jnp.argmax(hidden @ head, -1) == y) & (mask > 0)))
