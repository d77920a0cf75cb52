"""The hyper-connections' hand-written backward (ops/hyper_connection.py
``around``, ops/pallas_hyper_connection.py) against ``jax.grad`` of the
plain composition ``coefficients`` / ``read`` / ``write`` kept here:
every output of the rule, both stream dtypes, two and four lanes, token
counts the kernels take and refuse, with and without recomputation; a
second reader of the stream; what the rule keeps; the refusal's
counter."""

import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fmda_tpu.ops import hyper_connection as hc  # noqa: E402
from fmda_tpu.ops import pallas_hyper_connection as pallas_hc  # noqa: E402
from fmda_tpu.ops.dispatch import (  # noqa: E402
    kernel_fallbacks, reset_kernel_fallbacks)

HIDDEN = pallas_hc.D_BLOCK
KW = dict(norm_eps=1e-5, iters=20, eps=1e-6, clamp=30.0)
NAMES = ("dx", "p_pre", "p_post", "p_res", "a", "b", "w", "dy")


def _inputs(n, tokens, dtype, seed=0):
    """A stream, mixing parameters far from a fresh block's (every lane
    read, written and remixed), a sublayer's matrix, what is added to
    the sublayer's output (its gradient is ``dy``) and what the written
    stream is weighed by."""
    k = jax.random.split(jax.random.PRNGKey(seed), 10)
    d = HIDDEN
    x = jax.random.normal(k[0], (2, tokens, n, d)).astype(dtype)
    ps = tuple(0.2 * jax.random.normal(k[1 + i], (n * d, w))
               for i, w in enumerate((n, n, n * n)))
    a = (jnp.float32(0.5), jnp.float32(0.4), jnp.float32(0.6))
    b = (jax.random.normal(k[4], (n,)), jax.random.normal(k[5], (n,)),
         jax.random.normal(k[6], (n, n)))
    w = 0.1 * jax.random.normal(k[7], (d, d))
    added = jax.random.normal(k[8], (2, tokens, d)).astype(dtype)
    weight = jax.random.normal(k[9], (2, tokens, n, d))
    return (x, *ps, a, b, w, added), weight


def _sublayer(u, w, added):
    return jnp.tanh(jnp.dot(u, w.astype(u.dtype))) + added


def _plain(weight):
    """The composition autodiff differentiates: the reference."""
    def loss(x, p_pre, p_post, p_res, a, b, w, added):
        mix = hc.coefficients(x, p_pre, p_post, p_res, a, b, **KW)
        y = _sublayer(hc.read(x, mix.pre), w, added)
        out = hc.write(x, y, mix.post, mix.res)
        return jnp.sum(out.astype(jnp.float32) * weight)
    return loss


def _ruled(weight, impl, probe=None):
    """The same through ``hc.around``; ``probe`` weighs a second reading
    of the stream, beside the mixing."""
    def loss(x, p_pre, p_post, p_res, a, b, w, added):
        out, _, _ = hc.around(
            lambda u: (_sublayer(u, w, added), None), x, p_pre, p_post,
            p_res, a, b, impl=hc.backward_impl(impl, x.shape[-1], x.shape[1]),
            **KW)
        total = jnp.sum(out.astype(jnp.float32) * weight)
        if probe is not None:
            total += jnp.sum(x.astype(jnp.float32) * probe)
        return total
    return loss


def _grads(loss, args):
    with jax.default_matmul_precision("highest"):
        g = jax.jit(jax.grad(loss, argnums=tuple(range(len(args)))))(*args)
    return dict(zip(NAMES, g))


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "replayed"])
@pytest.mark.parametrize("tokens", [128, 40], ids=["tile", "ragged"])
@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_rule_gives_the_plain_compositions_gradient(
        dtype, n, tokens, remat):
    """Every output of the rule against autodiff of the plain
    composition, the kernels under the interpreter where they take the
    shape (128 tokens); where they refuse it (40) ``around`` gives the
    plain composition to autodiff.  float32: equal but for the order of
    sums.  bfloat16: the stream's gradient within one rounding of the
    float32 sum, the sublayer's within one too, the parameters' within
    1e-2 of a leaf's largest entry, which is what a bfloat16 rounding
    either way of ``dy``, ``du`` and the stream's gradient leaves of
    them (autodiff adds the stream's gradient up from bfloat16 parts).
    That the rule's two backward products take the product's gradient
    in the stream's dtype does not show beside it: the three matrices'
    worst entries read 3.2e-3 / 3.6e-3 / 3.1e-3 of the largest over
    three seeds, and 2.8e-3 / 3.8e-3 / 3.0e-3 with that gradient kept
    float32."""
    args, weight = _inputs(n, tokens, jnp.dtype(dtype))
    ruled = _ruled(weight, "interpret")
    if remat:
        ruled = jax.checkpoint(
            ruled, policy=jax.checkpoint_policies.save_only_these_names("o"))
    want, got = _grads(_plain(weight), args), _grads(ruled, args)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for name in NAMES:
        for g, w in zip(jax.tree.leaves(got[name]),
                        jax.tree.leaves(want[name])):
            assert g.dtype == w.dtype and g.shape == w.shape, name
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            size = float(np.abs(w).max())
            if dtype == "float32":
                np.testing.assert_allclose(
                    g, w, rtol=1e-5, atol=1e-5 * size, err_msg=name)
            elif name in ("dx", "dy"):
                # one rounding either way of a bfloat16 value: 2^-7 of it
                np.testing.assert_allclose(
                    g, w, rtol=2.0 ** -7, atol=2.0 ** -8 * size,
                    err_msg=name)
            else:
                np.testing.assert_allclose(
                    g, w, rtol=0, atol=1e-2 * size, err_msg=name)


def test_the_kernels_ran_where_the_shape_fits_and_not_where_it_does_not():
    reset_kernel_fallbacks()
    assert hc.backward_impl("interpret", HIDDEN, 128) == "interpret"
    assert hc.backward_impl("pallas", 3584, 4096) == "pallas"
    assert kernel_fallbacks() == {}
    # tokens that no tile divides; a width that is not whole blocks
    assert hc.backward_impl("pallas", HIDDEN, 40) == "jnp"
    assert hc.backward_impl("interpret", 96, 128) == "jnp"
    assert kernel_fallbacks() == {"decoder:hc_shape": 2}
    # nothing asked for, nothing refused
    assert hc.backward_impl("jnp", 96, 40) == "jnp"
    assert kernel_fallbacks() == {"decoder:hc_shape": 2}
    reset_kernel_fallbacks()


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_a_second_reader_of_the_stream_adds_its_own_gradient(impl):
    """The rule's second half hands the written stream's gradient back
    through what the first half carried, which never leaves ``around``:
    something else that reads the stream beside the mixing (a probe, an
    auxiliary loss) adds its gradient to the rule's and changes nothing
    else."""
    args, weight = _inputs(4, 128, jnp.dtype("float32"))
    probe = jax.random.normal(jax.random.PRNGKey(7), weight.shape)
    want = _grads(_plain(weight), args)
    got = _grads(_ruled(weight, impl, probe), args)
    np.testing.assert_allclose(
        got["dx"], want["dx"] + probe, rtol=1e-5,
        atol=1e-5 * float(jnp.abs(want["dx"]).max()))
    for name in NAMES[1:]:
        for g, w in zip(jax.tree.leaves(got[name]),
                        jax.tree.leaves(want[name])):
            np.testing.assert_allclose(
                g, w, rtol=1e-5, atol=1e-5 * float(jnp.abs(w).max()),
                err_msg=name)


def test_the_rule_keeps_no_float32_array_of_the_streams_size():
    """What the two halves' forward rules save for their backward: the
    stream and the sublayer's output in their own dtype, the parameters,
    and per-token float32 numbers."""
    (x, p_pre, p_post, p_res, a, b, w, added), _ = _inputs(
        4, 128, jnp.bfloat16)
    static = (KW["norm_eps"], KW["iters"], KW["eps"], KW["clamp"], True)
    (u, post, res, carried), kept = hc._enter_fwd(
        static, x, p_pre, p_post, p_res, a, b)
    _, kept_after = hc._leave_fwd(
        True, carried, _sublayer(u, w, added), post, res)
    tokens = x.shape[0] * x.shape[1]
    streams = 0
    for leaf in jax.tree.leaves((kept, kept_after)):
        if leaf.size >= x.size:
            assert leaf.dtype == x.dtype and leaf.shape == x.shape
            streams += 1
        elif leaf.dtype == jnp.float32 and leaf.ndim and leaf.size > tokens:
            # a parameter, or at most n*n + 2n numbers a token
            assert leaf.size <= 24 * tokens or leaf.shape[0] == 4 * HIDDEN
    assert streams == 2  # the stream, once a half


def test_a_recomputed_block_on_the_kernels_is_the_block_under_autodiff(
        monkeypatch):
    """``nn.remat`` around a latent block whose mixing's backward runs the
    kernels (under the interpreter) against the same block with the
    mixing left to autodiff: the output's gradient to the stream and to
    every parameter."""
    from fmda_tpu.config import ModelConfig
    from fmda_tpu.models import decoder

    cfg = ModelConfig(
        cell="decoder", hidden_size=HIDDEN, n_heads=2, vocab_size=64,
        layer_layout=(4,), rms_norm_eps=1e-5, q_lora_rank=16,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, ffn_size=32, first_dense_layers=1, hc_streams=4,
        dtype="float32", use_pallas=True)
    rng = np.random.default_rng(1)
    lanes = jnp.asarray(rng.normal(size=(1, 128, 4, HIDDEN)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=lanes.shape), jnp.float32)
    block = nn.remat(decoder.DecoderBlock)(cfg, 4, True)
    params = block.init({"params": jax.random.PRNGKey(0)}, lanes)["params"]
    params = jax.tree.map(  # off the fresh block's saturated mixing
        lambda p: p + 0.3 * jnp.asarray(rng.normal(size=p.shape), p.dtype),
        params)

    def grads(impl):
        monkeypatch.setattr(decoder, "kernel_impl", lambda use: impl)
        with jax.default_matmul_precision("highest"):
            return jax.grad(lambda p, x: jnp.sum(
                block.apply({"params": p}, x)[0] * weight),
                argnums=(0, 1))(params, lanes)

    want, got = grads("jnp"), grads("interpret")
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))
