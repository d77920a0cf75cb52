"""Plain reference for a decoder whose layers mix a gated delta rule (one
decay a head, a correction that may overshoot, keys and values of
different widths) with unrotated full attention under whole-width q/k
norms, a dense gated MLP in every layer, the block's norms on the
sublayers' OUTPUTS and an untied head (Olmo-Hybrid-7B's layers,
``model_type`` ``olmo_hybrid``): forward, loss and gradients in
straightforward ``jax.numpy``, float32, every product under
``jax.default_matmul_precision("highest")``.  No kernel, no chunked form
of the recurrence, no solve, no token chunks in the loss.  It imports
nothing of ``fmda_tpu``; where the mathematics is an accepted
reference's it is imported from there (the recurrence position by
position from ``reference/kda_decoder.py``, handed one decay a head on
every channel; the norm, the blockwise attention core, the gated MLP, the
rounding of the wrong runs, the head's loss and the first Adam step from
``reference/latent_decoder.py``; the shifted-sum convolution from
``reference/hybrid_decoder.py``; the wrong run's rotary from
``reference/moe_decoder.py``); it reads the program's parameter tree
(names below) and a record of sizes (``cfg``: the program's
``ModelConfig`` or anything with the same attributes).

One block on one sequence (``x``: the stream, ``(T, d)``; source: the
catalog's ``config`` for Olmo-Hybrid-7B; ``H`` heads held, ``dk`` = 96,
``dv`` = 192)::

    a gated-delta-rule layer (``layer_types`` ``linear_attention``; ``layer_layout`` 6), no position:
        q  = L2norm_head(silu(conv4(x @ wq))) ;  k = L2norm_head(silu(conv4(x @ wk)))     (T, H, dk)
        v  = silu(conv4(x @ wv))                                                          (T, H, dv)
        g  = -exp(a_log)[head] * softplus(x @ wa + dt_bias)        (T, H), <= 0: ONE log-decay a head
        b  = gdn_beta_scale * sigmoid(x @ wb)                      (T, H) in 0..2
        S_t = exp(g_t) S_{t-1} ;  S_t += b_t k_t (v_t - S_t^T k_t)^T     S_{-1} = 0, (dk, dv) a head
        o_t = S_t^T q_t * dk^-1/2
        mixer = (RMSNorm_head(o) * silu(x @ wg)) @ wo
    a full-attention layer (``full_attention``; ``layer_layout`` 0), no position:
        q = RMSNorm(x @ wq) ;  k = RMSNorm(x @ wk)   over the projection's whole held width
        a = causal softmax(q k^T * head_dim^-1/2) v ;  mixer = a @ wo
    x1 = x + RMSNorm(mixer(x)) ;  x2 = x1 + RMSNorm((silu(x1 w_gate) * (x1 w_up)) w_down)
    then a final RMSNorm and the head; the loss is the mean next-token
    cross-entropy over the tokens whose mask is 1.

**The recurrence is computed as written, position by position** (a
``lax.scan`` over t, the state a float32 ``(H, dk, dv)`` array): not the
chunked form the program runs.

Departures from the published description, each shared with the program
(the configuration's file lists them under ``assumed``): the share (the
heads held of both mixers, with nothing standing in for the other
chip's: a mixer's output before the block's norm and the q/k norms' sums
of squares are over the held heads alone; the MLP whole; the vocabulary
the held slice; one period of the layer pattern); documents cross joins
with neither the state nor the convolutions reset.  :func:`delta_mixer`
and :func:`attention` are the mixers BEFORE the block's norm, where the
deployment's reduction over the pair of chips sits: the shares' outputs
add up to the uncut mixer's (``attention`` then takes the whole width's
mean squares, ``squares``, which the pair reduces before the core).

Measures that keep 8,192 tokens inside a chip's memory without changing
a number: the recurrence's backward replays ``SEGMENT`` positions at a
time, attention scores a block of query rows against all keys at a time,
``remat=True`` recomputes each block in backward.  The comparison on the
chip takes the backward a layer at a time
(:func:`loss_and_grads_by_layer`).

Deliberately wrong runs (``wrong``: keywords of :func:`hidden_states`),
which the comparison that decides ``correct`` must catch: ``decay``
(``"none"``: ``g = 0``), ``correction`` (``False``: ``S_t += b_t k_t
v_t^T``), ``overshoot`` (``False``: ``b = sigmoid``), ``qk_norm``
(``False``: q and k not taken to unit length), ``gate`` (``"sigmoid"``
in place of ``silu``), ``pre_norm`` (``True``: the block's norms on the
sublayers' inputs), ``qk_rms`` (``False``: the attention layer's q/k
RMSNorm left out), ``rotary`` (``True``: rotary over the attention
layer's heads at ``cfg.rope_theta``), ``state_as`` (the carried state
and ``g`` rounded to a narrower type), ``products_as`` (every operand of
every product, and what enters the recurrence, rounded).

Parameter tree (the program's, float32): ``embed (V, D)``; ``block_<i>``:
``ln_attn (D,)``, ``ln_mlp (D,)``, ``w_gate``/``w_up (D, F)``, ``w_down
(F, D)``; a gated-delta-rule layer: ``wq``/``wk (D, H dk)``, ``wv``/``wg
(D, H dv)``, ``conv_q``/``conv_k (H dk, 4)``, ``conv_v (H dv, 4)``,
``wa``/``wb (D, H)``, ``dt_bias``/``a_log (H,)``, ``o_norm (dv,)``, ``wo
(H dv, D)``; an attention layer: ``wq``/``wk``/``wv (D, N hd)``,
``q_norm``/``k_norm (N hd,)``, ``wo (N hd, D)``; ``ln_final (D,)``;
``head (D, V)``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.hybrid_decoder import _conv
from benchmark.reference.kda_decoder import L2_NORM_EPS, _delta_recurrence
from benchmark.reference.latent_decoder import (  # noqa: F401  (re-exported)
    _attention_core, _gated, _head_logits, _nll_mean, _rms_norm, _rounder,
    first_adam_step)
from benchmark.reference.moe_decoder import _rotary

#: ``layer_layout``'s value for a gated-delta-rule layer.
GDN_LAYOUT = 6


def delta_mixer(p: Dict, h, cfg, remat: bool, wrong: Dict):
    """The gated-delta-rule mixer on the stream h (T, D) -> (T, D),
    before the block's norm."""
    t = h.shape[0]
    heads, dk, dv = cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    narrow = _rounder(wrong.get("products_as"))
    h_n = narrow(h)

    def short_conv(name, taps, width):
        w = p[taps]
        y = jax.nn.silu(_conv(h_n @ narrow(p[name]), w,
                              jnp.zeros(w.shape[:1], jnp.float32)))
        return y.reshape(t, heads, width)

    q, k, v = (short_conv(name, taps, width) for name, taps, width in (
        ("wq", "conv_q", dk), ("wk", "conv_k", dk), ("wv", "conv_v", dv)))
    if wrong.get("qk_norm", True):
        q, k = (x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + L2_NORM_EPS)
            for x in (q, k))
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(
        h_n @ narrow(p["wa"]) + p["dt_bias"])                  # (T, H)
    if wrong.get("decay") == "none":
        g = jnp.zeros_like(g)
    span = cfg.gdn_beta_scale if wrong.get("overshoot", True) else 1.0
    b = span * jax.nn.sigmoid(h_n @ narrow(p["wb"]))
    # one decay a head is that decay on every key channel
    o = dk ** -0.5 * _delta_recurrence(
        narrow(q), narrow(k), narrow(v),
        jnp.broadcast_to(g[..., None], q.shape), b, remat=remat,
        correction=wrong.get("correction", True),
        state_as=wrong.get("state_as"))
    gate = h_n @ narrow(p["wg"])
    gate = (jax.nn.sigmoid(gate) if wrong.get("gate") == "sigmoid"
            else jax.nn.silu(gate))
    o = _rms_norm(o, p["o_norm"], cfg.rms_norm_eps) * gate.reshape(
        t, heads, dv)
    return narrow(o.reshape(t, heads * dv)) @ narrow(p["wo"])


def attention(p: Dict, h, cfg, remat: bool, wrong: Dict, squares=None):
    """Full attention without position under whole-width q/k norms on the
    stream h (T, D) -> (T, D), before the block's norm.  ``squares``:
    ``(mean of q's squares, mean of k's squares)``, (T, 1) each, over a
    width this share holds only part of (what chips that share a layer by
    head reduce before the core); None: over the width held."""
    t = h.shape[0]
    n, hd = cfg.n_heads, cfg.head_dim
    narrow = _rounder(wrong.get("products_as"))
    h_n = narrow(h)
    q, k, v = (h_n @ narrow(p[name]) for name in ("wq", "wk", "wv"))
    if wrong.get("qk_rms", True):
        if squares is None:
            squares = tuple(jnp.mean(x * x, axis=-1, keepdims=True)
                            for x in (q, k))
        q, k = (x * jax.lax.rsqrt(mean + cfg.rms_norm_eps) * p[name]
                for x, mean, name in zip((q, k), squares,
                                         ("q_norm", "k_norm")))
    q, k, v = (x.reshape(t, n, hd).transpose(1, 0, 2) for x in (q, k, v))
    if wrong.get("rotary"):  # the configuration states none
        q, k = _rotary(q, cfg.rope_theta), _rotary(k, cfg.rope_theta)
    a = _attention_core(q, k, v, hd ** -0.5, remat, wrong)
    return narrow(a.transpose(1, 0, 2).reshape(t, n * hd)) @ narrow(p["wo"])


def block(p: Dict, x, cfg, layout: int, remat: bool, wrong: Dict):
    """One block on one sequence's stream x (T, d) -> x'."""
    eps = cfg.rms_norm_eps
    narrow = _rounder(wrong.get("products_as"))
    mixer = delta_mixer if layout == GDN_LAYOUT else attention

    def mlp(u):
        return _gated(narrow(u), p["w_gate"], p["w_up"], p["w_down"], narrow)

    if wrong.get("pre_norm"):  # every other configuration's block
        x = x + mixer(p, _rms_norm(x, p["ln_attn"], eps), cfg, remat, wrong)
        return x + mlp(_rms_norm(x, p["ln_mlp"], eps))
    x = x + _rms_norm(mixer(p, x, cfg, remat, wrong), p["ln_attn"], eps)
    return x + _rms_norm(mlp(x), p["ln_mlp"], eps)


def hidden_states(params: Dict, ids, cfg, *, remat: bool = False, **wrong):
    """ids (T,) -> the final norm's output (T, D)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][ids]
        for i, layout in enumerate(cfg.layer_layout):
            layer = lambda p, x, _l=int(layout): block(
                p, x, cfg, _l, remat, wrong)
            if remat:
                layer = jax.checkpoint(layer)
            x = layer(params[f"block_{i}"], x)
        return _rms_norm(x, params["ln_final"], cfg.rms_norm_eps)


def logits(params: Dict, ids, cfg, **kw):
    """ids (T,) -> (T, V) float32."""
    wrong = {k: v for k, v in kw.items() if k != "remat"}
    with jax.default_matmul_precision("highest"):
        return _head_logits(params, hidden_states(params, ids, cfg, **kw),
                            wrong)


def loss(params: Dict, ids, targets, mask, cfg, **kw):
    """Mean next-token cross-entropy over the masked tokens of one
    sequence."""
    with jax.default_matmul_precision("highest"):
        keep = mask > 0
        return _nll_mean(logits(params, ids, cfg, **kw), targets, keep,
                         jnp.maximum(jnp.sum(keep), 1))


def batch_loss(params: Dict, x, y, mask, cfg, **kw):
    """The trainer's step loss on a batch (B, T): the mean over all the
    batch's masked tokens."""
    total = count = 0.0
    for i in range(x.shape[0]):
        n_i = jnp.sum(mask[i] > 0)
        total = total + loss(params, x[i], y[i], mask[i], cfg, **kw) * n_i
        count = count + n_i
    return total / jnp.maximum(count, 1)


def loss_and_grads(params: Dict, x, y, mask, cfg, *, remat: bool = True,
                   **wrong):
    """``(loss, gradients)`` of :func:`batch_loss`, float32."""
    # the whole value_and_grad inside the precision context: the backward
    # is traced after the forward returns
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: batch_loss(p, x, y, mask, cfg, remat=remat, **wrong)
        )(params)


def loss_and_grads_by_layer(params: Dict, x, y, mask, cfg, **wrong):
    """:func:`loss_and_grads` again, the backward written out a block at
    a time: forward keeping each block's input, the head's gradient, then
    each block's vector-Jacobian product from the last to the first, the
    embedding's rows last; a batch's sequences one after the other.  The
    same numbers (tests/test_gdn_decoder.py); at the published widths no
    more than one block's backward is compiled (one a kind of block) or
    held at a time.  Gradients come back as host arrays."""
    def block_fn(layout):
        return lambda p, h: block(p, h, cfg, layout, True, wrong)

    def in_highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def head_loss(ln_final, head, h, targets, keep, count):
        hidden = _rms_norm(h, ln_final, cfg.rms_norm_eps)
        return _nll_mean(_head_logits({"head": head}, hidden, wrong),
                         targets, keep, count)

    layouts = [int(v) for v in cfg.layer_layout]
    forward = {v: in_highest(block_fn(v)) for v in set(layouts)}
    backward = {v: in_highest(
        lambda p, h, ct, _f=block_fn(v): jax.vjp(_f, p, h)[1](ct))
        for v in set(layouts)}
    head_grad = in_highest(jax.value_and_grad(head_loss, argnums=(0, 1, 2)))
    rows = params["embed"].shape
    embed_grad = jax.jit(
        lambda ids, ct: jnp.zeros(rows, jnp.float32).at[ids].add(ct))

    keeps = np.asarray(mask) > 0
    count = jnp.maximum(int(keeps.sum()), 1)
    total, grads = 0.0, None
    for ids, targets, keep in zip(x, y, keeps):
        if not keep.any():  # a padded sequence adds nothing
            continue
        inputs = [params["embed"][ids]]
        for i, layout in enumerate(layouts):
            inputs.append(forward[layout](params[f"block_{i}"], inputs[-1]))
        part, (g_ln, g_head, ct) = head_grad(
            params["ln_final"], params["head"], inputs.pop(), targets,
            jnp.asarray(keep), count)
        one = {"ln_final": np.asarray(g_ln), "head": np.asarray(g_head)}
        for i in reversed(range(len(layouts))):
            g_block, ct = backward[layouts[i]](
                params[f"block_{i}"], inputs.pop(), ct)
            one[f"block_{i}"] = jax.tree.map(np.asarray, g_block)
        one["embed"] = np.asarray(embed_grad(ids, ct))
        total = total + float(part)
        grads = one if grads is None else jax.tree.map(np.add, grads, one)
    return total, {k: grads[k] for k in params}
