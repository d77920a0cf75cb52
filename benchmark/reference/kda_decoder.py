"""Plain reference for a decoder whose layers mix a delta rule with a
decay a channel (Kimi Delta Attention) and unrotated latent attention,
with a sigmoid router over shared and routed experts (Kimi-Linear-48B-
A3B's layers, ``model_type`` ``kimi_linear``): forward, loss and
gradients in straightforward ``jax.numpy``, float32, every product under
``jax.default_matmul_precision("highest")``.  No kernel, no chunked form
of the recurrence, no solve, no sort, no gather of pairs, no token chunks
in the loss.  It imports nothing of ``fmda_tpu``; where the mathematics
is an accepted reference's it is imported from there (the router, the
experts and the shared expert from ``reference/mla_decoder.py``; the
norm, the blockwise attention core, the rounding of the wrong runs, the
head's loss, the first Adam step and the bias step from
``reference/latent_decoder.py``; the shifted-sum convolution from
``reference/hybrid_decoder.py``); it reads the program's parameter tree
(names below) and a record of sizes (``cfg``: the program's
``ModelConfig`` or anything with the same attributes).

One block on one sequence (``x``: the stream, ``(T, d)``; source: the
catalog's ``config`` for Kimi-Linear-48B-A3B-Instruct; ``H`` = 32 heads,
``dk`` = 128)::

    h  = RMSNorm(x)                                               eps 1e-5
    a delta-rule layer (``linear_attn_config.kda_layers``; ``layer_layout`` 5), no position:
        q  = L2norm_head(silu(conv4(h @ wq))) ;  k = L2norm_head(silu(conv4(h @ wk))) ;  v = silu(conv4(h @ wv))
        g  = -exp(a_log)[head] * softplus((h @ wf_a) @ wf_b + dt_bias)        (T, H, dk), <= 0
        b  = sigmoid(h @ wb)                                                   (T, H)
        S_t = Diag(exp(g_t)) S_{t-1} ;  S_t += b_t k_t (v_t - S_t^T k_t)^T     S_{-1} = 0, (dk, dk) a head
        o_t = S_t^T q_t * dk^-1/2
        x1 = x + (RMSNorm_head(o) * sigmoid((h @ wg_a) @ wg_b)) @ wo
    a latent layer (``full_attn_layers``; ``layer_layout`` 4):
        [qn | qr] = h @ wq (H x (128 | 64)) ;  [ckv | kr] = h @ wkv_a (512 | 64)
        [kn | v] = RMSNorm(ckv) @ wkv_b ;  NO rotary (``mla_use_nope``) ;  kr ONE head
        s[t, j] = (qn_t . kn_j + qr_t . kr_j) * 192^-1/2 ;  a = causal softmax(s) v ;  x1 = x + a @ wo
    u  = RMSNorm(x1)
    the first ``first_dense_layers`` layers:  x2 = x1 + (silu(u w_gate) * (u w_up)) w_down
    the others:  sc = sigmoid(u @ router) (256) ;  S = top-8 of (sc + router_bias)
                 g_e = 2.446 * sc_e / sum_{e' in S} sc_e'
                 x2 = x1 + SwiGLU_shared(u) + sum_{e in S, e held} g_e SwiGLU_e(u)
    then a final RMSNorm and the head; the loss is the mean next-token
    cross-entropy over the tokens whose mask is 1.

**The recurrence is computed as written, position by position**
(:func:`_delta_recurrence`: a ``lax.scan`` over t, the state a float32
``(H, dk, dk)`` array, its decay, its read and its correction
elementwise products and sums): not the chunked form the program runs.
After a train step ``router_bias_e += moe_bias_rate * sign(mean load -
load_e)`` over the step's pairs on all 256 experts; the bias has no
gradient.

Departures from the published description, each shared with the program
(the configuration's file lists them under ``assumed``): the share (only
the experts ``experts_held`` are summed; the router keeps its 256
outputs and normalises over the whole top-8; the shared expert is whole;
the vocabulary is the held slice; one dense layer and one period);
documents cross joins with neither the state nor the convolutions reset.

Measures that keep 8,192 tokens inside a chip's memory without changing
a number: the recurrence's backward replays :data:`SEGMENT` positions at
a time (``jax.checkpoint`` on a segment: 128 states of 2 MB and not
8,192); attention scores a block of query rows against all keys at a
time; ``remat=True`` recomputes each block in backward; the loop over
the held experts is a ``lax.scan``.  The comparison on the chip takes
the backward a layer at a time (:func:`loss_and_grads_by_layer`).

Deliberately wrong runs (``wrong``: keywords of :func:`hidden_states`),
which the comparison that decides ``correct`` must catch: ``decay``
(``"none"``: ``g = 0``, the plain delta rule; ``"head_mean"``: one decay
a head, the mean of ``g`` over the head's channels), ``correction``
(``False``: ``S_t += b_t k_t v_t^T``, no read of the state), ``qk_norm``
(``False``: q and k not taken to unit length), ``rotary`` (``True``:
rotary over the latent layer's 64 shared dims at ``rope_theta``),
``state_as`` (the carried state and ``g`` rounded to a narrower type),
``products_as`` (every operand of every product, and what enters the
recurrence, rounded), ``skip_shared`` (the shared expert left out).

Parameter tree (the program's, float32): ``embed (V, D)``; ``block_<i>``:
``ln_attn (D,)``; a delta-rule layer: ``wq``/``wk``/``wv (D, H dk)``,
``conv_q``/``conv_k``/``conv_v (H dk, 4)``, ``wf_a (D, dk)``, ``wf_b
(dk, H dk)``, ``dt_bias (H dk,)``, ``a_log (H,)``, ``wb (D, H)``, ``wg_a
(D, dk)``, ``wg_b (dk, H dk)``, ``o_norm (dk,)``, ``wo (H dk, D)``; a
latent layer: ``wq (D, H*192)``, ``wkv_a (D, 576)``, ``kv_norm (512,)``,
``wkv_b (512, H*256)``, ``wo (H*128, D)``; a dense layer: ``ln_mlp``,
``w_gate``/``w_up (D, F)``, ``w_down (F, D)``; an expert layer:
``ln_moe``, ``router (D, 256)``, ``router_bias (256,)``,
``ws_gate``/``ws_up (D, Fe)``, ``ws_down (Fe, D)``, ``w_gate``/``w_up
(count, D, Fe)``, ``w_down (count, Fe, D)``; ``ln_final (D,)``; ``head
(D, V)``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.hybrid_decoder import _conv
from benchmark.reference.latent_decoder import (  # noqa: F401  (re-exported)
    _attention_core, _head_logits, _nll_mean, _rms_norm, _rotary, _rounder,
    bias_step, first_adam_step, score_scale, yarn_inv_freq)
from benchmark.reference.mla_decoder import feed_forward

#: Positions of the recurrence replayed at a time in backward.
SEGMENT = 128
#: ``layer_layout``'s value for a delta-rule layer.
KDA_LAYOUT = 5
#: Added to a head's sum of squares before the root (q and k to unit
#: length): the mechanism's published value.
L2_NORM_EPS = 1e-6


def _delta_recurrence(q, k, v, g, b, *, remat: bool, correction: bool = True,
                      state_as=None):
    """``S_t = Diag(exp(g_t)) S_{t-1} ; S_t += b_t k_t (v_t - S_t^T
    k_t)^T ; o_t = S_t^T q_t`` position by position: q / k / g (T, H, K),
    v (T, H, V), b (T, H) -> o (T, H, V), the state float32 (H, K, V)."""
    t, h, dk = q.shape
    narrow = _rounder(state_as)
    g = narrow(g)

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[:, :, None] * state
        read = jnp.sum(state * k_t[:, :, None], axis=1) if correction else 0.0
        state = narrow(state + (b_t[:, None] * k_t)[:, :, None]
                       * (v_t - read)[:, None, :])
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    def run(state, segment):
        return jax.lax.scan(step, state, segment)

    state = jnp.zeros((h, dk, v.shape[-1]), jnp.float32)
    at = (q, k, v, g, b)
    if t <= SEGMENT or t % SEGMENT:
        return run(state, at)[1]
    if remat:
        run = jax.checkpoint(run)
    _, o = jax.lax.scan(run, state, jax.tree.map(
        lambda x: x.reshape((t // SEGMENT, SEGMENT) + x.shape[1:]), at))
    return o.reshape(t, h, v.shape[-1])


def delta_mixer(p: Dict, h, cfg, remat: bool, wrong: Dict):
    """The delta-rule mixer on the normalised stream h (T, D) -> (T, D)."""
    t = h.shape[0]
    heads, hd = cfg.kda_heads, cfg.kda_head_dim
    narrow = _rounder(wrong.get("products_as"))
    h_n = narrow(h)
    no_bias = jnp.zeros((heads * hd,), jnp.float32)

    def short_conv(name, taps):
        y = jax.nn.silu(_conv(h_n @ narrow(p[name]), p[taps], no_bias))
        return y.reshape(t, heads, hd)

    q, k, v = (short_conv(name, taps) for name, taps in (
        ("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    if wrong.get("qk_norm", True):
        q, k = (x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + L2_NORM_EPS)
            for x in (q, k))
    f = narrow(h_n @ narrow(p["wf_a"])) @ narrow(p["wf_b"])
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        (f + p["dt_bias"]).reshape(t, heads, hd))
    if wrong.get("decay") == "none":
        g = jnp.zeros_like(g)
    elif wrong.get("decay") == "head_mean":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    b = jax.nn.sigmoid(h_n @ narrow(p["wb"]))
    o = hd ** -0.5 * _delta_recurrence(
        narrow(q), narrow(k), narrow(v), g, b, remat=remat,
        correction=wrong.get("correction", True),
        state_as=wrong.get("state_as"))
    gate = jax.nn.sigmoid(narrow(h_n @ narrow(p["wg_a"])) @ narrow(p["wg_b"]))
    o = _rms_norm(o, p["o_norm"], cfg.rms_norm_eps) * gate.reshape(
        t, heads, hd)
    return narrow(o.reshape(t, heads * hd)) @ narrow(p["wo"])


def attention(p: Dict, h, cfg, remat: bool, wrong: Dict):
    """Latent attention with a direct query and no position on the
    normalised stream h (T, D) -> (T, D)."""
    t = h.shape[0]
    n, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    narrow = _rounder(wrong.get("products_as"))
    h_n = narrow(h)
    q = (h_n @ narrow(p["wq"])).reshape(t, n, dn + dr).transpose(1, 0, 2)
    ckv_kr = h_n @ narrow(p["wkv_a"])
    ckv, kr = ckv_kr[:, :cfg.kv_lora_rank], ckv_kr[None, :, cfg.kv_lora_rank:]
    kv = (narrow(_rms_norm(ckv, p["kv_norm"], cfg.rms_norm_eps))
          @ narrow(p["wkv_b"])).reshape(t, n, dn + dv).transpose(1, 0, 2)
    if wrong.get("rotary"):  # the configuration states none
        inv_freq = yarn_inv_freq(cfg)
        q = jnp.concatenate(
            [q[..., :dn], _rotary(q[..., dn:], inv_freq)], -1)
        kr = _rotary(kr, inv_freq)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(kr, (n, t, dr))], -1)
    a = _attention_core(q, k, kv[..., dn:], score_scale(cfg), remat, wrong)
    return narrow(a.transpose(1, 0, 2).reshape(t, n * dv)) @ narrow(p["wo"])


def block(p: Dict, x, cfg, layout: int, dense: bool, remat: bool,
          wrong: Dict):
    """One block on one sequence's stream x (T, d) -> ``(x', held pairs,
    load)``."""
    eps = cfg.rms_norm_eps
    mixer = delta_mixer if layout == KDA_LAYOUT else attention
    x = x + mixer(p, _rms_norm(x, p["ln_attn"], eps), cfg, remat, wrong)
    m, pairs, load, _ = feed_forward(
        p, _rms_norm(x, p["ln_mlp" if dense else "ln_moe"], eps), cfg,
        dense, wrong)
    return x + m, pairs, load


def _is_dense(cfg, i: int) -> bool:
    return not cfg.moe_experts or i < cfg.first_dense_layers


def _kinds(cfg):
    """``(layout, dense)`` of each layer."""
    return [(int(layout), _is_dense(cfg, i))
            for i, layout in enumerate(cfg.layer_layout)]


def hidden_states(params: Dict, ids, cfg, *, remat: bool = False, **wrong):
    """ids (T,) -> ``(final-normed hidden (T, D), held pairs (layers,
    count), load (layers, E))``."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][ids]
        pairs, loads = [], []
        for i, (layout, dense) in enumerate(_kinds(cfg)):
            layer = lambda p, x, _l=layout, _d=dense: block(
                p, x, cfg, _l, _d, remat, wrong)
            if remat:
                layer = jax.checkpoint(layer)
            x, layer_pairs, load = layer(params[f"block_{i}"], x)
            pairs.append(layer_pairs)
            loads.append(load)
        return (_rms_norm(x, params["ln_final"], cfg.rms_norm_eps),
                jnp.stack(pairs), jnp.stack(loads))


def logits(params: Dict, ids, cfg, **kw):
    """ids (T,) -> (T, V) float32."""
    wrong = {k: v for k, v in kw.items() if k != "remat"}
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(params, ids, cfg, **kw)[0]
        return _head_logits(params, hidden, wrong)


def loss_and_counts(params: Dict, ids, targets, mask, cfg, **kw):
    """Mean next-token cross-entropy over the masked tokens of one
    sequence, and ``(held pairs, load)`` a layer."""
    wrong = {k: v for k, v in kw.items() if k != "remat"}
    with jax.default_matmul_precision("highest"):
        hidden, pairs, load = hidden_states(params, ids, cfg, **kw)
        keep = mask > 0
        return _nll_mean(_head_logits(params, hidden, wrong), targets, keep,
                         jnp.maximum(jnp.sum(keep), 1)), (pairs, load)


def batch_loss(params: Dict, x, y, mask, cfg, **kw):
    """The trainer's step loss on a batch (B, T): the mean over all the
    batch's masked tokens."""
    total = count = 0.0
    for i in range(x.shape[0]):
        n_i = jnp.sum(mask[i] > 0)
        loss_i, _ = loss_and_counts(params, x[i], y[i], mask[i], cfg, **kw)
        total, count = total + loss_i * n_i, count + n_i
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
    same numbers (tests/test_kda_decoder.py); at the published widths no
    more than one block's backward is compiled (one a kind of block) or
    held at a time.  Gradients come back as host arrays."""
    def block_fn(kind):
        return lambda p, h: block(p, h, cfg, *kind, True, wrong)[0]

    def in_highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def head_loss(ln_final, head, h, targets, keep, count):
        hidden = _rms_norm(h, ln_final, cfg.rms_norm_eps)
        return _nll_mean(_head_logits({"head": head}, hidden, wrong),
                         targets, keep, count)

    kinds = _kinds(cfg)
    forward = {kind: in_highest(block_fn(kind)) for kind in set(kinds)}
    backward = {kind: in_highest(
        lambda p, h, ct, _f=block_fn(kind): jax.vjp(_f, p, h)[1](ct))
        for kind in set(kinds)}
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
        for i, kind in enumerate(kinds):
            inputs.append(forward[kind](params[f"block_{i}"], inputs[-1]))
        part, (g_ln, g_head, ct) = head_grad(
            params["ln_final"], params["head"], inputs.pop(), targets,
            jnp.asarray(keep), count)
        one = {"ln_final": np.asarray(g_ln), "head": np.asarray(g_head)}
        for i in reversed(range(len(kinds))):
            g_block, ct = backward[kinds[i]](
                params[f"block_{i}"], inputs.pop(), ct)
            one[f"block_{i}"] = jax.tree.map(np.asarray, g_block)
        one["embed"] = np.asarray(embed_grad(ids, ct))
        total = total + float(part)
        grads = one if grads is None else jax.tree.map(np.add, grads, one)
    return total, {k: grads[k] for k in params}
