"""Plain reference for a latent-attention decoder under a plain residual
whose router carries a per-sequence balance term (Moonlight-16B-A3B's
layer, ``model_type`` ``deepseek_v3``): forward, the training objective
and its gradients in straightforward ``jax.numpy``, float32, every
product under ``jax.default_matmul_precision("highest")``.  No kernel, no
sort, no gather of pairs, no token chunks in the loss: a dense loop over
the held experts with the gate as a mask, attention by an explicit mask
on the 192-wide concatenated queries and keys.  It imports nothing of
``fmda_tpu`` (the pieces it shares with ``reference/latent_decoder.py``
are imported from there: the rounding of the wrong runs, the norm,
rotary, the blockwise core, the gated unit, the head's loss, the first
Adam step and the bias step); it reads the program's parameter tree (names below) and a
record of sizes (``cfg``: the program's ``ModelConfig`` or anything with
the same attributes).

One block on one sequence (``x``: the stream, ``(T, d)``; source: the
catalog's ``config`` for Moonlight-16B-A3B)::

    h  = RMSNorm(x)                                               eps 1e-5
    [qn | qr] = h @ wq                                            16 heads x (128 | 64): q_lora_rank null,
                                                                  no latent and no norm on the query
    [ckv | kr] = h @ wkv_a  (512 | 64) ;  [kn | v] = RMSNorm(ckv) @ wkv_b      16 x (128 | 128)
    qr, kr rotary over 64 dims, theta 50,000, no stretch; kr is ONE head
    s[t, j] = (qn_t . kn_j + qr_t . kr_j) * 192^-1/2 ;  a = causal softmax(s) v ;  x1 = x + a @ wo
    u  = RMSNorm(x1)
    the first ``first_dense_layers`` layers:  x2 = x1 + (silu(u w_gate) * (u w_up)) w_down
    the others:  sc = sigmoid(u @ router) (64) ;  S = top-6 of (sc + router_bias)
                 g_e = 2.446 * sc_e / sum_{e' in S} sc_e'
                 x2 = x1 + SwiGLU_shared(u) + sum_{e in S, e held} g_e SwiGLU_e(u)
    balance term of such a layer, of the SEQUENCE (seq_aux: true), E = 64, K = 6:
                 s'[t, e] = sc[t, e] / sum_e' sc[t, e'] ;  P_e = mean_t s'[t, e]
                 f_e = E / (K T) * #{t : e in S_t}      (S_t as chosen: a count, no gradient)
                 L_bal = alpha * sum_e f_e P_e
    then a final RMSNorm and the head

and the objective a step differentiates: the mean next-token
cross-entropy over the tokens whose mask is 1, plus the mean over the
step's sequences of the sum over the expert layers of ``L_bal``
(:func:`objective`).  A validation loss is the first part alone
(:func:`loss`).  After a train step ``router_bias_e += moe_bias_rate *
sign(mean load - load_e)`` over the step's pairs on all 64 experts
(:func:`bias_step`); the bias has no gradient.

Departures from the published description, each shared with the program
(the configuration's file lists them under ``assumed``):

1. **The share.**  Only the experts ``experts_held = (first, count)``
   are summed; the router keeps its 64 outputs, the gates are normalised
   over the whole top-6 and the balance term is over all 64.  Both
   shared experts (one gated MLP 2 x 1408 wide) are whole.  The
   vocabulary is the held slice.  One dense layer and five of the 26
   expert layers.
2. **Rotary convention**: half-split pairs ``(i, i + 32)`` of the 64
   rotary dims.
3. **alpha** is not a key of the row (it keeps ``seq_aux``, not its
   coefficient): the family's 0.001, ``cfg.moe_seq_aux_alpha``.

Three measures keep 8,192 tokens inside a chip's memory without changing
a number: attention scores one block of :data:`QUERY_BLOCK` query rows
against all keys at a time; ``remat=True`` recomputes each block, and
each query block, in backward; the loop over the held experts is a
``lax.scan``.  The comparison on the chip takes the backward a layer at
a time (:func:`objective_and_grads_by_layer`).

Deliberately wrong runs (``wrong``: keywords of :func:`hidden_states`),
which the comparison that decides ``correct`` must catch: ``balance``
(``"none"``: the term left out; ``"unnormalised"``: ``sc`` in place of
``s'``), ``query_as`` (the query rounded to a
narrower type before the scores), ``softmax_as`` (attention's scores and
probabilities rounded), ``products_as`` (every operand of every product
rounded), ``skip_shared`` (the shared experts left out).

Parameter tree (the program's, float32): ``embed (V, D)``; ``block_<i>``:
``ln_attn (D,)``, ``wq (D, 16*192)``, ``wkv_a (D, 576)``, ``kv_norm
(512,)``, ``wkv_b (512, 16*256)``, ``wo (16*128, D)``; a dense layer:
``ln_mlp``, ``w_gate``/``w_up (D, F)``, ``w_down (F, D)``; an expert
layer: ``ln_moe``, ``router (D, 64)``, ``router_bias (64,)``,
``ws_gate``/``ws_up (D, 2*Fe)``, ``ws_down (2*Fe, D)``, ``w_gate``/``w_up
(count, D, Fe)``, ``w_down (count, Fe, D)``; ``ln_final (D,)``; ``head
(D, V)``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.latent_decoder import (  # noqa: F401  (re-exported)
    QUERY_BLOCK, _attention_core, _gated, _head_logits, _nll_mean, _rms_norm,
    _rotary, _rounder, bias_step, first_adam_step, score_scale,
    yarn_inv_freq)


def attention(p: Dict, h, cfg, remat: bool, wrong: Dict):
    """Latent attention with a direct query on the normalised stream h
    (T, D) -> (T, D)."""
    t = h.shape[0]
    n, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    narrow = _rounder(wrong.get("products_as"))
    h_n = narrow(h)
    q = (h_n @ narrow(p["wq"])).reshape(t, n, dn + dr).transpose(1, 0, 2)
    ckv_kr = h_n @ narrow(p["wkv_a"])
    ckv, kr = ckv_kr[:, :cfg.kv_lora_rank], ckv_kr[:, cfg.kv_lora_rank:]
    kv = (narrow(_rms_norm(ckv, p["kv_norm"], cfg.rms_norm_eps))
          @ narrow(p["wkv_b"])).reshape(t, n, dn + dv).transpose(1, 0, 2)
    # plain frequencies theta^(-2i/dr) and a scale of (dn + dr)^-1/2: the
    # row states no rope_scaling (rope_factor 1)
    inv_freq = yarn_inv_freq(cfg)
    q = _rounder(wrong.get("query_as"))(jnp.concatenate(
        [q[..., :dn], _rotary(q[..., dn:], inv_freq)], -1))
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(_rotary(kr[None], inv_freq), (n, t, dr))], -1)
    a = _attention_core(q, k, kv[..., dn:], score_scale(cfg), remat, wrong)
    return narrow(a.transpose(1, 0, 2).reshape(t, n * dv)) @ narrow(p["wo"])


def balance_term(scores, chosen, cfg, wrong: Dict):
    """``L_bal`` of the tokens whose router scores are ``scores`` (T, E)
    and whose chosen experts ``chosen`` (T, K): one sequence's."""
    alpha = getattr(cfg, "moe_seq_aux_alpha", 0.0)
    if not alpha or wrong.get("balance") == "none":
        return jnp.zeros((), jnp.float32)
    t, n_experts = scores.shape
    often = jnp.sum(
        chosen.reshape(-1)[:, None] == jnp.arange(n_experts)[None, :],
        axis=0).astype(jnp.float32) * n_experts / (cfg.moe_top_k * t)
    share = scores if wrong.get("balance") == "unnormalised" else (
        scores / jnp.sum(scores, axis=-1, keepdims=True))
    return alpha * jnp.sum(often * jnp.mean(share, axis=0))


def feed_forward(p: Dict, u, cfg, dense: bool, wrong: Dict):
    """``(output (T, D), pairs on each held expert (count,), pairs on
    each of all experts (E,), the balance term ())`` on the normalised
    stream u (T, D) of one sequence."""
    narrow = _rounder(wrong.get("products_as"))
    u_n = narrow(u)
    first, count = cfg.experts_held
    if dense:
        return (_gated(u_n, p["w_gate"], p["w_up"], p["w_down"], narrow),
                jnp.zeros((count,), jnp.int32),
                jnp.zeros((cfg.moe_experts,), jnp.int32),
                jnp.zeros((), jnp.float32))
    scores = jax.nn.sigmoid(u_n @ narrow(p["router"]))
    chosen_on = scores + jax.lax.stop_gradient(p["router_bias"]) \
        if "router_bias" in p else scores
    _, chosen = jax.lax.top_k(chosen_on, cfg.moe_top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = cfg.moe_routed_scaling * top / jnp.sum(
        top, axis=-1, keepdims=True)

    def add_expert(m, held):
        """The next held expert, densely, the gate a mask."""
        e, w_gate, w_up, w_down = held
        on_e = chosen == first + e                      # (T, k)
        gate_e = jnp.sum(jnp.where(on_e, gates, 0.0), axis=-1)
        y = _gated(u_n, w_gate, w_up, w_down, narrow)
        return m + gate_e[:, None] * y, jnp.sum(on_e, dtype=jnp.int32)

    m = jnp.zeros_like(u)
    if cfg.moe_shared_experts and not wrong.get("skip_shared"):
        m = _gated(u_n, p["ws_gate"], p["ws_up"], p["ws_down"], narrow)
    m, pairs = jax.lax.scan(
        add_expert, m,
        (jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]))
    load = jnp.sum(
        chosen.reshape(-1)[:, None] == jnp.arange(cfg.moe_experts)[None, :],
        axis=0, dtype=jnp.int32)
    return m, pairs, load, balance_term(scores, chosen, cfg, wrong)


def block(p: Dict, x, cfg, dense: bool, remat: bool, wrong: Dict):
    """One block on one sequence's stream x (T, d) -> ``(x', held pairs,
    load, the layer's balance term)``."""
    eps = cfg.rms_norm_eps
    x = x + attention(p, _rms_norm(x, p["ln_attn"], eps), cfg, remat, wrong)
    m, pairs, load, term = feed_forward(
        p, _rms_norm(x, p["ln_mlp" if dense else "ln_moe"], eps), cfg,
        dense, wrong)
    return x + m, pairs, load, term


def _is_dense(cfg, i: int) -> bool:
    return not cfg.moe_experts or i < cfg.first_dense_layers


def hidden_states(params: Dict, ids, cfg, *, remat: bool = False, **wrong):
    """ids (T,) -> ``(final-normed hidden (T, D), held pairs (layers,
    count), load (layers, E), balance terms (layers,))``."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][ids]
        pairs, loads, terms = [], [], []
        for i in range(len(cfg.layer_layout)):
            layer = lambda p, x, _dense=_is_dense(cfg, i): block(
                p, x, cfg, _dense, remat, wrong)
            if remat:
                layer = jax.checkpoint(layer)
            x, layer_pairs, load, term = layer(params[f"block_{i}"], x)
            pairs.append(layer_pairs)
            loads.append(load)
            terms.append(term)
        return (_rms_norm(x, params["ln_final"], cfg.rms_norm_eps),
                jnp.stack(pairs), jnp.stack(loads), jnp.stack(terms))


def logits(params: Dict, ids, cfg, **kw):
    """ids (T,) -> (T, V) float32."""
    wrong = {k: v for k, v in kw.items() if k != "remat"}
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(params, ids, cfg, **kw)[0]
        return _head_logits(params, hidden, wrong)


def loss_and_counts(params: Dict, ids, targets, mask, cfg, **kw):
    """Mean next-token cross-entropy over the masked tokens of one
    sequence (what a validation pass reports: no balance term in it), and
    ``(held pairs, load, balance terms)`` a layer."""
    wrong = {k: v for k, v in kw.items() if k != "remat"}
    with jax.default_matmul_precision("highest"):
        hidden, pairs, load, terms = hidden_states(params, ids, cfg, **kw)
        keep = mask > 0
        return _nll_mean(_head_logits(params, hidden, wrong), targets, keep,
                         jnp.maximum(jnp.sum(keep), 1)), (pairs, load, terms)


def loss(params: Dict, ids, targets, mask, cfg, **kw):
    return loss_and_counts(params, ids, targets, mask, cfg, **kw)[0]


def objective(params: Dict, x, y, mask, cfg, **kw):
    """What a train step differentiates on a batch (B, T): the mean
    next-token loss over all the batch's masked tokens plus the mean over
    its sequences (those with a masked token) of the sum over the layers
    of the balance term.  Returns ``(objective, (the next-token loss
    alone, the terms a layer (layers,): means over the sequences))``."""
    total = count = 0.0
    terms, seqs = 0.0, 0
    for i in range(x.shape[0]):
        n_i = jnp.sum(mask[i] > 0)
        loss_i, (_, _, terms_i) = loss_and_counts(
            params, x[i], y[i], mask[i], cfg, **kw)
        total, count = total + loss_i * n_i, count + n_i
        terms, seqs = terms + terms_i * (n_i > 0), seqs + (n_i > 0)
    token_loss = total / jnp.maximum(count, 1)
    terms = terms / jnp.maximum(seqs, 1)
    return token_loss + jnp.sum(terms), (token_loss, terms)


def objective_and_grads(params: Dict, x, y, mask, cfg, *, remat: bool = True,
                        **kw):
    """``((objective, (next-token loss, terms)), gradients)`` of
    :func:`objective`, float32."""
    # the whole value_and_grad inside the precision context: the backward
    # is traced after the forward returns
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: objective(p, x, y, mask, cfg, remat=remat, **kw),
            has_aux=True)(params)


def objective_and_grads_by_layer(params: Dict, x, y, mask, cfg, **wrong):
    """:func:`objective_and_grads` again, the backward written out a
    block at a time: forward keeping each block's input, the head's
    gradient, then each block's vector-Jacobian product from the last to
    the first (the stream's cotangent, and the block's own balance term
    at the weight the objective gives it), the embedding's rows last; a
    batch's sequences one after the other.  The same numbers
    (tests/test_mla_decoder.py); at the published widths no more than one
    block's backward is compiled (one a kind of block) or held at a time.
    Gradients come back as host arrays.  Returns ``(objective,
    next-token loss, terms (layers,), gradients)``."""
    def block_fn(dense):
        def fn(p, h):
            out = block(p, h, cfg, dense, True, wrong)
            return out[0], out[3]
        return fn

    def in_highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def head_loss(ln_final, head, h, targets, keep, count):
        hidden = _rms_norm(h, ln_final, cfg.rms_norm_eps)
        return _nll_mean(_head_logits({"head": head}, hidden, wrong),
                         targets, keep, count)

    depth = len(cfg.layer_layout)
    kinds = sorted({_is_dense(cfg, i) for i in range(depth)})
    forward = {v: in_highest(block_fn(v)) for v in kinds}
    backward = {v: in_highest(
        lambda p, h, ct, term_ct, _f=block_fn(v):
        jax.vjp(_f, p, h)[1]((ct, term_ct)))
        for v in kinds}
    head_grad = in_highest(jax.value_and_grad(head_loss, argnums=(0, 1, 2)))
    rows = params["embed"].shape
    embed_grad = jax.jit(
        lambda ids, ct: jnp.zeros(rows, jnp.float32).at[ids].add(ct))

    keeps = np.asarray(mask) > 0
    count = jnp.maximum(int(keeps.sum()), 1)
    seqs = max(int(keeps.any(axis=1).sum()), 1)
    token_loss, terms, grads = 0.0, np.zeros((depth,)), None
    for ids, targets, keep in zip(x, y, keeps):
        if not keep.any():  # a padded sequence adds nothing
            continue
        inputs, seq_terms = [params["embed"][ids]], []
        for i in range(depth):
            out, term = forward[_is_dense(cfg, i)](
                params[f"block_{i}"], inputs[-1])
            inputs.append(out)
            seq_terms.append(float(term))
        part, (g_ln, g_head, ct) = head_grad(
            params["ln_final"], params["head"], inputs.pop(), targets,
            jnp.asarray(keep), count)
        one = {"ln_final": np.asarray(g_ln), "head": np.asarray(g_head)}
        for i in reversed(range(depth)):
            g_block, ct = backward[_is_dense(cfg, i)](
                params[f"block_{i}"], inputs.pop(), ct,
                jnp.float32(1.0 / seqs))
            one[f"block_{i}"] = jax.tree.map(np.asarray, g_block)
        one["embed"] = np.asarray(embed_grad(ids, ct))
        token_loss = token_loss + float(part)
        terms = terms + np.asarray(seq_terms) / seqs
        grads = one if grads is None else jax.tree.map(np.add, grads, one)
    return (token_loss + float(terms.sum()), token_loss, terms,
            {k: grads[k] for k in params})
