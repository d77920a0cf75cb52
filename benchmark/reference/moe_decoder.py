"""Plain reference for the ``decoder`` family: forward, loss and gradients
of SmallThinker-21BA3B-Instruct's layer in straightforward ``jax.numpy``,
float32, every product under ``jax.default_matmul_precision("highest")``.
No kernel, no sort, no gather of pairs, no cache, no token chunks in the
loss: a dense loop over the held experts with the gate as a mask, and
attention by an explicit mask.  It imports nothing of ``fmda_tpu``; it
reads the program's parameter tree (names below) and a record of sizes.

One layer (x: residual stream ``(T, hidden)``; ``layout`` = the layer's
entry of ``layer_layout``; source: the catalog's ``config`` and
``described_as`` for SmallThinker-21BA3B-Instruct)::

    h  = RMSNorm(x; eps)
    p  = softmax(h @ router)                         over all 64 experts
    S  = top-6 of p ;  g_e = p_e / sum_{e' in S} p_e'     (norm_topk_prob)
    q, k, v = h @ wq, h @ wk, h @ wv                 28 query heads on 4 kv heads of 128
    layout 1: rotary on q, k over all 128 dims (theta 1.5e6); key j visible iff 0 <= i-j < 4096
    layout 0: no positional encoding; key j visible iff j <= i
    a  = softmax(q k^T / sqrt(128) + mask) v ;   x1 = x + a @ wo
    u  = RMSNorm(x1; eps)
    m  = sum_{e in S, e held} g_e * (relu(u @ w_gate[e]) * (u @ w_up[e])) @ w_down[e]
    x2 = x1 + m

then a final RMSNorm, the untied ``head`` and next-token cross-entropy
(mean over the tokens whose mask is 1).

Departures from the published description, each shared with the program:

1. **The share.**  Only the experts ``experts_held = (first, count)`` are
   summed (16 of 64 here); the router still has 64 outputs and the gates
   are normalised over the whole top-6.  The vocabulary is the held slice
   (37,984 rows): a smaller vocabulary.  4 of the 52 layers.
2. **The router reads h** (the attention block's normalised input): it is
   placed before attention; whether it reads ``h`` or ``x`` is not in the
   catalog's keys.
3. **No secondary experts** (``config`` has 64 primary, 6 active, 0
   shared; ``described_as`` mentions secondary ones; ``config`` wins).
4. **Rotary convention**: half-split pairs ``(i, i + 64)``.

Three measures keep 8,192 tokens inside a chip's memory, and the compile
inside a run's time, without changing a number: attention scores one
block of :data:`QUERY_BLOCK` query rows against all keys at a time (an
explicit mask per block, softmax over the whole row at once);
``remat=True`` recomputes each layer, and each query block, in backward;
and the loop over the held experts is a ``lax.scan`` in order (one
expert's products compiled, not sixteen copies).  The comparison on the
chip takes the backward a layer at a time
(:func:`loss_and_grads_by_layer`), so that it stays under the memory the
training itself peaks at.

Parameter tree (the program's, float32): ``embed (V, D)``; ``block_<i>``:
``ln_attn (D,)``, ``router (D, 64)``, ``wq (D, 28*128)``, ``wk``/``wv
(D, 4*128)``, ``wo (28*128, D)``, ``ln_moe (D,)``, ``w_gate``/``w_up
(count, D, F)``, ``w_down (count, F, D)``; ``ln_final (D,)``; ``head
(D, V)``.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

#: Query rows scored against all keys at a time.
QUERY_BLOCK = 512


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rotary(x, theta):
    """x (heads, T, d): dims i and i + d/2 rotate by pos * theta^(-2i/d)."""
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _narrower(dtype: Optional[str]):
    """Round a product's operand to a narrower type and back (the sums
    stay float32); the identity without one.  Only the value is rounded:
    a backward pass multiplies the rounded operands by cotangents that
    pass through as they are (a float8 cotangent would underflow to 0)."""
    if dtype is None:
        return lambda a: a
    return lambda a: a + jax.lax.stop_gradient(
        a.astype(dtype).astype(jnp.float32) - a)


def _attention(q, k, v, window: Optional[int], remat: bool,
               narrow=lambda a: a):
    """q (N, T, d), k/v (G, T, d) -> (N, T, d): softmax over an explicit
    causal (and windowed) mask, a block of query rows at a time."""
    n, t, d = q.shape
    group = n // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    k, v = narrow(k), narrow(v)
    key_pos = jnp.arange(t)

    def block(q_blk, pos):
        s = jnp.einsum("nqd,nkd->nqk", narrow(q_blk), k) / jnp.sqrt(
            jnp.float32(d))
        rel = pos[:, None] - key_pos[None, :]
        visible = rel >= 0
        if window is not None:
            visible = visible & (rel < window)
        s = jnp.where(visible[None], s, -jnp.inf)
        return jnp.einsum(
            "nqk,nkd->nqd", narrow(jax.nn.softmax(s, axis=-1)), v)

    blk = QUERY_BLOCK
    if t <= blk or t % blk:
        return block(q, key_pos)
    if remat:
        block = jax.checkpoint(block)
    out = jax.lax.map(
        lambda xs: block(*xs),
        (q.reshape(n, t // blk, blk, d).transpose(1, 0, 2, 3),
         key_pos.reshape(t // blk, blk)))
    return out.transpose(1, 0, 2, 3).reshape(n, t, d)


def _layer(p: Dict, x, layout: int, cfg, skip_expert: Optional[int],
           remat: bool, products_as: Optional[str] = None):
    """One layer on one sequence x (T, D) -> (x2, pairs per held expert)."""
    n, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    first, count = cfg.experts_held
    t = x.shape[0]
    # the deliberately wrong run in a narrower precision: every operand
    # of every product rounded to `products_as` (and back)
    narrow = _narrower(products_as)
    h = _rms_norm(x, p["ln_attn"], cfg.rms_norm_eps)
    h_n = narrow(h)
    probs = jax.nn.softmax(h_n @ narrow(p["router"]), axis=-1)
    top, chosen = jax.lax.top_k(probs, cfg.moe_top_k)
    gates = top / jnp.sum(top, axis=-1, keepdims=True)

    def heads(w, n_heads):
        return (h_n @ narrow(w)).reshape(t, n_heads, hd).transpose(1, 0, 2)

    q, k, v = heads(p["wq"], n), heads(p["wk"], g), heads(p["wv"], g)
    if layout:
        q, k = _rotary(q, cfg.rope_theta), _rotary(k, cfg.rope_theta)
    a = _attention(q, k, v, cfg.sliding_window if layout else None, remat,
                   narrow)
    x1 = x + narrow(a.transpose(1, 0, 2).reshape(t, n * hd)) @ narrow(p["wo"])

    u = _rms_norm(x1, p["ln_moe"], cfg.rms_norm_eps)
    u_e = narrow(u)

    def add_expert(m, held):
        """The next held expert, densely, the gate a mask."""
        e, w_gate, w_up, w_down = held
        on_e = chosen == first + e                      # (T, k)
        gate_e = jnp.sum(jnp.where(on_e, gates, 0.0), axis=-1)
        if skip_expert is not None:
            gate_e = jnp.where(e == skip_expert, 0.0, gate_e)
        y = narrow(jax.nn.relu(u_e @ narrow(w_gate))
                   * (u_e @ narrow(w_up))) @ narrow(w_down)
        return m + gate_e[:, None] * y, jnp.sum(on_e, dtype=jnp.int32)

    # a loop over the held experts in order (a scan, so that the compiler
    # sees one expert's products and not `count` copies of them)
    m, pairs = jax.lax.scan(
        add_expert, jnp.zeros_like(x1),
        (jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]))
    return x1 + m, pairs


def hidden_states(params: Dict, ids, cfg, *, skip_expert: Optional[int]
                  = None, products_as: Optional[str] = None,
                  remat: bool = False):
    """ids (T,) -> (final-normed hidden (T, D), pairs (layers, count)).
    ``skip_expert`` leaves one held expert out of every layer, and
    ``products_as`` (a dtype name, e.g. ``"float8_e4m3fn"``) rounds every
    operand of every product to a narrower type: the two deliberately
    wrong runs the comparison that decides ``correct`` must catch."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][ids]
        pairs = []
        for i, layout in enumerate(cfg.layer_layout):
            layer = lambda p, x, _layout=int(layout): _layer(
                p, x, _layout, cfg, skip_expert, remat, products_as)
            if remat:
                layer = jax.checkpoint(layer)
            x, layer_pairs = layer(params[f"block_{i}"], x)
            pairs.append(layer_pairs)
        return (_rms_norm(x, params["ln_final"], cfg.rms_norm_eps),
                jnp.stack(pairs))


def _head_logits(params: Dict, hidden, products_as: Optional[str] = None,
                 **_):
    narrow = _narrower(products_as)
    return narrow(hidden) @ narrow(params["head"])


def logits(params: Dict, ids, cfg, **kw):
    """ids (T,) -> (T, V) float32."""
    with jax.default_matmul_precision("highest"):
        hidden, _ = hidden_states(params, ids, cfg, **kw)
        return _head_logits(params, hidden, **kw)


def loss_and_pairs(params: Dict, ids, targets, mask, cfg, **kw
                   ) -> Tuple[jax.Array, jax.Array]:
    """Mean next-token cross-entropy over the masked tokens of one
    sequence, and the pairs each held expert of each layer received."""
    with jax.default_matmul_precision("highest"):
        hidden, pairs = hidden_states(params, ids, cfg, **kw)
        lg = _head_logits(params, hidden, **kw)
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, targets[:, None], axis=-1)[:, 0]
        keep = mask > 0
        return (jnp.sum(jnp.where(keep, nll, 0.0))
                / jnp.maximum(jnp.sum(keep), 1), pairs)


def loss(params: Dict, ids, targets, mask, cfg, **kw):
    return loss_and_pairs(params, ids, targets, mask, cfg, **kw)[0]


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
                   **kw):
    """``(loss, gradients)`` of :func:`batch_loss`, float32 (``kw``: the
    deliberately wrong runs of :func:`hidden_states`)."""
    # the whole value_and_grad inside the precision context: the backward
    # is traced after the forward returns
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: batch_loss(p, x, y, mask, cfg, remat=remat, **kw)
        )(params)


def loss_and_grads_by_layer(params: Dict, x, y, mask, cfg, **kw):
    """:func:`loss_and_grads` again, the backward written out a layer at
    a time: forward keeping each layer's input, the head's gradient,
    then each layer's vector-Jacobian product from the last to the
    first, the embedding's rows last; a batch's sequences one after the
    other.  The same numbers (tests/test_decoder_reference.py holds them to
    :func:`loss_and_grads`); at the published widths no more than one
    layer's backward is compiled (one a layout) or held at a time, so
    the comparison on the chip stays under the memory the training
    itself peaks at.  Gradients come back as host arrays."""
    import numpy as np

    def layer_fn(layout):
        return lambda p, h: _layer(
            p, h, layout, cfg, kw.get("skip_expert"), True,
            kw.get("products_as"))[0]

    def in_highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def head_loss(ln_final, head, h, targets, keep, count):
        hidden = _rms_norm(h, ln_final, cfg.rms_norm_eps)
        lg = _head_logits({"head": head}, hidden, **kw)
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, targets[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(keep, nll, 0.0)) / count

    layouts = sorted(set(int(v) for v in cfg.layer_layout))
    forward = {v: in_highest(layer_fn(v)) for v in layouts}
    backward = {v: in_highest(
        lambda p, h, ct, _f=layer_fn(v): jax.vjp(_f, p, h)[1](ct))
        for v in layouts}
    head_grad = in_highest(jax.value_and_grad(head_loss, argnums=(0, 1, 2)))
    rows = params["embed"].shape
    embed_grad = jax.jit(
        lambda ids, ct: jnp.zeros(rows, jnp.float32).at[ids].add(ct))

    count = jnp.maximum(jnp.sum(mask > 0), 1)
    total, grads = 0.0, None
    for ids, targets, keep in zip(x, y, mask > 0):
        inputs = [params["embed"][ids]]
        for i, layout in enumerate(cfg.layer_layout):
            inputs.append(forward[int(layout)](
                params[f"block_{i}"], inputs[-1]))
        part, (g_ln, g_head, ct) = head_grad(
            params["ln_final"], params["head"], inputs.pop(), targets,
            keep, count)
        one = {"ln_final": np.asarray(g_ln), "head": np.asarray(g_head)}
        for i in reversed(range(len(cfg.layer_layout))):
            g_block, ct = backward[int(cfg.layer_layout[i])](
                params[f"block_{i}"], inputs.pop(), ct)
            one[f"block_{i}"] = jax.tree.map(np.asarray, g_block)
        one["embed"] = np.asarray(embed_grad(ids, ct))
        total = total + float(part)
        grads = one if grads is None else jax.tree.map(np.add, grads, one)
    return total, {k: grads[k] for k in params}


def first_adam_step(grads: Dict, *, learning_rate: float, clip: float,
                    eps: float = 1e-8) -> Tuple[Dict, Dict]:
    """What the trainer's optimizer makes of the first step's gradients:
    ``(the gradients clipped to a global norm of clip, the parameters'
    change)``.  Adam's moments start at zero, so after one step the
    bias-corrected first moment is the clipped gradient ``g``, the second
    ``g * g``, and the change ``-learning_rate * g / (|g| + eps)``."""
    norm = sum(float((g * g).sum()) for g in jax.tree.leaves(grads)) ** 0.5
    scale = min(1.0, clip / norm)
    clipped = jax.tree.map(lambda g: g * scale, grads)
    return clipped, jax.tree.map(
        lambda g: -learning_rate * g / (abs(g) + eps), clipped)
