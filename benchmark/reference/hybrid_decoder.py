"""Plain reference for a hybrid ``decoder``: state-space layers and
attention layers in one stack, a dense gated MLP in every layer, a tied
head and four stated multipliers: granite-4.0-h-micro's layer in
straightforward ``jax.numpy``, float32, every product under
``jax.default_matmul_precision("highest")``.  No kernel, no chunked form
of the scan, no cache, no token chunks in the loss.  It imports nothing
of ``fmda_tpu``; it reads the program's parameter tree (names below) and
a record of sizes.

The equations (x: residual stream ``(T, hidden)``; source: the catalog's
``config`` for granite-4.0-h-micro, ``model_type`` granitemoehybrid)::

    x0 = embed[ids] * embedding_multiplier                        12
    layer:  h = RMSNorm(x) ;  x1 = x + residual_multiplier * mixer(h)     0.22
            u = RMSNorm(x1);  x2 = x1 + residual_multiplier * (silu(u Wg) * (u Wu)) Wd
    out:    logits = RMSNorm(x_L) embed^T / logits_scaling        tied, 8

    mixer, layout 0 ("attention"):  q, k, v = h Wq, h Wk, h Wv    32 heads on 8 kv heads of 64
            a = softmax(q k^T * attention_multiplier + causal) v ;  mixer = a Wo
            no positions, no bias; the multiplier (1/64) in place of 1/sqrt(64)

    mixer, layout 3 ("mamba"; H heads of P on a state of N, one B/C group, I = H P):
            [z | xBC | dt] = h W_in                               I | I + 2N | H
            xBC = silu(conv_b + sum_{j<K} conv_w[:, j] * xBC[t-(K-1)+j])   zero history before t = 0
            [xs | B | C] = xBC
            d_t = softplus(dt_t + dt_bias) ;  A = -exp(A_log)
            S_t = exp(d_t A) S_{t-1} + d_t * xs_t (x) B_t         S_{-1} = 0
            y_t = S_t C_t + D * xs_t
            mixer = RMSNorm_I(y * silu(z)) W_out                  gate first, one norm over all I

**The recurrence is computed as written, position by position**
(:func:`_recurrence`: a ``lax.scan`` over t, the state a float32
``(H, P, N)`` array, its update and its read elementwise products and
sums, no matrix product): not the chunked form the program runs.  The
convolution is K shifted sums; attention is a masked softmax a block of
query rows at a time.

Departures from the published description, each shared with the program:
the vocabulary is the held slice (a smaller vocabulary) and the stack is
one period of the layer pattern; documents cross joins with neither the
state nor the convolution reset.

Two measures keep 8,192 tokens inside a chip's memory without changing a
number: the scan's backward replays :data:`SEGMENT` positions at a time
(``jax.checkpoint`` on a segment: 128 states of 2 MB and not 8,192), and
``remat=True`` recomputes each layer, and each query block, in backward.
The comparison on the chip takes the backward a layer at a time
(:func:`loss_and_grads_by_layer`).

Deliberately wrong runs, each of which the comparison that decides
``correct`` has to catch (``wrong`` keywords of :func:`hidden_states`):
``products_as`` rounds every product's operands (and what enters the
recurrence) to a narrower type; ``state_as`` carries the state in a
narrower type; ``drop_state_every`` drops the carried state every that
many positions (a chunked scan that forgets its carry); ``conv_ahead``
makes the convolution read that many positions ahead; ``leave_out``
names one of ``d_skip``, ``gate``, ``embedding_multiplier``,
``residual_multiplier``, ``attention_multiplier``, ``logits_scaling``.

Parameter tree (the program's, float32): ``embed (V, D)``; ``block_<i>``:
``ln_attn (D,)``, then ``wq (D, 32*64)``, ``wk``/``wv (D, 8*64)``, ``wo
(32*64, D)`` or ``w_in (D, 2I + 2N + H)``, ``conv_w (I + 2N, K)``,
``conv_b (I + 2N,)``, ``dt_bias``/``a_log``/``d_skip (H,)``, ``ln_gate
(I,)``, ``w_out (I, D)``; ``ln_mlp (D,)``, ``w_gate``/``w_up (D, F)``,
``w_down (F, D)``; ``ln_final (D,)``.  No ``head``: it is ``embed``.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from benchmark.reference.moe_decoder import (  # noqa: F401  (re-exported)
    _narrower, _rms_norm, first_adam_step)

#: Query rows scored against all keys at a time.
QUERY_BLOCK = 512
#: Positions of the scan replayed at a time in backward.
SEGMENT = 128
#: ``layer_layout``'s value for a state-space layer.
SSM_LAYOUT = 3

_ACT = {"silu": jax.nn.silu, "relu": jax.nn.relu}
LEAVE_OUT = ("d_skip", "gate", "embedding_multiplier", "residual_multiplier",
             "attention_multiplier", "logits_scaling")


def _attention(q, k, v, scale: float, remat: bool, narrow=lambda a: a):
    """q (N, T, d), k/v (G, T, d) -> (N, T, d): softmax of ``q k^T *
    scale`` under the causal mask, a block of query rows at a time."""
    n, t, d = q.shape
    group = n // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    k, v = narrow(k), narrow(v)
    key_pos = jnp.arange(t)

    def block(q_blk, pos):
        s = jnp.einsum("nqd,nkd->nqk", narrow(q_blk), k) * scale
        s = jnp.where((pos[:, None] >= key_pos[None, :])[None], s, -jnp.inf)
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


def _conv(x, w, bias, ahead: int = 0):
    """x (T, C), w (C, K), bias (C,): ``out[t] = bias + sum_j w[:, j] *
    x[t - (K - 1) + j]`` as K shifted sums, zeros outside the sequence.
    ``ahead`` > 0 reads that many positions later (a wrong run)."""
    t, k = x.shape[0], w.shape[1]
    out = jnp.broadcast_to(bias, x.shape)
    for j in range(k):
        back = k - 1 - j - ahead
        zeros = jnp.zeros((abs(back), x.shape[1]), x.dtype)
        shifted = (jnp.concatenate([zeros, x[:t - back]]) if back >= 0
                   else jnp.concatenate([x[-back:], zeros]))
        out = out + w[:, j] * shifted
    return out


def _rounded_to(dtype: Optional[str]):
    """Round a float32 value to ``dtype``'s exponent and mantissa bits,
    the gradient passing through; the identity without one.  An explicit
    ``reduce_precision``: a cast to bfloat16 and back is a pair of
    converts that the TPU compiler is free to drop (it allows excess
    precision), and on the chip the wrong run then equals the right one
    to the last digit (PERF.md section 6, PR 34)."""
    if dtype is None:
        return lambda a: a
    info = jnp.finfo(dtype)
    return lambda a: a + jax.lax.stop_gradient(
        jax.lax.reduce_precision(a, info.nexp, info.nmant) - a)


def _recurrence(xs, d, a, b, c, *, remat: bool,
                state_as: Optional[str] = None,
                drop_state_every: Optional[int] = None):
    """``S_t = exp(d_t a) S_{t-1} + d_t xs_t (x) b_t ; y_t = S_t c_t``
    position by position: xs (T, H, P), d (T, H), a (H,), b / c (T, N)
    -> y (T, H, P), the state float32 (H, P, N)."""
    t, h, p = xs.shape
    round_state = _rounded_to(state_as)
    keep = jnp.ones((t,), jnp.float32)
    if drop_state_every:
        keep = jnp.where(jnp.arange(t) % drop_state_every == 0, 0.0, keep)

    def step(state, at):
        x_t, d_t, b_t, c_t, keep_t = at
        state = round_state(
            jnp.exp(d_t * a)[:, None, None] * (state * keep_t)
            + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, jnp.sum(state * c_t[None, None, :], axis=-1)

    def run(state, segment):
        return jax.lax.scan(step, state, segment)

    state = jnp.zeros((h, p, b.shape[-1]), jnp.float32)
    at = (xs, d, b, c, keep)
    if t <= SEGMENT or t % SEGMENT:
        return run(state, at)[1]
    if remat:
        run = jax.checkpoint(run)
    _, y = jax.lax.scan(run, state, jax.tree.map(
        lambda v: v.reshape((t // SEGMENT, SEGMENT) + v.shape[1:]), at))
    return y.reshape(t, h, p)


def _state_space_mixer(p: Dict, h, cfg, remat: bool, narrow, wrong: Dict):
    heads, width, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    inner, t = heads * width, h.shape[0]
    leave_out = wrong.get("leave_out")
    z, xbc, dt = jnp.split(narrow(h) @ narrow(p["w_in"]),
                           [inner, 2 * inner + 2 * n], axis=-1)
    xbc = jax.nn.silu(_conv(xbc, p["conv_w"], p["conv_b"],
                            wrong.get("conv_ahead", 0)))
    xs, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
    xs = xs.reshape(t, heads, width)
    y = _recurrence(
        narrow(xs), jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["a_log"]),
        narrow(b), narrow(c), remat=remat, state_as=wrong.get("state_as"),
        drop_state_every=wrong.get("drop_state_every"))
    if leave_out != "d_skip":
        y = y + p["d_skip"][:, None] * xs
    y = y.reshape(t, inner)
    if leave_out != "gate":
        y = y * jax.nn.silu(z)
    return narrow(_rms_norm(y, p["ln_gate"], cfg.rms_norm_eps)) @ narrow(
        p["w_out"])


def _attention_mixer(p: Dict, h, cfg, remat: bool, narrow, wrong: Dict):
    n, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    t = h.shape[0]
    scale = cfg.attention_multiplier
    if scale is None or wrong.get("leave_out") == "attention_multiplier":
        scale = hd ** -0.5
    h_n = narrow(h)

    def heads(w, n_heads):
        return (h_n @ narrow(w)).reshape(t, n_heads, hd).transpose(1, 0, 2)

    a = _attention(heads(p["wq"], n), heads(p["wk"], g), heads(p["wv"], g),
                   scale, remat, narrow)
    return narrow(a.transpose(1, 0, 2).reshape(t, n * hd)) @ narrow(p["wo"])


def _layer(p: Dict, x, layout: int, cfg, remat: bool, wrong: Dict):
    """One layer on one sequence x (T, D) -> x2."""
    narrow = _narrower(wrong.get("products_as"))
    r = (1.0 if wrong.get("leave_out") == "residual_multiplier"
         else cfg.residual_multiplier)
    mixer = (_state_space_mixer if layout == SSM_LAYOUT
             else _attention_mixer)
    h = _rms_norm(x, p["ln_attn"], cfg.rms_norm_eps)
    x1 = x + r * mixer(p, h, cfg, remat, narrow, wrong)
    u = narrow(_rms_norm(x1, p["ln_mlp"], cfg.rms_norm_eps))
    m = narrow(_ACT[cfg.hidden_act](u @ narrow(p["w_gate"]))
               * (u @ narrow(p["w_up"]))) @ narrow(p["w_down"])
    return x1 + r * m


def _embedded(params: Dict, ids, cfg, wrong: Dict):
    m = (1.0 if wrong.get("leave_out") == "embedding_multiplier"
         else cfg.embedding_multiplier)
    return params["embed"][ids] * m


def hidden_states(params: Dict, ids, cfg, *, remat: bool = False, **wrong):
    """ids (T,) -> the final norm's output (T, D).  ``wrong``: the
    deliberately wrong runs of the module docstring."""
    with jax.default_matmul_precision("highest"):
        x = _embedded(params, ids, cfg, wrong)
        for i, layout in enumerate(cfg.layer_layout):
            layer = lambda p, x, _layout=int(layout): _layer(
                p, x, _layout, cfg, remat, wrong)
            if remat:
                layer = jax.checkpoint(layer)
            x = layer(params[f"block_{i}"], x)
        return _rms_norm(x, params["ln_final"], cfg.rms_norm_eps)


def _head_logits(embed, hidden, cfg, wrong: Dict):
    narrow = _narrower(wrong.get("products_as"))
    s = (1.0 if wrong.get("leave_out") == "logits_scaling"
         else cfg.logits_scaling)
    return narrow(hidden) @ narrow(embed).T / s


def _nll(lg, targets, keep, count):
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(keep, nll, 0.0)) / count


def logits(params: Dict, ids, cfg, **kw):
    """ids (T,) -> (T, V) float32."""
    wrong = {k: v for k, v in kw.items() if k != "remat"}
    with jax.default_matmul_precision("highest"):
        return _head_logits(params["embed"],
                            hidden_states(params, ids, cfg, **kw), cfg, wrong)


def loss(params: Dict, ids, targets, mask, cfg, **kw):
    """Mean next-token cross-entropy over the masked tokens of one
    sequence."""
    with jax.default_matmul_precision("highest"):
        keep = mask > 0
        return _nll(logits(params, ids, cfg, **kw), targets, keep,
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
    """:func:`loss_and_grads` again, the backward written out a layer at
    a time: forward keeping each layer's input, the head's gradient, then
    each layer's vector-Jacobian product from the last to the first, the
    embedding's rows last (its gradient is the sum of the head's use and
    the rows' use); a batch's sequences one after the other.  The same
    numbers (tests/test_hybrid_decoder.py holds them to
    :func:`loss_and_grads`); at the published widths no more than one
    layer's backward is compiled (one a layout) or held at a time.
    Gradients come back as host arrays."""
    import numpy as np

    def layer_fn(layout):
        return lambda p, h: _layer(p, h, layout, cfg, True, wrong)

    def in_highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def head_loss(ln_final, embed, h, targets, keep, count):
        hidden = _rms_norm(h, ln_final, cfg.rms_norm_eps)
        return _nll(_head_logits(embed, hidden, cfg, wrong), targets, keep,
                    count)

    layouts = sorted(set(int(v) for v in cfg.layer_layout))
    forward = {v: in_highest(layer_fn(v)) for v in layouts}
    backward = {v: in_highest(
        lambda p, h, ct, _f=layer_fn(v): jax.vjp(_f, p, h)[1](ct))
        for v in layouts}
    head_grad = in_highest(jax.value_and_grad(head_loss, argnums=(0, 1, 2)))
    rows_grad = in_highest(lambda embed, ids, ct: jax.vjp(
        lambda e: _embedded({"embed": e}, ids, cfg, wrong), embed)[1](ct)[0])
    embedded = in_highest(
        lambda embed, ids: _embedded({"embed": embed}, ids, cfg, wrong))

    count = jnp.maximum(jnp.sum(mask > 0), 1)
    total, grads = 0.0, None
    for ids, targets, keep in zip(x, y, mask > 0):
        inputs = [embedded(params["embed"], ids)]
        for i, layout in enumerate(cfg.layer_layout):
            inputs.append(forward[int(layout)](
                params[f"block_{i}"], inputs[-1]))
        part, (g_ln, g_embed, ct) = head_grad(
            params["ln_final"], params["embed"], inputs.pop(), targets,
            keep, count)
        one = {"ln_final": np.asarray(g_ln)}
        g_embed = np.asarray(g_embed)
        for i in reversed(range(len(cfg.layer_layout))):
            g_block, ct = backward[int(cfg.layer_layout[i])](
                params[f"block_{i}"], inputs.pop(), ct)
            one[f"block_{i}"] = jax.tree.map(np.asarray, g_block)
        one["embed"] = g_embed + np.asarray(
            rows_grad(params["embed"], ids, ct))
        total = total + float(part)
        grads = one if grads is None else jax.tree.map(np.add, grads, one)
    return total, {k: grads[k] for k in params}
