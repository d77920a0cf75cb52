"""Plain reference for a latent-attention decoder with a hyper-connected
residual stream (Xing4.0-29B-A4B's layer): forward, loss and gradients
in straightforward ``jax.numpy``, float32, every product under
``jax.default_matmul_precision("highest")``.  No kernel, no sort, no
gather of pairs, no token chunks in the loss: a dense loop over the held
experts with the gate as a mask, attention by an explicit mask on the
192-wide concatenated queries and keys, Sinkhorn's turns written out.
It imports nothing of ``fmda_tpu``; it reads the program's parameter
tree (names below) and a record of sizes (``cfg``: the program's
``ModelConfig`` or anything with the same attributes).

One block on one sequence (``X``: the stream, ``(T, n, d)``, ``n`` =
``hc_streams``; two sublayers ``F``, attention then feed-forward, each
wrapped the same way with parameters of its own; source: the catalog's
``config`` for Xing4.0-29B-A4B)::

    x~    = vec(X_t) / sqrt(mean(vec(X_t)^2) + rms_norm_eps)      over all n*d, no scale
    Hpre  = sigmoid(a_pre  * (x~ @ P_pre)  + b_pre)                (n,)
    Hpost = 2 * sigmoid(a_post * (x~ @ P_post) + b_post)           (n,)
    M0    = exp(clip(a_res * mat(x~ @ P_res) + b_res, -30, 30))    (n, n)
    Hres  = 20 x { rows / (row sums + hc_eps) ; columns / (column sums + hc_eps) }
    u_t   = sum_i Hpre[i] X_t[i] ;  y = F(RMSNorm_d(u)) ;  X'_t[i] = sum_j Hres[i, j] X_t[j] + Hpost[i] y_t

    attention F(h):  cq = RMSNorm(h @ wq_a) ;  [qn | qr] = cq @ wq_b            32 heads x (128 | 64)
                     [ckv | kr] = h @ wkv_a  (512 | 64) ;  [kn | v] = RMSNorm(ckv) @ wkv_b   32 x (128 | 128)
                     qr, kr rotary over 64 dims at YaRN's frequencies (factor 64, beta 32 / 1,
                     original 4096); kr is ONE head
                     s[t, j] = (qn_t . kn_j + qr_t . kr_j) * 192^-1/2 * m^2 ,  m = 0.1 ln(64) + 1
                     causal softmax ;  a @ wo
    feed-forward F(u), the first ``first_dense_layers`` layers:  (silu(u w_gate) * (u w_up)) w_down
                     the others:  sc = sigmoid(u @ router) (64) ;  S = top-4 of (sc + router_bias)
                                  g_e = 2 * sc_e / sum_{e' in S} sc_e'
                                  m = SwiGLU_shared(u) + sum_{e in S, e held} g_e SwiGLU_e(u)
    entry: X_0[i] = embedding row, all i ;  exit: sum_i X[i] -> final RMSNorm -> head

and next-token cross-entropy (mean over the tokens whose mask is 1).
After a train step ``router_bias_e += moe_bias_rate * sign(mean load -
load_e)`` over the step's pairs on all 64 experts (:func:`bias_step`);
the bias has no gradient.

Departures from the published description, each shared with the program
(the configuration's file lists them under ``assumed``):

1. **The share.**  Only the experts ``experts_held = (first, count)``
   are summed; the router keeps its 64 outputs and the gates are
   normalised over the whole top-4.  The shared expert is whole.  The
   vocabulary is the held slice.  One dense layer and four expert layers
   of the 2 + 38; no multi-token-prediction module.
2. **Rotary convention**: half-split pairs ``(i, i + 32)`` of the 64
   rotary dims.
3. **The exit**: the lanes are summed.  **The norm over n*d** has no
   scale.  Initial values are the program's (they come with its tree).

Three measures keep 4,096 tokens inside a chip's memory without changing
a number: attention scores one block of :data:`QUERY_BLOCK` query rows
against all keys at a time; ``remat=True`` recomputes each block, and
each query block, in backward; the loop over the held experts is a
``lax.scan``.  The comparison on the chip takes the backward a layer at
a time (:func:`loss_and_grads_by_layer`).

Deliberately wrong runs (``wrong``: keywords of :func:`hidden_states`),
which the comparison that decides ``correct`` must catch:
``products_as`` (every operand of every product rounded to a narrower
type), ``sinkhorn_as`` (every turn's result rounded), ``softmax_as``
(attention's scores and probabilities rounded), ``sinkhorn_turns_less``
(that many turns fewer), ``router`` (``"softmax"`` scores in place of
sigmoid ones), ``skip_shared`` (the shared expert left out).  One
keyword makes the reference *more* like the program, to say where a
distance comes from: ``stream_as`` rounds the stream at the entry and
after every sublayer's write, as a program that keeps the lanes in the
compute dtype between sublayers does.

Parameter tree (the program's, float32): ``embed (V, D)``; ``block_<i>``:
``ln_attn (D,)``, ``wq_a (D, 768)``, ``q_norm (768,)``, ``wq_b (768,
32*192)``, ``wkv_a (D, 576)``, ``kv_norm (512,)``, ``wkv_b (512,
32*256)``, ``wo (32*128, D)``; ``hc_attn_*`` / ``hc_ffn_*``: ``p_pre``,
``p_post (n*D, n)``, ``p_res (n*D, n*n)``, ``a_pre``, ``a_post``,
``a_res ()``, ``b_pre``, ``b_post (n,)``, ``b_res (n, n)``; a dense
layer: ``ln_mlp``, ``w_gate``/``w_up (D, F)``, ``w_down (F, D)``; an
expert layer: ``ln_moe``, ``router (D, 64)``, ``router_bias (64,)``,
``ws_gate``/``ws_up (D, Fs)``, ``ws_down (Fs, D)``, ``w_gate``/``w_up
(count, D, Fe)``, ``w_down (count, Fe, D)``; ``ln_final (D,)``; ``head
(D, V)``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: Query rows scored against all keys at a time.
QUERY_BLOCK = 512

_BITS = {"bfloat16": (8, 7), "float16": (5, 10), "float8_e5m2": (5, 2),
         "float8_e4m3fn": (4, 3)}


def _rounder(kind: Optional[str]):
    """Round a value to a narrower type's exponent and mantissa and back
    (``lax.reduce_precision``: a convert pair is dropped by a compiler
    that allows excess precision); cotangents pass as they are.  The
    identity without a kind."""
    if kind is None:
        return lambda a: a
    exp, man = _BITS[kind]
    return lambda a: a + jax.lax.stop_gradient(
        jax.lax.reduce_precision(a, exp, man) - a)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def yarn_inv_freq(cfg) -> np.ndarray:
    """(dr / 2,) rotary frequencies, YaRN's where ``rope_factor > 1``."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1.0:
        return plain.astype(np.float32)

    def dim_turning(turns):
        return dim * math.log(cfg.rope_original_max / (turns * 2 * math.pi)
                              ) / (2 * math.log(base))

    low = max(math.floor(dim_turning(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(dim_turning(cfg.rope_beta_slow)), dim - 1)
    keep = 1.0 - np.clip(
        (np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / cfg.rope_factor * (1 - keep) + plain * keep
            ).astype(np.float32)


def _rotary(x, inv_freq):
    """x (heads, T, dr): dims i and i + dr/2 rotate by pos * inv_freq[i]."""
    t, d = x.shape[-2], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def sinkhorn(m, iters: int, eps: float, rounded=lambda a: a):
    """``iters`` turns on the last two axes of ``m`` (..., n, n): rows
    (the last axis) over their sums, then columns over theirs."""
    for _ in range(iters):
        m = rounded(m / (jnp.sum(m, axis=-1, keepdims=True) + eps))
        m = rounded(m / (jnp.sum(m, axis=-2, keepdims=True) + eps))
    return m


def mixing(p: Dict, name: str, x, cfg, wrong: Dict):
    """``(Hpre (T, n), Hpost (T, n), Hres (T, n, n))`` of sublayer
    ``name`` (``"attn"`` / ``"ffn"``) from the stream ``x`` (T, n, d)."""
    t, n, d = x.shape
    narrow = _rounder(wrong.get("products_as"))
    flat = x.reshape(t, n * d)
    xt = narrow(flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + cfg.rms_norm_eps))
    get = lambda k: p[f"hc_{name}_{k}"]
    pre = jax.nn.sigmoid(
        get("a_pre") * (xt @ narrow(get("p_pre"))) + get("b_pre"))
    post = 2.0 * jax.nn.sigmoid(
        get("a_post") * (xt @ narrow(get("p_post"))) + get("b_post"))
    logits = get("a_res") * (xt @ narrow(get("p_res"))).reshape(t, n, n) \
        + get("b_res")
    res = sinkhorn(
        jnp.exp(jnp.clip(logits, -cfg.hc_res_clamp, cfg.hc_res_clamp)),
        cfg.hc_sinkhorn_iters - int(wrong.get("sinkhorn_turns_less", 0)),
        cfg.hc_eps, _rounder(wrong.get("sinkhorn_as")))
    return pre, post, res


def _attention_core(q, k, v, scale: float, remat: bool, wrong: Dict):
    """q, k (N, T, dq), v (N, T, dv) -> (N, T, dv): softmax over an
    explicit causal mask, a block of query rows at a time."""
    n, t, dq = q.shape
    narrow = _rounder(wrong.get("products_as"))
    soft = _rounder(wrong.get("softmax_as"))
    k, v = narrow(k), narrow(v)
    key_pos = jnp.arange(t)

    def block(q_blk, pos):
        s = soft(jnp.einsum("nqd,nkd->nqk", narrow(q_blk), k) * scale)
        s = jnp.where((pos[:, None] >= key_pos[None, :])[None], s, -jnp.inf)
        return jnp.einsum(
            "nqk,nkd->nqd", narrow(soft(jax.nn.softmax(s, axis=-1))), v)

    blk = QUERY_BLOCK
    if t <= blk or t % blk:
        return block(q, key_pos)
    if remat:
        block = jax.checkpoint(block)
    out = jax.lax.map(
        lambda xs: block(*xs),
        (q.reshape(n, t // blk, blk, dq).transpose(1, 0, 2, 3),
         key_pos.reshape(t // blk, blk)))
    return out.transpose(1, 0, 2, 3).reshape(n, t, v.shape[-1])


def score_scale(cfg) -> float:
    m = 0.1 * math.log(cfg.rope_factor) + 1.0 if cfg.rope_factor > 1 else 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def attention(p: Dict, h, cfg, remat: bool, wrong: Dict):
    """Latent attention on the normalised stream h (T, D) -> (T, D)."""
    t = h.shape[0]
    n, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    narrow = _rounder(wrong.get("products_as"))
    eps = cfg.rms_norm_eps
    h_n = narrow(h)
    cq = _rms_norm(h_n @ narrow(p["wq_a"]), p["q_norm"], eps)
    q = (narrow(cq) @ narrow(p["wq_b"])).reshape(t, n, dn + dr) \
        .transpose(1, 0, 2)
    ckv_kr = h_n @ narrow(p["wkv_a"])
    ckv, kr = ckv_kr[:, :cfg.kv_lora_rank], ckv_kr[:, cfg.kv_lora_rank:]
    kv = (narrow(_rms_norm(ckv, p["kv_norm"], eps)) @ narrow(p["wkv_b"])
          ).reshape(t, n, dn + dv).transpose(1, 0, 2)
    inv_freq = yarn_inv_freq(cfg)
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], inv_freq)], -1)
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(_rotary(kr[None], inv_freq), (n, t, dr))], -1)
    a = _attention_core(q, k, kv[..., dn:], score_scale(cfg), remat, wrong)
    return narrow(a.transpose(1, 0, 2).reshape(t, n * dv)) @ narrow(p["wo"])


def _gated(u, w_gate, w_up, w_down, narrow):
    return narrow(jax.nn.silu(u @ narrow(w_gate)) * (u @ narrow(w_up))) \
        @ narrow(w_down)


def feed_forward(p: Dict, u, cfg, dense: bool, wrong: Dict):
    """``(output (T, D), pairs on each held expert (count,), pairs on
    each of all experts (E,))`` on the normalised stream u (T, D)."""
    narrow = _rounder(wrong.get("products_as"))
    u_n = narrow(u)
    first, count = cfg.experts_held
    if dense:
        return (_gated(u_n, p["w_gate"], p["w_up"], p["w_down"], narrow),
                jnp.zeros((count,), jnp.int32),
                jnp.zeros((cfg.moe_experts,), jnp.int32))
    logits = u_n @ narrow(p["router"])
    if wrong.get("router") == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
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
    return m, pairs, load


def sum_error(res):
    """Largest distance of a row or column sum of res (T, n, n) from 1."""
    return jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, -1) - 1.0)),
                       jnp.max(jnp.abs(jnp.sum(res, -2) - 1.0)))


def block(p: Dict, x, cfg, dense: bool, remat: bool, wrong: Dict):
    """One block on one sequence's stream x (T, n, d) (or (T, d) with one
    lane) -> ``(x', held pairs, load, the mixing matrices' sum error)``."""
    eps = cfg.rms_norm_eps
    errors = []
    stream = _rounder(wrong.get("stream_as"))

    def sublayer(x, name, ln, fn):
        if cfg.hc_streams == 1:
            y, out = fn(_rms_norm(x, p[ln], eps))
            return stream(x + y), out
        pre, post, res = mixing(p, name, x, cfg, wrong)
        errors.append(sum_error(res))
        u = jnp.einsum("tn,tnd->td", pre, x)
        y, out = fn(_rms_norm(u, p[ln], eps))
        return stream(jnp.einsum("tij,tjd->tid", res, x)
                      + post[:, :, None] * y[:, None, :]), out

    x, _ = sublayer(x, "attn", "ln_attn",
                    lambda h: (attention(p, h, cfg, remat, wrong), None))

    def ffn(u):
        m, pairs, load = feed_forward(p, u, cfg, dense, wrong)
        return m, (pairs, load)

    x, (pairs, load) = sublayer(
        x, "ffn", "ln_mlp" if dense or not cfg.moe_experts else "ln_moe", ffn)
    worst = jnp.max(jnp.stack(errors)) if errors else jnp.zeros(())
    return x, pairs, load, jax.lax.stop_gradient(worst)


def _is_dense(cfg, i: int) -> bool:
    return not cfg.moe_experts or i < cfg.first_dense_layers


def _enter(params: Dict, ids, cfg, wrong: Optional[Dict] = None):
    x = _rounder((wrong or {}).get("stream_as"))(params["embed"][ids])
    if cfg.hc_streams > 1:
        x = jnp.broadcast_to(x[:, None, :],
                             (x.shape[0], cfg.hc_streams, x.shape[1]))
    return x


def _leave(x, cfg):
    return jnp.sum(x, axis=1) if cfg.hc_streams > 1 else x


def hidden_states(params: Dict, ids, cfg, *, remat: bool = False, **wrong):
    """ids (T,) -> ``(final-normed hidden (T, D), held pairs (layers,
    count), load (layers, E), sum errors (layers,))``."""
    with jax.default_matmul_precision("highest"):
        x = _enter(params, ids, cfg, wrong)
        pairs, loads, errors = [], [], []
        for i in range(len(cfg.layer_layout)):
            layer = lambda p, x, _dense=_is_dense(cfg, i): block(
                p, x, cfg, _dense, remat, wrong)
            if remat:
                layer = jax.checkpoint(layer)
            x, layer_pairs, load, err = layer(params[f"block_{i}"], x)
            pairs.append(layer_pairs)
            loads.append(load)
            errors.append(err)
        return (_rms_norm(_leave(x, cfg), params["ln_final"],
                          cfg.rms_norm_eps),
                jnp.stack(pairs), jnp.stack(loads), jnp.stack(errors))


def _head_logits(params: Dict, hidden, wrong: Dict):
    narrow = _rounder(wrong.get("products_as"))
    return narrow(hidden) @ narrow(params["head"])


def logits(params: Dict, ids, cfg, **kw):
    """ids (T,) -> (T, V) float32."""
    wrong = {k: v for k, v in kw.items() if k != "remat"}
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(params, ids, cfg, **kw)[0]
        return _head_logits(params, hidden, wrong)


def _nll_mean(lg, targets, keep, count):
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(keep, nll, 0.0)) / count


def loss_and_counts(params: Dict, ids, targets, mask, cfg, **kw):
    """Mean next-token cross-entropy over the masked tokens of one
    sequence, and ``(held pairs, load, sum errors)`` a layer."""
    wrong = {k: v for k, v in kw.items() if k != "remat"}
    with jax.default_matmul_precision("highest"):
        hidden, pairs, load, errors = hidden_states(params, ids, cfg, **kw)
        keep = mask > 0
        return _nll_mean(_head_logits(params, hidden, wrong), targets, keep,
                         jnp.maximum(jnp.sum(keep), 1)), (pairs, load, errors)


def loss(params: Dict, ids, targets, mask, cfg, **kw):
    return loss_and_counts(params, ids, targets, mask, cfg, **kw)[0]


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
    """``(loss, gradients)`` of :func:`batch_loss`, float32."""
    # the whole value_and_grad inside the precision context: the backward
    # is traced after the forward returns
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: batch_loss(p, x, y, mask, cfg, remat=remat, **kw)
        )(params)


def loss_and_grads_by_layer(params: Dict, x, y, mask, cfg, **wrong):
    """:func:`loss_and_grads` again, the backward written out a block at
    a time: forward keeping each block's input, the head's gradient,
    then each block's vector-Jacobian product from the last to the
    first, the embedding's rows last; a batch's sequences one after the
    other.  The same numbers (tests/test_latent_reference.py); at the
    published widths no more than one block's backward is compiled (one
    a kind of block) or held at a time.  Gradients come back as host
    arrays."""
    def block_fn(dense):
        return lambda p, h: block(p, h, cfg, dense, True, wrong)[0]

    def in_highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def head_loss(ln_final, head, h, targets, keep, count):
        hidden = _rms_norm(_leave(h, cfg), ln_final, cfg.rms_norm_eps)
        return _nll_mean(_head_logits({"head": head}, hidden, wrong),
                         targets, keep, count)

    kinds = sorted({_is_dense(cfg, i) for i in range(len(cfg.layer_layout))})
    forward = {v: in_highest(block_fn(v)) for v in kinds}
    backward = {v: in_highest(
        lambda p, h, ct, _f=block_fn(v): jax.vjp(_f, p, h)[1](ct))
        for v in kinds}
    head_grad = in_highest(jax.value_and_grad(head_loss, argnums=(0, 1, 2)))
    rows = params["embed"].shape
    embed_grad = jax.jit(
        lambda ids, ct: jnp.zeros(rows, jnp.float32).at[ids].add(
            _leave(ct, cfg)))

    count = jnp.maximum(jnp.sum(mask > 0), 1)
    total, grads = 0.0, None
    for ids, targets, keep in zip(x, y, mask > 0):
        inputs = [_enter(params, ids, cfg, wrong)]
        for i in range(len(cfg.layer_layout)):
            inputs.append(forward[_is_dense(cfg, i)](
                params[f"block_{i}"], inputs[-1]))
        part, (g_ln, g_head, ct) = head_grad(
            params["ln_final"], params["head"], inputs.pop(), targets,
            keep, count)
        one = {"ln_final": np.asarray(g_ln), "head": np.asarray(g_head)}
        for i in reversed(range(len(cfg.layer_layout))):
            g_block, ct = backward[_is_dense(cfg, i)](
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


def bias_step(load, rate: float):
    """The selection biases' change after a step whose pairs on all the
    experts were ``load`` (layers, E): ``rate * sign(mean load - load)``
    a layer (0 for a dense layer's row of zeros)."""
    load = np.asarray(load, np.float64)
    return (rate * np.sign(load.mean(axis=-1, keepdims=True) - load)
            ).astype(np.float32)
