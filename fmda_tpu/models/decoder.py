"""Causal token decoder with routed experts and mixed window / full
attention — the token family, ``ModelConfig(cell="decoder")``.

Every other family classifies a float feature window; this one predicts
the next token of an id sequence.  One layer (x: residual stream,
``(T, hidden)``; ``layout`` from ``cfg.layer_layout``)::

    h  = RMSNorm(x)
    p  = softmax(h @ W_r)                     router, placed BEFORE attention
    S  = top-k of p ;  g_e = p_e / sum_{e' in S} p_e'
    q, k, v = h @ W_q, h @ W_k, h @ W_v       N query heads on G kv heads
    layout 1: rotary on q, k (all of head_dim); key j visible iff 0 <= i-j < window
    layout 0: no positional encoding;           key j visible iff j <= i
    a  = softmax(q k^T / sqrt(head_dim) + mask) v ;   x1 = x + a @ W_o
    u  = RMSNorm(x1)
    m  = sum_{e in S, held} g_e * (relu(u @ Wg_e) * (u @ Wu_e)) @ Wd_e
    x2 = x1 + m

A learned-sparse layer (``layout`` 2) decides the keys by a score it
learns instead of by position, and routes after attention::

    h  = RMSNorm(x)
    q, k, v = h @ W_q, h @ W_k, h @ W_v
    q, k = RMSNorm_head(q), RMSNorm_head(k) ;  rotary on q, k
    qI = rotary(h @ W_qI)  (Hi heads of Di) ;  kI = rotary(h @ W_kI)  (one head)
    w  = h @ W_wI  (Hi)
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          s <= t
    S_t = the min(t + 1, indexer_topk) keys s <= t with the largest I[t, s]
    a_t = softmax_{s in S_t}(q_t . k_s / sqrt(head_dim)) v_s ;  x1 = x + a @ W_o
    u  = RMSNorm(x1) ;  p = softmax(u @ W_r) ;  S, g as above
    m  = sum_{e in S, held} g_e * (act(u @ Wg_e) * (u @ Wu_e)) @ Wd_e
    x2 = x1 + m

(:mod:`fmda_tpu.ops.sparse_attention`; ``act`` is ``cfg.hidden_act``).
The top-k is piecewise constant: the next-token loss sends no gradient
to ``W_qI``, ``W_kI``, ``W_wI``, which keep their initial values (the
mechanism's published recipe trains them by a separate alignment term;
that term is not built, docs/training.md "The decoder family").

Then a final RMSNorm and an untied head ``(hidden, vocab_size)``.  The
expert layer computes the experts this chip holds
(``cfg.experts_held``; :mod:`fmda_tpu.ops.moe`), attention runs through
:func:`fmda_tpu.ops.attention.mha` (the fused kernel where
``cfg.use_pallas`` and the backend allow), and ``cfg.remat`` recomputes
each block in backward but for what :data:`REPLAY_KEEPS` names.
Parameters are float32; products run in
``cfg.dtype``; norms, softmaxes, rotary angles and the router's
probabilities are float32.

``__call__`` returns the logits whole (small sizes, tests).  Training
calls :meth:`MoEDecoder.features` and takes the loss over token chunks
(:func:`fmda_tpu.train.losses.chunked_next_token_loss`), so the
``(T, vocab_size)`` logits never exist at once.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fmda_tpu.config import ModelConfig
from fmda_tpu.ops.attention import CORE_LSE, CORE_OUT, mha
from fmda_tpu.ops.moe import ACTIVATIONS, expert_layer, kernel_impl, route
from fmda_tpu.ops.sparse_attention import (
    PICKS, kernels_dispatch, select_keys, sparse_mha)

#: Standard deviation of every weight matrix at init (the family's
#: convention; norm scales start at one).
INIT_STD = 0.02
#: ... and of the embedding's rows: the scale a trained model's residual
#: stream has against its blocks' outputs.  At ``INIT_STD`` the first
#: attention output (~0.1) drowns the token's own row (0.02), every
#: deeper router sees one row a sequence long, and routing collapses
#: onto ``moe_top_k`` experts (seen on the chip, PERF.md section 6, PR 28).
EMBED_INIT_STD = 1.0


#: ``layer_layout``'s value for a learned-sparse layer.
SPARSE_LAYOUT = 2

#: What a block's recomputation (``cfg.remat``) keeps from the forward
#: pass, by name; everything else it remakes from the block's input.
#: These are what attention's backward reads and only a second run of
#: the attention core (and, in a learned-sparse layer, of the indexer
#: and the selection) could remake: the core's output (heads x head_dim
#: wide, in the compute dtype), its rows' logsumexp (a float32 a head
#: and row; the learned-sparse kernel's packed tile, 128 lanes a row and
#: kv head) and a learned-sparse layer's picks (int8, T x T).  A layer
#: puts under a name what it has: one list serves every layout.
REPLAY_KEEPS = (CORE_OUT, CORE_LSE, PICKS)


class RoutingStats(NamedTuple):
    """What the expert layers counted in one forward pass."""

    expert_pairs: jax.Array  # (layers, held experts) int32
    dropped: jax.Array       # () int32: held pairs not computed (0)
    row_tiles_used: jax.Array  # (layers,) int32: row tiles holding a group
    #: What the learned-sparse layers' selection counted, None in a model
    #: without one: the keys kept, as (layers, 2) int32 ``[count >> 16,
    #: count & 0xffff]`` summed over the batch's sequences (a sequence of
    #: 16,384 tokens keeps 31 M keys a layer: a pass's sum outgrows
    #: int32), and the query rows they were kept for, (layers,) int32.
    keys_kept: Optional[jax.Array] = None
    query_rows: Optional[jax.Array] = None


def _weight(module: nn.Module, name: str, shape: Tuple[int, ...],
            std: float = INIT_STD):
    return module.param(name, nn.initializers.normal(std), shape,
                        jnp.float32)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """``x / sqrt(mean(x^2) + eps) * scale``, in float32, in x's dtype."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding over all of the last axis of
    ``(B, heads, T, head_dim)``, positions ``0 .. T-1``, the half-split
    convention: dims ``i`` and ``i + head_dim/2`` rotate together by
    ``pos * theta^(-2i/head_dim)``."""
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


class DecoderBlock(nn.Module):
    """One layer (module docstring).  A module of its own so that
    ``nn.remat`` wraps it whole when ``cfg.remat``."""

    cfg: ModelConfig
    layout: int

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.cfg
        b, t, d = x.shape
        n, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        first, count = cfg.experts_held
        dt = x.dtype

        sparse = self.layout == SPARSE_LAYOUT
        h = rms_norm(x, self.param("ln_attn", nn.initializers.ones, (d,)),
                     cfg.rms_norm_eps)

        def routed(y):
            return route(
                y.reshape(b * t, d),
                _weight(self, "router", (d, cfg.moe_experts)), cfg.moe_top_k)

        kept = None
        if not sparse:
            # the router reads the attention block's normalised input: it
            # is placed before attention, so its top-k is known a layer's
            # attention ahead of the experts it feeds
            gates, experts = routed(h)

        with jax.named_scope("attention"):
            def heads(name, n_heads, width=hd, src=h):
                y = jnp.dot(src, _weight(self, name, (d, n_heads * width))
                            .astype(dt))
                return y.reshape(b, t, n_heads, width).transpose(0, 2, 1, 3)

            q, k, v = heads("wq", n), heads("wk", g), heads("wv", g)
            if sparse:
                q, k = (rms_norm(y, self.param(name, nn.initializers.ones,
                                               (hd,)), cfg.rms_norm_eps)
                        for y, name in ((q, "q_norm"), (k, "k_norm")))
            if self.layout:
                with jax.named_scope("rope"):
                    q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
            if sparse:
                use_kernels = kernels_dispatch(
                    t, n // g, hd, use_kernels=cfg.use_pallas)
                with jax.named_scope("attention_indexer"):
                    # no gradient reaches the indexer (module docstring)
                    h_idx = jax.lax.stop_gradient(h)
                    hi, di = cfg.indexer_heads, cfg.indexer_head_dim
                    q_idx = rotary(heads("wq_idx", hi, di, h_idx),
                                   cfg.rope_theta)
                    k_idx = rotary(heads("wk_idx", 1, di, h_idx),
                                   cfg.rope_theta)[:, 0]
                    w_idx = jnp.dot(
                        h_idx, _weight(self, "ww_idx", (d, hi)).astype(dt),
                        preferred_element_type=jnp.float32)
                picked, kept = select_keys(
                    q_idx, k_idx, w_idx, cfg.indexer_topk,
                    use_kernels=use_kernels)
                self.sow("intermediates", "picked", picked)
                a = sparse_mha(q, k, v, picked, use_kernels=use_kernels)
            else:
                with jax.named_scope("attention_window" if self.layout
                                     else "attention_full"):
                    a = mha(q, k, v, causal=True,
                            window=(cfg.sliding_window if self.layout
                                    else None),
                            use_flash=cfg.use_pallas)
            a = a.transpose(0, 2, 1, 3).reshape(b, t, n * hd)
            x = x + jnp.dot(a, _weight(self, "wo", (n * hd, d)).astype(dt))

        u = rms_norm(x, self.param("ln_moe", nn.initializers.ones, (d,)),
                     cfg.rms_norm_eps)
        if sparse:
            gates, experts = routed(u)
        f = cfg.moe_ffn_size
        m, plan = expert_layer(
            u.reshape(b * t, d), gates, experts,
            _weight(self, "w_gate", (count, d, f)),
            _weight(self, "w_up", (count, d, f)),
            _weight(self, "w_down", (count, f, d)),
            experts_held=(first, count), impl=kernel_impl(cfg.use_pallas),
            act=cfg.hidden_act)
        if sparse:
            # (batch, row blocks) counts -> the split sum RoutingStats holds
            kept = (jnp.stack([jnp.sum(kept >> 16), jnp.sum(kept & 0xFFFF)]),
                    jnp.int32(b * t))
        return x + m.reshape(b, t, d), (
            plan.group_sizes, plan.dropped, plan.n_used[0], kept)


class MoEDecoder(nn.Module):
    """See module docstring."""

    cfg: ModelConfig

    def setup(self) -> None:
        cfg = self.cfg
        check_decoder_config(cfg)
        d = cfg.hidden_size
        self.embed = _weight(self, "embed", (cfg.vocab_size, d),
                             EMBED_INIT_STD)
        block_cls = DecoderBlock
        if cfg.remat:
            block_cls = nn.remat(
                DecoderBlock,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *REPLAY_KEEPS))
        self.blocks = [
            block_cls(cfg, int(layout), name=f"block_{i}")
            for i, layout in enumerate(cfg.layer_layout)]
        self.ln_final = self.param("ln_final", nn.initializers.ones, (d,))
        self.head = _weight(self, "head", (d, cfg.vocab_size))

    def features(self, ids: jax.Array) -> Tuple[jax.Array, RoutingStats]:
        """ids (B, T) int32 -> the final norm's output (B, T, hidden) in
        the compute dtype, and what the expert layers counted."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = jnp.take(self.embed, ids, axis=0).astype(jnp.dtype(cfg.dtype))
        sizes, tiles, dropped = [], [], jnp.zeros((), jnp.int32)
        zero = (jnp.zeros((2,), jnp.int32), jnp.zeros((), jnp.int32))
        kept = []
        for block in self.blocks:
            x, (layer_sizes, layer_dropped, layer_tiles, layer_kept) = block(x)
            sizes.append(layer_sizes)
            tiles.append(layer_tiles)
            dropped = dropped + layer_dropped
            kept.append(layer_kept)
        x = rms_norm(x, self.ln_final, cfg.rms_norm_eps)
        stats = RoutingStats(jnp.stack(sizes), dropped, jnp.stack(tiles))
        if any(k is not None for k in kept):
            keys, rows = zip(*(zero if k is None else k for k in kept))
            stats = stats._replace(keys_kept=jnp.stack(keys),
                                   query_rows=jnp.stack(rows))
        return x, stats

    def __call__(self, ids: jax.Array, *, deterministic: bool = True
                 ) -> jax.Array:
        """ids (B, T) -> logits (B, T, vocab_size) float32, whole."""
        del deterministic  # the family has no dropout
        x, _ = self.features(ids)
        with jax.named_scope("lm_head"):
            return jnp.dot(x, self.head.astype(x.dtype),
                           preferred_element_type=jnp.float32)


def check_decoder_config(cfg: ModelConfig) -> None:
    """Refuse a decoder configuration that leaves a size unset or
    inconsistent, naming the field."""
    first, count = cfg.experts_held
    sparse = SPARSE_LAYOUT in cfg.layer_layout
    problems = [name for name, ok in (
        ("vocab_size", cfg.vocab_size > 0),
        ("head_dim", cfg.head_dim > 0),
        ("n_kv_heads (must divide n_heads)",
         cfg.n_kv_heads > 0 and cfg.n_heads % cfg.n_kv_heads == 0),
        ("layer_layout (one of 0/1/2 per layer)",
         len(cfg.layer_layout) > 0
         and all(v in (0, 1, SPARSE_LAYOUT) for v in cfg.layer_layout)),
        ("moe_experts / moe_top_k",
         0 < cfg.moe_top_k <= cfg.moe_experts),
        ("moe_ffn_size", cfg.moe_ffn_size > 0),
        ("experts_held (first, count) inside moe_experts",
         count > 0 and first >= 0 and first + count <= cfg.moe_experts),
        ("head_dim (even, for rotary)", cfg.head_dim % 2 == 0),
        ("sliding_window", cfg.sliding_window > 0),
        ("hidden_act (one of %s)" % "/".join(sorted(ACTIVATIONS)),
         cfg.hidden_act in ACTIVATIONS),
        ("indexer_topk (layer_layout has a learned-sparse layer)",
         not sparse or cfg.indexer_topk > 0),
        ("indexer_heads (layer_layout has a learned-sparse layer)",
         not sparse or cfg.indexer_heads > 0),
        ("indexer_head_dim (even, for rotary; layer_layout has a "
         "learned-sparse layer)",
         not sparse or (cfg.indexer_head_dim > 0
                        and cfg.indexer_head_dim % 2 == 0)),
        ("n_heads / n_kv_heads / head_dim (a learned-sparse layer's "
         "kernels take a group that divides 128 and heads of at most 512)",
         not (sparse and cfg.use_pallas and cfg.n_kv_heads > 0)
         or (128 % max(cfg.n_heads // cfg.n_kv_heads, 1) == 0
             and cfg.head_dim <= 512)),
    ) if not ok]
    if problems:
        raise ValueError(
            "ModelConfig(cell='decoder') needs: " + "; ".join(problems))
