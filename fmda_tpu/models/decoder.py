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

A state-space layer (``layout`` 3) has no attention; its mixer carries a
``(P, N)`` matrix a head through the sequence (``H`` = ``ssm_heads``,
``P`` = ``ssm_head_dim``, ``N`` = ``ssm_state``, ``I = H * P``;
:mod:`fmda_tpu.ops.ssd`)::

    h  = RMSNorm(x)
    [z | xBC | dt] = h @ W_in                       I | I + 2N | H
    xBC = silu(conv_b + sum_{j<K} conv_w[:, j] * xBC[t-(K-1)+j])    causal, depthwise
    [xs | B | C] = xBC                              (T, H, P) | (T, N) | (T, N)
    d_t = softplus(dt_t + dt_bias) ;  A = -exp(A_log)
    S_t = exp(d_t A) S_{t-1} + d_t * xs_t (x) B_t   per head, S_{-1} = 0, float32
    y_t = S_t C_t + D * xs_t
    x1 = x + r * (RMSNorm_I(y * silu(z)) @ W_out)   gate first, one norm over all I

and where ``cfg.moe_experts`` is 0 every layer's feed-forward is one
dense gated MLP, with no router::

    u  = RMSNorm(x1) ;  x2 = x1 + r * ((act(u @ Wg) * (u @ Wu)) @ Wd)

``r`` is ``cfg.residual_multiplier`` (on the attention output too);
the embedding rows are multiplied by ``cfg.embedding_multiplier``, the
attention scores by ``cfg.attention_multiplier`` in place of
``1 / sqrt(head_dim)``, and the logits divided by ``cfg.logits_scaling``.
At their defaults none of the four is an operation of the program.

Then a final RMSNorm and a head ``(hidden, vocab_size)``: a leaf of its
own, or with ``cfg.tie_embeddings`` the embedding transposed.  The
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

import math
from typing import NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fmda_tpu.config import ModelConfig
from fmda_tpu.ops.attention import CORE_LSE, CORE_OUT, mha
from fmda_tpu.ops.moe import ACTIVATIONS, expert_layer, kernel_impl, route
from fmda_tpu.ops.sparse_attention import (
    PICKS, kernels_dispatch, select_keys, sparse_mha)
from fmda_tpu.ops.ssd import conv_silu, ssd_scan

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
#: ... and for a state-space layer (ops/ssd.py).
SSM_LAYOUT = 3
#: ... and for a latent-attention layer (models/latent_block.py).
LATENT_LAYOUT = 4

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
    """What the layers counted in one forward pass.  The first three are
    the expert layers', None in a model without experts."""

    expert_pairs: Optional[jax.Array]  # (layers, held experts) int32
    dropped: Optional[jax.Array]       # () int32: held pairs not computed (0)
    row_tiles_used: Optional[jax.Array]  # (layers,) int32: row tiles holding a group
    #: What the learned-sparse layers' selection counted, None in a model
    #: without one: the keys kept, as (layers, 2) int32 ``[count >> 16,
    #: count & 0xffff]`` summed over the batch's sequences (a sequence of
    #: 16,384 tokens keeps 31 M keys a layer: a pass's sum outgrows
    #: int32), and the query rows they were kept for, (layers,) int32.
    keys_kept: Optional[jax.Array] = None
    query_rows: Optional[jax.Array] = None
    #: What the state-space layers' scans walked, None in a model without
    #: one: chunks and positions, (layers,) int32 each, 0 in a layer of
    #: another kind.
    ssd_chunks: Optional[jax.Array] = None
    ssd_positions: Optional[jax.Array] = None
    #: What the latent-attention layers counted, None in a model without
    #: one: the pairs each of ALL the router's experts received, held
    #: here or not, (layers, moe_experts) int32 (what the selection bias
    #: steps on; a dense layer's row is 0); the largest size of a
    #: selection bias, (layers,) float32; the largest distance of a row
    #: or column sum of a residual mixing matrix from one, (layers,)
    #: float32 (0 with one lane); the causal pairs each core scored,
    #: (layers,) int32.
    router_load: Optional[jax.Array] = None
    router_bias_absmax: Optional[jax.Array] = None
    hc_sum_error: Optional[jax.Array] = None
    latent_pairs: Optional[jax.Array] = None


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


def _uniform(low: float, high: float, then=lambda v: v):
    """An initializer: ``then`` of a uniform draw from ``[low, high)``."""
    def init(key, shape, dtype=jnp.float32):
        return then(jax.random.uniform(key, shape, dtype, low, high))
    return init


def _inverse_softplus(step: jax.Array) -> jax.Array:
    return step + jnp.log(-jnp.expm1(-step))


def _ssm_mixer(module: nn.Module, cfg: ModelConfig, h: jax.Array):
    """A state-space layer's mixer (module docstring) on the normalised
    stream ``h`` (B, T, hidden): its output (B, T, hidden) and the
    ``(chunks, positions)`` its scan walked.  Parameters start where the
    mechanism's published code starts them: rates ``-A`` uniform in 1..16,
    step sizes log-uniform in 1e-3..1e-1 at a zero input, the skip at 1,
    the taps uniform in +-1/sqrt(taps)."""
    b, t, d = h.shape
    heads, p, n, taps = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                         cfg.ssm_conv)
    inner, dt, f32 = heads * p, h.dtype, jnp.float32
    with jax.named_scope("ssm_in_proj"):
        z, xbc, step = jnp.split(
            jnp.dot(h, _weight(module, "w_in",
                               (d, 2 * inner + 2 * n + heads)).astype(dt)),
            [inner, 2 * inner + 2 * n], axis=-1)
    with jax.named_scope("ssm_conv"):
        bound = taps ** -0.5
        xbc = conv_silu(
            xbc,
            module.param("conv_w", _uniform(-bound, bound),
                         (inner + 2 * n, taps), f32),
            module.param("conv_b", _uniform(-bound, bound),
                         (inner + 2 * n,), f32), dtype=dt)
    xs, b_in, c_out = jnp.split(xbc, [inner, inner + n], axis=-1)
    with jax.named_scope("ssd_scan"):
        step = jax.nn.softplus(step.astype(f32) + module.param(
            "dt_bias", _uniform(math.log(1e-3), math.log(1e-1),
                                lambda v: _inverse_softplus(jnp.exp(v))),
            (heads,), f32))
        rate = -jnp.exp(module.param(
            "a_log", _uniform(1.0, 16.0, jnp.log), (heads,), f32))
        y, states = ssd_scan(
            xs.reshape(b, t, heads, p), step, rate, b_in, c_out,
            module.param("d_skip", nn.initializers.ones, (heads,), f32),
            chunk=cfg.ssm_chunk, dtype=dt)
    with jax.named_scope("ssm_gate_norm"):
        gated = rms_norm(
            y.reshape(b, t, inner) * jax.nn.silu(z.astype(f32)),
            module.param("ln_gate", nn.initializers.ones, (inner,)),
            cfg.rms_norm_eps).astype(dt)
    with jax.named_scope("ssm_out_proj"):
        out = jnp.dot(gated, _weight(module, "w_out", (inner, d))
                      .astype(dt))
    return out, (jnp.int32(b * states.shape[1]), jnp.int32(b * t))


def _dense_mlp(module: nn.Module, cfg: ModelConfig, u: jax.Array,
               width: Optional[int] = None,
               names: Tuple[str, str, str] = ("w_gate", "w_up", "w_down"),
               scope: str = "dense_mlp"):
    """``(act(u @ Wg) * (u @ Wu)) @ Wd`` on the normalised stream,
    ``cfg.ffn_size`` wide unless a ``width`` is stated (a shared expert's,
    under its own leaves' ``names`` and its own ``scope``)."""
    d, f, dt = u.shape[-1], width or cfg.ffn_size, u.dtype
    with jax.named_scope(scope):
        gate = jnp.dot(u, _weight(module, names[0], (d, f)).astype(dt))
        up = jnp.dot(u, _weight(module, names[1], (d, f)).astype(dt))
        return jnp.dot(ACTIVATIONS[cfg.hidden_act](gate) * up,
                       _weight(module, names[2], (f, d)).astype(dt))


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
        dt = x.dtype

        sparse = self.layout == SPARSE_LAYOUT
        has_experts = cfg.moe_experts > 0
        h = rms_norm(x, self.param("ln_attn", nn.initializers.ones, (d,)),
                     cfg.rms_norm_eps)

        def joined(x, y):
            """The stream with a mixer's or a feed-forward's output."""
            if cfg.residual_multiplier == 1.0:
                return x + y
            return (x.astype(jnp.float32) + cfg.residual_multiplier
                    * y.astype(jnp.float32)).astype(dt)

        def routed(y):
            return route(
                y.reshape(b * t, d),
                _weight(self, "router", (d, cfg.moe_experts)), cfg.moe_top_k)

        def attention():
            """The attention layouts' mixer: its output before it joins
            the stream, and what a learned-sparse selection kept."""
            kept = None

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
                            use_flash=cfg.use_pallas,
                            scale=cfg.attention_multiplier)
            a = a.transpose(0, 2, 1, 3).reshape(b, t, n * hd)
            return jnp.dot(a, _weight(self, "wo", (n * hd, d)).astype(dt)), kept

        kept = walked = None
        if has_experts and not sparse:
            # the router reads the attention block's normalised input: it
            # is placed before attention, so its top-k is known a layer's
            # attention ahead of the experts it feeds
            gates, experts = routed(h)

        if self.layout == SSM_LAYOUT:
            with jax.named_scope("ssm_mixer"):
                mixed, walked = _ssm_mixer(self, cfg, h)
                x = joined(x, mixed)
        else:
            with jax.named_scope("attention"):
                mixed, kept = attention()
                x = joined(x, mixed)

        u = rms_norm(x, self.param("ln_moe" if has_experts else "ln_mlp",
                                   nn.initializers.ones, (d,)),
                     cfg.rms_norm_eps)
        if not has_experts:
            return joined(x, _dense_mlp(self, cfg, u)), (
                None, None, None, kept, walked)
        if sparse:
            gates, experts = routed(u)
        first, count = cfg.experts_held
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
        return joined(x, m.reshape(b, t, d)), (
            plan.group_sizes, plan.dropped, plan.n_used[0], kept, walked)


class MoEDecoder(nn.Module):
    """See module docstring."""

    cfg: ModelConfig

    def setup(self) -> None:
        cfg = self.cfg
        check_decoder_config(cfg)
        d = cfg.hidden_size
        # a tied embedding is a head too and starts at a head's scale
        self.embed = _weight(self, "embed", (cfg.vocab_size, d),
                             INIT_STD if cfg.tie_embeddings
                             else EMBED_INIT_STD)
        def replayed(block_cls):
            if not cfg.remat:
                return block_cls
            return nn.remat(
                block_cls,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *REPLAY_KEEPS))

        if LATENT_LAYOUT in cfg.layer_layout:
            from fmda_tpu.models.latent_block import LatentBlock

            block_cls = replayed(LatentBlock)
            self.blocks = [
                block_cls(cfg, i < cfg.first_dense_layers, name=f"block_{i}")
                for i in range(len(cfg.layer_layout))]
        else:
            block_cls = replayed(DecoderBlock)
            self.blocks = [
                block_cls(cfg, int(layout), name=f"block_{i}")
                for i, layout in enumerate(cfg.layer_layout)]
        self.ln_final = self.param("ln_final", nn.initializers.ones, (d,))
        if not cfg.tie_embeddings:
            self.head = _weight(self, "head", (d, cfg.vocab_size))

    def features(self, ids: jax.Array) -> Tuple[jax.Array, RoutingStats]:
        """ids (B, T) int32 -> the final norm's output (B, T, hidden) in
        the compute dtype, and what the layers counted."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = jnp.take(self.embed, ids, axis=0)
            if cfg.embedding_multiplier != 1.0:
                x = x * cfg.embedding_multiplier
            x = x.astype(jnp.dtype(cfg.dtype))
        has_experts = cfg.moe_experts > 0
        sizes, tiles, dropped = [], [], jnp.zeros((), jnp.int32)
        zero = (jnp.zeros((2,), jnp.int32), jnp.zeros((), jnp.int32))
        kept, walked, latent = [], [], []
        if cfg.hc_streams > 1:
            # every lane starts as the token's row
            x = jnp.broadcast_to(
                x[:, :, None, :], x.shape[:2] + (cfg.hc_streams, x.shape[-1]))
        for block in self.blocks:
            x, (layer_sizes, layer_dropped, layer_tiles, layer_kept,
                layer_walked, *layer_latent) = block(x)
            if has_experts:
                sizes.append(layer_sizes)
                tiles.append(layer_tiles)
                dropped = dropped + layer_dropped
            kept.append(layer_kept)
            walked.append(layer_walked)
            latent.extend(layer_latent)
        if cfg.hc_streams > 1:
            with jax.named_scope("hyper_conn"):  # the lanes leave as one
                x = jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)
        x = rms_norm(x, self.ln_final, cfg.rms_norm_eps)
        stats = (RoutingStats(jnp.stack(sizes), dropped, jnp.stack(tiles))
                 if has_experts else RoutingStats(None, None, None))
        if any(k is not None for k in kept):
            keys, rows = zip(*(zero if k is None else k for k in kept))
            stats = stats._replace(keys_kept=jnp.stack(keys),
                                   query_rows=jnp.stack(rows))
        if any(w is not None for w in walked):
            chunks, positions = zip(*((zero[1], zero[1]) if w is None else w
                                      for w in walked))
            stats = stats._replace(ssd_chunks=jnp.stack(chunks),
                                   ssd_positions=jnp.stack(positions))
        if latent:
            load, absmax, sum_error, pairs = (
                jnp.stack(v) for v in zip(*latent))
            stats = stats._replace(
                router_load=load if has_experts else None,
                router_bias_absmax=absmax if has_experts else None,
                hc_sum_error=sum_error if cfg.hc_streams > 1 else None,
                latent_pairs=pairs)
        return x, stats

    def __call__(self, ids: jax.Array, *, deterministic: bool = True
                 ) -> jax.Array:
        """ids (B, T) -> logits (B, T, vocab_size) float32, whole."""
        del deterministic  # the family has no dropout
        x, _ = self.features(ids)
        head = self.embed.T if self.cfg.tie_embeddings else self.head
        with jax.named_scope("lm_head"):
            logits = jnp.dot(x, head.astype(x.dtype),
                             preferred_element_type=jnp.float32)
        if self.cfg.logits_scaling != 1.0:
            logits = logits / self.cfg.logits_scaling
        return logits


def check_decoder_config(cfg: ModelConfig) -> None:
    """Refuse a decoder configuration that leaves a size unset or
    inconsistent, naming the field."""
    first, count = cfg.experts_held
    sparse = SPARSE_LAYOUT in cfg.layer_layout
    state_space = SSM_LAYOUT in cfg.layer_layout
    dense = cfg.moe_experts == 0
    latent = LATENT_LAYOUT in cfg.layer_layout
    lanes = cfg.hc_streams > 1
    sigmoid = cfg.moe_scoring == "sigmoid"
    why_ssm = " (layer_layout has a state-space layer)"
    why_latent = " (layer_layout has a latent-attention layer)"
    only_latent = " (a latent-attention model's)"
    problems = [name for name, ok in (
        ("vocab_size", cfg.vocab_size > 0),
        ("head_dim", latent or cfg.head_dim > 0),
        ("n_kv_heads (must divide n_heads)", latent or (
            cfg.n_kv_heads > 0 and cfg.n_heads % cfg.n_kv_heads == 0)),
        ("layer_layout (one of 0/1/2/3 per layer, or 4 in every layer)",
         len(cfg.layer_layout) > 0
         and (all(v == LATENT_LAYOUT for v in cfg.layer_layout) if latent
              else all(v in (0, 1, SPARSE_LAYOUT, SSM_LAYOUT)
                       for v in cfg.layer_layout))),
        ("ffn_size (moe_experts is 0 or first_dense_layers is not: a "
         "dense gated MLP)",
         not (dense or cfg.first_dense_layers > 0) or cfg.ffn_size > 0),
        ("q_lora_rank" + why_latent, not latent or cfg.q_lora_rank > 0),
        ("kv_lora_rank" + why_latent, not latent or cfg.kv_lora_rank > 0),
        ("qk_nope_head_dim" + why_latent,
         not latent or cfg.qk_nope_head_dim > 0),
        ("qk_rope_head_dim (even, for rotary)" + why_latent,
         not latent or (cfg.qk_rope_head_dim > 0
                        and cfg.qk_rope_head_dim % 2 == 0)),
        ("v_head_dim" + why_latent, not latent or cfg.v_head_dim > 0),
        ("rope_factor (at least 1) / rope_original_max (positive where "
         "the factor stretches)",
         cfg.rope_factor >= 1 and (cfg.rope_factor == 1
                                   or cfg.rope_original_max > 0)),
        ("attention_multiplier / residual_multiplier (a latent-attention "
         "layer states its own score scale and joins the stream unscaled)",
         not latent or (cfg.attention_multiplier is None
                        and cfg.residual_multiplier == 1.0)),
        ("first_dense_layers (0 .. the depth; more than 0 with experts"
         + only_latent + ")",
         0 <= cfg.first_dense_layers <= len(cfg.layer_layout)
         and (cfg.first_dense_layers == 0 or (latent and not dense))),
        ("moe_shared_experts (more than 0 with experts" + only_latent + ")",
         cfg.moe_shared_experts >= 0
         and (cfg.moe_shared_experts == 0 or (latent and not dense))),
        ("moe_scoring (softmax, or sigmoid" + only_latent + ")",
         cfg.moe_scoring == "softmax" or (sigmoid and latent)),
        ("moe_routed_scaling (positive; other than 1 with sigmoid scores)",
         cfg.moe_routed_scaling > 0
         and (cfg.moe_routed_scaling == 1.0 or sigmoid)),
        ("moe_bias_rate (0, or positive with sigmoid scores)",
         cfg.moe_bias_rate == 0 or (cfg.moe_bias_rate > 0 and sigmoid)),
        ("hc_streams (1, or more lanes" + only_latent + ")",
         cfg.hc_streams == 1 or (lanes and latent)),
        ("hc_sinkhorn_iters (hc_streams is more than 1)",
         not lanes or cfg.hc_sinkhorn_iters > 0),
        ("hc_eps / hc_res_clamp (positive; hc_streams is more than 1)",
         not lanes or (cfg.hc_eps > 0 and cfg.hc_res_clamp > 0)),
        ("moe_experts / moe_top_k",
         dense or 0 < cfg.moe_top_k <= cfg.moe_experts),
        ("moe_ffn_size", dense or cfg.moe_ffn_size > 0),
        ("experts_held (first, count) inside moe_experts",
         dense or (count > 0 and first >= 0
                   and first + count <= cfg.moe_experts)),
        ("ssm_heads" + why_ssm, not state_space or cfg.ssm_heads > 0),
        ("ssm_head_dim" + why_ssm, not state_space or cfg.ssm_head_dim > 0),
        ("ssm_state" + why_ssm, not state_space or cfg.ssm_state > 0),
        ("ssm_conv" + why_ssm, not state_space or cfg.ssm_conv > 0),
        ("ssm_chunk" + why_ssm, not state_space or cfg.ssm_chunk > 0),
        ("embedding_multiplier / residual_multiplier / logits_scaling "
         "(not zero)",
         cfg.embedding_multiplier != 0 and cfg.residual_multiplier != 0
         and cfg.logits_scaling != 0),
        ("attention_multiplier (None or positive)",
         cfg.attention_multiplier is None or cfg.attention_multiplier > 0),
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
