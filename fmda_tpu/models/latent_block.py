"""A decoder layer with latent attention, a shared expert beside the
routed ones, and a residual stream of several lanes — ``layer_layout``
4 of :mod:`fmda_tpu.models.decoder`.

One block has two sublayers ``F``, attention then feed-forward.  With
``cfg.hc_streams == 1`` each is a plain pre-norm residual, ``x + F(
RMSNorm(x))``; with ``n > 1`` lanes each is wrapped by learned mixing
(:mod:`fmda_tpu.ops.hyper_connection`: ``Hpre`` reads the lanes into one
stream, ``Hpost`` writes ``F``'s output back, ``Hres`` remixes the lanes,
doubly stochastic)::

    u = sum_i Hpre[i] X[i] ;  y = F(RMSNorm(u)) ;  X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y

Latent attention (``n`` heads; ``dn`` = ``qk_nope_head_dim``, ``dr`` =
``qk_rope_head_dim``, ``dv`` = ``v_head_dim``)::

    cq = RMSNorm(h @ wq_a)  (q_lora_rank) ;  [qn | qr] = cq @ wq_b              n x (dn | dr)
    [ckv | kr] = h @ wkv_a  (kv_lora_rank | dr) ;  [kn | v] = RMSNorm(ckv) @ wkv_b   n x (dn | dv)
    qr, kr rotary over dr dims at YaRN's frequencies; kr is ONE head for all n
    s[t, j] = (qn_t . kn_j + qr_t . kr_j) * (dn + dr)^-1/2 * m^2 ,  m = 0.1 ln(rope_factor) + 1
    a = causal softmax(s) v ;  out = a @ wo                                    (n * dv -> hidden)

The core runs through :func:`fmda_tpu.ops.attention.mha` on ``[qn |
qr]`` and ``[kn | kr]`` (the shared rotary key repeated over the heads,
``dr / (dn + dr)`` of the keys' bytes) with values ``dv`` wide: the
flash kernels take a value width of their own, nothing is padded.

Feed-forward: the dense gated MLP of ``cfg.ffn_size`` in the first
``cfg.first_dense_layers`` layers; after them (``E`` = ``moe_experts``)::

    sc = sigmoid(u @ router)  (E) ;  S = top-k of (sc + router_bias)
    g_e = moe_routed_scaling * sc_e / sum_{e' in S} sc_e'
    m = act(u ws_gate) * (u ws_up) @ ws_down  +  sum_{e in S, held} g_e expert_e(u)

``router_bias`` has no gradient; the trainer's task moves it after each
step from the step's load over all ``E`` experts
(:meth:`fmda_tpu.train.tasks.NextToken.after_update`).

Scopes (docs/observability.md "Spans and scopes"): ``attention`` holds
``mla_proj`` (the four products, two norms, rotary, the output product)
and ``attention_latent`` (the core); ``hyper_conn`` holds ``hc_coeff``,
``hc_pre``, ``hc_post_res``; ``moe_shared``; the expert layer's and the
dense MLP's own.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from fmda_tpu.config import ModelConfig
from fmda_tpu.models.decoder import _dense_mlp, _weight, rms_norm
from fmda_tpu.ops import hyper_connection as hc
from fmda_tpu.ops.attention import mha
from fmda_tpu.ops.moe import expert_layer, kernel_impl, route, router_load

#: Initial value of the three gains ``a_pre``, ``a_post``, ``a_res``: the
#: coefficients start nearly static (their offsets), the input's part
#: small beside them.
HC_GAIN_INIT = 0.01
#: ... and the size of the offsets' initial values (:func:`_lane_offsets`):
#: a lane is read at sigmoid(+-6) = 0.9975 / 0.0025 and the remix is the
#: identity to exp(-12).
HC_OFFSET_INIT = 6.0


class LatentStats(NamedTuple):
    """What one latent block counted in a forward pass."""

    router_load: jax.Array    # (E,) int32: pairs on each of all experts
    bias_absmax: jax.Array    # () float32: largest |selection bias|
    hc_sum_error: jax.Array   # () float32: Hres sums' distance from one
    pairs_scored: jax.Array   # () int32: causal (query, key) pairs


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """The rotary frequencies of the ``qk_rope_head_dim`` rotary dims,
    (dr / 2,) float32: ``theta^(-2i/dr)``, stretched by YaRN where
    ``cfg.rope_factor > 1`` (dims turning more than ``rope_beta_fast``
    times over ``rope_original_max`` positions keep theirs, those under
    ``rope_beta_slow`` turns are divided by the factor, a linear ramp
    over the dims between)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1.0:
        return plain.astype(np.float32)

    def dim_turning(turns: float) -> float:
        return dim * math.log(cfg.rope_original_max / (turns * 2 * math.pi)
                              ) / (2 * math.log(base))

    low = max(math.floor(dim_turning(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(dim_turning(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / cfg.rope_factor * ramp + plain * (1 - ramp)
            ).astype(np.float32)


def score_scale(cfg: ModelConfig) -> float:
    """What the latent core's scores are multiplied by."""
    m = 0.1 * math.log(cfg.rope_factor) + 1.0 if cfg.rope_factor > 1 else 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def rotary_at(x: jax.Array, inv_freq) -> jax.Array:
    """Rotary over all of the last axis of ``(B, heads, T, dr)`` at the
    given frequencies, positions ``0 .. T-1``, half-split pairs
    ``(i, i + dr/2)``; float32 angles, ``x``'s dtype."""
    t, d = x.shape[-2], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _lane_offsets(size: float, n: int):
    """The offsets' initial values: read lane 0 (``Hpre`` near one-hot),
    write it with weight one (``2 sigmoid(0)``), remix by nearly the
    identity."""
    first = np.arange(n) == 0
    return (np.where(first, size, -size).astype(np.float32),
            np.where(first, 0.0, -size).astype(np.float32),
            np.where(np.eye(n, dtype=bool), size, -size).astype(np.float32))


class LatentBlock(nn.Module):
    """One layer (module docstring); ``dense``: its feed-forward is the
    dense MLP.  A module of its own so that ``nn.remat`` wraps it whole
    when ``cfg.remat``."""

    cfg: ModelConfig
    dense: bool = False

    def _mixed(self, name: str, x: jax.Array, fn, impl: str):
        """``hc.around`` with this sublayer's mixing parameters: the
        stream after ``fn`` inside its mixing, what else ``fn``
        returns, and ``Hres``."""
        cfg = self.cfg
        n, d = x.shape[2], x.shape[3]
        const = nn.initializers.constant
        gain = const(HC_GAIN_INIT)
        b_pre, b_post, b_res = _lane_offsets(HC_OFFSET_INIT, n)
        return hc.around(
            fn, x,
            _weight(self, f"hc_{name}_p_pre", (n * d, n)),
            _weight(self, f"hc_{name}_p_post", (n * d, n)),
            _weight(self, f"hc_{name}_p_res", (n * d, n * n)),
            tuple(self.param(f"hc_{name}_a_{k}", gain, (), jnp.float32)
                  for k in ("pre", "post", "res")),
            (self.param(f"hc_{name}_b_pre", const(b_pre), (n,)),
             self.param(f"hc_{name}_b_post", const(b_post), (n,)),
             self.param(f"hc_{name}_b_res", const(b_res), (n, n))),
            norm_eps=cfg.rms_norm_eps, iters=cfg.hc_sinkhorn_iters,
            eps=cfg.hc_eps, clamp=cfg.hc_res_clamp, impl=impl)

    def _attention(self, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        b, t, d = h.shape
        n, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        dt, eps = h.dtype, cfg.rms_norm_eps
        ones = nn.initializers.ones

        def heads(y, width):
            return y.reshape(b, t, n, width).transpose(0, 2, 1, 3)

        with jax.named_scope("attention"):
            with jax.named_scope("mla_proj"):
                cq = rms_norm(
                    jnp.dot(h, _weight(self, "wq_a", (d, cfg.q_lora_rank))
                            .astype(dt)),
                    self.param("q_norm", ones, (cfg.q_lora_rank,)), eps)
                q = heads(jnp.dot(cq, _weight(
                    self, "wq_b", (cfg.q_lora_rank, n * (dn + dr)))
                    .astype(dt)), dn + dr)
                ckv, kr = jnp.split(
                    jnp.dot(h, _weight(self, "wkv_a",
                                       (d, cfg.kv_lora_rank + dr)).astype(dt)),
                    [cfg.kv_lora_rank], axis=-1)
                kv = heads(jnp.dot(
                    rms_norm(ckv, self.param("kv_norm", ones,
                                             (cfg.kv_lora_rank,)), eps),
                    _weight(self, "wkv_b", (cfg.kv_lora_rank, n * (dn + dv)))
                    .astype(dt)), dn + dv)
                kn, v = kv[..., :dn], kv[..., dn:]
                with jax.named_scope("rope"):
                    inv_freq = yarn_inv_freq(cfg)
                    qr = rotary_at(q[..., dn:], inv_freq)
                    kr = rotary_at(kr[:, None], inv_freq)  # one head
                q = jnp.concatenate([q[..., :dn], qr], axis=-1)
                k = jnp.concatenate(
                    [kn, jnp.broadcast_to(kr, (b, n, t, dr))], axis=-1)
            with jax.named_scope("attention_latent"):
                a = mha(q, k, v, causal=True, use_flash=cfg.use_pallas,
                        scale=score_scale(cfg))
            with jax.named_scope("mla_proj"):
                a = a.transpose(0, 2, 1, 3).reshape(b, t, n * dv)
                return jnp.dot(a, _weight(self, "wo", (n * dv, d)).astype(dt))

    def _experts(self, u: jax.Array):
        """The expert layers' feed-forward on the normalised stream: its
        output, and ``(the plan, the load on all experts, the bias's
        size)``."""
        cfg = self.cfg
        b, t, d = u.shape
        f = cfg.moe_ffn_size
        first, count = cfg.experts_held
        flat = u.reshape(b * t, d)
        bias = None
        if cfg.moe_bias_rate > 0:
            bias = self.param("router_bias", nn.initializers.zeros,
                              (cfg.moe_experts,), jnp.float32)
        gates, experts = route(
            flat, _weight(self, "router", (d, cfg.moe_experts)),
            cfg.moe_top_k, scoring=cfg.moe_scoring, bias=bias,
            scale=cfg.moe_routed_scaling)
        m, plan = expert_layer(
            flat, gates, experts,
            _weight(self, "w_gate", (count, d, f)),
            _weight(self, "w_up", (count, d, f)),
            _weight(self, "w_down", (count, f, d)),
            experts_held=(first, count), impl=kernel_impl(cfg.use_pallas),
            act=cfg.hidden_act)
        if cfg.moe_shared_experts:
            shared = _dense_mlp(
                self, cfg, flat, cfg.moe_shared_experts * f,
                ("ws_gate", "ws_up", "ws_down"), "moe_shared")
            with jax.named_scope("moe_shared"):
                m = (m.astype(jnp.float32) + shared.astype(jnp.float32)
                     ).astype(u.dtype)
        absmax = (jnp.zeros((), jnp.float32) if bias is None
                  else jnp.max(jnp.abs(bias)))
        return m.reshape(b, t, d), (
            plan, router_load(experts, cfg.moe_experts), absmax)

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg = self.cfg
        lanes = cfg.hc_streams > 1
        d, ones = x.shape[-1], nn.initializers.ones
        sum_errors = []
        if lanes:  # what differentiates the mixing
            hc_impl = hc.backward_impl(
                kernel_impl(cfg.use_pallas), d, x.shape[1])

        def sublayer(x, name, ln, fn):
            """``x`` after the sublayer ``fn`` (normalised stream ->
            output, anything else it returns), and that else."""
            scale = self.param(ln, ones, (d,))

            def normed(u):
                return fn(rms_norm(u, scale, cfg.rms_norm_eps))

            if not lanes:
                y, out = normed(x)
                return x + y, out
            x, out, res = self._mixed(name, x, normed, hc_impl)
            with jax.named_scope("hyper_conn"):
                sum_errors.append(hc.sum_error(res))
            return x, out

        b, t = x.shape[:2]
        x, _ = sublayer(x, "attn", "ln_attn",
                        lambda h: (self._attention(h), None))
        has_experts = cfg.moe_experts > 0 and not self.dense
        if has_experts:
            x, (plan, load, absmax) = sublayer(
                x, "ffn", "ln_moe", self._experts)
            sizes, dropped, tiles = (plan.group_sizes, plan.dropped,
                                     plan.n_used[0])
        else:
            x, _ = sublayer(x, "ffn", "ln_mlp",
                            lambda u: (_dense_mlp(self, cfg, u), None))
            sizes = dropped = tiles = None
            if cfg.moe_experts:  # a dense layer of a model with experts
                zero = jnp.zeros((), jnp.int32)
                sizes, dropped, tiles = (
                    jnp.zeros((cfg.experts_held[1],), jnp.int32), zero, zero)
            load = jnp.zeros((cfg.moe_experts,), jnp.int32)
            absmax = jnp.zeros((), jnp.float32)
        worst = (jnp.max(jnp.stack(sum_errors)) if sum_errors
                 else jnp.zeros((), jnp.float32))
        stats = LatentStats(
            load, absmax, jax.lax.stop_gradient(worst),
            jnp.int32(b * (t * (t + 1) // 2)))
        return x, (sizes, dropped, tiles, None, None, stats)
