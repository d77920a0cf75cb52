"""Causal token decoder with routed experts and mixed window / full
attention — the token family, ``ModelConfig(cell="decoder")``.

Every other family classifies a float feature window; this one predicts
the next token of an id sequence.  A layer is a mixer, a feed-forward and
a residual, each written once: :class:`DecoderBlock` runs ``mixer
sublayer -> feed-forward sublayer``, the mixer from :data:`KINDS` (the
one table that gives ``cfg.layer_layout``'s integers a meaning), the
feed-forward :func:`feed_forward`, and what a layer counts declared by
name (:func:`model_counts`).  A layer of kind 0 or 1 (x: residual
stream, ``(T, hidden)``)::

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

(kind 0: full attention; kind 1: window attention.)  A learned-sparse
layer (``layout`` 2) decides the keys by a score it learns instead of by
position, and routes after attention::

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
:func:`fmda_tpu.ops.attention.mha` (the fused kernels, and the delta-rule
walk's ``kda_intra``, where ``cfg.use_pallas`` and the backend allow), and
``cfg.remat`` recomputes each block but for what :data:`REPLAY_KEEPS` names.
Parameters are float32; products run in
``cfg.dtype``; norms, softmaxes, rotary angles and the router's
probabilities are float32.

A latent-attention layer (``layout`` 4) keeps ``n`` heads of ``dn`` =
``qk_nope_head_dim`` unrotated
and ``dr`` = ``qk_rope_head_dim`` rotated dims, values ``dv`` =
``v_head_dim`` wide, behind two low-rank products::

    cq = RMSNorm(h @ wq_a)  (q_lora_rank) ;  [qn | qr] = cq @ wq_b              n x (dn | dr)
    (q_lora_rank 0, a direct query:  [qn | qr] = h @ wq, no latent and no norm)
    [ckv | kr] = h @ wkv_a  (kv_lora_rank | dr) ;  [kn | v] = RMSNorm(ckv) @ wkv_b   n x (dn | dv)
    qr, kr rotary over dr dims at YaRN's frequencies; kr is ONE head for all n
    (``cfg.mla_use_nope``: the model states no position here; qr and kr stay, unrotated)
    s[t, j] = (qn_t . kn_j + qr_t . kr_j) * (dn + dr)^-1/2 * m^2 ,  m = 0.1 ln(rope_factor) + 1
    a = causal softmax(s) v ;  out = a @ wo                                    (n * dv -> hidden)

The core runs through :func:`~fmda_tpu.ops.attention.mha` on ``[qn |
qr]`` and ``[kn | kr]`` (the shared rotary key repeated over the heads,
``dr / (dn + dr)`` of the keys' bytes) with values ``dv`` wide: the
flash kernels take a value width of their own, nothing is padded.  Such
a model may state three more things.  Its first
``cfg.first_dense_layers`` layers take the dense MLP though the rest
have experts.  Its expert layers may score by sigmoid, choose on a
biased score and add a shared expert (``E`` = ``moe_experts``)::

    sc = sigmoid(u @ router)  (E) ;  S = top-k of (sc + router_bias)
    g_e = moe_routed_scaling * sc_e / sum_{e' in S} sc_e'
    m = act(u ws_gate) * (u ws_up) @ ws_down  +  sum_{e in S, held} g_e expert_e(u)

(``router_bias`` has no gradient; the trainer's task moves it after each
step from the step's load over all ``E`` experts,
:meth:`fmda_tpu.train.tasks.NextToken.after_update`).  Such an expert
layer may add a term to what training differentiates, the first LOSS
TERM A LAYER DECLARES (:data:`TERMS`; ``cfg.moe_seq_aux_alpha`` > 0):
the router's balance term of each SEQUENCE of ``T`` tokens, over all
``E`` experts, held here or not (``K`` = ``moe_top_k``)::

    s'[t, e] = sc[t, e] / sum_e' sc[t, e']         P_e = mean_t s'[t, e]
    f_e = E / (K T) * #{t : e in S_t}              (S_t as chosen; a count, no gradient)
    L_bal = alpha * sum_e f_e P_e                  (alpha at an even router)
    objective = mean next-token loss + mean over the step's sequences of sum over expert layers of L_bal

(:func:`fmda_tpu.ops.moe.seq_balance_term`; validation and test losses
are the next-token loss alone).  With a direct query, a plain residual,
``first_dense_layers`` 1, two shared experts and this term the block is
Moonlight-16B-A3B's (``x`` the stream ``(T, 2048)``)::

    h  = RMSNorm(x) ;  [qn | qr] = h @ wq                  16 heads x (128 | 64)
    [ckv | kr] = h @ wkv_a (512 | 64) ;  [kn | v] = RMSNorm(ckv) @ wkv_b      16 x (128 | 128)
    qr, kr rotary over 64 dims, theta 50,000, no stretch; kr ONE head
    s[t, j] = (qn_t . kn_j + qr_t . kr_j) * 192^-1/2 ;  a = causal softmax(s) v ;  x1 = x + a @ wo
    u  = RMSNorm(x1)
    layer 0:     x2 = x1 + (silu(u Wg) * (u Wu)) Wd                       11264 wide
    layers 1..:  sc = sigmoid(u @ router) (64) ;  S = top-6 of (sc + router_bias)
                 g_e = 2.446 * sc_e / sum_{e' in S} sc_e'
                 x2 = x1 + shared(u) + sum_{e in S, held} g_e expert_e(u)    shared: 2 x 1408 wide

A delta-rule layer with a decay a channel (``layout`` 5, Kimi Delta
Attention; :mod:`fmda_tpu.ops.kda`) has no attention and no positional
encoding; its mixer carries a ``(dk, dk)`` float32 state a head through
the sequence (``H`` = ``kda_heads``, ``dk`` = ``kda_head_dim``, the
convolutions ``kda_conv`` taps, causal, depthwise, no bias)::

    h  = RMSNorm(x)
    q  = L2norm_head(conv_silu(h @ wq)) ;  k = L2norm_head(conv_silu(h @ wk)) ;  v = conv_silu(h @ wv)    (T, H, dk) each
    g  = -exp(a_log)[head] * softplus((h @ wf_a) @ wf_b + dt_bias)     (T, H, dk) float32, <= 0: the log-decay A CHANNEL
    b  = sigmoid(h @ wb)                                               (T, H)
    S_t = Diag(exp(g_t)) S_{t-1} ;  S_t += b_t k_t (v_t - S_t^T k_t)^T           S_{-1} = 0
    o_t = S_t^T q_t * dk^-1/2
    x1 = x + (RMSNorm_head(o) * sigmoid((h @ wg_a) @ wg_b)) @ wo       the low-rank pairs dk wide; the norm over a head's dk

Layers of kinds 4 and 5 state their heads' widths themselves and may
share a model (they do not mix with kinds 0..3), each with the
feed-forward described above: an expert layer under either has the same
router, bias, shared expert and load.  With ``mla_use_nope``, a direct
query, ``first_dense_layers`` 1, one shared expert and three delta-rule
layers to one latent layer the block is Kimi-Linear-48B-A3B's (``x`` the
stream ``(T, 2304)``)::

    layers 1, 2, 3, 5, ... (of four, the first three):  the delta-rule mixer, 32 heads of 128 | 128, 4 taps
    layers 4, 8, ...:  [qn | qr] = h @ wq (32 x (128 | 64)) ;  [ckv | kr] = h @ wkv_a (512 | 64)
                 [kn | v] = RMSNorm(ckv) @ wkv_b ;  NO rotary ;  s = (qn . kn + qr . kr) * 192^-1/2 ;  causal softmax
    u  = RMSNorm(x1)
    layer 1:     x2 = x1 + (silu(u Wg) * (u Wu)) Wd                       9216 wide
    layers 2..:  sc = sigmoid(u @ router) (256) ;  S = top-8 of (sc + router_bias)
                 g_e = 2.446 * sc_e / sum_{e' in S} sc_e'
                 x2 = x1 + shared(u) + sum_{e in S, held} g_e expert_e(u)    shared and experts 1024 wide

A gated-delta-rule layer (``layout`` 6; :mod:`fmda_tpu.ops.kda` with
``g`` a head) has ONE decay and one step size a head where kind 5 has
them a channel, a correction that may overshoot, keys and values of
different widths and a full-rank output gate (``H`` = ``gdn_heads``,
``dk`` = ``gdn_key_dim``, ``dv`` = ``gdn_value_dim``, the convolutions
``gdn_conv`` taps, causal, depthwise, no bias)::

    q  = L2norm_head(conv_silu(h @ wq)) ;  k = L2norm_head(conv_silu(h @ wk))       (T, H, dk)
    v  = conv_silu(h @ wv)                                                            (T, H, dv)
    g  = -exp(a_log)[head] * softplus(h @ wa + dt_bias)      (T, H) float32, <= 0: ONE log-decay a head
    b  = gdn_beta_scale * sigmoid(h @ wb)                    (T, H) in 0..gdn_beta_scale (2: ``I - b k k^T`` may reflect)
    S_t = exp(g_t) S_{t-1} ;  S_t += b_t k_t (v_t - S_t^T k_t)^T          S: (dk, dv) a head, float32, S_{-1} = 0
    o_t = S_t^T q_t * dk^-1/2
    y  = (RMSNorm_head(o) * silu(h @ wg)) @ wo               wg: hidden -> H x dv; the norm over a head's dv

It shares a model with kinds 0..3.  Two more facts a configuration may
state: ``cfg.post_norm`` puts a block's two norms on the sublayers'
OUTPUTS, ``x1 = x + RMSNorm(mixer(x))``, ``x2 = x1 + RMSNorm(mlp(x1))``,
mixer and feed-forward reading the stream as it is (``h`` above is then
``x`` itself); ``cfg.qk_norm_whole`` gives a layer of kind 0 or 1 an
RMSNorm over the whole width of ``h @ W_q`` and of ``h @ W_k`` before the
heads are split.  With both, three gated-delta-rule layers to one layer
of kind 0 and a dense MLP the block is Olmo-Hybrid-7B's (``x`` the
stream ``(T, 3840)``; 30 heads published, a chip of the two that share a
layer holds 15 of both mixers)::

    layers 0, 1, 2, 4, ... (of four, the first three):  the gated-delta-rule mixer, heads of 96 | 192, 4 taps, b in 0..2
    layers 3, 7, ...:  q = RMSNorm(x @ wq) ;  k = RMSNorm(x @ wk) ;  v = x @ wv     heads of 128 on as many kv heads
                 NO rotary ;  a = causal softmax(q k^T * 128^-1/2) v ;  mixer = a @ wo
    x1 = x + RMSNorm(mixer(x)) ;  x2 = x1 + RMSNorm((silu(x1 Wg) * (x1 Wu)) Wd)     11008 wide; a final RMSNorm, an untied head

A latent-attention model's residual
may run in ``n`` = ``cfg.hc_streams`` lanes: at one lane each sublayer
``F`` is the plain pre-norm residual ``x + r * F(RMSNorm(x))`` every
kind has; at ``n > 1`` it is wrapped by learned mixing
(:mod:`fmda_tpu.ops.hyper_connection`: ``Hpre`` reads the lanes into one
stream, ``Hpost`` writes ``F``'s output back, ``Hres`` remixes the lanes,
doubly stochastic)::

    u = sum_i Hpre[i] X[i] ;  y = F(RMSNorm(u)) ;  X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y

Scopes (docs/observability.md "Spans and scopes"): ``attention`` holds
the cores' and ``mla_proj`` (latent attention's products, norms and
rotary); ``ssm_mixer`` a state-space mixer's five; ``kda_mixer`` a
delta-rule mixer's ``kda_proj``, ``kda_conv``, ``kda_gates``,
``kda_scan`` (the walk's ``kda_intra``, ``kda_solve``, ``kda_carry``,
``kda_out``) and ``kda_out_norm``; ``gdn_mixer`` a gated-delta-rule
mixer's ``gdn_proj``, ``gdn_conv``, ``gdn_gates``, ``gdn_scan`` (the same
walk's four ``kda_*`` scopes) and ``gdn_out_norm``, and with
``post_norm`` the block's norm of its output; ``hyper_conn`` the
lanes' ``hc_coeff``, ``hc_pre``, ``hc_post_res``; ``moe_shared``;
``moe_seq_aux`` the balance term; the expert layer's and the dense MLP's
own.

``__call__`` returns the logits whole (small sizes, tests).  Training
calls :meth:`MoEDecoder.features` and takes the loss over token chunks
(:func:`fmda_tpu.train.losses.chunked_next_token_loss`), so the
``(T, vocab_size)`` logits never exist at once.
"""

from __future__ import annotations

import math
from contextlib import nullcontext
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from fmda_tpu.config import ModelConfig
from fmda_tpu.ops.attention import CORE_LSE, CORE_OUT, mha
from fmda_tpu.ops.moe import (
    ACTIVATIONS, EXPERT_OUT, expert_layer, kernel_impl, route, router_load,
    seq_balance_term)
from fmda_tpu.ops.sparse_attention import (
    PICKS, kernels_dispatch, select_keys, sparse_mha)
from fmda_tpu.ops.kda import kda_scan
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
#: Initial value of the lanes' three gains ``a_pre``, ``a_post``,
#: ``a_res``: the coefficients start nearly static (their offsets), the
#: input's part small beside them.
HC_GAIN_INIT = 0.01
#: ... and the size of the offsets' initial values (:func:`_lane_mixed`):
#: a lane is read at sigmoid(+-6) = 0.9975 / 0.0025 and the remix is the
#: identity to exp(-12).
HC_OFFSET_INIT = 6.0

#: ``layer_layout``'s value for a learned-sparse layer (:data:`KINDS` has
#: every value's meaning; these three are names other files hold).
SPARSE_LAYOUT = 2
#: ... for a state-space layer (ops/ssd.py).
SSM_LAYOUT = 3
#: ... for a latent-attention layer.
LATENT_LAYOUT = 4
#: ... and for a delta-rule layer with a decay a channel (ops/kda.py).
KDA_LAYOUT = 5
#: ... and for a delta-rule layer with one decay a head (a gated delta
#: rule; ops/kda.py with ``g`` (B, T, H)).
GDN_LAYOUT = 6
#: What is added to a head's sum of squares before the root, where a
#: delta-rule layer takes its queries and keys to unit length.
L2_NORM_EPS = 1e-6

#: The names :func:`_dense_mlp` gives its two pre-activation products,
#: ``u @ Wg`` and ``u @ Wu`` (tokens x width, compute dtype): what the
#: backward of ``act(gate) * up`` reads (``d gate = d h * up *
#: act'(gate)``, ``d up = d h * act(gate)``).  Anywhere but under a
#: policy that saves them the names are identities.
MLP_GATE = "mlp_gate_product"
MLP_UP = "mlp_up_product"

#: What a block's recomputation (``cfg.remat``) keeps from the forward
#: pass, by name; everything else it remakes from the block's input:
#: what attention's backward reads and only a second run of the core (in
#: a learned-sparse layer, of the indexer and the selection) could remake
#: (the core's output, heads x head_dim wide in the compute dtype; its
#: rows' logsumexp, a float32 a head and row or the learned-sparse
#: kernel's packed tile; a learned-sparse layer's picks, int8, T x T),
#: the expert layer's output where the lanes' mixing reads it in backward,
#: and a gated MLP's two pre-activation products, so that the replay makes
#: neither projection again and ``act(gate) * up`` is an elementwise
#: remake: ``2 x T x f x 2 B`` a call (361 MB at 8,192 tokens by 11,008)
#: for ``2 x 2 T d f`` FLOP, a kept byte saving ``hidden_size`` FLOP
#: whatever the caller.  ``act(gate) * up`` alone, at half the bytes,
#: would save nothing: its backward reads ``gate`` and ``up`` themselves,
#: so both products would still be made again.
#: A layer puts under a name what it has: one list serves every layout.
REPLAY_KEEPS = (CORE_OUT, CORE_LSE, PICKS, EXPERT_OUT, MLP_GATE, MLP_UP)


def _weight(module: nn.Module, name: str, shape: Tuple[int, ...],
            std: float = INIT_STD):
    return module.param(name, nn.initializers.normal(std), shape,
                        jnp.float32)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """``x / sqrt(mean(x^2) + eps) * scale``, in float32, in x's dtype."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def rotary_at(x: jax.Array, inv_freq) -> jax.Array:
    """Rotary over all of the last axis of ``(B, heads, T, d)`` at the
    given ``d / 2`` frequencies, positions ``0 .. T-1``, the half-split
    convention: dims ``i`` and ``i + d/2`` rotate together by ``pos *
    inv_freq[i]``; float32 angles, ``x``'s dtype."""
    t, d = x.shape[-2], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """:func:`rotary_at` the plain frequencies ``theta^(-2i/head_dim)``."""
    d = x.shape[-1]
    return rotary_at(x, theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def _uniform(low: float, high: float, then=lambda v: v):
    """An initializer: ``then`` of a uniform draw from ``[low, high)``."""
    def init(key, shape, dtype=jnp.float32):
        return then(jax.random.uniform(key, shape, dtype, low, high))
    return init


def _inverse_softplus(step: jax.Array) -> jax.Array:
    return step + jnp.log(-jnp.expm1(-step))


#: Where the recurrent mixers' published code starts them: rates
#: ``exp(a_log)`` uniform in 1..16, step sizes log-uniform in 1e-3..1e-1
#: at a zero input (the bias is the step's inverse softplus).
_RATE_INIT = _uniform(1.0, 16.0, jnp.log)
_STEP_BIAS_INIT = _uniform(math.log(1e-3), math.log(1e-1),
                           lambda u: _inverse_softplus(jnp.exp(u)))


def _unit_length(x: jax.Array) -> jax.Array:
    """``x`` over its last axis' length (a delta-rule layer's queries and
    keys a head): float32, in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(
        jnp.square(x32), -1, keepdims=True) + L2_NORM_EPS)).astype(x.dtype)


class Count(NamedTuple):
    """One thing a layer counts in a forward pass, declared once: in
    :data:`EXPERT_COUNTS` (an expert layer's) or in the row of
    :data:`KINDS` whose layers count it."""

    dtype: Any
    shape: Callable[[ModelConfig], Tuple[int, ...]] = lambda cfg: ()
    #: Which of the layers that could count it do.
    where: Callable[[ModelConfig, int], bool] = lambda cfg, layer: True
    #: A pass's value from its steps' (and a step's from its
    #: microbatches'): ``"sum"`` or ``"max"``.
    fold: str = "sum"
    #: Whether the model keeps a value a layer (axis 0) or adds them up.
    stacked: bool = True
    #: What makes the layer's value of the array its kernels counted.
    settle: Optional[Callable[[jax.Array], jax.Array]] = None


class ModelCount(NamedTuple):
    """A :class:`Count` over one configuration's layers."""

    count: Count
    shape: Tuple[int, ...]   # (layers,) + one layer's, or one layer's
    layers: Tuple[int, ...]  # the layers that count it


@jax.tree_util.register_pytree_node_class
class Counts(dict):
    """A layer's counts by name, a pytree whose leaves keep the order
    they were put in (a dict's are sorted by name): the order of a
    compiled block's outputs."""

    def tree_flatten(self):
        return tuple(self.values()), tuple(self.keys())

    @classmethod
    def tree_unflatten(cls, names, values):
        return cls(zip(names, values))


def _has_experts(cfg: ModelConfig, layer: int) -> bool:
    return cfg.moe_experts > 0 and layer >= cfg.first_dense_layers


def _split_sum(kept: jax.Array) -> jax.Array:
    """(batch, row blocks) int32 counts -> (2,) int32 ``[sum of count >>
    16, sum of count & 0xffff]``: a sequence of 16,384 tokens keeps 31 M
    keys a layer, and a pass's sum outgrows int32
    (:func:`fmda_tpu.train.tasks.keys_kept_counts` joins the halves)."""
    return jnp.stack([jnp.sum(kept >> 16), jnp.sum(kept & 0xFFFF)])


#: What an expert layer counts (:func:`feed_forward`): the pairs it
#: computed on each held expert, the held pairs it did not compute (0),
#: the row tiles that held a group and the rounds of its layout they took.
EXPERT_COUNTS: Dict[str, Count] = {
    "expert_pairs": Count(jnp.int32, lambda cfg: (cfg.experts_held[1],),
                          _has_experts),
    "dropped": Count(jnp.int32, where=_has_experts, stacked=False),
    "row_tiles_used": Count(jnp.int32, where=_has_experts),
    "layout_rounds": Count(jnp.int32, where=_has_experts),
}


def _sigmoid_router(cfg: ModelConfig, layer: int) -> bool:
    return _has_experts(cfg, layer) and cfg.moe_scoring == "sigmoid"


#: What an expert layer whose router scores by sigmoid counts beside
#: that, whatever its mixer: the pairs each of ALL the router's experts
#: received, held here or not (what the selection bias steps on), and the
#: largest size of a selection bias.
ROUTER_COUNTS: Dict[str, Count] = {
    "router_load": Count(jnp.int32, lambda cfg: (cfg.moe_experts,),
                         _sigmoid_router),
    "router_bias_absmax": Count(jnp.float32, where=_sigmoid_router,
                                fold="max"),
}


def _settled(counts: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Each of ``counts`` in its declared form."""
    return {name: COUNTS[name].settle(value) if COUNTS[name].settle
            else value for name, value in counts.items()}


def _heads(module: nn.Module, src: jax.Array, name: str, n_heads: int,
           width: int) -> jax.Array:
    """``src @ W`` as ``(B, n_heads, T, width)``."""
    b, t, d = src.shape
    y = jnp.dot(src, _weight(module, name, (d, n_heads * width))
                .astype(src.dtype))
    return y.reshape(b, t, n_heads, width).transpose(0, 2, 1, 3)


def _merged(module: nn.Module, a: jax.Array, d: int) -> jax.Array:
    """``(B, heads, T, width)`` through the output product ``wo``."""
    b, n, t, width = a.shape
    a = a.transpose(0, 2, 1, 3).reshape(b, t, n * width)
    return jnp.dot(a, _weight(module, "wo", (n * width, d)).astype(a.dtype))


def _whole_width_norm(module: nn.Module, name: str, x: jax.Array,
                      eps: float) -> jax.Array:
    """RMSNorm over ALL the heads' channels of ``x`` (B, heads, T, width)
    at once (one mean of squares a position over ``heads * width``), the
    scale ``(heads * width,)``: float32, in ``x``'s dtype."""
    _, n, _, width = x.shape
    scale = module.param(name, nn.initializers.ones, (n * width,))
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=(1, 3), keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * scale.reshape(n, 1, width)).astype(x.dtype)


def _attention_mixer(window: bool):
    """Full attention (no positional encoding, every key ``j <= i``), or
    with ``window`` rotary on q, k and keys ``0 <= i - j <
    cfg.sliding_window``; where ``cfg.qk_norm_whole`` an RMSNorm over the
    whole width of the query and of the key projection first."""
    def mixer(module: nn.Module, cfg: ModelConfig, h: jax.Array):
        n, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q, k, v = (_heads(module, h, "wq", n, hd),
                   _heads(module, h, "wk", g, hd),
                   _heads(module, h, "wv", g, hd))
        if cfg.qk_norm_whole:
            q, k = (_whole_width_norm(module, name, y, cfg.rms_norm_eps)
                    for name, y in (("q_norm", q), ("k_norm", k)))
        if window:
            with jax.named_scope("rope"):
                q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
        with jax.named_scope("attention_window" if window
                             else "attention_full"):
            a = mha(q, k, v, causal=True,
                    window=cfg.sliding_window if window else None,
                    use_flash=cfg.use_pallas, scale=cfg.attention_multiplier)
        return _merged(module, a, h.shape[-1]), {}
    return mixer


def _sparse_mixer(module: nn.Module, cfg: ModelConfig, h: jax.Array):
    """Attention over the keys a learned indexer picks (module
    docstring), and what the selection kept."""
    b, t, d = h.shape
    n, g, hd, dt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, h.dtype
    q, k, v = (_heads(module, h, "wq", n, hd), _heads(module, h, "wk", g, hd),
               _heads(module, h, "wv", g, hd))
    q, k = (rms_norm(y, module.param(name, nn.initializers.ones, (hd,)),
                     cfg.rms_norm_eps)
            for y, name in ((q, "q_norm"), (k, "k_norm")))
    with jax.named_scope("rope"):
        q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
    use_kernels = kernels_dispatch(t, n // g, hd, use_kernels=cfg.use_pallas)
    with jax.named_scope("attention_indexer"):
        # no gradient reaches the indexer (module docstring)
        h_idx = jax.lax.stop_gradient(h)
        hi, di = cfg.indexer_heads, cfg.indexer_head_dim
        q_idx = rotary(_heads(module, h_idx, "wq_idx", hi, di),
                       cfg.rope_theta)
        k_idx = rotary(_heads(module, h_idx, "wk_idx", 1, di),
                       cfg.rope_theta)[:, 0]
        w_idx = jnp.dot(h_idx, _weight(module, "ww_idx", (d, hi)).astype(dt),
                        preferred_element_type=jnp.float32)
    picked, kept = select_keys(q_idx, k_idx, w_idx, cfg.indexer_topk,
                               use_kernels=use_kernels)
    module.sow("intermediates", "picked", picked)
    a = sparse_mha(q, k, v, picked, use_kernels=use_kernels)
    return _merged(module, a, d), {
        "sparse_keys_kept": kept, "sparse_query_rows": jnp.int32(b * t)}


def _ssm_mixer(module: nn.Module, cfg: ModelConfig, h: jax.Array):
    """A state-space layer's mixer (module docstring) on the normalised
    stream ``h`` (B, T, hidden): its output (B, T, hidden) and the chunks
    and positions its scan walked.  Parameters start where the
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
            "dt_bias", _STEP_BIAS_INIT, (heads,), f32))
        rate = -jnp.exp(module.param("a_log", _RATE_INIT, (heads,), f32))
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
    return out, {"ssd_chunks": jnp.int32(b * states.shape[1]),
                 "ssd_positions": jnp.int32(b * t)}


def _kda_mixer(module: nn.Module, cfg: ModelConfig, h: jax.Array):
    """A delta-rule layer's mixer (module docstring) on the normalised
    stream ``h`` (B, T, hidden): its output (B, T, hidden), the chunks and
    positions its walk took and the largest cumulative log-decay inside a
    chunk.  Parameters start where the mechanism's published code starts
    them: rates ``exp(a_log)`` uniform in 1..16 a head, step sizes
    log-uniform in 1e-3..1e-1 a channel at a zero input, the taps uniform
    in +-1/sqrt(taps)."""
    b, t, d = h.shape
    heads, hd, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
    inner, dt, f32 = heads * hd, h.dtype, jnp.float32

    def product(x, name, width, **kw):
        return jnp.dot(x, _weight(module, name, (x.shape[-1], width))
                       .astype(dt), **kw)

    def by_head(x):
        return x.reshape(b, t, heads, hd)

    with jax.named_scope("kda_proj"):
        q, k, v = (product(h, name, inner) for name in ("wq", "wk", "wv"))
        decay = product(product(h, "wf_a", hd), "wf_b", inner,
                        preferred_element_type=f32)
        beta = product(h, "wb", heads, preferred_element_type=f32)
        gate = product(product(h, "wg_a", hd), "wg_b", inner,
                       preferred_element_type=f32)
    with jax.named_scope("kda_conv"):
        bound = taps ** -0.5
        q, k, v = (by_head(conv_silu(
            x, module.param(name, _uniform(-bound, bound), (inner, taps),
                            f32), jnp.zeros((inner,), f32), dtype=dt))
            for x, name in ((q, "conv_q"), (k, "conv_k"), (v, "conv_v")))
    with jax.named_scope("kda_gates"):
        q, k = _unit_length(q), _unit_length(k)
        step = jax.nn.softplus(by_head(decay + module.param(
            "dt_bias", _STEP_BIAS_INIT, (inner,), f32)))
        log_decay = -jnp.exp(module.param(
            "a_log", _RATE_INIT, (heads,), f32))[:, None] * step
        beta = jax.nn.sigmoid(beta)
    with jax.named_scope("kda_scan"):
        o, _, absmax = kda_scan(q, k, v, log_decay, beta, chunk=cfg.kda_chunk,
                                dtype=dt, impl=kernel_impl(cfg.use_pallas))
    with jax.named_scope("kda_out_norm"):
        o = (rms_norm(o, module.param("o_norm", nn.initializers.ones, (hd,)),
                      cfg.rms_norm_eps)
             * jax.nn.sigmoid(by_head(gate))).astype(dt)
    with jax.named_scope("kda_proj"):
        out = product(o.reshape(b, t, inner), "wo", d)
    return out, {"kda_chunks": jnp.int32(b * -(-t // cfg.kda_chunk)),
                 "kda_positions": jnp.int32(b * t),
                 "kda_log_decay_absmax": absmax}


def _gdn_mixer(module: nn.Module, cfg: ModelConfig, h: jax.Array):
    """A gated-delta-rule layer's mixer (module docstring) on the stream
    ``h`` (B, T, hidden): its output (B, T, hidden), the chunks and
    positions its walk took, the largest cumulative log-decay inside a
    chunk and the largest ``b``.  One decay and one step size a HEAD,
    keys ``gdn_key_dim`` and values ``gdn_value_dim`` wide, a full-rank
    output gate; parameters start as :func:`_kda_mixer`'s do."""
    b, t, d = h.shape
    heads, dk, dv, taps = (cfg.gdn_heads, cfg.gdn_key_dim,
                           cfg.gdn_value_dim, cfg.gdn_conv)
    dt, f32 = h.dtype, jnp.float32

    def product(name, width, **kw):
        return jnp.dot(h, _weight(module, name, (d, width)).astype(dt), **kw)

    with jax.named_scope("gdn_proj"):
        q, k = product("wq", heads * dk), product("wk", heads * dk)
        v = product("wv", heads * dv)
        step = product("wa", heads, preferred_element_type=f32)
        beta = product("wb", heads, preferred_element_type=f32)
        gate = product("wg", heads * dv, preferred_element_type=f32)
    with jax.named_scope("gdn_conv"):
        bound = taps ** -0.5
        q, k, v = (conv_silu(
            x, module.param(name, _uniform(-bound, bound),
                            (x.shape[-1], taps), f32),
            jnp.zeros((x.shape[-1],), f32), dtype=dt
        ).reshape(b, t, heads, -1)
            for x, name in ((q, "conv_q"), (k, "conv_k"), (v, "conv_v")))
    with jax.named_scope("gdn_gates"):
        q, k = _unit_length(q), _unit_length(k)
        step = jax.nn.softplus(step + module.param(
            "dt_bias", _STEP_BIAS_INIT, (heads,), f32))
        log_decay = -jnp.exp(module.param(
            "a_log", _RATE_INIT, (heads,), f32)) * step
        beta = cfg.gdn_beta_scale * jax.nn.sigmoid(beta)
    with jax.named_scope("gdn_scan"):
        o, _, absmax = kda_scan(q, k, v, log_decay, beta,
                                chunk=cfg.gdn_chunk, dtype=dt)
    with jax.named_scope("gdn_out_norm"):
        o = (rms_norm(o, module.param("o_norm", nn.initializers.ones, (dv,)),
                      cfg.rms_norm_eps)
             * jax.nn.silu(gate.reshape(b, t, heads, dv))).astype(dt)
    with jax.named_scope("gdn_proj"):
        out = jnp.dot(o.reshape(b, t, heads * dv),
                      _weight(module, "wo", (heads * dv, d)).astype(dt))
    return out, {"gdn_chunks": jnp.int32(b * -(-t // cfg.gdn_chunk)),
                 "gdn_positions": jnp.int32(b * t),
                 "gdn_log_decay_absmax": absmax,
                 "gdn_beta_max": jax.lax.stop_gradient(jnp.max(beta))}


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


def _latent_mixer(module: nn.Module, cfg: ModelConfig, h: jax.Array):
    """Latent attention (module docstring), and the causal pairs its core
    scored."""
    b, t, d = h.shape
    n, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    dt, eps = h.dtype, cfg.rms_norm_eps
    ones = nn.initializers.ones

    def heads(y, width):
        return y.reshape(b, t, n, width).transpose(0, 2, 1, 3)

    with jax.named_scope("attention"):
        with jax.named_scope("mla_proj"):
            if cfg.q_lora_rank:
                cq = rms_norm(
                    jnp.dot(h, _weight(module, "wq_a", (d, cfg.q_lora_rank))
                            .astype(dt)),
                    module.param("q_norm", ones, (cfg.q_lora_rank,)), eps)
                q = heads(jnp.dot(cq, _weight(
                    module, "wq_b", (cfg.q_lora_rank, n * (dn + dr)))
                    .astype(dt)), dn + dr)
            else:  # a direct query: one product, no latent and no norm
                q = heads(jnp.dot(h, _weight(
                    module, "wq", (d, n * (dn + dr))).astype(dt)), dn + dr)
            ckv, kr = jnp.split(
                jnp.dot(h, _weight(module, "wkv_a",
                                   (d, cfg.kv_lora_rank + dr)).astype(dt)),
                [cfg.kv_lora_rank], axis=-1)
            kv = heads(jnp.dot(
                rms_norm(ckv, module.param("kv_norm", ones,
                                           (cfg.kv_lora_rank,)), eps),
                _weight(module, "wkv_b", (cfg.kv_lora_rank, n * (dn + dv)))
                .astype(dt)), dn + dv)
            kn, v = kv[..., :dn], kv[..., dn:]
            if cfg.mla_use_nope:  # no position: the dr dims stay as made
                kr = kr[:, None]
            else:
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
            out = _merged(module, a, d)
    return out, {"latent_pairs": jnp.int32(b * (t * (t + 1) // 2))}


# -- the kinds: the one place layer_layout's integers are given meaning -------


class Kind(NamedTuple):
    """One value of ``layer_layout``: its mixer, and what else a block
    has to know of it."""

    #: ``(module, cfg, h) -> (output, counts)``: the block's module (the
    #: parameters' owner) and the normalised stream (B, T, hidden) -> the
    #: output before it joins the stream, and what it counted by name.
    mixer: Callable[[nn.Module, ModelConfig, jax.Array],
                    Tuple[jax.Array, Dict[str, jax.Array]]]
    #: The scope the mixer runs and joins the stream under; None where
    #: the mixer names its own and the join is outside it.
    scope: Optional[str]
    #: Where an expert layer's router reads.  ``"mixer"``: the mixer's
    #: normalised input (placed before attention, its top-k is known a
    #: layer's attention ahead of the experts it feeds).  ``"feed_forward"``:
    #: the feed-forward's, which the block flattens for the router apart
    #: from the experts.  None: the same values, the rows the experts
    #: compute on (one ``reshape`` fewer; each kind keeps its parent's text).
    router_reads: Optional[str]
    #: What its layers count beside an expert layer's own, in the order
    #: a model stacks them.
    counts: Dict[str, Count] = {}
    #: ``cfg -> [(field and what it must be, whether it is)]``: its rows
    #: of :func:`check_decoder_config`.
    rules: Callable[[ModelConfig], list] = lambda cfg: []


def _sparse_rules(cfg: ModelConfig) -> list:
    why = "layer_layout has a learned-sparse layer"
    return [
        (f"indexer_topk ({why})", cfg.indexer_topk > 0),
        (f"indexer_heads ({why})", cfg.indexer_heads > 0),
        (f"indexer_head_dim (even, for rotary; {why})",
         cfg.indexer_head_dim > 0 and cfg.indexer_head_dim % 2 == 0),
        ("n_heads / n_kv_heads / head_dim (a learned-sparse layer's "
         "kernels take a group that divides 128 and heads of at most 512)",
         not (cfg.use_pallas and cfg.n_kv_heads > 0)
         or (128 % max(cfg.n_heads // cfg.n_kv_heads, 1) == 0
             and cfg.head_dim <= 512))]


def _ssm_rules(cfg: ModelConfig) -> list:
    return [(f"{name} (layer_layout has a state-space layer)",
             getattr(cfg, name) > 0)
            for name in ("ssm_heads", "ssm_head_dim", "ssm_state",
                         "ssm_conv", "ssm_chunk")]


def _latent_rules(cfg: ModelConfig) -> list:
    why = " (layer_layout has a latent-attention layer)"
    return [(name + why, getattr(cfg, name) > 0) for name in (
        "kv_lora_rank", "qk_nope_head_dim", "v_head_dim")] + [
        ("q_lora_rank (0: a direct query; or the latent's width)" + why,
         cfg.q_lora_rank >= 0),
        ("qk_rope_head_dim (even, for rotary)" + why,
         cfg.qk_rope_head_dim > 0 and cfg.qk_rope_head_dim % 2 == 0),
        ("attention_multiplier / residual_multiplier (a latent-attention "
         "layer states its own score scale and joins the stream unscaled)",
         cfg.attention_multiplier is None and cfg.residual_multiplier == 1.0)]


def _kda_rules(cfg: ModelConfig) -> list:
    return [(f"{name} (layer_layout has a delta-rule layer)",
             getattr(cfg, name) > 0)
            for name in ("kda_heads", "kda_head_dim", "kda_conv",
                         "kda_chunk")]


def _gdn_rules(cfg: ModelConfig) -> list:
    return [(f"{name} (layer_layout has a gated-delta-rule layer)",
             getattr(cfg, name) > 0)
            for name in ("gdn_heads", "gdn_key_dim", "gdn_value_dim",
                         "gdn_conv", "gdn_chunk", "gdn_beta_scale")]


KINDS: Dict[int, Kind] = {
    0: Kind(_attention_mixer(window=False), "attention", "mixer"),
    1: Kind(_attention_mixer(window=True), "attention", "mixer"),
    SPARSE_LAYOUT: Kind(_sparse_mixer, "attention", "feed_forward", {
        # the keys kept, as two halves, and the query rows they were kept
        # for
        "sparse_keys_kept": Count(jnp.int32, lambda cfg: (2,),
                                  settle=_split_sum),
        "sparse_query_rows": Count(jnp.int32)}, _sparse_rules),
    SSM_LAYOUT: Kind(_ssm_mixer, "ssm_mixer", "mixer", {
        # what the scan walked
        "ssd_chunks": Count(jnp.int32),
        "ssd_positions": Count(jnp.int32)}, _ssm_rules),
    LATENT_LAYOUT: Kind(_latent_mixer, None, None, {
        # such a model's lanes: the largest distance of a row or column
        # sum of a residual mixing matrix from one; its cores: the causal
        # pairs each scored
        "hc_sum_error": Count(
            jnp.float32, where=lambda cfg, layer: cfg.hc_streams > 1,
            fold="max"),
        "latent_pairs": Count(jnp.int32)}, _latent_rules),
    KDA_LAYOUT: Kind(_kda_mixer, "kda_mixer", None, {
        # what the walk took, and the largest |G| inside a chunk: how far
        # past float32's 87 an exponent the chunked form never takes
        # (ops/kda.py) would have gone
        "kda_chunks": Count(jnp.int32),
        "kda_positions": Count(jnp.int32),
        "kda_log_decay_absmax": Count(jnp.float32, fold="max")},
        _kda_rules),
    GDN_LAYOUT: Kind(_gdn_mixer, "gdn_mixer", None, {
        # as kind 5's, and the largest ``b`` a pass saw: over 1 where the
        # correction overshoots
        "gdn_chunks": Count(jnp.int32),
        "gdn_positions": Count(jnp.int32),
        "gdn_log_decay_absmax": Count(jnp.float32, fold="max"),
        "gdn_beta_max": Count(jnp.float32, fold="max")}, _gdn_rules),
}

#: Every declared count by name, whatever the configuration.
COUNTS: Dict[str, Count] = {**EXPERT_COUNTS, **ROUTER_COUNTS, **{
    name: count for kind in KINDS.values()
    for name, count in kind.counts.items()}}


def model_counts(cfg: ModelConfig) -> Dict[str, ModelCount]:
    """THE declaration: the counts a forward pass of ``cfg``'s model
    returns, by name, in the order :meth:`MoEDecoder.features` makes
    them.  The model fills and stacks by it; the task (train/tasks.py
    ``NextToken``) zeroes, folds and publishes by it.  No model is traced."""
    depth = range(len(cfg.layer_layout))
    groups = [(EXPERT_COUNTS, list(depth)), (ROUTER_COUNTS, list(depth))] + [
        (KINDS[code].counts, [i for i in depth if cfg.layer_layout[i] == code])
        for code in sorted(set(cfg.layer_layout)) if code in KINDS]
    declared = {}
    for counts, candidates in groups:
        for name, count in counts.items():
            layers = tuple(i for i in candidates if count.where(cfg, i))
            if layers:
                stack = (len(depth),) if count.stacked else ()
                declared[name] = ModelCount(
                    count, stack + tuple(count.shape(cfg)), layers)
    return declared


#: The loss terms a layer may add to what training differentiates beside
#: the next-token loss, by name: which layers have it.  A layer returns a
#: term's value a sequence, (B,) float32, WITH its gradient path (a count
#: has none); the model stacks them a layer, (layers, B), among what
#: :meth:`MoEDecoder.features` returns; the task (train/tasks.py
#: ``NextToken``) adds them to the objective, folds and publishes their
#: values like counts, and leaves them out of a validation pass's loss.
TERMS: Dict[str, Callable[[ModelConfig, int], bool]] = {
    # an expert layer's balance term a sequence (ops/moe.py
    # seq_balance_term), already times cfg.moe_seq_aux_alpha
    "seq_aux_loss": lambda cfg, layer: (
        cfg.moe_seq_aux_alpha > 0 and _has_experts(cfg, layer)),
}


def model_terms(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    """The loss terms ``cfg``'s model declares, by name: the layers that
    have each (:data:`TERMS`; beside :func:`model_counts`, and like it
    without tracing a model)."""
    depth = range(len(cfg.layer_layout))
    found = {name: tuple(i for i in depth if where(cfg, i))
             for name, where in TERMS.items()}
    return {name: layers for name, layers in found.items() if layers}


def _dense_mlp(module: nn.Module, cfg: ModelConfig, u: jax.Array,
               width: Optional[int] = None,
               names: Tuple[str, str, str] = ("w_gate", "w_up", "w_down"),
               scope: str = "dense_mlp"):
    """``(act(u @ Wg) * (u @ Wu)) @ Wd`` on the normalised stream,
    ``cfg.ffn_size`` wide unless a ``width`` is stated (a shared expert's,
    under its own leaves' ``names`` and its own ``scope``)."""
    d, f, dt = u.shape[-1], width or cfg.ffn_size, u.dtype
    with jax.named_scope(scope):
        gate = checkpoint_name(
            jnp.dot(u, _weight(module, names[0], (d, f)).astype(dt)), MLP_GATE)
        up = checkpoint_name(
            jnp.dot(u, _weight(module, names[1], (d, f)).astype(dt)), MLP_UP)
        return jnp.dot(ACTIVATIONS[cfg.hidden_act](gate) * up,
                       _weight(module, names[2], (f, d)).astype(dt))


def routing(module: nn.Module, cfg: ModelConfig, y: jax.Array):
    """``(gates, experts)`` of the stream or rows ``y`` (..., hidden),
    and the selection bias: the model's one router call, as ``cfg``
    scores, biases and scales; with the scores of all experts third where
    the model declares the balance term that reads them."""
    bias = None
    if cfg.moe_bias_rate > 0:
        bias = module.param("router_bias", nn.initializers.zeros,
                            (cfg.moe_experts,), jnp.float32)
    d = y.shape[-1]
    return route(
        y.reshape(-1, d), _weight(module, "router", (d, cfg.moe_experts)),
        cfg.moe_top_k, scoring=cfg.moe_scoring, bias=bias,
        scale=cfg.moe_routed_scaling,
        with_scores=cfg.moe_seq_aux_alpha > 0), bias


def feed_forward(module: nn.Module, cfg: ModelConfig, u: jax.Array, *,
                 dense: bool, routed=None, counted: Dict[str, jax.Array],
                 load: bool = False):
    """A layer's feed-forward on the normalised stream ``u`` (B, T,
    hidden): the dense gated MLP (``dense``, or a model without experts),
    or the router, the held experts and ``cfg.moe_shared_experts`` shared
    ones.  ``routed``: :func:`routing`'s answer where the block asked
    already.  Returns the output and all the layer counted: ``counted``
    (the mixer's, each now in its declared form), :data:`EXPERT_COUNTS`
    and, with ``load`` (:data:`ROUTER_COUNTS`), the load on all experts
    and the bias's size;
    among them, under its name, the balance term a sequence where the
    model declares it (:data:`TERMS`: a value with a gradient path)."""
    if dense or not cfg.moe_experts:
        return _dense_mlp(module, cfg, u), _settled(counted)
    b, t, d = u.shape
    f = cfg.moe_ffn_size
    first, count = cfg.experts_held
    flat = u.reshape(b * t, d)
    (gates, experts, *scores), bias = routed or routing(module, cfg, flat)
    m, laid = expert_layer(
        flat, gates, experts,
        _weight(module, "w_gate", (count, d, f)),
        _weight(module, "w_up", (count, d, f)),
        _weight(module, "w_down", (count, f, d)),
        experts_held=(first, count), impl=kernel_impl(cfg.use_pallas),
        act=cfg.hidden_act, n_experts=cfg.moe_experts)
    if cfg.moe_shared_experts:
        shared = _dense_mlp(
            module, cfg, flat, cfg.moe_shared_experts * f,
            ("ws_gate", "ws_up", "ws_down"), "moe_shared")
        with jax.named_scope("moe_shared"):
            m = (m.astype(jnp.float32) + shared.astype(jnp.float32)
                 ).astype(u.dtype)
    # EXPERT_COUNTS, under their names
    counts = dict(_settled(counted), **laid._asdict())
    if load:
        counts["router_bias_absmax"] = (
            jnp.zeros((), jnp.float32) if bias is None
            else jnp.max(jnp.abs(bias)))
    out = m.reshape(b, t, d)
    if load:
        counts["router_load"] = router_load(experts, cfg.moe_experts)
    if scores:
        counts["seq_aux_loss"] = seq_balance_term(
            scores[0], experts, b, cfg.moe_seq_aux_alpha)
    return out, counts


def _lane_mixed(module: nn.Module, cfg: ModelConfig, name: str,
                x: jax.Array, fn, impl: str):
    """``hc.around`` with the sublayer ``name``'s mixing parameters: the
    lanes after ``fn`` inside its mixing, ``fn``'s counts, and ``Hres``.
    The offsets start so that it reads lane 0 (``Hpre`` near one-hot),
    writes it with weight one (``2 sigmoid(0)``), remixes by the identity."""
    n, d = x.shape[2], x.shape[3]
    const, size = nn.initializers.constant, HC_OFFSET_INIT
    gain, first = const(HC_GAIN_INIT), np.arange(n) == 0
    b_pre, b_post, b_res = (
        np.where(mask, high, -size).astype(np.float32) for mask, high in (
            (first, size), (first, 0.0), (np.eye(n, dtype=bool), size)))
    from fmda_tpu.ops import hyper_connection as hc

    return hc.around(
        fn, x,
        _weight(module, f"hc_{name}_p_pre", (n * d, n)),
        _weight(module, f"hc_{name}_p_post", (n * d, n)),
        _weight(module, f"hc_{name}_p_res", (n * d, n * n)),
        tuple(module.param(f"hc_{name}_a_{k}", gain, (), jnp.float32)
              for k in ("pre", "post", "res")),
        (module.param(f"hc_{name}_b_pre", const(b_pre), (n,)),
         module.param(f"hc_{name}_b_post", const(b_post), (n,)),
         module.param(f"hc_{name}_b_res", const(b_res), (n, n))),
        norm_eps=cfg.rms_norm_eps, iters=cfg.hc_sinkhorn_iters,
        eps=cfg.hc_eps, clamp=cfg.hc_res_clamp, impl=impl)


class DecoderBlock(nn.Module):
    """One layer: the mixer of ``KINDS[layout]`` and the feed-forward (the
    dense MLP where ``dense``), each a pre-norm sublayer of the residual.
    Returns the stream and what the layer counted, by name.  A module of
    its own so that ``nn.remat`` wraps it whole when ``cfg.remat``."""

    cfg: ModelConfig
    layout: int
    dense: bool = False

    @nn.compact
    def __call__(self, x: jax.Array):
        cfg, kind = self.cfg, KINDS[self.layout]
        declared = model_counts(cfg)
        d, dt = x.shape[-1], x.dtype
        lanes = cfg.hc_streams > 1
        experts = cfg.moe_experts > 0 and not self.dense
        sum_errors = []
        if lanes:
            # imported where a model has lanes: its kernels' module would
            # cost every other model a second of start-up
            from fmda_tpu.ops import hyper_connection as hc

            hc_impl = hc.backward_impl(  # what differentiates the mixing
                kernel_impl(cfg.use_pallas), d, x.shape[1])

        def sublayer(x, name, ln, fn, scope=None):
            """``x`` after the sublayer ``fn`` (normalised stream ->
            output, what it counted), and those counts: ``x + r * fn(
            RMSNorm(x))`` at one lane, inside the lanes' mixing at more;
            where ``cfg.post_norm`` the norm sits on the sublayer's
            output, ``x + r * RMSNorm(fn(x))``."""
            scale = self.param(ln, nn.initializers.ones, (d,))

            def normed(u):
                if cfg.post_norm:
                    y, counts = fn(u)
                    with jax.named_scope(scope) if scope else nullcontext():
                        return rms_norm(y, scale, cfg.rms_norm_eps), counts
                return fn(rms_norm(u, scale, cfg.rms_norm_eps))

            if lanes:
                x, counts, res = _lane_mixed(self, cfg, name, x, normed,
                                             hc_impl)
                with jax.named_scope("hyper_conn"):
                    sum_errors.append(hc.sum_error(res))
                return x, counts
            y, counts = normed(x)
            with jax.named_scope(scope) if scope else nullcontext():
                if cfg.residual_multiplier == 1.0:
                    return x + y, counts
                return (x.astype(jnp.float32) + cfg.residual_multiplier
                        * y.astype(jnp.float32)).astype(dt), counts

        routed = None

        def mix(h):
            nonlocal routed
            if experts and kind.router_reads == "mixer":
                routed = routing(self, cfg, h)
            with jax.named_scope(kind.scope) if kind.scope else nullcontext():
                return kind.mixer(self, cfg, h)

        def feed(u):
            nonlocal routed
            if experts and kind.router_reads == "feed_forward":
                routed = routing(self, cfg, u)
            return feed_forward(
                self, cfg, u, dense=self.dense, routed=routed,
                counted=counted, load="router_load" in declared)

        x, counted = sublayer(x, "attn", "ln_attn", mix, kind.scope)
        x, counts = sublayer(x, "ffn", "ln_moe" if experts else "ln_mlp",
                             feed)
        # a layer answers for what the model declares of the feed-forward
        # and of its own kind, each in its declared form (feed_forward
        # settles the mixer's, the expert layer's come as declared): zeros
        # where it did not count (a dense layer of a model with experts)
        # -- of the declared shape, so that the layers' values stack
        for name, count in {**EXPERT_COUNTS, **ROUTER_COUNTS,
                            **kind.counts}.items():
            if name in declared and name not in counts:
                counts[name] = jnp.zeros(count.shape(cfg), count.dtype)
        if sum_errors:
            counts["hc_sum_error"] = jax.lax.stop_gradient(
                jnp.max(jnp.stack(sum_errors)))
        # ... and for the loss terms it declares: a dense layer of a model
        # whose expert layers have one adds nothing, a sequence
        terms = [(name, counts[name] if name in counts
                  else jnp.zeros(x.shape[:1], jnp.float32))
                 for name in model_terms(cfg)]
        return x, Counts([(name, counts[name]) for name in declared
                          if name in counts] + terms)


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
        block_cls = DecoderBlock
        if cfg.remat:
            block_cls = nn.remat(
                DecoderBlock,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *REPLAY_KEEPS))
        self.blocks = [
            block_cls(cfg, int(layout), i < cfg.first_dense_layers,
                      name=f"block_{i}")
            for i, layout in enumerate(cfg.layer_layout)]
        self.ln_final = self.param("ln_final", nn.initializers.ones, (d,))
        if not cfg.tie_embeddings:
            self.head = _weight(self, "head", (d, cfg.vocab_size))

    def features(self, ids: jax.Array
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """ids (B, T) int32 -> the final norm's output (B, T, hidden) in
        the compute dtype, and what the layers counted: an array a name
        of :func:`model_counts`, in its order and shapes; then each loss
        term of :func:`model_terms`, (layers, B) float32 with its
        gradient path."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = jnp.take(self.embed, ids, axis=0)
            if cfg.embedding_multiplier != 1.0:
                x = x * cfg.embedding_multiplier
            x = x.astype(jnp.dtype(cfg.dtype))
        declared = {name: c.count for name, c in model_counts(cfg).items()}
        # a count's values a layer, or their running sum where they add up
        kept = {name: [] if count.stacked
                else [jnp.zeros(count.shape(cfg), count.dtype)]
                for name, count in declared.items()}
        terms = {name: [] for name in model_terms(cfg)}
        if cfg.hc_streams > 1:
            # every lane starts as the token's row
            x = jnp.broadcast_to(
                x[:, :, None, :], x.shape[:2] + (cfg.hc_streams, x.shape[-1]))
        for block in self.blocks:
            x, counts = block(x)
            for name, count in declared.items():
                # zeros from a layer of another kind than the one counting
                value = counts[name] if name in counts else jnp.zeros(
                    count.shape(cfg), count.dtype)
                kept[name] = (kept[name] + [value] if count.stacked
                              else [kept[name][0] + value])
            for name in terms:
                terms[name].append(counts[name])
        if cfg.hc_streams > 1:
            with jax.named_scope("hyper_conn"):  # the lanes leave as one
                x = jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)
        x = rms_norm(x, self.ln_final, cfg.rms_norm_eps)
        return x, {
            **{name: jnp.stack(values) if declared[name].stacked
               else values[0] for name, values in kept.items()},
            **{name: jnp.stack(values) for name, values in terms.items()}}

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


def _feed_forward_rules(cfg: ModelConfig, self_sized: bool) -> list:
    """``self_sized``: the model's layers are of kinds 4 and 5, whose
    feed-forward may state what the rows marked so state."""
    first, count = cfg.experts_held
    dense = cfg.moe_experts == 0
    sigmoid = cfg.moe_scoring == "sigmoid"
    only_those = " (a model of layers of kinds 4 and 5)"
    return [
        ("ffn_size (moe_experts is 0 or first_dense_layers is not: a "
         "dense gated MLP)",
         not (dense or cfg.first_dense_layers > 0) or cfg.ffn_size > 0),
        ("first_dense_layers (0 .. the depth; more than 0 with experts"
         + only_those + ")",
         0 <= cfg.first_dense_layers <= len(cfg.layer_layout)
         and (cfg.first_dense_layers == 0 or (self_sized and not dense))),
        ("moe_shared_experts (more than 0 with experts" + only_those + ")",
         cfg.moe_shared_experts >= 0
         and (cfg.moe_shared_experts == 0 or (self_sized and not dense))),
        ("moe_scoring (softmax, or sigmoid" + only_those + ")",
         cfg.moe_scoring == "softmax" or (sigmoid and self_sized)),
        ("moe_routed_scaling (positive; other than 1 with sigmoid scores)",
         cfg.moe_routed_scaling > 0
         and (cfg.moe_routed_scaling == 1.0 or sigmoid)),
        ("moe_bias_rate (0, or positive with sigmoid scores)",
         cfg.moe_bias_rate == 0 or (cfg.moe_bias_rate > 0 and sigmoid)),
        ("moe_seq_aux_alpha (0, or positive with experts under a plain "
         "residual" + only_those + ")",
         cfg.moe_seq_aux_alpha == 0 or (
             cfg.moe_seq_aux_alpha > 0 and self_sized and not dense
             and cfg.hc_streams == 1)),
        ("moe_experts / moe_top_k",
         dense or 0 < cfg.moe_top_k <= cfg.moe_experts),
        ("moe_ffn_size", dense or cfg.moe_ffn_size > 0),
        ("experts_held (first, count) inside moe_experts",
         dense or (count > 0 and first >= 0
                   and first + count <= cfg.moe_experts)),
        ("hidden_act (one of %s)" % "/".join(sorted(ACTIVATIONS)),
         cfg.hidden_act in ACTIVATIONS)]


def check_decoder_config(cfg: ModelConfig) -> None:
    """Refuse a decoder configuration that leaves a size unset or
    inconsistent, naming the field: the rows every model answers (the
    residual's among them), those of each kind of layer it has
    (:data:`KINDS`) and the feed-forward's."""
    # layers of kinds 4 and 5 state their heads' widths themselves
    own_widths = {LATENT_LAYOUT, KDA_LAYOUT}
    self_sized = bool(own_widths & set(cfg.layer_layout))
    lanes = cfg.hc_streams > 1
    present = [KINDS[v] for v in sorted(set(cfg.layer_layout)) if v in KINDS]
    rules = [
        ("vocab_size", cfg.vocab_size > 0),
        ("head_dim", self_sized or cfg.head_dim > 0),
        ("n_kv_heads (must divide n_heads)", self_sized or (
            cfg.n_kv_heads > 0 and cfg.n_heads % cfg.n_kv_heads == 0)),
        ("layer_layout (one of 0/1/2/3/6 per layer, or of 4/5 in every "
         "layer)",
         len(cfg.layer_layout) > 0
         and all(v in KINDS for v in cfg.layer_layout)
         and (not self_sized or set(cfg.layer_layout) <= own_widths)),
        ("rope_factor (at least 1) / rope_original_max (positive where "
         "the factor stretches)",
         cfg.rope_factor >= 1 and (cfg.rope_factor == 1
                                   or cfg.rope_original_max > 0)),
        ("attention_multiplier (None or positive)",
         cfg.attention_multiplier is None or cfg.attention_multiplier > 0),
        ("head_dim (even, for rotary)", cfg.head_dim % 2 == 0),
        ("sliding_window", cfg.sliding_window > 0),
        ("hc_streams (1, or more lanes (a latent-attention model's))",
         cfg.hc_streams == 1 or (
             lanes and self_sized and KDA_LAYOUT not in cfg.layer_layout)),
        ("hc_sinkhorn_iters (hc_streams is more than 1)",
         not lanes or cfg.hc_sinkhorn_iters > 0),
        ("hc_eps / hc_res_clamp (positive; hc_streams is more than 1)",
         not lanes or (cfg.hc_eps > 0 and cfg.hc_res_clamp > 0)),
        ("post_norm (a plain residual's: hc_streams is 1)",
         not (cfg.post_norm and lanes)),
        ("qk_norm_whole (read by layers of kinds 0 and 1)",
         not cfg.qk_norm_whole or bool({0, 1} & set(cfg.layer_layout))),
        ("embedding_multiplier / residual_multiplier / logits_scaling "
         "(not zero)",
         cfg.embedding_multiplier != 0 and cfg.residual_multiplier != 0
         and cfg.logits_scaling != 0),
    ] + [rule for kind in present for rule in kind.rules(cfg)
         ] + _feed_forward_rules(cfg, self_sized)
    problems = list(dict.fromkeys(name for name, ok in rules if not ok))
    if problems:
        raise ValueError(
            "ModelConfig(cell='decoder') needs: " + "; ".join(problems))
