"""Operations and bytes of a latent-attention decoder's layers (latent
attention, a hyper-connected residual stream, a shared expert beside the
routed ones, dense and expert layers in one model), from their shapes
and from what the program counted: the counting functions behind
``mla_core_roofline``, ``hc_mix_roofline`` and ``latent_train_mfu``.

Matrix multiplications only, as ``harness/moe_decoder_flops.py`` counts
(norms, softmaxes, sigmoids, rotary, Sinkhorn's turns and the lanes'
elementwise mixes are VPU work beside them), and a backward pass costs
twice its forward; attention's backward recomputes the scores (2.5
forwards).  The core is counted over the causal pairs at a score width
of ``qk_nope_head_dim + qk_rope_head_dim`` and a value width of
``v_head_dim``: a program that pads either is held to the same count.
**The hyper-connection is counted by the bytes of the mechanism,
whatever implements it**: a sublayer reads the stream once (the
coefficient product and the read share that pass) and writes it once,
forward; backward reads the stream and the incoming gradient and writes
the stream's gradient.  Operations and traffic that recomputation
repeats are never counted.
"""

from __future__ import annotations

from benchmark.harness.moe_decoder_flops import visible_pairs


def dense_layers(mc) -> int:
    return mc.first_dense_layers if mc.moe_experts else len(mc.layer_layout)


def expert_layers(mc) -> int:
    return len(mc.layer_layout) - dense_layers(mc)


def core_flops_fwd(seq: int, mc) -> float:
    """``q k^T`` over the score width and ``p v`` over the value width,
    all heads, over the causal triangle of one layer and sequence."""
    width = mc.qk_nope_head_dim + mc.qk_rope_head_dim + mc.v_head_dim
    return 2.0 * visible_pairs(seq, None) * mc.n_heads * width


def cores_flops_step(mc, seq: int) -> float:
    """Forward + backward (3.5 forwards) of every layer's core."""
    return 3.5 * len(mc.layer_layout) * core_flops_fwd(seq, mc)


def cores_bytes_step(mc, seq: int, itemsize: int = 2) -> float:
    """The least traffic of the cores: q, k (one rotary key for all the
    heads: counted once), v and o once each way forward; backward reads
    q, k, v, o, do and writes dq, dk, dv."""
    dq = mc.qk_nope_head_dim + mc.qk_rope_head_dim
    q = seq * mc.n_heads * dq * itemsize
    k = seq * (mc.n_heads * mc.qk_nope_head_dim + mc.qk_rope_head_dim) \
        * itemsize
    v = seq * mc.n_heads * mc.v_head_dim * itemsize
    fwd = q + k + 2 * v           # q, k, v read; o written
    bwd = 2 * q + 2 * k + 4 * v   # q, k, v, o, do read; dq, dk, dv written
    return float(len(mc.layer_layout) * (fwd + bwd))


def projection_flops_fwd_per_token(mc) -> float:
    """The four latent products and the output product of one layer."""
    d, n = mc.hidden_size, mc.n_heads
    dq = mc.qk_nope_head_dim + mc.qk_rope_head_dim
    return 2.0 * (
        d * mc.q_lora_rank + mc.q_lora_rank * n * dq
        + d * (mc.kv_lora_rank + mc.qk_rope_head_dim)
        + mc.kv_lora_rank * n * (mc.qk_nope_head_dim + mc.v_head_dim)
        + n * mc.v_head_dim * d)


def mixing_flops_fwd_per_token(mc) -> float:
    """One sublayer's coefficient product: ``n * d`` against ``n + n +
    n * n`` outputs."""
    n = mc.hc_streams
    if n <= 1:
        return 0.0
    return 2.0 * n * mc.hidden_size * (2 * n + n * n)


def mixing_flops_step(mc, seq: int) -> float:
    """Two sublayers a layer, forward + backward."""
    return 3.0 * seq * 2 * len(mc.layer_layout) \
        * mixing_flops_fwd_per_token(mc)


def mixing_bytes_step(mc, seq: int, itemsize: int = 2) -> float:
    """The least traffic of the mechanism over one sequence: a sublayer
    reads the ``n``-lane stream once and writes it once forward (the
    sublayer's own input and output, one lane wide, beside them);
    backward reads the stream and the written stream's gradient and
    writes the stream's gradient (and the one-lane gradients)."""
    n = mc.hc_streams
    if n <= 1:
        return 0.0
    lanes = seq * n * mc.hidden_size * itemsize
    one = seq * mc.hidden_size * itemsize
    fwd = 2 * lanes + 2 * one
    bwd = 3 * lanes + 2 * one
    return float(2 * len(mc.layer_layout) * (fwd + bwd))


def forward_flops_per_token(mc, seq: int, pairs_per_token: float) -> float:
    """Analytic forward operations a token of a ``seq``-token sequence:
    every layer's projections, core over the mean causal span and two
    coefficient products; the dense layers' MLP; the expert layers'
    router, shared expert and held routed experts for
    ``pairs_per_token`` held pairs a layer; the head over the held
    vocabulary."""
    d = mc.hidden_size
    layers = len(mc.layer_layout)
    total = 2.0 * d * mc.vocab_size
    total += layers * (projection_flops_fwd_per_token(mc)
                       + core_flops_fwd(seq, mc) / seq
                       + 2 * mixing_flops_fwd_per_token(mc))
    total += dense_layers(mc) * 6.0 * d * mc.ffn_size
    total += expert_layers(mc) * (
        2.0 * d * mc.moe_experts
        + 6.0 * d * mc.moe_shared_experts * mc.moe_ffn_size
        + pairs_per_token * 6.0 * d * mc.moe_ffn_size)
    return total


def train_flops_per_sequence(mc, seq: int, pairs_per_token: float) -> float:
    """Forward + backward of one sequence: three forwards, the cores
    three and a half (recomputed operations do not count)."""
    return (3.0 * seq * forward_flops_per_token(mc, seq, pairs_per_token)
            + 0.5 * len(mc.layer_layout) * core_flops_fwd(seq, mc))
