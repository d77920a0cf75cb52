"""Operations of a latent-attention decoder under a plain residual
(latent attention with the query's product as the configuration states
it, direct or through a latent; shared experts beside the routed ones;
dense and expert layers in one model; a per-sequence balance term on the
router's scores), from their shapes and from what the program counted:
the counting functions behind ``mla_train_mfu``.

The accepted count (``harness/latent_decoder_flops.py``) with two
changes.  **The query's product** is ``d x n (dn + dr)`` where
``q_lora_rank`` is 0 (Moonlight: 2 x 2048 x 3072 = 12.6 MFLOP a token
and layer forward, which that file's ``d * q_lora_rank + q_lora_rank *
n * dq`` reads as 0) and the two latent products otherwise: the accepted
functions plus that one product (:func:`direct_query_flops_fwd_per_token`).
**No lane mixing**: at one lane the accepted count has no coefficient
product.  The cores are that file's
own functions (the same kernels, the same widths), imported.

Matrix multiplications only, as every decoder count here (norms,
softmaxes, sigmoids and rotary are VPU work beside them), and a backward
pass costs twice its forward; attention's backward recomputes the scores
(2.5 forwards).  **The balance term is such VPU work**: its operations
(:func:`balance_ops_fwd_per_token`) are given for scale and are not part
of the utilization's count.  Operations that recomputation repeats are
never counted.
"""

from __future__ import annotations

from benchmark.harness import latent_decoder_flops as accepted
from benchmark.harness.latent_decoder_flops import (  # noqa: F401
    core_flops_fwd, cores_bytes_step, cores_flops_step, dense_layers,
    expert_layers)


def direct_query_flops_fwd_per_token(mc) -> float:
    """What the accepted count misses, a token and layer: the direct
    query's product ``d x n (dn + dr)`` where ``q_lora_rank`` is 0 (the
    accepted ``d * r + r * n * dq`` reads 0 there); 0 through a latent."""
    if mc.q_lora_rank:
        return 0.0
    return 2.0 * mc.hidden_size * mc.n_heads * (
        mc.qk_nope_head_dim + mc.qk_rope_head_dim)


def projection_flops_fwd_per_token(mc) -> float:
    """The query's product, the two key-value products and the output
    product of one layer."""
    return (accepted.projection_flops_fwd_per_token(mc)
            + direct_query_flops_fwd_per_token(mc))


def balance_ops_fwd_per_token(mc) -> float:
    """Elementwise operations of one expert layer's balance term a token,
    forward: the scores' sum and quotient and their running mean over
    all ``E`` experts (3 E), and the choice's count (one compare and one
    add a chosen expert and router output, ``K E``).  VPU work: beside
    the layer's matrix products (about 1e8 a token) it is 1e-5 of them."""
    if not getattr(mc, "moe_seq_aux_alpha", 0.0):
        return 0.0
    return float(3 * mc.moe_experts + 2 * mc.moe_top_k * mc.moe_experts)


def forward_flops_per_token(mc, seq: int, pairs_per_token: float) -> float:
    """Analytic forward operations a token of a ``seq``-token sequence:
    the accepted count (every layer's projections and core over the mean
    causal span; the dense layers' MLP; the expert layers' router, shared
    experts and held routed experts for ``pairs_per_token`` held pairs a
    layer; the head over the held vocabulary; no coefficient product at
    one lane) and every layer's direct query product."""
    return (accepted.forward_flops_per_token(mc, seq, pairs_per_token)
            + len(mc.layer_layout) * direct_query_flops_fwd_per_token(mc))


def train_flops_per_sequence(mc, seq: int, pairs_per_token: float) -> float:
    """Forward + backward of one sequence: three forwards, the cores
    three and a half (recomputed operations do not count)."""
    return (accepted.train_flops_per_sequence(mc, seq, pairs_per_token)
            + 3.0 * seq * len(mc.layer_layout)
            * direct_query_flops_fwd_per_token(mc))
