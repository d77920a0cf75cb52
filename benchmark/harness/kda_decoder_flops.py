"""Operations and bytes of a decoder whose layers mix a delta rule with
a decay a channel (Kimi Delta Attention) and latent attention, under a
sigmoid router with a shared expert, from their shapes and from what the
program counted: the counting functions behind ``kda_scan_roofline``,
``kda_attention_roofline`` and ``kda_train_mfu``.

Matrix multiplications only, as every decoder count here (norms,
sigmoids, softplus, the L2 norms, the convolutions' four taps and the
decays' exponentials are VPU work beside them), and a backward pass costs
twice its forward; attention's backward recomputes the scores (2.5
forwards).  **The delta rule is counted in its published chunked form at
the configuration's chunk, over the causal pairs inside a chunk,
whatever implements it** (:func:`scan_flops_fwd`): a program that
multiplies whole ``chunk x chunk`` blocks and masks, takes the pairwise
decays on the vector unit, or walks the sequence another way, is held to
the same count; **the unit-lower-triangular solve is counted as its
products** (forward substitution: a row against the rows before it).
**The latent core is counted over the layers of kind 4 only**: the
accepted ``harness/latent_decoder_flops.py`` counts one for every entry
of ``layer_layout``, which is this model's five where it has one.
Operations that recomputation repeats are never counted.
"""

from __future__ import annotations

from benchmark.harness.latent_decoder_flops import (  # noqa: F401
    core_flops_fwd, cores_bytes_step, dense_layers, expert_layers)
from benchmark.harness.mla_decoder_flops import (
    projection_flops_fwd_per_token as latent_projection_flops_fwd_per_token)

#: ``layer_layout``'s values for a latent and for a delta-rule layer.
LATENT_LAYOUT = 4
KDA_LAYOUT = 5


def kda_layers(mc) -> int:
    return sum(1 for v in mc.layer_layout if v == KDA_LAYOUT)


def latent_layers(mc) -> int:
    return sum(1 for v in mc.layer_layout if v == LATENT_LAYOUT)


def chunk_pairs(chunk: int) -> int:
    """(i, j) pairs with ``j <= i`` inside one chunk."""
    return chunk * (chunk + 1) // 2


def scan_flops_fwd(positions: float, heads: int, head_dim: int, chunk: int
                   ) -> float:
    """The chunked delta rule's products over ``positions`` positions of
    one layer (``K`` = ``V`` = ``head_dim``), a head: ``k k^T`` and ``q
    k^T`` with the pairwise decays over a chunk's causal pairs (2 K a
    pair each); the solve's forward substitution against the ``V + K``
    columns of ``[v | k exp(G)]`` (2 (V + K) a pair); the intra-chunk
    output ``B u`` (2 V a pair); and against the carried state ``w S``,
    ``(q exp(G)) S`` and the state's update ``k^T u`` (2 K V a position
    each)."""
    k = v = head_dim
    pairs = positions / chunk * chunk_pairs(chunk)
    a_pair = 2.0 * k + 2.0 * k + 2.0 * (v + k) + 2.0 * v
    return heads * (pairs * a_pair + positions * 3 * 2.0 * k * v)


def scan_flops_step(positions: float, heads: int, head_dim: int, chunk: int
                    ) -> float:
    """Forward + backward: three forwards."""
    return 3.0 * scan_flops_fwd(positions, heads, head_dim, chunk)


def scan_bytes_step(positions: float, heads: int, head_dim: int, chunk: int,
                    itemsize: int = 2) -> float:
    """The least traffic of the walk over ``positions`` positions of one
    layer: ``q``, ``k``, ``v`` read in the compute dtype, the log-decays
    (a channel) and the correction's weight (a head) in float32, ``o``
    written in float32, a chunk's carried state (float32) written once
    and read once; backward reads what forward read and ``do``, reads the
    states again, and writes a gradient for each input: three times the
    forward's."""
    inner = heads * head_dim
    fwd = positions * (3 * inner * itemsize + inner * 4 + heads * 4
                       + inner * 4) \
        + 2.0 * (positions / chunk) * inner * head_dim * 4
    return 3.0 * fwd


def kda_projection_flops_fwd_per_token(mc) -> float:
    """One delta-rule layer's products a token: q, k, v and the output
    ``d x H dk`` each, the two low-rank pairs ``d x dk + dk x H dk``, and
    the correction's weight ``d x H``."""
    d, inner, hd = mc.hidden_size, mc.kda_heads * mc.kda_head_dim, \
        mc.kda_head_dim
    return 2.0 * (4 * d * inner + 2 * (d * hd + hd * inner)
                  + d * mc.kda_heads)


def attention_cores_flops_step(mc, seq: int) -> float:
    """Forward + backward (3.5 forwards) of the latent layers' cores."""
    return 3.5 * latent_layers(mc) * core_flops_fwd(seq, mc)


def attention_cores_bytes_step(mc, seq: int, itemsize: int = 2) -> float:
    """The least traffic of the latent layers' cores: what
    ``latent_decoder_flops.cores_bytes_step`` counts for every entry of
    ``layer_layout`` (q, k, the one shared key once, v and o once each
    way forward; backward reads q, k, v, o, do and writes dq, dk, dv),
    for the layers of kind 4."""
    return (cores_bytes_step(mc, seq, itemsize) * latent_layers(mc)
            / len(mc.layer_layout))


def forward_flops_per_token(mc, seq: int, pairs_per_token: float) -> float:
    """Analytic forward operations a token of a ``seq``-token sequence:
    each delta-rule layer's projections and chunked walk; each latent
    layer's projections and its core over the mean causal span; the
    dense layers' MLP; the expert layers' router, shared expert and held
    routed experts for ``pairs_per_token`` held pairs a layer; the head
    over the held vocabulary."""
    d = mc.hidden_size
    total = 2.0 * d * mc.vocab_size
    total += kda_layers(mc) * (
        kda_projection_flops_fwd_per_token(mc)
        + scan_flops_fwd(seq, mc.kda_heads, mc.kda_head_dim, mc.kda_chunk)
        / seq)
    total += latent_layers(mc) * (
        latent_projection_flops_fwd_per_token(mc)
        + core_flops_fwd(seq, mc) / seq)
    total += dense_layers(mc) * 6.0 * d * mc.ffn_size
    total += expert_layers(mc) * (
        2.0 * d * mc.moe_experts
        + 6.0 * d * mc.moe_shared_experts * mc.moe_ffn_size
        + pairs_per_token * 6.0 * d * mc.moe_ffn_size)
    return total


def train_flops_per_sequence(mc, seq: int, pairs_per_token: float) -> float:
    """Forward + backward of one sequence: three forwards, the latent
    cores three and a half (recomputed operations do not count)."""
    return (3.0 * seq * forward_flops_per_token(mc, seq, pairs_per_token)
            + 0.5 * latent_layers(mc) * core_flops_fwd(seq, mc))
