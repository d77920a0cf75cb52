"""Operations and bytes of a hybrid decoder's layers (state-space layers
and attention layers, a dense gated MLP, a tied head), from their shapes
and from what the program counted: the counting functions behind
``ssd_scan_roofline``, ``hybrid_attention_roofline`` and
``hybrid_train_mfu``.

Matrix multiplications only, as ``harness/moe_decoder_flops.py`` counts
(norms, softmaxes, softplus, the gate's and the convolution's
elementwise work are VPU noise beside them), and a backward pass costs
twice its forward; attention's backward recomputes the scores (2.5
forwards).  **The scan is counted in its published chunked form at the
configuration's chunk, over the causal pairs inside a chunk, whatever
implements it**: a program that multiplies whole ``chunk x chunk``
blocks and masks, or walks the sequence another way, is held to the same
count.  Operations that recomputation repeats are never counted.
"""

from __future__ import annotations

from benchmark.harness.moe_decoder_flops import (
    attention_core_bytes_step, attention_core_flops_fwd)

#: ``layer_layout``'s value for a state-space layer.
SSM_LAYOUT = 3


def chunk_pairs(chunk: int) -> int:
    """(i, j) pairs with ``j <= i`` inside one chunk."""
    return chunk * (chunk + 1) // 2


def scan_flops_fwd(positions: float, heads: int, head_dim: int, state: int,
                   chunk: int) -> float:
    """The chunked scan's four products over ``positions`` positions (one
    layer): ``C B^T`` over a chunk's causal pairs (one group: once, not a
    head), the pairs' weights against ``d * xs`` a head, a chunk's end
    state (``B^T`` against the decayed ``d * xs``) and the carried state
    against ``C``, each ``2 x head_dim x state`` a position and head."""
    chunks = positions / chunk
    pairs = chunks * chunk_pairs(chunk)
    return (2.0 * pairs * state + 2.0 * pairs * heads * head_dim
            + 4.0 * positions * heads * head_dim * state)


def scan_flops_step(positions: float, heads: int, head_dim: int, state: int,
                    chunk: int) -> float:
    """Forward + backward: three forwards."""
    return 3.0 * scan_flops_fwd(positions, heads, head_dim, state, chunk)


def scan_bytes_step(positions: float, heads: int, head_dim: int, state: int,
                    chunk: int, itemsize: int = 2) -> float:
    """The least traffic of the scan over ``positions`` positions of one
    layer: ``xs``, ``B``, ``C`` read in the compute dtype and the step
    sizes in float32, ``y`` written in float32, a chunk's carried state
    (float32) written once and read once; backward reads what forward
    read and ``dy``, reads the states again, and writes a gradient for
    each input: three times the forward's."""
    inner = heads * head_dim
    fwd = positions * ((inner + 2 * state) * itemsize + heads * 4
                       + inner * 4) \
        + 2.0 * (positions / chunk) * inner * state * 4
    return 3.0 * fwd


def forward_flops_per_token(mc, seq: int) -> float:
    """Analytic forward operations a token of a ``seq``-token sequence:
    every layer's projections and MLP, the scans, the attention cores
    over the causal triangle, and the tied head over the held
    vocabulary."""
    d, inner = mc.hidden_size, mc.ssm_heads * mc.ssm_head_dim
    mlp = 6.0 * d * mc.ffn_size
    total = 2.0 * d * mc.vocab_size
    for layout in mc.layer_layout:
        if layout == SSM_LAYOUT:
            total += 2.0 * d * (2 * inner + 2 * mc.ssm_state + mc.ssm_heads)
            total += 2.0 * inner * d
            total += scan_flops_fwd(seq, mc.ssm_heads, mc.ssm_head_dim,
                                    mc.ssm_state, mc.ssm_chunk) / seq
        else:
            total += 2.0 * d * (2 * mc.n_heads + 2 * mc.n_kv_heads) \
                * mc.head_dim
            total += attention_core_flops_fwd(
                seq, mc.n_heads, mc.head_dim, None) / seq
        total += mlp
    return total


def attention_layers(mc) -> int:
    return sum(1 for layout in mc.layer_layout if layout != SSM_LAYOUT)


def attention_cores_flops_step(mc, seq: int) -> float:
    """Forward + backward (3.5 forwards) of the full-attention cores of
    one sequence."""
    return 3.5 * attention_layers(mc) * attention_core_flops_fwd(
        seq, mc.n_heads, mc.head_dim, None)


def attention_cores_bytes_step(mc, seq: int) -> float:
    return attention_core_bytes_step(
        seq, mc.n_heads, mc.n_kv_heads, mc.head_dim, attention_layers(mc))


def train_flops_per_sequence(mc, seq: int) -> float:
    """Forward + backward of one sequence: three forwards, the attention
    cores three and a half (recomputed operations do not count)."""
    cores_fwd = attention_layers(mc) * attention_core_flops_fwd(
        seq, mc.n_heads, mc.head_dim, None)
    return 3.0 * seq * forward_flops_per_token(mc, seq) + 0.5 * cores_fwd
