"""Operations and bytes of a decoder whose layers mix a gated delta rule
(one decay a head, keys ``K`` and values ``V`` of different widths) with
full attention, a dense gated MLP in every layer and an untied head, from
their shapes and from what the program counted: the counting functions
behind ``gdn_scan_roofline``, ``gdn_attention_roofline`` and
``gdn_train_mfu``.

Matrix multiplications only, as every decoder count here (norms,
sigmoids, softplus, the L2 norms, the convolutions' four taps and the
decays' exponentials are VPU work beside them), and a backward pass costs
twice its forward; attention's backward recomputes the scores (2.5
forwards).  **The delta rule is counted in its chunked form with a
scalar decay at the configuration's chunk, over the causal pairs inside
a chunk, whatever implements it** (:func:`scan_flops_fwd`): a program
that multiplies whole ``chunk x chunk`` blocks and masks, or walks the
sequence another way, is held to the same count; **the
unit-lower-triangular solve is counted as its products** (forward
substitution: a row against the rows before it).  **The attention core
is counted over the layers of kind 0 only.**  Operations that
recomputation repeats are never counted.
"""

from __future__ import annotations

from benchmark.harness.moe_decoder_flops import (
    attention_core_bytes_step, attention_core_flops_fwd)

#: ``layer_layout``'s value for a gated-delta-rule layer.
GDN_LAYOUT = 6


def gdn_layers(mc) -> int:
    return sum(1 for v in mc.layer_layout if v == GDN_LAYOUT)


def attention_layers(mc) -> int:
    return sum(1 for v in mc.layer_layout if v != GDN_LAYOUT)


def chunk_pairs(chunk: int) -> int:
    """(i, j) pairs with ``j <= i`` inside one chunk."""
    return chunk * (chunk + 1) // 2


def scan_flops_fwd(positions: float, heads: int, key_dim: int,
                   value_dim: int, chunk: int) -> float:
    """The chunked delta rule's products over ``positions`` positions of
    one layer, a head: ``k k^T`` and ``q k^T`` over a chunk's causal
    pairs (2 K a pair each; the scalar decays multiply the ``(C, C)``
    results); the solve's forward substitution against the ``V + K``
    columns of ``[v | k exp(G)]`` (2 (V + K) a pair); the intra-chunk
    output ``B u`` (2 V a pair); and against the carried state ``w S``,
    ``(q exp(G)) S`` and the state's update ``k^T u`` (2 K V a position
    each)."""
    k, v = key_dim, value_dim
    pairs = positions / chunk * chunk_pairs(chunk)
    a_pair = 2.0 * k + 2.0 * k + 2.0 * (v + k) + 2.0 * v
    return heads * (pairs * a_pair + positions * 3 * 2.0 * k * v)


def scan_flops_step(positions: float, heads: int, key_dim: int,
                    value_dim: int, chunk: int) -> float:
    """Forward + backward: three forwards."""
    return 3.0 * scan_flops_fwd(positions, heads, key_dim, value_dim, chunk)


def scan_bytes_step(positions: float, heads: int, key_dim: int,
                    value_dim: int, chunk: int, itemsize: int = 2) -> float:
    """The least traffic of the walk over ``positions`` positions of one
    layer: ``q``, ``k``, ``v`` read in the compute dtype, the log-decay
    and the correction's weight (a head each) in float32, ``o`` written
    in float32, a chunk's carried state (float32) written once and read
    once; backward reads what forward read and ``do``, reads the states
    again, and writes a gradient for each input: three times the
    forward's."""
    fwd = positions * heads * ((2 * key_dim + value_dim) * itemsize
                               + 2 * 4 + value_dim * 4) \
        + 2.0 * (positions / chunk) * heads * key_dim * value_dim * 4
    return 3.0 * fwd


def gdn_projection_flops_fwd_per_token(mc) -> float:
    """One delta-rule layer's products a token: q and k ``d x H K``, v,
    the output gate and the output ``d x H V``, the decay's and the
    correction's weights ``d x H``."""
    heads = mc.gdn_heads
    return 2.0 * mc.hidden_size * (
        2 * heads * mc.gdn_key_dim + 3 * heads * mc.gdn_value_dim
        + 2 * heads)


def attention_projection_flops_fwd_per_token(mc) -> float:
    return 2.0 * mc.hidden_size * (2 * mc.n_heads + 2 * mc.n_kv_heads) \
        * mc.head_dim


def core_flops_fwd(mc, seq: int) -> float:
    """One attention layer's core over the causal triangle."""
    return attention_core_flops_fwd(seq, mc.n_heads, mc.head_dim, None)


def attention_cores_flops_step(mc, seq: int) -> float:
    """Forward + backward (3.5 forwards) of the attention layers' cores
    of one sequence."""
    return 3.5 * attention_layers(mc) * core_flops_fwd(mc, seq)


def attention_cores_bytes_step(mc, seq: int) -> float:
    return attention_core_bytes_step(
        seq, mc.n_heads, mc.n_kv_heads, mc.head_dim, attention_layers(mc))


def forward_flops_per_token(mc, seq: int) -> float:
    """Analytic forward operations a token of a ``seq``-token sequence:
    each delta-rule layer's projections and chunked walk; each attention
    layer's projections and its core over the mean causal span; every
    layer's MLP; the head over the held vocabulary."""
    d = mc.hidden_size
    total = 2.0 * d * mc.vocab_size
    total += gdn_layers(mc) * (
        gdn_projection_flops_fwd_per_token(mc)
        + scan_flops_fwd(seq, mc.gdn_heads, mc.gdn_key_dim,
                         mc.gdn_value_dim, mc.gdn_chunk) / seq)
    total += attention_layers(mc) * (
        attention_projection_flops_fwd_per_token(mc)
        + core_flops_fwd(mc, seq) / seq)
    total += len(mc.layer_layout) * 6.0 * d * mc.ffn_size
    return total


def train_flops_per_sequence(mc, seq: int) -> float:
    """Forward + backward of one sequence: three forwards, the attention
    cores three and a half (recomputed operations do not count)."""
    return (3.0 * seq * forward_flops_per_token(mc, seq)
            + 0.5 * attention_layers(mc) * core_flops_fwd(mc, seq))
