"""Operations and bytes of a learned-sparse decoder layer, from its
shapes and from what the program counted — the counting functions behind
``sparse_indexer_roofline``, ``sparse_select_roofline``,
``sparse_attention_roofline`` and ``sparse_train_mfu``.

Matrix multiplications only, as ``harness/moe_decoder_flops.py`` counts
them (a backward pass twice its forward, attention's backward 2.5
forwards, recomputed operations never counted), with two differences
that are the layer's own: the attention cores are counted over the
**picked** (query, key) pairs, whatever the kernel computes (a dense
masked pass therefore reads its true, lower share), and the indexer is
counted **once**, forward only: the top-k is piecewise constant, no
gradient reaches it, it has no backward.
"""

from __future__ import annotations

from benchmark.harness.moe_decoder_flops import (  # noqa: F401
    attention_core_bytes_step, expert_bytes_step, expert_flops_step)


def causal_pairs(seq: int) -> int:
    """(query, key) pairs with ``s <= t``: what the indexer scores."""
    return seq * (seq + 1) // 2


def picked_pairs(seq: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)``: the pairs attention keeps."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def indexer_score_flops(seq: int, heads: int, head_dim: int) -> float:
    """``qI[t, j] . kI[s]`` over the causal pairs and the indexer's
    heads: ``pairs * heads * head_dim * 2``, one layer, forward (there
    is no backward)."""
    return 2.0 * causal_pairs(seq) * heads * head_dim


def indexer_score_bytes(seq: int, heads: int, head_dim: int,
                        itemsize: int = 2) -> float:
    """The least traffic of one layer's scores: the indexer's queries,
    key and weights read once, a float32 score written a causal pair."""
    return float(seq * heads * head_dim * itemsize
                 + seq * head_dim * itemsize + seq * heads * 4
                 + causal_pairs(seq) * 4)


def select_bytes(seq: int) -> float:
    """The least traffic of one layer's selection: the scores read once
    (4 B a causal pair), the picks written once (a mask, 1 B a causal
    pair).  There are no products: the bound is memory's."""
    return float(causal_pairs(seq) * 5)


def sparse_core_flops_fwd(seq: int, topk: int, n_heads: int,
                          head_dim: int) -> float:
    """``q k^T`` and ``p v`` over the picked pairs, all query heads."""
    return 4.0 * picked_pairs(seq, topk) * n_heads * head_dim


def sparse_core_flops_step(seq: int, topk: int, n_heads: int,
                           head_dim: int) -> float:
    """Forward + backward (3.5 forwards) of one layer's cores."""
    return 3.5 * sparse_core_flops_fwd(seq, topk, n_heads, head_dim)


def indexer_flops_per_sequence(mc, seq: int) -> float:
    """One layer's indexer, whole: its three projections and its scores,
    forward only."""
    width = (mc.indexer_heads * mc.indexer_head_dim + mc.indexer_head_dim
             + mc.indexer_heads)
    return (2.0 * seq * mc.hidden_size * width
            + indexer_score_flops(seq, mc.indexer_heads,
                                  mc.indexer_head_dim))


def forward_flops_per_token(mc, seq: int, pairs_per_token: float) -> float:
    """Forward operations a token that a backward pass repeats twice:
    projections, router, the cores over the picked pairs, the held
    experts' products for ``pairs_per_token`` pairs a layer, the head
    over the held vocabulary.  The indexer is not in it
    (:func:`indexer_flops_per_sequence`)."""
    n_layers = len(mc.layer_layout)
    proj = 2.0 * mc.hidden_size * (
        2 * mc.n_heads * mc.head_dim + 2 * mc.n_kv_heads * mc.head_dim)
    router = 2.0 * mc.hidden_size * mc.moe_experts
    cores = sparse_core_flops_fwd(
        seq, mc.indexer_topk, mc.n_heads, mc.head_dim) / seq
    experts = pairs_per_token * 6.0 * mc.hidden_size * mc.moe_ffn_size
    head = 2.0 * mc.hidden_size * mc.vocab_size
    return n_layers * (proj + router + cores + experts) + head


def train_flops_per_sequence(mc, seq: int, pairs_per_token: float) -> float:
    """Forward + backward of one sequence: three forwards of everything
    but the indexer, which runs once a layer."""
    return (3.0 * seq * forward_flops_per_token(mc, seq, pairs_per_token)
            + len(mc.layer_layout) * indexer_flops_per_sequence(mc, seq))
