"""Operations and bytes of the decoder family's layers, from their
shapes and from what the program counted — the counting functions behind
``moe_experts_roofline``, ``attention_roofline`` and ``moe_train_mfu``.

Matrix multiplications only (norms, softmaxes, rotary and the ReGLU's
elementwise work are VPU noise beside them).  A backward pass costs twice
its forward for a product ``y = x @ w`` (``dx`` and ``dw``); attention's
backward recomputes the scores, so it is 2.5 forwards (five block
products for two).  Operations that recomputation (``remat``) repeats
are never counted: a roofline share and an MFU hold the *required* work
against the time all of the work took.
"""

from __future__ import annotations

from typing import Sequence


def visible_pairs(seq: int, window) -> int:
    """(query, key) pairs a causal layer scores over ``seq`` positions:
    key j visible to query i iff ``0 <= i - j < window`` (no window: the
    whole triangle, diagonal included)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    # the first `window` queries see i + 1 keys, the rest `window`
    return window * (window + 1) // 2 + (seq - window) * window


def attention_core_flops_fwd(seq: int, n_heads: int, head_dim: int,
                             window) -> float:
    """``q k^T`` and ``p v`` over the visible band, all query heads:
    ``pairs * heads * head_dim * 4``."""
    return 4.0 * visible_pairs(seq, window) * n_heads * head_dim


def attention_core_flops_step(seq: int, n_heads: int, head_dim: int,
                              window, layouts: Sequence[int]) -> float:
    """Forward + backward (3.5 forwards) of the attention cores of every
    layer of one sequence; layout 1 is windowed, 0 full."""
    return 3.5 * sum(
        attention_core_flops_fwd(seq, n_heads, head_dim,
                                 window if layout else None)
        for layout in layouts)


def attention_core_bytes_step(seq: int, n_heads: int, n_kv_heads: int,
                              head_dim: int, n_layers: int,
                              itemsize: int = 2) -> float:
    """The least traffic of the cores: q and o once each way, k and v
    once, forward and backward (q, k, v, o, do read; dq, dk, dv
    written), per layer."""
    q = seq * n_heads * head_dim * itemsize
    kv = seq * n_kv_heads * head_dim * itemsize
    fwd = 2 * q + 2 * kv
    bwd = 4 * q + 4 * kv
    return float(n_layers * (fwd + bwd))


def expert_flops_step(pairs: float, hidden: int, ffn: int) -> float:
    """The grouped products of ``pairs`` (token, expert) pairs, forward +
    backward: three products of ``2 * hidden * ffn`` a pair forward
    (``pairs * 6 * hidden * ffn``), twice that backward."""
    return 3.0 * pairs * 6.0 * hidden * ffn


def expert_bytes_step(pairs: float, experts_held: int, hidden: int,
                      ffn: int, n_layers: int, itemsize: int = 2) -> float:
    """The least traffic of the grouped products over ``n_layers``
    layers' worth of ``pairs`` in all: every held expert's three matrices
    read once forward and once backward in the compute dtype and their
    float32 gradients written once; each pair's row read and written at
    each product's two ends (hidden in, 2 x ffn between, hidden out),
    forward and backward."""
    weights = n_layers * experts_held * 3 * hidden * ffn
    rows = pairs * (2 * hidden + 3 * ffn)
    return float(weights * (2 * itemsize + 4) + 2 * rows * itemsize * 2)


def forward_flops_per_token(mc, seq: int, pairs_per_token: float) -> float:
    """Analytic forward operations a token of a ``seq``-token sequence:
    projections, router, the attention cores over the mean visible band,
    the held experts' products for ``pairs_per_token`` pairs a layer,
    and the head over the held vocabulary."""
    n_layers = len(mc.layer_layout)
    proj = 2.0 * mc.hidden_size * (
        2 * mc.n_heads * mc.head_dim + 2 * mc.n_kv_heads * mc.head_dim)
    router = 2.0 * mc.hidden_size * mc.moe_experts
    cores = sum(
        attention_core_flops_fwd(seq, mc.n_heads, mc.head_dim,
                                 mc.sliding_window if layout else None)
        for layout in mc.layer_layout) / seq
    experts = pairs_per_token * 6.0 * mc.hidden_size * mc.moe_ffn_size
    head = 2.0 * mc.hidden_size * mc.vocab_size
    return n_layers * (proj + router + experts) + cores + head


def train_flops_per_sequence(mc, seq: int, pairs_per_token: float) -> float:
    """Forward + backward of one sequence: three forwards (recomputed
    operations do not count)."""
    return 3.0 * seq * forward_flops_per_token(mc, seq, pairs_per_token)
