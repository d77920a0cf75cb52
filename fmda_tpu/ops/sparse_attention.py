"""Learned-sparse attention: an indexer picks the keys a query sees.

Every other attention layer of the framework decides which keys a query
sees by position (the causal triangle, a causal window).  Here a small
*indexer* scores every key of a query's causal past, and the heads
attend over the ``topk`` best-scored keys alone, one set for all heads::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        s <= t
    S_t     = the min(t + 1, topk) keys s <= t with the largest I[t, s]
              (ties to the lower s)
    a_t     = softmax_{s in S_t}(q_t . k_s / sqrt(D)) v_s

The top-k is piecewise constant, so nothing here sends a gradient to the
indexer's inputs: :func:`select_keys` is integer-valued and
:func:`sparse_mha` differentiates in q, k, v under a fixed mask.

Two paths, one result.  On a TPU with ``use_kernels`` the three steps
are the kernels of :mod:`fmda_tpu.ops.pallas_sparse_attention`; anywhere
else they are blockwise ``jax.numpy`` (scores and selection a block of
query rows at a time, attention through :func:`fmda_tpu.ops.attention.
mha`'s masked path), which is what the CPU tests and
``use_pallas=False`` run.  Both select by the same counting bisection
(:func:`kth_largest_mask`), exact and without a sort.  The (T, T) float32
scores never exist at once: the kernel path scores and ranks
:data:`SCORE_CHUNK` query rows at a time.

Scopes (docs/observability.md "Spans and scopes"): ``attention_indexer``
(the scores), ``attention_select`` (the top-k), ``attention_sparse``
(attention over the picked keys, forward and backward).  The caller
computes the indexer's three projections under ``attention_indexer``
too.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from fmda_tpu.ops.attention import FALLBACK_QUERY_BLOCK, mha

#: The name :func:`select_keys` gives its mask: a recomputation whose
#: policy saves it (:data:`fmda_tpu.models.decoder.REPLAY_KEEPS`) runs
#: neither the indexer nor the selection a second time.
PICKS = "attention_picks"

#: Query rows whose scores against every key exist at a time on the
#: kernel path: 2,048 x 16,384 float32 is 134 MB.
SCORE_CHUNK = 2048
_INT_MIN = -(2 ** 31)


def sortable_key(x: jax.Array) -> jax.Array:
    """float32 -> int32 whose signed order is the floats' order, with
    ``-0.0`` and ``+0.0`` one key."""
    x = jnp.where(x == 0.0, 0.0, x)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def kth_largest_mask(scores: jax.Array, rows: jax.Array, topk: int
                     ) -> jax.Array:
    """``scores`` (R, T) float32 of the queries at positions ``rows``
    (R,) -> (R, T) bool: the ``min(t + 1, topk)`` largest scores among
    each row's keys ``s <= t``, ties to the lower ``s``.

    No sort.  The scores become int32 keys of the same order; the k-th
    largest key is built bit by bit from the top (a candidate stands if
    at least k keys reach it: 32 counting passes), and of the keys equal
    to it the lowest columns are taken, up to the column that makes the
    count exact (found the same way, a pass a bit of the position)."""
    t = scores.shape[-1]
    col = jnp.arange(t, dtype=jnp.int32)[None, :]
    row = rows.astype(jnp.int32)[:, None]
    int_min = jnp.int32(_INT_MIN)
    key = jnp.where(col <= row, sortable_key(scores), int_min)
    want = jnp.minimum(row + 1, topk)

    def count(hit):
        return jnp.sum(hit, axis=-1, keepdims=True, dtype=jnp.int32)

    def key_bit(i, prefix):
        # offset binary: its unsigned order is the keys' signed order
        cand = prefix | jnp.left_shift(jnp.int32(1), 31 - i)
        return jnp.where(count(key >= (cand ^ int_min)) >= want, cand, prefix)

    tau = jax.lax.fori_loop(0, 32, key_bit, jnp.zeros_like(row)) ^ int_min
    short = want - count(key > tau)
    bits = max(t - 1, 1).bit_length()

    def col_bit(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        below = count((key == tau) & (col < cand))
        return jnp.where(below < short, cand, prefix)

    last_tie = jax.lax.fori_loop(0, bits, col_bit, jnp.zeros_like(row))
    return (key > tau) | ((key == tau) & (col <= last_tie))


def index_scores_block(q_idx: jax.Array, k_idx: jax.Array, w_idx: jax.Array
                       ) -> jax.Array:
    """``q_idx`` (Hi, R, Di), ``k_idx`` (T, Di), ``w_idx`` (R, Hi)
    float32 -> (R, T) float32: the products in the inputs' dtype with
    float32 accumulation, relu, weight and sum over heads in float32."""
    s = jnp.einsum("hqd,kd->hqk", q_idx, k_idx,
                   preferred_element_type=jnp.float32)
    return jnp.sum(w_idx.T[:, :, None] * jax.nn.relu(s), axis=0)


def _select_jnp(q_idx, k_idx, w_idx, topk: int) -> jax.Array:
    """One sequence: (Hi, T, Di), (T, Di), (T, Hi) -> (T, T) int8, and
    the keys kept a block of rows, (blocks,) int32."""
    t = k_idx.shape[0]

    def block(q_blk, w_blk, rows):
        with jax.named_scope("attention_indexer"):
            scores = index_scores_block(q_blk, k_idx, w_blk)
        with jax.named_scope("attention_select"):
            picked = kth_largest_mask(scores, rows, topk)
            return picked.astype(jnp.int8), jnp.sum(picked, dtype=jnp.int32)

    blk = FALLBACK_QUERY_BLOCK
    rows = jnp.arange(t, dtype=jnp.int32)
    if t <= blk or t % blk:
        picked, kept = block(q_idx, w_idx, rows)
        return picked, kept[None]
    n = t // blk
    picked, kept = jax.lax.map(
        lambda xs: block(*xs),
        (jnp.moveaxis(q_idx.reshape(q_idx.shape[0], n, blk, -1), 1, 0),
         w_idx.reshape(n, blk, -1), rows.reshape(n, blk)))
    return picked.reshape(t, t), kept


def _select_kernels(q_idx, k_idx, w_idx, topk: int, interpret: bool
                    ) -> jax.Array:
    from fmda_tpu.ops import pallas_sparse_attention as kernels

    b, hi, t, di = q_idx.shape
    chunk = min(SCORE_CHUNK, t)
    n = t // chunk

    def one_chunk(xs):
        q_chunk, w_chunk, row0 = xs
        with jax.named_scope("attention_indexer"):
            scores = kernels.index_scores(
                q_chunk, k_idx, w_chunk, row0, interpret=interpret)
        with jax.named_scope("attention_select"):
            picked = kernels.select_topk(scores, row0, topk,
                                         interpret=interpret)
            return picked, jnp.sum(picked, axis=(1, 2), dtype=jnp.int32)

    picked, kept = jax.lax.map(one_chunk, (
        jnp.moveaxis(q_idx.reshape(b, hi, n, chunk, di), 2, 0),
        jnp.moveaxis(w_idx.reshape(b, n, chunk, hi), 1, 0),
        (jnp.arange(n, dtype=jnp.int32) * chunk)[:, None]))
    return jnp.moveaxis(picked, 0, 1).reshape(b, t, t), kept.T


def kernels_dispatch(seq_len: int, group: int, d_head: int, *,
                     use_kernels: bool) -> bool:
    """Whether the kernel path serves this shape here (a TPU backend,
    the kernels' envelope, whole score chunks)."""
    if not use_kernels or jax.default_backend() != "tpu":
        return False
    from fmda_tpu.ops import pallas_sparse_attention as kernels

    return (kernels.sparse_supported(seq_len, group, d_head)
            and seq_len % min(SCORE_CHUNK, seq_len) == 0)


def select_keys(q_idx: jax.Array, k_idx: jax.Array, w_idx: jax.Array,
                topk: int, *, use_kernels: bool = False,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """The keys each query attends over, as a mask, and their count.

    ``q_idx`` (B, Hi, T, Di) and ``k_idx`` (B, T, Di) in the compute
    dtype (one indexer key head), ``w_idx`` (B, T, Hi) float32.  Returns
    (B, T, T) int8, 1 where key ``s`` is one of query ``t``'s picks, and
    (B, blocks) int32, the keys kept in each block of query rows (a
    whole sequence's count need not fit a float32's 24 bits, and a
    batch's not an int32: the caller sums as it sees fit).
    ``use_kernels`` is the caller's dispatch decision
    (:func:`kernels_dispatch`); ``interpret`` runs the kernels under the
    Pallas interpreter (the tests)."""
    q_idx, k_idx, w_idx = (jax.lax.stop_gradient(x)
                           for x in (q_idx, k_idx, w_idx))
    w_idx = w_idx.astype(jnp.float32)
    if use_kernels or interpret:
        picked, kept = _select_kernels(q_idx, k_idx, w_idx, topk, interpret)
    else:
        picked, kept = jax.vmap(
            lambda q, k, w: _select_jnp(q, k, w, topk))(q_idx, k_idx, w_idx)
    return checkpoint_name(picked, PICKS), kept


def sparse_mha(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
               *, use_kernels: bool = False, interpret: bool = False
               ) -> jax.Array:
    """Attention of q (B, N, T, D) over the keys of k/v (B, G, T, D)
    that ``mask`` (B, T, T) marks (:func:`select_keys`), the same keys
    for every head.  Differentiable in q, k, v."""
    with jax.named_scope("attention_sparse"):
        if use_kernels or interpret:
            from fmda_tpu.ops import pallas_sparse_attention as kernels

            return kernels.sparse_attention(q, k, v, mask,
                                            interpret=interpret)
        return mha(q, k, v, mask=(mask != 0)[:, None])
