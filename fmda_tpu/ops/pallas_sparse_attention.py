"""Pallas TPU kernels of learned-sparse attention: index scores, an exact
per-query top-k as a mask, and attention over the picked keys alone.

A learned-sparse layer (:mod:`fmda_tpu.ops.sparse_attention`) lets a
small *indexer* choose, for every query, the ``topk`` keys of its causal
past that the heads then attend over.  Three kernels, each under its own
scope in the caller:

- ``sparse_index`` — ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``
  for one chunk of query rows against all keys, blocks above the causal
  diagonal skipped.  Grid ``(B, rows / bq, T / bk)``; per block one
  ``(bq, Di) x (Di, bk)`` product an indexer head, float32 accumulation,
  the relu, the weight and the sum over heads on the VPU.
- ``sparse_select`` — for ``rows`` query rows at a time, the exact
  ``min(t + 1, topk)`` largest scores of each row's causal prefix, ties
  to the lower key, written as an int8 mask.  No sort: the scores become
  order-preserving int32 keys in VMEM, the k-th largest key is found bit
  by bit (32 counting passes over the prefix), the keys equal to it are
  cut at the position that makes the count exact (one more counting pass
  a bit of the position).  A pass is a compare and an add an element, and
  it walks only the column tiles at or below the row block's diagonal.
- ``sparse_fwd`` / ``sparse_bwd`` — the flash recurrence of
  :mod:`fmda_tpu.ops.pallas_attention` with the mask in place of a rule
  of position.  The picks of a learned indexer fall anywhere in the
  prefix, so no block below the diagonal is empty and none is skipped:
  this is a dense causal pass that zeroes the pairs not picked (4.3x the
  picked pairs' products at 16,384 tokens and 2,048 keys; PERF.md
  section 7 has what a gathered pass would cost instead).  The query
  heads of one key-value head ride one grid step together
  (``(group, bq, D)`` query block), so a key, value and mask block is
  fetched once a group.  ``lse`` and ``delta`` ride as ``(T, 128)``
  tiles whose lane ``l`` holds head ``l // (128 / group)``.

One backward kernel, five products a block and head.  ``dk`` / ``dv``
sum over query blocks and ``dq`` over key blocks, so one sweep keeps only
one of them in a block-sized scratch, and a backward in two sweeps makes
``q k^T``, ``do v^T``, the ``exp`` and the mask's select twice (seven
products for five).  ``sparse_bwd`` walks query blocks outside and key
blocks inside (grid ``(key-value heads, T / bq, T / bk)``): ``dq`` sits in
a ``(group, bq, D)`` scratch for the query block's key blocks, and the
group's ``dk`` and ``dv`` *for the whole sequence* sit in two ``(T, D)``
float32 scratches, added to at the rows of key block ``ki`` query block
by query block, head by head (the order a sweep over query blocks under
a fixed key block would sum them in).  That fits because the heads of a
group share one key-value head: 8 MB each at 16,384 x 128, where ``dq``
resident for the sequence would be ``group`` times that (64 MB a
key-value head, the whole of ``_VMEM_LIMIT``), and per-key-block partial
``dq`` s summed afterwards would be 32 x 268 MB a layer in HBM.  Key
block ``ki``'s rows are zeroed at the first query block that sees them
and written, in the compute dtype, at the last (which sees every key
block) into a ``(1, T, D)`` output block that goes back to HBM when the
key-value head changes.  :func:`sparse_supported` holds the shape to what
that keeps in VMEM at once.

Which kernel uses which blocks (``(query rows, keys)``; at 16,384 tokens
in brackets).  ``sparse_index`` and ``sparse_bwd`` take
:func:`blocks_for` [``(256, 512)``]: ``lse`` and ``delta`` arrive
finished, a block is products and element-wise work, and the MXU paces
it there.  ``sparse_fwd`` takes :func:`fwd_blocks_for`
[``(256, 1024)``], a key block twice as wide, because what it pays a
block and head is *per row, not per element*: the online softmax's lane
reductions through the XLU (~10 ns a vreg of eight rows by the sweep's
differences, and nothing else of the head's chain can start until the
maximum is known), the
``corr`` exponential, the rescale of ``acc``.  Three things keep that
off the MXU's path, each exact (PERF.md section 6, PR 35, has the sweep:
33.6 ms a run -> 15.4 at the learned-sparse cell's shape): the wide
block halves the reductions a score; the row sum is not reduced at all
until ``_finalize`` (``l`` is kept a lane tile wide, 128 partial sums a
row, so a block folds its lane tiles into it on the VPU); and a head's
``p v`` product is issued after the *next* head's scores and softmax, so
one head's reductions sit under another's products.  A pair the mask
drops scores ``-inf`` under a finite running maximum, so its ``exp`` is
exactly 0 without a second select.  Wider still (2,048 keys) pays the
causal band's overshoot (12.5 % against 5 %) for what the lane-wide sum
has already taken.

One mask serves every head.  Support envelope
(:func:`sparse_supported`): ``T`` a multiple of 128, ``group`` a divisor
of 128, ``D <= 512``, and ``sparse_bwd``'s residents inside
``_VMEM_LIMIT`` (16,384 x 128 at a group of 8 is, 32,768 x 128 is not).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fmda_tpu.compat import CompilerParams
from fmda_tpu.ops.attention import CORE_LSE, CORE_OUT
from fmda_tpu.ops.sparse_attention import _INT_MIN, sortable_key

_NEG = -1e30
#: Rows of queries ``sparse_select`` ranks at a time (one int8 tile).
SELECT_ROWS = 32
#: Columns a counting pass reads at a time.
SELECT_WIDTH = 2048
_VMEM_LIMIT = 64 * 1024 * 1024


def _largest_dividing(n: int, candidates) -> int:
    for c in candidates:
        if n % c == 0:
            return c
    raise ValueError(f"{n} is not a multiple of {candidates[-1]}")


def blocks_for(seq_len: int) -> Tuple[int, int]:
    """``(query rows, keys)`` of a block of the index kernel and of
    attention's backward kernel at this length."""
    return (_largest_dividing(seq_len, (256, 128)),
            _largest_dividing(seq_len, (512, 256, 128)))


def fwd_blocks_for(seq_len: int) -> Tuple[int, int]:
    """``(query rows, keys)`` of a block of attention's forward kernel:
    the key block as wide as 1,024 where the length allows it (module
    docstring), else what :func:`blocks_for` gives."""
    return (blocks_for(seq_len)[0],
            _largest_dividing(seq_len, (1024, 512, 256, 128)))


def _bwd_resident_bytes(seq_len: int, group: int, d_head: int) -> int:
    """What ``sparse_bwd`` keeps in VMEM at once, by count, at four bytes
    an element of q, k and v (the widest compute dtype; bfloat16 halves
    the blocks, not the scratches)."""
    bq, bk = blocks_for(seq_len)
    whole = seq_len * d_head * 4          # a key-value head's dk or dv
    q_block = group * bq * d_head * 4     # q, do, dq; the dq scratch
    blocks_in = (2 * q_block + 2 * bk * d_head * 4 + bq * bk
                 + 2 * bq * 128 * 4)      # q, do; k, v; mask; lse, delta
    scores = 2 * bq * bk * 4              # a head's s and dp tiles
    return (2 * whole + q_block           # the three scratches
            + 2 * (2 * whole + q_block)   # output blocks, in flight twice
            + 2 * blocks_in + scores)     # input blocks, in flight twice


def sparse_supported(seq_len: int, group: int, d_head: int) -> bool:
    """Shape gate for the kernels (module docstring)."""
    return (seq_len % 128 == 0 and group > 0 and 128 % group == 0
            and d_head <= 512
            and _bwd_resident_bytes(seq_len, group, d_head) <= _VMEM_LIMIT)


def _last_key_block(row0, qi, bq: int, bk: int):
    """The last key block holding a key visible to query block ``qi`` of
    a chunk that begins at row ``row0``."""
    return (row0 + (qi + 1) * bq - 1) // bk


# ---------------------------------------------------------------------------
# index scores
# ---------------------------------------------------------------------------


def _index_kernel(row0_ref, q_ref, k_ref, w_ref, o_ref, *, bq: int, bk: int):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki <= _last_key_block(row0_ref[0], qi, bq, bk))
    def _compute():
        k = k_ref[0]
        w = w_ref[0]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for j in range(q_ref.shape[1]):
            s = jax.lax.dot_general(
                q_ref[0, j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = acc + w[:, j:j + 1] * jnp.maximum(s, 0.0)
        o_ref[0] = acc


def index_scores(q_idx: jax.Array, k_idx: jax.Array, w_idx: jax.Array,
                 row0: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Index scores of a chunk of query rows: ``q_idx`` (B, Hi, C, Di)
    and ``w_idx`` (B, C, Hi) float32 are the chunk's, ``k_idx`` (B, T, Di)
    every key's, ``row0`` (1,) int32 the chunk's first row.  Returns
    (B, C, T) float32; entries above the causal diagonal are not written
    (the selection never reads them)."""
    b, hi, c, di = q_idx.shape
    t = k_idx.shape[1]
    bq, bk = blocks_for(c)[0], blocks_for(t)[1]

    def k_index(bi, qi, ki, r0):
        return (bi, jnp.minimum(ki, _last_key_block(r0[0], qi, bq, bk)), 0)

    return pl.pallas_call(
        functools.partial(_index_kernel, bq=bq, bk=bk),
        name="sparse_index",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, c // bq, t // bk),
            in_specs=[
                pl.BlockSpec((1, hi, bq, di),
                             lambda bi, qi, ki, r0: (bi, 0, qi, 0)),
                pl.BlockSpec((1, bk, di), k_index),
                pl.BlockSpec((1, bq, hi),
                             lambda bi, qi, ki, r0: (bi, qi, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, bq, bk), lambda bi, qi, ki, r0: (bi, qi, ki)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, c, t), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(row0, q_idx, k_idx, w_idx)


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------


def _select_kernel(row0_ref, s_ref, m_ref, key_scr, *, topk: int,
                   rows: int, width: int, seq_len: int):
    r0 = row0_ref[0] + pl.program_id(1) * rows
    row = r0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    want = jnp.minimum(row + 1, topk)
    # column tiles that hold a key some row of the block may see
    n_tiles = (r0 + rows + width - 1) // width
    int_min = jnp.int32(_INT_MIN)

    def tile(j):
        c0 = pl.multiple_of(j * width, width)
        col = c0 + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
        return c0, col

    def fill(j, carry):
        c0, col = tile(j)
        key = sortable_key(s_ref[0, :, pl.ds(c0, width)])
        key_scr[:, pl.ds(c0, width)] = jnp.where(col <= row, key, int_min)
        return carry

    jax.lax.fori_loop(0, n_tiles, fill, 0)

    def count(pred):
        """Per row: the keys of the prefix for which ``pred(key, col)``."""
        def body(j, acc):
            c0, col = tile(j)
            hit = pred(key_scr[:, pl.ds(c0, width)], col)
            return acc + jnp.sum(hit.astype(jnp.int32), axis=-1,
                                 keepdims=True)
        return jax.lax.fori_loop(
            0, n_tiles, body, jnp.zeros((rows, 1), jnp.int32))

    # the want-th largest key, bit by bit from the top, in the offset
    # binary whose unsigned order is the keys' signed order
    def key_bit(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), 31 - i)
        enough = count(lambda key, _: key >= (cand ^ int_min)) >= want
        return jnp.where(enough, cand, prefix)

    tau = jax.lax.fori_loop(
        0, 32, key_bit, jnp.zeros((rows, 1), jnp.int32)) ^ int_min
    # of the keys equal to it, the lowest `short` columns are taken: the
    # column of the short-th, bit by bit
    short = want - count(lambda key, _: key > tau)
    bits = max(seq_len - 1, 1).bit_length()

    def col_bit(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        below = count(lambda key, col: (key == tau) & (col < cand))
        return jnp.where(below < short, cand, prefix)

    last_tie = jax.lax.fori_loop(
        0, bits, col_bit, jnp.zeros((rows, 1), jnp.int32))

    def write(j, carry):
        c0, col = tile(j)
        key = key_scr[:, pl.ds(c0, width)]
        keep = (key > tau) | ((key == tau) & (col <= last_tie))
        m_ref[0, :, pl.ds(c0, width)] = jnp.where(keep, 1, 0).astype(
            m_ref.dtype)
        return carry

    def clear(j, carry):
        c0, _ = tile(j)
        m_ref[0, :, pl.ds(c0, width)] = jnp.zeros((rows, width), m_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_tiles, write, 0)
    jax.lax.fori_loop(n_tiles, seq_len // width, clear, 0)


def select_topk(scores: jax.Array, row0: jax.Array, topk: int, *,
                interpret: bool = False) -> jax.Array:
    """``scores`` (B, C, T) float32 of the query rows ``row0 .. row0 + C
    - 1`` -> (B, C, T) int8: 1 on the ``min(t + 1, topk)`` largest scores
    of row ``t``'s keys ``s <= t`` (ties to the lower ``s``), 0
    elsewhere."""
    b, c, t = scores.shape
    rows = min(SELECT_ROWS, c)
    width = min(SELECT_WIDTH, t)
    if c % rows or t % width:
        raise ValueError(f"selection needs rows % {rows} == 0 and keys % "
                         f"{width} == 0, got {c} x {t}")
    spec = pl.BlockSpec((1, rows, t), lambda bi, ri, r0: (bi, ri, 0))
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, rows=rows,
                          width=width, seq_len=t),
        name="sparse_select",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, c // rows),
            in_specs=[spec],
            out_specs=spec,
            scratch_shapes=[pltpu.VMEM((rows, t), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, c, t), jnp.int8),
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(row0, scores)


# ---------------------------------------------------------------------------
# attention over the picked keys
# ---------------------------------------------------------------------------


def _head_column(packed, h: int, group: int):
    """(rows, 1): head ``h``'s value from a (rows, 128) tile whose lane
    ``l`` holds head ``l // (128 / group)``."""
    at = h * (128 // group)
    return packed[:, at:at + 1]


def _pack_heads(columns, rows: int):
    """The inverse: ``group`` (rows, 1) columns -> one (rows, 128) tile."""
    group = len(columns)
    head_of_lane = jax.lax.broadcasted_iota(
        jnp.int32, (rows, 128), 1) // (128 // group)
    out = jnp.zeros((rows, 128), jnp.float32)
    for h, column in enumerate(columns):
        out = jnp.where(head_of_lane == h, column, out)
    return out


def _masked_scores(q, k, keep, fill=_NEG):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    return jnp.where(keep, s, fill), scale


def _fold_lanes(x):
    """(rows, n * 128) -> (rows, 128): the sum of the lane tiles,
    pairwise, on the VPU."""
    tiles = [x[:, i:i + 128] for i in range(0, x.shape[1], 128)]
    while len(tiles) > 1:  # an odd tile out waits for the next round
        tiles = [a + b for a, b in zip(tiles[::2], tiles[1::2])
                 ] + tiles[len(tiles) & ~1:]
    return tiles[0]


def _in_band(qi, ki, bq: int, bk: int):
    """Block (qi, ki) holds a key at or below some query's position."""
    return ki * bk < (qi + 1) * bq


def _band_key_block(qi, ki, bq: int, bk: int):
    """The key block grid step (qi, ki) references: its own in the band,
    the band's last again on a skipped step (nothing is fetched)."""
    return jnp.minimum(ki, ((qi + 1) * bq - 1) // bk)


def _one_head_behind(group: int, first, second) -> None:
    """``second(*first(h))`` for every head, with ``first(h + 1)`` issued
    in between: the compiler keeps the order a kernel is written in, so
    the one head's second stage runs under the next head's first."""
    behind = None
    for h in range(group):
        ahead = first(h)
        if behind is not None:
            second(*behind)
        behind = ahead
    second(*behind)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, bq: int, bk: int, n_k: int):
    """``m_scr[h]`` holds a row's running maximum on every lane,
    ``l_scr[h]`` 128 partial sums a row (lane ``j``: the keys of lane
    ``j`` of every lane tile so far), ``acc_scr[h]`` the unnormalised
    output (module docstring)."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    group = q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_in_band(qi, ki, bq, bk))
    def _compute():
        keep = mask_ref[0].astype(jnp.int32) != 0
        k, v = k_ref[0], v_ref[0]

        def softmax(h):
            # -inf off the picked keys: under the finite m_new (_NEG
            # where a row has seen no pick yet) exp gives exactly zero
            s, _ = _masked_scores(q_ref[0, h], k, keep, -jnp.inf)
            m_prev = m_scr[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + _fold_lanes(p)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            return h, p.astype(v.dtype), corr

        def accumulate(h, p, corr):
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        # a head's p v is issued behind the next head's scores and
        # softmax: the one head's reductions then sit under the other's
        # products
        _one_head_behind(group, softmax, accumulate)

    @pl.when(ki == n_k - 1)
    def _finalize():
        columns = []
        for h in range(group):
            l = jnp.sum(l_scr[h], axis=-1, keepdims=True)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc_scr[h] / l_safe).astype(o_ref.dtype)
            columns.append(jnp.where(
                l == 0.0, _NEG, m_scr[h][:, :1] + jnp.log(l_safe)))
        lse_ref[0] = _pack_heads(columns, bq)


def _p_and_ds(q, do, k, v, keep, lse, delta):
    s, scale = _masked_scores(q, k, keep)
    p = jnp.where(keep, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * scale


def _bwd_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *, bq: int,
                bk: int, n_q: int, n_k: int):
    """``dq_scr`` holds the query block's ``dq`` across its key blocks;
    ``dk_scr`` / ``dv_scr`` hold the key-value head's ``dk`` / ``dv``
    for the whole sequence across the query blocks (module docstring)."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    group = q_ref.shape[1]
    rows = pl.ds(pl.multiple_of(ki * bk, bk), bk)

    @pl.when(ki == 0)
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(qi == (ki * bk) // bq)  # the first query block that sees ki
    def _init_dkv():
        dk_scr[rows] = dv_scr[rows] = jnp.zeros(
            (bk, dk_scr.shape[1]), dk_scr.dtype)

    @pl.when(_in_band(qi, ki, bq, bk))
    def _compute():
        keep = mask_ref[0].astype(jnp.int32) != 0
        k, v = k_ref[0], v_ref[0]
        lse, delta = lse_ref[0], delta_ref[0]

        def scores(h):
            p, ds = _p_and_ds(q_ref[0, h], do_ref[0, h], k, v, keep,
                              _head_column(lse, h, group),
                              _head_column(delta, h, group))
            return h, p.astype(k.dtype), ds.astype(k.dtype)

        def accumulate(h, p, ds):
            dv_scr[rows] += jax.lax.dot_general(
                p, do_ref[0, h], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_scr[rows] += jax.lax.dot_general(
                ds, q_ref[0, h], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_scr[h] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        # a head's three gradient products are issued behind the next
        # head's two score products and element-wise work
        _one_head_behind(group, scores, accumulate)

    @pl.when(ki == n_k - 1)
    def _flush_dq():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)

    @pl.when(qi == n_q - 1)  # the last query block sees every key block
    def _flush_dkv():
        dk_ref[0, rows] = dk_scr[rows].astype(dk_ref.dtype)
        dv_ref[0, rows] = dv_scr[rows].astype(dv_ref.dtype)


def _params():
    return CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _fwd_impl(q, k, v, mask, *, blocks, interpret):
    """q (BG, group, T, D), k/v (BG, T, D), mask (B, T, T) int8 ->
    (o like q, lse (BG, T, 128) packed by head)."""
    bg, group, t, d = q.shape
    g = bg // mask.shape[0]
    bq, bk = blocks
    n_q, n_k = t // bq, t // bk

    k_block = functools.partial(_band_key_block, bq=bq, bk=bk)
    q_spec = pl.BlockSpec((1, group, bq, d), lambda b, qi, ki: (b, 0, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, bk, d), lambda b, qi, ki: (b, k_block(qi, ki), 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, n_k=n_k),
        name="sparse_fwd",
        grid=(bg, n_q, n_k),
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec((1, bq, bk), lambda b, qi, ki: (
                b // g, qi, k_block(qi, ki))),
        ],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, bq, 128), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bg, t, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, bq, 128), jnp.float32),
            pltpu.VMEM((group, bq, 128), jnp.float32),
            pltpu.VMEM((group, bq, d), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=interpret,
    )(q, k, v, mask)


def _bwd_impl(q, k, v, mask, o, lse, do, *, blocks, interpret):
    bg, group, t, d = q.shape
    g = bg // mask.shape[0]
    bq, bk = blocks
    n_q, n_k = t // bq, t // bk
    # delta = rowsum(do * o), packed by head like lse
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.repeat(delta.transpose(0, 2, 1), 128 // group, axis=-1)

    k_block = functools.partial(_band_key_block, bq=bq, bk=bk)
    q_spec = pl.BlockSpec((1, group, bq, d), lambda b, qi, ki: (b, 0, qi, 0))
    packed = pl.BlockSpec((1, bq, 128), lambda b, qi, ki: (b, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, bk, d), lambda b, qi, ki: (b, k_block(qi, ki), 0))
    # the head's dk / dv whole: written back when the head changes
    kv_whole = pl.BlockSpec((1, t, d), lambda b, qi, ki: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, bq=bq, bk=bk, n_q=n_q, n_k=n_k),
        name="sparse_bwd",
        grid=(bg, n_q, n_k),
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec((1, bq, bk), lambda b, qi, ki: (
                b // g, qi, k_block(qi, ki))),
            q_spec, packed, packed,
        ],
        out_specs=[q_spec, kv_whole, kv_whole],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((group, bq, d), jnp.float32),
                        pltpu.VMEM((t, d), jnp.float32),
                        pltpu.VMEM((t, d), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(q, k, v, mask, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _sparse(q, k, v, mask, blocks, interpret):
    """``blocks``: the forward kernel's pair, the backward kernel's."""
    return _fwd_impl(q, k, v, mask, blocks=blocks[0], interpret=interpret)[0]


def _sparse_fwd(q, k, v, mask, blocks, interpret):
    o, lse = _fwd_impl(q, k, v, mask, blocks=blocks[0], interpret=interpret)
    # named on the residuals' own arrays (pallas_attention._flash_fwd);
    # lse is already packed by head, a lane a head and row
    o = checkpoint_name(o, CORE_OUT)
    lse = checkpoint_name(lse, CORE_LSE)
    return o, (q, k, v, mask, o, lse)


def _sparse_bwd(blocks, interpret, residuals, do):
    q, k, v, mask, o, lse = residuals
    dq, dk, dv = _bwd_impl(q, k, v, mask, o, lse, do, blocks=blocks[1],
                           interpret=interpret)
    return dq, dk, dv, None


_sparse.defvjp(_sparse_fwd, _sparse_bwd)


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     mask: jax.Array, *, blocks=None, fwd_blocks=None,
                     interpret: bool = False) -> jax.Array:
    """Attention of q (B, N, T, D) over the keys of k/v (B, G, T, D)
    that ``mask`` (B, T, T) int8 marks, one mask for every head; rows of
    the mask hold nothing above the causal diagonal.  Differentiable in
    q, k, v.  ``blocks`` and ``fwd_blocks`` are for the tests and a
    sweep: the pairs follow from ``T`` (:func:`blocks_for` backward,
    :func:`fwd_blocks_for` forward; a ``blocks`` given alone serves
    both)."""
    b, n, t, d = q.shape
    g = k.shape[1]
    if n % g or not sparse_supported(t, n // g, d):
        raise ValueError(
            f"sparse kernels unsupported for T={t} heads={n}/{g} D={d}; "
            "gate on sparse_supported()")
    pairs = (tuple(fwd_blocks or blocks or fwd_blocks_for(t)),
             tuple(blocks or blocks_for(t)))
    out = _sparse(q.reshape(b * g, n // g, t, d), k.reshape(b * g, t, d),
                  v.reshape(b * g, t, d), mask, pairs, interpret)
    return out.reshape(b, n, t, d)
