"""Fused Pallas TPU flash-attention kernels: every attention core of the
decoder and attn families but the learned-sparse one.

The pure-jnp path (:func:`fmda_tpu.ops.attention.mha`) materialises the
(B, N, T, T) score matrix in HBM, and HBM bandwidth, not the MXU, then
bounds the step.  These kernels are the standard flash-attention
restructuring of the SAME online-softmax recurrence the module documents
(ops/attention.py docstring; the ring path folds K/V blocks with
identical math, parallel/ring_attention.py): scores only ever exist as
one block in VMEM.  Two kernels: ``flash_fwd`` and ``flash_bwd``.

Forward — grid ``(key-value heads x steps a group, T / bq, T / bk)``,
``dimension_semantics`` arbitrary: steps run in order, so VMEM scratch
carries a query block's row state across its key blocks.  One grid step
takes the query heads that share a key-value head together (q and o
blocks ``(1, heads, bq, D)``), so a key and value block is fetched once
a group and not once a head; a group of one is the same kernel.  Per
head ``h`` and block, the row state a head each in three scratches::

    s      = (q[h] @ k^T) * scale, -inf where masked   # MXU, f32
    m'     = max(m[h], rowmax(s))                      # the one XLU reduction
    p      = exp(s - m');  corr = exp(m[h] - m')       # f32
    l[h]   = l[h] * corr + fold_lanes(p)               # 128 partial sums a row
    acc[h] = acc[h] * corr + p @ v                     # MXU
    at the last key block:  o = acc / sum(l),  L = m + log sum(l)

What a block and head pay *per row, not per element* is what kept the
forward at a quarter of the MXU's rate while the backward, which gets
``L`` finished, ran at twice that a product: the lane reductions
through the XLU (nothing of a head's chain can start until its maximum
is known), the ``corr`` exponential, the rescale of ``acc``.  Four
things keep that off the MXU's path, each exact (PERF.md section 6,
PR 44, has the sweep at the three decoder cells' shapes; PR 35 made the
same moves in ``sparse_fwd``, whose ``_fold_lanes`` and
``_one_head_behind`` are used here):

- **the row sum stays a lane tile wide**: ``l`` holds 128 partial sums
  a row, a block folds its lane tiles into them on the VPU, and the one
  cross-lane sum happens in ``_finalize``;
- **masked scores are ``-inf`` under a finite running maximum** (``m``
  starts at ``_NEG``), so their ``exp`` is exactly 0 with no second
  select; a row whose block holds no visible key (a window's low edge)
  leaves ``m``, ``l`` and ``acc`` as they were, and a row that never
  sees a key reports ``lse = _NEG``, ``o = 0``;
- **a key block of 1,024** where ``T % 1024 == 0``
  (:func:`fwd_blocks_for`; the query block stays :func:`block_for`'s):
  half the reductions a score.  The band is then walked in ``(bq, bk)``
  blocks (:func:`_visible`, :func:`_keep`,
  :func:`_key_block`), every in-band block under one mask that all
  the step's heads share;
- **a head's ``p v`` is issued behind the next head's scores and
  softmax**, so one head's reduction sits under another's products.

What a shape does not get of this is counted at trace time
(``ops.dispatch.kernel_fallbacks()``): ``attention:narrow_key_block``
where 1,024 does not divide the length and the key block falls back to
the square one, ``attention:group_in_parts`` where a grid step does not
hold the group (:func:`heads_a_step`: half of the stated VMEM limit, at
most eight heads) and the group is walked in parts, its keys fetched
once a part.

Tried and dropped, with the chip's numbers in PERF.md section 6 (PR 43's
sweep, PR 44's for the head loop): a second, unmasked body for interior
blocks (2 % of the kernel for twice the compile and the program text);
a 256-row query block; a 512-key block with grouped heads; and every
way of unrolling fewer heads, which would cut the kernel's program text
(1.38 MB at a group of 7, all of a step's heads being straight-line
code) — a ``fori_loop`` over the heads through a VMEM scratch, the
heads rolled two or three a turn with the overlap kept inside a turn
(+9 to +14 % of the kernel: nothing overlaps across a loop's turns), at
most four heads a step with the group walked in two steps (+20 %).  The
text costs a warm set-up nothing that its faster first epoch does not
give back.

``L`` (the per-row logsumexp) leaves the forward as a ``(rows, 128)``
tile of equal lanes; its column is the only residual beyond the inputs
and ``o`` — the backward recomputes ``p = exp(s - L)`` blockwise instead
of storing probabilities.

Backward — one kernel, five products a block and head, shaped as
``sparse_bwd`` is (:mod:`fmda_tpu.ops.pallas_sparse_attention`; PERF.md
section 6, PR 37 and PR 47).  ``dk`` / ``dv`` sum over query blocks and
``dq`` over key blocks, so one sweep keeps only one of them in a
block-sized scratch, and a backward in two sweeps makes ``q k^T``,
``do v^T``, the ``exp`` and the mask twice (seven products for five).
``flash_bwd`` walks query blocks outside and key blocks inside, as the
forward does: grid ``(key-value heads x steps a group, T / blk,
T / blk)`` over square :func:`block_for` blocks (512 where T allows,
else 256 or 128: a grid step costs ~0.35 us whatever it computes), the
query heads of a key-value head in one grid step.
``dq`` sits in a ``(heads, blk, D)`` float32 scratch for the query
block's key blocks; the key-value head's ``dk`` and ``dv``
*for the whole sequence* sit in a ``(T, D)`` and a ``(T, Dv)`` float32
scratch, added to at the rows of key block ``ki`` query block by query
block, head by head: the group's sum happens there, in float32, and
nothing a query head wide leaves the kernel.  Key block ``ki``'s rows
are zeroed at the first query block that sees them and written, in the
compute dtype, at the last query block (whose grid steps pass every key
block) into ``(1, T, D)`` / ``(1, T, Dv)`` output blocks that go back to
HBM when the key-value head changes.  Per head ``h`` and block, with a
key a row (the scores are taken transposed, so that the two products
that contract the query rows need no transpose and only ``dq``'s does;
``L`` and ``delta`` then lie along the lanes and ride as they are kept,
one float32 a head and row, no 128-lane tile)::

    s^T  = (k @ q[h]^T) * scale, -inf where masked       # MXU, f32
    p^T  = exp(s^T - L[h])                               # exactly 0 if masked
    ds^T = p^T * (v @ do[h]^T - delta[h]) * scale        # MXU, f32
    dv[rows] += p^T @ do[h];  dk[rows] += ds^T @ q[h]    # MXU
    dq[h]    += ds @ k                                   # MXU

``delta = rowsum(do * o)`` (less the ``lse`` cotangent where there is
one) is cheap elementwise work computed outside in plain XLA.  The
step's heads are unrolled one after the other: all five of a head's
products are the MXU's and there is no reduction to hide, so issuing a
head's gradient products behind the next head's scores (the forward's
``_one_head_behind``) reads nothing here, and a ``fori_loop`` over the
heads costs 6 % of the kernel for a third of its program text (PERF.md
section 6, PR 47, has the sweeps).  On square blocks a group of one sums
every gradient's blocks in the order the two-sweep backward did: dQ, dK
and dV are that backward's bit for bit; a group above one differs by the
order of the group's float32 sum alone.

What fits is read from the shape (:func:`_bwd_resident_bytes`: the two
scratches, the output blocks in flight twice, a head's query-side blocks
times the heads, against ``_VMEM_LIMIT``): 27.9 MiB at seven bfloat16
heads of 128 by 8,192 tokens, 26.1 at one head of 192 on values of 128.
:func:`bwd_heads_a_step` walks a group that does not fit in parts, every
part adding into the one scratch (``attention:group_in_parts``); a
length whose scratches alone pass the limit (between 16k and 32k keys at
width 128) is outside the envelope: :func:`flash_supported` refuses it,
counts ``attention:backward_not_resident``, and ``mha`` takes its
blockwise path.

Two things ride inside both kernels:

- **grouped-query heads**: K/V may carry fewer heads than Q (``N`` query
  heads on ``G`` key-value heads, ``N % G == 0``).  Nothing is repeated
  in HBM: the K/V block index follows from the query heads' index.
- **a causal window**: key ``j`` is visible to query ``i`` iff
  ``0 <= i - j < window``.  Blocks wholly outside the band are skipped
  (no MXU/VPU work) and their block index is clamped into the band, so
  the pipeline re-references the block it already holds and fetches
  nothing; in the backward, blocks wholly inside the band skip the mask
  arithmetic (:func:`_interior`).

Support envelope (:func:`flash_supported`): self-attention with
``Tq == Tk``, ``T % 128 == 0``, no arbitrary mask (causal and the causal
window are in-kernel; a window implies causal), D small enough that
the per-block working set fits VMEM — in practice D <= 512 — and a
sequence whose ``dk`` and ``dv`` the backward can hold.  Values
may have a width of their own (``Dv``, latent attention's 128 beside
scores over 192): ``p @ v``, ``o``, ``do`` and ``dv`` are then ``Dv``
wide and nothing is padded.  Everything else takes the jnp path via
:func:`fmda_tpu.ops.attention.mha`'s dispatch.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fmda_tpu.compat import CompilerParams
from fmda_tpu.ops.attention import CORE_LSE, CORE_OUT
from fmda_tpu.ops.dispatch import count_kernel_fallback
from fmda_tpu.ops.pallas_sparse_attention import (
    _fold_lanes, _one_head_behind)

#: Smallest Q/K block edge.  128 = MXU tile edge = Mosaic lane count; T
#: must be a multiple (flash_supported gates on it).
_BLOCK = 128


def block_for(seq_len: int) -> int:
    """The square block edge the kernels use at this length: the largest
    of 512, 256, 128 that divides it."""
    for blk in (512, 256):
        if seq_len % blk == 0:
            return blk
    return _BLOCK

#: Finite stand-in for -inf: the forward's running maximum starts here
#: and a row that sees no key reports it as ``lse``; masked scores are a
#: true -inf beneath it in both kernels, so their ``exp`` is exactly 0.
_NEG = -1e30

#: What a kernel may hold in VMEM (a v5e has 128 MiB; the default scoped
#: limit, 16 MiB, is under the seven heads of a (512, 1024) forward step
#: and under the backward's two whole-sequence scratches at 8,192 x 128).
_VMEM_LIMIT = 64 * 1024 * 1024
#: Query heads of one grid step at most: each is unrolled in the kernel.
_MAX_HEADS_A_STEP = 8


def flash_supported(q_len: int, k_len: int, d_head: int,
                    d_value: Optional[int] = None) -> bool:
    """Shape gate for the fused kernels (see module docstring).  A length
    whose backward residents (one query head a step, four-byte elements)
    pass ``_VMEM_LIMIT`` is refused and counted,
    ``attention:backward_not_resident``."""
    if not (q_len == k_len and q_len % _BLOCK == 0 and d_head <= 512):
        return False
    if _bwd_resident_bytes(
            q_len, 1, d_head, d_value or d_head, 4) > _VMEM_LIMIT:
        count_kernel_fallback("attention", "backward_not_resident")
        return False
    return True


# ---------------------------------------------------------------------------
# the causal band in (bq, bk) blocks, for both kernels
# ---------------------------------------------------------------------------


def _visible(qi, ki, bq: int, bk: int, window: Optional[int]):
    """Block (qi, ki) holds at least one visible (query, key) pair: its
    greatest ``query - key`` is causal and its least is inside the
    window."""
    ok = (qi + 1) * bq - 1 - ki * bk >= 0
    if window is not None:
        ok = ok & (qi * bq - (ki + 1) * bk + 1 < window)
    return ok


def _interior(qi, ki, bq: int, bk: int, window: Optional[int]):
    """Every pair of block (qi, ki) is visible: its least ``query - key``
    is causal and its greatest inside the window.  No mask arithmetic."""
    ok = qi * bq - (ki + 1) * bk + 1 >= 0
    if window is not None:
        ok = ok & ((qi + 1) * bq - 1 - ki * bk < window)
    return ok


def _keep(qi, ki, bq: int, bk: int, window: Optional[int],
          by_key: bool = False):
    """The bool keep-mask of block (qi, ki), in global positions: causal,
    and inside the window where there is one.  ``(bq, bk)``, a query a
    row; ``by_key`` gives it transposed, ``(bk, bq)``, a key a row."""
    shape, q_axis = ((bk, bq), 1) if by_key else ((bq, bk), 0)
    rel = (qi * bq - ki * bk
           + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
           - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
    keep = rel >= 0
    if window is not None:
        keep = keep & (rel < window)
    return keep


def _key_block(qi, ki, bq: int, bk: int, window: Optional[int]):
    """The key block a (qi, ki) grid step references: ``ki`` inside the
    band, the band's nearest block outside it — an index the pipeline
    already holds, so a skipped step fetches nothing."""
    lo = 0 if window is None else jnp.maximum(
        qi * bq - window + 1, 0) // bk
    return jnp.clip(ki, lo, ((qi + 1) * bq - 1) // bk)


# ---------------------------------------------------------------------------
# forward: (bq, bk) blocks, a key-value head's query heads a grid step
# ---------------------------------------------------------------------------


def fwd_blocks_for(seq_len: int) -> Tuple[int, int]:
    """``(query rows, keys)`` of a block of the forward kernel: the key
    block 1,024 wide where the length allows it (module docstring), else
    the square :func:`block_for` block."""
    blk = block_for(seq_len)
    return blk, (1024 if seq_len % 1024 == 0 else blk)


def heads_a_step(group: int, bq: int, d: int, dv: int, itemsize: int) -> int:
    """How many of a key-value head's ``group`` query heads one forward
    grid step takes: all of them where that is at most
    ``_MAX_HEADS_A_STEP`` and their blocks (q and o in flight twice, the
    lse tile likewise, the three scratches) fit half of
    ``_VMEM_LIMIT`` beside the key, value and score tiles; else the
    largest divisor of the group that does."""
    a_head = bq * (2 * (d + dv) * itemsize + 2 * 128 * 4
                   + (2 * 128 + dv) * 4)
    most = max(1, min(_MAX_HEADS_A_STEP, _VMEM_LIMIT // 2 // a_head))
    return max(h for h in range(1, group + 1)
               if group % h == 0 and h <= most)


def _fwd_kernel(
    q_ref,  # (1, heads, bq, D): the query heads of this grid step
    k_ref,  # (1, bk, D): their one key-value head
    v_ref,  # (1, bk, Dv)
    o_ref,  # out (1, heads, bq, Dv)
    lse_ref,  # out (1, heads, bq, 128) lane-replicated logsumexp
    m_scr,  # VMEM (heads, bq, 128) f32: a row's running maximum, every lane
    l_scr,  # VMEM (heads, bq, 128) f32: 128 partial sums a row
    acc_scr,  # VMEM (heads, bq, Dv) f32: the unnormalised output
    *,
    causal: bool,
    window: Optional[int],
    bq: int,
    bk: int,
    n_k: int,
    scale: Optional[float] = None,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    heads = q_ref.shape[1]
    if scale is None:
        scale = 1.0 / (q_ref.shape[-1] ** 0.5)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        k, v = k_ref[0], v_ref[0]
        # one mask a grid step, for every head of it
        keep = _keep(qi, ki, bq, bk, window) if causal else None

        def softmax(h):
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                # -inf where masked: under the finite m_new (_NEG where a
                # row has seen no key yet) exp gives exactly zero, and
                # such a row's m, l and acc stay as they were
                s = jnp.where(keep, s, -jnp.inf)
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
        _one_head_behind(heads, softmax, accumulate)

    # blocks outside the band are fully masked: skip their MXU/VPU work
    # entirely, the state update is a no-op there by construction
    if causal:
        pl.when(_visible(qi, ki, bq, bk, window))(_compute)
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _finalize():
        for h in range(heads):
            # the one cross-lane sum of a row; a row that saw no key
            # reports o = 0, lse = _NEG (p recomputes to 0 in the backward)
            l = jnp.sum(l_scr[h], axis=-1, keepdims=True)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc_scr[h] / l_safe).astype(o_ref.dtype)
            lse = jnp.where(l == 0.0, _NEG,
                            m_scr[h][:, :1] + jnp.log(l_safe))
            lse_ref[0, h] = jnp.broadcast_to(lse, lse_ref.shape[2:])


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "interpret", "scale"))
def _fwd_impl(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool, window: Optional[int], interpret: bool,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """q (BN, T, D), k (BG, T, D), v (BG, T, Dv) -> (o (BN, T, Dv), lse
    (BN, T, 128)).  A ``jax.jit`` of its own, so that the layers of a
    step share one traced and lowered kernel body."""
    bn, t, d = q.shape
    dv = v.shape[-1]
    group = bn // k.shape[0]
    bq, bk = fwd_blocks_for(t)
    heads = heads_a_step(group, bq, d, dv, q.dtype.itemsize)
    # what a shape did not get of the mechanism, said once a trace
    if bk == bq:
        count_kernel_fallback("attention", "narrow_key_block")
    if heads < group:
        count_kernel_fallback("attention", "group_in_parts")
    steps = bn // heads
    kernel = functools.partial(
        _fwd_kernel, causal=causal, window=window, bq=bq, bk=bk,
        n_k=t // bk, **_stated(scale))

    def q_rows(width):
        return pl.BlockSpec((1, heads, bq, width),
                            lambda b, qi, ki: (b, 0, qi, 0))

    def kv_index(b, qi, ki):
        if causal:
            ki = _key_block(qi, ki, bq, bk, window)
        return ((b * heads) // group, ki, 0)

    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(steps, t // bq, t // bk),
        in_specs=[
            q_rows(d),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, dv), kv_index),
        ],
        out_specs=[q_rows(dv), q_rows(128)],
        out_shape=[
            jax.ShapeDtypeStruct((steps, heads, t, dv), q.dtype),
            jax.ShapeDtypeStruct((steps, heads, t, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, bq, 128), jnp.float32),
            pltpu.VMEM((heads, bq, 128), jnp.float32),
            pltpu.VMEM((heads, bq, dv), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(q.reshape(steps, heads, t, d), k, v)
    return o.reshape(bn, t, dv), lse.reshape(bn, t, 128)


# ---------------------------------------------------------------------------
# backward: one sweep, a key-value head's dk and dv resident for the sequence
# ---------------------------------------------------------------------------


def _bwd_resident_bytes(seq_len: int, heads: int, d: int, dv: int,
                        itemsize: int) -> int:
    """What ``flash_bwd`` keeps in VMEM at once with ``heads`` query heads
    a grid step, by count: the key-value head's ``dk`` and ``dv`` for the
    whole sequence (float32 scratches, and the output blocks in flight
    twice), a head's query-side blocks, the key and value blocks and a
    head's score tiles."""
    blk = block_for(seq_len)
    whole = seq_len * (d + dv)
    a_head = blk * (2 * (d + dv) * itemsize    # q, do, in flight twice
                    + 2 * d * itemsize + d * 4  # dq twice, its scratch
                    + 2 * 2 * 8 * 4)            # the lse and delta rows
    return (whole * 4 + 2 * whole * itemsize + heads * a_head
            + 2 * blk * (d + dv) * itemsize    # k, v, in flight twice
            + 4 * blk * blk * 4)               # s, p, dp, ds of a head


def bwd_heads_a_step(group: int, seq_len: int, d: int, dv: int,
                     itemsize: int) -> int:
    """:func:`heads_a_step` for the backward: the largest divisor of the
    group, eight at most, whose residents (:func:`_bwd_resident_bytes`)
    fit ``_VMEM_LIMIT``; one where nothing fits (:func:`flash_supported`
    refuses such a length)."""
    return max([h for h in range(1, min(group, _MAX_HEADS_A_STEP) + 1)
                if group % h == 0 and _bwd_resident_bytes(
                    seq_len, h, d, dv, itemsize) <= _VMEM_LIMIT] or [1])


def _bwd_kernel(
    q_ref,  # (1, heads, blk, D): the query heads of this grid step
    k_ref,  # (1, blk, D): their one key-value head
    v_ref,  # (1, blk, Dv)
    do_ref,  # (1, heads, blk, Dv)
    lse_ref,  # (1, heads, 1, blk): a head's rows side by side
    delta_ref,  # (1, heads, 1, blk)
    dq_ref,  # out (1, heads, blk, D)
    dk_ref,  # out (1, T, D): the key-value head's, whole
    dv_ref,  # out (1, T, Dv)
    dq_scr,  # VMEM (heads, blk, D) f32: across the query block's key blocks
    dk_scr,  # VMEM (T, D) f32: across the head's query blocks and heads
    dv_scr,  # VMEM (T, Dv) f32
    *,
    causal: bool,
    window: Optional[int],
    blk: int,
    n_blk: int,
    parts: int,
    scale: Optional[float] = None,
):
    step, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    heads = q_ref.shape[1]
    if scale is None:
        scale = 1.0 / (q_ref.shape[-1] ** 0.5)
    rows = pl.ds(pl.multiple_of(ki * blk, blk), blk)
    # a group walked in parts adds every part into the one scratch
    first_part = True if parts == 1 else step % parts == 0
    last_part = True if parts == 1 else step % parts == parts - 1

    @pl.when(ki == 0)
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    # the first query block that sees key block ki zeroes its rows
    @pl.when((qi == (ki if causal else 0)) & first_part)
    def _init_dkv():
        dk_scr[rows] = jnp.zeros((blk, dk_scr.shape[1]), dk_scr.dtype)
        dv_scr[rows] = jnp.zeros((blk, dv_scr.shape[1]), dv_scr.dtype)

    def _compute(masked: bool):
        k, v = k_ref[0], v_ref[0]
        # one mask a grid step, for every head of it
        keep = _keep(qi, ki, blk, blk, window, by_key=True) if masked else None

        for h in range(heads):
            # (blk, blk), a key a row: s^T = k q^T and dp^T = v do^T
            s = jax.lax.dot_general(
                k, q_ref[0, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                # -inf where masked: under the finite lse (_NEG where a
                # row saw no key) exp gives exactly zero, and so is ds
                s = jnp.where(keep, s, -jnp.inf)
            p = jnp.exp(s - lse_ref[0, h])
            dp = jax.lax.dot_general(
                v, do_ref[0, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, h]) * scale).astype(k.dtype)
            # dv += p^T do and dk += ds^T q as they stand; dq += ds k
            # contracts the keys, the one transposed operand of the five
            dv_scr[rows] += jax.lax.dot_general(
                p.astype(k.dtype), do_ref[0, h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_scr[rows] += jax.lax.dot_general(
                ds, q_ref[0, h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_scr[h] += jax.lax.dot_general(
                ds, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    # blocks outside the band give nothing to any gradient: no MXU or
    # VPU work; blocks wholly inside it skip the mask
    if causal:
        inside = _interior(qi, ki, blk, blk, window)
        pl.when(inside)(lambda: _compute(False))
        pl.when(_visible(qi, ki, blk, blk, window) & ~inside)(
            lambda: _compute(True))
    else:
        _compute(False)

    @pl.when(ki == n_blk - 1)
    def _flush_dq():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)

    # the last query block's grid steps pass every key block
    @pl.when((qi == n_blk - 1) & last_part)
    def _flush_dkv():
        dk_ref[0, rows] = dk_scr[rows].astype(dk_ref.dtype)
        dv_ref[0, rows] = dv_scr[rows].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "interpret", "scale"))
def _bwd_impl(
    q, k, v, o, lse, do, dlse=None, *, causal: bool,
    window: Optional[int], interpret: bool, scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q, do (BN, T, .), k, v (BG, T, .), lse (BN, T) -> dq, dk, dv in
    the inputs' dtypes.  A ``jax.jit`` of its own, as the forward's."""
    bn, t, d = q.shape
    dv = v.shape[-1]
    group = bn // k.shape[0]
    blk = block_for(t)
    heads = bwd_heads_a_step(group, t, d, dv, q.dtype.itemsize)
    if heads < group:
        count_kernel_fallback("attention", "group_in_parts")
    steps, parts = bn // heads, group // heads
    # delta = rowsum(do * o): cheap elementwise+reduce, plain XLA.  An
    # lse cotangent (the ring path differentiates through the per-block
    # logsumexp) folds in for free: d lse_i / d s_ij = p_ij, so
    # ds = p * (dp - delta + dlse) * scale — i.e. delta -= dlse.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    def q_rows(width):
        return pl.BlockSpec((1, heads, blk, width),
                            lambda b, qi, ki: (b, 0, qi, 0))

    # lse and delta ride as they are kept, one float32 a head and row: a
    # head's (1, blk) block lies along the lanes of the transposed scores
    a_row = pl.BlockSpec((1, heads, 1, blk), lambda b, qi, ki: (b, 0, 0, qi))

    def kv_index(b, qi, ki):
        if causal:
            ki = _key_block(qi, ki, blk, blk, window)
        return ((b * heads) // group, ki, 0)

    def kv_whole(width):  # goes back to HBM when the head changes
        return pl.BlockSpec((1, t, width),
                            lambda b, qi, ki: ((b * heads) // group, 0, 0))

    dq, dk, dv_ = pl.pallas_call(
        functools.partial(
            _bwd_kernel, causal=causal, window=window, blk=blk,
            n_blk=t // blk, parts=parts, **_stated(scale)),
        name="flash_bwd",
        grid=(steps, t // blk, t // blk),
        in_specs=[
            q_rows(d),
            pl.BlockSpec((1, blk, d), kv_index),
            pl.BlockSpec((1, blk, dv), kv_index),
            q_rows(dv), a_row, a_row,
        ],
        out_specs=[q_rows(d), kv_whole(d), kv_whole(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((steps, heads, t, d), q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, blk, d), jnp.float32),
            pltpu.VMEM((t, d), jnp.float32),
            pltpu.VMEM((t, dv), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(q.reshape(steps, heads, t, d), k, v, do.reshape(steps, heads, t, dv),
      lse.reshape(steps, heads, 1, t), delta.reshape(steps, heads, 1, t))
    return dq.reshape(bn, t, d), dk, dv_


def _stated(scale: Optional[float]) -> dict:
    """The kernels' ``scale`` argument where a caller states one; nothing
    where it is the default, so that such a kernel is the one it was."""
    return {} if scale is None else {"scale": float(scale)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, interpret, scale=None):
    o, lse = _fwd_impl(q, k, v, causal=causal, window=window,
                       interpret=interpret, scale=scale)
    return o, lse[..., 0]


def _flash_fwd(q, k, v, causal, window, interpret, scale=None):
    o, lse = _fwd_impl(q, k, v, causal=causal, window=window,
                       interpret=interpret, scale=scale)
    # named here, on the arrays the residuals hold, so that a policy that
    # saves the names replays a block without this kernel (a name on the
    # caller's copy would keep a copy and still run it for the residuals).
    # The kernel's lse tile is 128 equal lanes: its column is the
    # residual, 1/128 of the bytes, and the backward rebuilds the tile.
    o = checkpoint_name(o, CORE_OUT)
    lse = checkpoint_name(lse[..., 0], CORE_LSE)
    return (o, lse), (q, k, v, o, lse)


def _flash_bwd(causal, window, interpret, scale, residuals, cts):
    q, k, v, o, lse = residuals
    do, dlse = cts
    return _bwd_impl(q, k, v, o, lse, do, dlse, causal=causal,
                     window=window, interpret=interpret, scale=scale)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    interpret: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Fused-kernel multi-head attention, q (B, N, T, D) and k/v
    (B, G, T, D) with ``N % G == 0`` -> (B, N, T, D).

    Numerics match :func:`fmda_tpu.ops.attention.mha` (same online
    softmax, f32 accumulation); parity is test-locked in interpret mode
    and on hardware (tests/test_pallas_attention.py).  Call through
    ``mha(..., )``'s dispatch rather than directly unless you have
    already checked :func:`flash_supported`.
    """
    out, _ = flash_attention_with_lse(
        q, k, v, causal=causal, window=window, interpret=interpret,
        scale=scale)
    return out


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    interpret: bool = False,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused attention returning ``(o, lse)`` — o (B, N, T, Dv) in q's
    dtype plus the per-row logsumexp (B, N, T) f32.  Values may have a
    width of their own (v (B, G, T, Dv), ``Dv != D``): scores are taken
    over D, the output and its gradient are Dv wide.

    The lse is what makes the output *mergeable*: two attention results
    over disjoint key segments combine exactly via
    :func:`fmda_tpu.ops.attention.merge_softmax_segments`, which is how
    ring attention folds one fused-kernel call per ring step
    (parallel/ring_attention.py) instead of materialising jnp score
    blocks.  Differentiable in both outputs (the lse cotangent folds
    into the backward's delta term).  Fully-masked rows report
    ``lse = -1e30`` (the kernel's finite -inf sentinel) and ``o = 0``.
    ``window`` (a causal window: key j visible to query i iff
    ``0 <= i - j < window``) implies ``causal``.  ``scale`` multiplies
    the scores in place of ``1 / sqrt(D)`` (None).
    """
    b, n, t, d = q.shape
    g = k.shape[1]
    if not flash_supported(q.shape[-2], k.shape[-2], d, v.shape[-1]):
        raise ValueError(
            f"flash kernel unsupported for Tq={q.shape[-2]} "
            f"Tk={k.shape[-2]} D={d}; gate on flash_supported()")
    if n % g != 0 or v.shape[1] != g:
        raise ValueError(
            f"{n} query heads cannot share {g} key / {v.shape[1]} value "
            "heads: the query heads must be a multiple of both")
    causal = causal or window is not None
    if window is not None and window >= t:
        window = None  # the band is the whole causal triangle
    out, lse = _flash(
        q.reshape(b * n, t, d), k.reshape(b * g, t, d),
        v.reshape(b * g, t, v.shape[-1]), causal, window, interpret, scale)
    return out.reshape(b, n, t, v.shape[-1]), lse.reshape(b, n, t)
