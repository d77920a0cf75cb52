"""Fused Pallas TPU flash-attention kernels: every attention core of the
decoder and attn families but the learned-sparse one.

The pure-jnp path (:func:`fmda_tpu.ops.attention.mha`) materialises the
(B, N, T, T) score matrix in HBM, and HBM bandwidth, not the MXU, then
bounds the step.  These kernels are the standard flash-attention
restructuring of the SAME online-softmax recurrence the module documents
(ops/attention.py docstring; the ring path folds K/V blocks with
identical math, parallel/ring_attention.py): scores only ever exist as
one block in VMEM.  Three kernels: ``flash_fwd``, ``flash_bwd_dkv`` and
``flash_bwd_dq``.

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
forward at a quarter of the MXU's rate while the backward kernels, which
get ``L`` finished, ran at twice that a product: the lane reductions
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
  blocks (:func:`_fwd_visible`, :func:`_fwd_keep`,
  :func:`_fwd_key_block`), every in-band block under one mask that all
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

``L`` (the per-row logsumexp) leaves the kernel as a ``(rows, 128)``
tile of equal lanes; its column is the only residual beyond the inputs
and ``o`` — the backward recomputes ``p = exp(s - L)`` blockwise instead
of storing probabilities.  Backward runs as two kernels over square
blocks of :func:`block_for` ``(T)`` (512 where T allows, else 256 or
128: a grid step costs ~0.35 us whatever it computes), one query head a
grid step, the textbook split:

- **dK/dV sweep** — grid ``(B*N, T/blk [k], T/blk [q])``: for a fixed
  K/V block, walk the query blocks; ``dv += p^T @ do``,
  ``ds = p * (do @ v^T - delta) * scale``, ``dk += ds^T @ q``.
- **dQ sweep** — grid ``(B*N, T/blk [q], T/blk [k])``: for a fixed Q
  block, walk the key blocks; ``dq += ds @ k``.

``delta = rowsum(do * o)`` is cheap elementwise work computed outside in
plain XLA.  The backward masks with the large-negative finite ``_NEG``
and forces masked probabilities to exactly zero.  m/L/delta ride as
128-lane-replicated ``(rows, 128)`` tiles — Mosaic's tiling wants the
last dim to be 128 or the full array dim, and a (1, block) slab whose
sublane dim is neither 8-divisible nor full does not lower.

Two things ride inside all three kernels:

- **grouped-query heads**: K/V may carry fewer heads than Q (``N`` query
  heads on ``G`` key-value heads, ``N % G == 0``).  Nothing is repeated
  in HBM: the K/V block index follows from the query heads' index; the
  dK/dV sweep writes one float32 partial per query head and the
  ``N / G`` partials of a group are summed outside.
- **a causal window**: key ``j`` is visible to query ``i`` iff
  ``0 <= i - j < window``.  Blocks wholly outside the band are skipped
  (no MXU/VPU work) and their block index is clamped into the band, so
  the pipeline re-references the block it already holds and fetches
  nothing; in the backward, blocks wholly inside the band skip the mask
  arithmetic.

Support envelope (:func:`flash_supported`): self-attention with
``Tq == Tk``, ``T % 128 == 0``, no arbitrary mask (causal and the causal
window are in-kernel; a window implies causal), and D small enough that
the per-block working set fits VMEM — in practice D <= 512.  Values
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
#: (its masked scores are a true -inf beneath it) and a row that sees no
#: key reports it as ``lse``; the backward's masked score slots hold it
#: (exp(finite - finite) stays a number) and their probabilities are
#: forced to 0, so a fully-masked row cannot give exp(0) = 1.
_NEG = -1e30


def flash_supported(q_len: int, k_len: int, d_head: int) -> bool:
    """Shape gate for the fused kernel (see module docstring)."""
    return (
        q_len == k_len
        and q_len % _BLOCK == 0
        and d_head <= 512
    )


def _causal_mask_block(qi, ki, blk: int, window: Optional[int]):
    """(blk, blk) bool keep-mask for query block qi vs key block ki, in
    global positions: causal, and inside the window where there is one."""
    q_pos = qi * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    k_pos = ki * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    keep = q_pos >= k_pos
    if window is not None:
        keep = keep & (q_pos - k_pos < window)
    return keep


def _band_span(blk: int, window: Optional[int]) -> Optional[int]:
    """How many key blocks behind its own a query block still sees
    (None: all of them)."""
    return None if window is None else (window + blk - 2) // blk


def _in_band(qi, ki, span: Optional[int]):
    """Block (qi, ki) holds at least one visible (query, key) pair."""
    ok = ki <= qi
    return ok if span is None else ok & (qi - ki <= span)


def _interior(qi, ki, blk: int, window: Optional[int]):
    """Every pair of block (qi, ki) is visible: no mask arithmetic."""
    ok = ki < qi
    if window is not None:
        ok = ok & ((qi - ki) * blk + (blk - 1) < window)
    return ok


def _banded(causal: bool, qi, ki, blk, window, compute) -> None:
    """Run ``compute(masked)`` for block (qi, ki): always and unmasked
    without ``causal``; else only inside the band, masked on its edges."""
    if not causal:
        compute(False)
        return
    inside = _interior(qi, ki, blk, window)
    pl.when(inside)(lambda: compute(False))
    pl.when(_in_band(qi, ki, _band_span(blk, window)) & ~inside)(
        lambda: compute(True))


def _scores(q, k, qi, ki, *, blk, window, masked, scale=None):
    """Scaled scores of one block, masked slots at ``_NEG``; ``scale``
    None is ``1 / sqrt(D)``."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if masked:
        s = jnp.where(_causal_mask_block(qi, ki, blk, window), s, _NEG)
    return s, scale


# ---------------------------------------------------------------------------
# forward: (bq, bk) blocks, a key-value head's query heads a grid step
# ---------------------------------------------------------------------------


def fwd_blocks_for(seq_len: int) -> Tuple[int, int]:
    """``(query rows, keys)`` of a block of the forward kernel: the key
    block 1,024 wide where the length allows it (module docstring), else
    the square :func:`block_for` block."""
    blk = block_for(seq_len)
    return blk, (1024 if seq_len % 1024 == 0 else blk)


#: What ``flash_fwd`` may hold in VMEM (a v5e has 128 MiB; the default
#: scoped limit, 16 MiB, is under the seven heads of a (512, 1024) step).
_FWD_VMEM_LIMIT = 64 * 1024 * 1024
#: Query heads of one grid step at most: each is unrolled in the kernel.
_MAX_HEADS_A_STEP = 8


def heads_a_step(group: int, bq: int, d: int, dv: int, itemsize: int) -> int:
    """How many of a key-value head's ``group`` query heads one forward
    grid step takes: all of them where that is at most
    ``_MAX_HEADS_A_STEP`` and their blocks (q and o in flight twice, the
    lse tile likewise, the three scratches) fit half of
    ``_FWD_VMEM_LIMIT`` beside the key, value and score tiles; else the
    largest divisor of the group that does."""
    a_head = bq * (2 * (d + dv) * itemsize + 2 * 128 * 4
                   + (2 * 128 + dv) * 4)
    most = max(1, min(_MAX_HEADS_A_STEP, _FWD_VMEM_LIMIT // 2 // a_head))
    return max(h for h in range(1, group + 1)
               if group % h == 0 and h <= most)


def _fwd_visible(qi, ki, bq: int, bk: int, window: Optional[int]):
    """:func:`_in_band` for ``(bq, bk)`` blocks: block (qi, ki) holds at
    least one visible (query, key) pair, i.e. its greatest ``query - key``
    is causal and its least is inside the window."""
    ok = (qi + 1) * bq - 1 - ki * bk >= 0
    if window is not None:
        ok = ok & (qi * bq - (ki + 1) * bk + 1 < window)
    return ok


def _fwd_keep(qi, ki, bq: int, bk: int, window: Optional[int]):
    """:func:`_causal_mask_block` for ``(bq, bk)`` blocks: the bool
    keep-mask of block (qi, ki), causal, and inside the window where
    there is one."""
    rel = (qi * bq - ki * bk
           + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
           - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
    keep = rel >= 0
    if window is not None:
        keep = keep & (rel < window)
    return keep


def _fwd_key_block(qi, ki, bq: int, bk: int, window: Optional[int]):
    """:func:`_clamp_key_block` for ``(bq, bk)`` blocks: ``ki`` inside
    the band, the band's nearest block outside it."""
    lo = 0 if window is None else jnp.maximum(
        qi * bq - window + 1, 0) // bk
    return jnp.clip(ki, lo, ((qi + 1) * bq - 1) // bk)


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
        keep = _fwd_keep(qi, ki, bq, bk, window) if causal else None

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
        pl.when(_fwd_visible(qi, ki, bq, bk, window))(_compute)
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
            ki = _fwd_key_block(qi, ki, bq, bk, window)
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
            vmem_limit_bytes=_FWD_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(q.reshape(steps, heads, t, d), k, v)
    return o.reshape(bn, t, dv), lse.reshape(bn, t, 128)


# ---------------------------------------------------------------------------
# backward: square blocks, one query head a grid step
# ---------------------------------------------------------------------------


def _clamp_key_block(qi, ki, *, causal, blk, window):
    """The key block a (qi, ki) grid step references: ``ki`` inside the
    band, the band's nearest block outside it — an index the pipeline
    already holds, so a skipped step fetches nothing."""
    if not causal:
        return ki
    span = _band_span(blk, window)
    lo = 0 if span is None else jnp.maximum(qi - span, 0)
    return jnp.clip(ki, lo, qi)


def _clamp_query_block(ki, qi, *, causal, blk, window, n_q):
    """The dK/dV sweep's twin: the query block a (ki, qi) step
    references."""
    if not causal:
        return qi
    span = _band_span(blk, window)
    hi = n_q - 1 if span is None else jnp.minimum(ki + span, n_q - 1)
    return jnp.clip(qi, ki, hi)


def _p_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
              *, blk, window, masked, scale=None):
    """The backward's shared recompute for one block: probabilities
    ``p = exp(s - L)`` and ``ds = p * (do @ v^T - delta) * scale``."""
    f32 = jnp.float32
    s, scale = _scores(q_ref[0], k_ref[0], qi, ki, blk=blk, window=window,
                       masked=masked, scale=scale)
    p = jnp.exp(s - lse_ref[0][:, :1])
    if masked:
        p = jnp.where(s <= _NEG * 0.5, 0.0, p)
    dp = jax.lax.dot_general(
        do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=f32)
    return p, p * (dp - delta_ref[0][:, :1]) * scale


def _dkv_kernel(
    q_ref,  # (1, blk, D) — query block qi
    k_ref,  # (1, blk, D) — the fixed key block ki
    v_ref,  # (1, blk, Dv)
    do_ref,  # (1, blk, Dv) — dO for query block qi
    lse_ref,  # (1, blk, 128)
    delta_ref,  # (1, blk, 128)
    dk_ref,  # out (1, blk, D)
    dv_ref,  # out (1, blk, Dv)
    dk_scr,  # VMEM (blk, D) f32
    dv_scr,  # VMEM (blk, Dv) f32
    *,
    causal: bool,
    window: Optional[int],
    blk: int,
    n_q: int,
    scale: Optional[float] = None,
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr[:])
        dv_scr[:] = jnp.zeros_like(dv_scr[:])

    def _compute(masked: bool):
        f32 = jnp.float32
        p, ds = _p_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          qi, ki, blk=blk, window=window, masked=masked,
                          scale=scale)
        io_dtype = q_ref.dtype
        # dv += p^T @ do   (contract the query rows)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(io_dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=f32)
        # dk += ds^T @ q
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(io_dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=f32)

    # query blocks outside the band contribute nothing to this K/V
    # block's gradients — skip their matmuls
    _banded(causal, qi, ki, blk, window, _compute)

    @pl.when(qi == n_q - 1)
    def _flush():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(
    q_ref,  # (1, blk, D) — the fixed query block qi
    k_ref,  # (1, blk, D) — key block ki
    v_ref,  # (1, blk, Dv)
    do_ref,  # (1, blk, Dv)
    lse_ref,  # (1, blk, 128)
    delta_ref,  # (1, blk, 128)
    dq_ref,  # out (1, blk, D)
    dq_scr,  # VMEM (blk, D) f32
    *,
    causal: bool,
    window: Optional[int],
    blk: int,
    n_k: int,
    scale: Optional[float] = None,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr[:])

    def _compute(masked: bool):
        _, ds = _p_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          qi, ki, blk=blk, window=window, masked=masked,
                          scale=scale)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(q_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # key blocks outside the band are fully masked for this query
    # block — no dq contribution, skip the matmuls
    _banded(causal, qi, ki, blk, window, _compute)

    @pl.when(ki == n_k - 1)
    def _flush():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_impl(
    q, k, v, o, lse, do, dlse=None, *, causal: bool,
    window: Optional[int], interpret: bool, scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    bn, t, d = q.shape
    dv = v.shape[-1]
    bg = k.shape[0]
    group = bn // bg
    blk = block_for(t)
    n_blk = t // blk
    # delta = rowsum(do * o): cheap elementwise+reduce, plain XLA; ride
    # it in lane-replicated, matching lse's layout.  An lse cotangent
    # (the ring path differentiates through the per-block logsumexp)
    # folds in for free: d lse_i / d s_ij = p_ij, so
    # ds = p * (dp - delta + dlse) * scale — i.e. delta -= dlse.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[..., None], (bn, t, 128))
    clamp = dict(causal=causal, blk=blk, window=window)

    def q_rows(width):  # the dK/dV sweep's per-query-block operands
        return pl.BlockSpec((1, blk, width), lambda b, ki, qi: (
            b, _clamp_query_block(ki, qi, n_q=n_blk, **clamp), 0))

    def kspec(width):  # the fixed key block's operands
        return pl.BlockSpec((1, blk, width),
                            lambda b, ki, qi: (b // group, ki, 0))

    # one partial per query head; a group's partials are summed below, in
    # float32 where there is more than one
    part = q.dtype if group == 1 else jnp.float32
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, window=window,
                          blk=blk, n_q=n_blk, **_stated(scale)),
        name="flash_bwd_dkv",
        grid=(bn, n_blk, n_blk),
        in_specs=[q_rows(d), kspec(d), kspec(dv), q_rows(dv), q_rows(128),
                  q_rows(128)],
        out_specs=[
            pl.BlockSpec((1, blk, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, blk, dv), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, t, d), part),
            jax.ShapeDtypeStruct((bn, t, dv), part),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, d), jnp.float32),
            pltpu.VMEM((blk, dv), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    if group > 1:
        dk, dv_ = (x.reshape(bg, group, t, x.shape[-1]).sum(axis=1)
                   .astype(k.dtype) for x in (dk, dv_))

    def k_rows(b, qi, ki):
        return (b // group, _clamp_key_block(qi, ki, **clamp), 0)

    qspec2 = pl.BlockSpec((1, blk, d), lambda b, qi, ki: (b, qi, 0))
    dospec2 = pl.BlockSpec((1, blk, dv), lambda b, qi, ki: (b, qi, 0))
    kspec2 = pl.BlockSpec((1, blk, d), k_rows)
    vspec2 = pl.BlockSpec((1, blk, dv), k_rows)
    rspec2 = pl.BlockSpec((1, blk, 128), lambda b, qi, ki: (b, qi, 0))
    (dq,) = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, window=window,
                          blk=blk, n_k=n_blk, **_stated(scale)),
        name="flash_bwd_dq",
        grid=(bn, n_blk, n_blk),
        in_specs=[qspec2, kspec2, vspec2, dospec2, rspec2, rspec2],
        out_specs=[qspec2],
        out_shape=[jax.ShapeDtypeStruct((bn, t, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv_


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
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (128,))
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
    if not flash_supported(q.shape[-2], k.shape[-2], d):
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
