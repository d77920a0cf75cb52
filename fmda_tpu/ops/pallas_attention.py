"""Fused Pallas TPU flash-attention kernel for the attn model family.

The pure-jnp path (:func:`fmda_tpu.ops.attention.mha`) materialises the
(B, N, T, T) score matrix in HBM — at the long-context shape (B=16, N=4,
T=1024) that is ~256 MB of f32 traffic per layer per direction, and HBM
bandwidth, not the MXU, bounds the step.  This kernel is the standard
flash-attention restructuring of the SAME online-softmax recurrence the
module documents (ops/attention.py docstring; the ring path folds K/V
blocks with identical math, parallel/ring_attention.py:45-82): scores
only ever exist as a (128, 128) block in VMEM.

Forward — grid ``(B*N, T/128, T/128)`` (``dimension_semantics``
arbitrary: steps run sequentially, so VMEM scratch legitimately carries
the online state across the K axis)::

    s    = (q_blk @ k_blk^T) * scale           # MXU, f32 accumulate
    m'   = max(m, rowmax(s))
    corr = exp(m - m')
    p    = exp(s - m')                          # VPU, f32
    l    = l * corr + rowsum(p)
    acc  = acc * corr + p @ v_blk               # MXU
    at last K block:  o = acc / l,  L = m + log l

``L`` (the per-row logsumexp) is the only residual beyond the inputs and
``o`` — the backward recomputes ``p = exp(s - L)`` blockwise instead of
storing probabilities (the same fused-remat trade as the GRU/LSTM kernel
pairs, ops/pallas_gru.py).  Backward runs as two kernels over the same
block structure, the textbook split:

- **dK/dV sweep** — grid ``(B*N, T/128 [k], T/128 [q])``: for a fixed
  K/V block, walk the query blocks; ``dv += p^T @ do``,
  ``ds = p * (do @ v^T - delta) * scale``, ``dk += ds^T @ q``.
- **dQ sweep** — grid ``(B*N, T/128 [q], T/128 [k])``: for a fixed Q
  block, walk the key blocks; ``dq += ds @ k``.

``delta = rowsum(do * o)`` is cheap elementwise work computed outside in
plain XLA.  Masking uses a large-negative finite constant (not -inf) so
fully-masked causal blocks stay NaN-free; masked probabilities are
forced to exactly zero.  m/l/L/delta ride as 128-lane-replicated
``(rows, 128)`` tiles — Mosaic's tiling wants the last dim to be 128 or
the full array dim, and a (1, block) slab whose sublane dim is neither
8-divisible nor full does not lower (same constraint that forced the GRU
kernel time-major, ops/pallas_gru.py).

Support envelope (:func:`flash_supported`): self-attention with
``Tq == Tk``, ``T % 128 == 0``, no arbitrary mask (causal is in-kernel),
and D small enough that the per-block working set fits VMEM — in
practice D <= 512.  Everything else falls back to the jnp path via
:func:`fmda_tpu.ops.attention.mha`'s dispatch.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fmda_tpu.compat import CompilerParams

#: Q/K block edge.  128 = MXU tile edge = Mosaic lane count; T must be a
#: multiple (flash_supported gates on it).
_BLOCK = 128

#: Finite stand-in for -inf in masked score slots: far below any real
#: logit, but exp(finite - finite) stays a number (exp of ~-1e30 is 0.0
#: in f32 anyway); masked probabilities are additionally forced to 0 so
#: a fully-masked row cannot poison the state with exp(0)=1.
_NEG = -1e30


def flash_supported(q_len: int, k_len: int, d_head: int) -> bool:
    """Shape gate for the fused kernel (see module docstring)."""
    return (
        q_len == k_len
        and q_len % _BLOCK == 0
        and d_head <= 512
    )


def _causal_mask_block(qi, ki):
    """(BLOCK, BLOCK) bool keep-mask for query block qi vs key block ki,
    in global positions."""
    q_pos = qi * _BLOCK + jax.lax.broadcasted_iota(
        jnp.int32, (_BLOCK, _BLOCK), 0)
    k_pos = ki * _BLOCK + jax.lax.broadcasted_iota(
        jnp.int32, (_BLOCK, _BLOCK), 1)
    return q_pos >= k_pos


def _fwd_kernel(
    q_ref,  # (1, BLOCK, D)
    k_ref,  # (1, BLOCK, D)
    v_ref,  # (1, BLOCK, D)
    o_ref,  # out (1, BLOCK, D)
    lse_ref,  # out (1, BLOCK, 128) lane-replicated logsumexp
    m_scr,  # VMEM (BLOCK, 128) f32
    l_scr,  # VMEM (BLOCK, 128) f32
    acc_scr,  # VMEM (BLOCK, D) f32
    *,
    causal: bool,
    n_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr[:], _NEG)
        l_scr[:] = jnp.zeros_like(l_scr[:])
        acc_scr[:] = jnp.zeros_like(acc_scr[:])

    def _compute():
        f32 = jnp.float32
        q = q_ref[0]
        k = k_ref[0]
        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=f32
        ) * scale
        if causal:
            s = jnp.where(_causal_mask_block(qi, ki), s, _NEG)

        m_prev = m_scr[:, :1]  # (BLOCK, 1); lanes are replicated
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # exactly zero where masked (s==_NEG - m_new underflows to 0
        # anyway unless the whole row is masked and m_new==_NEG; this
        # kills that)
        p = jnp.where(s <= _NEG * 0.5, 0.0, p)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=f32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # blocks strictly above the diagonal are fully masked: skip their
        # MXU/VPU work entirely (round-4 advice: causal paid ~2x), the
        # state update is a no-op there by construction
        pl.when(ki <= qi)(_compute)
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # logsumexp residual; fully-masked rows keep _NEG (p recomputes
        # to 0 in backward)
        lse = jnp.where(l == 0.0, _NEG, m_scr[:, :1] + jnp.log(l_safe))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _fwd_impl(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool, interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """(BN, T, D) inputs -> (o (BN, T, D), lse (BN, T, 128))."""
    bn, t, d = q.shape
    n_blk = t // _BLOCK
    kernel = functools.partial(_fwd_kernel, causal=causal, n_k=n_blk)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bn, n_blk, n_blk),
        in_specs=[
            pl.BlockSpec((1, _BLOCK, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, _BLOCK, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, _BLOCK, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, _BLOCK, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, _BLOCK, 128), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, t, d), q.dtype),
            jax.ShapeDtypeStruct((bn, t, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((_BLOCK, 128), jnp.float32),
            pltpu.VMEM((_BLOCK, 128), jnp.float32),
            pltpu.VMEM((_BLOCK, d), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _dkv_kernel(
    q_ref,  # (1, BLOCK, D) — query block qi
    k_ref,  # (1, BLOCK, D) — the fixed key block ki
    v_ref,  # (1, BLOCK, D)
    do_ref,  # (1, BLOCK, D) — dO for query block qi
    lse_ref,  # (1, BLOCK, 128)
    delta_ref,  # (1, BLOCK, 128)
    dk_ref,  # out (1, BLOCK, D)
    dv_ref,  # out (1, BLOCK, D)
    dk_scr,  # VMEM (BLOCK, D) f32
    dv_scr,  # VMEM (BLOCK, D) f32
    *,
    causal: bool,
    n_q: int,
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr[:])
        dv_scr[:] = jnp.zeros_like(dv_scr[:])

    def _compute():
        f32 = jnp.float32
        q = q_ref[0]
        k = k_ref[0]
        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=f32
        ) * scale
        if causal:
            s = jnp.where(_causal_mask_block(qi, ki), s, _NEG)
        p = jnp.exp(s - lse_ref[0][:, :1])
        p = jnp.where(s <= _NEG * 0.5, 0.0, p)

        do = do_ref[0]
        io_dtype = q_ref.dtype
        # dv += p^T @ do   (contract the query rows)
        p_c = p.astype(io_dtype)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p_c, do, (((0,), (0,)), ((), ())), preferred_element_type=f32)
        # ds = p * (do @ v^T - delta) * scale
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=f32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        # dk += ds^T @ q
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(io_dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=f32)

    if causal:
        # query blocks above the diagonal contribute nothing to this
        # K/V block's gradients — skip their matmuls
        pl.when(qi >= ki)(_compute)
    else:
        _compute()

    @pl.when(qi == n_q - 1)
    def _flush():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(
    q_ref,  # (1, BLOCK, D) — the fixed query block qi
    k_ref,  # (1, BLOCK, D) — key block ki
    v_ref,  # (1, BLOCK, D)
    do_ref,  # (1, BLOCK, D)
    lse_ref,  # (1, BLOCK, 128)
    delta_ref,  # (1, BLOCK, 128)
    dq_ref,  # out (1, BLOCK, D)
    dq_scr,  # VMEM (BLOCK, D) f32
    *,
    causal: bool,
    n_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr[:])

    def _compute():
        f32 = jnp.float32
        q = q_ref[0]
        k = k_ref[0]
        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=f32
        ) * scale
        if causal:
            s = jnp.where(_causal_mask_block(qi, ki), s, _NEG)
        p = jnp.exp(s - lse_ref[0][:, :1])
        p = jnp.where(s <= _NEG * 0.5, 0.0, p)

        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=f32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(q_ref.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)

    if causal:
        # key blocks past the diagonal are fully masked for this query
        # block — no dq contribution, skip the matmuls
        pl.when(ki <= qi)(_compute)
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _flush():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_impl(
    q, k, v, o, lse, do, dlse=None, *, causal: bool, interpret: bool
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    bn, t, d = q.shape
    n_blk = t // _BLOCK
    # delta = rowsum(do * o): cheap elementwise+reduce, plain XLA; ride
    # it in lane-replicated, matching lse's layout.  An lse cotangent
    # (the ring path differentiates through the per-block logsumexp)
    # folds in for free: d lse_i / d s_ij = p_ij, so
    # ds = p * (dp - delta + dlse) * scale — i.e. delta -= dlse.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[..., None], (bn, t, 128))

    qspec = pl.BlockSpec((1, _BLOCK, d), lambda b, ki, qi: (b, qi, 0))
    kspec = pl.BlockSpec((1, _BLOCK, d), lambda b, ki, qi: (b, ki, 0))
    rspec = pl.BlockSpec((1, _BLOCK, 128), lambda b, ki, qi: (b, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, n_q=n_blk),
        name="flash_bwd_dkv",
        grid=(bn, n_blk, n_blk),
        in_specs=[qspec, kspec, kspec, qspec, rspec, rspec],
        out_specs=[
            pl.BlockSpec((1, _BLOCK, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, _BLOCK, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, t, d), q.dtype),
            jax.ShapeDtypeStruct((bn, t, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((_BLOCK, d), jnp.float32),
            pltpu.VMEM((_BLOCK, d), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    qspec2 = pl.BlockSpec((1, _BLOCK, d), lambda b, qi, ki: (b, qi, 0))
    kspec2 = pl.BlockSpec((1, _BLOCK, d), lambda b, qi, ki: (b, ki, 0))
    rspec2 = pl.BlockSpec((1, _BLOCK, 128), lambda b, qi, ki: (b, qi, 0))
    (dq,) = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, n_k=n_blk),
        name="flash_bwd_dq",
        grid=(bn, n_blk, n_blk),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rspec2, rspec2],
        out_specs=[qspec2],
        out_shape=[jax.ShapeDtypeStruct((bn, t, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((_BLOCK, d), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, interpret):
    o, lse = _fwd_impl(q, k, v, causal=causal, interpret=interpret)
    return o, lse[..., 0]


def _flash_fwd(q, k, v, causal, interpret):
    o, lse = _fwd_impl(q, k, v, causal=causal, interpret=interpret)
    return (o, lse[..., 0]), (q, k, v, o, lse)


def _flash_bwd(causal, interpret, residuals, cts):
    q, k, v, o, lse = residuals
    do, dlse = cts
    return _bwd_impl(q, k, v, o, lse, do, dlse, causal=causal,
                     interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Fused-kernel multi-head attention, (B, N, T, D) -> (B, N, T, D).

    Numerics match :func:`fmda_tpu.ops.attention.mha` (same online
    softmax, f32 accumulation); parity is test-locked in interpret mode
    and on hardware (tests/test_pallas_attention.py).  Call through
    ``mha(..., )``'s dispatch rather than directly unless you have
    already checked :func:`flash_supported`.
    """
    out, _ = flash_attention_with_lse(
        q, k, v, causal=causal, interpret=interpret)
    return out


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused attention returning ``(o, lse)`` — o (B, N, T, D) in q's
    dtype plus the per-row logsumexp (B, N, T) f32.

    The lse is what makes the output *mergeable*: two attention results
    over disjoint key segments combine exactly via
    :func:`fmda_tpu.ops.attention.merge_softmax_segments`, which is how
    ring attention folds one fused-kernel call per ring step
    (parallel/ring_attention.py) instead of materialising jnp score
    blocks.  Differentiable in both outputs (the lse cotangent folds
    into the backward's delta term).  Fully-masked rows report
    ``lse = -1e30`` (the kernel's finite -inf sentinel) and ``o = 0``.
    """
    b, n, t, d = q.shape
    if not flash_supported(q.shape[-2], k.shape[-2], d):
        raise ValueError(
            f"flash kernel unsupported for Tq={q.shape[-2]} "
            f"Tk={k.shape[-2]} D={d}; gate on flash_supported()")
    fold = lambda x: x.reshape(b * n, t, d)
    out, lse = _flash(fold(q), fold(k), fold(v), causal, interpret)
    return out.reshape(b, n, t, d), lse.reshape(b, n, t)
