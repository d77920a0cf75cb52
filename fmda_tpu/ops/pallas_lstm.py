"""Fused Pallas TPU kernel pair for the LSTM recurrence.

The LSTM twin of :mod:`fmda_tpu.ops.pallas_gru`, sharing its blocked
design (see that module's docstring for the layout/tiling rationale):
time-major ``(T, B, 4H)`` blocks, ``block_t`` timesteps unrolled per grid
step with ``dimension_semantics=("arbitrary",)``, VMEM-resident carries.
The differences are the cell's: TWO carried states (h and c) in VMEM
scratch, gates packed ``[i, f, g, o]`` (torch convention, matching
:func:`fmda_tpu.ops.lstm.lstm_gates` weight-for-weight), and the forward
kernel emits the per-step cell states ``cs`` alongside ``hs`` so the
backward kernel can recompute gates from (h_prev, xp) and chain
``dc`` through ``f`` without storing any per-step gate tensor in HBM
(fused rematerialisation, same trade as the GRU pair).

Backward recurrence carried in VMEM (f32), processing steps in reverse
order::

    dh   = dh_carry + dhs_t
    do   = dh * tanh(c_t);            do_pre = do * o * (1 - o)
    dc   = dc_carry + dh * o * (1 - tanh(c_t)^2)
    di   = dc * g;  df = dc * c_prev;  dg = dc * i
    dxp_t = [di*i*(1-i), df*f*(1-f), dg*(1-g^2), do_pre]
    dh_carry = dxp_t @ W_hh;  dc_carry = dc * f

with ``dW_hh``/``db`` accumulated across the block in VMEM registers and
flushed once per grid step into revisited output blocks.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fmda_tpu.compat import CompilerParams

from fmda_tpu.ops.pallas_gru import _VMEM_BUDGET, _default_block_t


def _fwd_const_bytes(batch: int, hidden: int, itemsize: int) -> int:
    """Grid-constant VMEM residents of the forward kernel: h0/c0 +
    h_last/c_last + h/c scratch (6 x B,H), w_hh_t (H,4H), b_hh (4H)."""
    return itemsize * (
        6 * batch * hidden + 4 * hidden * hidden + 4 * hidden)


def _bwd_const_bytes(batch: int, hidden: int, itemsize: int) -> int:
    """Grid-constant VMEM residents of the backward kernel: both weight
    copies (w_hh + w_hh_t, 4H*H each, I/O dtype) plus the f32
    accumulators (dh_last/dc_last/dh0/dc0 + 2 scratch: 6 x B,H;
    dwt: H,4H; db: 4H)."""
    f32 = 4
    return (
        itemsize * 8 * hidden * hidden
        + f32 * (6 * batch * hidden + 4 * hidden * hidden + 4 * hidden)
    )


def kernel_supported(
    batch: int, seq_len: int, hidden: int, itemsize: int
) -> bool:
    """LSTM twin of :func:`fmda_tpu.ops.pallas_gru.kernel_supported`:
    True when the fused kernel pair fits the VMEM budget at block_t=1.
    The LSTM's working set is ~4/3 the GRU's (4H gate blocks, two
    carried states), so its feasibility boundary sits at a slightly
    smaller H."""
    # fwd time-varying at K=1: xp (4H) + hs (H) + cs (H) = 6*B*H elems
    fwd = itemsize * 2 * (6 * batch * hidden) + _fwd_const_bytes(
        batch, hidden, itemsize)
    # bwd: xp + dxp (4H each) + hprev/cprev/cnew/dhs (H each) = 12*B*H
    bwd = itemsize * 2 * (12 * batch * hidden) + _bwd_const_bytes(
        batch, hidden, itemsize)
    return max(fwd, bwd) <= _VMEM_BUDGET


def _lstm_step_kernel(
    xp_ref,  # (K, B, 4H) this block's input projections
    h0_ref,  # (B, H)
    c0_ref,  # (B, H)
    w_hh_t_ref,  # (H, 4H) recurrent weights, pre-transposed
    b_hh_ref,  # (1, 4H)
    hs_ref,  # out: (K, B, H)
    cs_ref,  # out: (K, B, H) per-step cell states (backward residual)
    h_last_ref,  # out: (B, H)
    c_last_ref,  # out: (B, H)
    h_scratch,  # VMEM carry (B, H)
    c_scratch,  # VMEM carry (B, H)
    *,
    block_t: int,
    reverse: bool,
):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scratch[:] = h0_ref[:]
        c_scratch[:] = c0_ref[:]

    h = h_scratch[:]
    c = c_scratch[:]
    hidden = h.shape[-1]
    f32 = jnp.float32
    for k in range(block_t):
        kk = block_t - 1 - k if reverse else k
        xp_t = xp_ref[kk].astype(f32)
        hp = jnp.dot(
            h, w_hh_t_ref[:], preferred_element_type=f32
        ) + b_hh_ref[:].astype(f32)
        s = xp_t + hp
        i = jax.nn.sigmoid(s[:, :hidden])
        f = jax.nn.sigmoid(s[:, hidden : 2 * hidden])
        g = jnp.tanh(s[:, 2 * hidden : 3 * hidden])
        o = jax.nn.sigmoid(s[:, 3 * hidden :])
        c_new = f * c.astype(f32) + i * g
        h_new = (o * jnp.tanh(c_new)).astype(h.dtype)
        c_new = c_new.astype(h.dtype)
        hs_ref[kk] = h_new
        cs_ref[kk] = c_new
        h, c = h_new, c_new

    h_scratch[:] = h
    c_scratch[:] = c
    h_last_ref[:] = h
    c_last_ref[:] = c


def _lstm_fwd_impl(
    xp, h0, c0, w_hh, b_hh, *, reverse: bool, interpret: bool
):
    batch, seq_len, _ = xp.shape
    hidden = h0.shape[-1]
    w_hh_t = jnp.swapaxes(w_hh, 0, 1)  # (H, 4H)
    b_hh_2d = b_hh[None, :]
    xp_tm = jnp.swapaxes(xp, 0, 1)  # (T, B, 4H)

    # fwd per-step rows: xp 4H + hs H + cs H = 6H
    block_t = _default_block_t(
        seq_len, batch, hidden, xp.dtype.itemsize, units_per_step=6,
        const_bytes=_fwd_const_bytes(batch, hidden, xp.dtype.itemsize))
    n_blocks = seq_len // block_t

    if reverse:
        time_map = lambda t: (n_blocks - 1 - t, 0, 0)
    else:
        time_map = lambda t: (t, 0, 0)

    kernel = functools.partial(
        _lstm_step_kernel, block_t=block_t, reverse=reverse)
    hs_tm, cs_tm, h_last, c_last = pl.pallas_call(
        kernel,
        name="lstm_scan_fwd",
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_t, batch, 4 * hidden), time_map),
            pl.BlockSpec((batch, hidden), lambda t: (0, 0)),
            pl.BlockSpec((batch, hidden), lambda t: (0, 0)),
            pl.BlockSpec((hidden, 4 * hidden), lambda t: (0, 0)),
            pl.BlockSpec((1, 4 * hidden), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, batch, hidden), time_map),
            pl.BlockSpec((block_t, batch, hidden), time_map),
            pl.BlockSpec((batch, hidden), lambda t: (0, 0)),
            pl.BlockSpec((batch, hidden), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((seq_len, batch, hidden), xp.dtype),
            jax.ShapeDtypeStruct((seq_len, batch, hidden), xp.dtype),
            jax.ShapeDtypeStruct((batch, hidden), xp.dtype),
            jax.ShapeDtypeStruct((batch, hidden), xp.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((batch, hidden), xp.dtype),
            pltpu.VMEM((batch, hidden), xp.dtype),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(
        xp_tm, h0.astype(xp.dtype), c0.astype(xp.dtype),
        w_hh_t.astype(xp.dtype), b_hh_2d.astype(xp.dtype),
    )
    return (
        jnp.swapaxes(hs_tm, 0, 1),
        jnp.swapaxes(cs_tm, 0, 1),
        h_last,
        c_last,
    )


def _lstm_bwd_kernel(
    xp_ref,  # (K, B, 4H)
    hprev_ref,  # (K, B, H) hidden entering each step
    cprev_ref,  # (K, B, H) cell entering each step
    cnew_ref,  # (K, B, H) cell leaving each step
    dhs_ref,  # (K, B, H)
    dhlast_ref,  # (B, H)
    dclast_ref,  # (B, H)
    w_hh_ref,  # (4H, H) (for the dh chain)
    w_hh_t_ref,  # (H, 4H) (for the gate recompute)
    b_hh_ref,  # (1, 4H)
    dxp_ref,  # out: (K, B, 4H)
    dh0_ref,  # out: (B, H)
    dc0_ref,  # out: (B, H)
    dwt_ref,  # out: (H, 4H) accumulated
    db_ref,  # out: (1, 4H) accumulated
    dh_scratch,  # VMEM carry (B, H) f32
    dc_scratch,  # VMEM carry (B, H) f32
    *,
    block_t: int,
    reverse: bool,
):
    idx = pl.program_id(0)

    @pl.when(idx == 0)
    def _init():
        dh_scratch[:] = dhlast_ref[:]
        dc_scratch[:] = dclast_ref[:]
        dwt_ref[:] = jnp.zeros_like(dwt_ref[:])
        db_ref[:] = jnp.zeros_like(db_ref[:])

    hidden = hprev_ref.shape[-1]
    f32 = jnp.float32
    io_dtype = dxp_ref.dtype
    dh = dh_scratch[:].astype(f32)
    dc = dc_scratch[:].astype(f32)
    dwt_acc = jnp.zeros_like(dwt_ref[:])
    db_acc = jnp.zeros_like(db_ref[:])
    for k in range(block_t):
        kk = k if reverse else block_t - 1 - k
        xp_t = xp_ref[kk].astype(f32)
        c_prev = cprev_ref[kk].astype(f32)

        # gate recompute — identical math to the forward kernel
        hp = jnp.dot(
            hprev_ref[kk], w_hh_t_ref[:], preferred_element_type=f32
        ) + b_hh_ref[:].astype(f32)
        s = xp_t + hp
        i = jax.nn.sigmoid(s[:, :hidden])
        f = jax.nn.sigmoid(s[:, hidden : 2 * hidden])
        g = jnp.tanh(s[:, 2 * hidden : 3 * hidden])
        o = jax.nn.sigmoid(s[:, 3 * hidden :])
        tanh_c = jnp.tanh(cnew_ref[kk].astype(f32))

        dh = dh + dhs_ref[kk].astype(f32)
        do = dh * tanh_c
        dc = dc + dh * o * (1.0 - tanh_c * tanh_c)
        di = dc * g
        df = dc * c_prev
        dg = dc * i
        dgates = jnp.concatenate(
            [
                di * i * (1.0 - i),
                df * f * (1.0 - f),
                dg * (1.0 - g * g),
                do * o * (1.0 - o),
            ],
            axis=-1,
        )
        dxp_ref[kk] = dgates.astype(io_dtype)
        # same rounded dgates feeds the dh chain and the weight/bias grads
        # (see the GRU bwd kernel's dtype note); accumulators stay f32
        dg_c = dgates.astype(io_dtype)
        dh = jnp.dot(dg_c, w_hh_ref[:], preferred_element_type=f32)
        dc = dc * f
        dwt_acc += jax.lax.dot_general(
            hprev_ref[kk], dg_c, (((0,), (0,)), ((), ())),
            preferred_element_type=f32,
        )
        db_acc += jnp.sum(dg_c.astype(f32), axis=0, keepdims=True)
    dwt_ref[:] += dwt_acc
    db_ref[:] += db_acc
    dh_scratch[:] = dh
    dc_scratch[:] = dc
    dh0_ref[:] = dh
    dc0_ref[:] = dc


def _lstm_bwd_impl(
    xp, h0, c0, w_hh, b_hh, hs, cs, dh_last, dc_last, dhs,
    *, reverse: bool, interpret: bool
):
    batch, seq_len, _ = xp.shape
    hidden = h0.shape[-1]
    dtype = xp.dtype
    w_hh_t = jnp.swapaxes(w_hh, 0, 1)
    b_hh_2d = b_hh[None, :]

    # state *entering* each timestep, in time order (h0/c0 precede the
    # first-processed step: index 0 forward, T-1 reversed)
    if reverse:
        h_prev = jnp.concatenate([hs[:, 1:], h0[:, None]], axis=1)
        c_prev = jnp.concatenate([cs[:, 1:], c0[:, None]], axis=1)
    else:
        h_prev = jnp.concatenate([h0[:, None], hs[:, :-1]], axis=1)
        c_prev = jnp.concatenate([c0[:, None], cs[:, :-1]], axis=1)
    xp_tm = jnp.swapaxes(xp, 0, 1)
    hprev_tm = jnp.swapaxes(h_prev, 0, 1)
    cprev_tm = jnp.swapaxes(c_prev, 0, 1)
    cnew_tm = jnp.swapaxes(cs, 0, 1)
    dhs_tm = jnp.swapaxes(dhs, 0, 1)

    # bwd per-step rows: xp 4H + hprev/cprev/cnew/dhs 4x H + dxp 4H = 12H
    block_t = _default_block_t(
        seq_len, batch, hidden, xp.dtype.itemsize, units_per_step=12,
        const_bytes=_bwd_const_bytes(batch, hidden, xp.dtype.itemsize))
    n_blocks = seq_len // block_t

    if reverse:
        time_map = lambda i: (i, 0, 0)
    else:
        time_map = lambda i: (n_blocks - 1 - i, 0, 0)

    kernel = functools.partial(
        _lstm_bwd_kernel, block_t=block_t, reverse=reverse)
    dxp_tm, dh0, dc0, dwt, db = pl.pallas_call(
        kernel,
        name="lstm_scan_bwd",
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_t, batch, 4 * hidden), time_map),
            pl.BlockSpec((block_t, batch, hidden), time_map),
            pl.BlockSpec((block_t, batch, hidden), time_map),
            pl.BlockSpec((block_t, batch, hidden), time_map),
            pl.BlockSpec((block_t, batch, hidden), time_map),
            pl.BlockSpec((batch, hidden), lambda i: (0, 0)),
            pl.BlockSpec((batch, hidden), lambda i: (0, 0)),
            pl.BlockSpec((4 * hidden, hidden), lambda i: (0, 0)),
            pl.BlockSpec((hidden, 4 * hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, 4 * hidden), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, batch, 4 * hidden), time_map),
            pl.BlockSpec((batch, hidden), lambda i: (0, 0)),
            pl.BlockSpec((batch, hidden), lambda i: (0, 0)),
            pl.BlockSpec((hidden, 4 * hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, 4 * hidden), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((seq_len, batch, 4 * hidden), dtype),
            # f32 accumulators whatever the I/O dtype (GRU bwd note)
            jax.ShapeDtypeStruct((batch, hidden), jnp.float32),
            jax.ShapeDtypeStruct((batch, hidden), jnp.float32),
            jax.ShapeDtypeStruct((hidden, 4 * hidden), jnp.float32),
            jax.ShapeDtypeStruct((1, 4 * hidden), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((batch, hidden), jnp.float32),
            pltpu.VMEM((batch, hidden), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(
        xp_tm, hprev_tm, cprev_tm, cnew_tm, dhs_tm,
        dh_last.astype(jnp.float32), dc_last.astype(jnp.float32),
        w_hh.astype(dtype), w_hh_t.astype(dtype), b_hh_2d.astype(dtype),
    )
    return (
        jnp.swapaxes(dxp_tm, 0, 1).astype(xp.dtype),
        dh0.astype(h0.dtype),
        dc0.astype(c0.dtype),
        jnp.swapaxes(dwt, 0, 1).astype(w_hh.dtype),
        db[0].astype(b_hh.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _lstm_scan_pallas(xp, h0, c0, w_hh, b_hh, reverse, interpret):
    hs, cs, h_last, c_last = _lstm_fwd_impl(
        xp, h0, c0, w_hh, b_hh, reverse=reverse, interpret=interpret
    )
    return (h_last, c_last), hs


def _vjp_fwd(xp, h0, c0, w_hh, b_hh, reverse, interpret):
    hs, cs, h_last, c_last = _lstm_fwd_impl(
        xp, h0, c0, w_hh, b_hh, reverse=reverse, interpret=interpret
    )
    return ((h_last, c_last), hs), (xp, h0, c0, w_hh, b_hh, hs, cs)


def _vjp_bwd(reverse, interpret, residuals, cotangents):
    xp, h0, c0, w_hh, b_hh, hs, cs = residuals
    (dh_last, dc_last), dhs = cotangents
    return _lstm_bwd_impl(
        xp, h0, c0, w_hh, b_hh, hs, cs, dh_last, dc_last, dhs,
        reverse=reverse, interpret=interpret,
    )


_lstm_scan_pallas.defvjp(_vjp_fwd, _vjp_bwd)


def lstm_scan_pallas(
    xp: jax.Array,
    h0: jax.Array,
    c0: jax.Array,
    w_hh: jax.Array,
    b_hh: jax.Array,
    *,
    reverse: bool = False,
    interpret: bool = False,
) -> Tuple[Tuple[jax.Array, jax.Array], jax.Array]:
    """Drop-in fused-kernel replacement for
    :func:`fmda_tpu.ops.lstm.lstm_scan` (same signature minus ``mask``):
    returns ((h_last, c_last), hs)."""
    return _lstm_scan_pallas(xp, h0, c0, w_hh, b_hh, reverse, interpret)
