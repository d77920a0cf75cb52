"""Fused Pallas TPU kernel for the GRU recurrence.

The scan is the only part of the model XLA cannot tile freely: the hidden
state is a loop-carried dependency.  The lax.scan path round-trips the carry
through XLA's loop machinery each step; this kernel instead keeps ``h``
resident in a VMEM scratch buffer for the whole sequence and processes
``block_t`` timesteps per grid step:

- grid = (T / block_t,) with ``dimension_semantics=("arbitrary",)``: grid
  steps execute sequentially on the TPU core, so VMEM scratch legitimately
  carries state across steps;
- ``block_t`` is the largest divisor of T whose block fits a conservative
  VMEM budget (the f32 flagship B=256 T=30 runs as 2 forward / 3 backward
  grid steps; smaller B or bf16 collapse it to one).  Per-grid-step
  DMA/barrier overhead — which dominates at small (B, H), where each
  step's matmul is microseconds — is amortized over block_t unrolled
  in-kernel steps whose operands never leave VMEM (kernel against
  ``lax.scan`` on the chip: not measured in any cell — ROADMAP S3;
  ``PERF.md`` and the ledger, not this docstring, are the performance
  record);
- the sequence is laid out **time-major** ``(T, B, 3H)`` so each grid
  step's block is ``(block_t, B, 3H)`` — its last two dims span the
  array's full (B, 3H) plane, satisfying Mosaic's divisible-by-(8, 128)-
  or-full-dim tiling rule for *any* batch (validated against the real
  Mosaic TPU lowering via jax.export down to B = 2, covering the
  sub-batch microbatches of the pipelined sp scan), where the
  batch-major ``(B, 1, 3H)`` block (sublane dim 1) does not lower at
  all;
- per step: one (B,H) x (H,3H) matmul on the MXU (the input projection
  ``x @ W_ih^T`` is NOT in the kernel — it is a big batched matmul XLA
  already tiles perfectly, computed once outside; see fmda_tpu.ops.gru);
- gate sigmoid/tanh fusion on the VPU, h never leaves VMEM;
- ``reverse=True`` runs the same kernel with a mirrored time index map
  (for the backward direction of the bidirectional model).

VMEM footprint per grid step is the block working set, independent of T:
xp (B x 3H) + hs (B x H) + h scratch/h0/h_last (B x H each) + weights
(H x 3H) ≈ 0.9 MB at the flagship B=256, H=32 in f32 — far inside the
~16 MB/core budget; batch blocking only becomes necessary past B ~ 10k.

Gate math and packing match :func:`fmda_tpu.ops.gru.gru_gates` exactly
(torch-convention ``[r, z, n]``), verified in tests against the lax.scan
path, including gradients.

The backward pass is a Pallas kernel too (``_gru_bwd_kernel``): a
reverse-processing-order grid that carries ``dh`` in VMEM scratch,
*recomputes* the gates in-kernel from the saved ``hs`` (fused
rematerialisation — residuals are just the forward outputs, no per-step
gate storage in HBM), and accumulates the weight/bias gradients in VMEM
output blocks revisited across all grid steps.  Per step it runs three
MXU matmuls (gate recompute, ``dh`` chain through the recurrent weights,
and the ``dW_hh`` outer-product accumulation) plus VPU gate algebra, so a
full train step never leaves the fused path.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fmda_tpu.compat import CompilerParams


# Conservative per-core VMEM budget for a kernel's whole working set
# (blocks + constants + scratch).  Real VMEM is ~16 MB/core; staying
# well under leaves room for Mosaic's own temporaries and the gate
# algebra's f32 upcasts.
_VMEM_BUDGET = 12 * 1024 * 1024


def _fwd_const_bytes(batch: int, hidden: int, itemsize: int) -> int:
    """Grid-constant VMEM residents of the forward kernel: h0 + h_last +
    h scratch (B,H each), w_hh_t (H,3H), b_hh (3H)."""
    return itemsize * (3 * batch * hidden + 3 * hidden * hidden + 3 * hidden)


def _bwd_const_bytes(batch: int, hidden: int, itemsize: int) -> int:
    """Grid-constant VMEM residents of the backward kernel: both weight
    copies (w_hh + w_hh_t, 3H*H each, I/O dtype) plus the f32
    accumulators (dhlast, dh0, dh scratch: B,H; dwt: H,3H; db: 3H)."""
    f32 = 4
    return (
        itemsize * 6 * hidden * hidden
        + f32 * (3 * batch * hidden + 3 * hidden * hidden + 3 * hidden)
    )


def kernel_supported(
    batch: int, seq_len: int, hidden: int, itemsize: int
) -> bool:
    """True when the fused kernel *pair* (forward + backward) fits the
    VMEM budget at the minimum block size (block_t=1).

    This is the per-shape gate behind automatic kernel-vs-scan selection
    (:func:`fmda_tpu.ops.gru.select_scan_fn`): the kernel keeps the full
    recurrent weights and f32 gradient accumulators resident in VMEM for
    the whole sequence, so past H ~ 512 (f32) the backward's 6*H^2
    weight copies + 3*H^2 f32 dW accumulator alone outgrow the ~16 MB
    core budget and ``lax.scan`` — whose per-step matmul is MXU-shaped
    at such H anyway — is the right path.  The crossover itself is not
    measured on the chip (``PERF.md`` §7, per-kernel roofline).
    """
    # time-varying blocks at K=1, double-buffered by Mosaic:
    # fwd: xp (1,B,3H) in + hs (1,B,H) out -> 8*B*H elems
    fwd = itemsize * 2 * (4 * batch * hidden) + _fwd_const_bytes(
        batch, hidden, itemsize)
    # bwd: xp + dxp (3H each) + hprev + dhs (H each) -> 16*B*H elems
    bwd = itemsize * 2 * (8 * batch * hidden) + _bwd_const_bytes(
        batch, hidden, itemsize)
    return max(fwd, bwd) <= _VMEM_BUDGET


def _default_block_t(
    seq_len: int, batch: int, hidden: int, itemsize: int,
    units_per_step: int = 4, const_bytes: int = 0,
) -> int:
    """Largest divisor of T whose per-block working set stays inside a
    conservative VMEM budget.  ``units_per_step`` counts the H-sized rows
    a block carries per timestep (forward: xp 3H + hs H = 4; backward:
    xp 3H + hprev H + dhs H + dxp 3H = 8), doubled for Mosaic's block
    double-buffering.  ``const_bytes`` (the grid-constant residents:
    weights, f32 accumulators) is charged against the budget first, so
    large-H shapes pick smaller blocks instead of overflowing VMEM.
    T=1 always divides, so the fallback is the one-step-per-grid-step
    kernel; at the f32 flagship (B=256, T=30) this yields block_t=15
    forward / 10 backward (2 / 3 grid steps)."""
    budget = max(_VMEM_BUDGET // 2 - const_bytes, 0)
    per_step = batch * units_per_step * hidden * itemsize * 2
    cap = max(1, budget // max(per_step, 1))
    # unroll bound: past ~64 in-kernel steps the per-grid-step overhead is
    # already amortized away, while Mosaic compile time grows superlinearly
    # with the unroll (a 256-step unroll at a 4k-step shape compiled for
    # over 900 s; 64 compiles in seconds)
    cap = min(cap, 64)
    best = 1
    for d in range(1, seq_len + 1):
        if seq_len % d == 0 and d <= cap:
            best = d
    return best


def _gru_step_kernel(
    xp_ref,  # (K, B, 3H) this block's input projections
    h0_ref,  # (B, H) initial hidden
    w_hh_t_ref,  # (H, 3H) recurrent weights, pre-transposed
    b_hh_ref,  # (1, 3H)
    hs_ref,  # out: (K, B, H) this block's hiddens
    h_last_ref,  # out: (B, H) final hidden (written every block, last wins)
    h_scratch,  # VMEM carry (B, H)
    *,
    block_t: int,
    reverse: bool,
):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scratch[:] = h0_ref[:]

    h = h_scratch[:]
    hidden = h.shape[-1]
    # gate algebra in f32 on the VPU regardless of the I/O dtype: the MXU
    # matmul already accumulates f32, and Mosaic rejects mixed-dtype
    # scalar broadcasts (e.g. sigmoid's constants) on bf16 vectors
    f32 = jnp.float32
    # Unrolled walk over the block's timesteps: the whole block lives in
    # VMEM, so inter-step cost is pure compute — the per-grid-step
    # DMA/barrier overhead that dominates at small (B, H) is amortized
    # over block_t steps.  Blocks arrive end-first when reverse, and the
    # in-block walk mirrors to match.
    for k in range(block_t):
        kk = block_t - 1 - k if reverse else k
        xp_t = xp_ref[kk].astype(f32)
        hp = jnp.dot(
            h, w_hh_t_ref[:], preferred_element_type=f32
        ) + b_hh_ref[:].astype(f32)
        r = jax.nn.sigmoid(xp_t[:, :hidden] + hp[:, :hidden])
        z = jax.nn.sigmoid(
            xp_t[:, hidden : 2 * hidden] + hp[:, hidden : 2 * hidden])
        n = jnp.tanh(xp_t[:, 2 * hidden :] + r * hp[:, 2 * hidden :])
        h_new = ((1.0 - z) * n + z * h.astype(f32)).astype(h.dtype)
        hs_ref[kk] = h_new
        h = h_new

    h_scratch[:] = h
    h_last_ref[:] = h


def _gru_scan_pallas_fwd_impl(
    xp: jax.Array,
    h0: jax.Array,
    w_hh: jax.Array,
    b_hh: jax.Array,
    *,
    reverse: bool,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    batch, seq_len, _ = xp.shape
    hidden = h0.shape[-1]
    w_hh_t = jnp.swapaxes(w_hh, 0, 1)  # (H, 3H): dot(h, w_hh_t)
    b_hh_2d = b_hh[None, :]
    # time-major for the kernel: per-step blocks carry (B, 3H) in their
    # last two dims, the only layout Mosaic can tile for B % 8 == 0
    xp_tm = jnp.swapaxes(xp, 0, 1)  # (T, B, 3H)

    block_t = _default_block_t(
        seq_len, batch, hidden, xp.dtype.itemsize,
        const_bytes=_fwd_const_bytes(batch, hidden, xp.dtype.itemsize))
    n_blocks = seq_len // block_t

    # block index map (units of blocks): grid step t touches block t
    # forward, block n_blocks-1-t reversed (in-block order mirrored by
    # the kernel)
    if reverse:
        time_map = lambda t: (n_blocks - 1 - t, 0, 0)
    else:
        time_map = lambda t: (t, 0, 0)

    kernel = functools.partial(
        _gru_step_kernel, block_t=block_t, reverse=reverse)
    hs_tm, h_last = pl.pallas_call(
        kernel,
        name="gru_scan_fwd",
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_t, batch, 3 * hidden), time_map),
            pl.BlockSpec((batch, hidden), lambda t: (0, 0)),
            pl.BlockSpec((hidden, 3 * hidden), lambda t: (0, 0)),
            pl.BlockSpec((1, 3 * hidden), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, batch, hidden), time_map),
            pl.BlockSpec((batch, hidden), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((seq_len, batch, hidden), xp.dtype),
            jax.ShapeDtypeStruct((batch, hidden), xp.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((batch, hidden), xp.dtype)],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(xp_tm, h0.astype(xp.dtype), w_hh_t.astype(xp.dtype), b_hh_2d.astype(xp.dtype))
    return jnp.swapaxes(hs_tm, 0, 1), h_last


def _gru_bwd_kernel(
    xp_ref,  # (K, B, 3H) this block's input projections
    hprev_ref,  # (K, B, H) hidden entering each step (h0 at the first step)
    dhs_ref,  # (K, B, H) cotangent of this block's hs outputs
    dhlast_ref,  # (B, H) cotangent of h_last
    w_hh_ref,  # (3H, H) recurrent weights (for the dh chain)
    w_hh_t_ref,  # (H, 3H) transposed (for the gate recompute)
    b_hh_ref,  # (1, 3H)
    dxp_ref,  # out: (K, B, 3H) grad of this block's input projections
    dh0_ref,  # out: (B, H) grad of h0 (written every block, last wins)
    dwt_ref,  # out: (H, 3H) grad of w_hh_t, accumulated across steps
    db_ref,  # out: (1, 3H) grad of b_hh, accumulated across steps
    dh_scratch,  # VMEM carry (B, H)
    *,
    block_t: int,
    reverse: bool,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dh_scratch[:] = dhlast_ref[:]
        dwt_ref[:] = jnp.zeros_like(dwt_ref[:])
        db_ref[:] = jnp.zeros_like(db_ref[:])

    hidden = hprev_ref.shape[-1]
    f32 = jnp.float32
    io_dtype = dxp_ref.dtype
    dh = dh_scratch[:].astype(f32)
    dwt_acc = jnp.zeros_like(dwt_ref[:])
    db_acc = jnp.zeros_like(db_ref[:])
    # Unrolled walk in reverse *processing* order within the block (the
    # mirror of the forward kernel's walk); blocks arrive in reverse
    # processing order via the index map.  dwt/db accumulate into VMEM
    # registers across the block, hitting the revisited output block once.
    for k in range(block_t):
        kk = k if reverse else block_t - 1 - k
        # all gate/cotangent algebra in f32 (see forward kernel note)
        h_prev = hprev_ref[kk].astype(f32)
        xp_t = xp_ref[kk].astype(f32)

        # gate recompute — identical math to the forward kernel
        hp = jnp.dot(
            hprev_ref[kk], w_hh_t_ref[:], preferred_element_type=f32
        ) + b_hh_ref[:].astype(f32)
        r = jax.nn.sigmoid(xp_t[:, :hidden] + hp[:, :hidden])
        z = jax.nn.sigmoid(
            xp_t[:, hidden : 2 * hidden] + hp[:, hidden : 2 * hidden])
        n = jnp.tanh(xp_t[:, 2 * hidden :] + r * hp[:, 2 * hidden :])

        # h_t = (1-z)*n + z*h_prev
        dh = dh + dhs_ref[kk].astype(f32)
        dn = dh * (1.0 - z)
        dz = dh * (h_prev - n)
        dn_pre = dn * (1.0 - n * n)
        dr = dn_pre * hp[:, 2 * hidden :]
        dr_pre = dr * r * (1.0 - r)
        dz_pre = dz * z * (1.0 - z)
        # gradient w.r.t. the pre-activations: the x-projection sees dn_pre
        # directly, the h-projection sees it through the reset gate
        dg_x = jnp.concatenate([dr_pre, dz_pre, dn_pre], axis=-1)
        dg_h = jnp.concatenate([dr_pre, dz_pre, dn_pre * r], axis=-1)

        dxp_ref[kk] = dg_x.astype(io_dtype)
        # MXU operands in the I/O dtype (bf16 matmuls on TPU) with f32
        # accumulation; the SAME rounded dg_h feeds both the dh chain and
        # the weight/bias gradients so they stay mutually consistent.  The
        # dwt/db accumulators, the dh carry, and dh0 are f32 regardless of
        # the I/O dtype — a bf16 `+=` over T steps would stall once the
        # running sum outgrows the per-step terms (8 mantissa bits).
        dg_h_c = dg_h.astype(io_dtype)
        dh = dh * z + jnp.dot(
            dg_h_c, w_hh_ref[:], preferred_element_type=f32
        )
        dwt_acc += jax.lax.dot_general(
            hprev_ref[kk], dg_h_c, (((0,), (0,)), ((), ())),
            preferred_element_type=f32,
        )
        db_acc += jnp.sum(dg_h_c.astype(f32), axis=0, keepdims=True)
    dwt_ref[:] += dwt_acc
    db_ref[:] += db_acc
    dh_scratch[:] = dh
    dh0_ref[:] = dh


def _gru_scan_pallas_bwd_impl(
    xp, h0, w_hh, b_hh, hs, dh_last, dhs, *, reverse: bool, interpret: bool
):
    batch, seq_len, _ = xp.shape
    hidden = h0.shape[-1]
    dtype = xp.dtype
    w_hh_t = jnp.swapaxes(w_hh, 0, 1)
    b_hh_2d = b_hh[None, :]

    # hidden state *entering* each timestep, in time order: h0 precedes the
    # first-processed step (index 0 forward, T-1 reversed)
    if reverse:
        h_prev = jnp.concatenate([hs[:, 1:], h0[:, None]], axis=1)
    else:
        h_prev = jnp.concatenate([h0[:, None], hs[:, :-1]], axis=1)
    xp_tm = jnp.swapaxes(xp, 0, 1)  # (T, B, 3H)
    hprev_tm = jnp.swapaxes(h_prev, 0, 1)  # (T, B, H)
    dhs_tm = jnp.swapaxes(dhs, 0, 1)  # (T, B, H)

    block_t = _default_block_t(
        seq_len, batch, hidden, xp.dtype.itemsize, units_per_step=8,
        const_bytes=_bwd_const_bytes(batch, hidden, xp.dtype.itemsize))
    n_blocks = seq_len // block_t

    # grid step i processes blocks in reverse *processing* order
    if reverse:
        time_map = lambda i: (i, 0, 0)
    else:
        time_map = lambda i: (n_blocks - 1 - i, 0, 0)

    kernel = functools.partial(
        _gru_bwd_kernel, block_t=block_t, reverse=reverse)
    dxp_tm, dh0, dwt, db = pl.pallas_call(
        kernel,
        name="gru_scan_bwd",
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_t, batch, 3 * hidden), time_map),
            pl.BlockSpec((block_t, batch, hidden), time_map),
            pl.BlockSpec((block_t, batch, hidden), time_map),
            pl.BlockSpec((batch, hidden), lambda i: (0, 0)),
            pl.BlockSpec((3 * hidden, hidden), lambda i: (0, 0)),
            pl.BlockSpec((hidden, 3 * hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, 3 * hidden), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, batch, 3 * hidden), time_map),
            pl.BlockSpec((batch, hidden), lambda i: (0, 0)),
            pl.BlockSpec((hidden, 3 * hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, 3 * hidden), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((seq_len, batch, 3 * hidden), dtype),
            # dh0 / dwt / db accumulate in f32 whatever the I/O dtype (see
            # kernel note); cast to the residual dtypes on return
            jax.ShapeDtypeStruct((batch, hidden), jnp.float32),
            jax.ShapeDtypeStruct((hidden, 3 * hidden), jnp.float32),
            jax.ShapeDtypeStruct((1, 3 * hidden), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((batch, hidden), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(
        xp_tm,
        hprev_tm,
        dhs_tm,
        dh_last.astype(jnp.float32),
        w_hh.astype(dtype),
        w_hh_t.astype(dtype),
        b_hh_2d.astype(dtype),
    )
    return (
        jnp.swapaxes(dxp_tm, 0, 1).astype(xp.dtype),
        dh0.astype(h0.dtype),
        jnp.swapaxes(dwt, 0, 1).astype(w_hh.dtype),
        db[0].astype(b_hh.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gru_scan_pallas(xp, h0, w_hh, b_hh, reverse, interpret):
    hs, h_last = _gru_scan_pallas_fwd_impl(
        xp, h0, w_hh, b_hh, reverse=reverse, interpret=interpret
    )
    return h_last, hs


def _vjp_fwd(xp, h0, w_hh, b_hh, reverse, interpret):
    out = _gru_scan_pallas(xp, h0, w_hh, b_hh, reverse, interpret)
    h_last, hs = out
    return out, (xp, h0, w_hh, b_hh, hs)


def _vjp_bwd(reverse, interpret, residuals, cotangents):
    """Backward through the reverse-time Pallas kernel: gates recomputed
    in-kernel from the saved hs (fused remat), dh carried in VMEM."""
    xp, h0, w_hh, b_hh, hs = residuals
    dh_last, dhs = cotangents
    return _gru_scan_pallas_bwd_impl(
        xp, h0, w_hh, b_hh, hs, dh_last, dhs,
        reverse=reverse, interpret=interpret,
    )


_gru_scan_pallas.defvjp(_vjp_fwd, _vjp_bwd)


def gru_scan_pallas(
    xp: jax.Array,
    h0: jax.Array,
    w_hh: jax.Array,
    b_hh: jax.Array,
    *,
    reverse: bool = False,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Drop-in fused-kernel replacement for :func:`fmda_tpu.ops.gru.gru_scan`
    (same signature minus ``mask``): returns (h_last, hs)."""
    return _gru_scan_pallas(xp, h0, w_hh, b_hh, reverse, interpret)
