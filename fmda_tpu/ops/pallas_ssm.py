"""Fused Pallas TPU kernel for the SSM O(1) serve step.

The SSM family's per-tick device work is pure VPU algebra: split the
precomputed projection, one sigmoid-gated diagonal state update, a silu
output gate, and two EMA updates — eight elementwise passes over
``(B, H)`` operands.  Left to XLA those land as a handful of separate
fusions with their own HBM round trips; this kernel runs the whole tick
in one ``pallas_call`` with every operand resident in VMEM, so a serve
flush reads ``xp`` + the three cache rows once and writes ``h`` + the
three new cache rows once — the memory-bound ideal for the shape class
(B in the bucket set, H well under MXU width) the serving pool flushes.

Unlike the GRU/LSTM scan kernels there is no grid and no time axis: the
serving step IS one timestep (the whole point of the O(1) cache), so
the kernel is a single invocation with full-array VMEM blocks.  The
input projection stays outside, exactly like the sibling kernels — it
is the one MXU-shaped matmul of the family and XLA already tiles it.

Math is identical op-for-op to :func:`fmda_tpu.ops.ssm.ssm_cell_step`
(the jnp reference): gate algebra in f32 on the VPU regardless of the
I/O dtype (the same mixed-dtype-broadcast rule the GRU kernel
documents), outputs cast back to the I/O dtype.  Parity — including
interpret mode on CPU, which tier-1 runs — is pinned in
``tests/test_pallas_ssm.py``; selection happens per shape in
:func:`fmda_tpu.ops.ssm.select_ssm_step_fn` with counted fallbacks.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fmda_tpu.ops.ssm import SSMWeights

# Conservative VMEM budget for the whole working set (same constant
# class as the sibling kernels: real VMEM is ~16 MB/core, headroom left
# for Mosaic temporaries and the f32 upcasts).
_VMEM_BUDGET = 12 * 1024 * 1024


def kernel_supported(batch: int, hidden: int, itemsize: int) -> bool:
    """True when one serve step's operands fit the VMEM budget: xp
    (B, 3H) + 3 cache rows in + h + 3 cache rows out (B, H each) + the
    four (1, H) parameter rows, plus their f32 upcasts."""
    f32 = 4
    io = itemsize * (10 * batch * hidden + 4 * hidden)
    upcast = f32 * (10 * batch * hidden + 4 * hidden)
    return io + upcast <= _VMEM_BUDGET


def _ssm_step_kernel(
    xp_ref,  # (B, 3H) precomputed input projection
    s_ref,  # (B, H) diagonal state
    ef_ref,  # (B, H) fast head EMA
    es_ref,  # (B, H) slow head EMA
    a_base_ref,  # (1, H) decay offset
    d_ref,  # (1, H) feedthrough
    rho_f_ref,  # (1, H) fast EMA rate pre-activation
    rho_s_ref,  # (1, H) slow EMA rate pre-activation
    h_ref,  # out: (B, H)
    s_out_ref,  # out: (B, H)
    ef_out_ref,  # out: (B, H)
    es_out_ref,  # out: (B, H)
):
    f32 = jnp.float32
    io_dtype = h_ref.dtype
    hidden = s_ref.shape[-1]
    xp = xp_ref[:].astype(f32)
    zp = xp[:, :hidden]
    vp = xp[:, hidden : 2 * hidden]
    gp = xp[:, 2 * hidden :]
    a = jax.nn.sigmoid(zp + a_base_ref[:].astype(f32))
    s_new = a * s_ref[:].astype(f32) + (1.0 - a) * vp
    h = s_new * jax.nn.silu(gp) + d_ref[:].astype(f32) * vp
    rf = jax.nn.sigmoid(rho_f_ref[:].astype(f32))
    rs = jax.nn.sigmoid(rho_s_ref[:].astype(f32))
    ef_new = rf * ef_ref[:].astype(f32) + (1.0 - rf) * h
    es_new = rs * es_ref[:].astype(f32) + (1.0 - rs) * h
    h_ref[:] = h.astype(io_dtype)
    s_out_ref[:] = s_new.astype(io_dtype)
    ef_out_ref[:] = ef_new.astype(io_dtype)
    es_out_ref[:] = es_new.astype(io_dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_step_pallas(
    xp: jax.Array,
    s: jax.Array,
    ef: jax.Array,
    es: jax.Array,
    a_base: jax.Array,
    d: jax.Array,
    rho_f: jax.Array,
    rho_s: jax.Array,
    interpret: bool = False,
):
    batch, hidden = s.shape
    dtype = xp.dtype
    out = jax.ShapeDtypeStruct((batch, hidden), dtype)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _ssm_step_kernel,
        name="ssm_cell_step",
        out_shape=[out, out, out, out],
        in_specs=[vmem] * 8,
        out_specs=[vmem] * 4,
        interpret=interpret,
    )(
        xp,
        s.astype(dtype),
        ef.astype(dtype),
        es.astype(dtype),
        a_base[None, :].astype(dtype),
        d[None, :].astype(dtype),
        rho_f[None, :].astype(dtype),
        rho_s[None, :].astype(dtype),
    )


def ssm_cell_step_pallas(
    xp: jax.Array,
    carry: Tuple[jax.Array, ...],
    w: SSMWeights,
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """Drop-in fused replacement for
    :func:`fmda_tpu.ops.ssm.ssm_cell_step` (same signature plus
    ``interpret``): one tick of the serving cache in one kernel."""
    s, ef, es = carry
    h, s_new, ef_new, es_new = _ssm_step_pallas(
        xp, s, ef, es, w.a_base, w.d, w.rho_f, w.rho_s,
        interpret=interpret)
    return h, (s_new, ef_new, es_new)
