"""The hyper-connections' backward over token tiles (Pallas TPU).

:mod:`fmda_tpu.ops.hyper_connection` cuts one sublayer's backward in two
where the sublayer's own backward runs; the three walks over the
four-lane stream are kernels here.  They take the stream **tokens
last**: ``(B, n, d, T)``, a lane a ``(d, T)`` slab with the tokens on
the 128 vector lanes and ``hidden`` down the sublanes.  That is the
layout XLA itself gives the stream in a latent decoder's step (a
per-token coefficient is then one row that multiplies every sublane,
and a sum over ``hidden`` is a sum of vector registers), so the
transposes around a kernel are relabelings, not copies; the per-token
coefficients enter as ``(B, k, T)`` rows.

- ``hc_bwd_leave`` — from the written stream's gradient ``g'``, the
  stream ``x`` and the sublayer's output ``y``: ``dy = sum_i Hpost[i]
  g'[i]`` in the stream's dtype, and the float32 sums over ``hidden``
  ``<g'[i], y>`` and ``<g'[i], x[j]>``.  Grid ``(B, T tiles, d blocks)``,
  the sums accumulated over the ``d`` blocks in their output block.
- ``hc_bwd_pre`` — ``<du, x[i]>`` over ``hidden``, the same way.
- ``hc_bwd_enter`` — the stream's gradient, written once::

      dx[j] = sum_i Hres[i, j] g'[i] + Hpre[j] du + P[j] @ dzraw + norm * x[j]

  (``P[j]``: the coefficient matrices' rows of lane ``j``, ``(d, k)``;
  ``dzraw`` ``(k, T)``: the product's gradient in the stream's dtype;
  ``norm`` the norm's per-token factor), summed in float32 and rounded
  once; and beside it the matrices' gradient ``dP[j] = x[j] @ dzraw^T``
  from the same read of ``x``, float32.  Grid ``(d blocks, B, T
  tiles)``: ``dP``'s block stays in VMEM over all tokens.

Each entry point is a ``jax.jit`` of its own: a step calls each kernel
once a sublayer (ten times at five layers), and inside one trace the
ten calls then share one traced and lowered body; traced one by one,
the kernels' Python cost the cell 72 s of set-up on the chip machine's
host, every run, warm compile cache or not (``PERF.md`` §6, PR 42).

Every sum over ``hidden`` leaves a kernel as eight partial rows a token
(one vector register's sublanes); the caller adds the eight.  Inside a
block the work goes by ``(16, 128)`` pieces in a loop, so that a
piece's operands and the running sums stay in vector registers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fmda_tpu.compat import CompilerParams

#: ``hidden`` rows a block (a multiple of the 16 rows of a packed
#: bfloat16 register) ...
D_BLOCK = 128
#: ... and the token tiles tried, widest first (multiples of 128 lanes).
T_TILES = (512, 256, 128)
#: What the product's width ``k`` is padded to (a packed bfloat16 tile's
#: rows and more: the product's contraction is a whole number of tiles).
K_ALIGN = 32
#: Rows a piece, lanes a piece.
_ROWS, _LANES = 16, 128
_VMEM_LIMIT = 32 * 1024 * 1024


def token_tile(t: int) -> int:
    """The widest token tile that divides ``t``, 0 where none does."""
    return next((tt for tt in T_TILES if t % tt == 0), 0)


def fits(d: int, t: int) -> bool:
    """Whether the kernels take a stream ``d`` wide over ``t`` tokens."""
    return d % D_BLOCK == 0 and token_tile(t) > 0


def _pieces(tt: int):
    """The lane columns of a block, and the row loop's bounds."""
    return ([pl.ds(c * _LANES, _LANES) for c in range(tt // _LANES)],
            D_BLOCK // _ROWS)


def _rows(r):
    return pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)


def _halves(p):
    """A (16, 128) float32 piece as the sum of its two registers."""
    return p[:8] + p[8:]


def _leave_kernel(g_ref, x_ref, y_ref, post_ref, dy_ref, dpost_ref,
                  dres_ref, *, n: int, tt: int):
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dpost_ref[...] = jnp.zeros_like(dpost_ref)
        dres_ref[...] = jnp.zeros_like(dres_ref)

    columns, steps = _pieces(tt)
    for cols in columns:
        post = [post_ref[0, i:i + 1, cols] for i in range(n)]

        def piece(r, sums, cols=cols, post=post):
            rows = _rows(r)
            g = [g_ref[0, i, rows, cols].astype(f32) for i in range(n)]
            x = [x_ref[0, j, rows, cols].astype(f32) for j in range(n)]
            y = y_ref[0, rows, cols].astype(f32)
            dy = post[0] * g[0]
            for i in range(1, n):
                dy += post[i] * g[i]
            dy_ref[0, rows, cols] = dy.astype(dy_ref.dtype)
            new = [sums[i] + _halves(g[i] * y) for i in range(n)]
            new += [sums[n + i * n + j] + _halves(g[i] * x[j])
                    for i in range(n) for j in range(n)]
            return tuple(new)

        zero = jnp.zeros((8, _LANES), f32)
        sums = jax.lax.fori_loop(0, steps, piece, (zero,) * (n + n * n))
        for i in range(n):
            dpost_ref[0, i, :, cols] += sums[i]
        for k in range(n * n):
            dres_ref[0, k, :, cols] += sums[n + k]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bwd_leave(g, x, y, post, *, interpret: bool):
    """``g``, ``x`` (B, n, d, T), ``y`` (B, d, T), ``post`` (B, n, T)
    float32 -> ``dy`` (B, d, T) in ``y``'s dtype, ``dpost`` (B, n, 8, T)
    and ``dres`` (B, n*n, 8, T) float32 partial sums."""
    b, n, d, t = x.shape
    tt = token_tile(t)
    lanes = pl.BlockSpec((1, n, D_BLOCK, tt), lambda b, t, k: (b, 0, k, t))
    one = pl.BlockSpec((1, D_BLOCK, tt), lambda b, t, k: (b, k, t))

    def sums(rows):
        return pl.BlockSpec((1, rows, 8, tt), lambda b, t, k: (b, 0, 0, t))

    return pl.pallas_call(
        functools.partial(_leave_kernel, n=n, tt=tt),
        name="hc_bwd_leave",
        grid=(b, t // tt, d // D_BLOCK),
        in_specs=[lanes, lanes, one,
                  pl.BlockSpec((1, n, tt), lambda b, t, k: (b, 0, t))],
        out_specs=[one, sums(n), sums(n * n)],
        out_shape=[jax.ShapeDtypeStruct((b, d, t), y.dtype),
                   jax.ShapeDtypeStruct((b, n, 8, t), jnp.float32),
                   jax.ShapeDtypeStruct((b, n * n, 8, t), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(g, x, y, post)


def _pre_kernel(x_ref, du_ref, dpre_ref, *, n: int, tt: int):
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dpre_ref[...] = jnp.zeros_like(dpre_ref)

    columns, steps = _pieces(tt)
    for cols in columns:
        def piece(r, sums, cols=cols):
            rows = _rows(r)
            du = du_ref[0, rows, cols].astype(f32)
            return tuple(
                sums[i] + _halves(du * x_ref[0, i, rows, cols].astype(f32))
                for i in range(n))

        zero = jnp.zeros((8, _LANES), f32)
        sums = jax.lax.fori_loop(0, steps, piece, (zero,) * n)
        for i in range(n):
            dpre_ref[0, i, :, cols] += sums[i]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bwd_pre(x, du, *, interpret: bool):
    """``x`` (B, n, d, T), ``du`` (B, d, T) -> ``dpre`` (B, n, 8, T)
    float32 partial sums of ``<du, x[i]>``."""
    b, n, d, t = x.shape
    tt = token_tile(t)
    return pl.pallas_call(
        functools.partial(_pre_kernel, n=n, tt=tt),
        name="hc_bwd_pre",
        grid=(b, t // tt, d // D_BLOCK),
        in_specs=[
            pl.BlockSpec((1, n, D_BLOCK, tt), lambda b, t, k: (b, 0, k, t)),
            pl.BlockSpec((1, D_BLOCK, tt), lambda b, t, k: (b, k, t))],
        out_specs=pl.BlockSpec((1, n, 8, tt), lambda b, t, k: (b, 0, 0, t)),
        out_shape=jax.ShapeDtypeStruct((b, n, 8, t), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x, du)


def _enter_kernel(g_ref, x_ref, du_ref, res_ref, pre_ref, dz_ref, norm_ref,
                  p_ref, dx_ref, dp_ref, flat_ref, *, n: int, tt: int):
    f32 = jnp.float32

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _zero():
        dp_ref[...] = jnp.zeros_like(dp_ref)

    dz = dz_ref[0]
    for j in range(n):
        flat_ref[j] = jnp.dot(p_ref[j], dz, preferred_element_type=f32)
        dp_ref[j] += jax.lax.dot_general(
            x_ref[0, j], dz, (((1,), (1,)), ((), ())),
            preferred_element_type=f32)

    columns, steps = _pieces(tt)
    for cols in columns:
        res = [[res_ref[0, i * n + j:i * n + j + 1, cols] for j in range(n)]
               for i in range(n)]
        pre = [pre_ref[0, j:j + 1, cols] for j in range(n)]
        norm = norm_ref[0, :, cols]

        def piece(r, carry, cols=cols, res=res, pre=pre, norm=norm):
            rows = _rows(r)
            g = [g_ref[0, i, rows, cols].astype(f32) for i in range(n)]
            du = du_ref[0, rows, cols].astype(f32)
            for j in range(n):
                dx = (flat_ref[j, rows, cols]
                      + norm * x_ref[0, j, rows, cols].astype(f32)
                      + pre[j] * du)
                for i in range(n):
                    dx += res[i][j] * g[i]
                dx_ref[0, j, rows, cols] = dx.astype(dx_ref.dtype)
            return carry

        jax.lax.fori_loop(0, steps, piece, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bwd_enter(g, x, du, res, pre, dzraw, norm, p, *, interpret: bool):
    """``g``, ``x`` (B, n, d, T), ``du`` (B, d, T); ``res`` (B, n*n, T),
    ``pre`` (B, n, T), ``norm`` (B, 1, T) float32; ``dzraw`` (B, k, T)
    and ``p`` (n, d, k) in the stream's dtype (``k`` a multiple of
    ``K_ALIGN``, zeros past the product's width) -> ``dx`` (B, n, d, T) in
    the stream's dtype and ``dp`` (n, d, k) float32."""
    b, n, d, t = x.shape
    width = p.shape[-1]
    tt = token_tile(t)
    lanes = pl.BlockSpec((1, n, D_BLOCK, tt), lambda k, b, t: (b, 0, k, t))
    rows = pl.BlockSpec((n, D_BLOCK, width), lambda k, b, t: (0, k, 0))

    def coeff(count):
        return pl.BlockSpec((1, count, tt), lambda k, b, t: (b, 0, t))

    return pl.pallas_call(
        functools.partial(_enter_kernel, n=n, tt=tt),
        name="hc_bwd_enter",
        grid=(d // D_BLOCK, b, t // tt),
        in_specs=[lanes, lanes,
                  pl.BlockSpec((1, D_BLOCK, tt), lambda k, b, t: (b, k, t)),
                  coeff(n * n), coeff(n), coeff(width), coeff(1), rows],
        out_specs=[lanes, rows],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, d, width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, D_BLOCK, tt), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(g, x, du, res, pre, dzraw, norm, p)
