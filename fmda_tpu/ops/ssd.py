"""A scan over matrix-valued state in chunks (state-space duality), and
the causal depthwise convolution in front of it: the sequence mixer of a
``decoder`` layer of ``layer_layout`` 3 (models/decoder.py).

One head carries a ``(P, N)`` state (``xs``: ``(T, H, P)``; ``b``, ``c``:
``(T, N)``, one group shared by the heads; ``d``: ``(T, H)`` positive
step sizes; ``a``: ``(H,)`` negative rates; ``skip``: ``(H,)``)::

    S_t = exp(d_t a) S_{t-1} + d_t * xs_t (x) b_t          S_{-1} = 0
    y_t = S_t c_t + skip * xs_t

:func:`ssd_scan_stepwise` is that recurrence as written, a ``lax.scan``
over positions: the form the tests hold the chunked one to.  It keeps a
``(B, H, P, N)`` state per position in backward, which at a training
length is far more than a chip holds (``(T, 64, 64, 128)`` float32 is
17 GB at T = 8,192), so training runs :func:`ssd_scan`, the same sum in
chunks of ``chunk`` positions (``l = cumsum(d a)`` inside a chunk)::

    ssd_states  u_c  = sum_j exp(l_last - l_j) d_j xs_j (x) b_j      a chunk's own end state
    ssd_carry   s_c  = exp(l_last) s_{c-1} + u_c                     chunk after chunk
    ssd_intra   y_i  = sum_{j<=i} exp(l_i - l_j) d_j (c_i . b_j) xs_j   products on the MXU
    ssd_out     y_i += exp(l_i) s_{c-1} c_i                          the carried state, decayed

The chunks are walked once, in order: a ``lax.scan`` over groups of
``CHUNK_GROUP`` chunks carries the ``(B, H, P, N)`` state from one group
to the next, and a turn of it does all four parts for its group (the
group's own end states, the recurrence over them unrolled, then the two
output terms against the state before each chunk) and adds the skip.  So
a chunk's state is written once, into the stacked ``states``, and ``y``
once.  ``d_j`` rides on the float32 factor that multiplies an operand
before it is rounded (``exp(l_last - l_j) d_j`` on ``xs_j`` in
``ssd_states``, the decay matrix's column in ``ssd_intra``): ``d * xs``
is never an array, and ``xs`` enters a group in the dtype it came in.
No term divides by a decay: every exponent is a sum of non-positive
terms, so a decay that underflows inside a chunk gives 0, not inf or nan.

Cumulative decays, their exponentials and the carried state are float32;
the products take operands in ``dtype`` (each rounded once) and
accumulate in float32.  A turn runs under ``jax.checkpoint``: the
``(group, H, chunk, chunk)`` decay matrices exist a group at a time and
are made again in backward, which keeps of the walk the state at each
group's edge.

The convolution in front of it, as the mixer consumes it, is
:func:`conv_silu`: ``silu(causal_conv(x, w, bias))`` rounded once to the
mixer's dtype, on every backend one ``jnp`` form under a ``custom_vjp``.
Forward, :func:`causal_conv`'s sums letter for letter with the padding
done in ``x``'s dtype and each tap widened inside the sum: XLA makes one
fusion of it that reads ``x`` once, in the dtype it came in, and writes
the rounded result once, where ``silu(causal_conv(...))`` as written
keeps a widened copy of the input in HBM.  Backward, written out in
float32 from ``x``, ``w`` and ``bias`` alone: the pre-activation made
again, ``dpre = ct * silu'(pre)`` (the one float32 array of the sequence
that exists), ``dx`` the four shifted products of ``dpre`` added in
float32 and rounded once, ``dw`` and ``dbias`` float32 sums; autodiff of
the forward would round each tap's term of ``dx`` to ``x``'s dtype
before adding them, and of ``causal_conv`` as written keeps a float32
array a tap.  A Pallas kernel for the backward was built and timed and
did not earn its place (PERF.md section 6, PR 39).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

#: Chunks a turn of the walk takes, and whose ``(H, chunk, chunk)`` decay
#: matrices exist at a time: 4 x 64 x 256 x 256 float32 is 67 MB, where 32
#: chunks would be 537 MB and as much again in bfloat16.  On the TPU the
#: compiler makes them inside the products' fusions at 4 and at 8, and a
#: step read the same at both; at 32 they are arrays (PERF.md section 6,
#: PR 34 and PR 38).
CHUNK_GROUP = 4


def causal_conv(x: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """Depthwise causal convolution: ``x`` (B, T, C), ``w`` (C, K),
    ``bias`` (C,) -> (B, T, C) float32, ``out[t] = bias + sum_j w[:, j] *
    x[t - (K - 1) + j]`` with zeros before ``t = 0``."""
    k = w.shape[-1]
    t = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for j in range(k):
        out = out + w[:, j] * padded[:, j:j + t]
    return out


def _conv_taps(x, w, bias):
    """:func:`causal_conv`'s sums and the padded input they read: the
    padding in ``x``'s dtype, each tap widened inside the sum."""
    k, t = w.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for j in range(k):
        out = out + w[:, j] * padded[:, j:j + t].astype(jnp.float32)
    return out, padded


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu(x, w, bias, dtype):
    return jax.nn.silu(_conv_taps(x, w, bias)[0]).astype(dtype)


def _conv_silu_fwd(x, w, bias, dtype):
    return _conv_silu(x, w, bias, dtype), (x, w, bias)


def _conv_silu_bwd(dtype, residuals, ct):
    f32 = jnp.float32
    x, w, bias = residuals
    k, t = w.shape[-1], x.shape[1]
    pre, padded = _conv_taps(x, w, bias)
    sig = jax.nn.sigmoid(pre)
    dpre = ct.astype(f32) * (sig * (1.0 + pre * (1.0 - sig)))
    # dx[t] = sum_j w[:, j] * dpre[t + (K - 1) - j], zeros after the end
    after = jnp.pad(dpre, ((0, 0), (0, k - 1), (0, 0)))
    dx = w[:, 0] * after[:, k - 1:k - 1 + t]
    for j in range(1, k):
        dx = dx + w[:, j] * after[:, k - 1 - j:k - 1 - j + t]
    dw = jnp.stack([(dpre * padded[:, j:j + t].astype(f32)).sum((0, 1))
                    for j in range(k)], -1)
    return (dx.astype(x.dtype), dw.astype(w.dtype),
            dpre.sum((0, 1)).astype(bias.dtype))


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def conv_silu(x: jax.Array, w: jax.Array, bias: jax.Array, *, dtype
              ) -> jax.Array:
    """``silu(causal_conv(x, w, bias))`` rounded once to ``dtype``, the
    mixer's convolution as it is consumed (module docstring): the same
    float32 sums, ``x`` read in the dtype it came in, and a backward
    that keeps ``x``, ``w`` and ``bias`` alone and adds ``dx``'s terms
    in float32."""
    return _conv_silu(x, w, bias, jnp.dtype(dtype))


def ssd_scan_stepwise(xs, d, a, b, c, skip) -> jax.Array:
    """The recurrence as written, one position at a time, float32:
    ``xs`` (B, T, H, P), ``d`` (B, T, H), ``a`` (H,), ``b`` / ``c``
    (B, T, N), ``skip`` (H,) -> ``y`` (B, T, H, P)."""
    f32 = jnp.float32
    xs, d, b, c = (v.astype(f32) for v in (xs, d, b, c))
    batch, _, h, p = xs.shape

    def step(state, at):
        x_t, d_t, b_t, c_t = at
        state = (jnp.exp(d_t * a)[..., None, None] * state
                 + jnp.einsum("bh,bhp,bn->bhpn", d_t, x_t, b_t))
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    _, y = jax.lax.scan(
        step, jnp.zeros((batch, h, p, b.shape[-1]), f32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (xs, d, b, c)))
    return jnp.moveaxis(y, 0, 1) + skip[:, None] * xs


def pairwise_decays(l: jax.Array, causal: jax.Array) -> jax.Array:
    """``exp(l_i - l_j)`` where ``causal`` (``j <= i``) and 0 elsewhere:
    cumulative log-decays ``l`` (..., Q) float32, never rising -> (..., Q,
    Q).  The exponent is masked, not the result, so that no masked slot
    overflows.  One decay a head makes the pairwise factor this matrix,
    here and in ops/kda.py."""
    span = l[..., :, None] - l[..., None, :]
    return jnp.exp(jnp.where(causal, span, -jnp.inf))


def _by_chunk(v: jax.Array, chunk: int) -> jax.Array:
    """(B, T, ...) -> (B, T / chunk, chunk, ...)."""
    return v.reshape(v.shape[:1] + (-1, chunk) + v.shape[2:])


def _group_size(n_chunks: int) -> int:
    """The largest divisor of ``n_chunks`` that is at most ``CHUNK_GROUP``."""
    group = min(CHUNK_GROUP, n_chunks)
    while n_chunks % group:
        group -= 1
    return group


def ssd_scan(xs, d, a, b, c, skip, *, chunk: int, dtype=jnp.float32
             ) -> Tuple[jax.Array, jax.Array]:
    """:func:`ssd_scan_stepwise` in chunks of ``chunk`` positions
    (module docstring): ``(y (B, T, H, P) float32, the carried states
    (B, chunks, H, P, N) float32)``, ``states[:, k]`` the state after
    chunk ``k``.  A length that is no multiple of ``chunk`` is padded
    with steps of size zero, which leave the state as it is."""
    f32 = jnp.float32
    batch, t, h, p = xs.shape
    n = b.shape[-1]
    pad = -t % chunk
    if pad:
        xs, d, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (xs, d, b, c))
    n_chunks = (t + pad) // chunk
    group = _group_size(n_chunks)
    d = _by_chunk(d.astype(f32), chunk)                      # (B, C, Q, H)
    # l_i = sum_{r <= i} d_r a inside the chunk, never positive
    decay = jnp.cumsum(d * a, axis=2)
    x_c = _by_chunk(xs, chunk)                               # (B, C, Q, H, P)
    b_c, c_c = (_by_chunk(v, chunk).astype(dtype) for v in (b, c))
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def walk(state, at):
        """A group of chunks in order from the ``state`` (B, H, P, N)
        before it: the state after it, the group's ``y`` (B, G, Q, H, P)
        and the state after each of its chunks (B, G, H, P, N)."""
        x, d, decay, b_c, c_c = at
        x32 = x.astype(f32)
        with jax.named_scope("ssd_states"):
            # exp(l_last - l_j) d_j, the one float32 factor of xs_j
            to_end = jnp.exp(decay[:, :, -1:, :] - decay) * d    # (B, G, Q, H)
            own = jnp.einsum("bgqhp,bgqn->bghpn",
                             (to_end[..., None] * x32).astype(dtype), b_c,
                             preferred_element_type=f32)
        with jax.named_scope("ssd_carry"):
            # s_c = exp(l_last) s_{c-1} + u_c: one scalar a head and chunk
            through = jnp.exp(decay[:, :, -1, :])                # (B, G, H)
            before, after = [], []
            for k in range(x.shape[1]):
                before.append(state)
                state = through[:, k, :, None, None] * state + own[:, k]
                after.append(state)
            before, after = jnp.stack(before, 1), jnp.stack(after, 1)
        with jax.named_scope("ssd_intra"):
            scores = jnp.einsum("bgin,bgjn->bgij", c_c, b_c,
                                preferred_element_type=f32)
            # exp(l_i - l_j) d_j for j <= i
            by_head = jnp.swapaxes(decay, 2, 3)                  # (B, G, H, Q)
            weights = (pairwise_decays(by_head, causal)
                       * jnp.swapaxes(d, 2, 3)[..., None, :]
                       * scores[:, :, None])                    # (B, G, H, i, j)
            y = jnp.einsum("bghij,bgjhp->bgihp", weights.astype(dtype),
                           x.astype(dtype), preferred_element_type=f32)
        with jax.named_scope("ssd_out"):
            y = y + jnp.exp(decay)[..., None] * jnp.einsum(
                "bgin,bghpn->bgihp", c_c, before.astype(dtype),
                preferred_element_type=f32)
        return state, (y + skip[:, None] * x32, after)

    def grouped(v):  # (B, C, ...) -> (C / group, B, group, ...)
        return jnp.moveaxis(v.reshape(
            v.shape[:1] + (n_chunks // group, group) + v.shape[2:]), 1, 0)

    def whole(v):  # (C / group, B, group, ...) -> (B, C, ...)
        v = jnp.moveaxis(v, 0, 1)
        return v.reshape(v.shape[:1] + (n_chunks,) + v.shape[3:])

    start = jnp.zeros((batch, h, p, n), f32)
    args = (x_c, d, decay, b_c, c_c)
    if group == n_chunks:
        _, (y, states) = walk(start, args)
    else:
        _, (y, states) = jax.lax.scan(
            jax.checkpoint(walk), start, tuple(grouped(v) for v in args))
        y, states = whole(y), whole(states)
    return y.reshape(batch, t + pad, h, p)[:, :t], states
