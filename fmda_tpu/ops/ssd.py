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
    ssd_carry   s_c  = exp(l_last) s_{c-1} + u_c                     over the chunks
    ssd_intra   y_i  = sum_{j<=i} exp(l_i - l_j) (c_i . b_j) d_j xs_j   products on the MXU
    ssd_out     y_i += exp(l_i) s_{c-1} c_i                          the carried state, decayed

The recurrence over chunk states is first-order with one scalar a head
and chunk, the combine of :func:`fmda_tpu.ops.ssm.linear_scan_parallel`:
the vector recurrence of the ``ssm`` family and this one share it.  No
term divides by a decay: every exponent is a sum of non-positive terms,
so a decay that underflows inside a chunk gives 0, not inf or nan.

Cumulative decays, their exponentials and the carried state are float32;
the products take operands in ``dtype`` and accumulate in float32.  The
``(chunks, H, chunk, chunk)`` decay matrices are made a group of chunks
at a time and made again in backward (``jax.checkpoint`` on the group),
so neither pass holds them for the whole sequence.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from fmda_tpu.ops.ssm import linear_scan_parallel

#: Chunks whose ``(H, chunk, chunk)`` decay matrices exist at a time: 4 x
#: 64 x 256 x 256 float32 is 67 MB, where 32 chunks would be 537 MB and
#: as much again in bfloat16 (PERF.md section 6, PR 34).
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


def _by_chunk(v: jax.Array, chunk: int) -> jax.Array:
    """(B, T, ...) -> (B, T / chunk, chunk, ...)."""
    return v.reshape(v.shape[:1] + (-1, chunk) + v.shape[2:])


def _in_groups(fn, args, n_chunks: int):
    """``fn`` over the chunk axis (axis 1) of ``args``, ``CHUNK_GROUP``
    chunks at a time, each group made again in backward."""
    group = min(CHUNK_GROUP, n_chunks)
    while n_chunks % group:
        group -= 1
    if group == n_chunks:
        return fn(*args)

    def grouped(v):  # (B, C, ...) -> (C / group, B, group, ...)
        return jnp.moveaxis(v.reshape(
            v.shape[:1] + (n_chunks // group, group) + v.shape[2:]), 1, 0)

    out = jax.lax.map(lambda xs: jax.checkpoint(fn)(*xs),
                      tuple(grouped(v) for v in args))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(out.shape[:1] + (n_chunks,) + out.shape[3:])


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
    d = _by_chunk(d.astype(f32), chunk)                      # (B, C, Q, H)
    # l_i = sum_{r <= i} d_r a inside the chunk, never positive
    decay = jnp.cumsum(d * a, axis=2)
    x_c = _by_chunk(xs, chunk)                               # (B, C, Q, H, P)
    dx = (d[..., None] * x_c.astype(f32))
    b_c, c_c = (_by_chunk(v, chunk).astype(dtype) for v in (b, c))

    with jax.named_scope("ssd_states"):
        to_end = jnp.exp(decay[:, :, -1:, :] - decay)        # (B, C, Q, H)
        own = jnp.einsum("bcqhp,bcqn->bchpn",
                         (to_end[..., None] * dx).astype(dtype), b_c,
                         preferred_element_type=f32)
    with jax.named_scope("ssd_carry"):
        # s_c = exp(l_last) s_{c-1} + u_c: one scalar a head and chunk
        through = jnp.exp(decay[:, :, -1, :])                # (B, C, H)
        states = linear_scan_parallel(
            through[..., None],
            own.reshape(batch, n_chunks, h, p * n)).reshape(own.shape)
        before = jnp.concatenate(
            [jnp.zeros_like(states[:, :1]), states[:, :-1]], axis=1)

    def outputs(decay, dx, b_c, c_c, before):
        """``y`` of a group of chunks, (B, G, Q, H, P) float32."""
        with jax.named_scope("ssd_intra"):
            q = decay.shape[2]
            scores = jnp.einsum("bgin,bgjn->bgij", c_c, b_c,
                                preferred_element_type=f32)
            # exp(l_i - l_j) for j <= i; the exponent is masked, not the
            # result, so that no masked slot overflows
            by_head = jnp.swapaxes(decay, 2, 3)              # (B, G, H, Q)
            span = by_head[..., :, None] - by_head[..., None, :]
            causal = jnp.tril(jnp.ones((q, q), bool))
            weights = (jnp.exp(jnp.where(causal, span, -jnp.inf))
                       * scores[:, :, None])                # (B, G, H, i, j)
            y = jnp.einsum("bghij,bgjhp->bgihp", weights.astype(dtype),
                           dx.astype(dtype), preferred_element_type=f32)
        with jax.named_scope("ssd_out"):
            y = y + jnp.exp(decay)[..., None] * jnp.einsum(
                "bgin,bghpn->bgihp", c_c, before.astype(dtype),
                preferred_element_type=f32)
        return y

    y = _in_groups(outputs, (decay, dx, b_c, c_c, before), n_chunks)
    y = y.reshape(batch, t + pad, h, p)[:, :t]
    return y + skip[:, None] * xs[:, :t].astype(f32), states
