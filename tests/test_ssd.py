"""The chunked scan over matrix-valued state against the recurrence as
written (ops/ssd.py), forward and gradient, and the causal depthwise
convolution against shifted sums: small sizes, seeded inputs, float32
on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fmda_tpu.ops import ssd
from fmda_tpu.ops.ssd import causal_conv, ssd_scan, ssd_scan_stepwise

B, H, P, N = 2, 3, 4, 5


def _inputs(t, seed=0, step_scale=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    xs = jax.random.normal(k[0], (B, t, H, P))
    d = jax.nn.softplus(jax.random.normal(k[1], (B, t, H))) * step_scale
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    b = jax.random.normal(k[3], (B, t, N))
    c = jax.random.normal(k[4], (B, t, N))
    skip = jax.random.normal(k[5], (H,))
    return xs, d, a, b, c, skip


def _close(got, want, tol):
    scale = float(jnp.abs(want).max()) + 1e-12
    assert float(jnp.abs(got - want).max()) <= tol * scale


# lengths that are and are not a multiple of the chunk, one chunk alone,
# more chunks than a group holds (the grouped walk), and steps so large
# that a decay underflows inside a chunk
@pytest.mark.parametrize("t,chunk,step_scale", [
    (32, 8, 1.0), (37, 8, 1.0), (8, 8, 1.0), (5, 8, 1.0), (96, 8, 1.0),
    (64, 16, 60.0)])
def test_the_chunked_scan_is_the_recurrence_as_written(t, chunk, step_scale):
    args = _inputs(t, seed=t, step_scale=step_scale)
    with jax.default_matmul_precision("highest"):
        want = ssd_scan_stepwise(*args)
        got, states = ssd_scan(*args, chunk=chunk)
    assert got.shape == want.shape == (B, t, H, P)
    assert states.shape == (B, -(-t // chunk), H, P, N)
    assert bool(jnp.isfinite(got).all())
    _close(got, want, 2e-5)
    if step_scale > 1.0:  # exp(-60 x chunk) is 0 in float32: it did underflow
        assert float(jnp.exp(args[1] * args[2]).min()) == 0.0


@pytest.mark.parametrize("t,chunk,step_scale", [
    (32, 8, 1.0), (37, 8, 1.0), (96, 8, 1.0), (64, 16, 60.0)])
def test_the_chunked_scans_gradient_is_the_recurrences(t, chunk, step_scale):
    args = _inputs(t, seed=t + 1, step_scale=step_scale)

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=tuple(range(6)))(*args)

    with jax.default_matmul_precision("highest"):
        want = through(ssd_scan_stepwise)
        got = through(lambda *a: ssd_scan(*a, chunk=chunk)[0])
    for g, w in zip(got, want):
        assert bool(jnp.isfinite(g).all())
        _close(g, w, 1e-4)


def test_the_carried_states_are_the_recurrences_states_at_chunk_ends():
    t, chunk = 24, 8
    xs, d, a, b, c, skip = _inputs(t, seed=9)
    with jax.default_matmul_precision("highest"):
        _, states = ssd_scan(xs, d, a, b, c, skip, chunk=chunk)
    state = np.zeros((B, H, P, N))
    for i in range(t):
        state = (np.exp(np.asarray(d[:, i] * a))[..., None, None] * state
                 + np.einsum("bh,bhp,bn->bhpn", d[:, i], xs[:, i], b[:, i]))
        if (i + 1) % chunk == 0:
            np.testing.assert_allclose(states[:, i // chunk], state,
                                       rtol=1e-4, atol=1e-5)


def test_the_decay_matrices_exist_a_group_of_chunks_at_a_time(monkeypatch):
    """Twelve chunks are walked three groups of four, each made again in
    backward: no (chunks, H, chunk, chunk) array of the whole sequence is
    in the program."""
    args = _inputs(96, seed=2)
    text = jax.jit(jax.grad(lambda *a: ssd_scan(*a, chunk=8)[0].sum())
                   ).lower(*args).as_text()
    assert f"tensor<{B}x12x{H}x8x8xf32>" not in text
    assert f"tensor<{B}x{ssd.CHUNK_GROUP}x{H}x8x8xf32>" in text
    monkeypatch.setattr(ssd, "CHUNK_GROUP", 12)
    whole = jax.jit(lambda *a: ssd_scan(*a, chunk=8)[0]).lower(*args).as_text()
    assert f"tensor<{B}x12x{H}x8x8xf32>" in whole


def test_the_compute_dtype_rounds_the_products_operands_only():
    args = _inputs(32, seed=4)
    want = ssd_scan_stepwise(*args)
    got, states = ssd_scan(*args, chunk=8, dtype=jnp.bfloat16)
    assert got.dtype == states.dtype == jnp.float32
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert 1e-4 < err < 3e-2


def _shifted_sums(x, w, bias):
    """out[t] = bias + w0 x[t-3] + w1 x[t-2] + w2 x[t-1] + w3 x[t]."""
    x = np.asarray(x, np.float64)
    out = np.broadcast_to(np.asarray(bias, np.float64), x.shape).copy()
    k = w.shape[1]
    for j in range(k):
        back = k - 1 - j
        out[:, back:] += np.asarray(w[:, j], np.float64) * x[
            :, :x.shape[1] - back]
    return out


def test_the_convolution_is_four_shifted_sums():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (2, 19, 6))
    w = jax.random.normal(k[1], (6, 4))
    bias = jax.random.normal(k[2], (6,))
    got = causal_conv(x, w, bias)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    np.testing.assert_allclose(got, _shifted_sums(x, w, bias), rtol=1e-5,
                               atol=1e-6)
    # the first position sees its own tap and the bias alone
    np.testing.assert_allclose(got[:, 0], bias + w[:, 3] * x[:, 0],
                               rtol=1e-5, atol=1e-6)


def test_the_convolution_reads_no_future_position():
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(k[0], (1, 16, 5))
    w, bias = jax.random.normal(k[1], (5, 4)), jnp.zeros((5,))
    base = causal_conv(x, w, bias)
    moved = causal_conv(x.at[:, 9].add(1.0), w, bias)
    changed = np.flatnonzero(np.abs(np.asarray(moved - base)).max((0, 2)))
    assert changed.tolist() == [9, 10, 11, 12]
    # and the gradient of a position reaches back three, never forward
    grad = jax.grad(lambda v: causal_conv(v, w, bias)[0, 9].sum())(x)
    reached = np.flatnonzero(np.abs(np.asarray(grad)).max((0, 2)))
    assert reached.tolist() == [6, 7, 8, 9]
