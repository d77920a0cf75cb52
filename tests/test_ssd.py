"""The chunked scan over matrix-valued state against the recurrence as
written (ops/ssd.py), forward and gradient, and the causal depthwise
convolution against shifted sums: small sizes, seeded inputs, float32
on the CPU."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fmda_tpu.ops import ssd
from fmda_tpu.ops.ssd import (
    causal_conv, conv_silu, ssd_scan, ssd_scan_stepwise)

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
# twelve chunks in three groups (the ordered walk), ten chunks (no
# multiple of the group: five groups of two), a padded length of six
# chunks in two groups of three, and steps so large that a decay
# underflows inside a chunk
@pytest.mark.parametrize("t,chunk,step_scale", [
    (32, 8, 1.0), (37, 8, 1.0), (8, 8, 1.0), (5, 8, 1.0), (96, 8, 1.0),
    (64, 16, 60.0), (80, 8, 1.0), (43, 8, 1.0)])
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
    (32, 8, 1.0), (37, 8, 1.0), (96, 8, 1.0), (64, 16, 60.0), (80, 8, 1.0),
    (43, 8, 1.0)])
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


def _states_stepwise(xs, d, a, b, chunk):
    """The recurrence's state after every ``chunk`` positions and after
    the last one, (B, chunks, H, P, N), position by position."""
    t = xs.shape[1]

    def step(state, at):
        x_t, d_t, b_t = at
        state = (jnp.exp(d_t * a)[..., None, None] * state
                 + jnp.einsum("bh,bhp,bn->bhpn", d_t, x_t, b_t))
        return state, state

    _, every = jax.lax.scan(
        step, jnp.zeros((B, H, P, N)),
        tuple(jnp.moveaxis(v, 1, 0) for v in (xs, d, b)))
    ends = [i for i in range(t) if (i + 1) % chunk == 0 or i == t - 1]
    return jnp.moveaxis(every[jnp.asarray(ends)], 0, 1)


# one group, three groups of four, five groups of two, and a padded
# length (the last chunk's state is the state after the last position)
@pytest.mark.parametrize("t,chunk", [(24, 8), (96, 8), (80, 8), (43, 8)])
def test_the_carried_states_are_the_recurrences_states_at_chunk_ends(
        t, chunk):
    xs, d, a, b, c, skip = _inputs(t, seed=9)
    with jax.default_matmul_precision("highest"):
        _, states = ssd_scan(xs, d, a, b, c, skip, chunk=chunk)
        want = _states_stepwise(xs, d, a, b, chunk)
    np.testing.assert_allclose(states, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("t,chunk", [(96, 8), (80, 8), (43, 8)])
def test_the_carried_states_gradient_is_the_recurrences(t, chunk):
    """What flows back through ``states`` alone, into the four inputs
    that reach a state (``c`` and the skip do not), against the
    recurrence's own states under autodiff."""
    xs, d, a, b, c, skip = _inputs(t, seed=t + 2)

    def through(fn):
        return jax.grad(lambda *v: jnp.sum(jnp.sin(fn(*v))),
                        argnums=(0, 1, 2, 3))(xs, d, a, b)

    with jax.default_matmul_precision("highest"):
        want = through(lambda *v: _states_stepwise(*v, chunk))
        got = through(lambda *v: ssd_scan(*v, c, skip, chunk=chunk)[1])
    for g, w in zip(got, want):
        _close(g, w, 1e-4)


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


def _producers(text, shape):
    """The operations of a lowered program whose result has ``shape``."""
    made = set()
    for line in text.splitlines():
        m = re.search(r"= \"?(?:stablehlo\.|func\.)?([\w.]+)", line)
        if not m or " = " not in line:
            continue
        results = line.rsplit("->", 1)[-1] if "->" in line else (
            line.rsplit(" : ", 1)[-1])
        if f"tensor<{shape}>" in results:
            made.add(m.group(1))
    return made


def test_the_walk_holds_no_second_array_of_the_sequence_or_of_the_states():
    """Twelve chunks in three groups, inputs in bfloat16 as the decoder
    hands them over, value and gradient through ``y`` and ``states``:
    outside a group nothing computes a float32 array of ``d * xs``'s
    shape (by chunk, group-major or flat: ``y`` and its cotangent are
    moved, never multiplied, added or converted), the chunk states exist
    as the walk's stacked output and its cotangent alone (no shifted copy,
    no padded halves of a log-depth scan), and the carried state is
    float32."""
    xs, d, a, b, c, skip = _inputs(96, seed=6)
    half = jnp.bfloat16

    def value(*args):
        y, states = ssd_scan(*args, chunk=8, dtype=half)
        return jnp.sum(jnp.sin(y)) + jnp.sum(jnp.sin(states))

    text = jax.jit(jax.grad(value, argnums=tuple(range(6)))).lower(
        xs.astype(half), d, a, b.astype(half), c.astype(half), skip
    ).as_text()
    moves = {"reshape", "transpose", "while", "dynamic_slice",
             "dynamic_update_slice", "broadcast_in_dim", "constant"}
    g = ssd.CHUNK_GROUP
    groups = 12 // g
    for shape in (f"{B}x12x8x{H}x{P}", f"{B}x{groups}x{g}x8x{H}x{P}",
                  f"{groups}x{B}x{g}x8x{H}x{P}"):
        assert _producers(text, f"{shape}xf32") <= moves, shape
    # sin's derivative over y and over states is the one computation on
    # an array of either size: the test's own
    assert _producers(text, f"{B}x96x{H}x{P}xf32") <= moves | {
        "sine", "cosine", "multiply"}
    for shape in (f"{B}x12x{H}x{P}x{N}", f"{B}x{groups}x{g}x{H}x{P}x{N}",
                  f"{groups}x{B}x{g}x{H}x{P}x{N}"):
        extra = {"sine", "cosine", "multiply"} if shape.startswith(
            f"{B}x12x") else set()
        assert _producers(text, f"{shape}xf32") <= moves | extra, shape
        assert not _producers(text, f"{shape}xbf16"), shape
    for width in range(1, 12):  # no half, quarter ... of the chunk axis
        assert not _producers(text, f"{B}x{width}x{H}x{P * N}xf32")
    assert f"tensor<{B}x{H}x{P}x{N}xf32>" in text      # the carried state
    assert f"tensor<{B}x{H}x{P}x{N}xbf16>" not in text


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


def _as_written(x, w, bias, *, dtype):
    return jax.nn.silu(causal_conv(x, w, bias)).astype(dtype)


# the activated convolution as written (`causal_conv` + `silu`, the
# reference) and as the mixer runs it (`conv_silu`): one result
BOTH_FORMS = pytest.mark.parametrize(
    "conv", [_as_written, conv_silu], ids=["as_written", "conv_silu"])


@BOTH_FORMS
def test_the_convolution_is_four_shifted_sums(conv):
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (2, 19, 6))
    w = jax.random.normal(k[1], (6, 4))
    bias = jax.random.normal(k[2], (6,))
    want = _shifted_sums(x, w, bias)
    got = causal_conv(x, w, bias)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    got = conv(x, w, bias, dtype=jnp.float32)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    np.testing.assert_allclose(got, want / (1 + np.exp(-want)), rtol=1e-5,
                               atol=1e-6)
    # the first position sees its own tap and the bias alone
    np.testing.assert_allclose(
        got[:, 0], jax.nn.silu(bias + w[:, 3] * x[:, 0]), rtol=1e-5,
        atol=1e-6)


@BOTH_FORMS
def test_the_convolution_reads_no_future_position(conv):
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(k[0], (1, 16, 5))
    w, bias = jax.random.normal(k[1], (5, 4)), jnp.zeros((5,))
    base = conv(x, w, bias, dtype=jnp.float32)
    moved = conv(x.at[:, 9].add(1.0), w, bias, dtype=jnp.float32)
    changed = np.flatnonzero(np.abs(np.asarray(moved - base)).max((0, 2)))
    assert changed.tolist() == [9, 10, 11, 12]
    # and the gradient of a position reaches back three, never forward
    grad = jax.grad(
        lambda v: conv(v, w, bias, dtype=jnp.float32)[0, 9].sum())(x)
    reached = np.flatnonzero(np.abs(np.asarray(grad)).max((0, 2)))
    assert reached.tolist() == [6, 7, 8, 9]


@pytest.mark.parametrize("dtype,batch,t,channels", [
    (jnp.float32, 1, 512, 256),
    (jnp.bfloat16, 2, 512, 256),
    (jnp.float32, 2, 24, 256),
    (jnp.bfloat16, 1, 256, 256),
    (jnp.float32, 1, 600, 256),
    (jnp.bfloat16, 2, 600, 256),
    (jnp.bfloat16, 1, 512, 4352),
    (jnp.float32, 2, 300, 4352),
])
def test_conv_silu_is_the_convolution_as_written_and_its_gradient(
        dtype, batch, t, channels):
    """The value bit for bit (the same float32 sums in the same order,
    one rounding), and ``dx``, ``dw``, ``dbias`` against ``jax.grad`` of
    the form as written (``dx`` added in float32 and rounded once to
    ``x``'s dtype on both)."""
    f32 = jnp.float32
    k = jax.random.split(jax.random.PRNGKey(t + channels), 4)
    x = jax.random.normal(k[0], (batch, t, channels)).astype(dtype)
    w = jax.random.uniform(k[1], (channels, 4), minval=-0.5, maxval=0.5)
    bias = jax.random.uniform(k[2], (channels,), minval=-0.5, maxval=0.5)
    ct = jax.random.normal(k[3], (batch, t, channels))

    for out in (f32, dtype):
        got = conv_silu(x, w, bias, dtype=out)
        assert got.dtype == out
        assert (got == _as_written(x, w, bias, dtype=out)).all()

    def grads(conv):
        return jax.grad(lambda *a: (
            conv(*a, dtype=dtype).astype(f32) * ct).sum(), (0, 1, 2))(
                x, w, bias)

    for name, g, ref in zip(("dx", "dw", "dbias"), grads(conv_silu),
                            grads(_as_written)):
        assert g.dtype == ref.dtype and g.shape == ref.shape, name
        tol = 1e-2 if (name == "dx" and dtype == jnp.bfloat16) else 2e-5
        _close(g.astype(f32), ref.astype(f32), tol)


def test_conv_silu_keeps_its_arguments_alone_and_adds_dx_in_float32():
    """What backward holds of the forward is ``x``, ``w`` and ``bias``
    (no float32 array of the sequence), and a bfloat16 ``dx`` is the
    float32 sum of its four terms rounded once: as close to the float32
    gradient as one rounding allows, where autodiff of the forward's own
    sums (each term rounded to bfloat16, then added) is not."""
    from fmda_tpu.ops.ssd import _conv_taps

    f32, bf16 = jnp.float32, jnp.bfloat16
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(k[0], (2, 64, 128)).astype(bf16)
    w = jax.random.uniform(k[1], (128, 4), minval=-0.5, maxval=0.5)
    bias = jax.random.uniform(k[2], (128,), minval=-0.5, maxval=0.5)
    ct = jax.random.normal(k[3], x.shape)

    _, vjp = jax.vjp(lambda *a: conv_silu(*a, dtype=bf16), x, w, bias)
    kept = sorted((leaf.shape, leaf.dtype.name)
                  for leaf in jax.tree.leaves(vjp) if hasattr(leaf, "shape"))
    assert kept == sorted([(x.shape, "bfloat16"), (w.shape, "float32"),
                           (bias.shape, "float32")])

    def dx(conv, v):
        return jax.grad(lambda v: (conv(v).astype(f32) * ct).sum())(v)

    exact = dx(lambda v: _as_written(v, w, bias, dtype=f32), x.astype(f32))
    got = dx(lambda v: conv_silu(v, w, bias, dtype=bf16), x)
    naive = dx(lambda v: jax.nn.silu(_conv_taps(v, w, bias)[0]).astype(bf16),
               x)
    assert got.dtype == bf16 and naive.dtype == bf16
    # autodiff of the form as written also adds in float32 and rounds once
    rounded = dx(lambda v: _as_written(v, w, bias, dtype=bf16), x)
    assert (got == rounded).mean() > 0.99
    err = lambda g: float(jnp.abs(g.astype(f32) - exact).mean())
    assert err(got) < 0.75 * err(naive)
