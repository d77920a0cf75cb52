"""Fused Pallas LSTM kernel pair vs the lax.scan reference.

Same three coverage layers as test_pallas_gru.py: interpret-mode parity
(outputs and all gradients, both directions, nonzero initial state,
forced multi-block), Mosaic TPU lowering via jax.export at the listed
shapes, and an on-device parity test gated on a reachable TPU.
"""

import numpy as np
import pytest

import jax
# jax.export is a real submodule on every supported jax, but older
# releases only expose it as a `jax` attribute after an explicit import
import jax.export  # noqa: F401
import jax.numpy as jnp

from fmda_tpu.ops.lstm import LSTMWeights, lstm_input_projection, lstm_scan
from fmda_tpu.ops.pallas_lstm import lstm_scan_pallas


def _setup(batch=4, seq=12, feats=10, hidden=8, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    w = LSTMWeights(
        w_ih=jax.random.normal(ks[0], (4 * hidden, feats)) * 0.3,
        w_hh=jax.random.normal(ks[1], (4 * hidden, hidden)) * 0.3,
        b_ih=jax.random.normal(ks[2], (4 * hidden,)) * 0.1,
        b_hh=jax.random.normal(ks[3], (4 * hidden,)) * 0.1,
    )
    x = jax.random.normal(ks[4], (batch, seq, feats))
    xp = lstm_input_projection(x, w)
    h0 = jnp.zeros((batch, hidden))
    c0 = jnp.zeros((batch, hidden))
    return w, xp, h0, c0


@pytest.mark.parametrize("reverse", [False, True])
def test_pallas_lstm_matches_scan(reverse):
    w, xp, h0, c0 = _setup()
    (h_ref, c_ref), hs_ref = lstm_scan(
        xp, h0, c0, w.w_hh, w.b_hh, reverse=reverse)
    (h_pal, c_pal), hs_pal = lstm_scan_pallas(
        xp, h0, c0, w.w_hh, w.b_hh, reverse=reverse, interpret=True)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(c_pal), np.asarray(c_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hs_pal), np.asarray(hs_ref), atol=1e-5)


def test_pallas_lstm_nonzero_initial_state():
    w, xp, _, _ = _setup()
    h0 = jax.random.normal(jax.random.PRNGKey(8), (4, 8))
    c0 = jax.random.normal(jax.random.PRNGKey(9), (4, 8))
    (h_ref, c_ref), hs_ref = lstm_scan(xp, h0, c0, w.w_hh, w.b_hh)
    (h_pal, c_pal), hs_pal = lstm_scan_pallas(
        xp, h0, c0, w.w_hh, w.b_hh, interpret=True)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(c_pal), np.asarray(c_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hs_pal), np.asarray(hs_ref), atol=1e-5)


def _loss(fn, *args, **kw):
    (h_last, c_last), hs = fn(*args, **kw)
    return (jnp.sum(h_last**2) + jnp.sum(jnp.tanh(c_last))
            + jnp.sum(jnp.sin(hs)))


@pytest.mark.parametrize("reverse", [False, True])
def test_pallas_lstm_gradients_match(reverse):
    """The backward kernel (gate recompute from hs/cs, dh+dc VMEM carries)
    must give the scan's gradients for every input, both directions,
    including nonzero initial state and a cotangent on c_last."""
    w, xp, _, _ = _setup()
    h0 = jax.random.normal(jax.random.PRNGKey(8), (4, 8))
    c0 = jax.random.normal(jax.random.PRNGKey(9), (4, 8))

    g_pal = jax.grad(
        lambda *a: _loss(
            lambda *x: lstm_scan_pallas(*x, reverse=reverse, interpret=True),
            *a),
        argnums=(0, 1, 2, 3, 4))(xp, h0, c0, w.w_hh, w.b_hh)
    g_ref = jax.grad(
        lambda *a: _loss(lambda *x: lstm_scan(*x, reverse=reverse), *a),
        argnums=(0, 1, 2, 3, 4))(xp, h0, c0, w.w_hh, w.b_hh)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("reverse", [False, True])
def test_pallas_lstm_multiblock_parity(reverse, monkeypatch):
    """Force block_t < T so h/c (fwd) and dh/dc/dwt/db (bwd) carry across
    several grid steps."""
    from fmda_tpu.ops import pallas_lstm

    monkeypatch.setattr(pallas_lstm, "_default_block_t",
                        lambda *a, **k: 3)
    w, xp, _, _ = _setup(seq=12)  # 4 blocks of 3
    h0 = jax.random.normal(jax.random.PRNGKey(7), (4, 8))
    c0 = jax.random.normal(jax.random.PRNGKey(6), (4, 8))

    (h_ref, c_ref), hs_ref = lstm_scan(
        xp, h0, c0, w.w_hh, w.b_hh, reverse=reverse)
    (h_pal, c_pal), hs_pal = lstm_scan_pallas(
        xp, h0, c0, w.w_hh, w.b_hh, reverse=reverse, interpret=True)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(c_pal), np.asarray(c_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hs_pal), np.asarray(hs_ref), atol=1e-5)

    g_pal = jax.grad(
        lambda *a: _loss(
            lambda *x: lstm_scan_pallas(*x, reverse=reverse, interpret=True),
            *a),
        argnums=(0, 1, 2, 3, 4))(xp, h0, c0, w.w_hh, w.b_hh)
    g_ref = jax.grad(
        lambda *a: _loss(lambda *x: lstm_scan(*x, reverse=reverse), *a),
        argnums=(0, 1, 2, 3, 4))(xp, h0, c0, w.w_hh, w.b_hh)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("reverse", [False, True])
def test_pallas_lstm_bf16_numerics_close_to_scan(reverse):
    w, xp32, _, _ = _setup(batch=8, seq=16, hidden=8)
    bf16 = jnp.bfloat16
    xp = xp32.astype(bf16)
    h0 = jax.random.normal(jax.random.PRNGKey(5), (8, 8), bf16)
    c0 = jax.random.normal(jax.random.PRNGKey(4), (8, 8), bf16)
    args = (xp, h0, c0, w.w_hh.astype(bf16), w.b_hh.astype(bf16))

    def loss32(fn, *a):
        (h_last, c_last), hs = fn(*a)
        return (jnp.sum(h_last.astype(jnp.float32) ** 2)
                + jnp.sum(jnp.sin(hs.astype(jnp.float32))))

    g_pal = jax.grad(
        lambda *a: loss32(
            lambda *x: lstm_scan_pallas(*x, reverse=reverse, interpret=True),
            *a),
        argnums=(0, 1, 2, 3, 4))(*args)
    g_ref = jax.grad(
        lambda *a: loss32(lambda *x: lstm_scan(*x, reverse=reverse), *a),
        argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-2, atol=5e-2)


# ~4 s of Mosaic lowering per combo: tier-1 keeps one lowering per
# shape (directions alternated); the full matrix runs under slow
@pytest.mark.parametrize("batch,seq,hidden,reverse", [
    pytest.param(256, 30, 32, False, id="flagship-fwd"),
    pytest.param(16, 1024, 32, True, id="longctx-rev"),
    pytest.param(256, 30, 32, True, id="flagship-rev",
                 marks=pytest.mark.slow),
    pytest.param(16, 1024, 32, False, id="longctx-fwd",
                 marks=pytest.mark.slow),
])
def test_pallas_lstm_lowers_for_tpu(batch, seq, hidden, reverse):
    """Mosaic TPU lowering of the fwd+bwd pair at the listed shapes via
    jax.export — no hardware required."""
    xp = jnp.zeros((batch, seq, 4 * hidden))
    h0 = jnp.zeros((batch, hidden))
    c0 = jnp.zeros((batch, hidden))
    w_hh = jnp.zeros((4 * hidden, hidden))
    b_hh = jnp.zeros((4 * hidden,))

    def train_like(xp, h0, c0, w_hh, b_hh):
        def loss(*args):
            (h_last, c_last), hs = lstm_scan_pallas(*args, reverse=reverse)
            return (jnp.sum(h_last) + jnp.sum(c_last)
                    + jnp.sum(hs.astype(jnp.float32) ** 2))

        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(xp, h0, c0, w_hh, b_hh)

    exported = jax.export.export(jax.jit(train_like), platforms=["tpu"])(
        xp, h0, c0, w_hh, b_hh
    )
    assert "tpu" in exported.platforms


def test_pallas_lstm_on_tpu_device():
    """On-device parity vs the scan path — runs only when a TPU is
    actually reachable (skipped on the CPU-forced CI mesh)."""
    if jax.default_backend() != "tpu":
        pytest.skip("no TPU backend in this environment")
    w, xp, h0, c0 = _setup(batch=8, seq=12, hidden=8)

    def grads(use_pallas):
        def loss(xp_, h0_, c0_, w_hh, b_hh):
            fn = lstm_scan_pallas if use_pallas else lstm_scan
            (h_last, c_last), hs = fn(xp_, h0_, c0_, w_hh, b_hh)
            return jnp.sum(h_last**2) + jnp.sum(c_last**2) + jnp.sum(hs**2)

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))

    g_pal = grads(True)(xp, h0, c0, w.w_hh, w.b_hh)
    g_ref = grads(False)(xp, h0, c0, w.w_hh, w.b_hh)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
