"""Fused Pallas GRU kernel vs the lax.scan reference.

Three layers of coverage, in increasing hardware requirements:
- interpret-mode numerical parity (runs anywhere, including this CI);
- Mosaic TPU *lowering* via ``jax.export(platforms=['tpu'])`` — catches
  tiling/layout rejections (e.g. sub-8 sublane blocks) without a TPU;
- on-device parity, gated on an actual TPU backend being reachable.
"""

import numpy as np
import pytest

import jax
# jax.export is a real submodule on every supported jax, but older
# releases only expose it as a `jax` attribute after an explicit import
import jax.export  # noqa: F401
import jax.numpy as jnp

from fmda_tpu.ops.gru import GRUWeights, gru_scan, input_projection
from fmda_tpu.ops.pallas_gru import gru_scan_pallas


def _setup(batch=4, seq=12, feats=10, hidden=8, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    w = GRUWeights(
        w_ih=jax.random.normal(ks[0], (3 * hidden, feats)) * 0.3,
        w_hh=jax.random.normal(ks[1], (3 * hidden, hidden)) * 0.3,
        b_ih=jax.random.normal(ks[2], (3 * hidden,)) * 0.1,
        b_hh=jax.random.normal(ks[3], (3 * hidden,)) * 0.1,
    )
    x = jax.random.normal(ks[4], (batch, seq, feats))
    xp = input_projection(x, w)
    h0 = jnp.zeros((batch, hidden))
    return w, x, xp, h0


@pytest.mark.parametrize("reverse", [False, True])
def test_pallas_kernel_matches_scan(reverse):
    w, _, xp, h0 = _setup()
    h_ref, hs_ref = gru_scan(xp, h0, w.w_hh, w.b_hh, reverse=reverse)
    h_pal, hs_pal = gru_scan_pallas(
        xp, h0, w.w_hh, w.b_hh, reverse=reverse, interpret=True
    )
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hs_pal), np.asarray(hs_ref), atol=1e-5)


def test_pallas_kernel_nonzero_h0():
    w, _, xp, _ = _setup()
    h0 = jax.random.normal(jax.random.PRNGKey(9), (4, 8))
    h_ref, hs_ref = gru_scan(xp, h0, w.w_hh, w.b_hh)
    h_pal, hs_pal = gru_scan_pallas(xp, h0, w.w_hh, w.b_hh, interpret=True)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hs_pal), np.asarray(hs_ref), atol=1e-5)


@pytest.mark.parametrize("reverse", [
    False,
    # reverse-direction bf16 numerics ride the slow tier: the f32 parity
    # suite covers both directions and the bf16 gate math is direction-
    # independent (same fused kernel, mirrored walk)
    pytest.param(True, marks=pytest.mark.slow),
])
def test_pallas_kernel_bf16_numerics_close_to_scan(reverse):
    """bf16 kernel outputs and gradients track the bf16 lax.scan path
    within bf16 tolerance (catches precision bugs the all-zero lowering
    test cannot — e.g. low-precision accumulators)."""
    w, _, xp32, _ = _setup(batch=8, seq=16, hidden=8)
    bf16 = jnp.bfloat16
    xp = xp32.astype(bf16)
    h0 = jax.random.normal(jax.random.PRNGKey(5), (8, 8), bf16)

    def loss(fn, *args):
        h_last, hs = fn(*args)
        return (jnp.sum(h_last.astype(jnp.float32) ** 2)
                + jnp.sum(jnp.sin(hs.astype(jnp.float32))))

    args = (xp, h0, w.w_hh.astype(bf16), w.b_hh.astype(bf16))
    g_pal = jax.grad(
        lambda *a: loss(
            lambda *x: gru_scan_pallas(*x, reverse=reverse, interpret=True),
            *a),
        argnums=(0, 1, 2, 3))(*args)
    g_ref = jax.grad(
        lambda *a: loss(lambda *x: gru_scan(*x, reverse=reverse), *a),
        argnums=(0, 1, 2, 3))(*args)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("reverse", [False, True])
def test_pallas_kernel_gradients_match(reverse):
    """The backward Pallas kernel (reverse-time grid, in-kernel gate
    recompute) must give the reference scan's gradients for every input,
    in both directions, including a nonzero h0."""
    w, _, xp, _ = _setup()
    h0 = jax.random.normal(jax.random.PRNGKey(9), (4, 8))

    def loss_pallas(xp_, h0_, w_hh, b_hh):
        h_last, hs = gru_scan_pallas(
            xp_, h0_, w_hh, b_hh, reverse=reverse, interpret=True)
        return jnp.sum(h_last**2) + jnp.sum(jnp.sin(hs))

    def loss_ref(xp_, h0_, w_hh, b_hh):
        h_last, hs = gru_scan(xp_, h0_, w_hh, b_hh, reverse=reverse)
        return jnp.sum(h_last**2) + jnp.sum(jnp.sin(hs))

    g_pal = jax.grad(loss_pallas, argnums=(0, 1, 2, 3))(xp, h0, w.w_hh, w.b_hh)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(xp, h0, w.w_hh, w.b_hh)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("reverse", [False, True])
def test_pallas_kernel_multiblock_parity(reverse, monkeypatch):
    """Cross-block state carry: force block_t < T so the grid hands h (fwd)
    and dh/dwt/db (bwd) across several grid steps — the blocked path the
    tiny default shapes never exercise (their whole T fits one block) —
    and check outputs AND gradients against the scan, both directions."""
    from fmda_tpu.ops import pallas_gru

    monkeypatch.setattr(pallas_gru, "_default_block_t", lambda *a, **k: 3)
    w, _, xp, _ = _setup(seq=12)  # 4 blocks of 3
    h0 = jax.random.normal(jax.random.PRNGKey(7), (4, 8))

    h_ref, hs_ref = gru_scan(xp, h0, w.w_hh, w.b_hh, reverse=reverse)
    h_pal, hs_pal = gru_scan_pallas(
        xp, h0, w.w_hh, w.b_hh, reverse=reverse, interpret=True)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hs_pal), np.asarray(hs_ref), atol=1e-5)

    def make_loss(fn, **kw):
        def loss(xp_, h0_, w_hh, b_hh):
            h_last, hs = fn(xp_, h0_, w_hh, b_hh, reverse=reverse, **kw)
            return jnp.sum(h_last**2) + jnp.sum(jnp.sin(hs))
        return loss

    g_pal = jax.grad(make_loss(gru_scan_pallas, interpret=True),
                     argnums=(0, 1, 2, 3))(xp, h0, w.w_hh, w.b_hh)
    g_ref = jax.grad(make_loss(gru_scan),
                     argnums=(0, 1, 2, 3))(xp, h0, w.w_hh, w.b_hh)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# Each export costs ~4 s of Mosaic lowering on the one-core CI box, so
# tier-1 runs a representative slice — both dtypes AND both directions
# at the flagship shape, plus one lowering per remaining shape —
# and the full 12-combo matrix stays available under `-m slow`.
_LOWERING_CASES = [
    pytest.param(256, 30, 32, False, "float32", id="flagship-fwd-f32"),
    pytest.param(256, 30, 32, True, "bfloat16", id="flagship-rev-bf16"),
    pytest.param(16, 1024, 32, False, "float32", id="longctx-fwd-f32"),
    pytest.param(800, 30, 32, True, "float32", id="multiticker-rev-f32"),
] + [
    pytest.param(b, s, h, rev, dt, id=f"{name}-{'rev' if rev else 'fwd'}-"
                 f"{'bf16' if dt == 'bfloat16' else 'f32'}",
                 marks=pytest.mark.slow)
    for (b, s, h, name) in [(256, 30, 32, "flagship"),
                            (16, 1024, 32, "longctx"),
                            (800, 30, 32, "multiticker")]
    for rev in (False, True)
    for dt in ("float32", "bfloat16")
    if (b, s, h, rev, dt) not in [
        (256, 30, 32, False, "float32"), (256, 30, 32, True, "bfloat16"),
        (16, 1024, 32, False, "float32"), (800, 30, 32, True, "float32")]
]


@pytest.mark.parametrize("batch,seq,hidden,reverse,dtype", _LOWERING_CASES)
def test_pallas_kernel_lowers_for_tpu(batch, seq, hidden, reverse, dtype):
    """Mosaic TPU lowering of the full fwd+bwd kernel pair at every listed
    shape, both directions and compute dtypes, via jax.export — no TPU
    required.  This is what rejected the original batch-major (B, 1, 3H)
    block layout (sublane dim 1 < 8) and the mixed-dtype bf16 gate math."""
    dt = jnp.dtype(dtype)
    xp = jnp.zeros((batch, seq, 3 * hidden), dt)
    h0 = jnp.zeros((batch, hidden), dt)
    w_hh = jnp.zeros((3 * hidden, hidden), dt)
    b_hh = jnp.zeros((3 * hidden,), dt)

    def train_like(xp, h0, w_hh, b_hh):
        def loss(*args):
            h_last, hs = gru_scan_pallas(*args, reverse=reverse)
            return (jnp.sum(h_last.astype(jnp.float32))
                    + jnp.sum(hs.astype(jnp.float32) ** 2))

        return jax.grad(loss, argnums=(0, 1, 2, 3))(xp, h0, w_hh, b_hh)

    exported = jax.export.export(jax.jit(train_like), platforms=["tpu"])(
        xp, h0, w_hh, b_hh
    )
    assert "tpu" in exported.platforms


def test_pallas_kernel_on_tpu_device():
    """On-device parity vs the scan path — runs only when a TPU is
    actually reachable (skipped on the CPU-forced CI mesh)."""
    if jax.default_backend() != "tpu":
        pytest.skip("no TPU backend in this environment")
    w, _, xp, h0 = _setup(batch=8, seq=12, hidden=8)

    def loss_fn(use_pallas):
        def loss(xp_, h0_, w_hh, b_hh):
            fn = gru_scan_pallas if use_pallas else gru_scan
            h_last, hs = fn(xp_, h0_, w_hh, b_hh)
            return jnp.sum(h_last**2) + jnp.sum(hs**2)

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))

    g_pal = loss_fn(True)(xp, h0, w.w_hh, w.b_hh)
    g_ref = loss_fn(False)(xp, h0, w.w_hh, w.b_hh)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


class TestKernelSupported:
    """Per-shape VMEM feasibility gate behind automatic kernel-vs-scan
    selection (fmda_tpu.ops.gru.select_scan_fn)."""

    def test_flagship_and_longctx_supported(self):
        from fmda_tpu.ops.pallas_gru import kernel_supported

        assert kernel_supported(256, 30, 32, 4)      # flagship f32
        assert kernel_supported(16, 1024, 128, 4)    # longctx f32
        assert kernel_supported(256, 30, 128, 4)

    def test_mxu_wide_shapes_fall_back(self):
        from fmda_tpu.ops.pallas_gru import kernel_supported

        # H=1024: the backward's resident weights (6H^2) + f32 dW (3H^2)
        # alone exceed the ~16MB core VMEM; scan is the right path
        assert not kernel_supported(512, 30, 1024, 2)   # flagship_wide bf16
        assert not kernel_supported(256, 30, 1024, 4)

    def test_select_scan_fn_gates_on_shape(self, monkeypatch):
        from fmda_tpu.ops import gru

        # pretend the backend has the kernel so the shape gate is what
        # decides (CI runs on CPU where availability alone would skip it)
        monkeypatch.setattr(gru, "pallas_scan_available", lambda: True)
        from fmda_tpu.ops.pallas_gru import gru_scan_pallas

        assert gru.select_scan_fn(
            True, shape=(256, 30, 32), itemsize=4) is gru_scan_pallas
        assert gru.select_scan_fn(
            True, shape=(512, 30, 1024), itemsize=2) is gru.gru_scan
        # no shape -> previous behavior (kernel when available+unmasked)
        assert gru.select_scan_fn(True) is gru_scan_pallas
        assert gru.select_scan_fn(False, shape=(256, 30, 32)) is gru.gru_scan

    def test_lstm_predicate_mirrors_gru(self, monkeypatch):
        from fmda_tpu.ops import lstm as lstm_mod
        from fmda_tpu.ops.pallas_lstm import kernel_supported, lstm_scan_pallas

        assert kernel_supported(256, 30, 32, 4)
        assert not kernel_supported(512, 30, 1024, 2)
        monkeypatch.setattr(
            lstm_mod, "lstm_pallas_available", lambda: True)
        assert lstm_mod.select_lstm_scan_fn(
            True, shape=(256, 30, 32), itemsize=4) is lstm_scan_pallas
        assert lstm_mod.select_lstm_scan_fn(
            True, shape=(512, 30, 1024), itemsize=2) is lstm_mod.lstm_scan

    def test_block_t_shrinks_before_overflow(self):
        """Where the kernel IS supported but H is large, the block
        chooser charges the resident weights first: the chosen block's
        total working set stays under the budget."""
        from fmda_tpu.ops.pallas_gru import (
            _VMEM_BUDGET, _bwd_const_bytes, _default_block_t)

        batch, seq, hidden, itemsize = 64, 256, 256, 4
        const = _bwd_const_bytes(batch, hidden, itemsize)
        k = _default_block_t(seq, batch, hidden, itemsize,
                             units_per_step=8, const_bytes=const)
        per_step = batch * 8 * hidden * itemsize * 2
        assert seq % k == 0
        assert const + k * per_step <= _VMEM_BUDGET
