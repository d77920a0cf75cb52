"""GatedSSM (cell="ssm") family contract — the O(1)-cache dual form.

No torch parity here (the reference's only model is a GRU; this family
is net-new, ISSUE 14).  What's locked instead:

- the **duality contract** on shared parameters: the sequential
  ``lax.scan`` reference is op-for-op the serving step (tight ulp
  tolerance), the parallel associative-scan training mode matches it to
  the documented 1e-5, and the whole train-mode model forward matches
  the serve-mode carried core stepped over the same rows;
- the shared-protocol seams: build_model dispatch, logits shape/dtype,
  mask/padding invariance, chunked state carry, Trainer integration;
- serving-economics invariants: the carried cache is three H-vectors
  per layer with a zero-width ring (nothing sized by ``window``), and
  the family refuses the bidirectional carried core loudly.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fmda_tpu.config import ModelConfig, TrainConfig
from fmda_tpu.data.normalize import NormParams
from fmda_tpu.models import GatedSSM, build_model
from fmda_tpu.ops.ssm import (
    SSMWeights,
    ema_pool_parallel,
    ssm_cell_step,
    ssm_input_projection,
    ssm_scan,
    ssm_scan_parallel,
)
from fmda_tpu.serve.streaming import StreamingBiGRU


def _weights(hidden=8, feats=6, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    return SSMWeights(
        w_ih=jax.random.normal(ks[0], (3 * hidden, feats)) * 0.3,
        b_ih=jax.random.normal(ks[1], (3 * hidden,)) * 0.1,
        a_base=jax.random.uniform(ks[2], (hidden,), minval=1.0, maxval=3.0),
        d=jax.random.normal(ks[3], (hidden,)) * 0.3,
        rho_f=jnp.zeros((hidden,)),
        rho_s=jnp.full((hidden,), 3.0),
    )


def _cfg(**kw):
    base = dict(hidden_size=8, n_features=6, output_size=4, dropout=0.0,
                spatial_dropout=False, bidirectional=False, cell="ssm")
    base.update(kw)
    return ModelConfig(**base)


# ---------------------------------------------------------------------------
# ops-level duality
# ---------------------------------------------------------------------------


def test_sequential_scan_matches_stepped_serving_cache():
    """ssm_scan is op-for-op repeated ssm_cell_step: stepping the O(1)
    cache tick by tick reproduces the scan to ulp (separately compiled
    programs may differ in fusion order at the last bit — the
    documented caveat; the tolerance here is ~1 ulp, not 1e-5)."""
    w = _weights()
    B, T, H = 3, 12, 8
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, 6))
    xp = ssm_input_projection(x, w)
    carry = tuple(jnp.zeros((B, H)) for _ in range(3))
    c = carry
    hs = []
    for t in range(T):
        h, c = ssm_cell_step(xp[:, t], c, w)
        hs.append(h)
    c_scan, hs_scan = ssm_scan(xp, carry, w)
    np.testing.assert_allclose(
        np.asarray(jnp.stack(hs, axis=1)), np.asarray(hs_scan), atol=1e-6)
    for a, b in zip(c, c_scan):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("with_s0", [False, True])
def test_parallel_mode_matches_sequential_within_documented_tolerance(
        with_s0):
    """THE duality gate (ISSUE 14): the associative-scan training mode
    and the sequential serving recurrence agree on the same parameters
    to the documented 1e-5 — including from a carried nonzero initial
    state (the chunked-training seam)."""
    w = _weights(key=1)
    B, T, H = 4, 30, 8
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, 6))
    xp = ssm_input_projection(x, w)
    s0 = (jax.random.normal(jax.random.PRNGKey(6), (B, H))
          if with_s0 else jnp.zeros((B, H)))
    carry = (s0, jnp.zeros((B, H)), jnp.zeros((B, H)))
    c_scan, hs_scan = ssm_scan(xp, carry, w)
    hs_par, s_last = ssm_scan_parallel(xp, w, s0 if with_s0 else None)
    np.testing.assert_allclose(
        np.asarray(hs_par), np.asarray(hs_scan), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(s_last), np.asarray(c_scan[0]), atol=1e-5)
    # the head EMAs are the same linear-recurrence algebra: the parallel
    # pool equals the cache's carried EMA entries
    np.testing.assert_allclose(
        np.asarray(ema_pool_parallel(hs_scan, w.rho_f)),
        np.asarray(c_scan[1]), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ema_pool_parallel(hs_scan, w.rho_s)),
        np.asarray(c_scan[2]), atol=1e-5)


def test_reverse_parallel_scan_equals_flipped_forward():
    w = _weights(key=2)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 9, 6))
    xp = ssm_input_projection(x, w)
    hs_rev, s_rev = ssm_scan_parallel(xp, w, reverse=True)
    hs_fwd, s_fwd = ssm_scan_parallel(jnp.flip(xp, axis=1), w)
    np.testing.assert_allclose(
        np.asarray(hs_rev), np.asarray(jnp.flip(hs_fwd, axis=1)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(s_rev), np.asarray(s_fwd),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# model-level protocol seams
# ---------------------------------------------------------------------------


def test_build_model_dispatches_ssm():
    assert isinstance(build_model(_cfg()), GatedSSM)


@pytest.mark.parametrize("bidir,layers", [
    (False, 1), (True, 1), (False, 2), (True, 2)])
def test_logits_shape_and_dtype(bidir, layers):
    cfg = _cfg(bidirectional=bidir, n_layers=layers)
    model = build_model(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 10, 6))
    params = model.init({"params": jax.random.PRNGKey(1)}, x)
    logits = model.apply(params, x)
    assert logits.shape == (3, 4)
    assert logits.dtype == jnp.float32


@pytest.mark.parametrize("bidir", [False, True])
def test_masked_padding_equals_truncated_window(bidir):
    """A padded window with a validity mask must produce the truncated
    window's logits: masked steps are identities of the recurrence AND
    of the head EMAs (decay forced to 1, input to 0)."""
    cfg = _cfg(bidirectional=bidir)
    model = build_model(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 10, 6))
    params = model.init({"params": jax.random.PRNGKey(1)}, x)
    mask = jnp.concatenate([jnp.ones((3, 7)), jnp.zeros((3, 3))], axis=1)
    l_masked = model.apply(params, x.at[:, 7:].set(999.0), mask=mask)
    l_trunc = model.apply(params, x[:, :7])
    np.testing.assert_allclose(
        np.asarray(l_masked), np.asarray(l_trunc), atol=1e-5)


def test_chunked_state_carry_matches_full_window():
    """return_state -> feed the next chunk: identical to one long
    window (the linear scan folds s0/ema0 in exactly)."""
    cfg = _cfg()
    model = build_model(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 12, 6))
    params = model.init({"params": jax.random.PRNGKey(1)}, x)
    y_full = model.apply(params, x)
    _, st = model.apply(params, x[:, :7], return_state=True)
    y_chunked = model.apply(params, x[:, 7:], st)
    np.testing.assert_allclose(
        np.asarray(y_chunked), np.asarray(y_full), atol=1e-5)


def test_trainer_runs_ssm_cell_and_loss_drops():
    from fmda_tpu.data.pipeline import Batch
    from fmda_tpu.train.trainer import Trainer

    cfg = _cfg(dropout=0.1, bidirectional=True)
    trainer = Trainer(cfg, TrainConfig(batch_size=8, window=10))
    state = trainer.init_state(jax.random.PRNGKey(0))
    r = np.random.default_rng(0)
    x = r.normal(size=(8, 10, cfg.n_features)).astype(np.float32)
    y = (r.uniform(size=(8, 4)) > 0.5).astype(np.float32)
    b = Batch(x=jnp.asarray(x), y=jnp.asarray(y),
              mask=jnp.ones(8, np.float32))
    rng = jax.random.PRNGKey(1)
    losses = []
    for _ in range(30):
        state, vals = trainer.single_step(state, b, rng)
        losses.append(float(vals.loss))
    assert losses[-1] < losses[0]


def test_backtest_serves_ssm_family():
    """The window-re-scan backtester serves cell="ssm" via build_model —
    each window re-runs the parallel (training) mode, the family's
    bidirectional serving story."""
    from fmda_tpu.data import ArraySource
    from fmda_tpu.serve import backtest

    r = np.random.default_rng(0)
    n, f, window = 60, 6, 8
    x = r.normal(size=(n, f)).astype(np.float32)
    y = (x[:, :4] > 0).astype(np.float32)
    src = ArraySource(x, y, tuple(f"f{i}" for i in range(f)))
    cfg = _cfg(bidirectional=True)
    params = build_model(cfg).init(
        {"params": jax.random.PRNGKey(1)},
        jnp.zeros((1, window, f)))["params"]
    norm = NormParams(np.zeros(f, np.float32), np.ones(f, np.float32))
    result = backtest(src, cfg, params, norm, window=window, batch_size=16)
    assert result.probabilities.shape == (n - window + 1, 4)
    assert not np.any(np.isnan(result.probabilities))


# ---------------------------------------------------------------------------
# the train-mode / serve-mode duality on the WHOLE model path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layers", [1, 2])
def test_train_mode_forward_matches_serve_mode_core(layers):
    """The family's headline contract end to end: a train-mode forward
    (parallel scans + EMA head, models/ssm.py) over a T-window equals
    the serve-mode carried core (StreamingBiGRU with cell='ssm' — the
    O(1) cache stepped T times) on the SAME parameters, to the
    documented tolerance.  Identity normalization isolates the model
    math."""
    cfg = _cfg(n_layers=layers)
    model = build_model(cfg)
    T = 20
    rows = np.random.default_rng(8).normal(size=(T, 6)).astype(np.float32)
    params = model.init({"params": jax.random.PRNGKey(1)},
                        jnp.zeros((1, T, 6)))
    logits = model.apply(params, jnp.asarray(rows)[None])
    want = np.asarray(jax.nn.sigmoid(logits))[0]

    core = StreamingBiGRU(
        cfg, params["params"],
        NormParams(np.zeros(6, np.float32), np.ones(6, np.float32)),
        window=5)  # window is irrelevant to the ssm core: no ring
    for t in range(T):
        got = core.step(rows[t])[0]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_serve_core_carries_no_window_state():
    """The O(1) cache: the ssm core's ring is zero-width (nothing sized
    by `window`), its carry is exactly three H-vectors per layer, and
    ticks are ring-position independent — the serving-economics
    invariant the fleet's export/donate paths ride."""
    cfg = _cfg()
    params = build_model(cfg).init(
        {"params": jax.random.PRNGKey(1)}, jnp.zeros((1, 4, 6)))["params"]
    core = StreamingBiGRU(
        cfg, params,
        NormParams(np.zeros(6, np.float32), np.ones(6, np.float32)),
        window=30)
    assert core._ring.shape == (1, 0, cfg.hidden_size)
    assert len(core._h) == 1 and len(core._h[0]) == 3
    for h in core._h[0]:
        assert h.shape == (1, cfg.hidden_size)


def test_bidirectional_carried_core_refused_loudly():
    from fmda_tpu.serve.streaming import StreamingBiGRUBidirectional

    cfg = _cfg(bidirectional=True)
    with pytest.raises(ValueError, match="no bidirectional carried"):
        StreamingBiGRUBidirectional(
            cfg, {}, NormParams(np.zeros(6), np.ones(6)), window=4)


def test_cell_seams_raise_instead_of_inheriting_the_gru_path():
    """satellite: a third family can't silently inherit the GRU scan.
    The two production seams that branch on ModelConfig.cell must raise
    on families they don't implement: the carried-state serving
    dispatch, and sp_train — whose bare `else` used to route ANY
    non-attn cell into the GRU carry-handoff scan."""
    import optax

    from fmda_tpu.parallel.mesh import build_mesh
    from fmda_tpu.parallel.sp_train import make_sp_train_step
    from fmda_tpu.serve.streaming import _recurrent_cell_ops

    with pytest.raises(ValueError, match="window-re-scan Predictor"):
        _recurrent_cell_ops("tcn")
    mesh = build_mesh()  # 1-device mesh is enough to reach the dispatch
    for cell in ("ssm", "lstm", "tcn"):
        with pytest.raises(ValueError, match="sequence-parallel"):
            make_sp_train_step(
                mesh, _cfg(cell=cell, bidirectional=False), 8,
                optax.sgd(1e-3))


def test_kernel_fallbacks_are_counted_not_silent():
    """satellite: use_pallas resolving to the reference path leaves a
    counted signal, per cell and reason, for every family."""
    from fmda_tpu.ops.dispatch import (
        kernel_fallbacks, reset_kernel_fallbacks)
    from fmda_tpu.ops.gru import gru_scan, select_scan_fn
    from fmda_tpu.ops.lstm import lstm_scan, select_lstm_scan_fn
    from fmda_tpu.ops.ssm import select_ssm_step_fn, ssm_cell_step

    reset_kernel_fallbacks()
    # off-TPU: every family's kernel request falls back on backend
    assert select_scan_fn(True) is gru_scan
    assert select_lstm_scan_fn(True) is lstm_scan
    assert select_ssm_step_fn(True) is ssm_cell_step
    # masked requests fall back regardless of backend
    assert select_scan_fn(True, mask=jnp.ones((2, 3), bool)) is gru_scan
    counts = kernel_fallbacks()
    assert counts.get("gru:backend", 0) >= 1
    assert counts.get("lstm:backend", 0) >= 1
    assert counts.get("ssm:backend", 0) >= 1
    assert counts.get("gru:masked", 0) >= 1
    # use_pallas=False is not a fallback: nothing new counted
    before = dict(kernel_fallbacks())
    select_scan_fn(False)
    select_ssm_step_fn(False)
    assert kernel_fallbacks() == before
    reset_kernel_fallbacks()
