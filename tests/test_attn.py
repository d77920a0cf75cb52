"""TemporalTransformer (cell="attn") family contract.

No torch parity here — the reference's only model is a GRU, so this family
is net-new; what's locked instead: the shared-protocol seams (build_model
dispatch, pool-concat head, mask semantics, Trainer integration), padding
invariance, checkpoint reuse across window lengths (the reference ships
window=30 training vs window=5 serving, predict.py:71 vs notebook cell
11), and causal-mode future-blindness at the per-step level.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fmda_tpu.config import ModelConfig, TrainConfig
from fmda_tpu.models import TemporalTransformer, build_model
from fmda_tpu.models.attn import sinusoidal_positions


def _cfg(**kw):
    base = dict(hidden_size=16, n_features=6, output_size=4, n_layers=2,
                dropout=0.0, spatial_dropout=False, cell="attn", n_heads=4)
    base.update(kw)
    return ModelConfig(**base)


def _init(cfg, batch=3, seq=10, key=0):
    model = build_model(cfg)
    x = jax.random.normal(jax.random.PRNGKey(key), (batch, seq, cfg.n_features))
    params = model.init({"params": jax.random.PRNGKey(1)}, x)
    return model, params, x


def test_build_model_dispatches_attn():
    model = build_model(_cfg())
    assert isinstance(model, TemporalTransformer)


def test_bad_head_count_rejected():
    model, params, x = _init(_cfg())
    with pytest.raises(ValueError, match="n_heads"):
        bad = build_model(_cfg(n_heads=3))
        bad.init({"params": jax.random.PRNGKey(0)}, x)


def test_logits_shape_and_dtype():
    model, params, x = _init(_cfg())
    logits = model.apply(params, x)
    assert logits.shape == (3, 4)
    assert logits.dtype == jnp.float32


def test_padding_invariance_under_mask():
    """Garbage in masked-out steps must not move the logits."""
    cfg = _cfg()
    model, params, x = _init(cfg, seq=10)
    mask = jnp.concatenate(
        [jnp.ones((3, 7)), jnp.zeros((3, 3))], axis=1)
    x_a = x
    x_b = x.at[:, 7:].set(999.0)
    la = model.apply(params, x_a, mask=mask)
    lb = model.apply(params, x_b, mask=mask)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=1e-5)


def test_checkpoint_reuses_across_window_lengths():
    """Sinusoidal (parameter-free) positions: params initialised at T=30
    apply cleanly at T=5 — the reference's train/serve window mismatch."""
    cfg = _cfg()
    model, params, _ = _init(cfg, seq=30)
    x5 = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.n_features))
    logits = model.apply(params, x5)
    assert logits.shape == (2, 4)


def test_causal_per_step_future_blindness():
    """With attn_causal, the last *valid* step's hidden (via a mask that
    truncates the window) must equal running the truncated window alone —
    position t never reads t+1..T."""
    cfg = _cfg(attn_causal=True)
    model, params, x = _init(cfg, seq=8)
    # full window, mask keeps first 5 steps only
    mask = jnp.concatenate([jnp.ones((3, 5)), jnp.zeros((3, 3))], axis=1)
    l_masked = model.apply(params, x, mask=mask)
    # physically truncated window with a full mask
    l_trunc = model.apply(params, x[:, :5], mask=jnp.ones((3, 5)))
    np.testing.assert_allclose(
        np.asarray(l_masked), np.asarray(l_trunc), atol=1e-5)


def test_sinusoidal_positions_shape_and_range():
    enc = sinusoidal_positions(12, 16, jnp.float32)
    assert enc.shape == (12, 16)
    a = np.asarray(enc)
    assert np.all(a <= 1.0) and np.all(a >= -1.0)
    # distinct positions get distinct encodings
    assert len({tuple(np.round(r, 6)) for r in a}) == 12


def test_bfloat16_compute():
    cfg = _cfg(dtype="bfloat16")
    model, params, x = _init(cfg)
    logits = model.apply(params, x)
    assert logits.dtype == jnp.float32  # head always returns f32
    assert not np.any(np.isnan(np.asarray(logits)))


def test_trainer_runs_attn_cell_and_loss_drops():
    from fmda_tpu.data.pipeline import Batch
    from fmda_tpu.train.trainer import Trainer

    cfg = _cfg(dropout=0.1)
    trainer = Trainer(cfg, TrainConfig(batch_size=8, window=10))
    state = trainer.init_state(jax.random.PRNGKey(0))
    r = np.random.default_rng(0)
    x = r.normal(size=(8, 10, cfg.n_features)).astype(np.float32)
    y = (r.uniform(size=(8, 4)) > 0.5).astype(np.float32)
    b = Batch(x=jnp.asarray(x), y=jnp.asarray(y), mask=jnp.ones(8, np.float32))
    rng = jax.random.PRNGKey(1)
    losses = []
    for _ in range(30):
        state, vals = trainer.single_step(state, b, rng)
        losses.append(float(vals.loss))
    assert losses[-1] < losses[0]


def test_remat_matches_plain_forward_and_grads():
    """cfg.remat wraps each EncoderBlock in nn.remat: same function, same
    gradients, just recomputed in backward (the long-context HBM trade)."""
    cfg_plain, cfg_remat = _cfg(), _cfg(remat=True)
    model_p, params, x = _init(cfg_plain, seq=12)
    model_r = build_model(cfg_remat)

    lp = model_p.apply(params, x)
    lr = model_r.apply(params, x)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lp), atol=1e-6)

    def loss(m):
        return lambda p: jnp.sum(jnp.sin(m.apply(p, x)))

    gp = jax.grad(loss(model_p))(params)
    gr = jax.grad(loss(model_r))(params)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_backtest_serves_attn_family():
    """The serving path (window re-scan backtester) works for cell="attn"
    via build_model — the family's serving story, since per-window
    absolute positions make cross-tick K/V caching semantically invalid
    (each tick re-positions the same row within its window)."""
    from fmda_tpu.data import ArraySource
    from fmda_tpu.data.normalize import NormParams
    from fmda_tpu.serve import backtest

    r = np.random.default_rng(0)
    n, f, window = 60, 6, 8
    x = r.normal(size=(n, f)).astype(np.float32)
    y = (x[:, :4] > 0).astype(np.float32)
    src = ArraySource(x, y, tuple(f"f{i}" for i in range(f)))
    cfg = _cfg(n_layers=1)
    params = build_model(cfg).init(
        {"params": jax.random.PRNGKey(1)},
        jnp.zeros((1, window, f)))["params"]
    norm = NormParams(np.zeros(f, np.float32), np.ones(f, np.float32))
    result = backtest(src, cfg, params, norm, window=window, batch_size=16)
    assert result.probabilities.shape == (n - window + 1, 4)
    assert not np.any(np.isnan(result.probabilities))
