"""A pass's loss and metric totals ride through the compiled steps
(docs/training.md "The step and its totals").

``Trainer._run_batches`` hands each step the pass's running
:class:`StepTotals` and gets them back with that step's values added,
where it used to fold them with five eager adds after the call.  Only
the place of the sum moved, so a pass must read bit for bit what the
eager fold over the single-step helper reads; one program a step kind
must serve a pass's first step (zeros placed by the host) and every
later one (totals the step returned); and nothing but the step may be
dispatched.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fmda_tpu.config import ModelConfig, TrainConfig
from fmda_tpu.data.pipeline import ChunkDataset
from fmda_tpu.data.source import ArraySource
from fmda_tpu.train.trainer import StepTotals, Trainer

ROWS, FEATS, WINDOW = 320, 6, 8


def _source(classes=4):
    rng = np.random.default_rng(7)
    return ArraySource(
        rng.normal(size=(ROWS, FEATS)).astype(np.float32),
        (rng.random(size=(ROWS, classes)) < 0.3).astype(np.float32),
        [f"f{i}" for i in range(FEATS)])


def _trainer(cell="gru", classes=4, mesh=None, **train):
    mc = ModelConfig(cell=cell, hidden_size=4, n_features=FEATS,
                     output_size=classes, dropout=0.1)
    # 5 chunks of 64 rows: 57 windows each, so four batches of 16 a
    # chunk, the last one padded and masked
    tc = TrainConfig(**{**dict(
        batch_size=16, window=WINDOW, chunk_size=64, val_size=0.2,
        test_size=0.2, seed=0), **train})
    return Trainer(mc, tc, mesh=mesh)


def _placed_batches(trainer, chunks=(0, 1)):
    tc = trainer.train_cfg
    dataset = ChunkDataset(
        _source(trainer.model_cfg.output_size), tc.chunk_size, tc.window)
    return [b for c in chunks for b in trainer._chunk_batches(dataset, c)]


def _copy(state):
    return jax.tree.map(jnp.copy, state)  # the train step donates


def _eager_fold(trainer, state, batches, rng):
    """The plain reference: each step's own values from the single-step
    helper, folded by eager adds in pass order, drained as the loop
    drains."""
    acc = None
    for batch in batches:
        state, vals = trainer.single_step(state, batch, rng)
        acc = vals if acc is None else jax.tree.map(jnp.add, acc, vals)
    n = len(batches)
    loss, accuracy, hamming, fbeta, confusion = jax.device_get(acc)
    return state, (float(loss) / n, float(accuracy) / n, float(hamming) / n,
                   np.asarray(fbeta) / n), np.asarray(confusion, np.int64)


@pytest.mark.parametrize("accum", [1, 2])
@pytest.mark.parametrize("phase", ["train", "eval"])
@pytest.mark.parametrize("cell", ["gru", "ssm"])
def test_pass_is_bit_identical_to_the_eager_fold(cell, phase, accum):
    trainer = _trainer(cell, accum_steps=accum)
    batches = _placed_batches(trainer)
    assert len(batches) == 8 and float(batches[3].mask.sum()) < 16
    state0 = trainer.init_state(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1) if phase == "train" else None

    state, epoch, confusion = trainer._run_batches(
        _copy(state0), (batches,), rng, train=phase == "train")
    ref_state, ref_epoch, ref_confusion = _eager_fold(
        trainer, _copy(state0), batches, rng)

    assert epoch.loss == ref_epoch[0] and np.isfinite(epoch.loss)
    assert epoch.accuracy == ref_epoch[1]
    assert epoch.hamming == ref_epoch[2]
    assert np.array_equal(epoch.fbeta, ref_epoch[3])
    assert confusion.dtype == np.int64
    assert np.array_equal(confusion, ref_confusion)
    # every valid window of the pass is counted once, in every class
    valid = sum(float(b.mask.sum()) for b in batches)
    assert (confusion.sum(axis=(1, 2)) == valid).all()
    same = jax.tree.map(np.array_equal, jax.device_get(state),
                        jax.device_get(ref_state))
    assert all(jax.tree.leaves(same))
    if phase == "train":
        assert int(state.step) == len(batches)


@pytest.mark.parametrize("mesh", [False, True], ids=["meshless", "mesh1"])
def test_two_epochs_of_fit_compile_each_step_once(mesh):
    """The zeros a pass starts from hit the executable the carried
    totals hit: same dtypes, strong types, sharding and placement."""
    mesh = (jax.sharding.Mesh(np.array(jax.devices()[:1]), ("dp",))
            if mesh else None)
    trainer = _trainer(mesh=mesh)
    trainer.fit(_source(), epochs=1)
    trainer.mark_warm()
    _, history, _ = trainer.fit(_source(), epochs=2)
    assert len(history["train"]) == len(history["val"]) == 2
    assert np.isfinite(history["val"][-1].loss)
    assert trainer.compile_counts == {"train_step": 1, "eval_step": 1}
    assert trainer.unexpected_recompiles == 0


class _BackendCompiles:
    """XLA compiles made while it is open (jax.monitoring)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n, self.open = 0, True
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if self.open and event == self.EVENT:
            self.n += 1


@pytest.mark.parametrize("phase", ["train", "eval"])
def test_a_pass_dispatches_nothing_but_the_step(phase):
    """Counted in compile events, not time: a fresh trainer's first pass
    compiles its step and nothing else.  Five classes give the totals
    shapes (f32[5], i32[5,2,2]) no other test in the suite adds, so an
    eager fold would have to compile its ``add`` programs here."""
    trainer = _trainer(classes=5)
    batches = _placed_batches(trainer, chunks=(0,))
    state = trainer.init_state(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1) if phase == "train" else None
    watch = _BackendCompiles()
    try:
        trainer._run_batches(state, (batches,), rng, train=phase == "train")
    finally:
        watch.open = False
    assert watch.n == 1
    assert trainer.compile_counts[phase + "_step"] == 1


@pytest.mark.parametrize("phase", ["train", "eval"])
def test_empty_pass_warns_and_reads_nan(phase, caplog):
    trainer = _trainer()
    state = trainer.init_state(jax.random.PRNGKey(0))
    with caplog.at_level(logging.WARNING, logger="fmda_tpu.train"):
        out, epoch, confusion = trainer._run_batches(
            state, ([],), jax.random.PRNGKey(1), train=phase == "train")
    assert "pass produced no batches" in caplog.text
    assert out is state
    assert all(np.isnan(v) for v in epoch[:3])
    assert np.array_equal(epoch.fbeta, np.zeros(4))
    assert confusion.dtype == np.int64
    assert np.array_equal(confusion, np.zeros((4, 2, 2)))


def test_zero_totals_match_what_the_steps_return():
    """Leaf for leaf: shape, dtype, strong type and sharding."""
    trainer = _trainer()
    zero = trainer.zero_totals()
    _, vals = trainer.single_step(
        trainer.init_state(jax.random.PRNGKey(0)),
        _placed_batches(trainer, chunks=(0,))[0], jax.random.PRNGKey(1))
    assert isinstance(vals, StepTotals)
    for z, v in zip(zero, vals):
        assert (z.shape, z.dtype, z.weak_type) == (
            v.shape, v.dtype, v.weak_type)
        assert z.sharding == v.sharding and z.committed == v.committed
        assert not np.asarray(z).any()
