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

Since PR 29 one call carries a *group* of up to 16 consecutive steps
(``trainer.group_size``; every trainer of this file is small enough):
``_place_batches(host batches, state)`` stacks them, the group's
program runs the same step body ``n_live`` times, and a pass must still
read bit for bit the fold over ``single_step`` — its padded tail's dead
steps included, Adam's moments and the step counter among what is
compared — uncached, replayed from the placed cache, and resumed from a
checkpoint taken between passes.
"""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fmda_tpu.config import ModelConfig, TrainConfig
from fmda_tpu.data.pipeline import Batch, BatchGroup, ChunkDataset
from fmda_tpu.data.source import ArraySource
from fmda_tpu.train import trainer as trainer_module
from fmda_tpu.train.trainer import StepTotals, Trainer, group_size

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


def _host_batches(trainer, chunks):
    tc = trainer.train_cfg
    dataset = ChunkDataset(
        _source(trainer.model_cfg.output_size), tc.chunk_size, tc.window)
    return [b for c in chunks for b in trainer.task.batches(dataset, c)]


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


def _assert_same_pass(got, want):
    (state, epoch, confusion), (ref_state, ref_epoch, ref_confusion) = (
        got, want)
    assert epoch.loss == ref_epoch[0] and np.isfinite(epoch.loss)
    assert (epoch.accuracy, epoch.hamming) == ref_epoch[1:3]
    assert np.array_equal(epoch.fbeta, ref_epoch[3])
    assert np.array_equal(confusion, ref_confusion)
    # parameters, Adam's count and both moments, the step counter
    same = jax.tree.map(np.array_equal, jax.device_get(state),
                        jax.device_get(ref_state))
    assert all(jax.tree.leaves(same))


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

    _assert_same_pass((state, epoch, confusion),
                      (ref_state, ref_epoch, ref_confusion))
    assert confusion.dtype == np.int64
    # every valid window of the pass is counted once, in every class
    valid = sum(float(b.mask.sum()) for b in batches)
    assert (confusion.sum(axis=(1, 2)) == valid).all()
    if phase == "train":
        assert int(state.step) == len(batches)


@pytest.mark.parametrize("chunks", [(0, 1), (0, 1, 2, 3), (0, 1, 2, 3, 4)],
                         ids=["8of16", "16", "16+4of16"])
@pytest.mark.parametrize("accum", [1, 2])
@pytest.mark.parametrize("phase", ["train", "eval"])
@pytest.mark.parametrize("cell", ["gru", "ssm"])
def test_grouped_pass_is_bit_identical_to_the_fold_over_single_step(
        cell, phase, accum, chunks):
    """A pass shorter than a group, one that fills it, and one that ends
    in a padded group: the dead steps behind ``n_live`` change nothing."""
    trainer = _trainer(cell, accum_steps=accum)
    host = _host_batches(trainer, chunks)
    state0 = trainer.init_state(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1) if phase == "train" else None
    groups = list(trainer._place_batches(host, state0))
    assert all(isinstance(g, BatchGroup) for g in groups)
    assert all(g.batches.x.shape[:3] == (16, 1, 16) for g in groups)
    live = [g.n_live for g in groups]
    assert sum(live) == len(host) and live[:-1] == [16] * (len(live) - 1)
    # the padding is zeros, masked out like a batch's own padded lanes
    last = groups[-1]
    assert not np.asarray(last.batches.mask[last.n_live:]).any()

    got = trainer._run_batches(
        _copy(state0), (groups,), rng, train=phase == "train")
    want = _eager_fold(
        trainer, _copy(state0), list(trainer._place_batches(host)), rng)
    _assert_same_pass(got, want)
    if phase == "train":
        assert int(got[0].step) == len(host)
    # one program for the groups (the tail's included), one for the
    # reference's single steps
    assert trainer.compile_counts[phase + "_step"] == 2
    group = (trainer._train_group if phase == "train"
             else trainer._eval_group)
    assert group.cache_size() == 1


@pytest.mark.parametrize("cache_chunks", [0, 16], ids=["uncached", "cached"])
@pytest.mark.parametrize("cell", ["gru", "ssm"])
def test_fit_epochs_equal_the_fold_over_single_step(cell, cache_chunks):
    """Three epochs of ``fit``: the first places its groups, the later
    ones replay them from the placed cache (or place them again), and
    each reads what single steps over the same batches read."""
    trainer = _trainer(cell, cache_chunks=cache_chunks, batch_size=4)
    rng = jax.random.PRNGKey(3)
    state, history, dataset = trainer.fit(_source(), rng=rng, epochs=3)
    assert len(trainer._placed_cache) == (2 if cache_chunks else 0)

    ref = _trainer(cell, batch_size=4)
    init_rng, step_rng = jax.random.split(rng)
    train_chunks, val_chunks, _ = dataset.split(0.2, 0.2)
    batches = [b for c in train_chunks for b in ref._chunk_batches(dataset, c)]
    val = [b for c in val_chunks for b in ref._chunk_batches(dataset, c)]
    assert len(batches) % 16 and len(batches) > 16
    ref_state = ref.init_state(init_rng)
    for epoch in range(3):
        ref_state, ref_epoch, _ = _eager_fold(
            ref, ref_state, batches, step_rng)
        assert history["train"][epoch].loss == ref_epoch[0]
        _, val_epoch, _ = _eager_fold(ref, ref_state, val, None)
        assert history["val"][epoch].loss == val_epoch[0]
    same = jax.tree.map(np.array_equal, jax.device_get(state),
                        jax.device_get(ref_state))
    assert all(jax.tree.leaves(same))
    assert trainer.compile_counts == {"train_step": 1, "eval_step": 1}


@pytest.mark.parametrize("cell", ["gru", "ssm"])
def test_grouped_fit_resumes_exactly_between_passes(cell, tmp_path):
    """A checkpoint taken after an epoch whose last group was padded:
    the step counter (the dropout stream folds on it) and Adam's count
    land where single steps put them, so 1 + 2 resumed epochs are 3."""
    from fmda_tpu.train.checkpoint import save_checkpoint

    straight, history, _ = _trainer(cell, batch_size=4).fit(
        _source(), epochs=3)
    first = _trainer(cell, batch_size=4)
    state, _, dataset = first.fit(_source(), epochs=1)
    assert int(state.step) % 16 and int(state.step) > 16
    ckpt = save_checkpoint(
        str(tmp_path), state, first.task.norm_params(dataset))
    resumed = _trainer(cell, batch_size=4)
    restored = resumed.restore_state(ckpt)
    assert int(restored.step) == int(state.step)
    state_r, history_r, _ = resumed.fit(
        _source(), epochs=2, initial_state=restored)
    assert [m.loss for m in history_r["train"]] == [
        m.loss for m in history["train"][1:]]
    same = jax.tree.map(np.array_equal, jax.device_get(state_r),
                        jax.device_get(straight))
    assert all(jax.tree.leaves(same))


@pytest.mark.parametrize("batch_size", [16, 4], ids=["12of16", "16+16+12"])
@pytest.mark.parametrize("mesh", [False, True], ids=["meshless", "mesh1"])
def test_two_epochs_of_fit_compile_each_step_once(mesh, batch_size):
    """The zeros a pass starts from hit the executable the carried
    totals hit (same dtypes, strong types, sharding and placement), a
    pass's padded last group hits the one its full groups hit, and the
    cached replay the one the first pass compiled: one program a phase,
    and none after the first epoch."""
    mesh = (jax.sharding.Mesh(np.array(jax.devices()[:1]), ("dp",))
            if mesh else None)
    trainer = _trainer(mesh=mesh, batch_size=batch_size, cache_chunks=16)
    trainer.fit(_source(), epochs=1)
    trainer.mark_warm()
    _, history, _ = trainer.fit(_source(), epochs=2)
    assert len(history["train"]) == len(history["val"]) == 2
    assert np.isfinite(history["val"][-1].loss)
    assert trainer.compile_counts == {"train_step": 1, "eval_step": 1}
    assert trainer.unexpected_recompiles == 0
    # the loop ran the groups' programs and never the single ones
    assert trainer._train_step.cache_size() == 0
    assert trainer._eval_step.cache_size() == 0


def test_a_dp_mesh_places_groups_split_along_their_batch_axis():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",))
    trainer = _trainer(mesh=mesh)
    state = trainer.init_state(jax.random.PRNGKey(0))
    (group,) = trainer._place_batches(_host_batches(trainer, (0,)), state)
    spec = jax.sharding.PartitionSpec(None, None, "dp")
    for leaf in group.batches:
        assert leaf.sharding.spec == spec and leaf.shape[:3] == (16, 1, 16)
    trainer._run_batches(state, ([group],), jax.random.PRNGKey(1), True)
    assert trainer._train_group.cache_size() == 1


# -- the rule for the group's size ---------------------------------------------

MB = 1 << 20
#: a width-32 cell's batch: 256 windows of 30 x 108 float32, their four
#: labels and the mask
WIDTH32_BATCH = 256 * (30 * 108 + 4 + 1) * 4


@pytest.mark.parametrize("state_bytes,batch_bytes,want", [
    # the benchmark's width-32 cells: ~0.6 MB of parameters and Adam
    # moments, a 3.4 MB batch
    (600_000, WIDTH32_BATCH, 16),
    (0, WIDTH32_BATCH, 16),
    # the decoder cell: 656.5 M parameters x 12 B and two counters, one
    # 8,192-token batch
    (7_878_359_048, 8192 * 12, 1),
    # fit_multi's mixed batches: 800 windows a step
    (600_000, 800 * (30 * 108 + 4 + 1) * 4, 6),
    # a group may not outgrow its cap; a batch over the cap runs alone
    (600_000, 5 * MB, 12),
    (600_000, 40 * MB, 1),
    (600_000, 100 * MB, 1),
    # the threshold: state and one batch, each moved once
    (256 * MB - 1024, 1024, 1),
    (256 * MB - 1025, 1024, 16),
    (200 * MB, 60 * MB, 1),
])
def test_group_size_is_a_pure_function_of_byte_counts(
        state_bytes, batch_bytes, want):
    assert group_size(state_bytes, batch_bytes) == want


def test_group_size_reads_the_state_and_the_first_batch(monkeypatch):
    """What the trainer feeds the rule: the bytes of the state it is
    handed and of the pass's first host batch; no setting, no family."""
    seen = []

    def spy(state_bytes, batch_bytes):
        seen.append((state_bytes, batch_bytes))
        return group_size(state_bytes, batch_bytes)

    monkeypatch.setattr(trainer_module, "group_size", spy)
    trainer = _trainer()
    state = trainer.init_state(jax.random.PRNGKey(0))
    host = _host_batches(trainer, (0,))
    list(trainer._place_batches(host, state))
    want_state = sum(a.nbytes for a in jax.tree.leaves(state))
    want_batch = sum(a.nbytes for a in host[0])
    assert seen == [(want_state, want_batch)]
    assert not any("group" in f.name
                   for f in dataclasses.fields(trainer.train_cfg))


def test_a_step_too_large_to_be_host_bound_runs_alone(monkeypatch):
    """Past ``SOLO_STEP_BYTES`` the loop is the parent's: batches placed
    one by one, the single programs, a call a step."""
    monkeypatch.setattr(trainer_module, "SOLO_STEP_BYTES", 1)
    trainer = _trainer()
    state = trainer.init_state(jax.random.PRNGKey(0))
    placed = list(trainer._place_batches(_host_batches(trainer, (0,)), state))
    assert len(placed) == 4 and all(isinstance(b, Batch) for b in placed)
    trainer.fit(_source(), epochs=2)
    assert trainer.compile_counts == {"train_step": 1, "eval_step": 1}
    assert trainer._train_group.cache_size() == 0
    assert trainer._eval_group.cache_size() == 0


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
