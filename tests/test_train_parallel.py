"""High-throughput train step (ISSUE 20): sharded pjit path, microbatch
gradient accumulation, the overlapped/cached input pipeline, and the
lifetime contract behind them.

The equality pins, each against the plain meshless/synchronous seed
path on the same source and seed:

* a 1-device mesh lowers to the identical program — params bit-for-bit;
* a dp>1 mesh changes only the gradient all-reduce order — params equal
  to float tolerance;
* ``accum_steps=K`` sums the same per-element loss terms in K groups —
  equal to float re-association tolerance (exact at K=1, which IS the
  full-batch path);
* the window cache, the placed-batch cache, and the prefetch depth are
  pure plumbing — any setting is bit-identical to any other.

Plus the leak pin: a dropped Trainer must actually die (weak ledger
registration) — before PR 20 every Trainer constructed in a process
leaked its jit closure and placed device batches through the compile
ledger.
"""

import dataclasses
import gc
import weakref

import numpy as np
import pytest

import jax

from fmda_tpu.config import MeshConfig, ModelConfig, TrainConfig
from fmda_tpu.data.source import ArraySource
from fmda_tpu.parallel import build_mesh
from fmda_tpu.train.trainer import Trainer

ROWS, FEATS, CLASSES, WINDOW = 320, 6, 4, 8


@pytest.fixture
def source():
    rng = np.random.default_rng(7)
    return ArraySource(
        rng.normal(size=(ROWS, FEATS)).astype(np.float32),
        (rng.random(size=(ROWS, CLASSES)) < 0.3).astype(np.float32),
        [f"f{i}" for i in range(FEATS)])


def _model_cfg(**kw):
    base = dict(hidden_size=4, n_features=FEATS, output_size=CLASSES,
                dropout=0.0, bidirectional=False, use_pallas=False)
    base.update(kw)
    return ModelConfig(**base)


def _train_cfg(**kw):
    base = dict(batch_size=16, window=WINDOW, chunk_size=64,
                learning_rate=1e-3, epochs=2, clip=50.0,
                val_size=0.0, test_size=0.0, seed=0)
    base.update(kw)
    return TrainConfig(**base)


def _fit(source, model_cfg, train_cfg, *, mesh=None, epochs=2):
    trainer = Trainer(model_cfg, train_cfg, mesh=mesh)
    state, history, dataset = trainer.fit(source, epochs=epochs)
    return (jax.device_get(state.params),
            [m.loss for m in history["train"]],
            trainer, state, dataset)


def _tree_equal(a, b):
    return all(jax.tree.leaves(jax.tree.map(np.array_equal, a, b)))


def _tree_close(a, b, **kw):
    return all(jax.tree.leaves(jax.tree.map(
        lambda x, y: np.allclose(x, y, **kw), a, b)))


# ---------------------------------------------------------------------------
# sharded step
# ---------------------------------------------------------------------------


def test_one_device_mesh_bit_identical_to_meshless(source):
    """The pin the trainer docstring promises: a 1x1 mesh's explicit
    shardings lower to the same program as the meshless jit."""
    mc, tc = _model_cfg(), _train_cfg()
    base_params, base_losses, *_ = _fit(source, mc, tc)
    mesh = build_mesh(MeshConfig(dp=1, sp=1))
    mesh_params, mesh_losses, *_ = _fit(source, mc, tc, mesh=mesh)
    assert base_losses == mesh_losses
    assert _tree_equal(base_params, mesh_params)


def test_dp_mesh_matches_meshless_to_float_tolerance(source):
    """dp=2 splits the batch across devices; XLA's gradient all-reduce
    re-associates the same sums, nothing else changes."""
    mc, tc = _model_cfg(), _train_cfg()
    base_params, _, *_ = _fit(source, mc, tc)
    mesh = build_mesh(MeshConfig(dp=2, sp=1))
    dp_params, _, *_ = _fit(source, mc, tc, mesh=mesh)
    assert _tree_close(base_params, dp_params, rtol=1e-4, atol=1e-6)


def test_sharded_step_compiles_once(source):
    mesh = build_mesh(MeshConfig(dp=2, sp=1))
    trainer = Trainer(_model_cfg(), _train_cfg(), mesh=mesh)
    trainer.fit(source, epochs=2)
    assert trainer.compile_counts["train_step"] in (None, 1)
    assert trainer.unexpected_recompiles == 0


# ---------------------------------------------------------------------------
# gradient accumulation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("accum", [2, 4])
def test_accum_equals_full_batch_to_float_tolerance(source, accum):
    """K microbatches scanned into one update accumulate the identical
    unnormalized loss/gradient sums, normalized once — equal to the
    full-batch step up to float re-association (docs/training.md
    "Accumulation math")."""
    mc = _model_cfg()
    full_params, full_losses, *_ = _fit(source, mc, _train_cfg())
    acc_params, acc_losses, *_ = _fit(
        source, mc, _train_cfg(accum_steps=accum))
    assert np.allclose(full_losses, acc_losses, rtol=1e-5, atol=1e-6)
    assert _tree_close(full_params, acc_params, rtol=1e-4, atol=1e-6)


def test_accum_must_divide_batch_size():
    with pytest.raises(ValueError, match="accum_steps"):
        _train_cfg(accum_steps=3)  # batch_size 16


# ---------------------------------------------------------------------------
# input pipeline: caches and prefetch are pure plumbing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", [
    dict(prefetch_depth=0, cache_chunks=0),   # the seed's synchronous loop
    dict(prefetch_depth=3, cache_chunks=0),   # overlap only
    dict(prefetch_depth=2, cache_chunks=16),  # overlap + both cache tiers
])
def test_pipeline_variants_bit_identical(source, variant):
    mc = _model_cfg()
    base_params, base_losses, *_ = _fit(
        source, mc, _train_cfg(prefetch_depth=0, cache_chunks=0))
    var_params, var_losses, *_ = _fit(source, mc, _train_cfg(**variant))
    assert base_losses == var_losses
    assert _tree_equal(base_params, var_params)


def test_placed_cache_replay_is_bit_identical_and_hits(source):
    """Epochs 2+ of a cached fit replay the epoch-1 placed device
    batches; a dataset-reusing resumed fit keeps the same entries."""
    mc = _model_cfg()
    tc = _train_cfg(cache_chunks=16)
    trainer = Trainer(mc, tc)
    state, _, dataset = trainer.fit(source, epochs=1)
    assert len(trainer._placed_cache) == 1
    (entry_ds, entry_batches), = trainer._placed_cache.values()
    assert entry_ds is dataset
    # resume on the same dataset: the cache must hit (same entry object),
    # and the outcome must equal an uncached straight-through run
    state, history, _ = trainer.fit(
        source, epochs=1, initial_state=state, dataset=dataset)
    (entry_ds2, entry_batches2), = trainer._placed_cache.values()
    assert entry_batches2 is entry_batches
    plain_params, plain_losses, *_ = _fit(
        source, mc, _train_cfg(prefetch_depth=0, cache_chunks=0))
    assert [m.loss for m in history["train"]] == plain_losses[1:]
    assert _tree_equal(jax.device_get(state.params), plain_params)


def test_cache_disabled_when_split_exceeds_budget(source):
    """cache_chunks smaller than the split's chunk count: the placed
    cache must stay empty (the bound is the RAM contract)."""
    trainer = Trainer(_model_cfg(), _train_cfg(cache_chunks=1))
    trainer.fit(source, epochs=2)  # split has >1 chunks of 64 rows
    assert trainer._placed_cache == {}


# ---------------------------------------------------------------------------
# lifetime: the ledger must not retain dropped trainers
# ---------------------------------------------------------------------------


def test_dropped_trainer_is_collected(source):
    """The compile ledger registers weakly: deleting a Trainer frees its
    jit closures and placed device batches (the PR 20 leak fix — one
    process constructing many Trainers, as a sweep or the continuous
    loop does, must not accrete dead trainers' device memory)."""
    trainer = Trainer(_model_cfg(), _train_cfg(cache_chunks=16))
    trainer.fit(source, epochs=1)
    assert len(trainer._placed_cache) == 1
    ref = weakref.ref(trainer)
    del trainer
    gc.collect()
    assert ref() is None
