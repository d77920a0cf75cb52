"""The step loop's host spans and the compiled programs' named scopes
(docs/observability.md "Spans and scopes").

A tiny ``Trainer.fit`` runs under the JAX profiler on the CPU backend:
its step thread must carry ``<phase>_next_batch`` → ``<phase>`` →
``<phase>_fold`` once a call into the compiled step (a group of up to
16 steps at these sizes), in that order and never overlapping, one
``<phase>_pass_drain`` a pass, and nothing of the program's inside the
``train``/``eval`` annotation.  The scopes are read from the lowered
text of each compiled step, the single program's and the group's."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fmda_tpu.config import ModelConfig, TrainConfig
from fmda_tpu.data.pipeline import Batch
from fmda_tpu.data.source import ArraySource
from fmda_tpu.obs.registry import default_registry
from fmda_tpu.train.trainer import Trainer

PROGRAM_SPANS = (
    "train_next_batch", "train", "train_fold", "train_pass_drain",
    "eval_next_batch", "eval", "eval_fold", "eval_pass_drain",
    "fit_epoch_end", "input_compose", "input_place",
)
#: steps a pass, by the sizes below (batches of 4: 8 + 10 + 10 over the
#: train chunks, 10 + 10 over the validation chunks), and the calls that
#: carry them (groups of 16: 16 + 12, 16 + 4)
STEPS = {"train": 28, "eval": 20}
CALLS = {"train": 2, "eval": 2}


def _source(n=200, f=6, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, f)).astype(np.float32)
    y = (x[:, :4] > 0).astype(np.float32)
    return ArraySource(x, y, tuple(f"f{i}" for i in range(f)))


def _trainer(cell="gru", **train):
    mc = ModelConfig(cell=cell, hidden_size=4, n_features=6, output_size=4)
    # 6 chunks: 3 train, 2 validation, 1 test; a chunk's 32 to 40
    # windows are eight to ten batches of 4 -> 28 train steps and 20
    # eval steps a pass
    tc = TrainConfig(**{**dict(
        batch_size=4, window=5, chunk_size=40, cache_chunks=16,
        val_size=0.2, test_size=0.2), **train})
    return Trainer(mc, tc)


def _counter(name, **labels):
    return default_registry().counter(name, **labels).value


@pytest.fixture(scope="module")
def traced_epochs(tmp_path_factory):
    """Two epochs of one fit under the profiler: the first places its
    batches (cache miss), the second replays them (cache hit).  Returns
    the program's spans per host thread line, and the epochs' ends."""
    from jax.profiler import ProfileData

    trainer = _trainer()
    source = _source()
    # compile outside the capture, with another dataset: its cache
    # entries are not this fit's
    state, _, _ = trainer.fit(source, epochs=1)
    out = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        trainer.fit(source, epochs=2, initial_state=state)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{out}/plugins/profile/*/*.xplane.pb")
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in line.events if e.name in PROGRAM_SPANS)
            if spans:
                lines[(i, line.name)] = spans
    return lines


def _one_pass(traced, phase, cached):
    """The step thread's spans of one pass: the ``phase`` pass of the
    first (uncached) or second (cached) epoch."""
    assert len(traced) == 1, f"spans on several threads: {list(traced)}"
    (spans,) = traced.values()
    ends = [e for _, e, n in spans if n == "fit_epoch_end"]
    assert len(ends) == 2
    lo, hi = (ends[0], ends[1]) if cached else (0.0, ends[0])
    return [s for s in spans if lo <= s[0] < hi
            and (s[2].startswith(phase + "_") or s[2] == phase
                 or s[2].startswith("input_"))
            and _belongs(s, spans, phase)]


def _belongs(span, spans, phase):
    """input_* spans count for the pass whose next_batch holds them."""
    if not span[2].startswith("input_"):
        return True
    return any(n == phase + "_next_batch" and s <= span[0] and span[1] <= e
               for s, e, n in spans)


@pytest.mark.parametrize("cached", [False, True], ids=["uncached", "cached"])
@pytest.mark.parametrize("phase", ["train", "eval"])
def test_spans_tile_one_call_on_one_thread(traced_epochs, phase, cached):
    spans = _one_pass(traced_epochs, phase, cached)
    calls = CALLS[phase]
    top = [s for s in spans if not s[2].startswith("input_")]
    want = [phase + "_next_batch", phase, phase + "_fold"] * calls + [
        phase + "_next_batch", phase + "_pass_drain"]
    assert [n for _, _, n in top] == want
    # adjacent, never overlapping
    for (_, end, a), (start, _, b) in zip(top, top[1:]):
        assert end <= start, (a, b)
    # nothing of the program's under the step annotation
    for s, e, n in top:
        if n == phase:
            inside = [m for a, b, m in spans if s < a < e and m != n]
            assert inside == []
    # the input pipeline's own spans nest in next_batch, and only a pass
    # that places its batches has them
    nested = [s for s in spans if s[2].startswith("input_")]
    if cached:
        assert nested == []
    else:
        assert {n for _, _, n in nested} == {"input_compose", "input_place"}
        # a group is placed whole: one placement a call
        assert sum(n == "input_place" for _, _, n in nested) == calls


@pytest.mark.parametrize("phase", ["train", "eval"])
def test_a_call_counts_its_live_steps_and_itself_once(phase):
    """``train_steps_total`` advances by the live steps of each call,
    ``train_step_calls_total`` by one: a pass of n steps counts n and
    ceil(n / 16), cached or not."""
    trainer = _trainer()
    source = _source()

    def counts():
        return (_counter("train_steps_total", phase=phase),
                _counter("train_step_calls_total", phase=phase))

    before = counts()
    state, _, dataset = trainer.fit(source, epochs=1)
    first = counts()
    trainer.fit(source, epochs=1, initial_state=state, dataset=dataset)
    second = counts()
    want = (STEPS[phase], CALLS[phase])
    assert CALLS[phase] == -(-STEPS[phase] // 16)
    assert (first[0] - before[0], first[1] - before[1]) == want
    assert (second[0] - first[0], second[1] - first[1]) == want


def test_step_annotations_carry_the_index_of_their_first_step(monkeypatch):
    from fmda_tpu.utils import tracing

    seen = []
    real = tracing.step_annotation

    def spy(name, step):
        seen.append((name, step))
        return real(name, step)

    monkeypatch.setattr(tracing, "step_annotation", spy)
    _trainer().fit(_source(), epochs=1)
    assert seen == [("train", 0), ("train", 16), ("eval", 0), ("eval", 16)]


def test_placed_cache_counts_one_miss_then_hits():
    """Once a pass, not a step: over three epochs the train pass and the
    validation pass each miss once, then hit."""
    trainer = _trainer()
    before = (_counter("train_placed_cache_total", result="miss"),
              _counter("train_placed_cache_total", result="hit"))
    trainer.fit(_source(), epochs=3)
    after = (_counter("train_placed_cache_total", result="miss"),
             _counter("train_placed_cache_total", result="hit"))
    assert (after[0] - before[0], after[1] - before[1]) == (2, 4)


def test_stall_is_observed_at_the_loops_pull_on_cached_passes_too():
    trainer = _trainer()
    stall = default_registry().histogram("train_input_stall_seconds")
    source = _source()
    state, _, dataset = trainer.fit(source, epochs=1)
    before = stall.snapshot()["n"]
    trainer.fit(source, epochs=1, initial_state=state, dataset=dataset)
    # one observation a pull: every call's, and the pull that ends a pass
    assert stall.snapshot()["n"] - before == sum(CALLS.values()) + 2
    names = {h["name"] for h in default_registry().snapshot()["histograms"]}
    assert "train_input_stall_seconds" in names
    assert "train_step_seconds" not in names


def test_fit_compiles_each_step_once_and_nothing_else():
    """Spans and scopes add no program and no second lowering: a fit is
    two compiles on the ledger, whatever was traced before."""
    trainer = _trainer(cell="ssm")
    trainer.fit(_source(), epochs=2)
    assert trainer.compile_counts == {"train_step": 1, "eval_step": 1}
    assert trainer.unexpected_recompiles == 0


# -- named scopes --------------------------------------------------------------

STEP_SCOPES = {
    # under the gradient JAX wraps a scope's name in the transform
    "train_step": ("jvp(forward)", "transpose(jvp(forward))", "jvp(loss)",
                   "optimizer", "metrics"),
    "eval_step": ("forward", "loss", "metrics"),
}
FAMILY_SCOPES = {
    "gru": ("input_projection", "recurrence_fwd", "recurrence_rev", "head"),
    "lstm": ("input_projection", "recurrence_fwd", "recurrence_rev", "head"),
    "ssm": ("input_projection", "recurrence_fwd", "recurrence_rev", "head"),
    "attn": ("attention", "head"),
}


def _scope_components(lowered):
    text = lowered.as_text(debug_info=True)
    module = re.search(r"module @(\S+)", text).group(1)
    parts = set()
    for path in re.findall(r'"(jit\([^"]*)"', text):
        parts.update(path.split("/"))
    return module, parts


def _lower(trainer, step, grouped=False):
    """The single program of a step kind, or the one a group runs
    through (what ``fit`` dispatches at these sizes)."""
    state = trainer.init_state(jax.random.PRNGKey(0))
    totals = trainer.zero_totals()
    lead = (16, 1) if grouped else ()
    batch = Batch(jnp.zeros(lead + (64, 5, 6)), jnp.zeros(lead + (64, 4)),
                  jnp.ones(lead + (64,)))
    live = (3,) if grouped else ()
    if step == "train_step":
        fn = trainer._train_group if grouped else trainer._train_step
        return fn._jit.lower(
            state, totals, batch, *live, jax.random.PRNGKey(1))
    fn = trainer._eval_group if grouped else trainer._eval_step
    return fn._jit.lower(state.params, totals, batch, *live)


@pytest.mark.parametrize("grouped", [False, True], ids=["single", "group"])
@pytest.mark.parametrize("cell", sorted(FAMILY_SCOPES))
@pytest.mark.parametrize("step", sorted(STEP_SCOPES))
def test_compiled_steps_carry_the_scope_vocabulary(step, cell, grouped):
    module, parts = _scope_components(
        _lower(_trainer(cell=cell), step, grouped))
    # one name in the trace, the compile ledger and the docs
    assert module == "jit_" + step
    for scope in STEP_SCOPES[step] + FAMILY_SCOPES[cell]:
        assert scope in parts, (scope, sorted(parts))


@pytest.mark.parametrize("grouped", [False, True], ids=["single", "group"])
@pytest.mark.parametrize("step", sorted(STEP_SCOPES))
def test_pass_totals_are_added_under_the_metrics_scope(step, grouped):
    """The fold is in the compiled step: five adds (loss, accuracy,
    hamming, fbeta, confusion) directly under ``metrics``, so the
    profile's device lines charge them to that scope; in a group's
    program the same five, once, in the loop's body."""
    text = _lower(_trainer(), step, grouped).as_text(debug_info=True)
    where = "while/body/" if grouped else ""
    scoped = set(re.findall(
        r'(#loc\d+) = loc\("jit\(%s\)/%smetrics/add"' % (step, where),
        text))
    # an add of a program argument (the carried total: the loop's carry
    # in a group's program) and a value
    added = [kind for kind, loc in re.findall(
        r"stablehlo\.add %(?:arg|iterArg_)\d+, %\d+ : tensor<(\S+)> "
        r"loc\((#loc\d+)\)", text) if loc in scoped]
    assert sorted(added) == ["4x2x2xi32", "4xf32", "f32", "f32", "f32"]


@pytest.mark.parametrize("cell", ["gru", "ssm"])
def test_pool_step_carries_the_scope_vocabulary(cell):
    from fmda_tpu.models import build_model
    from fmda_tpu.runtime import SessionPool

    cfg = ModelConfig(hidden_size=5, n_features=6, output_size=4,
                      dropout=0.0, bidirectional=False, cell=cell)
    params = build_model(cfg).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 4, 6)))["params"]
    pool = SessionPool(cfg, params, capacity=4, window=4)
    lowered = pool._step._jit.lower(
        pool._params, pool._carry, pool._ring, pool._pos, pool._x_min,
        pool._x_range, np.zeros((2,), np.int32),
        np.zeros((2, 6), np.float32))
    module, parts = _scope_components(lowered)
    assert module == f"jit_session_pool_step_{cell}"
    want = {"normalize", "recurrence", "head", "state_writeback"}
    if cell == "gru":  # the ssm keeps no ring
        want.add("ring_update")
    assert want <= parts, sorted(parts)


def test_pallas_calls_are_named():
    import inspect

    from fmda_tpu.ops import (
        pallas_attention, pallas_gru, pallas_lstm, pallas_ssm)

    want = {pallas_gru: ("gru_scan_fwd", "gru_scan_bwd"),
            pallas_lstm: ("lstm_scan_fwd", "lstm_scan_bwd"),
            pallas_ssm: ("ssm_cell_step",),
            pallas_attention: ("flash_fwd", "flash_bwd")}
    for mod, names in want.items():
        src = inspect.getsource(mod)
        assert src.count("pl.pallas_call(") == len(names)
        for name in names:
            assert f'name="{name}"' in src, (mod.__name__, name)


def test_tracing_helpers_are_the_profilers_own_annotations():
    from fmda_tpu.utils import tracing

    assert isinstance(tracing.span("x"), jax.profiler.TraceAnnotation)
    assert isinstance(tracing.step_annotation("x", 3),
                      jax.profiler.StepTraceAnnotation)
    assert not hasattr(tracing, "device_scope")


def test_train_takes_jax_profile_like_serve_fleet():
    from fmda_tpu.cli import build_parser

    args = build_parser().parse_args(
        ["train", "--warehouse", "w.sqlite", "--jax-profile", "/tmp/p"])
    assert args.jax_profile == "/tmp/p"
    assert build_parser().parse_args(
        ["train", "--warehouse", "w.sqlite"]).jax_profile is None
