"""The token ``decoder`` family against its plain reference
(benchmark/reference/moe_decoder.py) at a small size on the CPU: logits,
loss and gradients on seeded random weights; window layers differ from
full layers past the window and agree inside it; rotary positions on
layout 1 only.  The published widths are compared on the chip
(benchmark/drivers/train_token_epochs.py)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import moe_decoder as ref  # noqa: E402
from fmda_tpu.config import ModelConfig, TrainConfig  # noqa: E402
from fmda_tpu.data.pipeline import Batch  # noqa: E402
from fmda_tpu.models import build_model  # noqa: E402
from fmda_tpu.models.decoder import check_decoder_config, rotary  # noqa: E402
from fmda_tpu.train.tasks import NextToken  # noqa: E402

SEQ, VOCAB, WINDOW = 64, 256, 16


def small_cfg(**over):
    return ModelConfig(**{**dict(
        cell="decoder", hidden_size=64, n_heads=4, n_kv_heads=2,
        head_dim=16, vocab_size=VOCAB, layer_layout=(0, 1, 1, 1),
        sliding_window=WINDOW, rope_theta=1.5e6, moe_experts=8,
        moe_top_k=2, moe_ffn_size=32, experts_held=(0, 8), loss_chunk=16,
        dtype="float32"), **over})


def _params(cfg, seed=0):
    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(seed)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # the family's init is N(0, 0.02): too flat for routing and attention
    # to matter at hidden 64, so the comparison is made on wider weights
    keys = jax.random.split(jax.random.PRNGKey(seed + 1),
                            len(jax.tree.leaves(params)))
    leaves, tree = jax.tree.flatten(params)
    wide = [l if l.ndim == 1 else 0.2 * jax.random.normal(k, l.shape)
            for l, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(tree, wide)


def _ids(seed=3, n=SEQ + 1, batch=2):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, size=(batch, n)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


@pytest.mark.parametrize("held", [(0, 8), (2, 4)])
def test_logits_match_the_reference(held):
    cfg = small_cfg(experts_held=held)
    model, params = _params(cfg)
    x, _ = _ids()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: model.apply({"params": p}, x))(params, x)
    reference = jax.jit(lambda p, ids: ref.logits(p, ids, cfg))
    for b in range(x.shape[0]):
        np.testing.assert_allclose(got[b], reference(params, x[b]),
                                   rtol=2e-4, atol=2e-4)


def _program_loss_and_grads(cfg, params, batch):
    model = build_model(cfg)
    task = NextToken(cfg, TrainConfig(batch_size=2, window=SEQ))

    def program_loss(p):
        return task.loss(p, task.forward(model, p, batch, None), batch)[0]

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(program_loss))(params)


@pytest.mark.parametrize("remat", [False, True, "replay"])
def test_loss_and_gradients_match_the_reference(remat):
    """``"replay"``: the reference here is the program itself without
    recomputation.  A block's replay keeps what it names and remakes the
    rest from the block's input: loss and every gradient leaf are the
    same bits with ``remat`` on and off."""
    layout = (0, 1) if remat == "replay" else small_cfg().layer_layout
    kw = dict(experts_held=(4, 4), layer_layout=layout)
    cfg = small_cfg(remat=bool(remat), **kw)
    _, params = _params(cfg)
    x, y = _ids()
    mask = jnp.ones(x.shape, jnp.float32).at[1, 40:].set(0.0)
    batch = Batch(x, y, mask)
    got, got_grads = _program_loss_and_grads(cfg, params, batch)
    if remat == "replay":
        want, want_grads = _program_loss_and_grads(
            small_cfg(remat=False, **kw), params, batch)
        np.testing.assert_array_equal(got, want)
        for (path, g), w in zip(
                jax.tree_util.tree_leaves_with_path(got_grads),
                jax.tree.leaves(want_grads)):
            np.testing.assert_array_equal(g, w, err_msg=str(path))
        return
    want, want_grads = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg, remat=remat))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
    flat_want = jax.tree.leaves(want_grads)
    for (path, g), w in zip(flat_got, flat_want):
        scale = float(jnp.abs(w).max()) + 1e-12
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-7, path


def test_expert_pair_counts_match_the_reference_routing():
    cfg = small_cfg(experts_held=(2, 4))
    model, params = _params(cfg)
    x, _ = _ids(batch=1)
    with jax.default_matmul_precision("highest"):
        _, stats = jax.jit(lambda p, x: model.apply(
            {"params": p}, x, method="features"))(params, x)
    _, want = jax.jit(lambda p, ids: ref.hidden_states(p, ids, cfg))(
        params, x[0])
    np.testing.assert_array_equal(stats["expert_pairs"], want)
    assert int(stats["dropped"]) == 0


def test_window_layers_agree_inside_the_window_and_differ_past_it():
    """A window layer and a full layer of the same weights give the same
    rows while the whole past fits the window, and different ones after;
    the layout is the only difference (rotary off for both via theta
    that leaves positions where they are is not possible, so the full
    layer is given rotary too by comparing layouts (1,) with window
    >= T against (1,) with the small window)."""
    wide = small_cfg(layer_layout=(1,), sliding_window=SEQ)
    narrow = small_cfg(layer_layout=(1,), sliding_window=WINDOW)
    model_w, params = _params(wide)
    model_n = build_model(narrow)
    x, _ = _ids(batch=1)
    with jax.default_matmul_precision("highest"):
        a = model_w.apply({"params": params}, x)[0]
        b = model_n.apply({"params": params}, x)[0]
    np.testing.assert_allclose(a[:WINDOW], b[:WINDOW], rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(a[WINDOW:] - b[WINDOW:]).max()) > 1e-3


def test_rotary_on_layout_one_only():
    """Layout 0 has no positional encoding: with its causal mask the
    last row's logits do not change when the earlier tokens are
    permuted; layout 1 rotates q and k, so they do."""
    x, _ = _ids(batch=1)
    perm = jnp.concatenate([x[:, :SEQ - 1][:, ::-1], x[:, SEQ - 1:]], axis=1)
    for layout, moves in ((0, False), (1, True)):
        cfg = small_cfg(layer_layout=(layout,), sliding_window=SEQ)
        model, params = _params(cfg)
        with jax.default_matmul_precision("highest"):
            a = model.apply({"params": params}, x)[0, -1]
            b = model.apply({"params": params}, perm)[0, -1]
        gap = float(jnp.abs(a - b).max())
        assert (gap > 1e-3) == moves, (layout, gap)


def test_rotary_keeps_norms_and_is_identity_at_position_zero():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, 16))
    r = rotary(q, 1.5e6)
    np.testing.assert_allclose(r[:, :, 0], q[:, :, 0], rtol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(r, axis=-1),
                               jnp.linalg.norm(q, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(r[0], ref._rotary(q[0], 1.5e6), rtol=1e-6)


@pytest.mark.parametrize("field,value", [
    ("vocab_size", 0), ("n_kv_heads", 3), ("layer_layout", ()),
    ("experts_held", (6, 4)), ("moe_top_k", 9), ("head_dim", 0)])
def test_an_unset_size_is_refused_by_name(field, value):
    with pytest.raises(ValueError, match=field.split("_")[0]):
        check_decoder_config(small_cfg(**{field: value}))


def test_the_chunked_loss_is_the_whole_loss():
    from fmda_tpu.train.losses import chunked_next_token_loss

    k = jax.random.split(jax.random.PRNGKey(5), 3)
    hidden = jax.random.normal(k[0], (96, 16))
    head = jax.random.normal(k[1], (16, 50))
    y = jax.random.randint(k[2], (96,), 0, 50)
    mask = jnp.ones((96,)).at[90:].set(0.0)

    def whole(hidden, head):
        lg = hidden @ head
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, y[:, None], -1)[:, 0]
        return jnp.sum(nll * mask)

    for chunk in (96, 32, 40):  # 40 does not divide: lowered to 32
        f = lambda h, w: chunked_next_token_loss(h, w, y, mask,
                                                 chunk=chunk)[0]
        with jax.default_matmul_precision("highest"):
            got = jax.value_and_grad(f, (0, 1))(hidden, head)
            want = jax.value_and_grad(whole, (0, 1))(hidden, head)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)
    _, tokens, correct = chunked_next_token_loss(hidden, head, y, mask,
                                                 chunk=32)
    assert int(tokens) == 90
    assert int(correct) == int(jnp.sum(
        (jnp.argmax(hidden @ head, -1) == y) & (mask > 0)))


# -- the guard of a refactor: six kinds' programs, pinned --------------------

_EXPERTS = dict(moe_experts=4, moe_top_k=2, moe_ffn_size=16,
                experts_held=(1, 2))
_LATENT = dict(
    _EXPERTS, layer_layout=(4, 4, 4), rms_norm_eps=1e-5, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
    rope_theta=10000.0, rope_factor=64.0, rope_original_max=64,
    hidden_act="silu", ffn_size=48, first_dense_layers=1,
    moe_shared_experts=1, moe_scoring="sigmoid", moe_routed_scaling=2.0)
#: Tiny copies of the five accepted decoder configurations (bfloat16,
#: recomputed blocks, as their files state: the fifth, ``latent_direct``,
#: is latent attention with a direct query, two shared experts, a
#: selection bias and the router's per-sequence balance term) and a
#: latent one with a plain residual: what each states beyond the
#: two-layer base below.
PINNED_KINDS = {
    "routed": _EXPERTS,
    "learned_sparse": dict(
        _EXPERTS, layer_layout=(2, 2), hidden_act="silu", indexer_heads=2,
        indexer_head_dim=8, indexer_topk=8, rope_theta=1e7),
    "hybrid": dict(
        layer_layout=(3, 0, 3), rms_norm_eps=1e-5, ffn_size=48,
        hidden_act="silu", ssm_heads=4, ssm_head_dim=16, ssm_state=8,
        ssm_conv=4, ssm_chunk=16, tie_embeddings=True,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.25, logits_scaling=8.0),
    "latent": dict(_LATENT, moe_bias_rate=1e-3, hc_streams=4),
    "latent_plain": _LATENT,
    "latent_direct": dict(
        _LATENT, q_lora_rank=0, rope_theta=50000.0, rope_factor=1.0,
        rope_original_max=0, moe_shared_experts=2, moe_routed_scaling=2.446,
        moe_bias_rate=1e-3, moe_seq_aux_alpha=1e-3),
}
#: sha256 (first 16 hex digits), jax 0.9.0, taken on PR 45's parent
#: (b804e58) by this very code, of: the lowered text of the single train
#: and eval programs; the sorted set of their operations' scope paths
#: (the ``"jit(...)/..."`` names of ``as_text(debug_info=True)``, no file
#: or line: every ``jax.named_scope`` and module name with its nesting,
#: less the ``block_1._method`` components flax writes for a module's
#: method, which name no scope the code opens and no reader asks for);
#: the parameter tree at ``PRNGKey(0)`` (paths, shapes, dtypes, bytes).
#: All six ``params`` and ``eval_scopes`` are still those; the five
#: kinds with an expert layer have PR 48's own text and scopes (the
#: layer's rounds in a ``while`` under one ``custom_vjp``, each direction
#: a ``jax.jit`` of its own: its scopes stand under ``jit(...)/while/
#: body``, the backward's under ``jvp(moe_*)`` and ``transpose(jvp(
#: moe_*))`` there; the output named for the replay; a fourth count,
#: ``layout_rounds``).  The four kinds that call ``_dense_mlp``
#: (``hybrid`` and the three latent ones) have PR 55's train text and
#: scopes: the replay keeps the MLP's two pre-activation products, so
#: six ``dot_general`` fewer a program, ``.../rematted_computation/
#: block_*/{dense_mlp,moe_shared}/dot_general`` gone and the kept
#: values' ``reduce_precision`` under ``jvp(forward)`` in their place;
#: their eval text is the parent's operation for operation under other
#: numbers for jax's private functions (``@silu_119`` for ``@silu_118``:
#: a name is an equation to the counter and nothing to the program).
#: A pin that moves means the change altered the program:
#: regenerate (``_program_pins(kind)``) only after a deliberate change to
#: these layers, their task or the step function.
PINNED = {
    "routed": dict(
        params="733593a24ee9f9e9",
        train_text="bb42a182719ea2b2", train_scopes="8540add247c1a98a",
        eval_text="9d04e135fe4b39c9", eval_scopes="79063c661c74d701"),
    "learned_sparse": dict(
        params="c338591f00479041",
        train_text="441f68d9f4812eb1", train_scopes="43f38c17e1f961c3",
        eval_text="e4a838a7f34577e5", eval_scopes="81ebb71f65af29da"),
    "hybrid": dict(
        params="7170fa193506754b",
        train_text="2a5132dd5304c6de", train_scopes="002b8d80e8eb4415",
        eval_text="31bbb51a12a8988a", eval_scopes="65a0fce208c4d6ab"),
    "latent": dict(
        params="43dbdb7823a39232",
        train_text="5963b4d0494d2ee7", train_scopes="54f49539ea312fca",
        eval_text="de2b65e31064ab7b", eval_scopes="73af4a412538784b"),
    "latent_plain": dict(
        params="881b57f6db59149d",
        train_text="c9d5b53ef001be63", train_scopes="6a183920ecf89faa",
        eval_text="b04d9606cf175032", eval_scopes="c55711daca10cd61"),
    "latent_direct": dict(
        params="5f93cbac51dae9f8",
        train_text="d2e78248ad81c5dc", train_scopes="e7f819bb57cb1025",
        eval_text="509f3361f7080458", eval_scopes="c14b381fe91fc917"),
}


def _tiny(kind, **over):
    return ModelConfig(**{**dict(
        cell="decoder", hidden_size=32, n_heads=4, n_kv_heads=2, head_dim=8,
        vocab_size=64, layer_layout=(0, 1), sliding_window=8,
        loss_chunk=16, dtype="bfloat16", remat=True),
        **PINNED_KINDS[kind], **over})


def _sha(text: str) -> str:
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _program_pins(kind):
    import re

    from fmda_tpu.data.source import TokenArraySource
    from fmda_tpu.train.trainer import Trainer

    seq, vocab = 32, 64
    tc = TrainConfig(batch_size=2, window=seq, chunk_size=2 * seq,
                     learning_rate=1e-2, clip=1.0, val_size=0.1,
                     test_size=0.1, cache_chunks=16, seed=0)
    rng = np.random.default_rng(0)
    ids = np.minimum(rng.zipf(1.3, size=21 * seq + 1) - 1, vocab - 1)
    trainer = Trainer(_tiny(kind), tc)
    dataset = trainer.task.dataset(TokenArraySource(ids, vocab))
    state = trainer.init_state(jax.random.PRNGKey(0))
    batch = next(iter(trainer._chunk_batches(dataset, 0)))
    totals = trainer.zero_totals()
    pins = {"params": _sha("\n".join(
        "%s %s %s %s" % (jax.tree_util.keystr(path), leaf.shape, leaf.dtype,
                         _sha(np.asarray(leaf).tobytes().hex()))
        for path, leaf in jax.tree_util.tree_leaves_with_path(state.params)))}
    for step, lowered in (
            ("train", trainer._train_step._jit.lower(
                state, totals, batch, jax.random.PRNGKey(1))),
            ("eval", trainer._eval_step._jit.lower(
                state.params, totals, batch))):
        pins[step + "_text"] = _sha(lowered.as_text())
        pins[step + "_scopes"] = _sha("\n".join(sorted({
            re.sub(r"/block_\d+\.\w+", "", path) for path in re.findall(
                r'"(jit\([^"]*)"', lowered.as_text(debug_info=True))})))
    return pins


@pytest.mark.parametrize("kind", sorted(PINNED_KINDS))
def test_the_accepted_configurations_steps_are_the_parents(
        monkeypatch, kind):
    from fmda_tpu.train import trainer as trainer_module

    monkeypatch.setattr(trainer_module, "SOLO_STEP_BYTES", 1)
    assert _program_pins(kind) == PINNED[kind]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_replay_keeps_the_mlps_two_products_and_moves_no_bit(
        monkeypatch, capsys, dtype):
    """A recomputed dense block and a recomputed expert block with a
    shared expert: with the gated MLP's two pre-activation products under
    ``REPLAY_KEEPS`` the forward hands backward two arrays a
    ``_dense_mlp`` call, tokens x width in the compute dtype, and none
    with the two names taken out; loss and every gradient leaf are the
    same either way (the kept values are the ones the replay would have
    made: bit for bit in float32; XLA on a CPU may fuse two bfloat16
    programs' roundings differently, so a bfloat16 leaf is held to 2 % of
    its largest entry)."""
    from fmda_tpu.models import decoder

    cfg = _tiny("latent_plain", layer_layout=(4, 4), dtype=dtype)
    params = build_model(cfg).init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, 8), jnp.int32))["params"]
    x, y = _ids(n=33)
    x, y = x % cfg.vocab_size, y % cfg.vocab_size
    batch = Batch(x, y, jnp.ones(x.shape, jnp.float32).at[1, 20:].set(0.0))
    task = NextToken(cfg, TrainConfig(batch_size=2, window=32))
    short = {"float32": "f32", "bfloat16": "bf16"}[dtype]
    # the dense layer's MLP on (batch, tokens, hidden), ffn_size wide; the
    # shared expert's on the flat rows, moe_shared_experts * moe_ffn_size
    kept_shapes = (f"{short}[2,32,{cfg.ffn_size}]",
                   f"{short}[64,{cfg.moe_shared_experts * cfg.moe_ffn_size}]")

    def run():
        model = build_model(cfg)

        def loss(p):
            return task.loss(p, task.forward(model, p, batch, None), batch)[0]

        capsys.readouterr()
        jax.ad_checkpoint.print_saved_residuals(loss, params)
        kept = [line.split(" ")[0]
                for line in capsys.readouterr().out.splitlines()
                if "(_dense_mlp)" in line]
        return jax.jit(jax.value_and_grad(loss))(params), kept

    (got, got_grads), kept = run()
    assert sorted(kept) == sorted(2 * kept_shapes), kept
    monkeypatch.setattr(decoder, "REPLAY_KEEPS", tuple(
        name for name in decoder.REPLAY_KEEPS
        if name not in (decoder.MLP_GATE, decoder.MLP_UP)))
    (want, want_grads), kept = run()
    assert kept == []
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path((got, got_grads)),
            jax.tree.leaves((want, want_grads))):
        if dtype == "float32":
            np.testing.assert_array_equal(g, w, err_msg=str(path))
        else:
            assert float(jnp.abs(g - w).max()) <= 2e-2 * float(
                jnp.abs(w).max()), path


# -- the seam: one declaration of what the layers count ----------------------


@pytest.mark.parametrize("kind", sorted(PINNED_KINDS))
def test_zero_totals_is_the_tree_a_step_returns(kind):
    """``zero_totals`` makes its shapes from the configuration; a traced
    step makes them from the model: the same tree, leaf for leaf."""
    cfg = _tiny(kind)
    task = NextToken(cfg, TrainConfig(batch_size=2, window=32))
    model = build_model(cfg)
    ids = jnp.zeros((2, 32), jnp.int32)
    batch = Batch(ids, ids, jnp.ones((2, 32), jnp.float32))

    def one_step(key):
        params = model.init({"params": key}, ids[:1, :8])["params"]
        loss, aux = task.loss(
            params, task.forward(model, params, batch, None), batch)
        return task.step_values(loss, aux, batch)

    stepped = jax.eval_shape(one_step, jax.random.PRNGKey(0))
    zeros = task.zero_totals()
    assert jax.tree.structure(stepped) == jax.tree.structure(zeros)
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(stepped),
            jax.tree.leaves(zeros)):
        assert (got.shape, got.dtype) == (want.shape, want.dtype), path


@pytest.mark.parametrize("kind", sorted(PINNED_KINDS))
def test_the_totals_hold_what_the_declaration_lists_and_nothing_else(kind):
    from fmda_tpu.models.decoder import COUNTS, model_counts

    cfg = _tiny(kind)
    totals = NextToken(
        cfg, TrainConfig(batch_size=2, window=32)).zero_totals()
    declared = model_counts(cfg)
    assert set(declared) <= set(COUNTS) <= set(totals._fields)
    for name in COUNTS:
        assert (getattr(totals, name) is not None) == (name in declared), name
    experts = ["expert_pairs", "dropped", "row_tiles_used", "layout_rounds"]
    want = {
        "routed": experts,
        "learned_sparse": experts + ["sparse_keys_kept", "sparse_query_rows"],
        "hybrid": ["ssd_chunks", "ssd_positions"],
        "latent": experts + ["router_load", "router_bias_absmax",
                             "hc_sum_error", "latent_pairs"],
        "latent_plain": experts + ["router_load", "router_bias_absmax",
                                   "latent_pairs"],
        "latent_direct": experts + ["router_load", "router_bias_absmax",
                                    "latent_pairs"]}[kind]
    assert list(declared) == want  # the order features stacks them in
    # which layers count: the dense first layer of a latent model has no
    # experts; a state-space count comes from the state-space layers
    if kind.startswith("latent"):
        assert declared["expert_pairs"].layers == (1, 2)
        assert declared["latent_pairs"].layers == (0, 1, 2)
    if kind == "hybrid":
        assert declared["ssd_chunks"].layers == (0, 2)
    # ... and the loss terms the layers declare, beside the counts: the
    # expert layers' balance term where the configuration states one
    from fmda_tpu.models.decoder import TERMS, model_terms

    assert not set(TERMS) & set(COUNTS) and set(TERMS) <= set(totals._fields)
    assert model_terms(cfg) == (
        {"seq_aux_loss": (1, 2)} if kind == "latent_direct" else {})
    for name in TERMS:
        assert (getattr(totals, name) is not None) == (
            name in model_terms(cfg)), name


@pytest.mark.parametrize("through", ["fold", "merge_micro"])
def test_a_count_folds_as_it_is_declared(through):
    """Every count of a latent model (it has both folds) through the
    pass's fold and the microbatches' merge: ``max`` where declared,
    sums elsewhere."""
    from fmda_tpu.models.decoder import model_counts
    from fmda_tpu.train.tasks import FOLDED_BY_MAX, TokenTotals

    cfg = _tiny("latent")
    task = NextToken(cfg, TrainConfig(batch_size=2, window=32))
    declared = model_counts(cfg)
    assert set(FOLDED_BY_MAX) == {"router_bias_absmax", "hc_sum_error",
                                  "kda_log_decay_absmax",
                                  "gdn_log_decay_absmax", "gdn_beta_max"}
    rng = np.random.default_rng(0)
    a, b = ({name: jnp.asarray(rng.integers(1, 9, c.shape), c.count.dtype)
             for name, c in declared.items()} for _ in range(2))
    if through == "fold":
        one = jnp.ones((), jnp.int32)
        got = task.fold(TokenTotals(jnp.float32(1.0), one, one, **a),
                        TokenTotals(jnp.float32(2.0), one, one, **b))
        assert float(got.loss) == 3.0 and int(got.tokens) == 2
        got = {name: getattr(got, name) for name in declared}
    else:
        two = jnp.ones((2,), jnp.int32)
        stacked = {name: jnp.stack([a[name], b[name]]) for name in declared}
        tokens, _, got = task.merge_micro((two, two, stacked))
        assert int(tokens) == 2
    for name, c in declared.items():
        want = (jnp.maximum if c.count.fold == "max" else jnp.add)(
            a[name], b[name])
        np.testing.assert_array_equal(got[name], want, err_msg=name)
        assert (c.count.fold == "max") == (name in FOLDED_BY_MAX)


@pytest.mark.parametrize("kind,over,message", [
    ("routed", dict(moe_ffn_size=0), "moe_ffn_size"),
    ("learned_sparse", dict(indexer_topk=0),
     "indexer_topk (layer_layout has a learned-sparse layer)"),
    ("hybrid", dict(ssm_state=0),
     "ssm_state (layer_layout has a state-space layer)"),
    ("latent", dict(kv_lora_rank=0),
     "kv_lora_rank (layer_layout has a latent-attention layer)"),
    ("latent_plain", dict(hc_streams=4, hc_sinkhorn_iters=0),
     "hc_sinkhorn_iters (hc_streams is more than 1)"),
    ("routed", dict(hc_streams=2),
     "hc_streams (1, or more lanes (a latent-attention model's))"),
    ("latent", dict(layer_layout=(4, 4, 0)),
     "layer_layout (one of 0/1/2/3/6 per layer, or of 4/5 in every layer)"),
    ("latent_direct", dict(hc_streams=4),
     "moe_seq_aux_alpha (0, or positive with experts under a plain "
     "residual (a model of layers of kinds 4 and 5))"),
    ("routed", dict(moe_seq_aux_alpha=1e-3),
     "moe_seq_aux_alpha (0, or positive with experts under a plain "
     "residual (a model of layers of kinds 4 and 5))"),
    ("latent_direct", dict(q_lora_rank=-1),
     "q_lora_rank (0: a direct query; or the latent's width) (layer_layout "
     "has a latent-attention layer)"),
])
def test_one_missing_field_is_refused_in_the_parents_words(
        kind, over, message):
    with pytest.raises(ValueError) as err:
        check_decoder_config(_tiny(kind, **over))
    assert str(err.value) == "ModelConfig(cell='decoder') needs: " + message
