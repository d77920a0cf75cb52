"""The decoder family's kernels compiled for a TPU v5e at the published
widths, without a chip: the TPU's compiler is installed here and
compiles for a described topology (what interpret mode cannot show: a
slice off the tiling, too much VMEM).  Nothing runs; no time, no result.
One file, so one worker loads the TPU's library; the topology is
described inside a fixture, never at import."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _pallas_grids(fn, *args):
    """The grid of every ``pallas_call`` in ``fn``'s trace, by its name."""
    grids = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids[eqn.params["name"]] = tuple(
                    eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


@pytest.mark.parametrize("window", [None, 4096])
def test_flash_kernels_compile_at_28_on_4_heads_of_128_by_8192(
        one_chip, window):
    from fmda_tpu.ops.pallas_attention import flash_attention

    def step(q, k, v):
        return jax.value_and_grad(lambda *a: flash_attention(
            *a, causal=True, window=window).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v)

    compiled = jax.jit(step).lower(
        _shape(one_chip, (1, 28, 8192, 128), BF16),
        _shape(one_chip, (1, 4, 8192, 128), BF16),
        _shape(one_chip, (1, 4, 8192, 128), BF16)).compile()
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_bwd"):
        assert name in text


@pytest.mark.parametrize("group,seq,window,counted", [
    (2, 4096, 1024, ()),      # two heads a grid step on (512, 1024) blocks
    (1, 4096, None, ()),      # a group of one: the same kernel, no overlap
    (2, 1536, None, ("attention:narrow_key_block",)),  # square blocks of 512
    (16, 2048, 512, ("attention:group_in_parts",)),    # two steps of eight
])
def test_the_forward_alone_compiles_by_group_and_says_what_it_did_not_get(
        one_chip, group, seq, window, counted):
    """``flash_fwd`` for a described v5e at a group of 2 and of 1 (the
    three cells' groups of 7, 4 and 1 compile with their backward in the
    tests around this one), and the two trace-time counters: a length
    that 1,024 does not divide and a group wider than a grid step are
    said in ``kernel_fallbacks()``, the cells' shapes say nothing."""
    from fmda_tpu.ops.dispatch import kernel_fallbacks, reset_kernel_fallbacks
    from fmda_tpu.ops.pallas_attention import flash_attention_with_lse

    reset_kernel_fallbacks()
    heads = 2 * group
    compiled = jax.jit(lambda *a: flash_attention_with_lse(
        *a, causal=True, window=window)).lower(
        _shape(one_chip, (1, heads, seq, 128), BF16),
        _shape(one_chip, (1, 2, seq, 128), BF16),
        _shape(one_chip, (1, 2, seq, 128), BF16)).compile()
    assert "flash_fwd" in compiled.as_text()
    assert tuple(sorted(kernel_fallbacks())) == counted
    reset_kernel_fallbacks()


@pytest.mark.parametrize("heads,kv_heads,seq,d,dv,window,grids", [
    # smallthinker_train_8k, its full and its window layers
    (28, 4, 8192, 128, 128, None, {"flash_fwd": (4, 16, 8),
                                   "flash_bwd": (4, 16, 16)}),
    (28, 4, 8192, 128, 128, 4096, {"flash_fwd": (4, 16, 8),
                                   "flash_bwd": (4, 16, 16)}),
    # moonlight_train_8k and xing_train_4k: a group of one
    (16, 16, 8192, 192, 128, None, {"flash_fwd": (16, 16, 8),
                                    "flash_bwd": (16, 16, 16)}),
    (32, 32, 4096, 192, 128, None, {"flash_fwd": (32, 8, 4),
                                    "flash_bwd": (32, 8, 8)}),
    # granite_h_train_8k
    (32, 8, 8192, 64, 64, None, {"flash_fwd": (8, 16, 8),
                                 "flash_bwd": (8, 16, 16)}),
])
def test_the_backward_is_one_sweep_at_the_four_cells_shapes(
        one_chip, heads, kv_heads, seq, d, dv, window, grids):
    """``flash_bwd`` at each cell's head layout: the grid as traced
    (key-value heads, query blocks, key blocks), one custom call a kernel
    inside the VMEM limit it states, a key-value head's ``dk`` and ``dv``
    summed over its group in the kernel's scratch — no float32 partial a
    query head in the compiled text — and nothing counted."""
    import re

    from fmda_tpu.ops import pallas_attention as kernels
    from fmda_tpu.ops.dispatch import kernel_fallbacks, reset_kernel_fallbacks

    reset_kernel_fallbacks()

    def step(q, k, v):
        return jax.value_and_grad(lambda *a: kernels.flash_attention(
            *a, causal=True, window=window).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v)

    args = (_shape(one_chip, (1, heads, seq, d), BF16),
            _shape(one_chip, (1, kv_heads, seq, d), BF16),
            _shape(one_chip, (1, kv_heads, seq, dv), BF16))
    assert _pallas_grids(step, *args) == grids
    text = jax.jit(step).lower(*args).compile().as_text()
    for name in grids:
        lines = re.findall(
            rf"(?m)^\s*%\S*{name}\S* = .*custom-call\(.*$", text)
        assert len(lines) == 1, name
        assert f'"size":"{kernels._VMEM_LIMIT}"' in lines[0], name
    # what the backward writes: dq a query head, dk and dv a key-value
    # head, all in the compute dtype (the parent's dK/dV sweep wrote
    # f32[heads, T, D] partials that XLA summed outside)
    outputs = re.search(
        r"(?m)^\s*%\S*flash_bwd\S* = (.*?)custom-call\(", text).group(1)
    assert "f32[" not in outputs and outputs.count("bf16[") == 3, outputs
    assert f"bf16[{kv_heads},{seq},{d}]" in outputs
    assert f"bf16[{kv_heads},{seq},{dv}]" in outputs
    assert kernel_fallbacks() == {}
    reset_kernel_fallbacks()


def test_sparse_attention_kernels_compile_at_32_on_4_heads_of_128_by_16384(
        one_chip):
    """Attention over picked keys, forward and backward, at the
    learned-sparse configuration's widths: a (16384, 16384) int8 mask,
    eight query heads a grid step."""
    import re

    from fmda_tpu.ops import pallas_sparse_attention as kernels

    t = 16384
    # the forward pays its row state once a 1,024-key block, the
    # backward kernel keeps its pair (the module's docstring says why)
    assert kernels.fwd_blocks_for(t) == (256, 1024)
    assert kernels.blocks_for(t) == (256, 512)

    def step(q, k, v, mask):
        return jax.value_and_grad(lambda q, k, v: kernels.sparse_attention(
            q, k, v, mask).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    args = (_shape(one_chip, (1, 32, t, 128), BF16),
            _shape(one_chip, (1, 4, t, 128), BF16),
            _shape(one_chip, (1, 4, t, 128), BF16),
            _shape(one_chip, (1, t, t), jnp.int8))
    # the grids as traced: (key-value heads, query blocks, key blocks);
    # one backward sweep, dk / dv held in VMEM for the whole sequence
    grids = _pallas_grids(step, *args)
    assert grids == {"sparse_fwd": (4, 64, 16), "sparse_bwd": (4, 64, 32)}
    compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    calls = {name: re.findall(
        rf"(?m)^\s*%\S*{name}\S* = .*custom-call\(.*$", text)
        for name in grids}
    for name, lines in calls.items():
        assert len(lines) == 1, name
        # the kernel compiled inside the limit it asks for
        assert f'"size":"{kernels._VMEM_LIMIT}"' in lines[0], name
    # the temporaries stay where they were (134.3 MB: o's cotangent in
    # float32 and the packed row statistics): no (T, T) float32
    assert compiled.memory_analysis().temp_size_in_bytes < 140_000_000
    assert not re.search(rf"f32\[(1,)?{t},{t}\]", text)


def test_index_and_selection_kernels_compile_at_16_heads_of_64_by_16384(
        one_chip):
    """The indexer's scores and the counting top-2,048, a chunk of 2,048
    query rows at a time inside one loop: the (16384, 16384) float32
    scores never exist, the mask is int8."""
    from fmda_tpu.ops.sparse_attention import select_keys

    compiled = jax.jit(
        lambda q, k, w: select_keys(q, k, w, 2048, use_kernels=True)).lower(
        _shape(one_chip, (1, 16, 16384, 64), BF16),
        _shape(one_chip, (1, 16384, 64), BF16),
        _shape(one_chip, (1, 16384, 16), jnp.float32)).compile()
    text = compiled.as_text()
    assert "sparse_index" in text and "sparse_select" in text
    assert "f32[1,2048,16384]" in text          # a chunk's scores
    assert "f32[1,16384,16384]" not in text     # never the square
    assert "s8[1,16384,16384]" in text or "s8[8,1,2048,16384]" in text
    # one chunk of scores and the mask, not gigabytes
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("k,n", [(2560, 768), (768, 2560)])
def test_grouped_product_kernels_compile_at_16_experts_of_2560_by_768(
        one_chip, k, n):
    from fmda_tpu.ops.moe import layout_rows
    from fmda_tpu.ops.pallas_moe import grouped_matmul

    tile = 256
    rows = layout_rows(8192 * 6, 16, 64, tile)

    def step(x, w, tile_expert, n_used):
        return jax.value_and_grad(lambda x, w: grouped_matmul(
            x, w, tile_expert, n_used, tile, "pallas").astype(
                jnp.float32).sum(), (0, 1))(x, w)

    compiled = jax.jit(step).lower(
        _shape(one_chip, (rows, k), BF16),
        _shape(one_chip, (16, k, n), jnp.float32),
        _shape(one_chip, (rows // tile,), jnp.int32),
        _shape(one_chip, (1,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "moe_gmm" in text and "moe_tgmm" in text


def test_expert_layer_row_passes_compile_bounded_and_in_place(one_chip):
    """The expert layer's forward and backward at T 8,192, k 6, D 2,560,
    16 of 64 experts held: both row passes (the gather forward and in
    backward's second making of the round, the combine's backward) are
    ``while`` loops inside the loop over rounds, whose 28,672-row buffer
    (twice the even share: 112 tiles, 208 for every pair) is updated in
    place (never copied, in a turn or around either loop), and the
    combine's backward gathers no (8192, 2560) block: the gates'
    gradient comes off the row pass."""
    import re

    from fmda_tpu.ops import moe

    t, k, d, f, count = 8192, 6, 2560, 768, 16
    rows = moe.layout_rows(t * k, count, 64, moe.default_row_tile(t * k))
    assert rows == 28672

    def step(u, gates, experts, w_gate, w_up, w_down):
        def loss(u, gates, w_gate, w_up, w_down):
            m, _ = moe.expert_layer(
                u, gates, experts, w_gate, w_up, w_down,
                experts_held=(0, count), n_experts=64, impl="pallas")
            return m.astype(jnp.float32).sum()
        return jax.value_and_grad(loss, (0, 1, 2, 3, 4))(
            u, gates, w_gate, w_up, w_down)

    text = jax.jit(step).lower(
        _shape(one_chip, (t, d), BF16),
        _shape(one_chip, (t, k), jnp.float32),
        _shape(one_chip, (t, k), jnp.int32),
        _shape(one_chip, (count, d, f), jnp.float32),
        _shape(one_chip, (count, d, f), jnp.float32),
        _shape(one_chip, (count, f, d), jnp.float32)).compile().as_text()
    buffer = rf"= bf16\[{rows},{d}\]\S* "
    lines = text.splitlines()
    # the row passes are loops, in both directions, and each turn updates
    # the row buffer in place (the update's output aliases operand 0)
    for loop in ("while/body/moe_dispatch/while/body/",
                 "while/body/jvp(moe_dispatch)/while/body/",
                 "while/body/transpose(jvp(moe_combine))/while/body/"):
        updates = [line for line in lines if loop in line and re.search(
            buffer + r"(dynamic-update-slice|fusion)\(", line)]
        assert updates, loop
        for line in updates:
            assert '"aliasing_operands":{"lists":[{"indices":["0",' in line
    # ... and nothing ever copies it
    assert not [line for line in lines if re.search(buffer + r"copy\(", line)]
    # no (T, D) gather is left in the combine's backward
    assert not [line for line in lines
                if "transpose(jvp(moe_combine))" in line and "gather" in line
                and re.search(rf"= \w+\[{t},{d}\]", line)]


def _loss_step_text(one_chip, cfg, t):
    """The compiled text of the next-token loss's value and gradient
    through ``cfg``'s model on one sequence of ``t`` tokens, from shapes,
    for the described chip."""
    from fmda_tpu.config import TrainConfig
    from fmda_tpu.data.pipeline import Batch
    from fmda_tpu.models import build_model
    from fmda_tpu.train.tasks import NextToken

    model = build_model(cfg)
    task = NextToken(cfg, TrainConfig(batch_size=1, window=t))
    params = jax.eval_shape(
        lambda key: model.init({"params": key},
                               jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))

    def step(p, x, y, mask):
        batch = Batch(x, y, mask)
        return jax.value_and_grad(lambda p: task.loss(
            p, task.forward(model, p, batch, None), batch)[0])(p)

    return jax.jit(step).lower(
        jax.tree.map(lambda l: _shape(one_chip, l.shape, l.dtype), params),
        _shape(one_chip, (1, t), jnp.int32),
        _shape(one_chip, (1, t), jnp.int32),
        _shape(one_chip, (1, t), jnp.float32)).compile().as_text()


def _kernel_runs(text):
    """The grouped-product kernels' custom calls in a compiled program."""
    import re

    return {name: len(re.findall(
        rf"(?m)^\s*%{name}(?:\.\d+)? = .*custom-call\(", text))
        for name in ("moe_gmm", "moe_tgmm")}


@pytest.mark.parametrize("layout,kernels", [
    ((0, 1), ("flash_fwd",)),
    ((2, 2), ("sparse_fwd", "sparse_select", "sparse_index")),
])
@pytest.mark.parametrize("keeps", ["names", "nothing"])
def test_a_recomputed_decoder_step_runs_each_attention_kernel_once_a_layer(
        one_chip, monkeypatch, layout, kernels, keeps):
    """The next-token loss's value and gradient through two recomputed
    blocks, compiled for the chip on the kernel path: with the replay
    keeping what it names (``decoder.REPLAY_KEEPS``) the program holds
    one core forward a layer, and on a learned-sparse layer one selection
    and one index-score kernel, none under ``rematted_computation``;
    keeping nothing (the policy the family had before) it holds two."""
    import re

    from fmda_tpu.config import ModelConfig
    from fmda_tpu.models import decoder
    from fmda_tpu.ops import attention

    monkeypatch.setattr(attention, "flash_available", lambda: True)
    monkeypatch.setattr(decoder, "kernel_impl", lambda use: "pallas")
    monkeypatch.setattr(decoder, "kernels_dispatch",
                        lambda t, g, d, use_kernels: t % 128 == 0)
    if keeps == "nothing":
        monkeypatch.setattr(decoder, "REPLAY_KEEPS", ())
    t = 2048
    cfg = ModelConfig(
        cell="decoder", hidden_size=256, n_heads=8, n_kv_heads=2,
        head_dim=128, vocab_size=512, layer_layout=layout,
        sliding_window=256, moe_experts=8, moe_top_k=2, moe_ffn_size=128,
        experts_held=(0, 4), hidden_act="silu", indexer_heads=4,
        indexer_head_dim=64, indexer_topk=128, loss_chunk=256,
        dtype="bfloat16", use_pallas=True, remat=True)
    text = _loss_step_text(one_chip, cfg, t)
    runs = 1 if keeps == "names" else 2
    for name in kernels:
        calls = re.findall(
            rf"(?m)^\s*%{name}(?:\.\d+)? = .*custom-call\(.*$", text)
        assert len(calls) == runs * len(layout), (name, len(calls))
        replayed = [c for c in calls if "rematted_computation" in c]
        assert len(replayed) == (runs - 1) * len(layout), name
    # the backward kernels read what was kept: one run a layer either way
    bwd = "sparse_bwd" if 2 in layout else "flash_bwd"
    assert len(re.findall(
        rf"(?m)^\s*%{bwd}(?:\.\d+)? = .*custom-call\(", text)) == len(layout)
    # the expert layer's forward rule keeps its inputs alone: behind a
    # plain residual the replay's forward is dead, and the three products
    # run forward, once more in backward, and transposed, whatever is kept
    assert _kernel_runs(text) == {"moe_gmm": 9 * len(layout),
                                  "moe_tgmm": 3 * len(layout)}


@pytest.mark.parametrize("over,width", [
    # a dense gated MLP a layer, as the hybrid configurations have
    pytest.param(dict(moe_experts=0, ffn_size=640), 640, id="dense"),
    # an expert layer with three shared experts: their MLP is one call
    pytest.param(dict(moe_experts=8, moe_top_k=2, moe_ffn_size=128,
                      experts_held=(0, 4), moe_shared_experts=3,
                      moe_scoring="sigmoid", moe_routed_scaling=2.0), 384,
                 id="shared"),
])
@pytest.mark.parametrize("keeps", ["names", "without_the_mlps"])
def test_a_recomputed_decoder_step_makes_the_mlps_two_products_once(
        one_chip, monkeypatch, over, width, keeps):
    """The next-token loss's value and gradient through two recomputed
    blocks, compiled for the chip: with the gated MLP's two
    pre-activation products kept by name (``decoder.MLP_GATE``,
    ``decoder.MLP_UP`` in ``decoder.REPLAY_KEEPS``) no product of the
    MLP's ``(T, d) x (d, f)`` shape stands under ``rematted_computation``;
    with the two names taken out the replay makes both again, two a
    ``_dense_mlp`` call.  Behind a pre-norm block's plain residual the
    third product's output feeds the block's output alone and is dead in
    the replay either way (a block that reads it in backward, ``post_norm``
    or the lanes, makes it again: PERF.md section 6, PR 55)."""
    import re

    from fmda_tpu.config import ModelConfig
    from fmda_tpu.models import decoder
    from fmda_tpu.ops import attention

    monkeypatch.setattr(attention, "flash_available", lambda: True)
    monkeypatch.setattr(decoder, "kernel_impl", lambda use: "pallas")
    if keeps == "without_the_mlps":
        monkeypatch.setattr(decoder, "REPLAY_KEEPS", tuple(
            name for name in decoder.REPLAY_KEEPS
            if name not in (decoder.MLP_GATE, decoder.MLP_UP)))
    t = 1024
    cfg = ModelConfig(**{**dict(
        cell="decoder", hidden_size=256, n_heads=4, vocab_size=512,
        layer_layout=(4, 4), q_lora_rank=128, kv_lora_rank=128,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_factor=64.0, rope_original_max=4096, hidden_act="silu",
        loss_chunk=256, dtype="bfloat16", use_pallas=True, remat=True),
        **over})
    text = _loss_step_text(one_chip, cfg, t)
    products = re.findall(
        rf"(?m)^.* = bf16\[(?:1,)?{t},{width}\]\S* (?:convolution|dot)\(.*$",
        text)
    replayed = [p for p in products if "rematted_computation" in p]
    # forward: gate and up; backward: the down product's transpose
    assert len(products) - len(replayed) == 3 * len(cfg.layer_layout)
    assert len(replayed) == (
        0 if keeps == "names" else 2 * len(cfg.layer_layout)), len(replayed)


def test_flash_kernels_compile_at_32_on_8_heads_of_64_with_a_stated_scale(
        one_chip):
    """The hybrid configuration's attention layer: heads of 64 (the
    kernels had run 128 only), four query heads a key-value head, no
    window, the scores multiplied by the model's 1/64 in place of
    1/sqrt(64)."""
    from fmda_tpu.ops.pallas_attention import flash_attention

    def step(q, k, v):
        return jax.value_and_grad(lambda *a: flash_attention(
            *a, causal=True, scale=0.015625).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v)

    compiled = jax.jit(step).lower(
        _shape(one_chip, (1, 32, 8192, 64), BF16),
        _shape(one_chip, (1, 8, 8192, 64), BF16),
        _shape(one_chip, (1, 8, 8192, 64), BF16)).compile()
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_bwd"):
        assert name in text


def test_the_chunked_scan_compiles_at_64_heads_of_64_on_state_128_by_8192(
        one_chip):
    """The state-space layer's scan, value and gradient, at the hybrid
    configuration's widths: 32 chunks of 256 walked four at a time, so
    that the program's temporaries stay far under what the decay
    matrices of the whole sequence would take alone (537 MB in float32,
    and as much again for their cotangent)."""
    from fmda_tpu.ops.ssd import ssd_scan

    t, h, p, n = 8192, 64, 64, 128

    def step(xs, d, a, b, c, skip):
        return jax.value_and_grad(
            lambda *args: ssd_scan(*args, chunk=256, dtype=BF16)[0].sum(),
            tuple(range(6)))(xs, d, a, b, c, skip)

    compiled = jax.jit(step).lower(
        _shape(one_chip, (1, t, h, p), BF16),
        _shape(one_chip, (1, t, h), jnp.float32),
        _shape(one_chip, (h,), jnp.float32),
        _shape(one_chip, (1, t, n), BF16), _shape(one_chip, (1, t, n), BF16),
        _shape(one_chip, (h,), jnp.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1_200_000_000


def _delta_rule_walk(one_chip, impl, walks=1, compiled=False, kept={}):
    """Value and gradient of ``walks`` delta-rule walks at Kimi-Linear's
    widths (32 heads of 128 by 8,192, chunks of 64), one after another
    as a model's layers are, lowered for the described chip, or
    ``compiled`` for it; a form is lowered and compiled once a test run."""
    from fmda_tpu.ops.kda import kda_scan

    if compiled:
        if (impl, walks, "compiled") not in kept:
            kept[impl, walks, "compiled"] = _delta_rule_walk(
                one_chip, impl, walks).compile()
        return kept[impl, walks, "compiled"]
    if (impl, walks) not in kept:
        t, h, k = 8192, 32, 128

        def value(q, key, v, g, b):
            for _ in range(walks):
                v = kda_scan(q, key, v, g, b, chunk=64, dtype=BF16,
                             impl=impl)[0].astype(BF16)
            return v.astype(jnp.float32).sum()

        wide = _shape(one_chip, (1, t, h, k), BF16)
        kept[impl, walks] = jax.jit(
            jax.value_and_grad(value, tuple(range(5)))).lower(
            wide, wide, wide, _shape(one_chip, (1, t, h, k), jnp.float32),
            _shape(one_chip, (1, t, h), jnp.float32))
    return kept[impl, walks]


def test_the_delta_rule_walk_compiles_at_32_heads_of_128_by_8192(one_chip):
    """The delta rule with a decay a channel, value and gradient, at
    Kimi-Linear's widths: 128 chunks of 64 walked eight at a time, the
    chunk's solve (float32 products: the program holds no triangular
    solve, as lowered or as compiled) and the pairwise decays'
    ``(16, 16, 128)`` sub-blocks among what the TPU's compiler has to
    take; the program's temporaries stay under what the pairwise decays
    of the whole sequence would take alone (2.1 GB in float32 on the
    diagonal sub-blocks)."""
    lowered = _delta_rule_walk(one_chip, "jnp")
    compiled = _delta_rule_walk(one_chip, "jnp", compiled=True)
    for text in (lowered.as_text(), compiled.as_text()):
        for solve in ("triangular_solve", "triangular-solve",
                      "TriangularSolve"):
            assert solve not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1_200_000_000


def test_the_walk_with_one_decay_a_head_compiles_at_15_heads_of_96_by_192(
        one_chip):
    """The delta rule with ONE decay a head, value and gradient, at the
    share of Olmo-Hybrid's layer a chip holds (15 heads, keys 96 wide,
    no multiple of 128 lanes, values 192, 8,192 positions in chunks of
    64): the TPU's compiler takes it; the pairwise factor is a chunk's
    ``(64, 64)`` matrix a head, so no ``(16, 16, 96)`` tensor of a
    decay a channel is in the program as lowered, and no triangular
    solve as lowered or as compiled."""
    from fmda_tpu.ops.kda import kda_scan

    t, h, k, v = 8192, 15, 96, 192

    def value(q, key, val, g, b):
        return kda_scan(q, key, val, g, b, chunk=64, dtype=BF16,
                        impl="pallas")[0].sum()

    a_head = _shape(one_chip, (1, t, h), jnp.float32)
    lowered = jax.jit(jax.value_and_grad(value, tuple(range(5)))).lower(
        _shape(one_chip, (1, t, h, k), BF16),
        _shape(one_chip, (1, t, h, k), BF16),
        _shape(one_chip, (1, t, h, v), BF16), a_head, a_head)
    compiled = lowered.compile()
    assert "x16x16x96xf32>" not in lowered.as_text()
    assert "x64x64xf32>" in lowered.as_text()
    for text in (lowered.as_text(), compiled.as_text()):
        assert "tpu_custom_call" not in text  # no kernel: nothing to keep
        for solve in ("triangular_solve", "triangular-solve",
                      "TriangularSolve"):
            assert solve not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 600_000_000


def _hbm_instructions(text):
    """A compiled module's instructions outside its fused computations,
    from the result type on: what exists as an array of its own."""
    out, fused = [], False
    for line in text.splitlines():
        if line and not line.startswith(" "):
            fused = "fused_computation" in line.split(" ", 1)[0]
        elif not fused and " = " in line:
            out.append(line.split(" = ", 1)[1])
    return out


def test_the_walks_pairwise_decays_stay_in_the_kernels(one_chip):
    """The same walk with ``kda_intra`` as the two kernels of
    ops/pallas_kda.py: value and gradient compile; no float32 array of
    the pairwise decays' ``(16, 16, 128)`` sub-blocks and none of
    ``k_right``'s shape exists outside a fusion (the ``jnp`` form holds
    the second, so the pattern can tell), and the program's temporaries
    are under the ``jnp`` form's.  The compiled walk runs the forward
    kernel twice (a turn, and the turn made again under backward) and
    the backward's once; as lowered, each kernel is a ``jax.jit`` whose
    body the module holds once a path (jax makes the jit's program again
    where it splits a turn for backward, so the forward's twice), and
    two walks, as two layers, hold no more bodies than one."""
    import re

    def decays(compiled):
        return [a[:60] for a in _hbm_instructions(compiled.as_text())
                if re.match(r"\(?(f32\[[0-9,]*16,16,128\]"
                            r"|(bf16|f32)\[(1,)?8,32,4,64,128\])", a)]

    def bodies(lowered, name):
        return len(re.findall(
            r"stablehlo.custom_call @tpu_custom_call.*kernel_name = "
            rf'"{name}"', lowered.as_text()))

    lowered = _delta_rule_walk(one_chip, "pallas")
    compiled = _delta_rule_walk(one_chip, "pallas", compiled=True)
    arrays = _delta_rule_walk(one_chip, "jnp", compiled=True)
    assert decays(arrays) and not decays(compiled)
    assert (compiled.memory_analysis().temp_size_in_bytes
            < arrays.memory_analysis().temp_size_in_bytes)
    twice = _delta_rule_walk(one_chip, "pallas", walks=2)
    for name, runs in (("kda_intra_fwd", 2), ("kda_intra_bwd", 1)):
        assert len(re.findall(
            rf"(?m)^\s*%{name}(?:\.\d+)? = .*custom-call\(",
            compiled.as_text())) == runs, name
        assert bodies(lowered, name) == bodies(twice, name) == runs, name


def test_the_convolution_compiles_at_4352_channels_by_8192(one_chip):
    """The state-space mixer's activated convolution at the hybrid
    configuration's widths, bfloat16 in and out, 4 taps.  Forward is one
    fusion that reads the input once and writes the result once: no
    float32 array of the sequence (``silu(causal_conv(...))`` as written
    keeps the widened input: 428 MB moved), no temporary.  With its
    three cotangents one float32 array exists, ``dpre`` (as written:
    the pre-activation's cotangent and one more a tap, 1.85 GB moved
    beyond the forward)."""
    import re

    from fmda_tpu.ops.ssd import conv_silu

    t, c = 8192, 4352
    args = (_shape(one_chip, (1, t, c), BF16),
            _shape(one_chip, (c, 4), jnp.float32),
            _shape(one_chip, (c,), jnp.float32))

    def value(x, w, bias):
        return conv_silu(x, w, bias, dtype=BF16)

    def step(x, w, bias, ct):
        out, vjp = jax.vjp(value, x, w, bias)
        return out, vjp(ct)

    def sequence_arrays(compiled):
        return [a for a in _hbm_instructions(compiled.as_text())
                if re.match(r"\(?f32\[1,(819[25],4352|4352,819[25])\]", a)]

    forward = jax.jit(value).lower(*args).compile()
    assert not sequence_arrays(forward)
    assert forward.memory_analysis().temp_size_in_bytes < 1 << 20
    assert forward.cost_analysis()["bytes accessed"] < 150e6
    both = jax.jit(step).lower(*args, args[0]).compile()
    assert len(sequence_arrays(both)) == 1
    assert both.memory_analysis().temp_size_in_bytes < 150e6
    assert both.cost_analysis()["bytes accessed"] < 600e6


def test_a_hybrid_decoder_step_keeps_one_float32_array_a_convolution(
        one_chip):
    """The next-token loss's value and gradient through two recomputed
    state-space layers, compiled for the chip: under ``ssm_conv`` no
    float32 array of the convolution's channels in forward or replay,
    and one a layer in backward (``dpre``)."""
    import re

    from fmda_tpu.config import ModelConfig

    t, layout = 2048, (3, 3)
    cfg = ModelConfig(
        cell="decoder", hidden_size=256, n_heads=4, n_kv_heads=2,
        head_dim=64, vocab_size=512, layer_layout=layout, moe_experts=0,
        ffn_size=512, hidden_act="silu", ssm_heads=8, ssm_head_dim=64,
        ssm_state=128, ssm_conv=4, ssm_chunk=256, tie_embeddings=True,
        loss_chunk=256, dtype="bfloat16", use_pallas=True, remat=True)
    text = _loss_step_text(one_chip, cfg, t)
    # inner 512 + 2 x 128 channels
    wide = [a for a in _hbm_instructions(text) if "ssm_conv" in a and re.match(
        r"\(?f32\[1,(20(48|51),768|768,20(48|51))\]", a)]
    assert len(wide) == len(layout), len(wide)
    assert all("transpose(jvp" in a for a in wide)


def test_flash_kernels_compile_at_32_heads_of_192_on_values_of_128_by_4096(
        one_chip):
    """Latent attention's core at the published widths: scores over 192
    (128 + the 64 rotary dims), values 128 wide, a stated scale.  Nothing
    is padded: the kernels' operands in the compiled text are 192 and
    128 wide."""
    from fmda_tpu.ops.pallas_attention import flash_attention

    def step(q, k, v):
        return jax.value_and_grad(lambda *a: flash_attention(
            *a, causal=True, scale=0.1447).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v)

    compiled = jax.jit(step).lower(
        _shape(one_chip, (1, 32, 4096, 192), BF16),
        _shape(one_chip, (1, 32, 4096, 192), BF16),
        _shape(one_chip, (1, 32, 4096, 128), BF16)).compile()
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_bwd"):
        assert name in text
    assert "bf16[32,4096,256]" not in text and "bf16[1,32,4096,256]" not in text


@pytest.mark.parametrize("keeps", ["names", "nothing"])
def test_a_recomputed_latent_decoder_step_runs_the_core_once_a_layer(
        one_chip, monkeypatch, keeps):
    """The latent-attention decoder's loss and gradient through three
    recomputed blocks (a dense one, two with experts and a shared
    expert), four lanes, compiled for the chip on the kernel path: the
    replay keeps the core's output and row statistics under the names
    every layout uses, so the program holds one ``flash_fwd`` a layer."""
    import re

    from fmda_tpu.config import ModelConfig
    from fmda_tpu.models import decoder
    from fmda_tpu.ops import attention

    monkeypatch.setattr(attention, "flash_available", lambda: True)
    monkeypatch.setattr(decoder, "kernel_impl", lambda use: "pallas")
    if keeps == "nothing":
        monkeypatch.setattr(decoder, "REPLAY_KEEPS", ())
    t = 1024
    cfg = ModelConfig(
        cell="decoder", hidden_size=256, n_heads=4, vocab_size=512,
        layer_layout=(4, 4, 4), q_lora_rank=128, kv_lora_rank=128,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_factor=64.0, rope_original_max=4096, moe_experts=8,
        moe_top_k=2, moe_ffn_size=128, experts_held=(0, 4),
        hidden_act="silu", ffn_size=512, first_dense_layers=1,
        moe_shared_experts=1, moe_scoring="sigmoid", moe_routed_scaling=2.0,
        moe_bias_rate=1e-3, hc_streams=4, loss_chunk=256, dtype="bfloat16",
        use_pallas=True, remat=True)
    text = _loss_step_text(one_chip, cfg, t)
    runs = 1 if keeps == "names" else 2
    calls = re.findall(
        r"(?m)^\s*%flash_fwd(?:\.\d+)? = .*custom-call\(.*$", text)
    assert len(calls) == runs * 3, len(calls)
    assert len(re.findall(
        r"(?m)^\s*%flash_bwd(?:\.\d+)? = .*custom-call\(", text)) == 3
    # the lanes' mixing reads an expert layer's output in backward (the
    # write weights' gradient): kept by name, else the replay runs the
    # forward's three products a third time
    assert _kernel_runs(text) == {"moe_gmm": (6 + 3 * runs) * 2,
                                  "moe_tgmm": 3 * 2}


def test_the_hyper_connections_backward_compiles_at_4_lanes_of_3584_by_4096(
        one_chip):
    """Two sublayers' mixing around a stand-in sublayer, value and
    gradient, compiled for the chip at the latent cell's widths: the
    three backward kernels once a sublayer, the stream handed to them
    as it lies (tokens last: the only copies of the stream's size are
    the program's own argument and result, which arrive and leave in
    the default layout), and no float32 array of the stream's size
    anywhere in the backward."""
    import re

    from fmda_tpu.ops import hyper_connection as hc

    t, n, d = 4096, 4, 3584
    kw = dict(norm_eps=1e-6, iters=20, eps=1e-6, clamp=30.0, impl="pallas")

    def loss(x, mixing, ws):
        for (p_pre, p_post, p_res, a, b), w in zip(mixing, ws):
            x, _, _ = hc.around(
                lambda u, w=w: (
                    jnp.dot(jax.nn.silu(u), w.astype(u.dtype)), None),
                x, p_pre, p_post, p_res, a, b, **kw)
        return jnp.sum(jnp.sum(x.astype(jnp.float32), axis=2) ** 2)

    f32 = jnp.float32
    scalar = _shape(one_chip, (), f32)
    one = (_shape(one_chip, (n * d, n), f32), _shape(one_chip, (n * d, n), f32),
           _shape(one_chip, (n * d, n * n), f32), (scalar,) * 3,
           (_shape(one_chip, (n,), f32), _shape(one_chip, (n,), f32),
            _shape(one_chip, (n, n), f32)))
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        _shape(one_chip, (1, t, n, d), BF16), [one, one],
        [_shape(one_chip, (d, d), f32)] * 2).compile().as_text()
    for name in ("hc_bwd_leave", "hc_bwd_pre", "hc_bwd_enter"):
        assert len(re.findall(
            rf"(?m)^\s*%{name}(?:\.\d+)? = .*custom-call\(", text)) == 2, name
    wide = re.compile(r"^\(?f32\[(1,4096,4,3584|1,4,3584,4096|1,4096,14336"
                      r"|4096,14336)\]")
    moved = re.compile(r"^bf16\[(1,4096,4,3584|1,4,3584,4096)\]\S* "
                       r"(copy|transpose)\(")
    arrays = _hbm_instructions(text)
    backward = [i for i in arrays if "transpose(jvp" in i]
    assert backward
    assert not [i[:120] for i in backward if wide.match(i)]
    assert len([i for i in arrays if moved.match(i)]) == 2
