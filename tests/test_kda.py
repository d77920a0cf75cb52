"""The chunked delta rule, with a decay a channel and with one a head,
against the recurrence as written (ops/kda.py): outputs, the final state
and every gradient, at small sizes and seeded inputs, float32 on the
CPU."""

import jax
import jax.numpy as jnp
import pytest

from fmda_tpu.ops import kda
from fmda_tpu.ops.kda import kda_scan, kda_stepwise

B, H, K, V = 2, 3, 8, 6


def _inputs(t, seed=0, decay=1.0, beta=None, key_noise=None,
            sizes=(B, H, K, V), a_head=False, beta_scale=1.0):
    """``a_head``: one log-decay a head, ``g`` (B, T, H); ``beta_scale``
    2: ``b`` drawn over 0..2, a correction that may overshoot."""
    batch, h, dk, dv = sizes
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(key, (batch, t, h, dk)) for key in keys[:2])
    if key_noise is not None:  # every position's key near one key a head
        k = jax.random.normal(jax.random.fold_in(keys[1], 1),
                              (batch, 1, h, dk)) + key_noise * k
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v = jax.random.normal(keys[2], (batch, t, h, dv))
    g = -jax.nn.softplus(jax.random.normal(
        keys[3], (batch, t, h) if a_head else (batch, t, h, dk))) * decay
    b = beta_scale * jax.nn.sigmoid(
        beta_scale * jax.random.normal(keys[4], (batch, t, h)))
    return q, k, v, g, b if beta is None else jnp.full_like(b, beta)


def _close(got, want, tol):
    scale = float(jnp.abs(want).max()) + 1e-12
    assert float(jnp.abs(got - want).max()) <= tol * scale


# a length that is no multiple of the chunk (two chunks of two sub-blocks
# each), one chunk alone, several groups (48 chunks in six groups of
# eight), a decay that underflows inside a chunk, no decay (the plain
# delta rule), no correction (the state only decays), and one chunk of 64
# whose keys all but agree (neighbouring tokens' do, in a trained layer):
# the chunk's unit-lower matrix is then near all ones under its diagonal,
# the powers of that part grow binomially before they cancel, and an
# inverse by doublings over 16 rows reads 4.8e-4 here where a solve by
# substitution reads 3e-7 (ops/kda.py SOLVE_ROWS)
CASES = {
    "ragged": (37, 32, dict()),
    "one_chunk": (8, 8, dict()),
    "six_groups": (384, 8, dict(decay=0.1)),
    "underflow": (64, 16, dict(decay=60.0)),
    "no_decay": (48, 16, dict(decay=0.0)),
    "no_correction": (48, 16, dict(beta=0.0)),
    "keys_alike": (64, 64, dict(decay=0.0, beta=1.0, key_noise=0.05)),
    # ... where the correction may overshoot (``b`` up to 2: the unit-lower
    # matrix's entries double, and ``I - 2 k k^T`` reflects the state)
    "keys_alike_beta_two": (64, 64, dict(decay=0.0, beta=2.0,
                                         key_noise=0.05)),
    # one decay a head (``g`` (B, T, H)), ``b`` drawn over 0..2, ``K`` !=
    # ``V`` as everywhere in this file
    "a_head_ragged": (37, 32, dict(a_head=True, beta_scale=2.0)),
    "a_head_six_groups": (384, 8, dict(a_head=True, decay=0.1,
                                       beta_scale=2.0)),
    "a_head_underflow": (64, 16, dict(a_head=True, decay=60.0,
                                      beta_scale=2.0)),
    "a_head_beta_two": (48, 16, dict(a_head=True, beta=2.0)),
    "a_head_keys_alike_beta_two": (64, 64, dict(
        a_head=True, decay=0.0, beta=2.0, key_noise=0.05)),
}


def _walk_against_the_recurrence(args, chunk, **walk):
    """Outputs, final state and the five gradients of ``kda_scan`` against
    ``kda_stepwise`` on ``args``, at the tolerances every path of the
    walk is held to: ``(o, state, the largest |G|)`` of the walk."""
    def through(fn):
        def value(*a):
            o, state, *absmax = fn(*a)
            return (jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(state)),
                    (o, state, absmax))
        return jax.jit(jax.value_and_grad(
            value, argnums=tuple(range(5)), has_aux=True))(*args)

    with jax.default_matmul_precision("highest"):
        (_, (want_o, want_s, _)), want = through(kda_stepwise)
        (_, (got_o, got_s, (absmax,))), got = through(
            lambda *a: kda_scan(*a, chunk=chunk, **walk))
    assert got_o.shape == want_o.shape == args[2].shape
    assert got_s.shape == want_s.shape
    _close(got_o, want_o, 2e-5)
    _close(got_s, want_s, 2e-5)
    for g, w in zip(got, want):
        assert bool(jnp.isfinite(g).all())  # every value finite
        _close(g, w, 1e-4)
    return got_o, got_s, absmax


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_walk_is_the_recurrence_as_written(case):
    t, chunk, kw = CASES[case]
    args = _inputs(t, seed=t, **kw)
    got_o, got_s, absmax = _walk_against_the_recurrence(args, chunk)
    assert got_o.shape == (B, t, H, V) and got_s.shape == (B, H, K, V)
    # the largest |G| inside a chunk: the sum of a chunk's log-decays
    g = args[3]
    pad = -t % chunk
    by_chunk = jnp.pad(g, ((0, 0), (0, pad)) + ((0, 0),) * (g.ndim - 2)
                       ).reshape((B, -1, chunk) + g.shape[2:])
    assert float(absmax) == pytest.approx(
        float(jnp.abs(by_chunk.sum(2)).max()), rel=1e-5)
    if kw.get("beta_scale") == 2.0:  # the overshoot is exercised
        assert float(args[4].max()) > 1.5
    if case.endswith("underflow"):  # past float32's 87: exp(-G) would be inf
        assert float(absmax) > 200.0
        assert float(jnp.exp(-absmax)) == 0.0
    if case == "no_decay":
        assert float(absmax) == 0.0
    if case == "no_correction":  # nothing was ever written
        assert not bool(jnp.any(got_s)) and not bool(jnp.any(got_o))


@pytest.mark.parametrize("chunk", [16, 32])
def test_one_decay_a_head_is_that_decay_on_every_channel(chunk):
    """``g`` (B, T, H) and the same ``g`` broadcast over the key
    channels agree, through the walk and through the recurrence as
    written; with one decay a head no (rows, rows, K) tensor is in the
    program and its pairwise factor is a chunk's (C, C) matrix."""
    q, k, v, g, b = _inputs(80, seed=5, a_head=True, beta_scale=2.0)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    with jax.default_matmul_precision("highest"):
        for fn in (kda_stepwise, lambda *a: kda_scan(*a, chunk=chunk)):
            one, many = (jax.jit(fn)(q, k, v, x, b) for x in (g, wide))
            for got, want in zip(one, many):
                _close(got, want, 2e-6)
    text = jax.jit(lambda *a: kda_scan(*a, chunk=chunk)[0]).lower(
        q, k, v, g, b).as_text()
    assert f"x{K}xf32>" in text and f"x{chunk}x{chunk}xf32>" in text
    assert f"x{kda.SUB_ROWS}x{kda.SUB_ROWS}x{K}xf32>" not in text


def test_the_pairwise_decays_exist_a_group_of_chunks_at_a_time(monkeypatch):
    """Forty-eight chunks are walked six groups of eight, each made
    again in backward: no (chunks, H, chunk, chunk) array of the whole
    sequence is in the program, and the (rows, rows, K) tensor is a
    sub-block's."""
    args = _inputs(384, seed=2)
    grad = jax.jit(jax.grad(lambda *a: kda_scan(*a, chunk=8)[0].sum()))
    text = grad.lower(*args).as_text()
    assert f"tensor<{B}x48x{H}x8x8xf32>" not in text
    assert f"tensor<{B}x{kda.CHUNK_GROUP}x{H}x8x8xf32>" in text
    assert f"tensor<{B}x{kda.CHUNK_GROUP}x{H}x1x8x8x{K}xf32>" in text
    monkeypatch.setattr(kda, "SUB_ROWS", 4)
    halves = jax.jit(lambda *a: kda_scan(*a, chunk=8)[0]).lower(
        *args).as_text()
    assert f"tensor<{B}x{kda.CHUNK_GROUP}x{H}x2x4x4x{K}xf32>" in halves
    assert f"x8x8x{K}xf32>" not in halves


def test_a_state_or_log_decays_rounded_to_bfloat16_are_refused():
    """The configuration guarantees a float32 state, float32 cumulative
    log-decays and a float32 solve, and a run on the chip cannot tell
    (PERF.md section 7: behind bfloat16 products the rounding reads as
    the program's own distance).  Held here: the walk's types are read
    from its traced program at ``dtype`` bfloat16, and the tolerance
    the cases above hold the walk to is a hundred times under what a
    recurrence with its state and ``g`` rounded to bfloat16 reads."""
    from benchmark.reference.kda_decoder import _delta_recurrence

    t, chunk, kw = CASES["six_groups"]
    q, k, v, g, b = _inputs(t, seed=t, **kw)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = jax.jit(
            lambda *a: kda_stepwise(*a, scale=1.0))(q, k, v, g, b)
        one = tuple(x[0] for x in (q, k, v, g, b))
        plain, rounded = (
            jax.jit(lambda *a: _delta_recurrence(
                *a, remat=False, state_as=state_as))(*one)
            for state_as in (None, "bfloat16"))
    scale = float(jnp.abs(want_o[0]).max())
    assert float(jnp.abs(plain - want_o[0]).max()) <= 2e-5 * scale
    assert float(jnp.abs(rounded - want_o[0]).max()) >= 2e-3 * scale

    narrow = tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, b)
    jaxpr = jax.make_jaxpr(lambda *a: kda_scan(
        *a, chunk=chunk, dtype=jnp.bfloat16))(*narrow)

    def equations(jaxpr, scopes=""):
        # an inner program's name stacks start at the equation that
        # calls it (the solve's products are inside its custom_vjp)
        for eqn in jaxpr.eqns:
            under = f"{scopes}/{eqn.source_info.name_stack}"
            yield under, eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub, under)

    # the solve: under the ``kda_solve`` scope every product on the MXU
    # asks for every pass, and those of the halving on the vector unit
    # multiply and sum float32
    found = {"scan": [], "cumsum": [], "kda_solve": [], "halving": []}
    for under, eqn in equations(jaxpr.jaxpr):
        name = eqn.primitive.name
        assert name not in ("reduce_precision", "triangular_solve")
        if name == "scan":  # the walk's: its one carry is the state
            n = eqn.params["num_carry"]
            consts = eqn.params["num_consts"]
            found[name] += [x.aval for x in eqn.invars[consts:consts + n]]
        elif name == "cumsum":
            found[name] += [x.aval for x in eqn.invars]
        elif name == "dot_general" and "kda_solve" in under:
            assert eqn.params["precision"] is not None and all(
                p == jax.lax.Precision.HIGHEST
                for p in eqn.params["precision"])
            found["kda_solve"] += [x.aval for x in eqn.invars + eqn.outvars]
        elif name in ("mul", "reduce_sum") and "kda_solve" in under:
            found["halving"] += [x.aval for x in eqn.invars + eqn.outvars]
    assert [(a.shape, a.dtype) for a in found["scan"]] == [
        ((B, H, K, V), jnp.float32)]
    for name in ("cumsum", "kda_solve", "halving"):
        assert found[name] and all(
            a.dtype == jnp.float32 for a in found[name]), name


def _strictly_lower(kind):
    """``Diag(b) A`` of a chunk of 64, (B, H, 64, 64) float32: of the
    ``keys_alike`` case (no decay, so ``A`` is the keys' products), or
    random."""
    if kind == "random":
        return jnp.tril(jax.random.normal(
            jax.random.PRNGKey(7), (B, H, 64, 64)), -1) / 8.0
    t, _, kw = CASES["keys_alike"]
    _, k, _, _, b = _inputs(t, seed=t, **kw)
    a = jnp.einsum("bihk,bjhk->bhij", k, k)
    return jnp.moveaxis(b, 1, 2)[..., None] * jnp.tril(a, -1)


@pytest.mark.parametrize("kind", ["keys_alike", "random"])
def test_the_chunks_inverse_is_the_inverse(kind):
    strict = _strictly_lower(kind)
    l = jnp.eye(64) + strict
    inv = jax.jit(kda._unit_lower_inverse)(strict)
    assert inv.shape == l.shape and inv.dtype == jnp.float32
    assert not bool(jnp.any(jnp.triu(inv, 1)))      # lower triangular
    assert float(jnp.abs(jnp.diagonal(inv, axis1=-2, axis2=-1) - 1).max()) \
        == 0.0                                       # of unit diagonal
    with jax.default_matmul_precision("highest"):
        assert float(jnp.abs(inv @ l - jnp.eye(64)).max()) <= 1e-5


@pytest.mark.parametrize("kind", ["keys_alike", "random"])
def test_the_solves_gradients_are_the_triangular_solves(kind):
    """``_unit_lower_solve``'s own rule (two products from what forward
    kept) against ``jax.grad`` through XLA's ``triangular_solve``."""
    strict = _strictly_lower(kind)
    r = jax.random.normal(jax.random.PRNGKey(8), (B, H, 64, 2 * V))

    def through(solve):
        def value(strict, r):
            x = solve(jnp.tril(strict, -1), r)
            return jnp.sum(jnp.sin(x)), x
        return jax.jit(jax.value_and_grad(value, (0, 1), has_aux=True))(
            strict, r)

    with jax.default_matmul_precision("highest"):
        (_, want_x), want = through(
            lambda n, r: jax.scipy.linalg.solve_triangular(
                jnp.eye(64) + n, r, lower=True, unit_diagonal=True))
        (_, got_x), got = through(kda._unit_lower_solve)
    _close(got_x, want_x, 1e-5)
    for g, w in zip(got, want):
        _close(g, w, 1e-5)


# the kernels of ops/pallas_kda.py under the Pallas interpreter, at a
# width they take (128 key channels a head)
WIDE = (1, 2, 128, 128)
# what a group's (q, k, gc) and the cotangents of (A, B) are made with:
# the decay's scale, the keys' noise about one key a head, and whether
# the cotangents arrive lower triangular (the walk's do: the solve's
# rule masks dA) or full (the kernel masks for itself)
INTRA_CASES = {
    "plain": dict(),
    "underflow": dict(decay=60.0),
    "no_decay": dict(decay=0.0),
    "keys_alike": dict(decay=0.0, key_noise=0.05),
    "full_cotangent": dict(lower=False),
}


@pytest.mark.parametrize("chunk", [64, 32])
@pytest.mark.parametrize("case", sorted(INTRA_CASES))
def test_the_kernels_are_the_pairwise_products_and_their_cotangents(
        case, chunk):
    from fmda_tpu.ops import pallas_kda

    kw = dict(INTRA_CASES[case])
    lower = kw.pop("lower", True)
    q, k, _, g, _ = _inputs(3 * chunk, seed=chunk, sizes=WIDE, **kw)
    # (B, T, H, K) -> a group of three chunks, (B, G, H, C, K)
    q, k, g = (jnp.swapaxes(x.reshape(1, 3, chunk, 2, 128), 2, 3)
               for x in (q, k, g))
    q, gc = q * 128 ** -0.5, jnp.cumsum(g, -2)
    da, db = (jax.random.normal(key, (1, 3, 2, chunk, chunk))
              for key in jax.random.split(jax.random.PRNGKey(chunk + 1)))
    if lower:
        da, db = jnp.tril(da, -1), jnp.tril(db)

    def through(fn):
        out, vjp = jax.vjp(fn, q, k, gc)
        return out, vjp((da, db))

    with jax.default_matmul_precision("highest"):
        want, want_ct = jax.jit(lambda: through(
            lambda *a: kda._pairwise(*a, kda.SUB_ROWS, jnp.float32)))()
        got, got_ct = jax.jit(lambda: through(
            lambda *a: pallas_kda.pairwise(
                kda.SUB_ROWS, jnp.float32, True, *a)))()
    for g_, w in zip(got, want):
        assert g_.shape == w.shape == (1, 3, 2, chunk, chunk)
        _close(g_, w, 1e-6)
    assert not bool(jnp.any(jnp.triu(got[0])))      # A strictly lower
    assert not bool(jnp.any(jnp.triu(got[1], 1)))   # B lower
    for g_, w in zip(got_ct, want_ct):
        assert bool(jnp.isfinite(g_).all())
        _close(g_, w, 2e-6)


# the walk's cases at that width and at chunks the kernels take (the
# six groups are of eight chunks of 16 here, not of 8)
WIDE_CASES = {
    "ragged": (37, 32, dict()),
    "six_groups": (768, 16, dict(decay=0.1)),
    "underflow": (64, 16, dict(decay=60.0)),
    "no_correction": (48, 16, dict(beta=0.0)),
    "keys_alike": (64, 64, dict(decay=0.0, beta=1.0, key_noise=0.05)),
}


@pytest.mark.parametrize("case", sorted(WIDE_CASES))
def test_the_walk_through_the_kernels_is_the_recurrence_as_written(case):
    from fmda_tpu.ops.dispatch import kernel_fallbacks, reset_kernel_fallbacks

    t, chunk, kw = WIDE_CASES[case]
    args = _inputs(t, seed=t, sizes=WIDE, **kw)
    reset_kernel_fallbacks()
    got_o, got_s, absmax = _walk_against_the_recurrence(
        args, chunk, impl="interpret")
    assert "decoder:kda_shape" not in kernel_fallbacks()
    if case == "underflow":
        assert float(absmax) > 200.0
    if case == "no_correction":
        assert not bool(jnp.any(got_s)) and not bool(jnp.any(got_o))


@pytest.mark.parametrize("chunk,sub,k,takes", [
    (64, 16, 128, True), (32, 16, 128, True), (64, 16, 256, True),
    (64, 16, 8, False), (64, 16, 192, False), (24, 12, 128, False),
    (8, 8, 128, False)])
def test_the_kernels_take_whole_sub_blocks_of_whole_lanes(chunk, sub, k,
                                                          takes):
    from fmda_tpu.ops import pallas_kda

    assert pallas_kda.fits(chunk, sub, k) is takes


@pytest.mark.parametrize("t,chunk,sizes", [
    (37, 32, (B, H, K, V)),      # 8 key channels a head
    (48, 24, WIDE),              # sub-blocks of 12 rows
])
def test_a_shape_the_kernels_refuse_runs_as_arrays_and_is_counted(
        t, chunk, sizes):
    from fmda_tpu.ops.dispatch import kernel_fallbacks, reset_kernel_fallbacks

    args = _inputs(t, seed=t, sizes=sizes)

    def through(impl):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(kda_scan(
                *a, chunk=chunk, impl=impl)[0])), tuple(range(5))))(*args)

    reset_kernel_fallbacks()
    want, want_grads = through("jnp")
    assert kernel_fallbacks() == {}                 # nothing was refused
    got, got_grads = through("interpret")
    assert kernel_fallbacks() == {"decoder:kda_shape": 1}
    assert float(got) == float(want)
    for g, w in zip(got_grads, want_grads):         # bit for bit
        assert bool(jnp.all(g == w))
