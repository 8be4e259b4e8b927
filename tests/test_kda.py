"""The chunked gated delta-rule scan (ray_tpu/ops/kda.py; its two Mosaic
kernels through the Pallas interpreter here) against the recurrence a
token at a time: outputs and the gradients of q, k, v, g and
beta, at lengths that are and are not whole chunks, with one channel that
decays by e^-20 a token and one that barely decays, in float32 tightly and
in bfloat16 under a stated limit."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.kda import CHUNK, kda_recurrent, kda_scan

NAMES = ("q", "k", "v", "g", "beta")
# float32 on the CPU: both sides do the same float32 arithmetic in another
# order (a triangular solve and cumulated decays against 64 single steps)
F32_TOL = 2e-5
# bfloat16 operands (q, k, v and every product's inputs rounded to 8 bits
# of mantissa, float32 accumulation, float32 gates and state): relative
# RMS error of outputs and gradients against the float32 recurrence on the
# SAME rounded inputs; read 0.004-0.012 at dk = dv = 8 and 0.0034-0.0043
# at the cell's 128 (o 0.00341, as the XLA form this replaced read; with
# the pairs within 16 rows rounded too, o read 0.00365)
BF16_TOL = 4e-2


def _inputs(T, seed=0, B=2, H=3, dk=8, dv=8, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, dk), minval=-6.0,
                                    maxval=1.0))
    # channel 0 forgets everything every token, channel 1 next to nothing
    g = g.at[..., 0].set(-20.0).at[..., 1].set(-1e-6)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


def _rel_rms(got, want):
    got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
    return float(jnp.sqrt(jnp.mean((got - want) ** 2)
                          / jnp.maximum(jnp.mean(want ** 2), 1e-30)))


def _grads(fn, args, weight):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight),
                    argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("T,group", [(CHUNK, 1), (3 * CHUNK, 2),
                                     (4 * CHUNK, 4), (37, 4), (200, 2),
                                     (CHUNK + 1, 1)])
def test_chunked_scan_is_the_recurrence_in_float32(T, group):
    args = _inputs(T, seed=T)
    want = kda_recurrent(*args)
    got = kda_scan(*args, group=group)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _rel_rms(got, want) < F32_TOL
    weight = jax.random.normal(jax.random.key(9), want.shape)
    for name, a, b in zip(NAMES, _grads(
            lambda *x: kda_scan(*x, group=group), args, weight),
            _grads(kda_recurrent, args, weight)):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert _rel_rms(a, b) < F32_TOL, name


@pytest.mark.parametrize("T", [2 * CHUNK, 100])
def test_chunked_scan_in_bfloat16_is_within_its_limit(T):
    args = _inputs(T, seed=3, dtype=jnp.bfloat16)
    want = kda_recurrent(*args)            # float32 on the rounded inputs
    got = kda_scan(*args)
    assert got.dtype == jnp.bfloat16
    assert _rel_rms(got, want) < BF16_TOL
    weight = jax.random.normal(jax.random.key(9), want.shape)
    for name, a, b in zip(NAMES, _grads(kda_scan, args, weight),
                          _grads(kda_recurrent, args, weight)):
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
        assert _rel_rms(a, b) < BF16_TOL, name


@pytest.mark.parametrize("log_decay", [-80.0, -20.0, -1e-6, 0.0])
def test_a_channel_stays_finite_however_it_decays(log_decay):
    """Every channel at one decay: e^-80 a token underflows any product of
    cumulated decays taken apart, and 0 is no decay at all (the plain
    delta rule). Outputs and gradients stay finite and are the
    recurrence's."""
    q, k, v, g, beta = _inputs(2 * CHUNK + 5, seed=1)
    args = (q, k, v, jnp.full_like(g, log_decay), beta)
    want, got = kda_recurrent(*args), kda_scan(*args, group=2)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _rel_rms(got, want) < F32_TOL
    weight = jnp.ones_like(want)
    for name, a, b in zip(NAMES, _grads(
            lambda *x: kda_scan(*x, group=2), args, weight),
            _grads(kda_recurrent, args, weight)):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert float(jnp.max(jnp.abs(a - b))) <= F32_TOL * max(
            1.0, float(jnp.max(jnp.abs(b)))), name


def test_a_scalar_decay_a_head_is_another_function():
    """The decay is per CHANNEL: the mean over a head's channels in its
    place gives another output."""
    args = _inputs(CHUNK, seed=5)
    q, k, v, g, beta = args
    scalar = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    assert _rel_rms(kda_scan(q, k, v, scalar, beta), kda_scan(*args)) > 0.05


def _eqns(jaxpr, name):
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == name:
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr)
    return found


def test_the_backward_walks_chunks_and_keeps_one_state_a_step():
    """The differentiated program is two kernels, `kda_scan_fwd` over T /
    (64 x group) grid steps a pair of heads and `kda_scan_bwd` over the
    same steps, and no `scan` or `while` outside them (no loop over single
    tokens, no checkpointed walk); what the forward leaves for the
    backward is its five inputs and one float32 state [dk, dv] a grid
    step."""
    B, T, H, d, group = 1, 8 * CHUNK, 2, 8, 2
    steps = T // (CHUNK * group)
    args = _inputs(T, B=B, H=H, dk=d, dv=d)
    jaxpr = jax.make_jaxpr(lambda *a: _grads(
        lambda *x: kda_scan(*x, group=group), a, 1.0))(*args).jaxpr
    calls = _eqns(jaxpr, "pallas_call")
    assert [c.params["name"] for c in calls] == [
        "kda_scan_fwd", "kda_scan_bwd"]
    assert all(c.params["grid_mapping"].grid == (B, H // 2, steps)
               for c in calls)
    assert not [e for e in jaxpr.eqns if e.primitive.name in ("scan",
                                                              "while")]
    fwd, bwd = calls
    # the forward's outputs: o and the states; the backward reads the
    # forward's inputs (two tables, q, k, v, g, beta), the states and do
    assert [v.aval.shape for v in fwd.outvars] == [
        (B, T, H * d), (B, H, steps, d, d)]
    assert fwd.outvars[1].aval.dtype == jnp.float32
    assert len(fwd.invars) == 7 and len(bwd.invars) == 9
    assert bwd.invars[2:7] == fwd.invars[2:] and (
        bwd.invars[7] is fwd.outvars[1])


def test_a_row_that_is_no_multiple_of_a_grid_steps_rows():
    """T = 5 chunks and 7 tokens at 2 chunks a grid step: three grid steps,
    the last padded with tokens that write nothing and decay nothing; four
    heads, so two a grid step (the other float32 cases have three, one a
    step). Outputs and the five gradients are the recurrence's."""
    T, group = 5 * CHUNK + 7, 2
    args = _inputs(T, seed=11, H=4)
    want, got = kda_recurrent(*args), kda_scan(*args, group=group)
    assert got.shape == want.shape
    assert _rel_rms(got, want) < F32_TOL
    weight = jax.random.normal(jax.random.key(9), want.shape)
    for name, a, b in zip(NAMES, _grads(
            lambda *x: kda_scan(*x, group=group), args, weight),
            _grads(kda_recurrent, args, weight)):
        assert a.shape == b.shape and bool(jnp.all(jnp.isfinite(a))), name
        assert _rel_rms(a, b) < F32_TOL, name


def test_bfloat16_at_the_cells_widths_is_within_its_limit():
    """dk = dv = 128 as the Kimi-Linear cell runs them (whole lanes, the
    blocks the chip takes), T = 512 = four grid steps, H = 2."""
    args = _inputs(512, seed=4, B=1, H=2, dk=128, dv=128,
                   dtype=jnp.bfloat16)
    want, got = kda_recurrent(*args), kda_scan(*args)
    assert got.dtype == jnp.bfloat16
    assert _rel_rms(got, want) < BF16_TOL
    weight = jax.random.normal(jax.random.key(9), want.shape)
    for name, a, b in zip(NAMES, _grads(kda_scan, args, weight),
                          _grads(kda_recurrent, args, weight)):
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
        assert _rel_rms(a, b) < BF16_TOL, name


def test_the_two_tables_cover_every_pair_of_a_chunk_once():
    """Every pair i < r of a chunk belongs to exactly one level, the two
    fall in the two halves of one block of that level, and the table's
    level exponents are sums over the rows between the pair's rows and
    the upper half's last row."""
    import numpy as np

    from ray_tpu.ops.kda import LEVELS, _tables

    sums, level = (np.asarray(x, np.float32) for x in _tables())
    r, i = np.tril_indices(CHUNK, -1)
    assert set(level[r, i]) == set(range(LEVELS))
    assert (level[np.triu_indices(CHUNK)] == -1).all()
    for l in range(LEVELS):
        s = 1 << l
        rr, ii = r[level[r, i] == l], i[level[r, i] == l]
        assert (rr // (2 * s) == ii // (2 * s)).all()
        assert (rr % (2 * s) >= s).all() and (ii % (2 * s) < s).all()
    g = np.random.default_rng(0).uniform(-1, 0, (CHUNK, 1)).astype("f4")
    G = np.cumsum(g, 0)
    assert np.allclose(sums[:CHUNK] @ g, G, atol=1e-5)
    for l in range(2):
        s = 1 << l
        rows = np.arange(CHUNK)
        m = rows // (2 * s) * (2 * s) + s - 1
        assert np.allclose(sums[(1 + l) * CHUNK:(2 + l) * CHUNK] @ g,
                           -np.abs(G - G[m]), atol=1e-5)


def test_pairs_within_16_rows_take_float32_products_in_bfloat16():
    """With bfloat16 operands the six level products of a chunk ([2C, dk]
    x [dk, C]: q's and k's rows against k's) keep float32 operands at the
    highest precision at levels 0-3, the pairs within 16 rows (the least
    decayed ones, which feed the float32 inverse; the XLA form this
    replaced built them in float32 too), and take bfloat16 at levels 4
    and 5, the pairs across 16-row blocks."""
    from ray_tpu.ops.kda import LEVELS, NEAR, _step, _tables

    d, bf16 = 128, jnp.bfloat16
    sums, level = _tables()
    x = jnp.zeros((CHUNK, d), bf16)
    jaxpr = jax.make_jaxpr(lambda S, q, k, v, g, beta: _step(
        S, q, k, v, g, beta, sums=sums, level=jnp.asarray(level)))(
            jnp.zeros((1, d, d)), x, x, x, x.astype(jnp.float32),
            jnp.zeros((CHUNK, 1))).jaxpr
    products = [e for e in _eqns(jaxpr, "dot_general")
                if e.outvars[0].aval.shape == (2 * CHUNK, CHUNK)]
    assert len(products) == LEVELS and NEAR == 16
    for l, e in enumerate(products):
        want = jnp.float32 if 2 << l <= NEAR else bf16
        assert [v.aval.dtype for v in e.invars] == [want, want], l
        if want == jnp.float32:
            assert all(p == jax.lax.Precision.HIGHEST
                       for p in e.params["precision"]), l
