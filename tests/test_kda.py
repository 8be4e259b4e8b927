"""The chunked gated delta-rule scan (ray_tpu/ops/kda.py) against the
recurrence a token at a time: outputs and the gradients of q, k, v, g and
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
# SAME rounded inputs; read 0.004-0.012 at these sizes
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


def test_the_backward_walks_chunks_and_keeps_one_state_a_step():
    """The differentiated program holds a scan over T / (64 x group) steps
    in each direction and no loop over single tokens; what the forward
    walk leaves for the backward is its inputs and one state a step."""
    B, T, H, d, group = 1, 8 * CHUNK, 2, 8, 2
    args = _inputs(T, B=B, H=H, dk=d, dv=d)
    jaxpr = jax.make_jaxpr(lambda *a: _grads(
        lambda *x: kda_scan(*x, group=group), a, 1.0))(*args)
    lengths = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                lengths.append(eqn.params["length"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    assert lengths and set(lengths) == {T // (CHUNK * group)}
