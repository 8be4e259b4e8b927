"""ops/kda.py as serving uses it: `kda_scan`'s final state and the
one-token `kda_decode_step` (the Mosaic kernel through the Pallas
interpreter, and its `jax.numpy` form) against `kda_recurrent`, the
recurrence a token at a time, with a write strength in (0, 2) (a negative
eigenvalue of I - b k k^T allowed) and rows that are no multiple of the
chunk of 64. CPU, float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda


def _inputs(B, T, H, d, seed=0, strength=2.0):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, T, H, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, d)))
    v = jax.random.normal(ks[2], (B, T, H, d))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (B, T, H, d)))
    beta = strength * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def _final_state(q, k, v, g, beta):
    """The state after the row by the recurrence itself."""
    B, T, H, d = q.shape
    S = jnp.zeros((B, H, d, v.shape[-1]), jnp.float32)
    for t in range(T):
        S = jnp.exp(g[:, t])[..., None] * S
        u = beta[:, t][..., None] * (v[:, t] - jnp.einsum(
            "bhkv,bhk->bhv", S, k[:, t], precision="highest"))
        S = S + k[:, t][..., None] * u[:, :, None, :]
    return S


@pytest.mark.parametrize("T, group", [(100, 1), (64, 1), (37, 2), (150, 2)])
def test_the_scan_hands_back_the_rows_final_state(T, group):
    q, k, v, g, beta = _inputs(2, T, 4, 16, seed=T)
    assert float(beta.max()) > 1.5          # (0, 2), not (0, 1)
    o, S = kda.kda_scan(q, k, v, g, beta, group=group, final_state=True)
    want = kda.kda_recurrent(q, k, v, g, beta)
    np.testing.assert_allclose(o, want, atol=2e-5)
    np.testing.assert_allclose(o, kda.kda_scan(q, k, v, g, beta, group=group),
                               atol=1e-6)   # the trainer's call, unchanged
    assert S.shape == (2, 4, 16, 16) and S.dtype == jnp.float32
    np.testing.assert_allclose(S, _final_state(q, k, v, g, beta), atol=2e-5)


def test_left_padding_that_writes_and_decays_nothing_leaves_no_trace():
    """Rows padded on the LEFT with b = 0, g = 0 and zero q, k, v (what
    `kda_mixer` makes of a prefill's padding): outputs and final state of
    the real tokens are those of the unpadded row."""
    q, k, v, g, beta = _inputs(1, 50, 2, 16, seed=5)
    pad = 27
    padded = [jnp.pad(x, ((0, 0), (pad, 0)) + ((0, 0),) * (x.ndim - 2))
              for x in (q, k, v, g, beta)]
    o, S = kda.kda_scan(*padded, group=1, final_state=True)
    o0, S0 = kda.kda_scan(q, k, v, g, beta, group=1, final_state=True)
    np.testing.assert_allclose(o[:, pad:], o0, atol=2e-5)
    np.testing.assert_allclose(S, S0, atol=2e-5)


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["mosaic-interpreted", "jax-numpy"])
def test_decode_steps_walk_the_recurrence(kernel):
    """T one-token steps on layer 1 of a stacked state give the
    recurrence's outputs and leave its final state; layer 0 and the slot
    that is not active are bit for bit what they were."""
    # the interpreted kernel costs a second a step: fewer of them
    B, T, H, d = 3, 20 if kernel else 70, 16, 16
    q, k, v, g, beta = _inputs(B, T, H, d, seed=9)
    start = jax.random.normal(jax.random.key(1), (2, B, H, d, d))
    state = start.at[1, :2].set(0.0)
    active = jnp.asarray([True, True, False])
    outs = []
    for t in range(T):
        state, o = kda.kda_decode_step(state, 1, q[:, t], k[:, t], v[:, t],
                                       g[:, t], beta[:, t], active,
                                       kernel=kernel)
        outs.append(o)
    want = kda.kda_recurrent(q, k, v, g, beta)
    np.testing.assert_allclose(jnp.stack(outs, 1)[:2], want[:2], atol=2e-5)
    np.testing.assert_allclose(state[1, :2],
                               _final_state(q, k, v, g, beta)[:2], atol=2e-5)
    np.testing.assert_array_equal(np.asarray(state[0]), np.asarray(start[0]))
    np.testing.assert_array_equal(np.asarray(state[1, 2]),
                                  np.asarray(start[1, 2]))


def test_a_prefills_state_then_decode_steps_continue_the_row():
    """The seam serving runs: the scan over the first 45 tokens hands its
    state to one-token steps over the rest."""
    q, k, v, g, beta = _inputs(2, 60, 4, 16, seed=3)
    cut = 45
    _, S = kda.kda_scan(*(x[:, :cut] for x in (q, k, v, g, beta)), group=1,
                        final_state=True)
    state, active = S[None], jnp.ones(2, bool)
    outs = []
    for t in range(cut, 60):
        state, o = kda.kda_decode_step(state, 0, q[:, t], k[:, t], v[:, t],
                                       g[:, t], beta[:, t], active)
        outs.append(o)
    want = kda.kda_recurrent(q, k, v, g, beta)[:, cut:]
    np.testing.assert_allclose(jnp.stack(outs, 1), want, atol=3e-5)
