"""A chip that holds a share of the experts works on the FRONT of the sorted
row buffer (models/moe.py: `front_rows`, `_front_or_whole`): where the
rows that fell on held experts fit it, every stage between the two sorts
is `front` rows long; where they do not, the whole [N*k, d] buffer, so
nothing is ever dropped. On the CPU at toy size, 4 of 16 experts held, 2 a
token: 512 tokens make 1,024 assignments, 256 expected here, a front of
512."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.transformer import init_params, loss_fn

B, T, D, F = 2, 256, 16, 24
E, HELD, FIRST, K = 16, 4, 4, 2
N = B * T
FRONT = 512


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=D, n_layers=2, n_heads=2, d_ff=F,
                dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
                attention_impl="xla", moe_experts=E, moe_held_experts=HELD,
                moe_first_expert=FIRST, moe_top_k=K, moe_scoring="sigmoid",
                moe_select_bias=True, moe_norm_topk=True)
    base.update(kw)
    return TransformerConfig(**base)


def _layer(cfg, seed=0):
    lp = jax.tree.map(lambda a: a[0],
                      moe.init_moe_params(jax.random.key(seed), cfg))
    h = jax.random.normal(jax.random.key(seed + 1), (B, T, D), cfg.dtype)
    return h, lp


def _steered(h, lp, also_held: int):
    """Every token picks held expert 4; its other pick is absent expert 0,
    but for the first ``also_held`` tokens, which pick held expert 5: the
    rows on held experts are N + also_held, the front holds N."""
    h = h.at[:, :, 0].set(-1.0).reshape(N, D).at[:also_held, 0].set(
        1.0).reshape(B, T, D)
    router = lp["router"].at[:, 0].set(0.0)          # score 0.5, + 5.0
    router = router.at[:, 5].set(0.0).at[0, 5].set(100.0)   # 0 or 1, + 4.8
    bias = jnp.zeros(E).at[4].set(10.0).at[0].set(5.0).at[5].set(4.8)
    return h, dict(lp, router=router, router_bias=bias)


def _reference(h, lp, cfg):
    """Token by token over the held experts, dense: drops nothing, sorts
    nothing. (The routing is `moe.route`'s on both sides.)"""
    x = h.reshape(N, D)
    _, top_p, top_i = moe.route(x, lp["router"], cfg, lp.get("router_bias"))
    y = jnp.zeros((N, D), jnp.float32)
    for e in range(HELD):
        weight = jnp.sum(jnp.where(top_i == FIRST + e, top_p, 0.0), axis=-1)
        ff = jax.nn.silu(x @ lp["w_gate"][e]) * (x @ lp["w_up"][e])
        y = y + weight[:, None] * (ff @ lp["w_down"][e])
    return y.reshape(h.shape)


def _value_and_grads(fn, h, lp, cot):
    """fn(h, lp) -> (y, stats): y, stats and the gradients of <y, cot> +
    aux for the rows and every leaf."""
    def f(h, lp):
        y, stats = fn(h, lp)
        return jnp.vdot(y.astype(jnp.float32), cot) + stats["aux"], (y,
                                                                     stats)
    (_, (y, stats)), grads = jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True)(h, lp)
    return y, stats, grads


@pytest.fixture
def whole(monkeypatch):
    """`moe_layer` as it was before it had a front: a front of N*k rows."""
    def layer(h, lp, cfg):
        with monkeypatch.context() as m:
            m.setattr(moe, "FRONT_OVER_EXPECTED", E)
            return moe.moe_layer(h, lp, cfg)
    return layer


def test_the_front_is_a_static_multiple_of_the_expected_load():
    assert moe.front_rows(N * K, HELD, E) == FRONT
    # the two cells that hold a share, and their one-row checks
    assert moe.front_rows(8 * 4096 * 4, 8, 64) == 32768
    assert moe.front_rows(4096 * 4, 8, 64) == 4096
    assert moe.front_rows(2 * 16384 * 8, 8, 256) == 16384
    # whole row tiles, and never more than there are assignments
    assert moe.front_rows(1000 * K, HELD, E) == 1024
    assert moe.front_rows(16 * K, HELD, E) == 16 * K
    assert moe.front_rows(N * K, E, E) == N * K


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 1e-6)])
def test_the_front_computes_what_the_whole_buffer_does(whole, dtype, tol):
    """Output, stats and every gradient: the same rows through the same
    operations, the dead rows left out."""
    cfg = _cfg(dtype=dtype, param_dtype=dtype, moe_shared_d_ff=F)
    h, lp = _layer(cfg)
    cot = jax.random.normal(jax.random.key(9), h.shape)
    y, stats, grads = _value_and_grads(
        lambda h, lp: moe.moe_layer(h, lp, cfg), h, lp, cot)
    y0, stats0, grads0 = _value_and_grads(
        lambda h, lp: whole(h, lp, cfg), h, lp, cot)
    assert float(stats["compact"]) == 1.0 == float(stats0["compact"])
    assert 0.15 < float(stats["held"]) < 0.35
    for name in ("aux", "load", "held"):
        assert float(stats[name]) == float(stats0[name])
    f32 = functools.partial(np.asarray, dtype=np.float32)
    np.testing.assert_allclose(f32(y), f32(y0), atol=tol, rtol=tol)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        assert got.dtype == want.dtype
        np.testing.assert_allclose(f32(got), f32(want), atol=tol, rtol=tol)
    assert float(jnp.abs(grads[1]["w_down"]).sum()) > 0
    assert not bool(jnp.any(grads[1]["router_bias"]))


@pytest.mark.parametrize("case,also_held,compact,held_share", [
    ("natural", None, 1.0, None),
    ("live_fills_the_front_exactly", 0, 1.0, 0.5),
    ("one_row_over_the_front", 1, 0.0, (N + 1) / (N * K)),
    ("every_row_on_a_held_expert", N, 0.0, 1.0),
])
def test_any_load_equals_a_reference_that_drops_nothing(case, also_held,
                                                        compact, held_share):
    cfg = _cfg()
    h, lp = _layer(cfg, seed=3)
    if also_held is not None:
        h, lp = _steered(h, lp, also_held)
    cot = jax.random.normal(jax.random.key(7), h.shape)
    y, stats, (dh, dlp) = _value_and_grads(
        lambda h, lp: moe.moe_layer(h, lp, cfg), h, lp, cot)
    assert float(stats["compact"]) == compact
    if held_share is not None:
        assert float(stats["held"]) == pytest.approx(held_share)

    def ref(h, lp):
        return jnp.vdot(_reference(h, lp, cfg), cot)

    def aux(h, lp):      # the layer's own: the reference has no such term
        return moe.moe_layer(h, lp, cfg)[1]["aux"]
    want = jax.grad(ref, argnums=(0, 1))(h, lp)
    own = jax.grad(aux, argnums=(0, 1))(h, lp)
    np.testing.assert_allclose(y, _reference(h, lp, cfg), atol=2e-5)
    for got, a, b in zip(jax.tree.leaves((dh, dlp)), jax.tree.leaves(want),
                         jax.tree.leaves(own)):
        np.testing.assert_allclose(got, a + b, atol=2e-4, rtol=1e-4)


def test_no_row_on_a_held_expert_gives_exact_zeros():
    cfg = _cfg()
    h, lp = _layer(cfg)
    lp = dict(lp, router_bias=jnp.zeros(E).at[0].set(10.0).at[1].set(10.0))
    cot = jnp.ones(h.shape)
    y, stats, (dh, dlp) = _value_and_grads(
        lambda h, lp: moe.moe_layer(h, lp, cfg), h, lp, cot)
    assert float(stats["compact"]) == 1.0 and float(stats["held"]) == 0.0
    assert not bool(jnp.any(y)) and not bool(jnp.any(dh))
    for name in ("w_gate", "w_up", "w_down"):
        assert not bool(jnp.any(dlp[name])), name


@pytest.mark.parametrize("case,kw,tokens,conds", [
    ("a_share_with_a_front", {}, N, 1),
    # 16 tokens: the front is all 32 assignments, the whole buffer
    ("a_share_whose_front_is_the_buffer", {}, 16, 0),
    ("every_expert_held", {"moe_held_experts": None, "moe_first_expert": 0},
     N, 0),
])
def test_the_cond_is_there_only_where_a_front_is(case, kw, tokens, conds):
    cfg = _cfg(**kw)
    h, lp = _layer(cfg)
    h = h.reshape(N, D)[:tokens].reshape(1, tokens, D)
    jaxpr = str(jax.make_jaxpr(lambda h, lp: moe.moe_layer(h, lp, cfg))(
        h, lp))
    assert jaxpr.count(" cond[") == conds
    grad = str(jax.make_jaxpr(jax.grad(
        lambda h, lp: moe.moe_layer(h, lp, cfg)[0].sum()))(h, lp))
    assert grad.count(" cond[") == 2 * conds       # forward, backward
    _, stats = moe.moe_layer(h, lp, cfg)
    assert float(stats["compact"]) == 1.0


@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("over", [False, True])
def test_under_checkpoint_and_scan_as_the_model_wraps_it(whole, monkeypatch,
                                                         remat, over):
    """`transformer._block_body` checkpoints the block and `_scan_stack`
    scans it over the layers: loss, counters and gradients with a front
    equal those with the whole buffer, on either side of the cond."""
    cfg = _cfg(remat=remat, moe_aux_weight=0.01, moe_shared_d_ff=F)
    params = init_params(jax.random.key(0), cfg)
    if over:     # two held experts take every token, in every layer
        params["layers"]["router_bias"] = jnp.zeros((2, E)).at[:, 4:6].set(
            10.0)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (B, T + 1), 0,
                                          cfg.vocab_size)}
    step = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg), has_aux=True))
    (loss, metrics), grads = step(params)
    monkeypatch.setattr(moe, "FRONT_OVER_EXPECTED", E)
    (loss0, metrics0), grads0 = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg), has_aux=True))(params)
    assert float(metrics["moe_compact_path_share"]) == (0.0 if over else 1.0)
    assert float(metrics0["moe_compact_path_share"]) == 1.0
    assert float(metrics["moe_held_share"]) == float(
        metrics0["moe_held_share"]) > 0
    np.testing.assert_allclose(loss, loss0, rtol=1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-5)


def test_a_model_that_holds_every_expert_reports_no_compact_share():
    cfg = _cfg(moe_held_experts=None, moe_first_expert=0)
    params = init_params(jax.random.key(0), cfg)
    batch = {"tokens": jnp.zeros((2, 9), jnp.int32)}
    _, metrics = loss_fn(params, batch, cfg)
    assert "moe_compact_path_share" not in metrics
    assert "moe_held_share" not in metrics and "moe_aux" in metrics
