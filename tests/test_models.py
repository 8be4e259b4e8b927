"""Model family tests: shapes, loss decrease, sharded parity (8-dev CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    forward,
    get_config,
    init_params,
    init_train_state,
    loss_fn,
    make_optimizer,
    make_train_step,
    param_logical_axes,
    tiny_config,
)
from ray_tpu.parallel import make_mesh


def _batch(cfg, b=2, t=16, seed=0):
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, cfg.vocab_size, size=(b, t + 1)).astype(np.int32)
    return {"inputs": jnp.asarray(toks[:, :-1]),
            "targets": jnp.asarray(toks[:, 1:])}


def test_forward_shapes_and_dtype():
    cfg = tiny_config()
    params = init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((2, 8), jnp.int32)
    logits = forward(params, toks, cfg)
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_param_count_matches_config():
    cfg = tiny_config()
    params = init_params(jax.random.key(0), cfg)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params


def test_logical_axes_structure_matches_params():
    cfg = tiny_config()
    params = init_params(jax.random.key(0), cfg)
    axes = param_logical_axes(cfg)
    p_leaves = jax.tree.leaves(params)
    a_leaves = jax.tree.leaves(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert len(p_leaves) == len(a_leaves)
    for p, a in zip(p_leaves, a_leaves):
        assert p.ndim == len(a), (p.shape, a)


def test_gqa_kv_heads():
    cfg = tiny_config(n_heads=4, n_kv_heads=2)
    params = init_params(jax.random.key(0), cfg)
    assert params["layers"]["wk"].shape[2] == 2
    logits = forward(params, jnp.zeros((1, 4), jnp.int32), cfg)
    assert logits.shape == (1, 4, cfg.vocab_size)


def test_causality():
    """Changing a future token must not affect earlier logits."""
    cfg = tiny_config()
    params = init_params(jax.random.key(0), cfg)
    t1 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    t2 = t1.at[0, -1].set(99)
    l1 = forward(params, t1, cfg)
    l2 = forward(params, t2, cfg)
    np.testing.assert_allclose(np.asarray(l1[:, :-1]), np.asarray(l2[:, :-1]),
                               rtol=1e-5, atol=1e-5)


def test_loss_decreases_single_device():
    cfg = tiny_config()
    tx = make_optimizer(1e-2, warmup_steps=0)
    state = init_train_state(jax.random.key(0), cfg, tx)
    step = make_train_step(cfg, tx)
    batch = _batch(cfg)
    losses = []
    for _ in range(10):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses


@pytest.mark.parametrize("mesh_kw", [
    dict(fsdp=4), dict(fsdp=2, tensor=2), dict(data=2, fsdp=2),
    dict(fsdp=2, sequence=2),
])
def test_sharded_train_step_matches_unsharded(mesh_kw):
    cfg = tiny_config()
    tx = make_optimizer(1e-2)
    batch = _batch(cfg, b=4, t=32)

    ref_state = init_train_state(jax.random.key(0), cfg, tx)
    ref_step = make_train_step(cfg, tx)
    ref_state, ref_metrics = ref_step(ref_state, batch)

    mesh = make_mesh(**mesh_kw)
    sh_state = init_train_state(jax.random.key(0), cfg, tx, mesh)
    sh_step = make_train_step(cfg, tx, mesh)
    sh_state, sh_metrics = sh_step(sh_state, batch)

    np.testing.assert_allclose(float(ref_metrics["loss"]),
                               float(sh_metrics["loss"]), rtol=1e-4)
    ref_emb = np.asarray(ref_state["params"]["embed"])
    sh_emb = np.asarray(jax.device_get(sh_state["params"]["embed"]))
    np.testing.assert_allclose(ref_emb, sh_emb, rtol=1e-3, atol=1e-5)


def test_state_sharding_zero3():
    """fsdp axis must actually shard params + optimizer moments."""
    cfg = tiny_config()
    mesh = make_mesh(fsdp=4)
    tx = make_optimizer()
    state = init_train_state(jax.random.key(0), cfg, tx, mesh)
    wq = state["params"]["layers"]["wq"]
    # embed dim (axis 1) sharded over fsdp=4
    assert wq.sharding.spec[1] == "fsdp"
    mu = jax.tree.leaves(state["opt_state"])  # moments somewhere in there
    sharded = [x for x in mu if hasattr(x, "sharding")
               and x.ndim >= 2 and x.sharding.spec[1] == "fsdp"]
    assert sharded, "optimizer moments are not ZeRO-sharded"


def test_presets_construct():
    for name in ("tiny", "gpt2-small", "llama3-8b", "llama3-70b"):
        cfg = get_config(name)
        assert cfg.num_params > 0
    assert 7e9 < get_config("llama3-8b").num_params < 9e9
    assert 1.0e8 < get_config("gpt2-small").num_params < 1.8e8


class TestMoE:
    """Mixture-of-Experts FFN + expert parallelism (models/moe.py; EP is
    greenfield per SURVEY.md §2.3 — absent from the reference)."""

    def _cfg(self, **kw):
        from ray_tpu.models.config import TransformerConfig
        import jax.numpy as jnp

        base = dict(vocab_size=64, d_model=16, n_layers=2, n_heads=2,
                    d_ff=32, dtype=jnp.float32, param_dtype=jnp.float32,
                    remat=False, attention_impl="xla", moe_experts=4,
                    moe_top_k=2)
        base.update(kw)
        return TransformerConfig(**base)

    def test_identical_experts_match_dense_ffn(self):
        """With every expert set to the same weights and combine weights
        renormalized, the MoE layer must equal the dense FFN exactly
        (dropless: there is no capacity to overflow)."""
        import jax, jax.numpy as jnp, numpy as np
        from ray_tpu.models.moe import moe_layer

        cfg = self._cfg()
        d, ff, E = cfg.d_model, cfg.d_ff, cfg.moe_experts
        key = jax.random.key(0)
        wg = jax.random.normal(key, (d, ff)) * 0.1
        wu = jax.random.normal(jax.random.key(1), (d, ff)) * 0.1
        wd = jax.random.normal(jax.random.key(2), (ff, d)) * 0.1
        lp = {
            "router": jax.random.normal(jax.random.key(3), (d, E)),
            "w_gate": jnp.broadcast_to(wg, (E, d, ff)),
            "w_up": jnp.broadcast_to(wu, (E, d, ff)),
            "w_down": jnp.broadcast_to(wd, (E, ff, d)),
        }
        h = jax.random.normal(jax.random.key(4), (2, 8, d))
        out, stats = moe_layer(h, lp, cfg)
        dense = jnp.einsum(
            "btf,fd->btd",
            jax.nn.silu(jnp.einsum("btd,df->btf", h, wg))
            * jnp.einsum("btd,df->btf", h, wu), wd)
        np.testing.assert_allclose(out, dense, atol=1e-5)
        assert float(stats["aux"]) > 0

    def test_expert_parallel_sharded_matches_unsharded(self):
        import jax, jax.numpy as jnp, numpy as np
        from ray_tpu.models.moe import init_moe_params, moe_layer
        from ray_tpu.parallel import make_mesh

        cfg = self._cfg(n_layers=1)
        params = init_moe_params(jax.random.key(0), cfg)
        lp = jax.tree.map(lambda p: p[0], params)  # layer 0
        h = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model))
        ref, stats_ref = moe_layer(h, lp, cfg)
        mesh = make_mesh(expert=4, fsdp=2)
        out, stats = jax.jit(
            lambda h, lp: moe_layer(h, lp, cfg, mesh))(h, lp)
        np.testing.assert_allclose(ref, out, atol=1e-5)
        for name in ("aux", "load"):
            np.testing.assert_allclose(float(stats_ref[name]),
                                       float(stats[name]), atol=1e-5)

    def test_moe_transformer_trains_and_routes(self):
        """End-to-end: MoE transformer loss decreases and aux loss is
        finite; grads flow to every expert parameter."""
        import jax, jax.numpy as jnp, numpy as np
        from ray_tpu.models import forward, init_params
        from ray_tpu.models.transformer import loss_fn

        cfg = self._cfg()
        params = init_params(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (4, 17), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks}

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, cfg)
        assert np.isfinite(float(loss))
        assert np.isfinite(float(metrics["moe_aux"]))
        for name in ("router", "w_gate", "w_up", "w_down"):
            g = grads["layers"][name]
            assert float(jnp.abs(g).sum()) > 0, f"no grad into {name}"

    def test_moe_with_expert_mesh_full_model(self):
        import jax, jax.numpy as jnp, numpy as np
        from ray_tpu.models import forward, init_params
        from ray_tpu.models.transformer import param_logical_axes
        from ray_tpu.parallel import make_mesh
        from ray_tpu.parallel.sharding import tree_shardings

        cfg = self._cfg()
        params = init_params(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (4, 16), 0,
                                  cfg.vocab_size)
        ref = forward(params, toks, cfg)
        mesh = make_mesh(expert=2, tensor=2, data=2, fsdp=1)
        sh = tree_shardings(mesh, param_logical_axes(cfg))
        ps = jax.device_put(params, sh)
        out = jax.jit(lambda p, t: forward(p, t, cfg, mesh))(ps, toks)
        np.testing.assert_allclose(ref, out, atol=1e-4, rtol=1e-4)
