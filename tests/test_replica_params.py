"""`serve.llm.drawn_serving_params`: a replica's random weights, made a
matrix at a time, are `serving_params(init_params(key(seed), cfg), cfg)` to
the bit on the CPU, and the float32 tree never stands whole: every matrix
leaves its one program already in the dtype the engine holds it in."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.config import TransformerConfig, tiny_config
from ray_tpu.models.transformer import (HEAD_COPY, init_params,
                                        read_in_float32, serving_params)
from ray_tpu.serve import llm

HYBRID = dict(vocab_size=128, d_model=64, n_layers=4, n_heads=4,
              n_kv_heads=2, d_ff=32, dtype=jnp.bfloat16, use_rope=False,
              attn_output_gate=True, kda_allow_neg_eigval=True,
              mixer_period=("attention", "kda", "kda", "kda"), kda_heads=4,
              kda_head_dim=16, kda_gate_rank=16, moe_experts=16,
              moe_top_k=2, moe_held_experts=4, moe_scoring="sigmoid",
              moe_select_bias=True, moe_shared_d_ff=32)
CONFIGS = {
    "dense": lambda: tiny_config(dtype=jnp.bfloat16),
    "dense-tied-float32": lambda: tiny_config(tie_embeddings=True),
    "olmoe-like": lambda: tiny_config(dtype=jnp.bfloat16, moe_experts=4,
                                      qk_norm=True),
    "hybrid-share": lambda: TransformerConfig(**HYBRID),
}


@pytest.mark.parametrize("name", list(CONFIGS))
def test_leaf_by_leaf_equals_the_whole_tree_converted(name):
    cfg = CONFIGS[name]()
    got = llm.drawn_serving_params(cfg, 11)
    want = serving_params(init_params(jax.random.key(11), cfg), cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    in_float32 = read_in_float32(cfg)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        where = jax.tree_util.keystr(path)
        assert a.shape == b.shape and a.dtype == b.dtype, where
        assert a.dtype == (jnp.float32 if path[-1].key in in_float32
                           else cfg.dtype), where
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)), err_msg=where)
    # the parameters, and the head's bf16 copy beside them where there is one
    copy = got.get(HEAD_COPY)
    assert (copy is not None) == (jnp.dtype(cfg.dtype) == jnp.bfloat16)
    assert sum(x.size for x in jax.tree.leaves(got)) == cfg.num_params \
        + (0 if copy is None else copy.size)


@pytest.mark.parametrize("name", list(CONFIGS))
def test_the_drawn_tree_carries_the_copy_the_whole_tree_would(name):
    """One function decides the copy for both makers of a replica's tree
    (`transformer.with_head_copy`): the drawn tree's is the drawn head
    leaf rounded once, the same leaf to the bit as
    `serving_params(init_params(...))`'s, and a float32-compute tree
    carries none from either."""
    cfg = CONFIGS[name]()
    got = llm.drawn_serving_params(cfg, 5)
    want = serving_params(init_params(jax.random.key(5), cfg), cfg)
    if jnp.dtype(cfg.dtype) != jnp.bfloat16:
        assert HEAD_COPY not in got and HEAD_COPY not in want
        return
    head = got[read_in_float32(cfg)[0]]
    copy = got[HEAD_COPY]
    assert head.dtype == jnp.float32 and copy.dtype == jnp.bfloat16
    assert copy.shape == head.shape == want[HEAD_COPY].shape
    np.testing.assert_array_equal(
        np.asarray(copy.astype(jnp.float32)),
        np.asarray(head.astype(jnp.bfloat16).astype(jnp.float32)))
    np.testing.assert_array_equal(
        np.asarray(copy.astype(jnp.float32)),
        np.asarray(want[HEAD_COPY].astype(jnp.float32)))


def test_the_float32_tree_never_stands_whole(monkeypatch):
    """Before each matrix is drawn, the float32 arrays alive are the
    leaves the engine HOLDS in float32 (head, router) and the small gains:
    never a matrix that is held in bf16, let alone all of them. The
    largest float32 buffer the draw ever needs is one leaf's, inside its
    own program."""
    cfg = TransformerConfig(**HYBRID)
    whole = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    tree_f32 = 4 * sum(x.size for x in jax.tree.leaves(whole))
    held_f32 = 4 * sum(
        x.size for path, x in jax.tree_util.tree_leaves_with_path(whole)
        if path[-1].key in read_in_float32(cfg))
    small = 4 * sum(x.size for x in jax.tree.leaves(whole) if x.ndim <= 3
                    and x.size < 4096)
    before = {id(a) for a in jax.live_arrays()}
    seen, made = [], []
    draw = llm._drawn

    def watched(key, shape, scale, made_in, held):
        seen.append(sum(a.nbytes for a in jax.live_arrays()
                        if id(a) not in before and a.dtype == jnp.float32))
        out = draw(key, shape, scale, made_in, held)
        made.append((int(np.prod(shape)), out.dtype))
        return out

    monkeypatch.setattr(llm, "_drawn", watched)
    params = llm.drawn_serving_params(cfg, 3)
    jax.block_until_ready(params)
    assert len(seen) > 30
    assert max(seen) <= held_f32 + small < tree_f32 / 3
    # every matrix left its program in the dtype it is held in: float32
    # for the four routers and the head, bf16 for every other
    assert sorted(dt.name for _, dt in made).count("float32") == 5
    assert {dt.name for _, dt in made} == {"bfloat16", "float32"}


def test_the_initialiser_alone_is_what_it_was():
    """`init_params` without a `normal` of the caller's draws on the spot,
    in `param_dtype`: the trainer's call."""
    cfg = TransformerConfig(**HYBRID)
    a = init_params(jax.random.key(2), cfg)
    assert all(isinstance(x, jax.Array) and x.dtype == cfg.param_dtype
               for x in jax.tree.leaves(a))
