"""`serving_params`: a replica holds each weight in the dtype the forward
reads it in, converted once, and the programs then compute what they
computed on the float32 tree (the `.astype(cfg.dtype)` at every use was
the same rounding). In the fast tier: small programs on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import engine as engine_mod
from ray_tpu.models.config import tiny_config
from ray_tpu.models.engine import (InferenceEngine, _decode_one,
                                   init_slot_cache, prefill_slots)
from ray_tpu.models.generate import _final_logits, _prefill_hidden
from ray_tpu.models.transformer import (HEAD_COPY, forward, init_params,
                                        lm_head, read_in_float32,
                                        serving_params, with_head_copy,
                                        without_head_copy)

_MODELS = {
    "untied": dict(),
    "tied": dict(tie_embeddings=True),
    "moe_qk_norm": dict(moe_experts=4, moe_top_k=2, qk_norm=True),
    "moe_tied": dict(moe_experts=4, moe_top_k=2, tie_embeddings=True),
}
# the leaves the forward reads through `.astype(float32)`: the vocabulary
# head (`lm_head`; the embedding table where tied) and `moe.route`'s router
_FLOAT32_LEAVES = {
    "untied": {"lm_head"},
    "tied": {"embed"},
    "moe_qk_norm": {"lm_head", "router"},
    "moe_tied": {"embed", "router"},
}


def _named(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("model", list(_MODELS))
def test_float32_compute_returns_the_tree_untouched(model):
    cfg = tiny_config(**_MODELS[model])  # dtype float32
    params = init_params(jax.random.key(0), cfg)
    held = serving_params(params, cfg)
    assert jax.tree.structure(held) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(params)):
        assert a is b
    assert HEAD_COPY not in held and with_head_copy(params, cfg) is params


@pytest.mark.parametrize("model", list(_MODELS))
def test_bfloat16_compute_holds_each_leaf_as_the_forward_reads_it(model):
    cfg = tiny_config(dtype=jnp.bfloat16, **_MODELS[model])
    params = init_params(jax.random.key(0), cfg)
    held = _named(serving_params(params, cfg))
    given = _named(params)
    copy = held.pop(f"['{HEAD_COPY}']")  # its own test, below
    assert copy.dtype == jnp.bfloat16
    assert set(held) == set(given)
    assert ("['lm_head']" in held) == (not cfg.tie_embeddings)
    assert set(read_in_float32(cfg)) == _FLOAT32_LEAVES[model]
    for name, leaf in held.items():
        if name.split("'")[-2] in _FLOAT32_LEAVES[model]:
            assert leaf is given[name] and leaf.dtype == jnp.float32, name
        else:
            assert leaf.dtype == jnp.bfloat16, name
            assert (leaf == given[name].astype(jnp.bfloat16)).all(), name
    if cfg.moe_experts:
        assert "['layers']['router']" in held
    # held already: nothing to do, nothing copied
    again = serving_params(serving_params(params, cfg), cfg)
    for a, b in zip(jax.tree.leaves(again),
                    jax.tree.leaves(serving_params(params, cfg))):
        assert a.dtype == b.dtype and (a == b).all()
    once = serving_params(params, cfg)
    assert all(a is b for a, b in zip(
        jax.tree.leaves(serving_params(once, cfg)), jax.tree.leaves(once)))


def test_host_arrays_and_traced_trees_are_held_too():
    """A checkpoint arrives as numpy arrays; the replica's random weights
    are made under `jit`: the same tree either way."""
    cfg = tiny_config(dtype=jnp.bfloat16, moe_experts=4)
    params = init_params(jax.random.key(1), cfg)
    want = serving_params(params, cfg)
    from_host = serving_params(jax.tree.map(np.asarray, params), cfg)
    traced = jax.jit(lambda p: serving_params(p, cfg))(params)
    for w, h, t in zip(*(jax.tree.leaves(x)
                         for x in (want, from_host, traced))):
        assert isinstance(h, jax.Array) and h.dtype == w.dtype == t.dtype
        assert (h == w).all() and (t == w).all()


def test_a_tensor_parallel_engine_holds_sharded_leaves_in_the_compute_dtype():
    from ray_tpu.models.transformer import param_logical_axes
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.parallel.sharding import tree_shardings

    cfg = tiny_config(dtype=jnp.bfloat16, moe_experts=4)
    params = init_params(jax.random.key(1), cfg)
    mesh = MeshSpec(data=1, fsdp=1, tensor=2).build(jax.devices()[:2])
    shardings = tree_shardings(mesh, param_logical_axes(cfg))
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=8,
                          max_new_tokens=4, mesh=mesh)
    want = serving_params(params, cfg)
    for leaf, s, w in zip(*(jax.tree.leaves(x) for x in (
            without_head_copy(eng.params), shardings,
            without_head_copy(want)))):
        assert leaf.sharding == s and leaf.dtype == w.dtype
        assert (leaf == w).all()
    assert any(len(leaf.sharding.device_set) == 2 and
               not leaf.sharding.is_fully_replicated
               for leaf in jax.tree.leaves(eng.params))
    # the head's copy lies as the head does: split over its vocabulary
    copy, head = eng.params[HEAD_COPY], eng.params["lm_head"]
    assert copy.sharding == head.sharding == shardings["lm_head"]
    assert not copy.sharding.is_fully_replicated
    assert copy.dtype == jnp.bfloat16 and (copy == want[HEAD_COPY]).all()
    # given the held, placed tree again, an engine places it the same
    again = InferenceEngine(eng.params, cfg, slots=2, max_prompt_len=8,
                            max_new_tokens=4, mesh=mesh)
    assert again.params[HEAD_COPY].sharding == head.sharding
    assert (again.params[HEAD_COPY] == copy).all()


def _prompts(cfg, K, P):
    lengths = [P, 3, 5, 1][:K]
    toks = np.zeros((K, P), np.int32)
    for i, n in enumerate(lengths):
        toks[i, P - n:] = np.random.RandomState(i).randint(
            1, cfg.vocab_size, n)
    return jnp.asarray(toks), jnp.asarray([P - n for n in lengths],
                                          jnp.int32)


@pytest.mark.parametrize("model", list(_MODELS))
def test_programs_on_the_held_tree_equal_those_on_the_float32_tree(
        model, monkeypatch):
    """Hidden states of `_prefill_hidden` and `_decode_one` and the K/V
    they write are bit-equal: rounding a leaf once is what the program's
    own cast did at every use. The logits on the held tree are the head's
    with BOTH operands rounded to bf16 and float32 accumulation: what the
    chip's one bf16 pass has always computed for the float32 product
    `lm_head` writes (on the CPU a float32 matmul is a float32 matmul, so
    the unrounded product differs by the rounding and is no yardstick
    here; `chip_head_copy.py` holds the two equal to the bit on the
    chip). On the float32 leaf alone they are the float32 tree's."""
    cfg = tiny_config(dtype=jnp.bfloat16, **_MODELS[model])
    params = init_params(jax.random.key(0), cfg)
    held = serving_params(params, cfg)
    K, P, S = 4, 8, 16
    toks, starts = _prompts(cfg, K, P)
    rng = jax.random.key(0)

    x32, kv32 = _prefill_hidden(params, toks, cfg, P, starts)
    x16, kv16 = _prefill_hidden(held, toks, cfg, P, starts)
    assert x16.dtype == x32.dtype == jnp.bfloat16
    assert (x16 == x32).all()
    assert (kv16["k"] == kv32["k"]).all() and (kv16["v"] == kv32["v"]).all()

    def decode(tree, hidden: bool):
        cache, first = prefill_slots(
            tree, init_slot_cache(cfg, K, S), toks,
            jnp.arange(K, dtype=jnp.int32), starts, rng, cfg)
        with monkeypatch.context() as m:
            if hidden:  # `_decode_one` (not jitted) with its head taken off
                m.setattr(engine_mod, "_final_logits",
                          lambda params, x, cfg: x)
            cache, out = _decode_one(tree, cache, first, cfg)
        return first, cache, out

    f32, c32, h32 = decode(params, hidden=True)
    f16, c16, h16 = decode(held, hidden=True)
    # the prompt pass reads the float32 leaf, copy or none: its logits,
    # and so its first tokens, are the float32 tree's
    assert (_final_logits(without_head_copy(held), x16[:, -1:], cfg)
            == _final_logits(params, x32[:, -1:], cfg)).all()
    assert (f16 == f32).all()
    # (a copy of NaNs changes nothing: `prefill_slots` does not read it)
    poisoned = dict(held, **{HEAD_COPY: jnp.full_like(held[HEAD_COPY],
                                                      jnp.nan)})
    assert (prefill_slots(poisoned, init_slot_cache(cfg, K, S), toks,
                          jnp.arange(K, dtype=jnp.int32), starts, rng,
                          cfg)[1] == f32).all()
    assert h16.shape == (K, cfg.d_model) and (h16 == h32).all()
    assert (c16["k"] == c32["k"]).all() and (c16["v"] == c32["v"]).all()

    _, _, logits32 = decode(params, hidden=False)
    _, _, logits_leaf = decode(without_head_copy(held), hidden=False)
    assert logits_leaf.dtype == jnp.float32
    assert (logits_leaf == logits32).all()
    _, _, logits_held = decode(held, hidden=False)
    assert logits_held.dtype == jnp.float32
    rounded = jax.tree_util.tree_map_with_path(
        lambda path, x: x.astype(jnp.bfloat16).astype(jnp.float32)
        if path[-1].key in read_in_float32(cfg)[:1] else x, params)
    with jax.default_matmul_precision("float32"):
        want = lm_head(rounded, h32[:, None], cfg)[:, 0]
    # products of two bf16 numbers are exact in float32: only the order
    # of the sum is the backend's
    np.testing.assert_allclose(logits_held, want, rtol=0, atol=2e-6)
    assert (logits_held != logits32).any()     # the rounding is there


@pytest.mark.parametrize("model", list(_MODELS))
def test_the_float32_leaves_are_those_the_forward_reads_in_float32(model):
    """What the rule rests on, leaf by leaf: rounding a leaf to bf16 in
    advance leaves the forward's logits bit-equal if and only if the
    forward reads it through ``.astype(cfg.dtype)``. The leaves it moves
    (on the CPU, where a float32 matmul is one) are exactly those
    `read_in_float32` names; a new leaf read in float32 and not listed
    would be rounded by `serving_params` and fail here."""
    cfg = tiny_config(dtype=jnp.bfloat16, **_MODELS[model])
    params = init_params(jax.random.key(0), cfg)
    # gains are ones at init, which rounding cannot move
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x if x.ndim > 2 or "norm" not in path[-1].key
        else x + 0.1 * jax.random.normal(jax.random.key(7), x.shape),
        params)
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        1, cfg.vocab_size, (2, 8)), jnp.int32)
    want = np.asarray(forward(params, tokens, cfg))
    moved = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        rounded = leaf.astype(jnp.bfloat16).astype(jnp.float32)
        assert (rounded != leaf).any(), path
        one = jax.tree_util.tree_map_with_path(
            lambda p, x: rounded if p == path else x, params)
        if (np.asarray(forward(one, tokens, cfg)) != want).any():
            moved.add(path[-1].key)
    assert moved == set(read_in_float32(cfg)) == _FLOAT32_LEAVES[model]


@pytest.mark.parametrize("model", list(_MODELS))
def test_engine_holds_the_tree_and_serves_the_forwards_greedy_tokens(model):
    """An engine given float32 weights at bf16 compute keeps no float32
    leaf but the named ones and serves what an engine given the held tree
    serves. For the dense blocks that is what
    `test_engine_decode.py::test_decode_chunk_equals_the_full_forward
    [bfloat16]` holds a chunk to: the full forward's argmax wherever its
    top two logits are further apart than the cached path's error (a
    sparse block's bf16 near-ties in routing make that no test of it:
    tests/test_olmoe_reference.py holds it in float32)."""
    cfg = tiny_config(dtype=jnp.bfloat16, **_MODELS[model])
    params = init_params(jax.random.key(0), cfg)
    sizes = dict(slots=4, max_prompt_len=8, max_new_tokens=6, min_bucket=8,
                 decode_chunk=4)
    eng = InferenceEngine(params, cfg, **sizes)
    for name, leaf in _named(eng.params).items():
        want = jnp.float32 if name.split("'")[-2] in \
            _FLOAT32_LEAVES[model] else jnp.bfloat16
        assert leaf.dtype == want, name
    assert HEAD_COPY in eng.params
    prompts = [[int(t) for t in np.random.RandomState(i).randint(
        1, cfg.vocab_size, n)] for i, n in enumerate((8, 3, 5))]
    reqs = [eng.submit(p, 6) for p in prompts]
    while not all(r.done.is_set() for r in reqs):
        assert eng.step()
    served = [list(r.tokens) for r in reqs]
    assert [len(t) for t in served] == [6, 6, 6]
    # given the held tree, the engine copies nothing and serves the same
    held = serving_params(params, cfg)
    eng2 = InferenceEngine(held, cfg, **sizes)
    assert all(a is b for a, b in zip(jax.tree.leaves(eng2.params),
                                      jax.tree.leaves(held)))
    assert [eng2.generate(p, 6) for p in prompts] == served
    if cfg.moe_experts:
        return
    err = 0.02  # the cached bf16 path's logits against the forward's here
    checked = 0
    for p, toks in zip(prompts, served):
        seq = list(p)
        for tok in toks:
            ref = np.asarray(forward(params, jnp.asarray([seq], jnp.int32),
                                     cfg)[0, -1])
            top = np.sort(ref)[-2:]
            if top[1] - top[0] > 6 * err * np.sqrt(np.mean(ref ** 2)):
                assert tok == int(np.argmax(ref)), (seq, tok)
                checked += 1
            seq.append(tok)  # the engine's own token: each step on its own
    assert checked >= 9  # half of the 18 owed


@pytest.mark.parametrize("model", list(_MODELS))
def test_a_bfloat16_tree_carries_the_heads_copy_beside_the_float32_leaf(
        model):
    """`with_head_copy`: the head leaf (`lm_head`; the table where tied)
    rounded ONCE to bf16, in the leaf's own layout, under one name; the
    float32 leaf stays, the same array. A tree that holds the copy
    already comes back as it is."""
    cfg = tiny_config(dtype=jnp.bfloat16, **_MODELS[model])
    params = init_params(jax.random.key(0), cfg)
    held = serving_params(params, cfg)
    name = "embed" if cfg.tie_embeddings else "lm_head"
    assert read_in_float32(cfg)[0] == name
    assert set(held) == set(params) | {HEAD_COPY}
    leaf, copy = held[name], held[HEAD_COPY]
    assert leaf is params[name] and leaf.dtype == jnp.float32
    assert copy.dtype == jnp.bfloat16 and copy.shape == leaf.shape
    assert (copy == leaf.astype(jnp.bfloat16)).all()
    assert (copy.astype(jnp.float32) != leaf).any()
    assert with_head_copy(held, cfg) is held
    assert serving_params(held, cfg)[HEAD_COPY] is copy
    # a train tree holds none and `lm_head` on it is the float32 product
    assert HEAD_COPY not in params


@pytest.mark.parametrize("model", list(_MODELS))
def test_the_step_reads_the_copy_if_the_tree_holds_it(model):
    """By structure, as `ffn_block` asks ``"router" in lp``: the head on a
    tree with the copy does not read the float32 leaf at all (a leaf of
    NaNs changes nothing), and without the copy it reads nothing else.
    A tied table's rows reach the stream from the copy too: the rows
    rounded are the copy's rows."""
    from ray_tpu.models.generate import embed_tokens

    cfg = tiny_config(dtype=jnp.bfloat16, **_MODELS[model])
    held = serving_params(init_params(jax.random.key(0), cfg), cfg)
    name = read_in_float32(cfg)[0]
    x = jax.random.normal(jax.random.key(1), (3, 1, cfg.d_model),
                          jnp.bfloat16)
    want = lm_head(held, x, cfg)
    assert want.dtype == jnp.float32 and np.isfinite(want).all()
    poisoned = dict(held, **{name: jnp.full_like(held[name], jnp.nan)})
    assert (lm_head(poisoned, x, cfg) == want).all()
    assert np.isnan(lm_head(without_head_copy(poisoned), x, cfg)).all()
    tokens = jnp.asarray([[1], [5], [7]], jnp.int32)
    rows = embed_tokens(held, tokens, cfg)
    assert rows.dtype == jnp.bfloat16
    assert (rows == embed_tokens(without_head_copy(held), tokens,
                                 cfg)).all()
    if cfg.tie_embeddings:
        assert (embed_tokens(poisoned, tokens, cfg) == rows).all()


def test_a_scaled_tied_table_is_read_from_the_leaf():
    """`embed_scale` multiplies the float32 row before it is rounded: the
    copy's rows would be rounded first, another number."""
    from ray_tpu.models.generate import embed_tokens

    cfg = tiny_config(dtype=jnp.bfloat16, tie_embeddings=True,
                      embed_scale=12.0)
    params = init_params(jax.random.key(0), cfg)
    held = serving_params(params, cfg)
    tokens = jnp.asarray([[1, 5, 7]], jnp.int32)
    assert (embed_tokens(held, tokens, cfg)
            == embed_tokens(params, tokens, cfg)).all()
    poisoned = dict(held, **{HEAD_COPY: jnp.full_like(held[HEAD_COPY],
                                                      jnp.nan)})
    assert (embed_tokens(poisoned, tokens, cfg)
            == embed_tokens(params, tokens, cfg)).all()
