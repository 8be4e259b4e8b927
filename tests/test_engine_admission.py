"""Which queued prompts share a prefill (toy model, CPU, inline mode): a
group is the oldest queued request and the oldest after it of its own
bucket, so a group pads to its members' bucket and not to a stranger's."""

import jax
import jax.numpy as jnp
import pytest
from test_served_program_goldens import TOYS, spec

from ray_tpu.models.config import tiny_config
from ray_tpu.models.engine import (InferenceEngine, cache_logical_axes,
                                   init_slot_cache, prefill_slots)
from ray_tpu.models.generate import MIXERS
from ray_tpu.models.transformer import init_params, serving_params

SHORT, LONG = [3] * 5, [5] * 40           # buckets 16 and 64


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, init_params(jax.random.key(0), cfg)


def _engine(model, **kw):
    cfg, params = model
    return InferenceEngine(params, cfg, slots=8, max_prompt_len=64,
                           max_new_tokens=4, decode_chunk=2, **kw)


def _drive(eng, reqs, steps=200):
    for _ in range(steps):
        if all(r.done.is_set() for r in reqs):
            return
        eng.step()
    raise AssertionError("requests did not finish")


def _groups(eng):
    """Spy on `_admit_group`: -> the list its calls' (bucket, rids) land
    in."""
    seen, admit = [], eng._admit_group

    def spy(group):
        admit(group)
        seen.append((group[0][1].bucket, [req.rid for _, req in group]))
    eng._admit_group = spy
    return seen


def test_a_group_is_the_oldest_and_the_oldest_of_its_bucket(model):
    eng = _engine(model)
    seen = _groups(eng)
    reqs = [eng.submit(p) for p in
            (LONG, SHORT, SHORT, LONG, SHORT, SHORT, SHORT, LONG)]
    rid = [r.rid for r in reqs]
    _drive(eng, reqs)
    # the oldest leads: the long ones first, two of the three (a group of
    # three is not compiled), then four of the five short ones, then what
    # is left as it came, each group in the order its members came
    assert seen == [(64, [rid[0], rid[3]]),
                    (16, [rid[1], rid[2], rid[4], rid[5]]),
                    (16, [rid[6]]), (64, [rid[7]])]
    assert eng.stats["prefill_padded_tokens"] == 3 * 64 + 5 * 16
    # every layer of this pattern leaves keys and values: every position
    # of a group passes every layer
    assert eng.stats["prefill_layer_tokens"] \
        == eng.stats["prefill_padded_layer_tokens"] \
        == (3 * 64 + 5 * 16) * eng.cfg.n_layers
    assert eng.stats["prefills"] == 8 and not eng._queue.qsize()
    # every answer is what the request gets alone
    alone = _engine(model)
    for req in reqs:
        one = alone.submit(list(req.prompt))
        _drive(alone, [one])
        assert one.tokens == req.tokens


def test_no_more_are_taken_than_slots_are_free(model):
    eng = _engine(model)
    seen = _groups(eng)
    first = [eng.submit(SHORT) for _ in range(6)]
    eng.step()                     # six slots planned, two free
    late = [eng.submit(p) for p in (LONG, SHORT, SHORT, SHORT)]
    eng.step()
    # room for two: the long one leads alone (no other of its bucket),
    # then one short one; two short ones stay queued, in their order
    assert [b for b, _ in seen[-2:]] == [64, 16]
    assert [len(r) for _, r in seen[-2:]] == [1, 1]
    assert [r.rid for r in eng._queue.queue] == [late[2].rid, late[3].rid]
    _drive(eng, first + late)
    assert all(len(r.tokens) == 4 for r in first + late)


def test_the_look_ahead_ends_at_as_many_entries_as_there_are_slots(model):
    eng = _engine(model)
    seen = _groups(eng)
    reqs = [eng.submit(p) for p in [LONG] + [SHORT] * 8 + [LONG]]
    eng.step()
    # the tenth entry is past the eight the first look sees
    assert seen[0] == (64, [reqs[0].rid])
    _drive(eng, reqs)


@pytest.mark.parametrize("name", sorted(TOYS))
def test_the_slot_cache_is_what_the_table_of_mixer_kinds_says(name):
    """A served configuration's cache (toy widths): beside the engine's own
    leaves, the names, shapes and dtypes `init_slot_cache` makes and the
    axes `cache_logical_axes` gives are `generate.MIXERS`' ``leaves`` and
    ``axes`` of the kinds the model has, and a prefill hands every leaf back
    as it took it."""
    cfg = spec.build_transformer_config(
        spec.load_config(spec.load_benchmark(), name), **TOYS[name])
    slots, max_len, (K, P) = 4, 48, (2, 32)
    cache = jax.eval_shape(lambda: init_slot_cache(cfg, slots, max_len))
    kinds = [kind for kind in MIXERS if cfg.layers_of_kind(kind)]
    assert set(kinds) == set(cfg.mixer_period)
    leaves = {leaf: (shape, jnp.dtype(dtype)) for kind in kinds for
              leaf, (shape, dtype) in MIXERS[kind].leaves(
                  cfg, slots, max_len).items()}
    axes = {leaf: ax for kind in kinds
            for leaf, ax in MIXERS[kind].axes.items()}
    own = {"pos": ((slots,), jnp.int32), "start": ((slots,), jnp.int32)}
    if cfg.moe_experts:
        own["moe_counts"] = ((3,), jnp.float32)
    assert {leaf: (x.shape, x.dtype) for leaf, x in cache.items()} \
        == {**leaves, **own}
    assert list(axes) == list(leaves)
    assert cache_logical_axes(cache) \
        == {**axes, **{leaf: (None,) for leaf in own}}
    for leaf, ax in cache_logical_axes(cache).items():
        assert len(ax) == cache[leaf].ndim, leaf
    assert list(cache_logical_axes()) == ["k", "v", "pos", "start"]
    for kind in ("gmu", "cross"):       # they keep a slot nothing
        assert MIXERS[kind].leaves(cfg, slots, max_len) == {} \
            and not MIXERS[kind].axes and MIXERS[kind].land is None
    params = jax.eval_shape(
        lambda k: serving_params(init_params(k, cfg), cfg), jax.random.key(0))
    new, toks = jax.eval_shape(
        lambda p, c, t, s, r: prefill_slots(p, c, t, s, s, r, cfg), params,
        cache, jax.ShapeDtypeStruct((K, P), jnp.int32),
        jax.ShapeDtypeStruct((K,), jnp.int32), jax.random.key(0))
    assert toks.shape == (K,)
    assert {leaf: (x.shape, x.dtype) for leaf, x in new.items()} \
        == {leaf: (x.shape, x.dtype) for leaf, x in cache.items()}
