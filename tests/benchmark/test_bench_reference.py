"""The dense GQA block's plain float32 reference
(`benchmark/architectures/dense_gqa.py`) against the program, at toy size
on the CPU:
`models/transformer.py`'s forward and loss, and prefill-then-decode through
the engine's slot cache. The same comparison runs at published widths on
the chip inside every cell."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths  # noqa: F401
from benchmark.harness import reference, spec
from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.transformer import forward, init_params, loss_fn

DENSE = spec.load_architecture({})   # what a config naming none gets

SHAPES = {
    "gqa": dict(n_heads=4, n_kv_heads=2),
    "mha": dict(n_heads=4, n_kv_heads=None),
    "mqa": dict(n_heads=4, n_kv_heads=1),
    "tied": dict(n_heads=2, n_kv_heads=1, tie_embeddings=True),
}


def _cfg(shape, dtype=jnp.float32, **kw):
    base = dict(vocab_size=96, d_model=32, n_layers=3, d_ff=48,
                max_seq_len=64, rope_theta=1e6, rms_eps=1e-5, dtype=dtype,
                attention_impl="xla", remat=False)
    base.update(SHAPES[shape])
    base.update(kw)
    return TransformerConfig(**base)


def _fields(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                rope_theta=cfg.rope_theta, rms_eps=cfg.rms_eps,
                n_layers=cfg.n_layers, tie_embeddings=cfg.tie_embeddings)


def _tokens(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n, dtype=np.int32)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("seq", [5, 33])
def test_forward_agrees_with_the_reference(shape, seq):
    cfg = _cfg(shape)
    params = init_params(jax.random.key(1), cfg)
    toks = _tokens(cfg, seq)
    got = forward(params, jnp.asarray(toks)[None], cfg)[0]
    want = DENSE.reference_logits(params, toks, _fields(cfg), {})
    res = reference.logits_agree(got, want, "float32")
    assert res["ok"], res


@pytest.mark.parametrize("shape", ["gqa", "tied"])
def test_loss_agrees_with_the_reference(shape):
    cfg = _cfg(shape)
    params = init_params(jax.random.key(2), cfg)
    toks = _tokens(cfg, 41)
    got, _ = loss_fn(params, {"tokens": jnp.asarray(toks)[None]}, cfg)
    want = reference.reference_loss(
        DENSE.reference_logits(params, toks[:-1], _fields(cfg), {}),
        toks[1:])
    assert abs(float(got) - float(want)) <= \
        reference.LOSS_ABS_TOL["float32"]


@pytest.mark.parametrize("last", [1, 2, 7])
def test_last_positions_are_the_tail_of_the_full_logits(last):
    cfg = _cfg("gqa")
    params = init_params(jax.random.key(3), cfg)
    toks = _tokens(cfg, 19)
    full = DENSE.reference_logits(params, toks, _fields(cfg), {})
    tail = DENSE.reference_logits(params, toks, _fields(cfg), {},
                                  last=last)
    np.testing.assert_array_equal(np.asarray(full[-last:]),
                                  np.asarray(tail))


@pytest.mark.parametrize("shape", ["gqa", "mqa"])
def test_bf16_compute_where_float32_is_stated_fails(shape):
    """The tolerance is tight enough to catch a lower precision than the
    configuration states."""
    cfg32, cfg16 = _cfg(shape), _cfg(shape, dtype=jnp.bfloat16)
    params = init_params(jax.random.key(4), cfg32)
    toks = _tokens(cfg32, 33)
    want = DENSE.reference_logits(params, toks, _fields(cfg32), {})
    got16 = forward(params, jnp.asarray(toks)[None], cfg16)[0]
    assert not reference.logits_agree(got16, want, "float32")["ok"]
    # ... while bf16 stated as bf16 passes its own, looser bound
    assert reference.logits_agree(got16, want, "bfloat16")["ok"]


@pytest.mark.parametrize("noise,ok", [(0.0, True), (1e-3, True),
                                      (0.2, False)])
def test_bf16_tolerance_catches_an_eight_bit_sized_error(noise, ok):
    rng = np.random.default_rng(0)
    want = rng.standard_normal((4, 50)).astype(np.float32)
    got = want * (1 + noise * rng.standard_normal(want.shape))
    assert reference.logits_agree(got, want, "bfloat16")["ok"] is ok


@pytest.mark.parametrize("bad", [np.nan, np.inf])
def test_non_finite_logits_never_agree(bad):
    want = np.ones((2, 5), np.float32)
    got = want.copy()
    got[0, 0] = bad
    assert not reference.logits_agree(got, want, "bfloat16")["ok"]


@pytest.mark.parametrize("shape", ["gqa", "mha"])
@pytest.mark.parametrize("lengths", [[9, 16, 3, 12], [16, 16]])
def test_prefill_then_decode_through_the_slot_cache(shape, lengths):
    """The engine's own programs: `prefill_slots` writes left-padded
    prompts into slot rows, one decode step reads them back through the
    cache; both sets of logits against the reference's full forward."""
    from ray_tpu.models.engine import (_decode_one, init_slot_cache,
                                       prefill_slots)
    from ray_tpu.models.generate import _final_logits, _prefill_hidden

    cfg = _cfg(shape)
    fields = _fields(cfg)
    params = init_params(jax.random.key(5), cfg)
    K, P, slots = len(lengths), 16, 6
    prompts = [list(_tokens(cfg, n, seed=i)) for i, n in enumerate(lengths)]
    toks = np.zeros((K, P), np.int32)
    starts = np.zeros(K, np.int32)
    for i, p in enumerate(prompts):
        toks[i, P - len(p):] = p
        starts[i] = P - len(p)
    cache = init_slot_cache(cfg, slots, P + 8)
    rows = jnp.arange(K, dtype=jnp.int32)
    hidden, _ = _prefill_hidden(params, jnp.asarray(toks), cfg, P,
                                jnp.asarray(starts))
    got_pre = _final_logits(params, hidden[:, -1:], cfg)[:, 0]
    cache, first = prefill_slots(params, cache, jnp.asarray(toks), rows,
                                 jnp.asarray(starts), jax.random.key(0),
                                 cfg)
    pending = jnp.zeros(slots, jnp.int32).at[rows].set(first)
    _, got_dec = _decode_one(params, cache, pending, cfg)
    for i, p in enumerate(prompts):
        want = DENSE.reference_logits(
            params, [int(t) for t in p] + [int(first[i])], fields, {},
            last=2)
        assert reference.logits_agree(got_pre[i], want[0], "float32")["ok"]
        assert reference.logits_agree(got_dec[i], want[1], "float32")["ok"]
        assert int(first[i]) == int(np.argmax(np.asarray(want[0])))


# ---- the objective, term by term ---------------------------------------------

_PROGRAM = {"loss": 6.20, "aux": 2.01, "perplexity": 492.7}
_REFERENCE = {"loss": 6.2004, "aux": 2.012}
_WEIGHTS = {"loss": 1.0, "aux": 0.01}


@pytest.mark.parametrize("total,program,want,weights,ok,why", [
    (6.2201, _PROGRAM, _REFERENCE, _WEIGHTS, True, None),
    # no weights stated: the terms are compared and the total only finite
    (99.0, _PROGRAM, _REFERENCE, None, True, None),
    (float("nan"), _PROGRAM, _REFERENCE, None, False, None),
    # a term further from the reference than the bf16 tolerance, 1e-2
    (6.2201, dict(_PROGRAM, aux=2.03), _REFERENCE, None, False, "aux"),
    # a term of the reference that the program does not report
    (6.2, {"loss": 6.20}, _REFERENCE, None, False, "aux"),
    # the total is not the weighted sum of the program's own terms
    (6.2402, _PROGRAM, _REFERENCE, _WEIGHTS, False, "weighted_sum"),
    # a weighted term nobody compares: the reference has to give it
    (6.2201, _PROGRAM, {"loss": 6.2004}, _WEIGHTS, False, "weighted_sum"),
    # ... unless its weight is nought
    (6.20, _PROGRAM, {"loss": 6.2004}, {"loss": 1.0, "aux": 0.0}, True,
     None)])
def test_objective_agrees_term_by_term(total, program, want, weights, ok,
                                       why):
    got = reference.objective_agrees(total, program, want, weights,
                                     "bfloat16")
    assert got["ok"] is ok
    assert sorted(got["terms"]) == sorted(want)
    assert ("weighted_sum" in got) == (weights is not None)
    if why == "aux":
        assert not got["terms"]["aux"]["ok"] and got["terms"]["loss"]["ok"]
    if why == "weighted_sum":
        assert not got["weighted_sum"]["ok"]
        assert all(t["ok"] for t in got["terms"].values())
    for term in got["terms"].values():
        assert term["tolerance"] == reference.LOSS_ABS_TOL["bfloat16"] \
            == 1e-2


def test_a_further_term_takes_its_architectures_limit_the_loss_never():
    """`TERM_ABS_TOL` of an architecture file widens (or narrows) a
    further term's limit; the cross entropy's is `LOSS_ABS_TOL` whatever
    the file says."""
    program = {"loss": 6.25, "aux": 2.10}
    want = {"loss": 6.20, "aux": 2.00}
    own = {"aux": {"bfloat16": 0.25}, "loss": {"bfloat16": 1.0}}
    plain = reference.objective_agrees(6.25, program, want, None, "bfloat16")
    assert not plain["terms"]["aux"]["ok"] and not plain["terms"]["loss"]["ok"]
    got = reference.objective_agrees(6.25, program, want, None, "bfloat16",
                                     own)
    assert got["terms"]["aux"]["ok"] and got["terms"]["aux"][
        "tolerance"] == 0.25
    assert not got["terms"]["loss"]["ok"] and got["terms"]["loss"][
        "tolerance"] == 1e-2
    assert not got["ok"]
    # a limit stated for another dtype only: the table's own
    got = reference.objective_agrees(6.2, {"loss": 6.2, "aux": 2.0}, want,
                                     None, "float32", {"aux": {
                                         "float32": 1e-4, "bfloat16": 0.25}})
    assert got["ok"] and got["terms"]["aux"]["tolerance"] == 1e-4
