"""The program's MoE block (models/moe.py, the q/k norms of
models/transformer.py) against the plain OLMoE reference
(benchmark/architectures/olmoe.py: a Python loop over layers and over every
expert, no sort, no gather, no grouping), on the CPU, float32, toy widths.

TOL = 2e-4 relative RMS, the float32 tolerance of the benchmark's own check
(benchmark/harness/reference.py): both sides do the same float32 arithmetic
in another order (the program sums a token's k expert outputs, the
reference all E with zeros), 1e-6-class error through a few layers; 2e-4
leaves room for depth and is 50 times under what bf16 anywhere in the
block gives (2^-9 a rounding). A token routed to another expert than the
reference's moves that token's output by order one, so a routing fault
cannot hide under it.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import spec  # noqa: E402
from ray_tpu.models.config import TransformerConfig  # noqa: E402
from ray_tpu.models.transformer import (forward, init_params,  # noqa: E402
                                        loss_fn)

TOL = 2e-4
OLMOE = spec.load_architecture({"architecture": "olmoe"})
T = 24


def _conf(top_k=2, norm_topk=False, aux=False, layers=4):
    """A toy config file in the published keys."""
    return {"vocab_size": 96, "hidden_size": 64, "num_hidden_layers": layers,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "intermediate_size": 32, "rope_theta": 10000,
            "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
            "num_experts": 8, "num_experts_per_tok": top_k,
            "norm_topk_prob": norm_topk, "output_router_logits": aux,
            "router_aux_loss_coef": 0.01, "architecture": "olmoe"}


def _setup(conf, seed=0, skew=0.0, rows=2):
    fields = OLMOE.fields(conf)
    cfg = TransformerConfig(**fields, dtype=jnp.float32,
                            param_dtype=jnp.float32, remat=False,
                            attention_impl="xla", max_seq_len=64)
    params = init_params(jax.random.key(seed), cfg)
    # gains that are not all ones, so that a norm applied to the wrong
    # axis or left out shows
    lay = params["layers"]
    for i, name in enumerate(("q_norm", "k_norm", "attn_norm", "mlp_norm")):
        lay[name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.key(100 + i), lay[name].shape)
    if skew:
        # a component all tokens share, and a router whose expert 0 reads
        # it: most tokens then have expert 0 as their first choice
        params["embed"] = params["embed"] + 1.0
        lay["router"] = lay["router"].at[:, :, 0].add(skew)
    tokens = jax.random.randint(jax.random.key(seed + 1), (rows, T + 1), 0,
                                cfg.vocab_size)
    return cfg, fields, params, tokens


def _rel_rms(got, want):
    got, want = jnp.asarray(got), jnp.asarray(want)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2)
                          / jnp.maximum(jnp.mean(want ** 2), 1e-30)))


def _reference_loss(params, tokens, fields, conf):
    """Cross entropy of the reference (+ its auxiliary loss where the
    configuration trains with it), mean over the rows."""
    from benchmark.harness.reference import reference_loss

    total = 0.0
    for row in tokens:
        logits, aux = OLMOE._forward(params, row[:-1], fields, conf)
        total = total + reference_loss(logits, row[1:]) \
            + fields["moe_aux_weight"] * aux
    return total / len(tokens)


CASES = {"top2": dict(top_k=2), "top8of8": dict(top_k=8),
         "top2_norm_topk": dict(top_k=2, norm_topk=True),
         "top2_aux": dict(top_k=2, aux=True)}


def _rows(case):
    """The program pools the rows of a batch before it multiplies share and
    probability in the auxiliary loss; the reference is per sequence. On
    one row they are the same number, so the case that trains with the
    auxiliary loss compares one row."""
    return 1 if CASES[case].get("aux") else 2


@pytest.mark.parametrize("case", sorted(CASES))
def test_logits_and_loss_agree_with_the_reference(case):
    conf = _conf(**CASES[case])
    cfg, fields, params, tokens = _setup(conf, rows=_rows(case))
    got = forward(params, tokens[:, :-1], cfg)
    for r in range(len(tokens)):
        want = OLMOE.reference_logits(params, tokens[r, :-1], fields, conf)
        assert _rel_rms(got[r], want) < TOL
        tail = OLMOE.reference_logits(params, tokens[r, :-1], fields, conf,
                                      last=5)
        np.testing.assert_array_equal(tail, want[-5:])
    loss = loss_fn(params, {"tokens": tokens}, cfg)[0]
    want_loss = _reference_loss(params, tokens, fields, conf)
    assert abs(float(loss) - float(want_loss)) < TOL * float(want_loss)


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_gradient_agrees_with_the_reference(case):
    conf = _conf(**CASES[case], layers=2)
    cfg, fields, params, tokens = _setup(conf, rows=_rows(case))
    got = jax.grad(lambda p: loss_fn(p, {"tokens": tokens}, cfg)[0])(params)
    want = jax.grad(functools.partial(_reference_loss, tokens=tokens,
                                      fields=fields, conf=conf))(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert set(flat_got) == set(flat_want) and len(flat_got) == 15
    for path, w in flat_want.items():
        assert float(jnp.abs(w).max()) > 0, path   # the gradient is there
        assert _rel_rms(flat_got[path], w) < TOL, (path, case)


def test_auxiliary_loss_and_its_gradient_into_the_router():
    conf = _conf(top_k=2, aux=True, layers=2)
    cfg, fields, params, tokens = _setup(conf)
    assert fields["moe_aux_weight"] == 0.01
    assert OLMOE.fields(_conf())["moe_aux_weight"] == 0.0

    router = params["layers"]["router"]
    # the program pools the rows of a batch before it multiplies share and
    # probability, the reference is per sequence: equal on one row
    one = tokens[:1]
    got1 = loss_fn(params, {"tokens": one}, cfg)[1]["moe_aux"]
    want1 = OLMOE.reference_aux_loss(params, one[0, :-1], fields, conf)
    assert abs(float(got1) - float(want1)) < TOL * float(want1)
    assert 1.9 < float(got1) < 8.0     # k at balance, E at collapse
    g1 = jax.grad(lambda r: loss_fn(
        dict(params, layers=dict(params["layers"], router=r)),
        {"tokens": one}, cfg)[1]["moe_aux"])(router)
    g1_want = jax.grad(lambda r: OLMOE.reference_aux_loss(
        dict(params, layers=dict(params["layers"], router=r)),
        one[0, :-1], fields, conf))(router)
    assert _rel_rms(g1, g1_want) < TOL


@pytest.mark.parametrize("top_k,least_share", [(1, 0.5), (2, 0.375)])
def test_dropless_under_a_router_skewed_to_one_expert(top_k, least_share):
    """One expert of 8 takes over half of all assignments at top 1, and
    over three quarters of the half it can take at most at top 2 (a token
    picks an expert once): a capacity-bound dispatch (1.25 x the mean
    group) would drop most of them, this one none, so logits and
    gradients still equal the reference's."""
    conf = _conf(top_k=top_k, layers=2)
    cfg, fields, params, tokens = _setup(conf, skew=0.01)
    (_, metrics), got = jax.value_and_grad(
        lambda p: loss_fn(p, {"tokens": tokens}, cfg), has_aux=True)(params)
    load = float(metrics["moe_load_max_over_mean"])   # largest / mean group
    assert least_share * 8 < load <= 8.0 / top_k, load
    logits = forward(params, tokens[:, :-1], cfg)
    for r in range(len(tokens)):
        want = OLMOE.reference_logits(params, tokens[r, :-1], fields, conf)
        assert _rel_rms(logits[r], want) < TOL
    want = jax.grad(functools.partial(_reference_loss, tokens=tokens,
                                      fields=fields, conf=conf))(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert _rel_rms(g, w) < TOL, path


def test_expert_layer_alone_and_the_routing_choices():
    """`moe_layer` against the reference's expert loop on the same rows:
    the layer's output, and the experts each token was given."""
    from ray_tpu.models.moe import moe_layer

    conf = _conf(top_k=2, layers=1)
    cfg, fields, params, _ = _setup(conf)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.key(7), (2, T, cfg.d_model))
    got, stats = moe_layer(h, lp, cfg)
    want, aux, keep = OLMOE.moe_ffn_reference(h.reshape(-1, cfg.d_model),
                                              lp, fields, conf)
    assert _rel_rms(got.reshape(want.shape), want) < TOL
    assert abs(float(stats["aux"]) - float(aux)) < TOL * float(aux)
    assert keep.shape == (2 * T, 8) and bool((keep.sum(-1) == 2).all())
    per_expert = keep.sum(0)
    assert float(stats["load"]) == pytest.approx(
        float(per_expert.max()) * 8 / (2 * T * 2))


def test_no_buffer_grows_with_experts_times_capacity():
    """The dispatch holds [N*k, ...] rows and [N, E] router tensors and
    nothing of the old [B, T*k, E, C] kind: no intermediate of the jaxpr
    has more elements than the routed rows times the model width."""
    from ray_tpu.models.moe import moe_layer

    conf = _conf(top_k=2, layers=1)
    cfg, _, params, _ = _setup(conf)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    h = jnp.zeros((2, T, cfg.d_model))
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda h, lp: moe_layer(h, lp, cfg)[0].sum(),
                 argnums=(0, 1)))(h, lp)
    n_k = 2 * T * cfg.moe_top_k
    biggest = max(cfg.moe_experts * cfg.d_model * cfg.d_ff,
                  n_k * cfg.d_model)

    def walk(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                yield eqn.primitive.name, v.aval.shape
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    seen = list(walk(jaxpr.jaxpr))
    assert any(name == "ragged_dot_general" for name, _ in seen)
    for name, shape in seen:
        assert int(np.prod(shape, dtype=np.int64)) <= biggest, (name, shape)


@pytest.mark.parametrize("qk_norm", [True, False])
def test_prefill_and_decode_agree_with_the_reference_forward(qk_norm):
    """`qkv_proj` is the one definition the cache paths share: prefill on
    a prompt and four decode steps through the slot cache give the logits
    of the reference's full forward at those positions. Without the norms
    (the same weights) they must NOT agree: the norms are in effect."""
    from ray_tpu.models.engine import (_decode_one, init_slot_cache,
                                       prefill_slots)
    from ray_tpu.models.generate import _final_logits, _prefill_hidden

    conf = _conf(top_k=2, layers=2)
    cfg, fields, params, tokens = _setup(conf)
    if not qk_norm:
        import dataclasses

        cfg = dataclasses.replace(cfg, qk_norm=False)
    row = tokens[0, :16]
    want = OLMOE.reference_logits(params, row, fields, conf)
    P = 12
    slot, start = jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32)
    hidden, _ = _prefill_hidden(params, row[None, :P], cfg, P, start)
    errs = [_rel_rms(_final_logits(params, hidden, cfg)[0], want[:P])]
    cache, _ = prefill_slots(params, init_slot_cache(cfg, 1, 32),
                             row[None, :P], slot, start, jax.random.key(0),
                             cfg)
    for t in range(P, 16):
        cache, logits = _decode_one(params, cache, row[None, t], cfg)
        errs.append(_rel_rms(logits[0], want[t]))
    if qk_norm:
        assert max(errs) < TOL, errs
    else:
        assert min(errs) > 100 * TOL, errs


@pytest.mark.parametrize("layers,total,active", [(16, 6.92e9, 1.28e9),
                                                 (3, 1.46e9, None)])
def test_num_params_counts_experts_router_and_norms(layers, total, active):
    """TransformerConfig.num_params against the architecture file's count
    and the published sizes (6.92 B held, 1.28 B active at 16 layers)."""
    conf = dict(_conf(), vocab_size=50304, hidden_size=2048,
                num_hidden_layers=layers, num_attention_heads=16,
                num_key_value_heads=16, intermediate_size=1024,
                num_experts=64, num_experts_per_tok=8)
    fields = OLMOE.fields(conf)
    cfg = TransformerConfig(**fields)
    assert cfg.num_params == OLMOE.num_params(fields, conf)
    assert abs(cfg.num_params - total) < 0.01 * total
    if active:
        got = OLMOE.active_params(fields, conf)
        assert abs(got - active) < 0.01 * active
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    assert cfg.num_params == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def test_train_step_reports_the_moe_counters_beside_the_loss():
    """Through `make_train_step`, the trainer's normal path: the step's
    metrics carry `moe_aux` and `moe_load_max_over_mean` (what a user's
    loop hands to `train.report`), and the loss falls."""
    from ray_tpu.models.training import (init_train_state, make_optimizer,
                                         make_train_step)

    conf = _conf(top_k=2, aux=True, layers=2)
    cfg, _, _, tokens = _setup(conf)
    tx = make_optimizer(1e-2)
    state = init_train_state(jax.random.key(0), cfg, tx)
    step = make_train_step(cfg, tx)
    losses = []
    for _ in range(5):
        state, metrics = step(state, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert 1.9 < float(metrics["moe_aux"]) < 8.0
    assert 1.0 <= float(metrics["moe_load_max_over_mean"]) <= 4.0
    assert float(metrics["total_loss"]) > float(metrics["loss"])


def test_the_chip_comparison_of_the_expert_layer_runs_at_toy_size():
    """`chip_expert_layer.py --config olmoe-1b-7b` (published widths, bf16, on the
    chip) at a toy size here: its three checks hold, and rounding the
    layer's inputs to 8-bit floats is told apart from bf16."""
    import chip_expert_layer as script

    conf = spec.load_config(spec.load_benchmark(), "olmoe-1b-7b")
    r = script.compare(5, conf, shape=(1, 32), **script.TOY)
    assert (r["rows"], r["d_model"], r["experts"], r["top_k"]) == (32, 64, 8, 2)
    assert all(script.holds(r).values()), r
    assert r["rel_rms_error"] < script.TOL < r[
        "rel_rms_error_inputs_rounded_to_fp8"]
    assert r["aux_program"] == pytest.approx(r["aux_reference"], rel=1e-5)
    bad = dict(r, tokens_whose_experts_differ=1)
    assert not script.holds(bad)["no_token_routed_differently"]
