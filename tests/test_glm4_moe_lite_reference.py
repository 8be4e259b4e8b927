"""The program's GLM-4.7-Flash block (models/transformer.py: latent
attention, a leading dense layer, the multi-token-prediction module;
models/moe.py: sigmoid router with a selection bias, shared expert, one
chip's share of the experts) against the plain reference
(benchmark/architectures/glm4_moe_lite.py: a Python loop over layers and
over the held experts, no sort, no gather, no kernel), on the CPU, float32,
toy widths, seeded random weights.

TOL = 2e-4 relative RMS, the float32 tolerance of the benchmark's own check
(benchmark/harness/reference.py): both sides do the same float32 arithmetic
in another order. A token routed to another expert than the reference's
moves that token's output by order one, so a routing fault cannot hide
under it.

Last: the three accepted configurations' `init_params` trees and `loss_fn`
values at toy size are what they were at the parent commit (golden numbers
taken from the parent, a8c2ccf, before this PR's edit of the model).
"""

import dataclasses
import json
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import spec  # noqa: E402
from ray_tpu.models import moe  # noqa: E402
from ray_tpu.models.config import TransformerConfig  # noqa: E402
from ray_tpu.models.transformer import (forward, init_params,  # noqa: E402
                                        loss_fn, param_logical_axes,
                                        qkv_proj)

TOL = 2e-4
BENCH = spec.load_benchmark()
CONF = spec.load_config(BENCH, "glm-4.7-flash")
ARCH = spec.load_architecture(CONF)
T = 24
# toy SIZES; every RULE stays the config file's (sigmoid, bias, norm,
# 1.8, interleaved rotary pairs, one dense layer, one prediction module)
TOY = dict(vocab_size=96, d_model=32, n_layers=3, n_heads=4, n_kv_heads=4,
           d_ff=24, head_dim=12, v_head_dim=16, rope_head_dim=4,
           q_lora_rank=10, kv_lora_rank=8, moe_experts=16,
           moe_held_experts=4, moe_first_expert=4, moe_top_k=2,
           moe_shared_d_ff=24, moe_dense_d_ff=40)


def _rel_rms(got, want):
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2)
                          / jnp.mean(want ** 2)))


def _setup(seed=0, rows=2, **over):
    fields = dict(ARCH.fields(CONF), **dict(TOY, **over))
    cfg = TransformerConfig(**fields, dtype=jnp.float32,
                            param_dtype=jnp.float32, remat=False,
                            attention_impl="xla", max_seq_len=64)
    params = init_params(jax.random.key(seed), cfg)

    def stir(path, x):
        """Gains that are not all ones and a bias that is not all zeros,
        so that a norm on the wrong axis or a bias left out shows."""
        name = path[-1].key
        # (crc32, not `hash`: a str's hash differs from process to process)
        key = jax.random.fold_in(jax.random.key(seed + 1), zlib.crc32(
            jax.tree_util.keystr(path).encode()) % (2 ** 31))
        if "norm" in name:
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if name == "router_bias":
            return 0.2 * jax.random.normal(key, x.shape)
        return x
    params = jax.tree_util.tree_map_with_path(stir, params)
    tokens = np.asarray(jax.random.randint(
        jax.random.key(seed + 2), (rows, T + 1), 0, cfg.vocab_size))
    return cfg, fields, params, tokens


# ---- the model against the reference -------------------------------------------

def test_logits_loss_and_mtp_loss_agree_with_the_reference():
    cfg, fields, params, tokens = _setup()
    got = forward(params, jnp.asarray(tokens[:, :-1]), cfg)
    total, metrics = loss_fn(params, {"tokens": jnp.asarray(tokens)}, cfg)
    want_loss, want_mtp = [], []
    for r in range(tokens.shape[0]):
        want = ARCH.reference_logits(params, tokens[r, :-1], fields, CONF)
        assert _rel_rms(got[r], want) < TOL
        logz = jax.nn.logsumexp(want, axis=-1)
        want_loss.append(float(jnp.mean(
            logz - want[jnp.arange(T), tokens[r, 1:]])))
        want_mtp.append(ARCH.reference_terms(params, tokens[r], fields,
                                             CONF)["mtp_loss"])
    assert float(metrics["loss"]) == pytest.approx(np.mean(want_loss),
                                                   abs=1e-5)
    assert float(metrics["mtp_loss"]) == pytest.approx(np.mean(want_mtp),
                                                       abs=1e-5)
    # the total is the cross entropy + 0.1 x the module's, nothing else
    # (noaux_tc: the balance statistic is reported and not weighed)
    assert cfg.mtp_weight == CONF["objective"]["mtp_loss"] == 0.1
    assert float(total) == pytest.approx(
        float(metrics["loss"]) + 0.1 * float(metrics["mtp_loss"]), rel=1e-6)
    assert float(metrics["total_loss"]) == float(total)
    assert 0.0 < float(metrics["moe_held_share"]) < 1.0


def test_the_reference_remembers_one_pass_and_only_that_one():
    """`reference_terms` right after `reference_logits` on the same row
    reuses the main stack's hidden state; on another row or other weights
    it computes its own, to the same number."""
    cfg, fields, params, tokens = _setup(seed=3)
    ARCH.reference_logits(params, tokens[0, :-1], fields, CONF)
    warm = ARCH.reference_terms(params, tokens[0], fields, CONF)
    cold = ARCH.reference_terms(params, tokens[0], fields, CONF)
    assert warm == cold
    ARCH.reference_logits(params, tokens[0, :-1], fields, CONF)
    other = ARCH.reference_terms(params, tokens[1], fields, CONF)
    assert other != warm
    assert other == ARCH.reference_terms(params, tokens[1], fields, CONF)


def test_gradients_of_the_total_agree_leaf_by_leaf():
    cfg, fields, params, tokens = _setup(seed=1, rows=1)
    got = jax.grad(lambda p: loss_fn(p, {"tokens": jnp.asarray(tokens)},
                                     cfg)[0])(params)
    want = jax.grad(lambda p: ARCH.reference_objective(
        p, tokens[0], fields, CONF))(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert set(flat_got) == set(flat_want) and len(flat_got) == 52
    for path, g in flat_got.items():
        name = jax.tree_util.keystr(path)
        if path[-1].key == "router_bias":   # takes no gradient
            assert not np.any(np.asarray(g)), name
            assert not np.any(np.asarray(flat_want[path])), name
        else:
            assert np.any(np.asarray(flat_want[path])), name   # a real test
            assert _rel_rms(g, flat_want[path]) < TOL, name


def test_the_tree_is_what_the_counts_and_the_axes_say():
    cfg, fields, params, _ = _setup()
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params == ARCH.num_params(fields, CONF)
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    axes = param_logical_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == \
        jax.tree.structure(jax.tree.map(lambda a: 0, axes, is_leaf=is_axes))
    for leaf, ax in zip(jax.tree.leaves(params),
                        jax.tree.leaves(axes, is_leaf=is_axes)):
        assert leaf.ndim == len(ax)
    # one dense layer of the dense width, then expert layers; the module
    assert params["dense_layers"]["w_gate"].shape == (1, 32, 40)
    assert params["layers"]["w_gate"].shape == (2, 4, 32, 24)   # held
    assert params["layers"]["router"].shape == (2, 32, 16)      # all
    assert params["mtp"]["proj"].shape == (64, 32)
    assert params["mtp"]["layers"]["router_bias"].shape == (1, 16)
    assert "lm_head" not in params["mtp"] and "embed" not in params["mtp"]


# ---- the router ------------------------------------------------------------------

def _route(cfg, bias, n=64, seed=4):
    x = jax.random.normal(jax.random.key(seed), (n, cfg.d_model))
    router = jax.random.normal(jax.random.key(seed + 1),
                               (cfg.d_model, cfg.moe_experts))
    return moe.route(x, router, cfg, bias) + (
        jax.nn.sigmoid(x @ router),)


def test_the_bias_moves_which_experts_are_kept_and_not_their_weights():
    cfg, *_ = _setup()
    E = cfg.moe_experts
    _, w0, i0, scores = _route(cfg, jnp.zeros((E,)))
    bias = jnp.zeros((E,)).at[3].set(10.0)   # expert 3 wins every token
    _, w1, i1, _ = _route(cfg, bias)
    assert bool(jnp.all(jnp.any(i1 == 3, axis=-1)))
    assert not bool(jnp.all(jnp.any(i0 == 3, axis=-1)))
    # the weights are the UNBIASED scores of the kept, over their sum,
    # times the scaling factor: the 10.0 is nowhere in them
    kept = jnp.take_along_axis(scores, i1, axis=-1)
    want = cfg.moe_route_scale * kept / kept.sum(-1, keepdims=True)
    np.testing.assert_allclose(w1, want, rtol=1e-6)
    assert float(w1.max()) < cfg.moe_route_scale


def test_the_kept_weights_sum_to_the_scaling_factor():
    cfg, *_ = _setup()
    bias = 0.3 * jax.random.normal(jax.random.key(9), (cfg.moe_experts,))
    _, w, i, _ = _route(cfg, bias)
    assert w.shape == (64, cfg.moe_top_k) and cfg.moe_route_scale == 1.8
    np.testing.assert_allclose(w.sum(-1), 1.8, rtol=1e-6)
    # a softmax router without the rules is today's: unscaled, from top_k
    plain = dataclasses.replace(cfg, moe_scoring="softmax",
                                moe_route_scale=1.0, moe_select_bias=False)
    probs, w2, _, _ = _route(plain, None)
    np.testing.assert_allclose(w2.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-6)


def test_the_bias_leaf_is_unchanged_by_an_optimiser_step():
    from ray_tpu.models.training import make_optimizer, make_train_step

    cfg, _, params, tokens = _setup(seed=6)
    tx = make_optimizer(1e-2, weight_decay=0.1)
    state = {"step": jnp.zeros((), jnp.int32), "params": params,
             "opt_state": tx.init(params)}
    before = jax.tree.map(np.asarray, params)
    assert np.any(before["layers"]["router_bias"])   # decay would show
    state, metrics = make_train_step(cfg, tx)(
        state, {"tokens": jnp.asarray(tokens)})
    after = state["params"]
    np.testing.assert_array_equal(after["layers"]["router_bias"],
                                  before["layers"]["router_bias"])
    np.testing.assert_array_equal(
        after["mtp"]["layers"]["router_bias"],
        before["mtp"]["layers"]["router_bias"])
    assert np.any(np.asarray(after["layers"]["router"])
                  != before["layers"]["router"])
    assert {"mtp_loss", "moe_held_share", "moe_load_max_over_mean",
            "grad_norm"} <= set(metrics)


# ---- latent attention ------------------------------------------------------------

def test_one_rotary_key_shared_by_the_heads_and_the_rest_not_rotated():
    cfg, _, params, _ = _setup()
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.key(2), (1, 5, cfg.d_model))
    nope = cfg.head_dim - cfg.rope_head_dim
    q0, k0, v0 = qkv_proj(h, lp, cfg, jnp.arange(5))
    q1, k1, v1 = qkv_proj(h, lp, cfg, jnp.arange(5) + 7)
    assert q0.shape == k0.shape == (1, 5, 4, 12)
    assert v0.shape == (1, 5, 4, 16)
    # position reaches only the last `rope_head_dim` columns of q and k
    np.testing.assert_array_equal(q0[..., :nope], q1[..., :nope])
    np.testing.assert_array_equal(k0[..., :nope], k1[..., :nope])
    np.testing.assert_array_equal(v0, v1)
    assert float(jnp.abs(q0[..., nope:] - q1[..., nope:]).max()) > 1e-3
    assert float(jnp.abs(k0[..., nope:] - k1[..., nope:]).max()) > 1e-3
    # ONE rotary key a token: every head carries the same
    for head in range(1, cfg.n_heads):
        np.testing.assert_array_equal(k0[:, :, head, nope:],
                                      k0[:, :, 0, nope:])
    assert float(jnp.abs(k0[:, :, 1, :nope] - k0[:, :, 0, :nope]
                         ).max()) > 1e-3
    # scores are scaled by the WHOLE query/key width, (8 + 4) ** -0.5
    assert cfg.head_dim == 12 and cfg.v_head_dim == 16


@pytest.mark.parametrize("seed", [0, 1])
def test_the_rotary_columns_are_paired_as_the_config_file_says(seed):
    """Latent attention pairs the stored rotary columns (2i, 2i+1): a rule
    of the layer, which the config file states and the reference reads
    there; by halves is another model, and a file that asks for it is
    refused."""
    cfg, fields, params, tokens = _setup(seed=seed, rows=1)
    assert CONF["rope_interleave"] is True
    got = forward(params, jnp.asarray(tokens[:, :-1]), cfg)[0]
    assert _rel_rms(got, ARCH.reference_logits(params, tokens[0, :-1],
                                               fields, CONF)) < TOL
    other = dict(CONF, rope_interleave=False)
    assert _rel_rms(got, ARCH.reference_logits(params, tokens[0, :-1],
                                               fields, other)) > 10 * TOL
    with pytest.raises(ValueError, match="by halves"):
        ARCH.fields(other)


# ---- the share ------------------------------------------------------------------

def _layer_case(pick, seed=0, n=48):
    """One expert layer with a selection bias of +10 on experts ``pick``:
    program output and gradients against the reference's."""
    cfg, fields, params, _ = _setup(seed=seed)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    lp["router_bias"] = jnp.zeros((cfg.moe_experts,)).at[
        jnp.asarray(pick)].set(10.0)
    h = jax.random.normal(jax.random.key(seed + 5), (1, n, cfg.d_model))
    cot = jax.random.normal(jax.random.key(seed + 6), (n, cfg.d_model))

    def program(h, lp):
        y, stats = moe.moe_layer(h, lp, cfg)
        return jnp.sum(y[0] * cot), (y[0], stats)

    def reference(h, lp):
        y = ARCH.expert_ffn_reference(h[0], lp, fields, CONF)
        return jnp.sum(y * cot), y

    (_, (y, stats)), grads = jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True)(h, lp)
    (_, want), want_grads = jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True)(h, lp)
    return cfg, y, stats, grads, want, want_grads


@pytest.mark.parametrize("case,pick,held_share", [
    ("one_held_expert", [5, 0], 0.5),       # 5 is held (4..7), 0 is not
    ("no_held_expert", [0, 9], 0.0),
    ("every_row_live", [4, 7], 1.0),
])
def test_dropless_at_the_corners_of_the_routing(case, pick, held_share):
    """All assignments that can fall on held experts on ONE of them, none
    on any, and all N*k rows of the buffer live: the same program, the
    same shapes, and the reference's numbers, forward and backward."""
    cfg, y, stats, (dh, dlp), want, (want_dh, want_dlp) = _layer_case(pick)
    assert float(stats["held"]) == pytest.approx(held_share)
    assert _rel_rms(y, want) < TOL
    assert _rel_rms(dh, want_dh) < TOL
    for name in dlp:
        if not np.any(np.asarray(want_dlp[name])):   # exact zeros
            assert not np.any(np.asarray(dlp[name])), name
        else:
            assert _rel_rms(dlp[name], want_dlp[name]) < TOL, name
    if case == "no_held_expert":
        assert not np.any(np.asarray(dlp["w_gate"]))
        assert not np.any(np.asarray(dlp["router"]))
        assert float(stats["load"]) == 0.0
    if case == "one_held_expert":   # one group holds every live row
        assert float(stats["load"]) == pytest.approx(cfg.held_experts)
        assert np.any(np.asarray(dlp["w_gate"][1]))
        assert not np.any(np.asarray(dlp["w_gate"][0]))


def test_no_shape_depends_on_the_routing():
    """The step's buffers are [N*k, ...] whatever the routing: one
    compiled program serves every case."""
    cfg, _, params, _ = _setup()
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    layer = jax.jit(lambda h, lp: moe.moe_layer(h, lp, cfg)[0])
    h = jnp.ones((1, 16, cfg.d_model))
    layer(h, lp)
    n = layer._cache_size()
    for pick in ([0, 1], [4, 5], [5, 0]):
        layer(h, dict(lp, router_bias=jnp.zeros((16,)).at[
            jnp.asarray(pick)].set(10.0)))
    assert layer._cache_size() == n == 1


def test_the_shares_add_up_to_the_whole_layer():
    """The test that ties the share to the model: the routed parts that
    the 4 chips' shares give (4 of 16 experts each) plus the shared expert
    counted ONCE are what the uncut reference gives for the whole layer."""
    cfg, fields, params, _ = _setup(seed=2, moe_held_experts=None,
                                    moe_first_expert=0)
    assert cfg.held_experts == 16
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.key(8), (2, 20, cfg.d_model))
    whole = ARCH.expert_ffn_reference(h.reshape(-1, cfg.d_model), lp,
                                      fields, CONF, first=0, held=16)
    shared = moe.swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], cfg)
    routed, held_shares = jnp.zeros_like(h), []
    for first in range(0, 16, 4):
        share = dataclasses.replace(cfg, moe_held_experts=4,
                                    moe_first_expert=first)
        mine = dict(lp, **{k: lp[k][first:first + 4]
                           for k in ("w_gate", "w_up", "w_down")})
        y, stats = moe.moe_layer(h, mine, share)
        routed = routed + (y - shared)
        held_shares.append(float(stats["held"]))
        # each share alone is what the reference gives for that share
        assert _rel_rms(y.reshape(-1, cfg.d_model), ARCH.
                        expert_ffn_reference(
                            h.reshape(-1, cfg.d_model), mine, fields, CONF,
                            first=first, held=4)) < TOL
    assert sum(held_shares) == pytest.approx(1.0)
    assert _rel_rms((routed + shared).reshape(-1, cfg.d_model), whole) < TOL
    # ... and the program holding every expert gives the same
    y_all, stats = moe.moe_layer(h, lp, cfg)
    assert _rel_rms(y_all.reshape(-1, cfg.d_model), whole) < TOL
    assert float(stats["held"]) == 1.0


# ---- what does not serve yet -----------------------------------------------------

@pytest.mark.parametrize("over,names", [
    ({}, ["latent attention", "leading dense layers",
          "multi-token-prediction"]),
    (dict(mtp_layers=0, mtp_weight=0.0, moe_dense_layers=0),
     ["latent attention"]),
])
def test_the_engines_refuse_what_they_cannot_serve(over, names):
    from ray_tpu.models.engine import InferenceEngine, init_slot_cache
    from ray_tpu.models.generate import _prefill_hidden

    cfg, _, params, tokens = _setup(**over)
    for call in (lambda: InferenceEngine(params, cfg, slots=2),
                 lambda: init_slot_cache(cfg, 2, 32),
                 lambda: _prefill_hidden(params, jnp.asarray(tokens[:, :8]),
                                         cfg, 16, jnp.zeros((2,), jnp.int32))):
        with pytest.raises(NotImplementedError) as e:
            call()
        for name in names:
            assert name in str(e.value)
        assert "serving is not implemented" in str(e.value)


def test_the_chip_comparison_runs_at_toy_size():
    """`chip_expert_layer.py --config glm-4.7-flash` (published widths,
    bf16, on the chip) at a toy size here: every check holds, and a fault
    is told."""
    import chip_expert_layer as script

    k = script.kernel(5, shape=(2, 64, 2, 16))
    s = script.share(5, CONF, rows=(1, 32), timed_rows=(1, 32),
                     **script.SHARE_TOY)
    s["top_k"] = script.SHARE_TOY["moe_top_k"]
    checks = script.holds_share(k, s)
    assert all(checks.values()), (checks, k, s)
    assert set(s["cases"]) == set(script.CASES)
    assert s["cases"]["none_held"]["errors"]["d_w_gate"] == 0.0
    bad = json.loads(json.dumps(s))
    bad["cases"]["all_held"]["errors"]["d_rows"] = 0.05
    assert not script.holds_share(k, bad)["all_held:within_tolerance"]


# ---- the accepted configurations are what they were ------------------------------

# `init_params(jax.random.key(11), cfg)` of each accepted configuration at
# a toy size (leaf: shape, sum, sum of magnitudes) and `loss_fn` on 2 x 33
# seeded tokens, computed AT THE PARENT COMMIT (a8c2ccf) by the lines of
# `_golden` below, before this PR's edit of models/.
GOLDEN_SIZES = {
    "internlm2-1.8b": dict(vocab_size=160, d_model=64, n_layers=2,
                           n_heads=4, n_kv_heads=2, d_ff=96),
    "mistral-7b-v0.3": dict(vocab_size=160, d_model=64, n_layers=3,
                            n_heads=8, n_kv_heads=2, d_ff=112),
    "olmoe-1b-7b": dict(vocab_size=160, d_model=64, n_layers=2, n_heads=4,
                        n_kv_heads=4, d_ff=32, moe_experts=8, moe_top_k=2),
}
GOLDEN = json.loads(r'''{"internlm2-1.8b": {"leaves": {"['embed']": [[160, 64], -20.081165313720703,
1032.180419921875], "['final_norm']": [[64], 64.0, 64.0],
"['layers']['attn_norm']": [[2, 64], 128.0, 128.0],
"['layers']['mlp_norm']": [[2, 64], 128.0, 128.0],
"['layers']['w_down']": [[2, 96, 64], -10.240190505981445, 745.48193359375],
"['layers']['w_gate']": [[2, 64, 96], 4.587540626525879,
1243.0987548828125], "['layers']['w_up']": [[2, 64, 96],
-19.130449295043945, 1226.974609375], "['layers']['wk']": [[2, 64, 2, 16],
11.553174018859863, 400.14306640625], "['layers']['wo']": [[2, 4, 16, 64],
-0.08895635604858398, 403.7366027832031], "['layers']['wq']": [[2, 64, 4,
16], -16.870410919189453, 809.03466796875], "['layers']['wv']": [[2, 64, 2,
16], -4.582850933074951, 408.8184814453125], "['lm_head']": [[64, 160],
-6.803068161010742, 1018.2574462890625]},
"metrics": {"loss": 5.843506336212158, "perplexity": 344.98687744140625},
"total": 5.843506336212158},
"mistral-7b-v0.3": {"leaves": {"['embed']": [[160, 64], -20.081165313720703,
1032.180419921875], "['final_norm']": [[64], 64.0, 64.0],
"['layers']['attn_norm']": [[3, 64], 192.0, 192.0],
"['layers']['mlp_norm']": [[3, 64], 192.0, 192.0],
"['layers']['w_down']": [[3, 112, 64], -3.2143445014953613, 1156.990234375],
"['layers']['w_gate']": [[3, 64, 112], -1.9793033599853516,
2159.204833984375], "['layers']['w_up']": [[3, 64, 112], -4.426115989685059,
2153.66064453125], "['layers']['wk']": [[3, 64, 2, 8], 8.963245391845703,
301.49969482421875], "['layers']['wo']": [[3, 8, 8, 64],
-0.7020041942596436, 500.61273193359375], "['layers']['wq']": [[3, 64, 8,
8], -14.27039623260498, 1215.42236328125], "['layers']['wv']": [[3, 64, 2,
8], 0.4912666082382202, 306.49566650390625], "['lm_head']": [[64, 160],
-6.803068161010742, 1018.2574462890625]},
"metrics": {"loss": 5.6429123878479, "perplexity": 282.28363037109375},
"total": 5.6429123878479}, "olmoe-1b-7b": {"leaves": {"['embed']": [[160,
64], -15.341669082641602, 1020.0645751953125], "['final_norm']": [[64],
64.0, 64.0], "['layers']['attn_norm']": [[2, 64], 128.0, 128.0],
"['layers']['k_norm']": [[2, 64], 128.0, 128.0],
"['layers']['mlp_norm']": [[2, 64], 128.0, 128.0],
"['layers']['q_norm']": [[2, 64], 128.0, 128.0],
"['layers']['router']": [[2, 64, 8], 0.1755489706993103,
100.68655395507812], "['layers']['w_down']": [[2, 8, 32, 64],
2.6587986946105957, 1163.4970703125], "['layers']['w_gate']": [[2, 8, 64,
32], 27.488666534423828, 3272.50146484375], "['layers']['w_up']": [[2, 8,
64, 32], -26.763721466064453, 3269.7763671875], "['layers']['wk']": [[2, 64,
4, 16], 21.690227508544922, 807.4439697265625], "['layers']['wo']": [[2, 4,
16, 64], -0.08895635604858398, 403.7366027832031], "['layers']['wq']": [[2,
64, 4, 16], -16.870410919189453, 809.03466796875], "['layers']['wv']": [[2,
64, 4, 16], -14.302366256713867, 813.6290893554688], "['lm_head']": [[64,
160], -8.552083969116211, 1015.6947021484375]},
"metrics": {"loss": 5.499564170837402, "moe_aux": 2.547736167907715,
"moe_load_max_over_mean": 2.8125, "perplexity": 244.58531188964844,
"total_loss": 5.499564170837402}, "total": 5.499564170837402}}''')


def _golden(name):
    conf = spec.load_config(BENCH, name)
    cfg = spec.build_transformer_config(conf, max_seq_len=32,
                                        dtype="float32",
                                        **GOLDEN_SIZES[name])
    p = init_params(jax.random.key(11), cfg)
    leaves = {jax.tree_util.keystr(k): [
        list(v.shape), float(jnp.sum(v.astype(jnp.float32))),
        float(jnp.sum(jnp.abs(v.astype(jnp.float32))))]
        for k, v in jax.tree_util.tree_leaves_with_path(p)}
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, 160, (2, 33), dtype=np.int32))
    total, m = loss_fn(p, {"tokens": toks}, cfg)
    return {"leaves": leaves, "total": float(total),
            "metrics": {k: float(v) for k, v in sorted(m.items())}}


@pytest.mark.parametrize("name", sorted(GOLDEN_SIZES))
def test_an_accepted_configuration_is_what_it_was_at_the_parent(name):
    got, want = _golden(name), GOLDEN[name]
    assert sorted(got["leaves"]) == sorted(want["leaves"])   # the names
    for leaf, (shape, total, mag) in want["leaves"].items():
        g = got["leaves"][leaf]
        assert g[0] == shape, leaf
        assert g[1] == pytest.approx(total, rel=1e-6, abs=1e-6), leaf
        assert g[2] == pytest.approx(mag, rel=1e-6), leaf
    assert sorted(got["metrics"]) == sorted(want["metrics"])
    assert got["total"] == pytest.approx(want["total"], rel=1e-6)
    for k, v in want["metrics"].items():
        assert got["metrics"][k] == pytest.approx(v, rel=1e-6), k


def test_the_new_fields_default_to_todays_block():
    cfg = TransformerConfig()
    assert cfg.head_dim == cfg.v_head_dim == cfg.d_model // cfg.n_heads
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.rope_head_dim,
            cfg.moe_dense_layers, cfg.moe_shared_d_ff, cfg.mtp_layers) == (
        0, 0, 0, 0, 0, 0)
    assert cfg.moe_scoring == "softmax" and not cfg.moe_select_bias
    assert cfg.moe_route_scale == 1.0 and cfg.moe_held_experts is None
    moe_cfg = TransformerConfig(moe_experts=8)
    assert moe_cfg.held_experts == 8
    assert set(init_params(jax.random.key(0), dataclasses.replace(
        moe_cfg, vocab_size=64, d_model=32, n_layers=1, n_heads=2,
        d_ff=16))["layers"]) == {
        "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "router",
        "w_gate", "w_up", "w_down"}
