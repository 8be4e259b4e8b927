"""The program's Kimi-Linear block (models/transformer.py: a period of
mixer kinds, the KDA mixer over ops/kda.py's chunked scan, latent attention
without a query latent and without positions; models/moe.py: sigmoid top 8
of a router 4 times the held width, shared expert) against the plain
reference (benchmark/architectures/kimi_linear.py: a Python loop over
layers, the KDA state walked a TOKEN at a time, the experts a loop over the
held ones), on the CPU, float32, toy widths, seeded random weights.

TOL = 2e-4 relative RMS, the float32 tolerance of the benchmark's own check
(benchmark/harness/reference.py): both sides do the same float32 arithmetic
in another order (a chunk's triangular system and cumulated decays against
single steps). A wrong rule moves the logits by order one.

Last: GLM-4.7-Flash's `init_params` tree and `loss_fn` values at toy size
are what they were at the parent commit (a79edfc; the three older
configurations' are held by tests/test_glm4_moe_lite_reference.py).
"""

import copy
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
from ray_tpu.models import moe, transformer  # noqa: E402
from ray_tpu.models.config import TransformerConfig  # noqa: E402
from ray_tpu.models.transformer import (forward, init_params,  # noqa: E402
                                        loss_fn, param_logical_axes,
                                        qkv_proj, refuse_unserved)

TOL = 2e-4
BENCH = spec.load_benchmark()
CONF = spec.load_config(BENCH, "kimi-linear-48b-a3b")
ARCH = spec.load_architecture(CONF)
T = 72     # a whole chunk of 64 and a part of one
# toy SIZES; every RULE stays the config file's (the published layer
# lists, sigmoid + bias, renormalised, x 2.446, no rotation, one dense
# layer). The router is 4 times as wide as the share held, 8 a token.
TOY = dict(vocab_size=96, d_model=32, n_layers=5, n_heads=4, n_kv_heads=4,
           d_ff=24, nope_head_dim=8, rope_head_dim=4, v_head_dim=8,
           kv_lora_rank=8, kda_heads=4, kda_head_dim=8, kda_gate_rank=6,
           moe_experts=32, moe_held_experts=8, moe_first_expert=8,
           moe_top_k=8, moe_shared_d_ff=24, moe_dense_d_ff=40)


def _rel_rms(got, want):
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2)
                          / jnp.mean(want ** 2)))


def _only(kind, n_layers):
    """The config file with every layer of one kind."""
    conf = copy.deepcopy(CONF)
    layers = list(range(1, n_layers + 1))
    conf["linear_attn_config"].update(
        kda_layers=layers if kind == "kda" else [],
        full_attn_layers=layers if kind == "attention" else [])
    conf["num_hidden_layers"] = n_layers
    return conf


def _setup(seed=0, rows=2, conf=CONF, seq=T, **over):
    fields = dict(ARCH.fields(conf), **dict(TOY, **over))
    cfg = TransformerConfig(**fields, dtype=jnp.float32,
                            param_dtype=jnp.float32, remat=False,
                            attention_impl="xla", max_seq_len=128)
    params = init_params(jax.random.key(seed), cfg)

    def stir(path, x):
        """Gains that are not all ones and a bias that is not all zeros,
        so that a norm on the wrong axis or a bias left out shows."""
        name = path[-1].key
        key = jax.random.fold_in(jax.random.key(seed + 1), zlib.crc32(
            jax.tree_util.keystr(path).encode()) % (2 ** 31))
        if "norm" in name:
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if name == "router_bias":
            return 0.2 * jax.random.normal(key, x.shape)
        return x
    params = jax.tree_util.tree_map_with_path(stir, params)
    tokens = np.asarray(jax.random.randint(
        jax.random.key(seed + 2), (rows, seq + 1), 0, cfg.vocab_size))
    return cfg, fields, params, tokens


def _reference_loss(params, row, fields, conf):
    want = ARCH.reference_logits(params, row[:-1], fields, conf)
    logz = jax.nn.logsumexp(want, axis=-1)
    return want, jnp.mean(logz - want[jnp.arange(len(row) - 1),
                                      jnp.asarray(row[1:])])


# ---- the model against the reference -------------------------------------------

def test_logits_and_loss_of_a_dense_layer_and_a_period_agree():
    cfg, fields, params, tokens = _setup()
    assert cfg.mixer_period == ("kda", "kda", "kda", "attention")
    assert [cfg.mixer_kind(i) for i in range(5)] == ARCH.layer_kinds(
        CONF, 5) == ["kda", "kda", "kda", "attention", "kda"]
    # the leading layer is the dense one AND a KDA one; the expert stack
    # after it starts KDA, KDA, MLA, KDA: one stack a position
    assert "kda_wq" in params["dense_layers"] \
        and "router" not in params["dense_layers"]
    assert ["kda_wq" in lay for lay in params["layers"]] == [
        True, True, False, True]
    assert all("router" in lay for lay in params["layers"])
    got = forward(params, jnp.asarray(tokens[:, :-1]), cfg)
    total, metrics = loss_fn(params, {"tokens": jnp.asarray(tokens)}, cfg)
    losses = []
    for r in range(tokens.shape[0]):
        want, loss = _reference_loss(params, tokens[r], fields, CONF)
        assert _rel_rms(got[r], want) < TOL
        losses.append(float(loss))
    assert float(metrics["loss"]) == pytest.approx(np.mean(losses),
                                                   abs=1e-5)
    # the objective is the cross entropy alone
    assert CONF["objective"] == {"loss": 1.0} and cfg.moe_aux_weight == 0.0
    assert float(total) == float(metrics["loss"]) \
        == float(metrics["total_loss"])
    assert 0.0 < float(metrics["moe_held_share"]) < 1.0


@pytest.mark.parametrize("layers", [9, 13])
def test_deeper_stacks_scan_whole_periods(layers):
    cfg, fields, params, tokens = _setup(seed=4, rows=1, seq=24,
                                         n_layers=layers)
    assert all(jax.tree.leaves(lay)[0].shape[0] == (layers - 1) // 4
               for lay in params["layers"])
    got = forward(params, jnp.asarray(tokens[:, :-1]), cfg, return_aux=True)
    want = ARCH.reference_logits(params, tokens[0, :-1], fields, CONF)
    assert _rel_rms(got[0][0], want) < TOL
    with pytest.raises(ValueError, match="whole periods"):
        dataclasses.replace(cfg, n_layers=layers + 1)


def test_rematerialised_and_plain_blocks_give_the_same_gradients():
    cfg, _, params, tokens = _setup(seed=6, rows=1, seq=24)
    batch = {"tokens": jnp.asarray(tokens)}
    plain = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(params)
    remat = jax.grad(lambda p: loss_fn(p, batch, dataclasses.replace(
        cfg, remat=True))[0])(params)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(remat)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


def test_gradients_of_the_loss_agree_leaf_by_leaf():
    cfg, fields, params, tokens = _setup(seed=1, rows=1, seq=40)
    got = jax.grad(lambda p: loss_fn(p, {"tokens": jnp.asarray(tokens)},
                                     cfg)[0])(params)
    want = jax.grad(lambda p: _reference_loss(p, tokens[0], fields,
                                              CONF)[1])(params)
    flat = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b) in zip(flat(got), flat(want)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):     # takes no gradient
            assert float(jnp.abs(a).max()) == 0.0, name
            continue
        scale = max(float(jnp.abs(b).max()), 1e-6)
        assert float(jnp.abs(a - b).max()) < 2e-3 * scale, name


# ---- latent attention without a query latent and without positions -------------

def test_nope_latent_attention_alone_agrees_with_the_reference():
    conf = _only("attention", 3)
    cfg, fields, params, tokens = _setup(seed=2, conf=conf, n_layers=3,
                                         seq=40)
    assert cfg.mixer_period == ("attention",) and not cfg.use_rope
    assert (cfg.head_dim, cfg.v_head_dim, cfg.q_lora_rank) == (12, 8, 0)
    lay = params["layers"]                        # one kind: a plain stack
    assert lay["wq"].shape == (2, 32, 4, 12) and "wq_a" not in lay
    assert lay["wkv_b"].shape == (2, 8, 4, 8 + 8)
    assert lay["wo"].shape == (2, 4, 8, 32)
    got = forward(params, jnp.asarray(tokens[:, :-1]), cfg)
    for r in range(tokens.shape[0]):
        want = ARCH.reference_logits(params, tokens[r, :-1], fields, conf)
        assert _rel_rms(got[r], want) < TOL


def test_one_unrotated_key_vector_a_token_shared_by_the_heads():
    cfg, _, params, _ = _setup(seed=3, conf=_only("attention", 3),
                               n_layers=3)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.key(7), (2, 10, cfg.d_model))
    q, k, v = qkv_proj(h, lp, cfg, jnp.arange(10))
    later = qkv_proj(h, lp, cfg, jnp.arange(10) + 1000)
    for a, b in zip((q, k, v), later):            # no position enters
        np.testing.assert_array_equal(a, b)
    assert q.shape == k.shape == (2, 10, 4, 12) and v.shape == (2, 10, 4, 8)
    shared = jnp.einsum("btd,dr->btr", h, lp["wkv_a"][:, 8:])
    for head in range(4):
        np.testing.assert_allclose(k[:, :, head, 8:], shared, rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(
        q, jnp.einsum("btd,dhk->bthk", h, lp["wq"]), rtol=1e-5, atol=1e-6)


def test_the_attention_kernel_takes_the_narrower_value_head_padded():
    """Off the XLA path a value head narrower than a query/key head is
    filled with zero columns outside the kernel and the result cut back:
    the Pallas kernel (interpreted here) gives what plain attention gives."""
    cfg, _, _, _ = _setup(conf=_only("attention", 3), n_layers=3)
    ks = jax.random.split(jax.random.key(5), 3)
    q, k = (jax.random.normal(kk, (1, 16, 4, 12)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (1, 16, 4, 8))
    want = transformer._attention(q, k, v, cfg, None, None)
    got = transformer._attention(
        q, k, v, dataclasses.replace(cfg, attention_impl="pallas"), None,
        None)
    assert got.shape == want.shape == (1, 16, 4, 8)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# ---- a wrong rule is told --------------------------------------------------------

def _scalar_decay(monkeypatch):
    from ray_tpu.ops import kda

    real = kda.kda_scan

    def scan(q, k, v, g, beta, **kw):      # one decay a head, not a channel
        return real(q, k, v, jnp.broadcast_to(
            g.mean(-1, keepdims=True), g.shape), beta, **kw)
    monkeypatch.setattr(kda, "kda_scan", scan)
    return {}


def _no_short_conv(monkeypatch):
    monkeypatch.setattr(transformer, "_conv_silu",
                        lambda x, w: jax.nn.silu(x.astype(jnp.float32)))
    return {}


def _beta_left_out(monkeypatch):
    from ray_tpu.ops import kda

    real = kda.kda_scan
    monkeypatch.setattr(kda, "kda_scan", lambda q, k, v, g, beta, **kw:
                        real(q, k, v, g, jnp.ones_like(beta), **kw))
    return {}


@pytest.mark.parametrize("rule,edit", [
    ("the key vector rotated", lambda mp: {"use_rope": True}),
    ("a softmax router", lambda mp: {"moe_scoring": "softmax"}),
    ("no renormalisation", lambda mp: {"moe_norm_topk": False}),
    ("no scaling factor", lambda mp: {"moe_route_scale": 1.0}),
    ("a scalar decay a head", _scalar_decay),
    ("no short convolution", _no_short_conv),
    ("every write at full strength", _beta_left_out),
    ("attention in every layer", lambda mp: {"mixer_period": ("attention",),
                                             "moe_dense_layers": 0}),
])
def test_a_rule_computed_otherwise_does_not_agree(rule, edit, monkeypatch):
    good = _setup(seed=5, rows=1)
    cfg, fields, params, tokens = good
    want = ARCH.reference_logits(params, tokens[0, :-1], fields, CONF)
    assert _rel_rms(forward(params, jnp.asarray(tokens[:, :-1]), cfg)[0],
                    want) < TOL
    over = edit(monkeypatch)
    if "mixer_period" in over:      # another tree: its own weights
        cfg, _, params, _ = _setup(seed=5, rows=1, **over)
    else:
        cfg = dataclasses.replace(cfg, **over)
    bad = forward(params, jnp.asarray(tokens[:, :-1]), cfg)[0]
    assert _rel_rms(bad, want) > 50 * TOL, rule


# ---- the tree is what the counts and the axes say ---------------------------------

@pytest.mark.parametrize("case,conf,over", [
    ("a dense layer and a period", CONF, {}),
    ("two periods", CONF, {"n_layers": 9}),
    ("kda alone", _only("kda", 3), {"n_layers": 3}),
    ("latent attention alone", _only("attention", 3), {"n_layers": 3}),
    ("no dense layer", _only("kda", 2), {"n_layers": 2,
                                           "moe_dense_layers": 0}),
])
def test_num_params_and_axes_are_the_leaves_of_init_params(case, conf, over):
    cfg, fields, params, _ = _setup(conf=conf, **over)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert cfg.num_params == n
    if fields["moe_dense_layers"]:
        assert ARCH.num_params(fields, conf) == n
    is_axes = lambda x: isinstance(x, tuple) and all(   # noqa: E731
        a is None or isinstance(a, str) for a in x)
    axes = param_logical_axes(cfg)
    flat = jax.tree_util.tree_flatten_with_path
    named = dict(flat(axes, is_leaf=is_axes)[0])
    assert set(named) == {p for p, _ in flat(params)[0]}
    for path, leaf in flat(params)[0]:
        assert len(named[path]) == leaf.ndim, jax.tree_util.keystr(path)


def test_seeded_decays_spread_over_the_unit_interval():
    """`A_log` and `dt_bias` as drawn: across heads and channels a token's
    decay exp(g) runs from strong to next to none."""
    cfg, _, params, tokens = _setup(seed=8, conf=_only("kda", 3),
                                    n_layers=3, kda_heads=8, kda_head_dim=16)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    a_log, dt = lp["kda_A_log"], lp["kda_dt_bias"]
    assert a_log.shape == (8,) and dt.shape == (8, 16)
    assert 0.0 <= float(a_log.min()) and float(a_log.max()) < np.log(16.0)
    soft = jax.nn.softplus(dt)
    assert 0.001 <= float(soft.min()) and float(soft.max()) < 0.1001
    h = jax.random.normal(jax.random.key(1), (1, 64, cfg.d_model))
    logits = jnp.einsum("btr,rhk->bthk", h @ lp["kda_f_a"], lp["kda_f_b"])
    alpha = jnp.exp(-jnp.exp(a_log)[:, None] * jax.nn.softplus(logits + dt))
    assert float(alpha.min()) < 0.2 and float(alpha.max()) > 0.995


# ---- the share tied to the model ----------------------------------------------------

def test_the_shares_add_up_to_the_whole_layer():
    """Top 8 of a router 4 times the held width, renormalised, x 2.446,
    one shared expert: the routed parts that the 4 chips' shares give (8
    of 32 experts each) plus the shared expert counted ONCE are what the
    uncut reference gives for the whole layer."""
    cfg, fields, params, _ = _setup(seed=2, moe_held_experts=None,
                                    moe_first_expert=0)
    assert (cfg.held_experts, cfg.moe_top_k, cfg.moe_route_scale) == (
        32, 8, 2.446)
    lp = jax.tree.map(lambda a: a[0], params["layers"][0])
    h = jax.random.normal(jax.random.key(8), (2, 20, cfg.d_model))
    rows = h.reshape(-1, cfg.d_model)
    whole = ARCH.expert_ffn_reference(rows, lp, fields, CONF, first=0,
                                      held=32)
    shared = moe.swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], cfg)
    routed, held_shares = jnp.zeros_like(h), []
    for first in range(0, 32, 8):
        share = dataclasses.replace(cfg, moe_held_experts=8,
                                    moe_first_expert=first)
        mine = dict(lp, **{k: lp[k][first:first + 8]
                           for k in ("w_gate", "w_up", "w_down")})
        y, stats = moe.moe_layer(h, mine, share)
        routed = routed + (y - shared)
        held_shares.append(float(stats["held"]))
        assert _rel_rms(y.reshape(rows.shape), ARCH.expert_ffn_reference(
            rows, mine, fields, CONF, first=first, held=8)) < TOL
    assert sum(held_shares) == pytest.approx(1.0)
    assert _rel_rms((routed + shared).reshape(rows.shape), whole) < TOL
    y_all, stats = moe.moe_layer(h, lp, cfg)
    assert _rel_rms(y_all.reshape(rows.shape), whole) < TOL
    assert float(stats["held"]) == 1.0


# ---- what does not serve yet, what does not go together -----------------------------

@pytest.mark.parametrize("conf,over,names", [
    (CONF, {}, ["latent attention", "leading dense layers"]),
    (_only("kda", 2), {"n_layers": 2, "moe_dense_layers": 0},
     ["latent attention"]),
])
def test_the_engines_refuse_what_they_cannot_serve(conf, over, names):
    """``names``: EVERY reason the refusal gives, no more (a period of
    mixer kinds and a KDA layer's state are served since PR 42;
    tests/test_solar_open2_reference.py holds that no refusal names them)."""
    from ray_tpu.models.engine import init_slot_cache

    cfg, _, _, _ = _setup(conf=conf, **over)
    for call in (lambda: refuse_unserved(cfg),
                 lambda: init_slot_cache(cfg, 2, 32)):
        with pytest.raises(NotImplementedError) as e:
            call()
        for name in names:
            assert name in str(e.value)
        assert str(e.value).count(";") == len(names) - 1
        assert "serving is not implemented" in str(e.value)
    if len(cfg.mixer_period) == 1:
        assert "a period of mixer kinds" not in str(e.value)


@pytest.mark.parametrize("why,fields", [
    ("mixer_period", {"mixer_period": ("kda", "window")}),
    ("mixer_period", {"mixer_period": ()}),
    ("kda_heads", {"mixer_period": ("kda",)}),
    ("causal", {"mixer_period": ("kda",), "kda_heads": 2, "kda_head_dim": 8,
                "kda_gate_rank": 4, "causal": False}),
    ("whole periods", {"mixer_period": ("kda", "attention"), "n_layers": 3,
                       "kda_heads": 2, "kda_head_dim": 8,
                       "kda_gate_rank": 4}),
    ("nope_head_dim", {"nope_head_dim": 8}),
    ("nope_head_dim", {"nope_head_dim": 8, "rope_head_dim": 4,
                       "kv_lora_rank": 8, "head_dim": 16}),
    ("rope_head_dim", {"kv_lora_rank": 8}),
])
def test_what_does_not_go_together_is_refused(why, fields):
    with pytest.raises(ValueError, match=why):
        TransformerConfig(**fields)


def test_the_new_fields_default_to_todays_block():
    cfg = TransformerConfig()
    assert cfg.mixer_period == ("attention",) and cfg.use_rope
    assert cfg.nope_head_dim is None and cfg.kda_heads == 0
    assert isinstance(init_params(jax.random.key(0), dataclasses.replace(
        cfg, vocab_size=64, d_model=32, n_layers=2, n_heads=2,
        d_ff=16))["layers"], dict)
    # a list, as a JSON file gives it, is taken as the tuple
    assert TransformerConfig(mixer_period=["attention"]) == cfg


def test_a_pipeline_takes_one_stack_of_one_kind():
    import inspect

    assert "one stack of one kind under a pipeline" in inspect.getsource(
        transformer._trunk)


# ---- an accepted configuration is what it was ------------------------------------

# `init_params(jax.random.key(11), cfg)` of GLM-4.7-Flash at a toy size
# (leaf: shape, sum, sum of magnitudes) and `loss_fn` on 2 x 33 seeded
# tokens, computed AT THE PARENT COMMIT (a79edfc) by the lines of `_golden`
# below, before this PR's edit of models/.
GLM_TINY = dict(vocab_size=160, d_model=32, n_layers=3, n_heads=4,
                n_kv_heads=4, d_ff=24, head_dim=12, v_head_dim=16,
                rope_head_dim=4, q_lora_rank=10, kv_lora_rank=8,
                moe_experts=16, moe_held_experts=4, moe_first_expert=4,
                moe_top_k=2, moe_shared_d_ff=24, moe_dense_d_ff=40)
GLM_GOLDEN = json.loads(r"""{"leaves": {"['dense_layers']['attn_norm']": [[1, 32], 32.0, 32.0],
"['dense_layers']['kv_a_norm']": [[1, 8], 8.0, 8.0],
"['dense_layers']['mlp_norm']": [[1, 32], 32.0, 32.0],
"['dense_layers']['q_a_norm']": [[1, 10], 10.0, 10.0],
"['dense_layers']['w_down']": [[1, 40, 32], 1.9651974439620972,
81.97731018066406], "['dense_layers']['w_gate']": [[1, 32, 40],
1.1564652919769287, 183.76470947265625], "['dense_layers']['w_up']": [[1,
32, 40], -4.551886081695557, 176.1561279296875],
"['dense_layers']['wkv_a']": [[1, 32, 12], 1.8059475421905518,
56.06684875488281], "['dense_layers']['wkv_b']": [[1, 8, 4, 24],
8.976387023925781, 216.66323852539062], "['dense_layers']['wo']": [[1, 4,
16, 32], 3.3937315940856934, 120.83708953857422],
"['dense_layers']['wq_a']": [[1, 32, 10], 3.5627496242523193,
44.646270751953125], "['dense_layers']['wq_b']": [[1, 10, 4, 12],
10.53632926940918, 123.96967315673828], "['embed']": [[160, 32],
-11.964947700500488, 718.138671875], "['final_norm']": [[32], 32.0, 32.0],
"['layers']['attn_norm']": [[2, 32], 64.0, 64.0], "['layers']['kv_a_norm']":
[[2, 8], 16.0, 16.0], "['layers']['mlp_norm']": [[2, 32], 64.0, 64.0],
"['layers']['q_a_norm']": [[2, 10], 20.0, 20.0], "['layers']['router']":
[[2, 32, 16], -1.1239328384399414, 138.73922729492188],
"['layers']['router_bias']": [[2, 16], 0.0, 0.0], "['layers']['w_down']":
[[2, 4, 24, 32], -1.7206521034240723, 306.3412170410156],
"['layers']['w_gate']": [[2, 4, 32, 24], 17.21654510498047,
868.8045043945312], "['layers']['w_up']": [[2, 4, 32, 24],
19.292051315307617, 854.7632446289062], "['layers']['wkv_a']": [[2, 32, 12],
-1.3555852174758911, 109.25], "['layers']['wkv_b']": [[2, 8, 4, 24],
-19.304182052612305, 417.9757385253906], "['layers']['wo']": [[2, 4, 16,
32], 6.53371000289917, 242.08773803710938], "['layers']['wq_a']": [[2, 32,
10], -1.0479497909545898, 92.39625549316406], "['layers']['wq_b']": [[2, 10,
4, 12], -2.5701096057891846, 241.95083618164062], "['layers']['ws_down']":
[[2, 24, 32], -1.3898124694824219, 77.02505493164062],
"['layers']['ws_gate']": [[2, 32, 24], 0.8937943577766418,
212.0736541748047], "['layers']['ws_up']": [[2, 32, 24], -7.402698040008545,
216.17385864257812], "['lm_head']": [[32, 160], -9.161465644836426,
720.74755859375], "['mtp']['e_norm']": [[32], 32.0, 32.0],
"['mtp']['h_norm']": [[32], 32.0, 32.0], "['mtp']['layers']['attn_norm']":
[[1, 32], 32.0, 32.0], "['mtp']['layers']['kv_a_norm']": [[1, 8], 8.0, 8.0],
"['mtp']['layers']['mlp_norm']": [[1, 32], 32.0, 32.0],
"['mtp']['layers']['q_a_norm']": [[1, 10], 10.0, 10.0],
"['mtp']['layers']['router']": [[1, 32, 16], -5.798997402191162,
72.615478515625], "['mtp']['layers']['router_bias']": [[1, 16], 0.0, 0.0],
"['mtp']['layers']['w_down']": [[1, 4, 24, 32], 2.1345367431640625,
153.79588317871094], "['mtp']['layers']['w_gate']": [[1, 4, 32, 24],
17.125415802001953, 441.21514892578125], "['mtp']['layers']['w_up']": [[1,
4, 32, 24], -14.977073669433594, 425.8851013183594],
"['mtp']['layers']['wkv_a']": [[1, 32, 12], 0.18976885080337524,
55.60258865356445], "['mtp']['layers']['wkv_b']": [[1, 8, 4, 24],
-7.407182693481445, 213.20326232910156], "['mtp']['layers']['wo']": [[1, 4,
16, 32], 7.621994495391846, 117.68333435058594],
"['mtp']['layers']['wq_a']": [[1, 32, 10], 6.4447712898254395,
46.07378005981445], "['mtp']['layers']['wq_b']": [[1, 10, 4, 12],
-1.7739949226379395, 120.9406509399414], "['mtp']['layers']['ws_down']":
[[1, 24, 32], -0.6334412693977356, 39.62949752807617],
"['mtp']['layers']['ws_gate']": [[1, 32, 24], 1.6287169456481934,
105.97367095947266], "['mtp']['layers']['ws_up']": [[1, 32, 24],
2.183063268661499, 107.89508056640625], "['mtp']['proj']": [[64, 32],
4.231618404388428, 204.92739868164062]}, "total": 5.7885355949401855,
"metrics": {"loss": 5.2183732986450195, "moe_aux": 0.6689664721488953,
"moe_held_share": 0.2864583432674408, "moe_load_max_over_mean": 2.0,
"mtp_loss": 5.7016215324401855, "perplexity": 184.63360595703125,
"total_loss": 5.7885355949401855}}""")


def _golden():
    conf = spec.load_config(BENCH, "glm-4.7-flash")
    cfg = spec.build_transformer_config(conf, max_seq_len=32,
                                        dtype="float32", **GLM_TINY)
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


def test_glm_is_what_it_was_at_the_parent():
    got, want = _golden(), GLM_GOLDEN
    assert sorted(got["leaves"]) == sorted(want["leaves"])
    for leaf, (shape, total, mag) in want["leaves"].items():
        g = got["leaves"][leaf]
        assert g[0] == shape, leaf
        assert g[1] == pytest.approx(total, rel=1e-6, abs=1e-6), leaf
        assert g[2] == pytest.approx(mag, rel=1e-6), leaf
    # one counter came since (PR 41): every layer's rows fit the front
    assert got["metrics"].pop("moe_compact_path_share") == 1.0
    assert sorted(got["metrics"]) == sorted(want["metrics"])
    assert got["total"] == pytest.approx(want["total"], rel=1e-6)
    for k, v in want["metrics"].items():
        assert got["metrics"][k] == pytest.approx(v, rel=1e-6), k
