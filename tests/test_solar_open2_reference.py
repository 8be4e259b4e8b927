"""The SERVED Solar-Open2 block (models/engine.py: a slot cache that holds
keys and values for the gated GQA layer and a float32 state and a
convolution's tail for the three KDA layers of a period; prefill of
left-padded rows, then decode through the cache a token at a time;
models/moe.py: a held share of a sigmoid router's experts) against the
plain reference (benchmark/architectures/solar_open2.py: a Python loop
over layers, the KDA state walked a TOKEN at a time, attention over the
whole row, the experts a loop over the held ones), on the CPU, float32,
toy widths, seeded random weights. Logits, not tokens.

TOL = 2e-4 relative RMS, the float32 tolerance of the benchmark's own check
(benchmark/harness/reference.py): both sides do the same float32 arithmetic
in another order. A wrong rule moves the logits by order one.
"""

import copy
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
from ray_tpu.models.config import TransformerConfig  # noqa: E402
from ray_tpu.models.engine import (InferenceEngine, _decode_one,  # noqa: E402
                                   decode_slots, init_slot_cache,
                                   prefill_slots)
from ray_tpu.models.generate import _final_logits, _prefill_hidden  # noqa: E402
from ray_tpu.models.transformer import (forward, init_params,  # noqa: E402
                                        refuse_unserved)

TOL = 2e-4
BENCH = spec.load_benchmark()
CONF = spec.load_config(BENCH, "solar-open2-250b")
ARCH = spec.load_architecture(CONF)
# toy SIZES; every RULE stays the config file's (the published layer
# list, no positions, the gate, b in (0, 2), sigmoid + bias, renormalised).
# The router is 4 times as wide as the share held.
TOY = dict(vocab_size=96, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
           head_dim=8, d_ff=24, kda_heads=4, kda_head_dim=8, kda_gate_rank=6,
           moe_experts=16, moe_held_experts=4, moe_first_expert=4,
           moe_top_k=4, moe_shared_d_ff=24)


def _rel_rms(got, want):
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2)
                          / jnp.mean(want ** 2)))


def _setup(seed=0, conf=CONF, **over):
    fields = dict(ARCH.fields(conf), **dict(TOY, **over))
    cfg = TransformerConfig(**dict(
        fields, dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        attention_impl="xla", max_seq_len=256))
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
    return cfg, fields, jax.tree_util.tree_map_with_path(stir, params)


def _prompts(cfg, lengths, seed=3):
    return [list(np.asarray(jax.random.randint(
        jax.random.key(seed + i), (n,), 0, cfg.vocab_size)))
        for i, n in enumerate(lengths)]


def _padded(prompts, P, pad=0):
    toks = np.full((len(prompts), P), pad, np.int32)
    starts = np.zeros(len(prompts), np.int32)
    for i, p in enumerate(prompts):
        toks[i, P - len(p):] = p
        starts[i] = P - len(p)
    return jnp.asarray(toks), jnp.asarray(starts)


def _served_logits(cfg, params, prompts, P, steps, slots=None, cache=None,
                   at=None):
    """Prefill ``prompts`` as one left-padded group into slots ``at`` of a
    cache, then ``steps`` decode steps through it, each slot fed the
    REFERENCE's next token of its row (the last ``steps`` of the prompt's
    continuation): -> (prefill logits [K, V], decode logits [steps, K, V],
    cache)."""
    K = len(prompts)
    slots = slots or K
    at = jnp.arange(K, dtype=jnp.int32) if at is None else jnp.asarray(
        at, jnp.int32)
    cache = init_slot_cache(cfg, slots, P + steps + 1) \
        if cache is None else cache
    heads = [p[:len(p) - steps] for p in prompts]
    toks, starts = _padded(heads, P)
    hidden, _ = _prefill_hidden(params, toks, cfg, P, starts)
    pre = _final_logits(params, hidden[:, -1:], cfg)[:, 0]
    cache, _ = prefill_slots(params, cache, toks, at, starts,
                             jax.random.key(0), cfg)
    active = jnp.zeros(slots, bool).at[at].set(True)
    dec = []
    for s in range(steps):
        pending = jnp.zeros(slots, jnp.int32).at[at].set(jnp.asarray(
            [p[len(p) - steps + s] for p in prompts], jnp.int32))
        cache, logits = _decode_one(params, cache, pending, cfg, active)
        dec.append(logits[at])
    return pre, jnp.stack(dec) if dec else None, cache


def test_the_configuration_is_served_and_the_rest_is_still_refused():
    cfg, _, _ = _setup()
    assert cfg.mixer_period == ("attention", "kda", "kda", "kda")
    assert [cfg.mixer_kind(i) for i in range(4)] == ARCH.layer_kinds(CONF, 4)
    assert cfg.kda_allow_neg_eigval and cfg.attn_output_gate \
        and not cfg.use_rope
    refuse_unserved(cfg)    # a period of kinds and a recurrent state serve
    for over, name in ((dict(kv_lora_rank=8, rope_head_dim=4,
                             n_kv_heads=4, attn_output_gate=False,
                             attn_float32=False),
                        "latent attention"),
                       (dict(moe_dense_layers=4, moe_dense_d_ff=8,
                             n_layers=8), "leading dense layers"),
                       (dict(mtp_layers=1, mixer_period=("attention",),
                             kda_heads=0), "multi-token-prediction")):
        with pytest.raises(NotImplementedError, match=name) as e:
            refuse_unserved(TransformerConfig(**dict(
                ARCH.fields(CONF), **dict(TOY, **over))))
        for served in ("mixer kinds", "recurrent", "gated delta-rule",
                       "M1", "M6"):
            assert served not in str(e.value)


def test_prefill_and_eight_decode_steps_agree_with_the_reference():
    """Two prompts of different lengths in one group (left padding in
    play: the shorter row's KDA states must not see its padding, nor its
    convolutions reach into it), neither a multiple of the scan's chunk of
    64, one longer than a chunk; then 8 decode steps through the cache."""
    cfg, fields, params = _setup()
    steps = 8
    prompts = _prompts(cfg, [70 + steps, 23 + steps])
    pre, dec, _ = _served_logits(cfg, params, prompts, 128, steps)
    for i, p in enumerate(prompts):
        want = ARCH.reference_logits(params, p, fields, CONF,
                                     last=steps + 1)
        assert _rel_rms(pre[i], want[0]) < TOL
        for s in range(steps):
            assert _rel_rms(dec[s, i], want[s + 1]) < TOL, (i, s)
    # the whole forward (the trainer's path) says the same
    row = jnp.asarray(prompts[0], jnp.int32)[None]
    want = ARCH.reference_logits(params, prompts[0], fields, CONF)
    assert _rel_rms(forward(params, row, cfg)[0], want) < TOL


@pytest.mark.parametrize("rule, wrong", [
    ("kda_allow_neg_eigval", False), ("use_gqa_gate", False),
    ("norm_topk_prob", False)])
def test_a_program_configured_to_another_rule_does_not_agree(rule, wrong):
    """The comparison is tight enough to tell: the reference by the
    published rule against a program configured to the other."""
    conf = copy.deepcopy(CONF)
    conf[rule] = wrong
    cfg, fields, params = _setup(conf=conf)
    served = params
    if rule == "use_gqa_gate":
        # the reference reads the gate's leaf: the tree WITH it, which the
        # program configured without a gate is handed less that leaf
        params = _setup()[2]
        served = dict(params, layers=tuple(
            {k: v for k, v in lp.items() if k != "wg"}
            for lp in params["layers"]))
    prompts = _prompts(cfg, [40])
    pre, dec, _ = _served_logits(cfg, served, prompts, 64, 2)
    want = ARCH.reference_logits(params, prompts[0], fields, CONF, last=3)
    assert _rel_rms(pre[0], want[0]) > 50 * TOL
    assert _rel_rms(dec[1, 0], want[2]) > 50 * TOL


def test_a_slot_admitted_a_second_request_gives_a_fresh_engines_logits():
    """Slot 1 serves one request, then is admitted another: prefill
    replaces the slot's state and tail, so what the second request reads is
    what a fresh cache gives, to the bit; and slot 0, which stayed
    resident and inactive meanwhile, is bit for bit untouched."""
    cfg, _, params = _setup()
    first = _prompts(cfg, [30, 45], seed=11)
    second = _prompts(cfg, [37], seed=23)
    _, _, used = _served_logits(cfg, params, first, 64, 4, slots=3)
    kept = {name: np.asarray(used[name][:, 0])
            for name in ("kda_state", "kda_tail")}
    pre_a, dec_a, after = _served_logits(cfg, params, second, 64, 4,
                                         slots=3, cache=used, at=[1])
    pre_b, dec_b, fresh = _served_logits(cfg, params, second, 64, 4,
                                         slots=3, at=[1])
    np.testing.assert_array_equal(np.asarray(pre_a), np.asarray(pre_b))
    np.testing.assert_array_equal(np.asarray(dec_a), np.asarray(dec_b))
    for name in ("kda_state", "kda_tail"):
        np.testing.assert_array_equal(np.asarray(after[name][:, 1]),
                                      np.asarray(fresh[name][:, 1]))
        # slot 0 was not active in the second request's substeps
        np.testing.assert_array_equal(np.asarray(after[name][:, 0]),
                                      kept[name])
        assert np.abs(kept[name]).max() > 0


def test_an_inactive_slots_state_is_untouched_by_a_chunk():
    """The chunk program as the scheduler calls it: an inactive slot's
    state and tail come back bit for bit, an active one's move, and the
    chunk's own expert counts ride the cache."""
    cfg, _, params = _setup()
    prompts = _prompts(cfg, [20, 33])
    _, _, cache = _served_logits(cfg, params, prompts, 64, 0, slots=3)
    before = {k: np.asarray(v) for k, v in cache.items()}
    active = jnp.asarray([True, False, False])
    cache, toks = decode_slots(params, cache, jnp.zeros(3, jnp.int32),
                               active, jax.random.key(0), cfg, steps=4)
    assert toks.shape == (3, 5)
    for name in ("kda_state", "kda_tail"):
        after = np.asarray(cache[name])
        np.testing.assert_array_equal(after[:, 1:], before[name][:, 1:])
        assert np.abs(after[:, 0] - before[name][:, 0]).max() > 0
    assert list(np.asarray(cache["pos"])) == [68, 64, 0]
    fetched, held, kernel = np.asarray(cache["moe_counts"])
    assert kernel == 0      # off the chip `ragged_dot` throughout
    # 4 substeps x 4 layers x 4 held experts offered; 3 rows x 4 a token
    assert 0 < fetched <= 4 * 4 * 4 and 0 < held <= 4 * 4 * 3 * 4
    assert fetched == int(fetched) and held == int(held)


def test_the_engine_serves_the_model_and_counts_what_it_did():
    """`InferenceEngine` end to end at toy size: greedy tokens are the
    reference's argmaxes, and the new counters add up."""
    cfg, fields, params = _setup()
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=32,
                          max_new_tokens=6, decode_chunk=2)
    assert set(eng.cache) == {"k", "v", "kda_state", "kda_tail",
                              "moe_counts", "pos", "start"}
    assert eng.cache["k"].shape[0] == 1 and \
        eng.cache["kda_state"].shape[:2] == (3, 2)
    eng.warmup()
    prompt = _prompts(cfg, [19], seed=5)[0]
    got = eng.generate(prompt, 6)
    row = list(prompt)
    for tok in got:
        want = ARCH.reference_logits(params, row, fields, CONF, last=1)[0]
        top2 = np.sort(np.asarray(want))[-2:]
        assert tok == int(np.argmax(want)) or top2[1] - top2[0] < 1e-3
        row.append(tok)
    st = eng.stats
    steps = st["decode_steps"]
    assert steps > 0 and st["kda_state_updates"] == steps  # one active slot
    assert st["moe_expert_calls"] == steps * 4 * 4
    assert st["moe_assignments"] == steps * 4 * 2 * 4
    assert 0 < st["moe_expert_fetches"] <= st["moe_expert_calls"]
    assert 0 < st["moe_held_assignments"] <= st["moe_assignments"]
    # a model of attention layers alone counts none of them, and its cache
    # is the four leaves it has always been
    dense = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                              n_heads=4, d_ff=48, dtype=jnp.float32)
    eng = InferenceEngine(init_params(jax.random.key(0), dense), dense,
                          slots=2, max_prompt_len=16, max_new_tokens=4)
    assert set(eng.cache) == {"k", "v", "pos", "start"}
    eng.generate([1, 2, 3], 4)
    assert eng.stats["kda_state_updates"] == 0 \
        and eng.stats["moe_expert_calls"] == 0


def test_the_eight_shares_of_a_layers_experts_add_up_to_the_uncut_layer():
    """One layer's experts cut eight ways as the deployment cuts them
    (the router whole on every chip, 1/8 of the experts held), each
    share's routed part by the PROGRAM, summed, plus the shared expert
    counted once: the reference's uncut layer."""
    from ray_tpu.models.moe import moe_layer

    E, chips = 16, 8
    cfg, fields, params = _setup(moe_held_experts=E, moe_first_expert=0)
    lp = jax.tree.map(lambda a: a[0], params["layers"][1])
    m = jax.random.normal(jax.random.key(9), (1, 24, cfg.d_model))
    whole = ARCH.expert_ffn_reference(m[0], lp, dict(fields), CONF,
                                      first=0, held=E)
    shared = ARCH.expert_ffn_reference(m[0], lp, dict(fields), CONF,
                                       first=0, held=0)
    import dataclasses
    total, held_share = jnp.zeros_like(whole), 0.0
    for c in range(chips):
        part = dataclasses.replace(cfg, moe_held_experts=E // chips,
                                   moe_first_expert=c * E // chips,
                                   moe_shared_d_ff=0)
        own = {k: (v[c * E // chips:(c + 1) * E // chips]
                   if k in ("w_gate", "w_up", "w_down") else v)
               for k, v in lp.items() if not k.startswith("ws_")}
        y, stats = moe_layer(m, own, part)
        total = total + y[0]
        held_share += float(stats["held"])
    assert _rel_rms(total + shared, whole) < TOL
    assert abs(held_share - 1.0) < 1e-6   # every assignment on one chip


def test_a_state_held_in_bfloat16_does_not_agree():
    """`assumed.state_dtype` float32 is held HERE: eight decode steps
    through a cache whose KDA state is rounded to bfloat16 wherever a slot
    keeps it (`chip_serve_controls.state_bf16`) leave the float32
    tolerance behind. The chip's check decodes one step and cannot tell
    (PERF.md section 6, PR 42)."""
    import chip_serve_controls as controls

    cfg, fields, params = _setup()
    steps = 8
    prompts = _prompts(cfg, [70 + steps])
    want = ARCH.reference_logits(params, prompts[0], fields, CONF,
                                 last=steps + 1)
    with controls.state_bf16():
        pre, dec, _ = _served_logits(cfg, params, prompts, 128, steps)
    assert _rel_rms(pre[0], want[0]) < TOL   # the scan's own state is float32
    errs = [_rel_rms(dec[s, 0], want[s + 1]) for s in range(steps)]
    assert min(errs) > 3 * TOL


def test_the_controls_script_runs_the_harness_check_at_toy_size():
    """`chip_serve_controls.py --toy`: the cell's `BenchReplica` and
    `bench_check` under a control and without: the plumbing (the verdicts
    are the chip's, at the cell's sizes)."""
    import chip_serve_controls as controls

    assert controls.main(["--toy", "--controls", "program",
                          "state_bf16"]) == 0


def test_init_depth_scales_the_residual_outputs_and_nothing_else():
    """`TransformerConfig.init_depth` (the configuration's `fields()` state
    the published 48): the matrices that write into the residual stream
    are drawn as a layer OF that depth draws them, (2 x depth) ** -0.5,
    every other leaf as ever."""
    import dataclasses

    cfg, _, _ = _setup()
    assert cfg.init_depth == 48 and cfg.n_layers == 4
    own = init_params(jax.random.key(5), cfg)
    cut = init_params(jax.random.key(5),
                      dataclasses.replace(cfg, init_depth=None))
    ratio, scaled = (cfg.n_layers / cfg.init_depth) ** 0.5, set()
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(own),
                            jax.tree.leaves(cut)):
        if np.array_equal(a, b):
            continue
        np.testing.assert_allclose(a, np.asarray(b) * ratio, rtol=1e-6)
        scaled.add(path[-1].key)
    assert scaled == {"wo", "w_down", "ws_down"}


def test_attn_float32_hands_the_first_layers_router_a_float32_stream(
        monkeypatch):
    """`attn_float32` (the architecture file states it): in a bf16 model
    the attention layer's mixer, the sum after it and the router's input
    are float32 in training, prefill and decode alike; the KDA layers, the
    experts and the layer's result keep bf16. The first layer's router
    then reads what a float32 model reads (bf16 weights are exact in
    both), where without the field it reads bf16's rounding."""
    from ray_tpu.models import moe, transformer

    seen = []
    real = moe.route

    def route(x, *args):
        seen.append(x)
        return real(x, *args)
    monkeypatch.setattr(moe, "route", route)

    def model(dtype, **over):
        cfg, _, params = _setup(**over)
        params = jax.tree.map(      # bf16 numbers, whatever holds them
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
        return TransformerConfig(**dict(
            {f: getattr(cfg, f) for f in cfg.__dataclass_fields__},
            dtype=dtype)), params
    assert ARCH.fields(CONF)["attn_float32"] is True
    toks, starts = _padded(_prompts(model(jnp.bfloat16)[0], [9, 16]), 16)
    reads = {}
    for name, dtype, flag in (("float32", jnp.float32, False),
                              ("field", jnp.bfloat16, True),
                              ("bf16", jnp.bfloat16, False)):
        cfg, params = model(dtype, attn_float32=flag)
        first = jax.tree.map(lambda a: a[0], params["layers"][0])
        x = params["embed"].astype(cfg.dtype)[toks]
        del seen[:]
        out, _ = transformer._block(x, first, cfg, None, jnp.arange(16))
        assert out.dtype == cfg.dtype
        reads[name] = seen[0]
        kinds = [jnp.float32 if flag else dtype] + [dtype] * 3
        del seen[:]
        forward(params, toks, cfg)
        assert [x.dtype for x in seen] == kinds, name
        del seen[:]
        _, cache = _prefill_hidden(params, toks, cfg, 24, starts)
        assert [x.dtype for x in seen] == kinds, name
        assert cache["k"].dtype == cfg.dtype
        del seen[:]
        cache = prefill_slots(params, init_slot_cache(cfg, 2, 24), toks,
                              jnp.arange(2), starts, jax.random.key(0),
                              cfg)[0]
        del seen[:]
        _decode_one(params, cache, jnp.asarray([1, 2]), cfg)
        assert [x.dtype for x in seen] == kinds, name
    assert _rel_rms(reads["field"], reads["float32"]) < 1e-5
    assert _rel_rms(reads["bf16"], reads["float32"]) > 1e-3
