"""The Granite 4.0-H decoder through the engine's programs at toy widths
(two periods of `m m a m`, 8 mamba heads of 8, 16 states, chunks of 8),
float32 on the CPU, held to the configuration's plain reference
(`benchmark/architectures/granitemoehybrid.py`): prefill and decode through
the slot cache at every step, left padding, a slot's reset at admission, an
inactive slot, the chunked scan against the recurrence, the one-token kernel
in the Pallas interpreter, each of the four multipliers, what the defaults
leave of an accepted model, the counts and what the configuration refuses."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec  # noqa: E402
from ray_tpu.models import engine as E  # noqa: E402
from ray_tpu.models.config import TransformerConfig  # noqa: E402
from ray_tpu.models.generate import (MIXERS, _final_logits,  # noqa: E402
                                     _prefill_hidden)
from ray_tpu.models.transformer import (forward, init_params,  # noqa: E402
                                        param_logical_axes)
from ray_tpu.ops import mamba2  # noqa: E402

BENCH = spec.load_benchmark()
PUBLISHED = spec.load_config(BENCH, "granite-4.0-h-micro")
# the rules are the published ones; the pattern a shorter period in the
# same spirit (three state-space layers to one attention layer)
CONF = dict(PUBLISHED, layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
            num_hidden_layers=8)
ARCH = spec.load_architecture(CONF)
TOY = dict(vocab_size=96, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
           d_ff=48, mamba_heads=8, mamba_head_dim=8, mamba_d_state=16,
           mamba_chunk=8, dtype="float32", param_dtype="float32")
TOL = 2e-4      # `reference.LOGIT_REL_RMS_TOL["float32"]`


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.fixture(scope="module")
def toy():
    """(cfg, fields, params): seeded weights with the convolution's bias, D
    and the norm's gain moved off their initial values, so that one left
    out shows."""
    fields = dict(spec.transformer_fields(CONF), **TOY)
    cfg = spec.build_transformer_config(CONF, **TOY)
    params = init_params(jax.random.key(1), cfg)

    def moved(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name in ("mamba2_conv_b", "mamba2_D", "mamba2_norm"):
            return x + 0.1 * jax.random.normal(
                jax.random.key(len(name) + x.size), x.shape)
        return x
    return cfg, fields, jax.tree_util.tree_map_with_path(moved, params)


def _group(prompts, P):
    toks = np.zeros((len(prompts), P), np.int32)
    starts = np.zeros(len(prompts), np.int32)
    for i, p in enumerate(prompts):
        toks[i, P - len(p):] = p
        starts[i] = P - len(p)
    return jnp.asarray(toks), jnp.asarray(starts)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 96, n)] for n in lengths]


def _prefill(cfg, params, cache, prompts, P, slots):
    toks, starts = _group(prompts, P)
    return E.prefill_slots(params, cache, toks,
                           jnp.asarray(slots, jnp.int32), starts,
                           jax.random.key(0), cfg)


def _prefill_logits(cfg, params, prompts, P):
    toks, starts = _group(prompts, P)
    return _final_logits(params, _prefill_hidden(
        params, toks, cfg, P, starts)[0][:, -1:], cfg)[:, 0]


# ---- the engine's programs against the reference -------------------------------

def test_prefill_then_twelve_decode_steps_agree_at_every_step(toy):
    """Three rows of one prefill group (one fills its bucket of four chunks,
    two are padded on the left, one by a length that is no multiple of the
    chunk), then 12 decode steps, each step one row's logits against the
    reference's recurrence on the growing sequence (every row four times:
    an error in a state shows in every later step)."""
    cfg, fields, params = toy
    assert cfg.mixer_period == ("mamba2", "mamba2", "attention", "mamba2")
    P, steps = 32, 12
    prompts = _prompts([32, 21, 5])
    cache = E.init_slot_cache(cfg, 3, P + steps + 2)
    cache, tok = _prefill(cfg, params, cache, prompts, P, [0, 1, 2])
    pre = _prefill_logits(cfg, params, prompts, P)
    seqs = [list(p) for p in prompts]
    for i in range(3):
        want = ARCH.reference_logits(params, seqs[i], fields, CONF, last=1)
        assert _rel_rms(pre[i], want[0]) < TOL
        assert int(tok[i]) == int(np.argmax(want[0]))
    decode = jax.jit(lambda p, c, t: E._decode_one(p, c, t, cfg))
    for step in range(steps):
        for i in range(3):
            seqs[i].append(int(tok[i]))
        cache, logits = decode(params, cache, tok)
        # (the reference compiles anew for every length: a row a step)
        i = step % 3
        want = ARCH.reference_logits(params, seqs[i], fields, CONF, last=1)
        assert _rel_rms(logits[i], want[0]) < TOL, (len(seqs[i]), i)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    assert int(cache["pos"][0]) == P + steps


def test_the_served_chunk_is_the_single_steps(toy):
    cfg, _, params = toy
    prompts = _prompts([9, 16], seed=3)
    cache = E.init_slot_cache(cfg, 2, 40)
    cache, tok = _prefill(cfg, params, cache, prompts, 16, [0, 1])
    single, c, t = [], dict(cache), tok
    for _ in range(4):
        c, logits = E._decode_one(params, c, t, cfg)
        t = jnp.argmax(logits, -1).astype(jnp.int32)
        single.append(np.asarray(t))
    _, chunk = E.decode_slots(params, cache, tok, jnp.ones(2, bool),
                              jax.random.key(0), cfg, steps=4)
    np.testing.assert_array_equal(np.asarray(chunk)[:, 1:],
                                  np.stack(single, axis=1))


def test_left_padding_reaches_no_leaf(toy):
    """A prompt lands in a slot the same whether its group's bucket is its
    own length or four times that: the state, the tail and the logits."""
    cfg, _, params = toy
    prompt = _prompts([8], seed=5)
    tight = E.init_slot_cache(cfg, 1, 48)
    tight, _ = _prefill(cfg, params, tight, prompt, 8, [0])
    wide = E.init_slot_cache(cfg, 1, 48)
    wide, _ = _prefill(cfg, params, wide, prompt, 32, [0])
    for name in ("mamba2_state", "mamba2_tail"):
        np.testing.assert_allclose(np.asarray(wide[name]),
                                   np.asarray(tight[name]), atol=2e-6)
    assert _rel_rms(_prefill_logits(cfg, params, prompt, 32),
                    _prefill_logits(cfg, params, prompt, 8)) < 1e-5


def test_a_slot_admitted_anew_reads_nothing_of_its_last_tenant(toy):
    cfg, fields, params = toy
    first, second = _prompts([16], seed=7), _prompts([11], seed=8)
    cache = E.init_slot_cache(cfg, 2, 40)
    cache, tok = _prefill(cfg, params, cache, first, 16, [1])
    for _ in range(3):
        cache, logits = E._decode_one(params, cache, jnp.zeros(
            2, jnp.int32).at[1].set(tok[0]), cfg)
        tok = jnp.argmax(logits[1:], -1).astype(jnp.int32)
    cache, tok = _prefill(cfg, params, cache, second, 16, [1])
    _, logits = E._decode_one(params, cache, jnp.zeros(
        2, jnp.int32).at[1].set(tok[0]), cfg)
    want = ARCH.reference_logits(params, second[0] + [int(tok[0])], fields,
                                 CONF, last=1)
    assert _rel_rms(logits[1], want[0]) < TOL


def test_the_step_that_hands_back_is_the_step_in_place(toy):
    """`_decode_one` without ``active`` (a caller that may keep the cache it
    gave: this file's tests, the benchmark's check) leaves the stacks of
    states alone and hands each layer's new leaves back; with every slot
    named active (the served chunk) it updates them where they lie: the
    same logits and the same cache, and the first form carries no stack
    through its scans."""
    cfg, _, params = toy
    cache = E.init_slot_cache(cfg, 2, 40)
    cache, tok = _prefill(cfg, params, cache, _prompts([16, 7], seed=4), 16,
                          [0, 1])
    back, logits = E._decode_one(params, dict(cache), tok, cfg)
    there, same = E._decode_one(params, dict(cache), tok, cfg,
                                active=jnp.ones(2, bool))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(same))
    assert set(back) == set(there)
    for name in back:
        np.testing.assert_array_equal(np.asarray(back[name]),
                                      np.asarray(there[name]), err_msg=name)

    def carried(active):
        jaxpr = jax.make_jaxpr(lambda p, c, t: E._decode_one(
            p, c, t, cfg, active))(params, cache, tok).jaxpr
        scan = next(e for e in jaxpr.eqns if e.primitive.name == "scan")
        n = scan.params["num_carry"]
        return [v.aval.shape for v in scan.outvars[:n]]
    state = cache["mamba2_state"].shape
    assert state in carried(jnp.ones(2, bool))
    assert state not in carried(None)


def test_an_inactive_slot_keeps_its_leaves(toy):
    cfg, _, params = toy
    cache = E.init_slot_cache(cfg, 2, 40)
    cache, tok = _prefill(cfg, params, cache, _prompts([16, 7], seed=9), 16,
                          [0, 1])
    new, _ = E._decode_one(params, dict(cache), tok, cfg,
                           active=jnp.asarray([True, False]))
    for name in ("mamba2_state", "mamba2_tail"):
        np.testing.assert_array_equal(np.asarray(new[name][:, 1]),
                                      np.asarray(cache[name][:, 1]))
        assert not np.array_equal(np.asarray(new[name][:, 0]),
                                  np.asarray(cache[name][:, 0]))
    assert [int(p) for p in new["pos"]] == [17, 16]


def test_the_pairs_through_the_decode_kernel_are_the_contraction(
        toy, monkeypatch):
    """On a chip the attention layers' keys and values, two heads side by
    side, go through `ops/decode_attention.py`, a query head as [q | 0] or
    [0 | q]; here the interpreter walking blocks of 8 positions: 8 steps
    from three left-padded rows, one of them parked, give the logits of the
    masked contraction (the program the tests above hold to the
    reference), and the unpaired cache's too."""
    from ray_tpu.ops import decode_attention

    cfg, _, params = toy
    assert cfg.kv_head_pairs
    P, steps = 16, 8
    active = jnp.asarray([True, True, False, True])

    def run(cfg):
        cache, tok = _prefill(cfg, params, E.init_slot_cache(cfg, 4, 32),
                              _prompts([16, 11, 5]), P, [0, 1, 3])
        tok = jnp.zeros(4, jnp.int32).at[jnp.asarray([0, 1, 3])].set(tok)
        step = jax.jit(lambda p, c, t: E._decode_one(p, c, t, cfg, active))
        out = []
        for _ in range(steps):
            cache, logits = step(params, cache, tok)
            out.append(logits)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return cache, out
    want_cache, want = run(cfg)
    assert want_cache["k"].shape == (2, 4, 1, 32, 16)
    assert E._kv_block(want_cache) is None
    loose_cache, loose = run(dataclasses.replace(cfg, kv_head_pairs=False))
    assert loose_cache["k"].shape == (2, 4, 2, 32, 8)
    monkeypatch.setattr(decode_attention, "_BLOCK", 8)
    monkeypatch.setattr(E, "_on_chip", lambda: True)
    assert E._kv_block(want_cache) == 8
    got_cache, got = run(cfg)
    live = np.asarray(active)
    for a, b, c in zip(got, want, loose):
        assert _rel_rms(a[live], b[live]) < 1e-5
        assert _rel_rms(c[live], b[live]) < 1e-5
    assert int(got_cache["pos"][0]) == P + steps and \
        int(got_cache["pos"][2]) == 0
    np.testing.assert_allclose(np.asarray(got_cache["k"]),
                               np.asarray(want_cache["k"]), atol=1e-5)
    # a pair is two adjacent heads side by side
    np.testing.assert_allclose(
        np.asarray(want_cache["k"])[:, :, 0, :, 8:],
        np.asarray(loose_cache["k"])[:, :, 1], atol=1e-6)


# ---- the chunked scan and the one-token step against the recurrence -----------

def _scan_inputs(B, T, H=4, P=8, N=16, seed=0, starts=None):
    k = jax.random.split(jax.random.key(seed), 4)
    dt = jax.nn.softplus(jax.random.normal(k[0], (B, T, H)) - 1.0)
    x = jax.random.normal(k[1], (B, T, H, P))
    Bm, Cm = (jax.random.normal(k[i], (B, T, N)) for i in (2, 3))
    if starts is not None:    # as the mixer hands a left-padded row on
        valid = jnp.arange(T)[None, :] >= jnp.asarray(starts)[:, None]
        dt = jnp.where(valid[..., None], dt, 0.0)
        x = jnp.where(valid[..., None, None], x, 0.0)
    return dt, x, Bm, Cm, -jnp.arange(1, H + 1, dtype=jnp.float32)


def _recurrence(dt, x, Bm, Cm, A):
    """The equations a token at a time, the state [H, P, N]."""
    def row(dt, x, Bm, Cm):
        def token(S, t):
            d, xt, b, c = t
            S = jnp.exp(d * A)[:, None, None] * S \
                + (d[:, None] * xt)[:, :, None] * b[None, None, :]
            return S, jnp.einsum("hpn,n->hp", S, c)
        S, y = jax.lax.scan(token, jnp.zeros(x.shape[1:] + Bm.shape[1:]),
                            (dt, x, Bm, Cm))
        return y, S
    return jax.vmap(row)(dt, x, Bm, Cm)


@pytest.mark.parametrize("T, chunk, starts", [
    (32, 8, None),            # whole chunks
    (21, 8, None),            # a row that is no multiple of the chunk
    (5, 8, None),             # shorter than one chunk
    (32, 8, [0, 11, 27]),     # left-padded rows in one group
    (21, 256, [0, 3, 20]),    # one chunk, the published size
    (24, 7, [2, 0, 9]),       # a chunk that divides nothing
])
def test_the_chunked_scan_is_the_recurrence(T, chunk, starts):
    B = 3
    dt, x, Bm, Cm, A = _scan_inputs(B, T, seed=T + chunk, starts=starts)
    want_y, want_S = jax.jit(_recurrence)(dt, x, Bm, Cm, A)
    scan = jax.jit(lambda *a: mamba2.mamba2_scan(*a, chunk=chunk))
    y, S = scan(dt, x, Bm, Cm, A)
    assert y.shape == want_y.shape and S.shape == (B, 16, 4 * 8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=3e-5)
    # state-major [N, H x P] against the equations' [H, P, N]
    np.testing.assert_allclose(
        np.asarray(S).reshape(B, 16, 4, 8),
        np.asarray(want_S).transpose(0, 3, 1, 2), atol=3e-5)
    if starts is not None:    # padding wrote nothing: the row from its start
        i = int(np.argmax(starts))
        alone = tuple(a[i:i + 1, starts[i]:] for a in (dt, x, Bm, Cm))
        _, S1 = scan(*alone, A)
        np.testing.assert_allclose(np.asarray(S[i]), np.asarray(S1[0]),
                                   atol=3e-5)


def _scan_lengths(jaxpr) -> list:
    """The trip count of every `lax.scan` in ``jaxpr``, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn.params["length"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scan_lengths(sub)
    return found


def test_the_prompt_pass_holds_no_scan_a_token(toy):
    """The prompt's recurrence runs a CHUNK a step: the prefill of a
    32-position bucket scans the pattern's 2 periods and, inside each mamba2
    layer, the bucket's 4 chunks; nothing walks its 32 positions."""
    cfg, _, params = toy
    toks, starts = _group(_prompts([32, 9]), 32)
    lengths = _scan_lengths(jax.make_jaxpr(
        lambda p, t, s: _prefill_hidden(p, t, cfg, 32, s))(
            params, toks, starts).jaxpr)
    assert sorted(set(lengths)) == [2, 4], lengths
    assert lengths.count(4) == 3        # the period's three mamba2 layers


@pytest.mark.parametrize("C, N", [(64, 16), (4096, 128)])
def test_the_kernel_in_the_interpreter_is_the_numpy_step(C, N):
    """`mamba2_decode_step`'s Mosaic kernel (Pallas interpret mode) against
    its `jax.numpy` form on a stack of three layers: the layer it was
    given alone changes, an inactive slot's state stays bit for bit."""
    slots, H = 3, C // 32
    dt, x, Bm, Cm, A = _scan_inputs(slots, 1, H=H, P=32, N=N, seed=C)
    state = jax.random.normal(jax.random.key(C + 1), (3, slots, N, C))
    active = jnp.asarray([True, False, True])
    token = (dt[:, 0], x[:, 0], Bm[:, 0], Cm[:, 0], A)
    s_np, y_np = mamba2.mamba2_decode_step(state, 1, *token, active,
                                           kernel=False)
    # the form that only READS the stack: the layer's new states alone
    for kernel in (False, True):
        mine, y = mamba2.mamba2_decode_step(state, jnp.asarray(1), *token,
                                            active, kernel=kernel,
                                            in_place=False)
        np.testing.assert_allclose(np.asarray(mine), np.asarray(s_np[1]),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(y)[0], np.asarray(y_np)[0],
                                   atol=1e-5)
    s_k, y_k = mamba2.mamba2_decode_step(state, jnp.asarray(1), *token,
                                         active, kernel=True)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_np), atol=1e-6)
    np.testing.assert_allclose(np.asarray(y_k)[[0, 2]],
                               np.asarray(y_np)[[0, 2]], atol=1e-5)
    was = np.asarray(state)
    for s in (np.asarray(s_k), np.asarray(s_np)):
        np.testing.assert_array_equal(s[1, 1], was[1, 1])
        np.testing.assert_array_equal(s[[0, 2]], was[[0, 2]])
    # and it is the recurrence's one step
    S = np.asarray(state[1]).reshape(slots, N, H, 32).transpose(0, 2, 3, 1)
    d = np.asarray(dt[:, 0])
    want = np.exp(d * np.asarray(A))[..., None, None] * S \
        + (d[..., None] * np.asarray(x[:, 0]))[..., None] \
        * np.asarray(Bm[:, 0])[:, None, None, :]
    np.testing.assert_allclose(
        np.asarray(s_np[1]).reshape(slots, N, H, 32).transpose(0, 2, 3, 1)[0],
        want[0], atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(y_np[0]), np.einsum("hpn,n->hp", want[0],
                                       np.asarray(Cm[0, 0])), atol=1e-4)


# ---- the four multipliers ------------------------------------------------------

@pytest.mark.parametrize("field, wrong", [
    ("embed_scale", 1.0), ("residual_scale", 1.0),
    ("attn_scale", None), ("logit_divisor", None)])
def test_a_multiplier_off_its_published_value_does_not_agree(toy, field,
                                                             wrong):
    """Each of the four enters the logits: the program configured to another
    value (or to none: an absent `attn_scale` is head_dim ** -0.5) is
    outside the limit the published one meets, in prefill and in a decode
    step. (The attention scale moves the logits least: two attention layers
    of eight behind a 0.22.)"""
    cfg, fields, params = toy
    prompts = _prompts([16, 9], seed=11)
    seq = prompts[0]
    want = ARCH.reference_logits(params, seq, fields, CONF, last=1)[0]
    assert _rel_rms(_prefill_logits(cfg, params, prompts, 16)[0], want) < TOL
    off = dataclasses.replace(cfg, **{field: wrong})
    assert _rel_rms(_prefill_logits(off, params, prompts, 16)[0],
                    want) > 10 * TOL
    cache = E.init_slot_cache(cfg, 2, 24)
    cache, tok = _prefill(cfg, params, cache, prompts, 16, [0, 1])
    want = ARCH.reference_logits(params, seq + [int(tok[0])], fields, CONF,
                                 last=1)[0]
    assert _rel_rms(E._decode_one(params, dict(cache), tok, cfg)[1][0],
                    want) < TOL
    assert _rel_rms(E._decode_one(params, dict(cache), tok, off)[1][0],
                    want) > 10 * TOL


@pytest.mark.parametrize("field, neutral", [
    ("embed_scale", 1.0), ("residual_scale", 1.0),
    ("attn_scale", 16 ** -0.5), ("logit_divisor", 1.0)])
def test_a_default_leaves_internlm2s_logits_bit_equal(field, neutral):
    """Absent is the neutral value, to the bit: InternLM2 at toy widths
    through prefill and a decode step with the field left out and with the
    field at the value that changes nothing. (That absent emits NO
    operation is what `test_served_program_goldens.py` holds.)"""
    conf = spec.load_config(BENCH, "internlm2-1.8b")
    cfg = spec.build_transformer_config(
        conf, attention_impl="xla", remat=False, max_seq_len=128,
        dtype="float32", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=128)
    assert getattr(cfg, field) is None and cfg.head_dim == 16
    stated = dataclasses.replace(cfg, **{field: neutral})
    params = init_params(jax.random.key(2), cfg)
    prompts = _prompts([16, 9], seed=13)
    np.testing.assert_array_equal(
        np.asarray(_prefill_logits(cfg, params, prompts, 16)),
        np.asarray(_prefill_logits(stated, params, prompts, 16)))
    cache = E.init_slot_cache(cfg, 2, 24)
    cache, tok = _prefill(cfg, params, cache, prompts, 16, [0, 1])
    np.testing.assert_array_equal(
        np.asarray(E._decode_one(params, dict(cache), tok, cfg)[1]),
        np.asarray(E._decode_one(params, dict(cache), tok, stated)[1]))


# ---- counts, the initialiser, the cache, refusals --------------------------------

def test_the_architecture_counts_what_the_initialiser_makes(toy):
    cfg, fields, params = toy
    made = sum(x.size for x in jax.tree.leaves(params))
    assert made == cfg.num_params == ARCH.num_params(fields, CONF)
    axes = param_logical_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)
            and not any(isinstance(e, dict) for e in a)))
    # the published model: the issue's sum, term by term
    f = spec.transformer_fields(PUBLISHED)
    mamba = 2048 * 8512 + 4096 * 2048 + 5 * 4352 + 3 * 64 + 4096
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 3 * 2048 * 8192
    want = 36 * (mamba + mlp + 2 * 2048) + 4 * (attention + mlp + 2 * 2048) \
        + 100352 * 2048 + 2048
    assert ARCH.num_params(f, PUBLISHED) == want == 3_191_396_096
    assert spec.build_transformer_config(PUBLISHED).num_params == want
    assert ARCH.period(PUBLISHED, 40) == ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    # 2 a weight that multiplies and the recurrence's 5 N C a mamba layer
    assert ARCH.mamba2_step_cost(4096, 128) == {
        "flops": 5.0 * 128 * 4096, "decode_bytes": 8.0 * 128 * 4096}
    # 2 x 3.19 G weights that multiply (the table once, as the head), the
    # recurrence and four layers of attention over 512.5 keys
    assert ARCH.forward_flops_per_token(f, PUBLISHED, 1024) == 2.0 * (
        want - 36 * (4352 + 3 * 64 + 4096) - 81 * 2048) \
        + 36 * 5.0 * 128 * 4096 + 4 * 32 * 4.0 * 64 * 512.5


def test_the_initialiser_draws_the_recurrence_as_published():
    cfg = spec.build_transformer_config(CONF, **TOY)
    lp = init_params(jax.random.key(3), cfg)["layers"][0]
    H = cfg.mamba_heads
    np.testing.assert_allclose(np.exp(np.asarray(lp["mamba2_A_log"])),
                               np.broadcast_to(np.arange(1, H + 1), (2, H)),
                               rtol=1e-6)
    step = np.asarray(jax.nn.softplus(lp["mamba2_dt_b"]))
    assert step.min() >= 0.001 * (1 - 1e-5) and step.max() <= 0.1
    for name in ("mamba2_D", "mamba2_norm"):
        assert np.all(np.asarray(lp[name]) == 1.0), name
    assert lp["mamba2_in"].shape == (2, 32, 64 + 64 + 2 * 16)
    assert lp["mamba2_dt"].shape == (2, 32, 8)
    assert lp["mamba2_conv"].shape == (2, 4, 64 + 2 * 16)
    assert "wo" not in lp and "wq" not in lp


def test_the_cache_holds_the_state_and_the_bytes_stated():
    """The published widths at the cell's slots (shapes alone): 36 layers x
    64 slots x 2.1 MB of float32 state, 75.5 MB a slot; the tails one row a
    slot; four layers of keys and values."""
    cfg = spec.build_transformer_config(PUBLISHED)
    cache = jax.eval_shape(lambda: E.init_slot_cache(cfg, 64, 2048))
    state, tail = cache["mamba2_state"], cache["mamba2_tail"]
    assert (state.shape, state.dtype) == ((36, 64, 128, 4096), jnp.float32)
    assert (tail.shape, tail.dtype) == ((36, 64, 3 * 4352), jnp.bfloat16)
    # 8 key/value heads of 64 as 4 pairs of 128
    assert cache["k"].shape == (4, 64, 4, 2048, 128)
    slot = state.size * 4 // 64
    assert slot == 36 * 64 * 64 * 128 * 4 == 75_497_472
    assert set(E.cache_logical_axes(cache)) == set(cache)
    assert MIXERS["mamba2"].land == "slot"


def test_training_refuses_the_configuration_by_name(toy):
    cfg, _, params = toy
    with pytest.raises(NotImplementedError, match="mamba2"):
        forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    plain = dataclasses.replace(
        cfg, mixer_period=("attention",), embed_scale=None, attn_scale=None,
        logit_divisor=None)
    with pytest.raises(NotImplementedError, match="residual_scale"):
        forward(params, jnp.zeros((1, 8), jnp.int32), plain)


@pytest.mark.parametrize("change, why", [
    (dict(mamba_groups=2), "mamba_groups 1"),
    (dict(mamba_heads=3), "mamba_heads x mamba_head_dim"),
    (dict(mamba_d_state=0), "mamba_d_state"),
    (dict(causal=False), "causal"),
    (dict(diff_attn=True, use_rope=False), "kv_head_pairs"),
    (dict(diff_attn=True, kv_head_pairs=False), "attn_scale")])
def test_the_configuration_refuses_what_it_cannot_state(toy, change, why):
    with pytest.raises(ValueError, match=why):
        dataclasses.replace(toy[0], **change)


def test_a_mesh_refuses_the_kind(toy):
    from jax.sharding import Mesh

    cfg, _, params = toy
    if len(jax.devices()) < 2:
        pytest.skip("one device: no mesh of two")
    with pytest.raises(NotImplementedError, match="mamba2"):
        E.InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                          max_new_tokens=4, mesh=Mesh(
                              np.asarray(jax.devices()[:2]), ("tensor",)))


def test_the_engine_counts_a_state_update_a_slot_and_substep(toy):
    cfg, _, params = toy
    eng = E.InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                            max_new_tokens=8, decode_chunk=4)
    out = eng.generate(_prompts([7])[0], 8)
    assert len(out) == 8
    st = eng.stats
    # one request: one active slot in every substep dispatched
    assert st["mamba2_state_updates"] == st["decode_steps"] > 0
    assert st["mamba_state_updates"] == st["kda_state_updates"] == 0
    assert isinstance(cfg, TransformerConfig)


def test_the_controls_script_runs_the_harness_check_at_toy_size(capsys):
    import json

    import chip_serve_controls

    # (the script's plumbing, two controls of seven: the verdicts at the
    # cell's sizes are the chip's)
    rc = chip_serve_controls.main([
        "--workload", "granite-4.0-h-micro.reason-closed-64", "--toy",
        "--seeds", "5", "--controls", "program", "no_decay"])
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    by = {line["control"]: line for line in lines if "control" in line}
    assert list(by) == ["program", "no_decay"] and by["program"]["ok"]
    assert by["no_decay"]["worst"]["prefill"] \
        > 2 * by["program"]["worst"]["prefill"]
    assert set(chip_serve_controls.CONTROLS["granitemoehybrid"]) \
        <= set(chip_serve_controls.EXPECT)
    assert rc == 0 and lines[-1]["ok"] is True
