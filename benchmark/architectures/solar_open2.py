"""The Solar-Open2 decoder (upstage/Solar-Open2-250B, `model_type`
`solar_open2`): its plain reference and the work its forward pass
requires, for ONE chip's share of a deployment in which 8 chips share each
layer.

Written from the published description, not from `ray_tpu/models/`: the
keys of the model's `config.json` (the catalog row of the `model-configs`
guide) and its `described_as` ("gated delta-rule linear (neg. eigenvalues,
conv4); softmax NoPE GQA 64Q/8KV, 48L 3:1; 320 experts, top-8, 1 shared").
Pre-norm residual block, n = RMSNorm(x), eps `rms_norm_eps`: `x +=
mixer(n)`, `x += ffn(RMSNorm(x))`. Layers are numbered from 0 as
`gqa_layers` counts them: a layer in `gqa_layers` is softmax attention,
every other one (`gqa_interval` of them after each) gated delta rule.

  GQA mixer (64 query heads, 8 key/value heads of 128), NO positions
  (`use_rope` false):
    q = Wq n [64 x 128], k = Wk n, v = Wv n [8 x 128]
    o = softmax(q k^T / sqrt(128), causal) v     8 query heads a kv head
    y = Wo [o . sigmoid(Wg n)]     (`use_gqa_gate`; Wg [d, 64 x 128], the
                                    gate elementwise, from the same n)
  KDA mixer (`linear_attn_config`: H = 64 heads of d_k = d_v = 128, one
  key head a query head), token t:
    q~ = Wq n, k~ = Wk n, v~ = Wv n                              [H x 128]
    each through its own causal depthwise convolution of
    `short_conv_kernel_size` taps, y_t[c] = sum_i w[i, c] z_(t-3+i)[c] with
    zeros before the row's first token, then SiLU
    q_t = l2norm(q) 128^-0.5, k_t = l2norm(k) a head, v_t as it is
    g_t = -exp(A_log[h]) softplus(Wf2 (Wf1 n) + dt_bias)   in R^128 a head
          (through a rank of 128: `kda_use_full_proj` false)
    a_t = exp(g_t) in (0, 1)^128
    b_t = 2 sigmoid(Wbeta n) a head, in (0, 2): `kda_allow_neg_eigval`
          (without it sigmoid alone)
    S' = Diag(a_t) S_(t-1);  S_t = S' + b_t k_t (v_t - S'^T k_t)^T, S_0 = 0
    o_t = S_t^T q_t
    y_t = Wo [RMSNorm_head(o_t; gain in R^128) . sigmoid(Wg2 (Wg1 n))]
  feed-forward, every layer (`first_k_dense_replace` 0), m = RMSNorm(x):
    s   = sigmoid(Wr m) over ALL 320 experts, float32
    S   = the 8 experts of largest s + b (b a per-expert bias, zero at a
          seeded draw)
    w_e = `routed_scaling_factor` s_e / sum_{e' in S} s_e'
          (`norm_topk_prob`; the UNBIASED scores)
    out = SwiGLU_shared(m) + sum_{e in S} w_e SwiGLU_e(m)
  logits = Whead RMSNorm_final(x_L).

The share (guide, section 4; the config file's `deployment`): this chip
holds experts `first_expert` .. + `n_routed_experts` of each layer's 320
and rows 0 .. `vocab_size` of the vocabulary. The router scores and
chooses over all 320; the sum over S runs over the chosen experts THAT ARE
HELD; what the absent experts would add is left out.

What is written here otherwise than the program computes it: a KDA layer
is the recurrence above a TOKEN at a time (a `lax.scan` over the row: no
chunks, no triangular system, no cache, no one-token kernel); attention is
the plain causal softmax over the whole row, no cache; the experts are a
Python loop over the HELD experts, each applied to every token and
weighted by that token's weight for it, or by zero. What was assumed is in
the config file's `assumed`.

It reads the program's parameter pytree because the weights ARE the
program's, made from the seed: `layers` one stack a position of the
period (a tuple), each with a leading axis of periods. A GQA layer's
leaves: `wq` [d, 64, 128], `wk`, `wv` [d, 8, 128], `wg` [d, 64, 128],
`wo` [64, 128, d]; a KDA layer's: `kda_wq`/`kda_wk`/`kda_wv` [d, H, 128],
`kda_conv_q`/`_k`/`_v` [taps, H, 128], `kda_f_a` [d, r], `kda_f_b` [r, H,
128], `kda_g_a`, `kda_g_b`, `kda_beta` [d, H], `kda_A_log` [H],
`kda_dt_bias` [H, 128], `kda_o_norm` [128], `wo` [H, 128, d]; every
layer's: `router` [d, 320], `router_bias` [320], `w_gate`/`w_up` [held, d,
f], `w_down` [held, f, d], `ws_gate`/`ws_up`/`ws_down`, the two norms.
RULES come from ``conf`` (published keys), SIZES from ``fields``. JAX is
imported inside the functions that compute.
"""

from __future__ import annotations

import functools

L2_EPS = 1e-6     # the config file's `assumed.l2norm_eps`


def layer_kinds(conf: dict, n_layers: int) -> list:
    """'attention' or 'kda' for layers 0 .. n_layers - 1: `gqa_layers`
    lists the attention layers, `gqa_interval` KDA layers follow each."""
    gqa = set(conf["gqa_layers"])
    kinds = ["attention" if i in gqa else "kda" for i in range(n_layers)]
    step = conf["gqa_interval"] + 1
    if any((kind == "attention") != (i % step == 0)
           for i, kind in enumerate(kinds)):
        raise ValueError("gqa_layers is not one layer in gqa_interval + 1")
    return kinds


def fields(conf: dict) -> dict:
    """Published keys -> TransformerConfig fields. `n_routed_experts` in
    the file is the count HELD here (listed in `reduced`); the router keeps
    the published width, `deployment.router_experts`."""
    dep, lin = conf["deployment"], conf["linear_attn_config"]
    if conf["use_rope"] or conf["kda_use_full_proj"] \
            or lin["num_kv_heads"] is not None:
        raise ValueError("only attention without positions, the decay and "
                         "gate through a low rank, one key head a query "
                         "head in a KDA layer")
    if conf["first_k_dense_replace"] or conf["tie_word_embeddings"]:
        raise ValueError("every layer has experts and the head is untied")
    kinds = layer_kinds(conf, conf["num_hidden_layers"])
    period = conf["gqa_interval"] + 1
    return {
        "vocab_size": conf["vocab_size"],
        "d_model": conf["hidden_size"],
        "n_layers": conf["num_hidden_layers"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["head_dim"],
        "use_rope": False,
        "attn_output_gate": bool(conf["use_gqa_gate"]),
        # the attention layers' mixer, the sum after it and the router's
        # input in float32: layer 0 is one, and the config file's
        # `assumed.first_layer_precision` says what it is there for
        "attn_float32": True,
        "rope_theta": float(conf["rope_theta"]),      # unused: no positions
        "rms_eps": conf["rms_norm_eps"],
        "tie_embeddings": False,
        # seeded weights as layers 0-3 OF the published depth have them:
        # the config file's `assumed.initializer`
        "init_depth": len(conf["gqa_layers"]) * period,
        # the checkpoint's dtype: the initialiser draws bf16 weights, which
        # a replica holds as they are (`assumed.checkpoint_dtype`)
        "param_dtype": "bfloat16",
        "mixer_period": tuple(kinds[:period]),
        "kda_heads": lin["num_heads"],
        "kda_head_dim": lin["head_dim"],
        "kda_conv": lin["short_conv_kernel_size"],
        "kda_gate_rank": lin["head_dim"],
        "kda_allow_neg_eigval": bool(conf["kda_allow_neg_eigval"]),
        "d_ff": conf["moe_intermediate_size"],
        "moe_experts": dep["router_experts"],
        "moe_held_experts": conf["n_routed_experts"],
        "moe_first_expert": dep["first_expert"],
        "moe_top_k": conf["num_experts_per_tok"],
        "moe_scoring": "sigmoid",
        "moe_select_bias": True,
        "moe_norm_topk": bool(conf["norm_topk_prob"]),
        "moe_route_scale": float(conf["routed_scaling_factor"]),
        "moe_shared_d_ff": conf["n_shared_experts"]
        * conf["moe_intermediate_size"],
        "moe_aux_weight": 0.0,
    }


# ---- the plain reference ---------------------------------------------------


def _rms_norm(x, gamma, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma.astype(jnp.float32)


def _mm(eq, a, w):
    import jax
    import jax.numpy as jnp

    return jnp.einsum(eq, a, w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _short_conv(z, w):
    """z [T, H, D], w [taps, H, D]: y_t = sum_i w[i] z_(t - taps + 1 + i),
    zeros before the first token; then SiLU."""
    import jax
    import jax.numpy as jnp

    taps, T = w.shape[0], z.shape[0]
    w = w.astype(jnp.float32)
    y = jnp.zeros_like(z)
    for i in range(taps):
        back = taps - 1 - i                       # tokens before t
        y = y + w[i] * jnp.concatenate(
            [jnp.zeros_like(z[:back]), z[:T - back]], axis=0)
    return jax.nn.silu(y)


def _kda(x, lp, *, eps, neg_eigval):
    """x [T, d] float32 -> x + the KDA mixer, the state walked a token at
    a time."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    n = _rms_norm(x, lp["attn_norm"], eps)
    q, k, v = (_short_conv(_mm("td,dhk->thk", n, lp[f"kda_w{c}"]),
                           lp[f"kda_conv_{c}"]) for c in "qkv")
    width = q.shape[-1]

    def l2norm(z):
        return z / jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True) + L2_EPS)
    q, k = l2norm(q) * width ** -0.5, l2norm(k)
    decay = _mm("tr,rhk->thk", _mm("td,dr->tr", n, lp["kda_f_a"]),
                lp["kda_f_b"])
    g = -jnp.exp(lp["kda_A_log"].astype(jnp.float32))[None, :, None] \
        * jax.nn.softplus(decay + lp["kda_dt_bias"].astype(jnp.float32))
    alpha = jnp.exp(g)                                      # (0, 1)
    beta = jax.nn.sigmoid(_mm("td,dh->th", n, lp["kda_beta"]))
    if neg_eigval:
        beta = 2.0 * beta                                   # (0, 2)

    def token(S, t):           # S [H, dk, dv]
        q_t, k_t, v_t, a_t, b_t = t
        S = a_t[:, :, None] * S
        read = jnp.einsum("hkv,hk->hv", S, k_t, precision=hp)
        S = S + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - read)[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=hp)
    S0 = jnp.zeros((q.shape[1], width, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(token, S0, (q, k, v, alpha, beta))  # [T, H, dv]
    gate = jax.nn.sigmoid(_mm("tr,rhk->thk",
                              _mm("td,dr->tr", n, lp["kda_g_a"]),
                              lp["kda_g_b"]))
    return x + _mm("thk,hkd->td",
                   _rms_norm(o, lp["kda_o_norm"], eps) * gate, lp["wo"])


def _attention(x, lp, *, eps, gated):
    """x [T, d] float32 -> x + grouped-query softmax attention without
    positions, its output gated elementwise where the model says so."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    T = x.shape[0]
    n = _rms_norm(x, lp["attn_norm"], eps)
    q = _mm("td,dhk->thk", n, lp["wq"])                 # [T, 64, 128]
    k = _mm("td,dhk->thk", n, lp["wk"])                 # [T, 8, 128]
    v = _mm("td,dhk->thk", n, lp["wv"])
    H, KV, width = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(T, KV, H // KV, width)       # query heads by kv head
    s = jnp.einsum("qgrk,sgk->grqs", qg, k, precision=hp) * width ** -0.5
    seen = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("grqs,sgk->qgrk", p, v, precision=hp).reshape(q.shape)
    if gated:
        o = o * jax.nn.sigmoid(_mm("td,dhk->thk", n, lp["wg"]))
    return x + _mm("qhk,hkd->qd", o, lp["wo"])


def _swiglu(m, w_gate, w_up, w_down):
    import jax

    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", m, w_gate))
               * _mm("td,df->tf", m, w_up), w_down)


def _route(m, router, bias, *, top_k, renormalize, scale):
    """m [T, d] -> weights [T, E]: each token's weight for each of ALL the
    experts, zero outside its top k."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(_mm("td,de->te", m, router))
    choice = s + bias.astype(jnp.float32)[None, :]
    keep = choice >= jnp.sort(choice, axis=-1)[:, -top_k][:, None]
    w = jnp.where(keep, s, 0.0)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale


@functools.lru_cache(maxsize=None)
def _jitted(eps: float, neg_eigval: bool, gated: bool, top_k: int,
            renormalize: bool, scale: float):
    import jax

    return {
        "kda": jax.jit(functools.partial(_kda, eps=eps,
                                         neg_eigval=neg_eigval)),
        "attention": jax.jit(functools.partial(_attention, eps=eps,
                                               gated=gated)),
        "norm": jax.jit(functools.partial(_rms_norm, eps=eps)),
        "route": jax.jit(functools.partial(
            _route, top_k=top_k, renormalize=renormalize, scale=scale)),
        "swiglu": jax.jit(_swiglu),
        "head": jax.jit(lambda x, g, w: _mm("td,dv->tv",
                                            _rms_norm(x, g, eps), w)),
    }


def _pieces(fields: dict, conf: dict):
    """Sizes from ``fields`` (a test runs a toy size), rules from the
    published keys in ``conf``: a program configured to another rule than
    the published one must not agree."""
    return _jitted(float(fields["rms_eps"]),
                   bool(conf["kda_allow_neg_eigval"]),
                   bool(conf["use_gqa_gate"]), int(fields["moe_top_k"]),
                   bool(conf["norm_topk_prob"]),
                   float(conf["routed_scaling_factor"]))


def expert_ffn_reference(m, lp, fields: dict, conf: dict, *,
                         first=None, held=None, shared=True):
    """The expert branch alone on normed rows m [T, d] float32 with one
    layer's weights: the shared expert (where ``shared``) plus the routed
    part of experts ``first`` .. ``first + held`` (default: the share
    ``fields`` states; ``lp``'s expert weights are THOSE experts')."""
    import jax.numpy as jnp

    fn = _pieces(fields, conf)
    first = fields.get("moe_first_expert", 0) if first is None else first
    held = (fields.get("moe_held_experts") or fields["moe_experts"]) \
        if held is None else held
    w = fn["route"](m, lp["router"], lp["router_bias"])
    y = fn["swiglu"](m, lp["ws_gate"], lp["ws_up"], lp["ws_down"]) \
        if shared else jnp.zeros_like(m)
    for e in range(held):    # every held expert on every token
        y = y + w[:, first + e:first + e + 1] * fn["swiglu"](
            m, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
    return y


def _layer(x, lp, kind: str, fields: dict, conf: dict):
    """One layer: the mixer its number gives it, then the experts. x [T,
    d] -> [T, d]."""
    fn = _pieces(fields, conf)
    a = fn[kind](x, lp)
    return a + expert_ffn_reference(fn["norm"](a, lp["mlp_norm"]), lp,
                                    fields, conf)


def _stack_layer(stack, j: int):
    """Layer ``j`` of a stack: a plain stack's slice, or, of one stack a
    position of the period, slice j // period of stack j mod period."""
    from benchmark.harness.reference import layer

    if isinstance(stack, dict):
        return layer(stack, j)
    return layer(stack[j % len(stack)], j // len(stack))


def reference_logits(params, tokens, fields: dict, conf: dict,
                     last: int = 0):
    """tokens [T] int -> float32 logits [T, V] over this chip's slice of
    the vocabulary (or the last ``last`` positions). ``params``: device
    arrays, or numpy arrays on the host, whose leaves are then on the
    device one layer at a time (`harness/reference.py`)."""
    from benchmark.harness.reference import embedding_rows, on_device

    x = embedding_rows(params["embed"], tokens)
    for i, kind in enumerate(layer_kinds(conf, fields["n_layers"])):
        x = on_device(_stack_layer(params["layers"], i),
                      lambda lp: _layer(x, lp, kind, fields, conf))
    x = x[-last:] if last else x
    return on_device((params["final_norm"], params["lm_head"]),
                     lambda w: _pieces(fields, conf)["head"](x, *w))


# ---- the work the forward pass requires -------------------------------------


def kda_step_cost(heads: int, dk: int, dv: int) -> dict:
    """What the gated delta rule REQUIRES of one token and layer: a head's
    state decayed, read, written and read out (7 x dk x dv FLOPs); in a
    decode step the float32 state has to be read and written once (it
    lives in HBM between a slot's tokens): 2 x 4 x dk x dv bytes a head.
    Over a prompt the state never has to leave the chip's fast memory."""
    return {"flops": 7.0 * dk * dv * heads,
            "decode_bytes": 8.0 * dk * dv * heads}


def _kda_matmul_params(f: dict) -> int:
    d, width, r = f["d_model"], f["kda_heads"] * f["kda_head_dim"], \
        f["kda_gate_rank"]
    return (4 * d * width + 2 * (d * r + r * width) + d * f["kda_heads"]
            + 3 * f["kda_conv"] * width)


def _gqa_matmul_params(f: dict) -> int:
    d, H, KV, hd = f["d_model"], f["n_heads"], f["n_kv_heads"], \
        f["head_dim"]
    gate = d * H * hd if f.get("attn_output_gate") else 0
    return 2 * d * H * hd + 2 * d * KV * hd + gate


def _held(f: dict) -> int:
    return f.get("moe_held_experts") or f["moe_experts"]


def layer_flops_per_token(f: dict, seq_len: int, kind: str) -> float:
    """One layer's forward FLOPs a token: 2 a weight that multiplies (the
    mixer's projections, gates and convolutions; the router, the shared
    expert and the EXPECTED routed work on this chip, experts a token x
    held / all), plus the mixer's own: the recurrence's required work a
    token (`kda_step_cost`), or causal attention, QK^T and PV 2 x 128 each
    per query head and visible key, (T + 1) / 2 keys a query."""
    d = f["d_model"]
    if kind == "kda":
        mixer = 2.0 * _kda_matmul_params(f) + kda_step_cost(
            f["kda_heads"], f["kda_head_dim"], f["kda_head_dim"])["flops"]
    else:
        mixer = 2.0 * _gqa_matmul_params(f) + f["n_heads"] * 4 \
            * f["head_dim"] * (seq_len + 1) / 2
    ffn = (d * f["moe_experts"] + 3 * d * f["moe_shared_d_ff"]
           + 3 * d * f["d_ff"] * f["moe_top_k"] * _held(f)
           / f["moe_experts"])
    return mixer + 2.0 * ffn


def forward_flops_per_token(fields: dict, conf: dict,
                            seq_len: int) -> float:
    return sum(layer_flops_per_token(fields, seq_len, kind)
               for kind in layer_kinds(conf, fields["n_layers"])) \
        + 2.0 * fields["d_model"] * fields["vocab_size"]


def num_params(fields: dict, conf: dict) -> int:
    """What this chip holds, which is what the program's initialiser
    makes: its slice of the embedding and of the head, per layer the whole
    mixer (a KDA layer's projections, convolutions, gates, `A_log`,
    `dt_bias` and head norm; a GQA layer's projections and gate), two
    block norms, the router, its bias, the shared expert and the HELD
    experts; the final norm."""
    f = fields
    d, v = f["d_model"], f["vocab_size"]
    width = f["kda_heads"] * f["kda_head_dim"]
    mixer = {"kda": _kda_matmul_params(f) + f["kda_heads"] + width
             + f["kda_head_dim"],
             "attention": _gqa_matmul_params(f)}
    ffn = d * f["moe_experts"] + f["moe_experts"] \
        + 3 * d * f["moe_shared_d_ff"] + _held(f) * 3 * d * f["d_ff"]
    layers = sum(mixer[kind] + 2 * d + ffn
                 for kind in layer_kinds(conf, f["n_layers"]))
    return 2 * v * d + layers + d
