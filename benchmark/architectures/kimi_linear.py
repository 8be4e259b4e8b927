"""The Kimi-Linear decoder (moonshotai/Kimi-Linear-48B-A3B-Instruct,
`model_type` `kimi_linear`): its plain reference and the work its forward
pass requires, for ONE chip's share of a deployment in which 32 chips
share each layer.

Written from the published description, not from `ray_tpu/models/`: the
keys of the model's `config.json` (the catalog row of the `model-configs`
guide) and the equations of the Kimi Linear report (arXiv:2510.26692:
Kimi Delta Attention, section 3; the hybrid with NoPE latent attention,
section 4). Pre-norm residual block, n = RMSNorm(x), eps `rms_norm_eps`:
`x += mixer(n)`, `x += ffn(RMSNorm(x))`. Layers are numbered from 1 as
`linear_attn_config` counts them.

  KDA mixer (`kda_layers`; H = 32 heads of d_k = d_v = 128), token t:
    q~ = Wq n, k~ = Wk n, v~ = Wv n                              [H x 128]
    each through its own causal depthwise convolution of
    `short_conv_kernel_size` taps, y_t[c] = sum_i w[i, c] z_(t-3+i)[c] with
    zeros before the row's first token, then SiLU
    q_t = l2norm(q) 128^-0.5, k_t = l2norm(k) a head, v_t as it is
    g_t = -exp(A_log[h]) softplus(Wf2 (Wf1 n) + dt_bias)   in R^128 a head
    a_t = exp(g_t) in (0, 1)^128, b_t = sigmoid(Wbeta n) a head
    S' = Diag(a_t) S_(t-1);  S_t = S' + b_t k_t (v_t - S'^T k_t)^T, S_0 = 0
    o_t = S_t^T q_t
    y_t = Wo [RMSNorm_head(o_t; gain in R^128) . sigmoid(Wg2 (Wg1 n))]
  NoPE latent mixer (`full_attn_layers`; 32 heads), `q_lora_rank` null,
  `mla_use_nope` true:
    q_h = Wq_h n = [q_h^nope (128) ; q_h^pe (64)]     straight from n
    [c ; k^pe] = Wkva n                          [kv_lora_rank 512 ; 64]
    [k_h^nope (128) ; v_h (128)] = Wkvb_h RMSNorm_kv(c)
    k_h = [k_h^nope ; k^pe]   ONE vector a token for all heads, NOT rotated
    y = Wo . concat_h softmax(q_h k_h^T (128 + 64)^-0.5, causal) v_h
    No position enters the model but through the KDA layers.
  feed-forward: layer 1 (`first_k_dense_replace` 1) a SwiGLU of
  `intermediate_size`; every other layer, m = RMSNorm(x):
    s   = sigmoid(Wr m) over ALL 256 experts, float32
    S   = the 8 experts of largest s + b (b a per-expert bias that takes
          no gradient; `num_expert_group` 1 = one group)
    w_e = 2.446 s_e / sum_{e' in S} s_e'    (`moe_renormalize`,
          `routed_scaling_factor`; the UNBIASED scores)
    out = SwiGLU_shared(m) + sum_{e in S} w_e SwiGLU_e(m)
  logits = Whead RMSNorm_final(x_L); the objective is the next-token cross
  entropy alone (`num_nextn_predict_layers` 0).

The share (guide, section 4; the config file's `deployment`): this chip
holds experts `first_expert` .. + `num_experts` of each layer's 256 and
rows 0 .. `vocab_size` of the vocabulary. The router scores and chooses
over all 256; the sum over S runs over the chosen experts THAT ARE HELD,
weights as above; what the absent experts would add is left out and the
partial result goes on.

What is written here otherwise than the program computes it: the KDA layer
is the recurrence above a TOKEN at a time (a `lax.scan` over the row: no
chunks, no triangular system, no cumulated decays); attention is the
plain softmax over blocks of query rows, so that 16,384 positions fit; the
experts are a Python loop over the HELD experts, each applied to every
token and weighted by that token's weight for it, or by zero. What was
assumed is in the config file's `assumed`.

It reads the program's parameter pytree because the weights ARE the
program's, made from the seed: `dense_layers` a stack of the leading
layer(s); `layers` one stack a position of the period (a tuple; a plain
stack where the model has one kind), each with a leading axis of periods.
A KDA layer's leaves: `kda_wq`/`kda_wk`/`kda_wv` [d, H, 128],
`kda_conv_q`/`_k`/`_v` [taps, H, 128], `kda_f_a` [d, r], `kda_f_b` [r, H,
128] (the decay's pair), `kda_g_a`, `kda_g_b` (the output gate's),
`kda_beta` [d, H], `kda_A_log` [H], `kda_dt_bias` [H, 128], `kda_o_norm`
[128], `wo` [H, 128, d]; a latent layer's: `wq` [d, H, 192], `wkv_a` [d,
512 + 64], `kv_a_norm`, `wkv_b` [512, H, 128 + 128], `wo` [H, 128, d].
RULES come from ``conf`` (published keys), SIZES from ``fields``. JAX is
imported inside the functions that compute.
"""

from __future__ import annotations

import functools

L2_EPS = 1e-6     # the config file's `assumed.l2norm_eps`
QUERY_BLOCK = 256


def layer_kinds(conf: dict, n_layers: int) -> list:
    """'kda' or 'attention' for layers 1 .. n_layers, from the published
    lists."""
    lin = conf["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda & full:
        raise ValueError("a layer is in kda_layers and full_attn_layers")
    kinds = []
    for i in range(1, n_layers + 1):
        if i not in kda | full:
            raise ValueError(f"layer {i} is in neither list")
        kinds.append("kda" if i in kda else "attention")
    return kinds


def fields(conf: dict) -> dict:
    """Published keys -> TransformerConfig fields. `num_experts` in the
    file is the count HELD here (listed in `reduced`); the router keeps
    the published width, `deployment.router_experts`."""
    dep, lin = conf["deployment"], conf["linear_attn_config"]
    if conf["num_expert_group"] != 1 or conf["topk_group"] != 1:
        raise ValueError("group-limited routing is not implemented")
    if conf["moe_router_activation_func"] != "sigmoid" \
            or conf["moe_layer_freq"] != 1:
        raise ValueError("only a sigmoid router in every layer after the "
                         "leading dense ones")
    if not conf["mla_use_nope"] or conf["q_lora_rank"] is not None \
            or conf["rope_scaling"] is not None:
        raise ValueError("only latent attention without positions and "
                         "without a query latent")
    if conf["num_nextn_predict_layers"]:
        raise ValueError("no prediction module is described for this model")
    kinds = layer_kinds(conf, conf["num_hidden_layers"])
    period = next(p for p in range(1, len(kinds) + 1)
                  if all(k == kinds[i % p] for i, k in enumerate(kinds)))
    return {
        "vocab_size": conf["vocab_size"],
        "d_model": conf["hidden_size"],
        "n_layers": conf["num_hidden_layers"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        # the published `head_dim` (hidden / heads) is no head's width here
        "nope_head_dim": conf["qk_nope_head_dim"],
        "rope_head_dim": conf["qk_rope_head_dim"],
        "v_head_dim": conf["v_head_dim"],
        "q_lora_rank": 0,
        "kv_lora_rank": conf["kv_lora_rank"],
        "use_rope": False,                 # mla_use_nope
        "rope_theta": float(conf["rope_theta"]),
        "rms_eps": conf["rms_norm_eps"],
        "tie_embeddings": bool(conf["tie_word_embeddings"]),
        "mixer_period": tuple(kinds[:period]),
        "kda_heads": lin["num_heads"],
        "kda_head_dim": lin["head_dim"],
        "kda_conv": lin["short_conv_kernel_size"],
        "kda_gate_rank": lin["head_dim"],
        "d_ff": conf["moe_intermediate_size"],
        "moe_dense_layers": conf["first_k_dense_replace"],
        "moe_dense_d_ff": conf["intermediate_size"],
        "moe_experts": dep["router_experts"],
        "moe_held_experts": conf["num_experts"],
        "moe_first_expert": dep["first_expert"],
        "moe_top_k": conf["num_experts_per_token"],
        "moe_scoring": "sigmoid",
        "moe_select_bias": True,
        "moe_norm_topk": bool(conf["moe_renormalize"]),
        "moe_route_scale": float(conf["routed_scaling_factor"]),
        "moe_shared_d_ff": conf["num_shared_experts"]
        * conf["moe_intermediate_size"],
        "moe_aux_weight": 0.0,             # no balance loss in the objective
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


def _kda(x, lp, *, eps):
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


def _attention(x, lp, *, rope, kv_rank, eps):
    """x [T, d] float32 -> x + latent attention without positions, the
    softmax over blocks of QUERY_BLOCK query rows."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    T = x.shape[0]
    n = _rms_norm(x, lp["attn_norm"], eps)
    q = _mm("td,dhk->thk", n, lp["wq"])                 # [T, H, 128 + 64]
    width = q.shape[-1]
    nope = width - rope
    ckv = _mm("td,dr->tr", n, lp["wkv_a"])              # [T, 512 + 64]
    kv = _mm("tr,rhk->thk", _rms_norm(ckv[:, :kv_rank], lp["kv_a_norm"],
                                      eps), lp["wkv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_pe = ckv[:, kv_rank:]                             # one a token
    block = min(QUERY_BLOCK, T)
    pad = -T % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, block, axis=0)
        s = (jnp.einsum("qhk,shk->hqs", qb[..., :nope], k_nope, precision=hp)
             + jnp.einsum("qhk,sk->hqs", qb[..., nope:], k_pe, precision=hp)
             ) * width ** -0.5
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shk->qhk", p, v, precision=hp)
    o = jax.lax.map(rows, jnp.arange(0, T + pad, block))
    o = o.reshape(T + pad, *o.shape[2:])[:T]
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
def _jitted(rope: int, kv_rank: int, eps: float, top_k: int,
            renormalize: bool, scale: float):
    import jax

    return {
        "kda": jax.jit(functools.partial(_kda, eps=eps)),
        "attention": jax.jit(functools.partial(
            _attention, rope=rope, kv_rank=kv_rank, eps=eps)),
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
    return _jitted(int(fields["rope_head_dim"]), int(fields["kv_lora_rank"]),
                   float(fields["rms_eps"]), int(fields["moe_top_k"]),
                   bool(conf["moe_renormalize"]),
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
    """One layer: the mixer the published lists give its number, the
    feed-forward its leaves hold. x [T, d] -> [T, d]."""
    fn = _pieces(fields, conf)
    a = fn[kind](x, lp)
    m = fn["norm"](a, lp["mlp_norm"])
    if "router" in lp:
        return a + expert_ffn_reference(m, lp, fields, conf)
    return a + fn["swiglu"](m, lp["w_gate"], lp["w_up"], lp["w_down"])


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
    dense = fields.get("moe_dense_layers", 0)
    kinds = layer_kinds(conf, fields["n_layers"])
    for i, kind in enumerate(kinds):
        lp = _stack_layer(params["dense_layers"], i) if i < dense \
            else _stack_layer(params["layers"], i - dense)
        x = on_device(lp, lambda lp: _layer(x, lp, kind, fields, conf))
    x = x[-last:] if last else x
    head = params["embed"].T if fields.get("tie_embeddings") \
        else params["lm_head"]
    return on_device((params["final_norm"], head),
                     lambda w: _pieces(fields, conf)["head"](x, *w))


# ---- the work the forward pass requires -------------------------------------


def kda_scan_cost(batch: int, heads: int, seq: int, dk: int, dv: int, *,
                  backward: bool = False, bytes_per_elem: int = 2) -> dict:
    """What the gated delta rule REQUIRES of one call over [batch, seq,
    heads]: the recurrence's own arithmetic, a token and head the decay of
    the state (dk x dv), the read S'^T k, the write k u^T and the output
    S^T q (2 x dk x dv each): 7 x dk x dv FLOPs; the backward pass twice
    that (the gradient for the state and for the token's inputs). A
    chunked form spends more (its triangular system, its decayed
    products) and a rematerialised step runs the forward twice: neither is
    required work. Bytes: q, k, v read and o written once in the compute
    dtype, the log-decays (float32, dk a head) and beta (float32) read;
    backward: all of those and do read, dq, dk, dv, dg and dbeta written.
    The state never has to leave the chip's fast memory."""
    rows = batch * seq * heads
    flops = 7.0 * dk * dv * rows * (2 if backward else 1)
    qkv = (2 * dk + dv) * bytes_per_elem
    gates = 4 * dk + 4
    nbytes = rows * (qkv + gates + dv * bytes_per_elem)
    if backward:
        nbytes += rows * (qkv + gates)
    return {"flops": flops, "bytes": float(nbytes)}


def _kda_matmul_params(f: dict) -> int:
    d, width, r = f["d_model"], f["kda_heads"] * f["kda_head_dim"], \
        f["kda_gate_rank"]
    return (4 * d * width + 2 * (d * r + r * width) + d * f["kda_heads"]
            + 3 * f["kda_conv"] * width)


def _latent_matmul_params(f: dict) -> int:
    d, H = f["d_model"], f["n_heads"]
    nope, rope, vd, rkv = (f["nope_head_dim"], f["rope_head_dim"],
                           f["v_head_dim"], f["kv_lora_rank"])
    return (d * H * (nope + rope) + d * (rkv + rope)
            + rkv * H * (nope + vd) + H * vd * d)


def _held(f: dict) -> int:
    return f.get("moe_held_experts") or f["moe_experts"]


def layer_flops_per_token(f: dict, seq_len: int, kind: str,
                          moe: bool) -> float:
    """One layer's forward FLOPs a token: 2 a weight that multiplies (the
    mixer's projections, gates and convolutions; the router, the shared
    expert and the EXPECTED routed work on this chip, experts a token x
    held / all; or the dense SwiGLU), plus the mixer's own: the scan's
    required work a token (`kda_scan_cost`), or causal attention at the
    whole head widths, QK^T 2 x (128 + 64) and PV 2 x 128 per head, query
    and visible key, (T + 1) / 2 keys a query."""
    d = f["d_model"]
    if kind == "kda":
        mixer = 2.0 * _kda_matmul_params(f) + kda_scan_cost(
            1, f["kda_heads"], 1, f["kda_head_dim"],
            f["kda_head_dim"])["flops"]
    else:
        mixer = 2.0 * _latent_matmul_params(f) + f["n_heads"] * 2 * (
            f["nope_head_dim"] + f["rope_head_dim"] + f["v_head_dim"]) \
            * (seq_len + 1) / 2
    if moe:
        ffn = (d * f["moe_experts"] + 3 * d * f["moe_shared_d_ff"]
               + 3 * d * f["d_ff"] * f["moe_top_k"] * _held(f)
               / f["moe_experts"])
    else:
        ffn = 3 * d * f["moe_dense_d_ff"]
    return mixer + 2.0 * ffn


def forward_flops_per_token(fields: dict, conf: dict,
                            seq_len: int) -> float:
    f = fields
    dense = f["moe_dense_layers"]
    kinds = layer_kinds(conf, f["n_layers"])
    return sum(layer_flops_per_token(f, seq_len, kind, i >= dense)
               for i, kind in enumerate(kinds)) \
        + 2.0 * f["d_model"] * f["vocab_size"]


def num_params(fields: dict, conf: dict) -> int:
    """What this chip holds: its slice of the embedding and of the head,
    per layer the whole mixer (a KDA layer's projections, convolutions,
    gates, `A_log`, `dt_bias` and head norm; a latent layer's projections
    and latent norm), two block norms and either the dense SwiGLU or the
    router, its bias, the shared expert and the HELD experts; the final
    norm."""
    f = fields
    d, v = f["d_model"], f["vocab_size"]
    width = f["kda_heads"] * f["kda_head_dim"]
    mixer = {"kda": _kda_matmul_params(f) + f["kda_heads"] + width
             + f["kda_head_dim"],
             "attention": _latent_matmul_params(f) + f["kv_lora_rank"]}
    expert_ffn = d * f["moe_experts"] + f["moe_experts"] \
        + 3 * d * f["moe_shared_d_ff"] + _held(f) * 3 * d * f["d_ff"]
    dense_ffn = 3 * d * f["moe_dense_d_ff"]
    dense = f["moe_dense_layers"]
    layers = sum(mixer[kind] + 2 * d
                 + (expert_ffn if i >= dense else dense_ffn)
                 for i, kind in enumerate(layer_kinds(conf, f["n_layers"])))
    head = 0 if f.get("tie_embeddings") else d * v
    return v * d + layers + d + head
