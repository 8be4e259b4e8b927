"""The GLM-4.7-Flash decoder (zai-org/GLM-4.7-Flash, `model_type`
`glm4_moe_lite`): its plain reference and the work its forward pass
requires, for ONE chip's share of a deployment in which 8 chips share
each layer.

Written from the published description, not from `ray_tpu/models/`: the
keys of the model's `config.json` (the catalog row of the `model-configs`
guide) and its family's block, the DeepSeek-V3 block (`transformers`
4.57.6, `models/deepseek_v3/modeling_deepseek_v3.py`, read for the
attention, the router and the expert layer; DeepSeek-V3 Technical Report,
section 2.2, for the prediction module). With n = RMSNorm(x):

  latent attention (every layer)
    cq = RMSNorm_q(Wqa n)                               [q_lora_rank]
    q_h = Wqb_h cq = [q_h^nope (192) ; q_h^rope (64)]   per head h of 20
    [c ; k^rope] = Wkva n                               [kv_lora_rank ; 64]
    [k_h^nope (192) ; v_h (256)] = Wkvb_h RMSNorm_kv(c)
    k_h = [k_h^nope ; RoPE(k^rope)]   ONE rotary key a token, all heads
    q_h = [q_h^nope ; RoPE(q_h^rope)]    the nope parts are not rotated
    a   = x + Wo . concat_h softmax(q_h k_h^T (192 + 64)^-0.5, causal) v_h
  leading `first_k_dense_replace` layers:  out = a + SwiGLU_10240(RMSNorm(a))
  expert layers, m = RMSNorm(a):
    s   = sigmoid(Wr m) over ALL 64 experts, float32
    S   = the 4 experts of largest s + b (b a per-expert bias that takes
          no gradient; `topk_method` noaux_tc, `n_group` 1 = one group)
    w_e = 1.8 . s_e / sum_{e' in S} s_e'   for e in S (the UNBIASED scores;
          `norm_topk_prob`, `routed_scaling_factor`)
    out = a + SwiGLU_shared(m) + sum_{e in S} w_e . SwiGLU_e(m)
  logits = Whead . RMSNorm_final(x_L)
  prediction module (`num_nextn_predict_layers` 1), position i of T - 1:
    h'_i = M [RMSNorm_h(x_L,i) ; RMSNorm_e(Emb(t_{i+1}))]    2 x 2048 -> 2048
    one more expert layer of the same kind (own attention, router, shared
    expert, experts), then the main model's final norm and head; cross
    entropy against t_{i+2}: `mtp_loss`.

The share (guide, section 4; the config file's `deployment`): this chip
holds experts `moe_first_expert` .. + `moe_held_experts` of each layer's
64 and rows 0 .. `vocab_size` of the vocabulary. The router scores and
chooses over all 64; the sum over S runs over the chosen experts THAT ARE
HELD, weights as above (normalised over all 4 chosen); what the absent
experts would add is left out and the partial result goes on. The
embedding, the head, the logits and both cross entropies are over the
slice.

Departures from the published description, and what was assumed:
  - the rotary pairing: `deepseek_v3`'s `rope_interleave` defaults to true
    (columns 2i, 2i+1 of a stored rotary vector turn by frequency i) and
    the catalog row holds no such key; taken as true (the config file's
    `assumed`). Scores are the same whichever way both q and k are
    permuted, so it is written here as the rotation of pairs in place.
  - the prediction module is handed the main stack's last hidden state
    BEFORE the final norm (the report's equation 21 norms h^{k-1} itself)
    and concatenates [hidden ; embedding] in the equation's order; served
    implementations hand it the normed state and put the embedding first,
    which at seeded random weights is a permutation of M's rows. The
    final norm's gain is the main model's (the checkpoint's module has a
    norm of its own before the shared head; the issue reuses the main
    one).
  - the selection bias b is a leaf of zeros that nothing moves (the rule
    that moves it in training is outside the gradient and not here).
  - the experts are a Python loop over the HELD experts, each applied to
    every token and weighted by that token's weight for it, or by zero: no
    sort, no gather, no grouping, nothing the program's dispatch could
    share a fault with.

It reads the program's parameter pytree because the weights ARE the
program's, made from the seed (`dense_layers` and `layers`: stacks with a
leading layer axis; `mtp`: `h_norm`, `e_norm`, `proj` [2d, d] and a stack
of one layer; per layer `wq_a`, `q_a_norm`, `wq_b` [r, H, 256], `wkv_a`
[d, 512 + 64], `kv_a_norm`, `wkv_b` [r, H, 192 + 256], `wo`, `router`
[d, 64], `router_bias` [64], `w_gate`/`w_up`/`w_down` of the held experts,
`ws_*` of the shared one); everything it computes with them is its own.
RULES come from ``conf`` (published keys), SIZES from ``fields``. JAX is
imported inside the functions that compute.
"""

from __future__ import annotations

import functools

def fields(conf: dict) -> dict:
    """Published keys -> TransformerConfig fields. `n_routed_experts` in
    the file is the count HELD here (listed in `reduced`); the router keeps
    the published width, `deployment.router_experts`."""
    dep = conf["deployment"]
    if conf["n_group"] != 1 or conf["topk_group"] != 1:
        raise ValueError("group-limited routing is not implemented")
    if conf["topk_method"] != "noaux_tc" or conf["rope_scaling"] is not None:
        raise ValueError("only topk_method noaux_tc without rope_scaling")
    if not conf["rope_interleave"]:   # the program's latent attention
        raise ValueError("rotary columns paired by halves are not "
                         "implemented under latent attention")
    return {
        "vocab_size": conf["vocab_size"],
        "d_model": conf["hidden_size"],
        "n_layers": conf["num_hidden_layers"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        "v_head_dim": conf["v_head_dim"],
        "rope_head_dim": conf["qk_rope_head_dim"],
        "q_lora_rank": conf["q_lora_rank"],
        "kv_lora_rank": conf["kv_lora_rank"],
        "rope_theta": float(conf["rope_theta"]),
        "rms_eps": conf["rms_norm_eps"],
        "tie_embeddings": bool(conf["tie_word_embeddings"]),
        "d_ff": conf["moe_intermediate_size"],
        "moe_dense_layers": conf["first_k_dense_replace"],
        "moe_dense_d_ff": conf["intermediate_size"],
        "moe_experts": dep["router_experts"],
        "moe_held_experts": conf["n_routed_experts"],
        "moe_first_expert": dep["first_expert"],
        "moe_top_k": conf["num_experts_per_tok"],
        "moe_scoring": "sigmoid",          # topk_method noaux_tc
        "moe_select_bias": True,
        "moe_norm_topk": bool(conf["norm_topk_prob"]),
        "moe_route_scale": float(conf["routed_scaling_factor"]),
        "moe_shared_d_ff": conf["n_shared_experts"]
        * conf["moe_intermediate_size"],
        "moe_aux_weight": 0.0,             # noaux_tc: no balance loss
        "mtp_layers": conf["num_nextn_predict_layers"],
        "mtp_weight": float(conf["objective"]["mtp_loss"]),
    }


# ---- the plain reference ---------------------------------------------------


def _rms_norm(x, gamma, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma.astype(jnp.float32)


def _rotate(x, theta, interleave):
    """x [T, H, R] at positions 0..T-1. interleave: columns (2i, 2i+1)
    are a pair that turns by theta^(-2i/R) a position; else (i, i + R/2)."""
    import jax.numpy as jnp

    T, _, R = x.shape
    half = R // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _mm(eq, a, w):
    import jax
    import jax.numpy as jnp

    return jnp.einsum(eq, a, w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _attention(x, lp, *, rope, kv_rank, theta, eps, interleave):
    """x [T, d] float32 -> x + latent attention."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    n = _rms_norm(x, lp["attn_norm"], eps)
    q = _mm("tr,rhk->thk", _rms_norm(_mm("td,dr->tr", n, lp["wq_a"]),
                                     lp["q_a_norm"], eps), lp["wq_b"])
    width = q.shape[-1]                       # 192 + 64
    nope = width - rope
    ckv = _mm("td,dr->tr", n, lp["wkv_a"])    # [T, kv_rank + rope]
    kv = _mm("tr,rhk->thk", _rms_norm(ckv[:, :kv_rank], lp["kv_a_norm"],
                                      eps), lp["wkv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rot = _rotate(q[..., nope:], theta, interleave)
    k_rot = _rotate(ckv[:, None, kv_rank:], theta, interleave)  # [T, 1, R]
    hp = jax.lax.Precision.HIGHEST
    s = (jnp.einsum("qhk,shk->hqs", q[..., :nope], k_nope, precision=hp)
         + jnp.einsum("qhk,sk->hqs", q_rot, k_rot[:, 0], precision=hp)) \
        * width ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqs,shk->qhk", p, v, precision=hp)
    return x + _mm("qhk,hkd->qd", o, lp["wo"])


def _swiglu(m, w_gate, w_up, w_down):
    import jax

    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", m, w_gate))
               * _mm("td,df->tf", m, w_up), w_down)


def _route(m, router, bias, *, top_k, norm_topk, scale):
    """m [T, d] -> weights [T, E]: each token's weight for each of ALL
    the experts, zero outside its top k."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(_mm("td,de->te", m, router))
    choice = s + bias.astype(jnp.float32)[None, :]
    # the k-th largest biased score of each token; experts at or above it
    # are chosen (a tie there is a measure-zero event at random weights)
    keep = choice >= jnp.sort(choice, axis=-1)[:, -top_k][:, None]
    w = jnp.where(keep, s, 0.0)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale


@functools.lru_cache(maxsize=None)
def _jitted(rope: int, kv_rank: int, theta: float, eps: float,
            interleave: bool, top_k: int, norm_topk: bool, scale: float):
    import jax

    return {
        "attention": jax.jit(functools.partial(
            _attention, rope=rope, kv_rank=kv_rank, theta=theta, eps=eps,
            interleave=interleave)),
        "norm": jax.jit(functools.partial(_rms_norm, eps=eps)),
        "route": jax.jit(functools.partial(
            _route, top_k=top_k, norm_topk=norm_topk, scale=scale)),
        "swiglu": jax.jit(_swiglu),
        "head": jax.jit(lambda x, g, w: _mm("td,dv->tv",
                                            _rms_norm(x, g, eps), w)),
    }


def _rules(fields: dict, conf: dict) -> tuple:
    """Sizes from ``fields`` (a test runs a toy size), rules from the
    published keys in ``conf``: a program configured to another rule than
    the published one must not agree."""
    return (int(fields["rope_head_dim"]), int(fields["kv_lora_rank"]),
            float(fields["rope_theta"]), float(fields["rms_eps"]),
            bool(conf["rope_interleave"]), int(fields["moe_top_k"]),
            bool(conf["norm_topk_prob"]),
            float(conf["routed_scaling_factor"]))


def _pieces(fields: dict, conf: dict):
    return _jitted(*_rules(fields, conf))


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


def _layer(x, lp, fields: dict, conf: dict):
    """One layer of whichever kind ``lp`` holds: x [T, d] -> [T, d]."""
    fn = _pieces(fields, conf)
    a = fn["attention"](x, lp)
    m = fn["norm"](a, lp["mlp_norm"])
    if "router" in lp:
        return a + expert_ffn_reference(m, lp, fields, conf)
    return a + fn["swiglu"](m, lp["w_gate"], lp["w_up"], lp["w_down"])


def _embed(params, tokens):
    import jax.numpy as jnp

    return jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32),
                    axis=0).astype(jnp.float32)


def _head_weights(params, fields: dict):
    return params["embed"].T if fields.get("tie_embeddings") \
        else params["lm_head"]


def reference_hidden(params, tokens, fields: dict, conf: dict):
    """tokens [T] -> the last layer's hidden state [T, d], before the
    final norm."""
    import jax

    x = _embed(params, tokens)
    dense = fields.get("moe_dense_layers", 0)
    for i in range(fields["n_layers"]):
        stack, j = (params["dense_layers"], i) if i < dense \
            else (params["layers"], i - dense)
        x = _layer(x, jax.tree.map(lambda a: a[j], stack), fields, conf)
    return x


# The hidden state of the forward pass made last, with what it was made
# from: `reference_terms` is asked about the row `reference_logits` has just
# run, and the check pays one pass of the main stack for both. Weak
# references to the weights' leaves: the same (immutable) arrays, still
# alive; nothing here keeps a tree on the device.
_LAST_PASS: dict = {}


def _pass_key(params, tokens, fields: dict, conf: dict):
    import weakref

    import jax
    import numpy as np

    return ([weakref.ref(x) for x in jax.tree.leaves(params)],
            np.asarray(tokens).tobytes(), repr(sorted(fields.items())),
            _rules(fields, conf))


def _same_pass(key) -> bool:
    leaves, *rest = key
    was_leaves, *was = _LAST_PASS.get("key", ([], None))
    return rest == was and len(leaves) == len(was_leaves) and all(
        a() is not None and a() is b() for a, b in zip(leaves, was_leaves))


def reference_logits(params, tokens, fields: dict, conf: dict,
                     last: int = 0):
    """tokens [T] int -> float32 logits [T, V] over this chip's slice of
    the vocabulary (or the last ``last`` positions)."""
    x = reference_hidden(params, tokens, fields, conf)
    _LAST_PASS.update(key=_pass_key(params, tokens, fields, conf), hidden=x)
    return _pieces(fields, conf)["head"](
        x[-last:] if last else x, params["final_norm"],
        _head_weights(params, fields))


def reference_mtp_loss(params, tokens, fields: dict, conf: dict,
                       hidden=None):
    """tokens [T + 1] (a row's inputs and shifted targets) -> the
    prediction module's mean cross entropy over the T - 1 positions that
    have a token after next. ``hidden``: the main stack's last hidden
    state on tokens[:-1], where the caller has it."""
    import jax
    import jax.numpy as jnp

    fn, mp = _pieces(fields, conf), params["mtp"]
    tokens = jnp.asarray(tokens, jnp.int32)
    if hidden is None:
        hidden = reference_hidden(params, tokens[:-1], fields, conf)
    both = jnp.concatenate([                       # positions i < T - 1
        fn["norm"](hidden[:-1], mp["h_norm"]),
        fn["norm"](_embed(params, tokens[1:-1]), mp["e_norm"])], axis=-1)
    h = _mm("te,ed->td", both, mp["proj"])
    h = _layer(h, jax.tree.map(lambda a: a[0], mp["layers"]), fields, conf)
    logits = fn["head"](h, params["final_norm"],
                        _head_weights(params, fields))
    gold = jnp.take_along_axis(logits, tokens[2:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def reference_terms(params, tokens, fields: dict, conf: dict) -> dict:
    """The further term of the published objective on one row of the batch
    (``tokens`` [T + 1]), by the name the program reports it under."""
    hidden = None
    if _same_pass(_pass_key(params, tokens[:-1], fields, conf)):
        hidden = _LAST_PASS["hidden"]
    _LAST_PASS.clear()
    return {"mtp_loss": float(reference_mtp_loss(params, tokens, fields,
                                                 conf, hidden))}


def reference_objective(params, tokens, fields: dict, conf: dict):
    """The objective on one row as a differentiable number: cross entropy
    + the config file's weight x `mtp_loss` (the tests' `jax.grad`)."""
    import jax
    import jax.numpy as jnp

    tokens = jnp.asarray(tokens, jnp.int32)
    hidden = reference_hidden(params, tokens[:-1], fields, conf)
    logits = _pieces(fields, conf)["head"](
        hidden, params["final_norm"], _head_weights(params, fields))
    gold = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
    return loss + conf["objective"]["mtp_loss"] * reference_mtp_loss(
        params, tokens, fields, conf, hidden)


# ---- the work the forward pass requires -------------------------------------


def _attention_matmul_params(f: dict) -> int:
    d, H, hd = f["d_model"], f["n_heads"], f["head_dim"]
    rq, rkv, rope, vd = (f["q_lora_rank"], f["kv_lora_rank"],
                         f["rope_head_dim"], f["v_head_dim"])
    return (d * rq + rq * H * hd + d * (rkv + rope)
            + rkv * H * (hd - rope + vd) + H * vd * d)


def _held(f: dict) -> int:
    return f.get("moe_held_experts") or f["moe_experts"]


def layer_flops_per_token(f: dict, seq_len: int, moe: bool) -> float:
    """One layer's forward FLOPs a token: 2 a weight that multiplies
    (the latent projections; the router, the shared expert and the
    EXPECTED routed work on this chip, experts a token x held / all; or
    the dense SwiGLU) plus causal attention at the whole head widths:
    QK^T 2*head_dim and PV 2*v_head_dim per head, query and visible key,
    (T + 1) / 2 keys a query."""
    d, H = f["d_model"], f["n_heads"]
    attn = H * 2 * (f["head_dim"] + f["v_head_dim"]) * (seq_len + 1) / 2
    if moe:
        ffn = (d * f["moe_experts"] + 3 * d * f["moe_shared_d_ff"]
               + 3 * d * f["d_ff"] * f["moe_top_k"] * _held(f)
               / f["moe_experts"])
    else:
        ffn = 3 * d * f["moe_dense_d_ff"]
    return 2.0 * (_attention_matmul_params(f) + ffn) + attn


def forward_flops_per_token(fields: dict, conf: dict,
                            seq_len: int) -> float:
    """Main stack (dense then expert layers) and head, plus the
    prediction module on T - 1 of a row's T positions: its projection
    [2d -> d], its layer (attention over T - 1 positions) and its own
    pass through the head."""
    f = fields
    d, v = f["d_model"], f["vocab_size"]
    dense = f["moe_dense_layers"]
    main = dense * layer_flops_per_token(f, seq_len, False) \
        + (f["n_layers"] - dense) * layer_flops_per_token(f, seq_len, True) \
        + 2.0 * d * v
    mtp = f["mtp_layers"] * (seq_len - 1) / seq_len * (
        2.0 * 2 * d * d + layer_flops_per_token(f, seq_len - 1, True)
        + 2.0 * d * v)
    return main + mtp


def num_params(fields: dict, conf: dict) -> int:
    """What this chip holds: its slice of the embedding and of the head,
    per layer the whole attention (with its two latent norms), two block
    norms and either the dense SwiGLU or the router, its bias, the shared
    expert and the HELD experts; the final norm; the prediction module's
    two norms, projection and layer."""
    f = fields
    d, v = f["d_model"], f["vocab_size"]
    attn = _attention_matmul_params(f) + f["q_lora_rank"] \
        + f["kv_lora_rank"] + 2 * d
    expert_layer = attn + d * f["moe_experts"] + f["moe_experts"] \
        + 3 * d * f["moe_shared_d_ff"] + _held(f) * 3 * d * f["d_ff"]
    dense_layer = attn + 3 * d * f["moe_dense_d_ff"]
    dense = f["moe_dense_layers"]
    head = 0 if f.get("tie_embeddings") else d * v
    return (v * d + dense * dense_layer
            + (f["n_layers"] - dense) * expert_layer + d + head
            + f["mtp_layers"] * (2 * d + 2 * d * d + expert_layer))
