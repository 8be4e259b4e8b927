"""The OLMoE decoder block (allenai/OLMoE-1B-7B): its plain reference and
the work its forward pass requires.

Written from the published description of the block (config.json of
OLMoE-1B-7B-0125-Instruct and the model card's architecture: "OLMoE: Open
Mixture-of-Experts Language Models"), not from `ray_tpu/models/`:

    q = RMSNorm_q(Wq n1), k = RMSNorm_k(Wk n1), v = Wv n1,   n1 = RMSNorm(x)
        (each norm over the WHOLE projection, all heads together, with its
        own learned gain, before the split into heads and before RoPE)
    h   = x + Wo . Attn(RoPE(q), RoPE(k), v)
    p   = softmax(Wr n2) over ALL experts, in float32,        n2 = RMSNorm(h)
    S   = the `num_experts_per_tok` experts of largest p per token; their
          weights are p as it is (`norm_topk_prob` false) or p / sum_S p
    out = h + sum_{e in S} p_e . Wdown_e (SiLU(Wgate_e n2) * (Wup_e n2))
    logits = Whead . RMSNorm(x_L)

causal softmax(QK^T / sqrt(head_dim)) over `num_attention_heads` heads with
`num_key_value_heads` key/value heads, rotary embedding in the half-split
convention with base `rope_theta`, no biases, no shared expert, `clip_qkv`
null (no clipping), untied head.

The experts are a Python loop over ALL `num_experts`, each applied to every
token and weighted by that token's routing weight for it, or by zero: no
sort, no gather, no grouping, nothing the program's dispatch could share a
fault with. (64 times the arithmetic a token needs; the check runs it on
one row of 4,096 positions outside the window.)

The auxiliary loss is the load-balancing loss of the published
implementation, per layer: num_experts * sum_e f_e * P_e, with f_e the
share of tokens that have expert e among their top k (summing to k over
the experts) and P_e the mean router probability of e; `reference_aux_loss`
averages it over the layers. (The `transformers` forward pools the tokens
of all layers before it multiplies, which is the same number when the
layers' loads agree; the paper's equation is per layer.)

It reads the program's parameter pytree (layer weights stacked on a
leading axis; `router` [L,d,E], `w_gate`/`w_up` [L,E,d,f], `w_down`
[L,E,f,d], `q_norm` [L,H*hd], `k_norm` [L,KV*hd]) because the weights ARE
the program's, made from the seed; everything it computes with them is its
own. JAX is imported inside the functions that compute: the driver process
loads this module for its counts and never imports JAX.
"""

from __future__ import annotations

import functools


def fields(conf: dict) -> dict:
    """Published keys -> TransformerConfig fields. `intermediate_size` is
    the width of ONE expert (the catalog's reading of a config that has no
    key of its own for it). The auxiliary loss is in the training loss only
    where the config asks for the router's logits, as the published
    forward has it."""
    aux = conf.get("router_aux_loss_coef", 0.01) \
        if conf.get("output_router_logits") else 0.0
    return {
        "vocab_size": conf["vocab_size"],
        "d_model": conf["hidden_size"],
        "n_layers": conf["num_hidden_layers"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        "d_ff": conf["intermediate_size"],
        "rope_theta": float(conf["rope_theta"]),
        "rms_eps": conf["rms_norm_eps"],
        "tie_embeddings": bool(conf["tie_word_embeddings"]),
        "moe_experts": conf["num_experts"],
        "moe_top_k": conf["num_experts_per_tok"],
        "moe_norm_topk": bool(conf["norm_topk_prob"]),
        "moe_aux_weight": float(aux),
        "qk_norm": True,
    }


# ---- the plain reference ---------------------------------------------------


def _dense():
    """The dense block's reference, for what the two blocks share to the
    letter: RMSNorm, the half-split rotary embedding, the head."""
    import os

    from benchmark.harness import spec

    return spec.load_architecture({}, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def _attention(x, lp, *, n_heads, n_kv_heads, theta, eps):
    """x [T, d] float32 -> x + attention, with the q/k norms."""
    import jax
    import jax.numpy as jnp

    HIGHEST = jax.lax.Precision.HIGHEST
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    _rms_norm, _rope = _dense()._rms_norm, _dense()._rope
    T, d = x.shape
    hd = d // n_heads
    n1 = _rms_norm(x, f32(lp["attn_norm"]), eps)
    q = jnp.einsum("td,dn->tn", n1, f32(lp["wq"]).reshape(d, -1),
                   precision=HIGHEST)
    k = jnp.einsum("td,dn->tn", n1, f32(lp["wk"]).reshape(d, -1),
                   precision=HIGHEST)
    v = jnp.einsum("td,dn->tn", n1, f32(lp["wv"]).reshape(d, -1),
                   precision=HIGHEST)
    q = _rms_norm(q, f32(lp["q_norm"]), eps)   # over all heads together
    k = _rms_norm(k, f32(lp["k_norm"]), eps)
    q = _rope(q.reshape(T, n_heads, hd), theta)
    k = _rope(k.reshape(T, n_kv_heads, hd), theta)
    v = v.reshape(T, n_kv_heads, hd)
    reps = n_heads // n_kv_heads
    k = jnp.repeat(k, reps, axis=1)
    v = jnp.repeat(v, reps, axis=1)
    s = jnp.einsum("qhk,shk->hqs", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqs,shk->qhk", p, v, precision=HIGHEST)
    return x + jnp.einsum("qhk,hkd->qd", o, f32(lp["wo"]), precision=HIGHEST)


def _route(n2, router, *, top_k, norm_topk):
    """n2 [T, d], router [d, E] -> (weights [T, E]: the routing weight of
    each token for each expert, zero outside its top k; probs [T, E];
    keep [T, E]: whether the expert is among the token's top k)."""
    import jax
    import jax.numpy as jnp

    logits = jnp.einsum("td,de->te", n2, router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    # the k-th largest probability of each token; experts at or above it
    # are its top k (a tie at the threshold is a measure-zero event for
    # float32 softmaxes of random weights, and would keep both)
    kth = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
    keep = probs >= kth
    weights = jnp.where(keep, probs, 0.0)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, probs, keep


def _expert(n2, w_gate, w_up, w_down):
    """One expert applied to every token: n2 [T, d] -> [T, d]."""
    import jax
    import jax.numpy as jnp

    HIGHEST = jax.lax.Precision.HIGHEST
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    gate = jnp.einsum("td,df->tf", n2, f32(w_gate), precision=HIGHEST)
    up = jnp.einsum("td,df->tf", n2, f32(w_up), precision=HIGHEST)
    return jnp.einsum("tf,fd->td", jax.nn.silu(gate) * up, f32(w_down),
                      precision=HIGHEST)


@functools.lru_cache(maxsize=None)
def _jitted(n_heads: int, n_kv_heads: int, theta: float, eps: float,
            top_k: int, norm_topk: bool):
    """The pieces, jitted once per set of sizes (one expert is one jitted
    call, so 64 experts compile one)."""
    import jax

    return (jax.jit(functools.partial(_attention, n_heads=n_heads,
                                      n_kv_heads=n_kv_heads, theta=theta,
                                      eps=eps)),
            jax.jit(functools.partial(_dense()._rms_norm, eps=eps)),
            jax.jit(functools.partial(_route, top_k=top_k,
                                      norm_topk=norm_topk)),
            jax.jit(_expert),
            jax.jit(functools.partial(_dense()._head, eps=eps)))


def _pieces(fields: dict, conf: dict):
    """Sizes come from ``fields`` (a test may run a toy size), the routing
    RULE from the published key in ``conf``: a program configured to
    another rule than the published one must not agree."""
    return _jitted(fields["n_heads"],
                   fields.get("n_kv_heads") or fields["n_heads"],
                   float(fields["rope_theta"]), float(fields["rms_eps"]),
                   int(fields["moe_top_k"]), bool(conf["norm_topk_prob"]))


def moe_ffn_reference(n2, lp, fields: dict, conf: dict):
    """The expert branch alone on normed rows n2 [T, d] float32 with one
    layer's weights: -> (y [T, d], aux loss of the layer, keep [T, E])."""
    import jax.numpy as jnp

    _, _, route, expert, _ = _pieces(fields, conf)
    E = fields["moe_experts"]
    weights, probs, keep = route(n2, lp["router"])
    y = jnp.zeros_like(n2)
    for e in range(E):   # every expert on every token, weighted or zeroed
        y = y + weights[:, e:e + 1] * expert(
            n2, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
    share = jnp.mean(keep.astype(jnp.float32), axis=0)   # sums to k
    aux = E * jnp.sum(share * jnp.mean(probs, axis=0))
    return y, aux, keep


def _forward(params, tokens, fields: dict, conf: dict, last: int = 0):
    import jax
    import jax.numpy as jnp

    attention, norm, _, _, head_fn = _pieces(fields, conf)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    auxes = []
    for i in range(fields["n_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = attention(x, lp)
        n2 = norm(h, lp["mlp_norm"].astype(jnp.float32))
        y, aux, _ = moe_ffn_reference(n2, lp, fields, conf)
        x = h + y
        auxes.append(aux)
    head = params["embed"].T if fields.get("tie_embeddings") \
        else params["lm_head"]
    if last:
        x = x[-last:]
    return head_fn(x, params["final_norm"], head), sum(auxes) / len(auxes)


# The auxiliary loss of the forward pass made last, with what it was made
# from: `reference_terms` is asked about the row `reference_logits` has
# just run, and the check pays one pass for both (a second, at the cell's
# size, is a second of every run's set-up). Weak references to the
# weights' leaves: the same (immutable) arrays, still alive.
_LAST_PASS: dict = {}


def _pass_key(params, tokens, fields: dict, conf: dict):
    import weakref

    import jax
    import numpy as np

    return ([weakref.ref(x) for x in jax.tree.leaves(params)],
            np.asarray(tokens).tobytes(),
            repr(sorted(fields.items())), bool(conf["norm_topk_prob"]))


def _same_pass(key) -> bool:
    leaves, *rest = key
    was_leaves, *was = _LAST_PASS.get("key", ([], None))
    return rest == was and len(leaves) == len(was_leaves) and all(
        a() is not None and a() is b() for a, b in zip(leaves, was_leaves))


def reference_logits(params, tokens, fields: dict, conf: dict,
                     last: int = 0):
    """tokens [T] int -> float32 logits [T, V] (or the last ``last``
    positions)."""
    logits, aux = _forward(params, tokens, fields, conf, last)
    _LAST_PASS.update(key=_pass_key(params, tokens, fields, conf), aux=aux)
    return logits


def reference_aux_loss(params, tokens, fields: dict, conf: dict):
    """The load-balancing loss on one sequence, averaged over layers."""
    if _same_pass(_pass_key(params, tokens, fields, conf)):
        return _LAST_PASS["aux"]
    return _forward(params, tokens, fields, conf)[1]


# |program's `moe_aux` - reference's| on the check's row. The term is
# E * sum_e f_e * P_e with f_e a COUNT of top-k memberships: a token whose
# k-th and (k+1)-th probabilities lie within bf16's rounding of the router's
# input flips, and moves 1/T of a share between two experts whose mean
# probabilities differ widely at random weights (the term reads 16.0-21.5
# where balance gives k = 8), so the gap swings from seed to seed. Read at
# the cell's size, 1 x 4096, 3 layers, bf16 (`benchmark/term_limits.py`,
# my chip runs, PR 31): sound runs 0.00012-0.0269 over 23 seeds (three
# over 0.01: the cross entropy's limit refused them); the float8 control
# 0.0052-0.76 (it does not separate here; it fails the logits on every
# seed, 0.127-0.142 against 0.08). What this number is for is a term
# DEFINED otherwise: shares counted over assignments instead of tokens
# (1/k of it) reads 14.0-18.8 on those seeds' references, a sum over the
# layers instead of their mean 32-43. The limit lies nine times over the
# largest sound reading and fifty times under the least such fault.
# float32: both sides route alike and the gap is rounding (1e-6 at toy
# size), the cross entropy's limit.
TERM_ABS_TOL = {"moe_aux": {"float32": 1e-4, "bfloat16": 0.25}}


def reference_terms(params, tokens, fields: dict, conf: dict) -> dict:
    """The further terms of the published objective on one row of the
    batch (``tokens`` [T + 1]: inputs and shifted targets), by the name
    the program reports each under: `moe_aux`, the load-balancing loss of
    the row's T input positions. The check holds the program's number to
    it whether or not the cell's objective gives it a weight (the config
    file's `objective`; `output_router_logits` false: none)."""
    return {"moe_aux": float(reference_aux_loss(params, tokens[:-1], fields,
                                                conf))}


# ---- the work the forward pass requires -------------------------------------


def matmul_params(fields: dict) -> dict:
    """Weights that multiply per token: attention projections, the router,
    and the `moe_top_k` experts a token is routed to (not the others);
    the embedding lookup is a gather, norms are elementwise."""
    d, f = fields["d_model"], fields["d_ff"]
    H = fields["n_heads"]
    KV = fields.get("n_kv_heads") or H
    hd = d // H
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    router = d * fields["moe_experts"]
    routed = fields["moe_top_k"] * 3 * d * f
    per_layer = attn + router + routed
    head = d * fields["vocab_size"]
    return {"per_layer": per_layer, "head": head,
            "total": fields["n_layers"] * per_layer + head}


def num_params(fields: dict, conf: dict) -> int:
    """All weights held: embedding, per layer attention, router, ALL
    experts, four norm gains (two block norms, q and k), final norm,
    untied head."""
    d, v, L, f = (fields["d_model"], fields["vocab_size"],
                  fields["n_layers"], fields["d_ff"])
    H = fields["n_heads"]
    KV = fields.get("n_kv_heads") or H
    hd = d // H
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    per_layer = attn + d * fields["moe_experts"] \
        + fields["moe_experts"] * 3 * d * f \
        + 2 * d + H * hd + KV * hd
    head = 0 if fields.get("tie_embeddings") else d * v
    return v * d + L * per_layer + d + head


def active_params(fields: dict, conf: dict) -> int:
    """Weights one token meets: as `num_params`, with `moe_top_k` experts
    in place of all of them."""
    idle = (fields["moe_experts"] - fields["moe_top_k"]) \
        * 3 * fields["d_model"] * fields["d_ff"]
    return num_params(fields, conf) - fields["n_layers"] * idle


def forward_flops_per_token(fields: dict, conf: dict,
                            seq_len: int) -> float:
    """2 FLOPs per weight that multiplies (the routed experts and the
    router, not the experts a token never meets), plus causal attention:
    QK^T and PV are each 2*T*hd per head and query, of which causality
    needs half (mean (T+1)/2 keys a query)."""
    d, L = fields["d_model"], fields["n_layers"]
    attn = L * 2 * 2 * d * (seq_len + 1) / 2
    return 2.0 * matmul_params(fields)["total"] + attn
