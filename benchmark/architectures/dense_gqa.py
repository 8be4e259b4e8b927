"""The dense GQA decoder block (InternLM2 / Mistral / Llama): its plain
reference and the work its forward pass requires. The architecture of
every config file that names none.

The reference is the block's forward pass in straightforward `jax.numpy`,
float32, matmuls at "highest" precision (on a TPU a float32 matmul
otherwise runs in bf16 passes). No kernel, no cache, no batching tricks,
no scan: a Python loop over layers. Written from the published
description of the block, not from `ray_tpu/models/transformer.py`:

    h   = x + Wo . Attn(RoPE(Wq n1), RoPE(Wk n1), Wv n1),  n1 = RMSNorm(x)
    out = h + Wdown . (SiLU(Wgate n2) * (Wup n2)),          n2 = RMSNorm(h)
    logits = Whead . RMSNorm(x_L)

with grouped-query attention (each group of heads/kv_heads query heads
shares one key/value head), causal softmax(QK^T / sqrt(head_dim)), rotary
embedding in the half-split ("rotate_half") convention of the published
checkpoints with base `rope_theta`, no biases, untied head. InternLM2's
checkpoint stores Wq, Wk, Wv fused as one `wqkv`; that is the same
mathematics as three projections.

It reads the program's parameter pytree (layer weights stacked on a
leading axis) because the weights ARE the program's, made from the seed;
everything it computes with them is its own.

The counts follow the rules at the top of `harness/flops.py`. ``fields``
is the TransformerConfig field dict `spec.transformer_fields` makes from
the config file, ``conf`` the file itself (this block needs nothing from
it). JAX is imported inside the functions that compute: the driver
process loads this module for its counts and never imports JAX.
"""

from __future__ import annotations

import functools

# ---- the plain reference ---------------------------------------------------


def _rms_norm(x, gamma, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma


def _rope(x, theta):
    """x [T, H, D] at positions 0..T-1, half-split convention."""
    import jax.numpy as jnp

    T, _, D = x.shape
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, lp, *, n_heads, n_kv_heads, theta, eps):
    """One decoder block on one sequence: x [T, d] float32."""
    import jax
    import jax.numpy as jnp

    HIGHEST = jax.lax.Precision.HIGHEST
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    T, d = x.shape
    hd = d // n_heads
    n1 = _rms_norm(x, f32(lp["attn_norm"]), eps)
    q = jnp.einsum("td,dhk->thk", n1, f32(lp["wq"]), precision=HIGHEST)
    k = jnp.einsum("td,dhk->thk", n1, f32(lp["wk"]), precision=HIGHEST)
    v = jnp.einsum("td,dhk->thk", n1, f32(lp["wv"]), precision=HIGHEST)
    q, k = _rope(q, theta), _rope(k, theta)
    reps = n_heads // n_kv_heads
    k = jnp.repeat(k, reps, axis=1)   # each kv head serves `reps` q heads
    v = jnp.repeat(v, reps, axis=1)
    s = jnp.einsum("qhk,shk->hqs", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqs,shk->qhk", p, v, precision=HIGHEST)
    h = x + jnp.einsum("qhk,hkd->qd", o, f32(lp["wo"]), precision=HIGHEST)
    n2 = _rms_norm(h, f32(lp["mlp_norm"]), eps)
    gate = jnp.einsum("td,df->tf", n2, f32(lp["w_gate"]), precision=HIGHEST)
    up = jnp.einsum("td,df->tf", n2, f32(lp["w_up"]), precision=HIGHEST)
    return h + jnp.einsum("tf,fd->td", jax.nn.silu(gate) * up,
                          f32(lp["w_down"]), precision=HIGHEST)


def _head(x, final_norm, head, eps):
    import jax
    import jax.numpy as jnp

    n = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    return jnp.einsum("td,dv->tv", n, head.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=None)
def _jitted(n_heads: int, n_kv_heads: int, theta: float, eps: float):
    """The block and the head, jitted once per set of sizes: a fresh
    `jax.jit(partial(...))` per call would trace again every time."""
    import jax

    return (jax.jit(functools.partial(_block, n_heads=n_heads,
                                      n_kv_heads=n_kv_heads, theta=theta,
                                      eps=eps)),
            jax.jit(functools.partial(_head, eps=eps)))


def reference_logits(params, tokens, fields: dict, conf: dict,
                     last: int = 0):
    """tokens [T] int -> float32 logits [T, V] (or the last ``last``
    positions). ``fields``: the TransformerConfig field dict of the
    configuration (n_heads, n_kv_heads, rope_theta, rms_eps, n_layers,
    tie_embeddings). Each block is one jitted call, so a deep model
    compiles one block once."""
    import jax
    import jax.numpy as jnp

    block, head_fn = _jitted(
        fields["n_heads"], fields.get("n_kv_heads") or fields["n_heads"],
        float(fields["rope_theta"]), float(fields["rms_eps"]))
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    for i in range(fields["n_layers"]):
        x = block(x, jax.tree.map(lambda a: a[i], params["layers"]))
    head = params["embed"].T if fields.get("tie_embeddings") \
        else params["lm_head"]
    if last:
        x = x[-last:]
    return head_fn(x, params["final_norm"], head)


# ---- the work the forward pass requires -------------------------------------


def matmul_params(fields: dict) -> dict:
    """Weights that take part in a matrix multiplication per token: the
    embedding lookup is a gather and does no arithmetic; norms are
    elementwise and left out."""
    d, ff = fields["d_model"], fields["d_ff"]
    H = fields["n_heads"]
    KV = fields.get("n_kv_heads") or H
    hd = d // H
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff
    return {"per_layer": per_layer, "head": d * fields["vocab_size"],
            "total": fields["n_layers"] * per_layer
            + d * fields["vocab_size"]}


def num_params(fields: dict, conf: dict) -> int:
    """All weights held (embedding, blocks with their two norms, final
    norm, untied head)."""
    d, v, L = fields["d_model"], fields["vocab_size"], fields["n_layers"]
    mm = matmul_params(fields)
    head = 0 if fields.get("tie_embeddings") else d * v
    return v * d + L * (mm["per_layer"] + 2 * d) + d + head


def forward_flops_per_token(fields: dict, conf: dict,
                            seq_len: int) -> float:
    """2 FLOPs per weight that multiplies, plus causal attention: QK^T and
    PV are each 2*T*hd per head and query, of which causality needs half
    (a query at position t attends t+1 keys; mean (T+1)/2)."""
    d, L = fields["d_model"], fields["n_layers"]
    attn = L * 2 * 2 * d * (seq_len + 1) / 2
    return 2.0 * matmul_params(fields)["total"] + attn
