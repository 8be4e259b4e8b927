"""The plain reference: a decoder block's forward pass and the next-token
loss in straightforward `jax.numpy`, float32, matmuls at "highest"
precision (on a TPU a float32 matmul otherwise runs in bf16 passes). No
kernel, no cache, no batching tricks, no scan: a Python loop over layers.

Written from the published description of the InternLM2 / Mistral /
Llama block, not from `ray_tpu/models/transformer.py`:

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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma


def _rope(x, theta):
    """x [T, H, D] at positions 0..T-1, half-split convention."""
    T, _, D = x.shape
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, lp, *, n_heads, n_kv_heads, theta, eps):
    """One decoder block on one sequence: x [T, d] float32."""
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
    n = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    return jnp.einsum("td,dv->tv", n, head.astype(jnp.float32),
                      precision=HIGHEST)


@functools.lru_cache(maxsize=None)
def _jitted(n_heads: int, n_kv_heads: int, theta: float, eps: float):
    """The block and the head, jitted once per set of sizes: a fresh
    `jax.jit(partial(...))` per call would trace again every time."""
    return (jax.jit(functools.partial(_block, n_heads=n_heads,
                                      n_kv_heads=n_kv_heads, theta=theta,
                                      eps=eps)),
            jax.jit(functools.partial(_head, eps=eps)))


def reference_logits(params, tokens, fields: dict, last: int = 0):
    """tokens [T] int -> float32 logits [T, V] (or the last ``last``
    positions). ``fields``: the TransformerConfig field dict of the
    configuration (n_heads, n_kv_heads, rope_theta, rms_eps, n_layers,
    tie_embeddings). Each block is one jitted call, so a deep model
    compiles one block once."""
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


def reference_loss(logits, targets):
    """Mean next-token cross entropy: logits [T, V] against targets [T]."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.asarray(targets)[:, None],
                               axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# ---- the comparison that decides `correct` --------------------------------

# Relative RMS error of the program's logits against the reference's,
# by the compute dtype the configuration STATES. float32: both sides do
# the same float32 arithmetic in another order, 1e-6-class error through a
# few layers; 2e-4 leaves room for depth and long rows and is 50 times
# under what bf16 anywhere in the block gives. bfloat16: activations and
# matmul inputs rounded to 8 bits of mantissa (2^-9 relative each) through
# every layer, float32 accumulation and a float32 vocabulary head; read on
# the chip at 18 layers x 2048 wide (my chip runs, PR 23): 0.026 for the
# train forward over 4,096 positions, 0.030-0.038 for prefill and decode
# through the slot cache. The bound is a little over twice the worst
# reading; an 8-bit float or integer path (2^-4 relative, sixteen times
# bf16's rounding) lands several times above it.
LOGIT_REL_RMS_TOL = {"float32": 2e-4, "bfloat16": 8e-2}
# |loss - reference loss| on the compared rows: a mean over thousands of
# positions, so rounding errors average out; bf16 reads 1e-3-class.
LOSS_ABS_TOL = {"float32": 1e-4, "bfloat16": 1e-2}


def rel_rms_error(got, want) -> float:
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2)
                          / jnp.mean(want ** 2)))


def logits_agree(got, want, compute_dtype: str) -> dict:
    err = rel_rms_error(got, want)
    tol = LOGIT_REL_RMS_TOL[compute_dtype]
    finite = bool(jnp.all(jnp.isfinite(jnp.asarray(got))))
    return {"rel_rms_error": err, "tolerance": tol,
            "ok": finite and err <= tol}
