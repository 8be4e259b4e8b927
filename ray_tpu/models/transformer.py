"""Llama-family decoder in functional JAX: pytree params, scan over layers.

TPU-first design choices:
  - Layer weights are *stacked* on a leading `layers` axis and the block is a
    `lax.scan` body — one trace/compile of the block regardless of depth, and
    a natural substrate for pipeline parallelism later.
  - Every parameter and activation carries *logical* axis names; actual
    sharding comes from `ray_tpu.parallel.sharding` rules, so the same model
    runs DP, FSDP, TP, and ring-CP unchanged.
  - Compute in bfloat16 on the MXU, loss/softmax accumulation float32;
    the trainer's master params are float32 (`param_dtype`), a serving
    replica holds them as the forward reads them (`serving_params`).
  - `jax.checkpoint` on the scanned block trades FLOPs for HBM (remat).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models.config import TransformerConfig
from ray_tpu.parallel.ring import (reference_attention, ring_attention,
                                   shard_map)
from ray_tpu.parallel.sharding import logical_to_spec
from ray_tpu.parallel.sharding import with_logical_constraint as _wlc

Params = Dict[str, Any]


# ---- parameter structure ---------------------------------------------------

def param_logical_axes(cfg: TransformerConfig) -> Params:
    """Same-structure pytree of logical axis tuples (for shardings)."""
    lay = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads", "qkv_dim"),
        "wk": ("layers", "embed", "kv_heads", "qkv_dim"),
        "wv": ("layers", "embed", "kv_heads", "qkv_dim"),
        "wo": ("layers", "heads", "qkv_dim", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    if cfg.qk_norm:
        # gains over the flattened (heads x head_dim) projection: replicated
        lay.update({"q_norm": ("layers", None), "k_norm": ("layers", None)})
    if cfg.moe_experts:
        from ray_tpu.models.moe import moe_param_logical_axes

        lay.update(moe_param_logical_axes())
    else:
        lay.update({
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        })
    axes = {
        "embed": ("vocab", "embed"),
        "layers": lay,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Params:
    d, v, L = cfg.d_model, cfg.vocab_size, cfg.n_layers
    hd, H, KV, ff = cfg.head_dim, cfg.n_heads, cfg.kv_heads, cfg.d_ff
    pd = cfg.param_dtype
    k = iter(jax.random.split(rng, 16))

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(pd)

    emb_scale = d ** -0.5
    in_scale = d ** -0.5
    out_scale = (2 * L) ** -0.5 * d ** -0.5  # depth-scaled residual outputs
    lay = {
        "attn_norm": jnp.ones((L, d), pd),
        "wq": normal(next(k), (L, d, H, hd), in_scale),
        "wk": normal(next(k), (L, d, KV, hd), in_scale),
        "wv": normal(next(k), (L, d, KV, hd), in_scale),
        "wo": normal(next(k), (L, H, hd, d), out_scale),
        "mlp_norm": jnp.ones((L, d), pd),
    }
    if cfg.qk_norm:
        lay.update({"q_norm": jnp.ones((L, H * hd), pd),
                    "k_norm": jnp.ones((L, KV * hd), pd)})
    if cfg.moe_experts:
        from ray_tpu.models.moe import init_moe_params

        lay.update(init_moe_params(next(k), cfg))
    else:
        lay.update({
            "w_gate": normal(next(k), (L, d, ff), in_scale),
            "w_up": normal(next(k), (L, d, ff), in_scale),
            "w_down": normal(next(k), (L, ff, d),
                             out_scale * (ff / d) ** 0.5),
        })
    params: Params = {
        "embed": normal(next(k), (v, d), emb_scale),
        "layers": lay,
        "final_norm": jnp.ones((d,), pd),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(next(k), (d, v), in_scale)
    return params


def serving_params(params: Params, cfg: TransformerConfig,
                   shardings: Optional[Params] = None) -> Params:
    """``params`` as a serving replica holds them: every leaf in the dtype
    the forward reads it in, so no program converts a weight it reads in
    ``cfg.dtype`` (the ``.astype(cfg.dtype)`` at each use is the same
    rounding, made once here). The leaves read through
    ``.astype(float32)`` (`read_in_float32`: the vocabulary head, the MoE
    router) stay float32. A leaf already in its dtype is returned as it
    is: with ``cfg.dtype`` float32, a float32 tree comes back untouched.
    Converted leaf by leaf (after its ``device_put`` where ``shardings``
    gives one, which the conversion keeps), so a second whole tree stands
    beside the first only as long as the caller keeps the first.
    """
    in_float32 = read_in_float32(cfg)

    def held(path, x, sharding=None):
        want = jnp.dtype(jnp.float32 if path[-1].key in in_float32
                         else cfg.dtype)
        x = jnp.asarray(x) if sharding is None \
            else jax.device_put(x, sharding)
        return x if x.dtype == want else x.astype(want)

    return jax.tree_util.tree_map_with_path(
        held, params, *(() if shardings is None else (shardings,)))


# ---- building blocks -------------------------------------------------------

def rms_norm(x, gamma, eps):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * gamma.astype(x.dtype)


def _rope(x, positions, theta):
    """Rotary embedding. x: [B, T, H, D]; positions: [T] (shared across
    the batch) or [B, T] (per-row — continuous-batching decode, where
    each cache slot sits at its own write position)."""
    d_half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, d_half, dtype=jnp.float32) / d_half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [...,T,Dh]
    if angles.ndim == 2:
        angles = angles[None]  # shared positions: broadcast over batch
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _select_attention(cfg: TransformerConfig, mesh: Optional[Mesh]):
    impl = cfg.attention_impl
    if impl == "auto":
        if mesh is not None and mesh.shape.get("sequence", 1) > 1:
            impl = "ring"
        elif jax.default_backend() != "cpu":
            impl = "pallas"
        else:
            impl = "xla"
    return impl


def _attention(q, k, v, cfg: TransformerConfig, mesh: Optional[Mesh],
               positions):
    impl = _select_attention(cfg, mesh)
    if impl == "ring":
        return ring_attention(q, k, v, mesh, causal=cfg.causal)
    if impl == "pallas":
        from ray_tpu.ops import flash_attention  # lazy: pallas import cost
        attn = functools.partial(flash_attention, causal=cfg.causal)
        if mesh is None or mesh.size == 1:
            return attn(q, k, v)
        if mesh.shape.get("sequence", 1) > 1:
            raise ValueError(
                "attention_impl='pallas' keeps whole K/V per (batch, head) "
                "and cannot shard the sequence; use 'ring' (or 'auto') on "
                "a mesh whose sequence axis is > 1")
        # GSPMD cannot partition a Mosaic kernel: run it per shard over
        # the mesh axes the batch and heads logical axes map to (K/V were
        # already expanded to n_heads, so one spec serves all three).
        spec = logical_to_spec(("batch", None, "heads", None),
                               mesh_axes=mesh.axis_names)
        return shard_map(attn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)
    return reference_attention(q, k, v, causal=cfg.causal)


def qkv_proj(h, lp, cfg: TransformerConfig, positions):
    """Q/K/V projections (+ q/k norms) + RoPE — the single definition
    shared by the training forward and the KV-cache inference paths
    (models/generate, models/engine), so a numeric change lands in all."""
    q = jnp.einsum("btd,dhk->bthk", h, lp["wq"].astype(cfg.dtype))
    k = jnp.einsum("btd,dhk->bthk", h, lp["wk"].astype(cfg.dtype))
    v = jnp.einsum("btd,dhk->bthk", h, lp["wv"].astype(cfg.dtype))
    if cfg.qk_norm:  # over the whole projection, all heads together
        B, T = h.shape[:2]
        q = rms_norm(q.reshape(B, T, -1), lp["q_norm"],
                     cfg.rms_eps).reshape(q.shape)
        k = rms_norm(k.reshape(B, T, -1), lp["k_norm"],
                     cfg.rms_eps).reshape(k.shape)
    return (_rope(q, positions, cfg.rope_theta),
            _rope(k, positions, cfg.rope_theta), v)


def _no_moe_stats():
    zero = jnp.zeros((), jnp.float32)
    return {"aux": zero, "load": zero}


def ffn_block(h, lp, cfg: TransformerConfig, mesh: Optional[Mesh] = None):
    """SwiGLU (or MoE) FFN -> (down, stats); shared by train + inference.
    stats: {"aux": load-balance loss, "load": largest expert group over
    the mean group}, zeros for a dense layer."""
    if cfg.moe_experts:
        from ray_tpu.models.moe import moe_layer

        return moe_layer(h, lp, cfg, mesh)
    gate = jnp.einsum("btd,df->btf", h, lp["w_gate"].astype(cfg.dtype))
    up = jnp.einsum("btd,df->btf", h, lp["w_up"].astype(cfg.dtype))
    ff = jax.nn.silu(gate) * up
    ff = _wlc(ff, ("batch", "seq", "mlp"), mesh=mesh)
    down = jnp.einsum("btf,fd->btd", ff, lp["w_down"].astype(cfg.dtype))
    return down, _no_moe_stats()


def lm_head(params: Params, x, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None):
    """Final norm + (tied or separate) vocabulary projection."""
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("btd,dv->btv", x.astype(jnp.float32),
                        head.astype(jnp.float32))
    return _wlc(logits, ("batch", "seq", "vocab"), mesh=mesh)


def read_in_float32(cfg: TransformerConfig) -> tuple:
    """Names of the leaves the forward reads through ``.astype(float32)``
    and not through ``.astype(cfg.dtype)``: `lm_head`'s matrix (the
    embedding table where it is tied) and `moe.route`'s. A serving replica
    holds these in float32 and every other leaf in ``cfg.dtype``
    (`serving_params`); tests/test_serving_params.py holds the list to
    what the forward does."""
    from ray_tpu.models.moe import READ_IN_FLOAT32

    head = "embed" if cfg.tie_embeddings else "lm_head"
    return (head,) + (READ_IN_FLOAT32 if cfg.moe_experts else ())


# ---- forward ---------------------------------------------------------------

def forward(params: Params, tokens: jax.Array, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None, return_aux: bool = False):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32.

    With ``return_aux=True`` returns (logits, stats): ``aux`` the MoE
    load-balance loss averaged over the layers, ``load`` the largest
    expert group over the mean group in the worst layer (both 0.0 for
    dense or pipelined execution)."""
    B, T = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens]  # [B, T, d]
    x = _wlc(x, ("batch", "seq", "embed"), mesh=mesh)
    positions = jnp.arange(T)

    def block(x, lp):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = qkv_proj(h, lp, cfg, positions)
        reps = cfg.n_heads // cfg.kv_heads
        if reps > 1:  # GQA: expand kv heads to match q heads
            k = jnp.repeat(k, reps, axis=2)
            v = jnp.repeat(v, reps, axis=2)
        q = _wlc(q, ("batch", "seq", "heads", None), mesh=mesh)
        o = _attention(q, k, v, cfg, mesh, positions)
        o = jnp.einsum("bthk,hkd->btd", o, lp["wo"].astype(cfg.dtype))
        x = x + _wlc(o, ("batch", "seq", "embed"), mesh=mesh)

        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        down, stats = ffn_block(h, lp, cfg, mesh)
        x = x + _wlc(down, ("batch", "seq", "embed"), mesh=mesh)
        # the MoE stats ride the scan's per-layer outputs; the pipelined
        # path drops them (pipeline stages emit activations only) —
        # acceptable: aux is a regularizer, not the model output.
        return x, stats

    body = block
    if cfg.remat:
        policy = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if getattr(cfg, "remat_policy", "nothing") == "dots"
            else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy)
    stats = _no_moe_stats()
    if mesh is not None and mesh.shape.get("pipeline", 1) > 1:
        # GPipe-style microbatched stages over the pipeline mesh axis; the
        # same block body, numerically identical to the plain scan
        # (parallel/pipeline.py).
        from ray_tpu.parallel.pipeline import pipeline_scan

        x = pipeline_scan(body, x, params["layers"], mesh,
                          cfg.pipeline_microbatches)
    else:
        x, per_layer = jax.lax.scan(
            lambda c, lp: body(c, lp), x, params["layers"])
        stats = {"aux": per_layer["aux"].mean(),
                 "load": per_layer["load"].max()}

    logits = lm_head(params, x, cfg, mesh)
    return (logits, stats) if return_aux else logits


def loss_fn(params: Params, batch: Dict[str, jax.Array],
            cfg: TransformerConfig, mesh: Optional[Mesh] = None):
    """Next-token cross entropy. batch: {"tokens": [B,T]} (targets shifted)
    or {"inputs": [B,T], "targets": [B,T], optional "mask": [B,T]}."""
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
        mask = batch.get("mask")
    else:
        toks = batch["tokens"]
        inputs, targets = toks[:, :-1], toks[:, 1:]
        mask = None
    logits, stats = forward(params, inputs, cfg, mesh, return_aux=True)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (nll * mask).sum() / denom
    else:
        loss = nll.mean()
    metrics = {"loss": loss, "perplexity": jnp.exp(loss)}
    if cfg.moe_experts:
        metrics["moe_aux"] = stats["aux"]
        metrics["moe_load_max_over_mean"] = stats["load"]
        loss = loss + cfg.moe_aux_weight * stats["aux"]
        metrics["total_loss"] = loss
    return loss, metrics
