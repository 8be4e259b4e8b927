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

import contextlib
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.moe import (READ_IN_FLOAT32, init_moe_params, moe_layer,
                                moe_param_logical_axes, scaled_normal,
                                swiglu)
from ray_tpu.parallel.ring import (reference_attention, ring_attention,
                                   shard_map)
from ray_tpu.parallel.sharding import logical_to_spec
from ray_tpu.parallel.sharding import with_logical_constraint as _wlc

Params = Dict[str, Any]


# ---- parameter structure ---------------------------------------------------

def _per_kind(cfg: TransformerConfig, first: int, count: int, make):
    """A stack of layers ``first`` .. ``first + count``: ``make(s, j, kind,
    repetitions)`` for position j of segment s (`cfg.segments`). One
    segment of one kind: what it makes, as a stack has always been; one
    segment of several kinds: a tuple, one entry a position in the period;
    several segments: a tuple of such tuples, one a segment."""
    made = tuple(tuple(make(s, j, kind, reps)
                       for j, kind in enumerate(kinds))
                 for s, (kinds, reps) in enumerate(cfg.segments(first,
                                                                count)))
    if len(made) > 1:
        return made
    return made[0][0] if len(made[0]) == 1 else made[0]


def layer_segments(layers) -> tuple:
    """A stack as `_per_kind` builds it -> a tuple of segments, each a
    tuple of stacks, one a position of the segment's period."""
    if isinstance(layers, dict):
        return ((layers,),)
    return (tuple(layers),) if isinstance(layers[0], dict) else tuple(layers)


def _stack_axes(cfg: TransformerConfig, moe: bool,
                kind: str = "attention") -> Params:
    lay = {
        "attn_norm": ("layers", "embed"),
        "wo": ("layers", "heads", "qkv_dim", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    if cfg.norm == "layer":
        lay.update({"attn_norm_b": ("layers", "embed"),
                    "mlp_norm_b": ("layers", "embed")})
    if kind in ("mamba", "mamba2", "gmu"):
        del lay["wo"]
    if kind == "mamba2":
        lay.update({
            "mamba2_in": ("layers", "embed", "mlp"),
            "mamba2_dt": ("layers", "embed", "heads"),
            "mamba2_conv": ("layers", None, "mlp"),
            "mamba2_conv_b": ("layers", "mlp"),
            "mamba2_dt_b": ("layers", "heads"),
            "mamba2_A_log": ("layers", "heads"),
            "mamba2_D": ("layers", "heads"),
            "mamba2_norm": ("layers", "mlp"),
            "mamba2_out": ("layers", "mlp", "embed"),
        })
    elif kind == "mamba":
        lay.update({
            "mamba_in": ("layers", None, "embed", "mlp"),
            "mamba_conv": ("layers", None, "mlp"),
            "mamba_conv_b": ("layers", "mlp"),
            "mamba_x": ("layers", "mlp", None),
            "mamba_dt": ("layers", None, "mlp"),
            "mamba_dt_b": ("layers", "mlp"),
            "mamba_A_log": ("layers", None, "mlp"),
            "mamba_D": ("layers", "mlp"),
            "mamba_out": ("layers", "mlp", "embed"),
        })
    elif kind == "gmu":
        lay.update({"gmu_in": ("layers", "embed", "mlp"),
                    "gmu_out": ("layers", "mlp", "embed")})
    elif kind == "kda":
        proj = ("layers", "embed", "heads", "qkv_dim")
        lay.update({
            "kda_wq": proj, "kda_wk": proj, "kda_wv": proj,
            "kda_conv_q": ("layers", None, "heads", "qkv_dim"),
            "kda_conv_k": ("layers", None, "heads", "qkv_dim"),
            "kda_conv_v": ("layers", None, "heads", "qkv_dim"),
            "kda_f_a": ("layers", "embed", None),
            "kda_f_b": ("layers", None, "heads", "qkv_dim"),
            "kda_g_a": ("layers", "embed", None),
            "kda_g_b": ("layers", None, "heads", "qkv_dim"),
            "kda_beta": ("layers", "embed", "heads"),
            "kda_A_log": ("layers", "heads"),
            "kda_dt_bias": ("layers", "heads", "qkv_dim"),
            "kda_o_norm": ("layers", None),
        })
    elif cfg.kv_lora_rank:
        lay.update({
            "wq_a": ("layers", "embed", None),
            "q_a_norm": ("layers", None),
            "wq_b": ("layers", None, "heads", "qkv_dim"),
        } if cfg.q_lora_rank else {
            "wq": ("layers", "embed", "heads", "qkv_dim")})
        lay.update({
            "wkv_a": ("layers", "embed", None),
            "kv_a_norm": ("layers", None),
            "wkv_b": ("layers", None, "heads", "qkv_dim"),
        })
    else:
        lay["wq"] = ("layers", "embed", "heads", "qkv_dim")
        if kind != "cross":     # a cross layer reads another layer's K/V
            lay.update({
                "wk": ("layers", "embed", "kv_heads", "qkv_dim"),
                "wv": ("layers", "embed", "kv_heads", "qkv_dim"),
            })
        if cfg.attn_bias:
            lay.update({"bq": ("layers", "heads", "qkv_dim"),
                        "bo": ("layers", "embed")})
            if kind != "cross":
                lay.update({"bk": ("layers", "kv_heads", "qkv_dim"),
                            "bv": ("layers", "kv_heads", "qkv_dim")})
        if cfg.diff_attn:
            lay.update({"diff_lambda": ("layers", None, None),
                        "diff_norm": ("layers", None)})
        if cfg.attn_output_gate:
            lay["wg"] = ("layers", "embed", "heads", "qkv_dim")
    if cfg.qk_norm and kind != "kda":
        # gains over the flattened (heads x head_dim) projection: replicated
        lay.update({"q_norm": ("layers", None), "k_norm": ("layers", None)})
    if moe:
        lay.update(moe_param_logical_axes(cfg))
    else:
        lay.update({
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        })
    return lay


def param_logical_axes(cfg: TransformerConfig) -> Params:
    """Same-structure pytree of logical axis tuples (for shardings)."""
    moe = bool(cfg.moe_experts)
    dense = cfg.moe_dense_layers   # 0 without experts
    axes = {
        "embed": ("vocab", "embed"),
        "layers": _per_kind(
            cfg, dense, cfg.n_layers - dense,
            lambda s, j, kind, n: _stack_axes(cfg, moe, kind)),
        "final_norm": ("embed",),
    }
    if cfg.norm == "layer":
        axes["final_norm_b"] = ("embed",)
    if dense:
        axes["dense_layers"] = _per_kind(
            cfg, 0, dense,
            lambda s, j, kind, n: _stack_axes(cfg, False, kind))
    if cfg.mtp_layers:
        axes["mtp"] = {"h_norm": ("embed",), "e_norm": ("embed",),
                       "proj": (None, "embed"),
                       "layers": _stack_axes(cfg, moe, cfg.mixer_kind(0))}
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _init_kda(k, cfg: TransformerConfig, L: int, normal) -> Params:
    """The leaves of ``L`` stacked KDA mixers but for `wo`. `A_log` and
    `dt_bias` as the published implementation draws them (A uniform in
    [1, 16); dt log-uniform in [0.001, 0.1) through the inverse softplus),
    so that seeded decays spread over (0, 1)."""
    d, H, hd, r = cfg.d_model, cfg.kda_heads, cfg.kda_head_dim, \
        cfg.kda_gate_rank
    pd = cfg.param_dtype
    lay = {name: normal(next(k), (L, d, H, hd), d ** -0.5)
           for name in ("kda_wq", "kda_wk", "kda_wv")}
    lay.update({name: normal(next(k), (L, cfg.kda_conv, H, hd),
                             cfg.kda_conv ** -0.5)
                for name in ("kda_conv_q", "kda_conv_k", "kda_conv_v")})
    for gate in ("f", "g"):     # the decay's pair, the output gate's
        lay[f"kda_{gate}_a"] = normal(next(k), (L, d, r), d ** -0.5)
        lay[f"kda_{gate}_b"] = normal(next(k), (L, r, H, hd), r ** -0.5)
    lay["kda_beta"] = normal(next(k), (L, d, H), d ** -0.5)
    lay["kda_A_log"] = jnp.log(jax.random.uniform(
        next(k), (L, H), jnp.float32, 1.0, 16.0)).astype(pd)
    dt = jnp.exp(jax.random.uniform(next(k), (L, H, hd), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    lay["kda_dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(pd)
    lay["kda_o_norm"] = jnp.ones((L, hd), pd)
    return lay


def _init_mamba(k, cfg: TransformerConfig, L: int, normal,
                out_scale: float) -> Params:
    """The leaves of ``L`` stacked Mamba-1 mixers. `A_log`, `dt_b` and `D`
    as the published implementation draws them: A = 1 .. N a channel, the
    step's bias so that softplus gives dt log-uniform in [0.001, 0.1), D
    ones. The step's and the convolution's biases are small and made
    here; `A_log` [N, C] state-major, as the states are held."""
    d, C, N, R = cfg.d_model, cfg.mamba_channels, cfg.mamba_d_state, \
        cfg.mamba_dt_rank
    pd, taps = cfg.param_dtype, cfg.mamba_d_conv
    dt = jnp.exp(jax.random.uniform(next(k), (L, C), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    return {
        # a's matrix, then z's: the scan's input, then the gate
        "mamba_in": normal(next(k), (L, 2, d, C), d ** -0.5),
        "mamba_conv": normal(next(k), (L, taps, C), taps ** -0.5),
        "mamba_conv_b": scaled_normal(next(k), (L, C), taps ** -0.5, pd),
        # columns: the step's rank, then B, then C
        "mamba_x": normal(next(k), (L, C, R + 2 * N), C ** -0.5),
        "mamba_dt": normal(next(k), (L, R, C), R ** -0.5),
        "mamba_dt_b": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        "mamba_A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
            (L, N, C)).astype(pd),
        "mamba_D": jnp.ones((L, C), pd),
        "mamba_out": normal(next(k), (L, C, d), out_scale * (d / C) ** 0.5),
    }


def _init_mamba2(k, cfg: TransformerConfig, L: int, normal,
                 out_scale: float) -> Params:
    """The leaves of ``L`` stacked Mamba-2 mixers. `A_log`, `dt_b`, `D` and
    the norm's gain as the published implementation draws them: A = 1 .. H
    a head, the step's bias so that softplus gives dt log-uniform in
    [0.001, 0.1), D and the gain ones."""
    d, C, H = cfg.d_model, cfg.mamba_channels, cfg.mamba_heads
    wide, pd, taps = cfg.mamba2_conv_width, cfg.param_dtype, cfg.mamba_d_conv
    dt = jnp.exp(jax.random.uniform(next(k), (L, H), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    return {
        # columns: the gate z, then [x | B | C]; the step a head is a leaf
        # of its own: one published matrix [z | xBC | dt] in two, so that
        # both are whole lanes wide and neither is sliced by column
        "mamba2_in": normal(next(k), (L, d, C + wide), d ** -0.5),
        "mamba2_dt": normal(next(k), (L, d, H), d ** -0.5),
        "mamba2_conv": normal(next(k), (L, taps, wide), taps ** -0.5),
        "mamba2_conv_b": scaled_normal(next(k), (L, wide), taps ** -0.5, pd),
        "mamba2_dt_b": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        "mamba2_A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
            (L, H)).astype(pd),
        "mamba2_D": jnp.ones((L, H), pd),
        "mamba2_norm": jnp.ones((L, C), pd),
        "mamba2_out": normal(next(k), (L, C, d), out_scale * (d / C) ** 0.5),
    }


def _init_stack(k, cfg: TransformerConfig, L: int, moe: bool,
                kind: str = "attention", normal=None) -> Params:
    """``L`` stacked layers of one kind, keys drawn from the iterator
    ``k`` (the mixer first, then the FFN); ``normal`` as `init_params`
    takes it."""
    d, hd, H, KV = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.kv_heads
    pd = cfg.param_dtype
    normal = normal or functools.partial(scaled_normal, dtype=pd)
    in_scale = d ** -0.5
    # depth-scaled residual outputs, by the depth of the whole model
    out_scale = (2 * (cfg.init_depth or cfg.n_layers)) ** -0.5 * d ** -0.5
    lay = {"attn_norm": jnp.ones((L, d), pd),
           "mlp_norm": jnp.ones((L, d), pd)}
    if cfg.norm == "layer":
        lay.update({"attn_norm_b": jnp.zeros((L, d), pd),
                    "mlp_norm_b": jnp.zeros((L, d), pd)})
    out_heads = (H, cfg.v_head_dim)
    if kind == "mamba":
        lay.update(_init_mamba(k, cfg, L, normal, out_scale))
        out_heads = None
    elif kind == "mamba2":
        lay.update(_init_mamba2(k, cfg, L, normal, out_scale))
        out_heads = None
    elif kind == "gmu":
        C = cfg.mamba_channels
        lay.update({"gmu_in": normal(next(k), (L, d, C), in_scale),
                    "gmu_out": normal(next(k), (L, C, d),
                                      out_scale * (d / C) ** 0.5)})
        out_heads = None
    elif kind == "kda":
        lay.update(_init_kda(k, cfg, L, normal))
        out_heads = (cfg.kda_heads, cfg.kda_head_dim)
    elif cfg.kv_lora_rank:
        rq, rkv, rope = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.rope_head_dim
        lay.update({
            "wq_a": normal(next(k), (L, d, rq), in_scale),
            "q_a_norm": jnp.ones((L, rq), pd),
            "wq_b": normal(next(k), (L, rq, H, hd), rq ** -0.5),
        } if rq else {"wq": normal(next(k), (L, d, H, hd), in_scale)})
        lay.update({
            "wkv_a": normal(next(k), (L, d, rkv + rope), in_scale),
            "kv_a_norm": jnp.ones((L, rkv), pd),
            # per head: the unrotated part of the key, then the value
            "wkv_b": normal(next(k), (L, rkv, H, hd - rope + cfg.v_head_dim),
                            rkv ** -0.5),
        })
    else:
        lay["wq"] = normal(next(k), (L, d, H, hd), in_scale)
        if kind != "cross":
            lay.update({"wk": normal(next(k), (L, d, KV, hd), in_scale),
                        "wv": normal(next(k), (L, d, KV, hd), in_scale)})
        if cfg.attn_bias:   # small, made here, in one draw
            heads = H + (0 if kind == "cross" else 2 * KV)
            b = scaled_normal(next(k), (L, heads * hd + d), in_scale, pd)
            lay.update({"bq": b[:, :H * hd].reshape(L, H, hd),
                        "bo": b[:, heads * hd:]})
            if kind != "cross":
                lay.update({
                    "bk": b[:, H * hd:(H + KV) * hd].reshape(L, KV, hd),
                    "bv": b[:, (H + KV) * hd:heads * hd].reshape(L, KV, hd)})
        if cfg.diff_attn:
            # lq1, lk1, lq2, lk2: normal(0, 0.1), as published; the pairs'
            # norm's gain over [v1 | v2]
            lay.update({
                "diff_lambda": scaled_normal(next(k), (L, 4, hd), 0.1, pd),
                "diff_norm": jnp.ones((L, 2 * hd), pd)})
        if cfg.attn_output_gate:
            lay["wg"] = normal(next(k), (L, d, H, hd), in_scale)
    if out_heads is not None:
        lay["wo"] = normal(next(k), (L, *out_heads, d), out_scale)
    if cfg.qk_norm and kind != "kda":
        lay.update({"q_norm": jnp.ones((L, H * hd), pd),
                    "k_norm": jnp.ones((L, KV * hd), pd)})
    if moe:
        lay.update(init_moe_params(next(k), cfg, L, normal))
    else:
        ff = cfg.moe_dense_d_ff if cfg.moe_experts else cfg.d_ff
        lay.update({
            "w_gate": normal(next(k), (L, d, ff), in_scale),
            "w_up": normal(next(k), (L, d, ff), in_scale),
            "w_down": normal(next(k), (L, ff, d),
                             out_scale * (ff / d) ** 0.5),
        })
    return lay


def init_params(rng: jax.Array, cfg: TransformerConfig,
                normal=None) -> Params:
    """The model's weights from ``rng``, in ``cfg.param_dtype``.
    ``normal(key, shape, scale)`` draws a matrix: left out, a scaled
    normal made on the spot; a serving replica passes one that puts the
    draw off (`serve.llm.drawn_serving_params`), so that it can make and
    convert the matrices one at a time. Gains, biases and a KDA layer's
    decay parameters are small and made here either way."""
    d, v = cfg.d_model, cfg.vocab_size
    pd = cfg.param_dtype
    normal = normal or functools.partial(scaled_normal, dtype=pd)
    moe = bool(cfg.moe_experts)
    dense = cfg.moe_dense_layers   # 0 without experts
    n_keys = 32 if "kda" in cfg.mixer_period else 16   # a KDA layer: 14
    k = iter(jax.random.split(rng, n_keys))

    def stack(first, count, moe, own, salt):
        """Layers ``first`` .. + ``count``. One kind: from ``own``, the
        keys that stack has always drawn from; a position of a period:
        from keys of its own (and of its segment's, past the first)."""
        segments = cfg.segments(first, count)
        one = len(segments) == 1 and len(segments[0][0]) == 1

        def keys(s, j):
            key = jax.random.fold_in(rng, salt + j)
            return iter(jax.random.split(
                jax.random.fold_in(key, s) if s else key, n_keys))
        return _per_kind(cfg, first, count, lambda s, j, kind, n: _init_stack(
            own if one else keys(s, j), cfg, n, moe, kind, normal))
    params: Params = {
        # an MoE model's leading dense layers are a stack of their own
        "layers": stack(dense, cfg.n_layers - dense, moe, k, 16),
        "embed": normal(next(k), (v, d), d ** -0.5),
        "final_norm": jnp.ones((d,), pd),
    }
    if cfg.norm == "layer":
        params["final_norm_b"] = jnp.zeros((d,), pd)
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(next(k), (d, v), d ** -0.5)
    # further stacks draw from keys of their own, so the leaves above are
    # what they were before a configuration could have these
    if dense:
        params["dense_layers"] = stack(0, dense, False, iter(
            jax.random.split(jax.random.fold_in(rng, 1), n_keys)), 48)
    if cfg.mtp_layers:
        km = iter(jax.random.split(jax.random.fold_in(rng, 2), 16))
        params["mtp"] = {
            "h_norm": jnp.ones((d,), pd), "e_norm": jnp.ones((d,), pd),
            # rows: the hidden state's half, then the embedding's
            "proj": normal(next(km), (2 * d, d), (2 * d) ** -0.5),
            "layers": _init_stack(km, cfg, cfg.mtp_layers, moe,
                                  cfg.mixer_kind(0), normal),
        }
    return params


# the leaf a served tree carries beside its float32 vocabulary head
HEAD_COPY = "head_bf16"


def serving_params(params: Params, cfg: TransformerConfig,
                   shardings: Optional[Params] = None) -> Params:
    """``params`` as a serving replica holds them: every leaf in the dtype
    the forward reads it in, so no program converts a weight it reads in
    ``cfg.dtype`` (the ``.astype(cfg.dtype)`` at each use is the same
    rounding, made once here). The leaves read through
    ``.astype(float32)`` (`read_in_float32`: the vocabulary head, the MoE
    router) stay float32; a bf16 tree carries the head's bf16 copy beside
    its float32 leaf (`with_head_copy`). A leaf already in its dtype is
    returned as it is: with ``cfg.dtype`` float32, a float32 tree comes
    back untouched.
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

    if shardings is not None and HEAD_COPY in params:   # held already
        shardings = dict(shardings, **{HEAD_COPY: shardings[in_float32[0]]})
    return with_head_copy(jax.tree_util.tree_map_with_path(
        held, params, *(() if shardings is None else (shardings,))), cfg)


def with_head_copy(held: Params, cfg: TransformerConfig) -> Params:
    """``held``, a tree as a replica holds it, with the vocabulary head
    rounded ONCE to bf16 beside its float32 leaf (`read_in_float32`'s
    first: `lm_head`, or `embed` where tied), in the leaf's own layout and
    so, by the conversion, its sharding. It is the operand the MXU has
    always been given: a float32 matmul at the default precision is one
    bf16 pass with float32 accumulation, for which XLA rounded the leaf in
    every program (hoisted out of a decode chunk's substeps and no
    further: as long as the head's own matmul, once a chunk for ever).
    `lm_head` reads the copy where the tree holds it, and
    `generate.embed_tokens` a tied table's rows; the prompt pass is given
    the tree without it (`engine.prefill_slots` says why). Only a tree
    that computes in bf16 gets one: float32 compute multiplies in float32
    and its tree comes back as it is, as does one that holds the copy
    already. The ONE place that decides the copy's name, dtype, layout and
    sharding, for `serving_params` and `serve.llm.drawn_serving_params`."""
    if jnp.dtype(cfg.dtype) != jnp.bfloat16 or HEAD_COPY in held:
        return held
    leaf = held[read_in_float32(cfg)[0]]
    return dict(held, **{HEAD_COPY: leaf.astype(jnp.bfloat16)})


def without_head_copy(held: Params) -> Params:
    """``held`` less the head's copy (the leaves shared): the tree a
    program that must read the float32 leaf is given."""
    return {k: v for k, v in held.items() if k != HEAD_COPY}


# ---- building blocks -------------------------------------------------------

def rms_norm(x, gamma, eps):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * gamma.astype(x.dtype)


def layer_norm(x, gamma, beta, eps):
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * gamma.astype(x.dtype) \
        + beta.astype(x.dtype)


def block_norm(x, p, name: str, cfg: TransformerConfig):
    """The norm ``name`` of a layer (or the final one) as `cfg.norm` says:
    RMSNorm with the gain ``p[name]``, or LayerNorm with the bias
    ``p[name + "_b"]`` beside it."""
    if cfg.norm == "layer":
        return layer_norm(x, p[name], p[name + "_b"], cfg.rms_eps)
    return rms_norm(x, p[name], cfg.rms_eps)


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
    narrow = q.shape[-1] - v.shape[-1]
    if narrow and impl != "xla":
        # the kernels take one width for q, k and v: a narrower value head
        # is filled with zero columns, which come back as zero columns
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, narrow),))
        return _attention(q, k, v, cfg, mesh,
                          positions)[..., :-narrow]
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


def _latent_qkv(h, lp, cfg: TransformerConfig, positions):
    """Latent attention in its expanded (training) form: q through a
    normed latent of `q_lora_rank` (or, where that is 0, straight from
    the hidden state), keys and values through one of `kv_lora_rank`; the
    last `rope_head_dim` of each query head and ONE such vector a token
    for the keys, shared by all heads, carry the rotary embedding (none
    where `use_rope` is off: the columns stay as they are), the rest of a
    head none. -> q, k [B, T, H, head_dim] and v [B, T, H, v_head_dim]:
    ordinary multi-head attention from here on. The weights are sliced,
    not the activations."""
    dt, eps = cfg.dtype, cfg.rms_eps
    rkv, rope = cfg.kv_lora_rank, cfg.rope_head_dim
    nope = cfg.head_dim - rope
    wkv_b = lp["wkv_b"].astype(dt)
    wkv_a = lp["wkv_a"].astype(dt)
    with jax.named_scope("mla.q"):
        if cfg.q_lora_rank:
            wq, cq = lp["wq_b"].astype(dt), rms_norm(jnp.einsum(
                "btd,dr->btr", h, lp["wq_a"].astype(dt)), lp["q_a_norm"],
                eps)
        else:
            wq, cq = lp["wq"].astype(dt), h
        q_nope = jnp.einsum("btr,rhk->bthk", cq, wq[..., :nope])
        q_rope = jnp.einsum("btr,rhk->bthk", cq, wq[..., nope:])
    with jax.named_scope("mla.kv"):
        c = jnp.einsum("btd,dr->btr", h, wkv_a[:, :rkv])
        c = rms_norm(c, lp["kv_a_norm"], eps)
        k_nope = jnp.einsum("btr,rhk->bthk", c, wkv_b[..., :nope])
        v = jnp.einsum("btr,rhk->bthk", c, wkv_b[..., nope:])
        k_rope = jnp.einsum("btd,dr->btr", h, wkv_a[:, rkv:])[:, :, None]
    with jax.named_scope("mla.rope"):
        # stored pairs (2i, 2i+1) -> the halves (i, i + n/2) `_rope` turns
        halves = lambda x: jnp.concatenate(  # noqa: E731
            [x[..., 0::2], x[..., 1::2]], axis=-1)
        if cfg.use_rope:
            q_rope = _rope(halves(q_rope), positions, cfg.rope_theta)
            k_rope = _rope(halves(k_rope), positions, cfg.rope_theta)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
    return q, k, v


def qkv_proj(h, lp, cfg: TransformerConfig, positions):
    """Q/K/V projections (+ q/k norms) + RoPE — the single definition
    shared by the training forward and the KV-cache inference paths
    (models/generate, models/engine), so a numeric change lands in all."""
    if cfg.kv_lora_rank:
        return _latent_qkv(h, lp, cfg, positions)
    q = jnp.einsum("btd,dhk->bthk", h, lp["wq"].astype(cfg.dtype))
    k = jnp.einsum("btd,dhk->bthk", h, lp["wk"].astype(cfg.dtype))
    v = jnp.einsum("btd,dhk->bthk", h, lp["wv"].astype(cfg.dtype))
    if cfg.qk_norm:  # over the whole projection, all heads together
        B, T = h.shape[:2]
        q = rms_norm(q.reshape(B, T, -1), lp["q_norm"],
                     cfg.rms_eps).reshape(q.shape)
        k = rms_norm(k.reshape(B, T, -1), lp["k_norm"],
                     cfg.rms_eps).reshape(k.shape)
    if not cfg.use_rope:
        return q, k, v
    return (_rope(q, positions, cfg.rope_theta),
            _rope(k, positions, cfg.rope_theta), v)


# ---- gated delta-rule linear attention (KDA) -----------------------------------

KDA_L2_EPS = 1e-6


def _conv_silu(x, w, before=None):
    """A causal depthwise convolution along the row, then SiLU. x [B, T,
    H, D]; w [taps, H, D]: y_t = sum_i w[i] x_(t - taps + 1 + i), zeros
    before the row's first token, or ``before`` [B, taps - 1, H, D], the
    rows a decode step's slot keeps."""
    taps, T = w.shape[0], x.shape[1]
    # padded and sliced as it arrives; float32 from the products on
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0), (0, 0))) \
        if before is None else jnp.concatenate([before, x], axis=1)
    w = w.astype(jnp.float32)
    y = sum(xp[:, i:i + T].astype(jnp.float32) * w[i] for i in range(taps))
    return jax.nn.silu(y)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + KDA_L2_EPS)


def kda_mixer(h, lp, cfg: TransformerConfig, *, valid=None, tail=None,
              step=None):
    """Kimi delta attention on normed rows h [B, T, d] -> [B, T, d]: q, k,
    v each through a short causal convolution and SiLU, q and k
    l2-normalised a head; a per-channel decay exp(-exp(A_log) x softplus(
    W_f2 W_f1 h + dt_bias)) and a write strength sigmoid(W_beta h) a head
    (twice that where `cfg.kda_allow_neg_eigval`), float32; the gated
    delta rule over the row (ops/kda.py); the heads' RMSNorm times a
    sigmoid gate W_g2 W_g1 h; the output projection. Each part under a
    `jax.named_scope` a profile groups by.

    Training calls it as it is. Serving's prefill gives ``valid`` [B, T]
    bool (False on a row's left padding: such a row is zero before the
    convolution, writes nothing and decays nothing, so the first real
    token sees a fresh row) and gets (out, the final state [B, H, dk, dv]
    float32, the last `kda_conv - 1` projected rows of q, k, v [B, taps -
    1, 3 x H x dk]). Serving's decode gives T = 1, the slots' ``tail`` of
    that shape and ``step``, a function (q, k, v, g, beta) [B, H, ...] ->
    o [B, H, dv] that advances the states (engine: `kda_decode_step` on
    the layer's slice of the cache), and gets (out, the tail shifted by
    this token)."""
    from ray_tpu.ops import kda

    dt, f32 = cfg.dtype, jnp.float32
    B, T = h.shape[:2]
    H, hd, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv

    def low_rank(a, b, out):
        return jnp.einsum(
            "btr,rhk->bthk", jnp.einsum("btd,dr->btr", h, lp[a].astype(dt)),
            lp[b].astype(dt), preferred_element_type=out)

    with jax.named_scope("kda.proj"):
        q, k, v = (jnp.einsum("btd,dhk->bthk", h, lp[name].astype(dt))
                   for name in ("kda_wq", "kda_wk", "kda_wv"))
    before = (None,) * 3
    if valid is not None:
        q, k, v = (jnp.where(valid[:, :, None, None], x, 0)
                   for x in (q, k, v))
    if tail is not None:
        before = tuple(x.reshape(B, taps - 1, H, hd)
                       for x in jnp.split(tail, 3, axis=-1))
    if valid is not None or tail is not None:
        # the rows the next token's convolution reaches back to
        kept = jnp.concatenate(
            [(x if b is None else jnp.concatenate([b, x], axis=1)
              ).reshape(B, -1, H * hd) for x, b in zip((q, k, v), before)],
            axis=-1)
        short = max(0, taps - 1 - kept.shape[1])   # a row of 1 or 2 tokens
        kept = jnp.pad(kept, ((0, 0), (short, 0), (0, 0)))
        kept = kept[:, kept.shape[1] - (taps - 1):]
    with jax.named_scope("kda.conv"):
        q, k, v = (_conv_silu(x, lp[f"kda_conv_{c}"], *(() if b is None
                                                       else (b,)))
                   for x, c, b in zip((q, k, v), "qkv", before))
        q = (_l2norm(q) * cfg.kda_head_dim ** -0.5).astype(dt)
        k, v = _l2norm(k).astype(dt), v.astype(dt)
    with jax.named_scope("kda.gate"):
        g = -jnp.exp(lp["kda_A_log"].astype(f32))[:, None] * jax.nn.softplus(
            low_rank("kda_f_a", "kda_f_b", f32)
            + lp["kda_dt_bias"].astype(f32))
        beta = jax.nn.sigmoid(jnp.einsum(
            "btd,dh->bth", h, lp["kda_beta"].astype(dt),
            preferred_element_type=f32))
        if cfg.kda_allow_neg_eigval:
            beta = 2.0 * beta
        if valid is not None:
            g = jnp.where(valid[:, :, None, None], g, 0.0)
            beta = jnp.where(valid[:, :, None], beta, 0.0)
        gate = low_rank("kda_g_a", "kda_g_b", dt)
    if step is not None:
        o = step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])[:, None]
        o = o.astype(dt)
    elif valid is not None:
        with jax.named_scope("kda.prefill"):
            o, state = kda.kda_scan(q, k, v, g, beta, final_state=True)
    else:
        o = kda.kda_scan(q, k, v, g, beta)
    with jax.named_scope("kda.out"):
        o = rms_norm(o, lp["kda_o_norm"], cfg.rms_eps) \
            * jax.nn.sigmoid(gate.astype(f32)).astype(dt)
        out = jnp.einsum("bthk,hkd->btd", o, lp["wo"].astype(dt))
    if step is not None:
        return out, kept
    return (out, state, kept) if valid is not None else out


# ---- Mamba-1 selective scan, gated memory unit ------------------------------

def mamba_mixer(h, lp, cfg: TransformerConfig, *, valid=None, tail=None,
                step=None):
    """A Mamba-1 mixer on normed rows h [B, T, d]: [a, z] = W_in h; a
    through a causal depthwise convolution with a bias, then SiLU; [r, B,
    C] = W_x a; dt = softplus(W_dt r + b_dt); the selective scan with A =
    -exp(A_log) (ops/mamba.py: dt, A, the exponent and the state float32);
    y = scan + D a, which is also the MEMORY a later gated memory unit
    reads (after the skip, before the gate); out = W_out (y . silu(z)).
    Each part under a `jax.named_scope` a profile groups by.

    Prefill gives ``valid`` [B, T] bool (False on a row's left padding:
    such a row is zero before the convolution and has dt = 0, so it writes
    nothing and decays nothing) and gets (out, y, the final state [B, N, C]
    float32, the last `mamba_d_conv - 1` rows of a before the convolution
    [B, taps - 1, C]). Decode gives T = 1, the slots' ``tail`` of that shape
    and ``step``, a function (dt, a, B, C, A) -> y [B, C] float32 that
    advances the states (engine: `mamba_decode_step` on the layer's slice
    of the cache), and gets (out, y, the tail shifted by this token)."""
    from ray_tpu.ops import mamba

    dt_, f32 = cfg.dtype, jnp.float32
    T = h.shape[1]
    N, R, taps = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    with jax.named_scope("mamba.proj"):
        a, z = (jnp.einsum("btd,dc->btc", h, lp["mamba_in"][g].astype(dt_))
                for g in range(2))
    if valid is not None:
        a = jnp.where(valid[:, :, None], a, 0)
    with jax.named_scope("mamba.conv"):
        rows = jnp.pad(a, ((0, 0), (taps - 1, 0), (0, 0))) if tail is None \
            else jnp.concatenate([tail.astype(a.dtype), a], axis=1)
        kept = rows[:, rows.shape[1] - (taps - 1):]
        w = lp["mamba_conv"].astype(f32)
        a = jax.nn.silu(
            sum(rows[:, i:i + T].astype(f32) * w[i] for i in range(taps))
            + lp["mamba_conv_b"].astype(f32)).astype(dt_)
        rbc = jnp.einsum("btc,cr->btr", a, lp["mamba_x"].astype(dt_),
                         preferred_element_type=f32)
        r, Bm, Cm = rbc[..., :R], rbc[..., R:R + N], rbc[..., R + N:]
        # the step keeps float32 through its small projection
        step_size = jax.nn.softplus(jnp.einsum(
            "btr,rc->btc", r, lp["mamba_dt"].astype(f32),
            precision=jax.lax.Precision.HIGHEST)
            + lp["mamba_dt_b"].astype(f32))
        if valid is not None:
            step_size = jnp.where(valid[:, :, None], step_size, 0.0)
    A = -jnp.exp(lp["mamba_A_log"].astype(f32))
    if step is not None:
        y = step(step_size[:, 0], a[:, 0], Bm[:, 0], Cm[:, 0], A)[:, None]
    else:
        y, state = mamba.mamba_scan(step_size, a, Bm, Cm, A)
    with jax.named_scope("mamba.out"):
        y = y + lp["mamba_D"].astype(f32) * a.astype(f32)
        out = jnp.einsum(
            "btc,cd->btd", (y * jax.nn.silu(z.astype(f32))).astype(dt_),
            lp["mamba_out"].astype(dt_))
    if step is not None or valid is None:
        return out, y, kept
    return out, y, state, kept


def mamba2_gated_norm(y, z, gain, eps):
    """RMSNorm(y . silu(z)) over all channels with a gain, float32: the gate
    FIRST, then the norm (`chip_serve_controls.py` runs the other order)."""
    return rms_norm(y * jax.nn.silu(z.astype(jnp.float32)), gain, eps)


def mamba2_mixer(h, lp, cfg: TransformerConfig, *, valid=None, tail=None,
                 step=None):
    """A Mamba-2 mixer on normed rows h [B, T, d]: [z | xBC] = W_in h
    (widths C | C + 2 N), dt = W_dt h (H wide, float32 from the product on:
    the published [z | xBC | dt] matrix as two leaves); xBC
    through a causal depthwise convolution with a bias, then SiLU, and
    split [x | B | C]: x [H, P] a head, ONE B and one C [N] a token for all
    heads; dt = softplus(dt + b_dt) a head; the recurrence with the SCALAR
    A = -exp(A_log) a head (ops/mamba2.py: dt, A, the exponent and the state
    float32); y = scan + D x; the gate FIRST, then the norm: RMSNorm(y .
    silu(z)) over all C channels with a gain; out = W_out y. Each part
    under a `jax.named_scope` a profile groups by.

    Prefill gives ``valid`` [B, T] bool (False on a row's left padding:
    such a row is zero before the convolution and has dt = 0, so it writes
    nothing and decays nothing) and gets (out, the final state [B, N, C]
    float32, the last `mamba_d_conv - 1` rows of xBC before the convolution
    as ONE row [B, (taps - 1) x (C + 2 N)]: whole lanes under any tiling).
    Decode gives T = 1, the slots' ``tail`` of that shape and ``step``, a
    function (dt [B, H], x [B, H, P], B, C [B, N], A [H]) -> y [B, H, P]
    float32 that advances the states (engine: `mamba2_decode_step` on the
    layer's slice of the cache), and gets (out, the tail shifted by this
    token)."""
    from ray_tpu.ops import mamba2

    dt_, f32 = cfg.dtype, jnp.float32
    B, T = h.shape[:2]
    C, H, P, N = cfg.mamba_channels, cfg.mamba_heads, cfg.mamba_head_dim, \
        cfg.mamba_d_state
    wide, taps = cfg.mamba2_conv_width, cfg.mamba_d_conv
    with jax.named_scope("mamba2.proj"):
        zx = jnp.einsum("btd,dc->btc", h, lp["mamba2_in"].astype(dt_))
        z, xbc = zx[..., :C], zx[..., C:]
        step_size = jnp.einsum("btd,dh->bth", h, lp["mamba2_dt"].astype(dt_),
                               preferred_element_type=f32)
    if valid is not None:
        xbc = jnp.where(valid[:, :, None], xbc, 0)
    with jax.named_scope("mamba2.conv"):
        rows = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0))) if tail is None \
            else jnp.concatenate(
                [tail.astype(xbc.dtype).reshape(B, taps - 1, wide), xbc],
                axis=1)
        kept = rows[:, rows.shape[1] - (taps - 1):].reshape(B, -1)
        wc = lp["mamba2_conv"].astype(f32)
        xbc = jax.nn.silu(
            sum(rows[:, i:i + T].astype(f32) * wc[i] for i in range(taps))
            + lp["mamba2_conv_b"].astype(f32))
        x = xbc[..., :C].astype(dt_).reshape(B, T, H, P)
        Bm, Cm = xbc[..., C:C + N], xbc[..., C + N:]
        step_size = jax.nn.softplus(step_size
                                    + lp["mamba2_dt_b"].astype(f32))
        if valid is not None:
            step_size = jnp.where(valid[:, :, None], step_size, 0.0)
    A = -jnp.exp(lp["mamba2_A_log"].astype(f32))
    if step is not None:
        y = step(step_size[:, 0], x[:, 0], Bm[:, 0], Cm[:, 0], A)[:, None]
    else:
        y, state = mamba2.mamba2_scan(step_size, x, Bm, Cm, A,
                                      chunk=cfg.mamba_chunk)
    with jax.named_scope("mamba2.out"):
        y = y + lp["mamba2_D"].astype(f32)[:, None] * x.astype(f32)
        y = mamba2_gated_norm(y.reshape(B, T, C), z, lp["mamba2_norm"],
                              cfg.rms_eps)
        out = jnp.einsum("btc,cd->btd", y.astype(dt_),
                         lp["mamba2_out"].astype(dt_))
    if step is not None or valid is None:
        return out, kept
    return out, state, kept


def gmu_mixer(h, memory, lp, cfg: TransformerConfig):
    """A gated memory unit: W_out (m . silu(W_in h)), ``memory`` [B, T, C]
    float32 the same tokens' scan output of the nearest mamba layer."""
    with jax.named_scope("gmu"):
        gate = jnp.einsum("btd,dc->btc", h, lp["gmu_in"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)
        return jnp.einsum(
            "btc,cd->btd", (memory * jax.nn.silu(gate)).astype(cfg.dtype),
            lp["gmu_out"].astype(cfg.dtype))


# ---- differential attention -------------------------------------------------

def diff_qkv(h, lp, cfg: TransformerConfig):
    """The projections of a differential attention layer on normed rows h
    [B, T, d], with their biases, in PAIRS of adjacent heads: q [B, T, H /
    2, 2, 2 hd], query pair j as [q1 | 0] and [0 | q2], so that each scores
    against a key pair [k1 | k2] over whole lanes and reads only its own
    half; k, v [B, T, KV / 2, 2 hd], None for a cross layer (which holds
    no `wk`)."""
    dt = cfg.dtype
    B, T = h.shape[:2]
    hd = cfg.head_dim

    def proj(w, b):
        x = jnp.einsum("btd,dhk->bthk", h, lp[w].astype(dt))
        return x + lp[b].astype(dt) if b in lp else x
    with jax.named_scope("diff_attn.qkv"):
        q = proj("wq", "bq").reshape(B, T, cfg.n_heads // 2, 2, 1, hd)
        q = (q * jnp.eye(2, dtype=dt)[:, :, None]).reshape(
            B, T, cfg.n_heads // 2, 2, 2 * hd)
        if "wk" not in lp:
            return q, None, None
        k, v = (proj(w, b).reshape(B, T, cfg.kv_heads // 2, 2 * hd)
                for w, b in (("wk", "bk"), ("wv", "bv")))
    return q, k, v


def diff_out(o, lp, cfg: TransformerConfig, layer):
    """o [B, T, H / 2, 2, 2 hd] float32 (a pair's two softmaxes over [v1 |
    v2]) -> [B, T, d]: lam from the layer's four vectors and its index
    ``layer`` (0-based, the whole model's; traced or not), the difference
    and the pairs' RMSNorm in float32, the output projection."""
    f32 = jnp.float32
    B, T, pairs = o.shape[:3]
    with jax.named_scope("diff_attn.mix"):
        lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, f32))
        lq1, lk1, lq2, lk2 = lp["diff_lambda"].astype(f32)
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
        o = o[:, :, :, 0] - lam * o[:, :, :, 1]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_eps)
        o = (o * lp["diff_norm"].astype(f32) * (1.0 - lam0)).astype(cfg.dtype)
    with jax.named_scope("diff_attn.out"):
        out = jnp.einsum("bthk,hkd->btd",
                         o.reshape(B, T, 2 * pairs, cfg.head_dim),
                         lp["wo"].astype(cfg.dtype))
        return out + lp["bo"].astype(cfg.dtype) if "bo" in lp else out


def refuse_untrained(cfg: TransformerConfig):
    """`forward` and `loss_fn` walk one segment of attention and KDA layers:
    raise for a configuration whose layers they would compute as another
    model's (a stated layer pattern, its mamba, mamba2, gmu, window and
    cross layers, differential attention, LayerNorm, projection biases, the
    four fixed multipliers on the stream)."""
    cannot = [what for has, what in (
        (cfg.layer_pattern is not None and len(cfg.layer_pattern) > 1,
         "a layer pattern of several segments (layer_pattern)"),
        (set(cfg.mixer_period) - {"attention", "kda"},
         "mamba, mamba2, gmu, window or cross layers"),
        (any(scale is not None for scale in (
            cfg.embed_scale, cfg.residual_scale, cfg.attn_scale,
            cfg.logit_divisor)),
         "fixed multipliers on the stream (embed_scale, residual_scale, "
         "attn_scale, logit_divisor)"),
        (cfg.diff_attn, "differential attention (diff_attn)"),
        (cfg.attn_bias, "attention projection biases (attn_bias)"),
        (cfg.norm != "rms", "LayerNorm (norm)")) if has]
    if cannot:
        raise NotImplementedError(
            "training is not implemented for a configuration with "
            + "; ".join(cannot) + ": it serves (models/engine.py) and does "
            "not train yet")


def refuse_unserved(cfg: TransformerConfig):
    """The serving paths (models/generate, models/engine) walk the segments
    of ONE stack of layers, a period of mixer kinds at a time; hold for an
    attention layer one k and one v row of `head_dim` a token (a window
    layer: the last `sliding_window` of them), for a KDA or mamba layer a
    float32 state and the convolutions' tail a slot (a mamba2 layer's
    likewise), and emit one token a
    step: raise for a configuration that needs a latent cache and the
    absorbed decode form, a second stack beside the first (leading dense
    layers, a prediction module) or a step of more than one token."""
    cannot = [what for has, what in (
        (cfg.kv_lora_rank, "latent attention (kv_lora_rank: a latent slot "
         "cache and the absorbed decode form)"),
        (cfg.moe_experts and cfg.moe_dense_layers,
         "leading dense layers (moe_dense_layers: a second layer stack)"),
        (cfg.mtp_layers, "a multi-token-prediction module (mtp_layers: a "
         "decode step of more than one token)")) if has]
    if cannot:
        raise NotImplementedError(
            "serving is not implemented for a configuration with "
            + "; ".join(cannot) + ": it trains (models/transformer.py) "
            "and does not serve yet")


def _no_moe_stats():
    zero = jnp.zeros((), jnp.float32)
    return {"aux": zero, "load": zero, "held": zero, "compact": zero,
            "fetched": zero, "rows_kernel": zero}


def ffn_block(h, lp, cfg: TransformerConfig, mesh: Optional[Mesh] = None):
    """SwiGLU (or MoE) FFN -> (down, stats); shared by train + inference.
    A layer has experts if its leaves hold a router (an MoE model's
    leading dense layers hold none). stats: {"aux": load-balance loss,
    "load": largest expert group over the mean group, "held": share of
    the assignments that fall on held experts, "compact": 1.0 where the
    layer's rows fit the sorted buffer's front, "fetched": held experts
    with at least one row, "rows_kernel": 1.0 where the grouped matmuls
    were `ops.grouped_matmul`'s}, zeros for a dense layer."""
    if "router" in lp:
        return moe_layer(h, lp, cfg, mesh)
    return swiglu(h.astype(cfg.dtype), lp["w_gate"], lp["w_up"],
                  lp["w_down"], cfg, mesh), _no_moe_stats()


def lm_head(params: Params, x, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None):
    """Final norm + (tied or separate) vocabulary projection, over
    `cfg.logit_divisor` where the model states one."""
    x = block_norm(x, params, "final_norm", cfg)
    if HEAD_COPY in params:     # a replica's tree: `with_head_copy`
        head = params[HEAD_COPY]
        logits = jnp.einsum(
            "btd,vd->btv" if cfg.tie_embeddings else "btd,dv->btv",
            x.astype(head.dtype), head,
            preferred_element_type=jnp.float32)
    else:
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("btd,dv->btv", x.astype(jnp.float32),
                            head.astype(jnp.float32))
    if cfg.logit_divisor is not None:
        logits = logits / cfg.logit_divisor
    return _wlc(logits, ("batch", "seq", "vocab"), mesh=mesh)


def read_in_float32(cfg: TransformerConfig) -> tuple:
    """Names of the leaves the forward reads through ``.astype(float32)``
    and not through ``.astype(cfg.dtype)``: `lm_head`'s matrix (the
    embedding table where it is tied) and `moe.route`'s (the router, and
    the selection bias where there is one). A serving replica
    holds these in float32 and every other leaf in ``cfg.dtype``
    (`serving_params`); tests/test_serving_params.py holds the list to
    what the forward does."""
    head = "embed" if cfg.tie_embeddings else "lm_head"
    if not cfg.moe_experts:
        return (head,)
    return (head,) + tuple(n for n in READ_IN_FLOAT32
                           if n != "router_bias" or cfg.moe_select_bias)


# ---- forward ---------------------------------------------------------------

@contextlib.contextmanager
def mixer_precision(cfg: TransformerConfig, lp):
    """The dtype layer ``lp`` computes its mixer in, as a context around
    it: float32, with every matmul traced inside at the highest precision,
    for an ordinary attention layer of a model with `attn_float32`;
    ``cfg.dtype`` and nothing changed for every other layer. The caller
    hands the mixer its input in that dtype, keeps the sum after it, the
    FFN's norm and the router's input so, and rounds the layer's result
    to ``cfg.dtype`` (`moe_layer` rounds what the experts read)."""
    if cfg.attn_float32 and "kda_wq" not in lp \
            and jnp.dtype(cfg.dtype) != jnp.float32:
        with jax.default_matmul_precision("float32"):
            yield jnp.float32
    else:
        yield cfg.dtype


def attention_out(o, h, lp, cfg: TransformerConfig):
    """The attention output o [B, T, H, hd] through the layer's output
    gate, where it has one (`wg`: o . sigmoid(W_g h), h the layer's normed
    input), and the output projection -> [B, T, d]. Shared by training,
    prefill and decode."""
    if "wg" in lp:
        with jax.named_scope("attn.gate"):
            gate = jnp.einsum("btd,dhk->bthk", h, lp["wg"].astype(cfg.dtype),
                              preferred_element_type=jnp.float32)
            o = o * jax.nn.sigmoid(gate).astype(o.dtype)
    return jnp.einsum("bthk,hkd->btd", o, lp["wo"].astype(cfg.dtype))


def _attention_mixer(h, lp, cfg: TransformerConfig, mesh: Optional[Mesh],
                     positions):
    q, k, v = qkv_proj(h, lp, cfg, positions)
    reps = cfg.n_heads // cfg.kv_heads
    if reps > 1:  # GQA: expand kv heads to match q heads
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    q = _wlc(q, ("batch", "seq", "heads", None), mesh=mesh)
    o = _attention(q, k, v, cfg, mesh, positions)
    with jax.named_scope("mla.out") if cfg.kv_lora_rank \
            else contextlib.nullcontext():
        return attention_out(o, h, lp, cfg)


def _block(x, lp, cfg: TransformerConfig, mesh: Optional[Mesh], positions):
    """One decoder layer, of whichever kinds ``lp`` holds (a KDA mixer
    where it holds one's leaves, else attention; experts where it holds a
    router): x [B, T, d] -> (x, the FFN's stats)."""
    with mixer_precision(cfg, lp) as dtype:
        x = x.astype(dtype)
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        if "kda_wq" in lp:
            o = kda_mixer(h, lp, cfg)
        else:
            o = _attention_mixer(h, lp, cfg, mesh, positions)
    x = x + _wlc(o, ("batch", "seq", "embed"), mesh=mesh)

    h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    down, stats = ffn_block(h, lp, cfg, mesh)
    x = (x + _wlc(down, ("batch", "seq", "embed"), mesh=mesh)).astype(
        cfg.dtype)
    # the MoE stats ride the scan's per-layer outputs; the pipelined
    # path drops them (pipeline stages emit activations only) —
    # acceptable: aux is a regularizer, not the model output.
    return x, stats


def _block_body(cfg: TransformerConfig, mesh: Optional[Mesh], positions):
    """`_block` as a scan body (x, lp) -> (x, stats), checkpointed per
    layer where the configuration asks for it."""
    body = functools.partial(_block, cfg=cfg, mesh=mesh, positions=positions)
    if cfg.remat:
        policy = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if getattr(cfg, "remat_policy", "nothing") == "dots"
            else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy)
    return body


def _trunk(params: Params, tokens: jax.Array, cfg: TransformerConfig,
           mesh: Optional[Mesh] = None):
    """tokens [B, T] -> (the last layer's hidden state [B, T, d], before
    the final norm; the expert layers' stats, one entry a layer)."""
    refuse_untrained(cfg)
    B, T = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens]  # [B, T, d]
    x = _wlc(x, ("batch", "seq", "embed"), mesh=mesh)
    body = _block_body(cfg, mesh, jnp.arange(T))
    if mesh is not None and mesh.shape.get("pipeline", 1) > 1:
        # GPipe-style microbatched stages over the pipeline mesh axis; the
        # same block body, numerically identical to the plain scan
        # (parallel/pipeline.py).
        from ray_tpu.parallel.pipeline import pipeline_scan

        assert "dense_layers" not in params and isinstance(
            params["layers"], dict), "one stack of one kind under a pipeline"
        x = pipeline_scan(body, x, params["layers"], mesh,
                          cfg.pipeline_microbatches)
        return x, jax.tree.map(lambda z: z[None], _no_moe_stats())
    if "dense_layers" in params:   # an MoE model's leading dense layers
        x, _ = _scan_stack(body, x, params["dense_layers"])
    return _scan_stack(body, x, params["layers"])


def _scan_stack(body, x, stack):
    """``body`` over a stack of layers -> (x, the layers' stats, one
    entry a layer). One kind: a scan over the layers. Several (a tuple,
    one entry a position in the period): a scan over whole periods, whose
    body is the period's blocks one after another, each checkpointed as
    ``body`` is."""
    if isinstance(stack, dict):
        return jax.lax.scan(lambda c, lp: body(c, lp), x, stack)

    def period(c, lps):
        stats = []
        for lp in lps:
            c, st = body(c, lp)
            stats.append(st)
        return c, jax.tree.map(lambda *a: jnp.stack(a), *stats)
    x, stats = jax.lax.scan(period, x, stack)
    return x, jax.tree.map(lambda a: a.reshape(-1), stats)


def _model_stats(per_layer):
    return {"aux": per_layer["aux"].mean(), "load": per_layer["load"].max(),
            "held": per_layer["held"].mean(),
            "compact": per_layer["compact"].mean()}


def forward(params: Params, tokens: jax.Array, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None, return_aux: bool = False):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32.

    With ``return_aux=True`` returns (logits, stats): ``aux`` the MoE
    load-balance loss averaged over the expert layers, ``load`` the
    largest expert group over the mean group in the worst layer, ``held``
    the mean share of assignments on held experts (all 0.0 for dense or
    pipelined execution)."""
    x, per_layer = _trunk(params, tokens, cfg, mesh)
    logits = lm_head(params, x, cfg, mesh)
    return (logits, _model_stats(per_layer)) if return_aux else logits


def _cross_entropy(logits, targets, mask):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        denom = jnp.maximum(mask.sum(), 1.0)
        return (nll * mask).sum() / denom
    return nll.mean()


def _mtp_loss(params: Params, x, targets, mask, cfg: TransformerConfig,
              mesh: Optional[Mesh]):
    """The multi-token-prediction module (DeepSeek-V3 report, section 2.2,
    depth 1): position i joins the main stack's last hidden state h_i
    (BEFORE the final norm; the module norms it itself) with the embedding
    of the NEXT token, each under its own RMSNorm, concatenated [hidden;
    embedding] and projected 2d -> d; one more layer of the main kind;
    the main model's final norm and head; cross entropy on the token
    AFTER next. ``targets`` [B, T] are the next tokens, so all T positions
    have an input and the first T - 1 a target: the block runs on T rows
    (causal, so the last changes nothing before it) and the loss is over
    T - 1. -> (loss, the layer's stats)."""
    mp = params["mtp"]
    with jax.named_scope("mtp.merge"):
        nxt = params["embed"].astype(cfg.dtype)[targets]
        both = jnp.concatenate([rms_norm(x, mp["h_norm"], cfg.rms_eps),
                                rms_norm(nxt, mp["e_norm"], cfg.rms_eps)],
                               axis=-1)
        h = jnp.einsum("bte,ed->btd", both, mp["proj"].astype(cfg.dtype))
        h = _wlc(h, ("batch", "seq", "embed"), mesh=mesh)
    with jax.named_scope("mtp.block"):
        body = _block_body(cfg, mesh, jnp.arange(x.shape[1]))
        h, stats = body(h, jax.tree.map(lambda a: a[0], mp["layers"]))
    with jax.named_scope("mtp.head"):
        logits = lm_head(params, h[:, :-1], cfg, mesh)
        loss = _cross_entropy(logits, targets[:, 1:],
                              None if mask is None else mask[:, 1:])
    return loss, stats


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
    x, per_layer = _trunk(params, inputs, cfg, mesh)
    loss = _cross_entropy(lm_head(params, x, cfg, mesh), targets, mask)
    metrics = {"loss": loss, "perplexity": jnp.exp(loss)}
    total = loss
    if cfg.mtp_layers:
        assert cfg.causal, "a token after next needs a causal model"
        metrics["mtp_loss"], mtp_stats = _mtp_loss(params, x, targets, mask,
                                                   cfg, mesh)
        total = total + cfg.mtp_weight * metrics["mtp_loss"]
        # the module's layer counts as one more layer of the model
        per_layer = jax.tree.map(lambda a, b: jnp.append(a, b), per_layer,
                                 mtp_stats)
    if cfg.moe_experts:
        stats = _model_stats(per_layer)
        metrics["moe_aux"] = stats["aux"]
        metrics["moe_load_max_over_mean"] = stats["load"]
        if cfg.moe_held_experts is not None:
            metrics["moe_held_share"] = stats["held"]
            metrics["moe_compact_path_share"] = stats["compact"]
        total = total + cfg.moe_aux_weight * stats["aux"]
    if cfg.mtp_layers or cfg.moe_experts:
        metrics["total_loss"] = total
    return total, metrics
