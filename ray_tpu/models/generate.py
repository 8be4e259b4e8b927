"""The building blocks of the serving programs: the prompt pass, attention
and the table of served mixer kinds (`MIXERS`).

`ray_tpu.models.engine` compiles its two programs from these (prefill:
`_prefill_hidden` + `_final_logits`; decode: its own layer scan over the
table's one-token steps). There is no decode loop or cache of this
module's own. TPU-first design:

  - Static shapes everywhere: a prompt is left-padded to a bucket and
    its K/V padded out to ``max_len``, so each (rows, bucket) pair is one
    compiled program.
  - The layer dimension rides the same stacked-params ``lax.scan`` as
    training (`transformer.forward`: a period of mixer kinds a step, a scan
    a segment of the layer pattern), so depth costs one trace and what a
    prompt leaves comes back as one [L, B, ...] array a leaf — contiguous
    HBM, no per-layer Python lists.
  - The prompt pass computes what a slot keeps and the last position's
    logits, nothing else: through trailing layers that leave a slot
    nothing (`cfg.tail_segment`) only a row's last position passes.
  - Keys/values are cached *post-RoPE* and *pre-GQA-expansion* (KV heads,
    not Q heads): memory scales with kv_heads, and the repeat to Q heads
    happens inside the attention contraction.
  - Decode attention at T=1 per step is HBM-bandwidth-bound: its time is
    the cache bytes it reads. The masked contractions here read every
    position of every slot: the CPU path, and the reference of the decode
    kernel, which reads only the blocks of positions a request owns.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.transformer import (HEAD_COPY, Params, attention_out,
                                        block_norm, diff_out, diff_qkv,
                                        ffn_block, gmu_mixer, kda_mixer,
                                        layer_segments, lm_head,
                                        mamba2_mixer, mamba_mixer,
                                        mixer_precision, qkv_proj,
                                        refuse_unserved)
from ray_tpu.ops.decode_attention import decode_attention
from ray_tpu.ops.kda import kda_decode_step
from ray_tpu.ops.mamba import mamba_decode_step
from ray_tpu.ops.mamba2 import mamba2_decode_step
from ray_tpu.parallel.ring import shard_map
from ray_tpu.parallel.sharding import logical_to_spec

# Large-finite instead of -inf for masked scores: a fully-masked query row
# (a pad position in a left-padded batch) then softmaxes to uniform junk
# instead of NaN — junk at pad positions is never attended (their keys are
# masked) nor read (only real positions' logits are consumed), while NaN
# would propagate through 0*NaN in the value contraction.
_MASKED = jnp.float32(jnp.finfo(jnp.float32).min / 2)


def _gqa_attention(q, k, v, mask, scale=None):
    """q [B,T,H,hd] vs keys/values [B,S,KV,hd] under a broadcastable
    mask [B,T,1,1,S]. GQA expansion happens by reshaping q into
    [KV, reps] groups — no materialized repeat of k/v. ``scale`` of the
    scores: left out, hd ** -0.5."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    reps = H // KV
    scale = hd ** -0.5 if scale is None else scale
    qg = q.reshape(B, T, KV, reps, hd)
    scores = jnp.einsum("btkrh,bskh->btkrs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = jnp.where(mask, scores, _MASKED)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("btkrs,bskh->btkrh", probs, v.astype(jnp.float32))
    return o.reshape(B, T, H, hd).astype(q.dtype)


def _gqa_decode_attention(q, k_cache, v_cache, k_new, v_new, mask,
                          scale=None):
    """One query per row, q [B,1,H,hd], against a cache it only READS:
    keys/values [B,KV,S,hd] (KV-major, the order this contraction walks)
    under ``mask`` [B,S], plus the token's own ``k_new``/``v_new``
    [B,KV,hd] as one more key column — one softmax over both, so the
    result is what `_gqa_attention` gives once the row is written. The
    caller rounds k_new/v_new to the cache's dtype first (attention then
    sees the values it would have read back). ``scale`` of the scores: left
    out, hd ** -0.5."""
    B, _, H, hd = q.shape
    KV = k_cache.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    qg = q.reshape(B, KV, H // KV, hd).astype(jnp.float32)
    s_old = jnp.einsum("bkrh,bksh->bkrs", qg,
                       k_cache.astype(jnp.float32)) * scale
    s_old = jnp.where(mask[:, None, None, :], s_old, _MASKED)
    s_new = jnp.einsum("bkrh,bkh->bkr", qg,
                       k_new.astype(jnp.float32)) * scale
    # softmax over [s_old | s_new] without concatenating to S+1 columns
    top = jnp.maximum(s_old.max(axis=-1), s_new)
    e_old = jnp.exp(s_old - top[..., None])
    e_new = jnp.exp(s_new - top)
    o = jnp.einsum("bkrs,bksh->bkrh", e_old, v_cache.astype(jnp.float32)) \
        + e_new[..., None] * v_new.astype(jnp.float32)[:, :, None, :]
    o = o / (e_old.sum(axis=-1) + e_new)[..., None]
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def _weighted_values(p, v, eq: str):
    """einsum(eq, p, v) for float32 weights ``p`` and cached values ``v``
    with the weights' float32 kept: against float32 values at the highest
    precision; against bf16 values as ONE bf16 contraction of the weights'
    leading 8 bits and the next 8, side by side as query rows (the axis
    ``r`` of ``eq``) of one operand so that the values are read once (a
    float32 matmul at the default precision rounds p to its leading 8, the
    highest spends six passes on values that have only 8, and two
    contractions read a cache leaf twice)."""
    f32 = jnp.float32
    if v.dtype == f32:
        return jnp.einsum(eq, p, v, precision=jax.lax.Precision.HIGHEST)
    hi = p.astype(v.dtype)
    lo = (p - hi.astype(f32)).astype(v.dtype)
    rows, out = eq.split(",")[0].index("r"), eq.split("->")[1].index("r")
    both = jnp.einsum(eq, jnp.concatenate([hi, lo], axis=rows), v,
                      preferred_element_type=f32)
    hi, lo = jnp.split(both, 2, axis=out)
    return hi + lo


def _diff_attention(q, k, v, mask):
    """Differential attention's two softmaxes over a row, in pairs of heads
    (`transformer.diff_qkv`): q [B, T, P, 2, c] (a pair's [q1 | 0] and [0 |
    q2], c = 2 head_dim), k, v [B, S, G, c] ([k1 | k2], [v1 | v2]; query
    pair j reads key pair j // (P / G)), mask [B, T, S] -> o [B, T, P, 2,
    c] float32: softmax(q1 k1^T) [v1 | v2] and softmax(q2 k2^T) [v1 | v2],
    scores scaled by head_dim ** -0.5, float32 from the scores on."""
    B, T, P, _, c = q.shape
    G = k.shape[2]
    qg = q.reshape(B, T, G, P // G, 2, c)
    s = jnp.einsum("btgrec,bsgc->btgres", qg, k,
                   preferred_element_type=jnp.float32) * (c // 2) ** -0.5
    s = jnp.where(mask[:, :, None, None, None, :], s, _MASKED)
    o = _weighted_values(jax.nn.softmax(s, axis=-1), v,
                         "btgres,bsgc->btgrec")
    return o.reshape(B, T, P, 2, c)


def _diff_decode_attention(q, k_cache, v_cache, k_new, v_new, mask):
    """`_diff_attention` for one query a row against a cache it only READS
    (a full-length leaf or a window's ring): q [B, 1, P, 2, c], keys and
    values [B, G, S, c] under ``mask`` [B, S], plus the token's own
    ``k_new``/``v_new`` [B, G, c] as one more key column, as
    `_gqa_decode_attention` takes them -> o [B, 1, P, 2, c] float32."""
    B, _, P, _, c = q.shape
    G = k_cache.shape[1]
    f32 = jnp.float32
    qg = q.reshape(B, G, P // G, 2, c)
    scale = (c // 2) ** -0.5
    s_old = jnp.einsum("bgrec,bgsc->bgres", qg, k_cache,
                       preferred_element_type=f32) * scale
    s_old = jnp.where(mask[:, None, None, None, :], s_old, _MASKED)
    s_new = jnp.einsum("bgrec,bgc->bgre", qg, k_new,
                       preferred_element_type=f32) * scale
    top = jnp.maximum(s_old.max(axis=-1), s_new)
    e_old = jnp.exp(s_old - top[..., None])
    e_new = jnp.exp(s_new - top)
    o = _weighted_values(e_old, v_cache, "bgres,bgsc->bgrec") \
        + e_new[..., None] * v_new.astype(f32)[:, :, None, None, :]
    o = o / (e_old.sum(axis=-1) + e_new)[..., None]
    return o.reshape(B, 1, P, 2, c)


def window_ring(rows, window: int):
    """The last ``window`` positions of rows [B, P, ...] as a decode step
    finds them in a ring of ``window`` places: position p at place p %
    window (places no position has reached yet are zero)."""
    P = rows.shape[1]
    if P <= window:
        return jnp.pad(rows, [(0, 0), (0, window - P)]
                       + [(0, 0)] * (rows.ndim - 2))
    return jnp.roll(rows[:, P - window:], (P - window) % window, axis=1)


def _final_logits(params, x, cfg):
    # shared final norm + head with the training path
    return lm_head(params, x, cfg, None)


def embed_tokens(params, tokens, cfg):
    """The tokens' rows of the table in the compute dtype, times
    `cfg.embed_scale` where the model states one (rows first, then the
    rounding). A replica's tied, unscaled table is read from its rounded
    copy (`transformer.with_head_copy`), whose rows ARE the rows rounded:
    XLA gathers the float32 leaf's by rounding the WHOLE table first (in a
    decode chunk the convert it shared with the head's, 2 GB read and 1 GB
    written for 64 rows of Phi-4-mini-flash's)."""
    if cfg.embed_scale is None and cfg.tie_embeddings \
            and HEAD_COPY in params:
        return params[HEAD_COPY][tokens]
    x = params["embed"][tokens]
    if cfg.embed_scale is not None:
        x = x * cfg.embed_scale
    return x.astype(cfg.dtype)


def residual(x, o, cfg):
    """The stream after a mixer's or a feed-forward's output ``o`` joins it:
    x + o, o times `cfg.residual_scale` where the model states one. The ONE
    place the prompt walk, every kind's one-token step and the engine's
    feed-forward add through."""
    return x + o if cfg.residual_scale is None \
        else x + o * cfg.residual_scale


def join_segments(parts):
    """One kind's leaves [layers of a segment, ...], a segment each, in
    layer order: [layers, ...]."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def join_period(parts):
    """What the positions of ONE kind handed out of a scan over periods,
    each [periods, ...], in layer order: [periods x positions, ...]."""
    if len(parts) == 1:
        return parts[0]
    x = jnp.stack(parts, axis=1)
    return x.reshape((-1,) + x.shape[2:])


# ---- the served mixer kinds -------------------------------------------------
# What serving knows of a KIND of token mixer stands here and nowhere else,
# one record a kind (`MIXERS`). `_prefill_hidden` below and the engine's
# cache and programs walk the layers and look the kind up.

_KV_AXES = ("layers", None, "kv_heads", None, None)


class Mixer(NamedTuple):
    # (cfg, slots, max_len) -> {leaf: (shape, dtype)}: what the kind keeps a
    # slot, stacked over its layers; and the leaves' logical axes
    leaves: Callable = lambda cfg, slots, max_len: {}
    axes: dict = {}
    # how a prefill's leaf reaches the slots, and a step's row the leaf:
    # "rows"  [L, K, P, KV, hd] -> KV-major, a slot's rows from position 0;
    #         a step hands back the token's (k, v), written at ``pos``
    # "ring"  the same transpose, the whole slot; the row at ``pos % places``
    # "slot"  the whole slot as made; a step updates the carried stack (or,
    #         `hands_back`, leaves it alone and hands back the layer's leaves)
    land: Optional[str] = None
    # (h, lp, cfg, ctx) -> (o, {leaf: the prompt's}): h the normed rows, ctx
    # what `_prefill_hidden` shares: seen [B, T, P] (causal, no padding),
    # near (seen, within the window), index() (the layer's, of the model)
    prefill: Optional[Callable] = None
    # (x, lp, cfg, ctx) -> (x after the mixer, the token's (k, v) or ()): ctx
    # what `engine._decode_one` shares: slabs {leaf: [the kind's layers in
    # the period, B, ...]}, layer (this one among its kind's: the leaves'
    # leading axis), i (the same within the period), seen {kind: its layers
    # before the segment}, mask [B, S] (the places [start, pos) of a full-
    # length leaf), kernel (the decode kernel takes those leaves), index()
    step: Optional[Callable] = None
    # (cfg, B, T, cache) -> what rides the carry from a layer before the
    # kind's (a step's: T None, the cache given); ``ctx.carry`` is writable
    carry: Callable = lambda cfg, B, T, cache: {}
    reads: Optional[str] = None     # the kind whose leaves its layers read
    # True: where the walk may not write into the cache it was given
    # (``ctx.in_place`` False: `engine._decode_one` without ``active``, a
    # step whose caller may keep that cache) the kind's stacks do not ride
    # the carry; a step READS its layer of ``ctx.cache`` and hands back the
    # layer's new leaves as its row, which replace the stacks after the scans
    hands_back: bool = False


def _kv_leaves(kind: str, names, places):
    """Keys and values [L, B, KV, places, hd]: KV-major, the order decode
    attention contracts in (else XLA transposes the whole cache into every
    chunk). Under differential attention (and `cfg.kv_head_pairs`) PAIRS of
    adjacent heads, [.., KV / 2, places, 2 hd]: as the layer reads them,
    whole lanes for a 64-wide head. A window layer's are a ring, position p
    at place p % W."""
    def leaves(cfg, slots, max_len):
        pair = 2 if cfg.diff_attn or cfg.kv_head_pairs else 1
        shape = (cfg.layers_of_kind(kind), slots, cfg.kv_heads // pair,
                 places(cfg, max_len), pair * cfg.head_dim)
        return {name: (shape, cfg.dtype) for name in names}
    return leaves, dict.fromkeys(names, _KV_AXES)


def _kda_leaves(cfg, slots, max_len):    # the delta rule's state, and the
    # projected rows of q, k, v the short convolutions reach back to
    n, H, hd = cfg.layers_of_kind("kda"), cfg.kda_heads, cfg.kda_head_dim
    return {"kda_state": ((n, slots, H, hd, hd), jnp.float32),
            "kda_tail": ((n, slots, cfg.kda_conv - 1, 3 * H * hd), cfg.dtype)}


def _mamba_leaves(cfg, slots, max_len):
    """The scan's N states a channel (state-major: [.., C, 16] would be
    stored padded to 128 lanes) and the rows its convolution reaches to."""
    n, C = cfg.layers_of_kind("mamba"), cfg.mamba_channels
    return {"mamba_state": ((n, slots, cfg.mamba_d_state, C), jnp.float32),
            "mamba_tail": ((n, slots, cfg.mamba_d_conv - 1, C), cfg.dtype)}


def _mamba2_leaves(cfg, slots, max_len):
    """The recurrence's states, state-major as Mamba-1's are (N states x
    the H x P channels: `ops/mamba2.py` says why), and the rows of [x | B |
    C] its convolution reaches to, side by side as ONE row a slot: [.., 3,
    4352] is stored padded to 4 rows, and XLA then re-tiles the whole stack
    between two layers' updates to save the padding."""
    n = cfg.layers_of_kind("mamba2")
    return {"mamba2_state": ((n, slots, cfg.mamba_d_state,
                              cfg.mamba_channels), jnp.float32),
            "mamba2_tail": ((n, slots, (cfg.mamba_d_conv - 1)
                             * cfg.mamba2_conv_width), cfg.dtype)}


def _gmu_carry(cfg, B, T, cache):   # the nearest mamba layer's scan output
    return {"memory": jnp.zeros((B, T or 1, cfg.mamba_channels),
                                jnp.float32)}


def _cross_carry(cfg, B, T, cache):
    # the nearest attention layer's keys and values of the same token(s)
    rows = jnp.zeros(
        ((B,) if T is None else (B, T))
        + (cfg.kv_heads // 2, 2 * cfg.head_dim),
        cfg.dtype if cache is None else cache["k"].dtype)
    return {"shared_k": rows, "shared_v": rows}


def _attention_prefill(h, lp, cfg, ctx):
    if cfg.diff_attn:
        q, k, v = diff_qkv(h, lp, cfg)
        new = {"k": jnp.pad(k, ctx.pad), "v": jnp.pad(v, ctx.pad)}
        if "shared_k" in ctx.carry:
            ctx.carry["shared_k"], ctx.carry["shared_v"] = k, v
        return diff_out(_diff_attention(q, k, v, ctx.seen), lp, cfg,
                        ctx.index()), new
    q, k, v = qkv_proj(h, lp, cfg, ctx.positions)
    o = attention_out(_gqa_attention(q, k, v, ctx.prompt_mask,
                                     cfg.attn_scale), h, lp, cfg)
    if cfg.kv_head_pairs:   # as the cache holds them: [.., KV / 2, 2 hd]
        k, v = (r.reshape(r.shape[:2] + (cfg.kv_heads // 2, -1))
                for r in (k, v))
    # pad this layer's k/v out to max_len for the cache
    return o, {"k": jnp.pad(k.astype(cfg.dtype), ctx.pad),
               "v": jnp.pad(v.astype(cfg.dtype), ctx.pad)}


def _window_prefill(h, lp, cfg, ctx):
    q, k, v = diff_qkv(h, lp, cfg)
    new = {"win_k": window_ring(k, cfg.sliding_window),
           "win_v": window_ring(v, cfg.sliding_window)}
    return diff_out(_diff_attention(q, k, v, ctx.near), lp, cfg,
                    ctx.index()), new


def _cross_prefill(h, lp, cfg, ctx):
    q, _, _ = diff_qkv(h, lp, cfg)
    o = _diff_attention(q, ctx.carry["shared_k"], ctx.carry["shared_v"],
                        ctx.seen)
    return diff_out(o, lp, cfg, ctx.index()), {}


def _kda_prefill(h, lp, cfg, ctx):
    o, state, tail = kda_mixer(h, lp, cfg, valid=ctx.valid)
    return o, {"kda_state": state, "kda_tail": tail}


def _mamba_prefill(h, lp, cfg, ctx):
    o, y, state, tail = mamba_mixer(h, lp, cfg, valid=ctx.valid)
    if "memory" in ctx.carry:
        ctx.carry["memory"] = y
    return o, {"mamba_state": state, "mamba_tail": tail}


def _mamba2_prefill(h, lp, cfg, ctx):
    o, state, tail = mamba2_mixer(h, lp, cfg, valid=ctx.valid)
    return o, {"mamba2_state": state, "mamba2_tail": tail}


def _gmu_prefill(h, lp, cfg, ctx):
    return gmu_mixer(h, ctx.carry["memory"], lp, cfg), {}


def _kernel_attention(q, cache, k_new, v_new, active, layer, mesh, **how):
    """`decode_attention` on one layer of the whole stacked cache (``how``:
    its scale and output dtype, where they are not a GQA layer's). GSPMD
    cannot partition a Mosaic kernel: on a mesh it runs per shard of the
    KV heads, as `transformer._attention` runs the train kernel."""
    args = (q, cache["k"], cache["v"], k_new, v_new, cache["pos"],
            cache["start"], active, layer)
    if mesh is None or mesh.size == 1:
        return decode_attention(*args, **how)
    kv, heads, new = (
        logical_to_spec(axes, mesh_axes=mesh.axis_names)
        for axes in (_KV_AXES, (None, None, "heads", None),
                     (None, "kv_heads", None)))
    rep = jax.sharding.PartitionSpec()
    return shard_map(functools.partial(decode_attention, **how), mesh=mesh,
                     in_specs=(heads, kv, kv, new, new, rep, rep, rep, rep),
                     out_specs=heads)(*args)


def _diff_attend(q, k, v, cfg, ctx, layer, slab):
    """Differential attention's token against ``layer`` of the full-length
    leaf. To the kernel the pairs are a grouped-query call: a key pair's
    query rows [q1 | 0], [0 | q2] a query pair, scaled by the head's width, o
    float32 for `diff_out` to subtract. Else the contraction over ``slab()``."""
    if ctx.kernel:
        return _kernel_attention(
            q.reshape(q.shape[0], 1, -1, q.shape[-1]), ctx.cache, k, v,
            ctx.active, layer, ctx.mesh, scale=cfg.head_dim ** -0.5,
            out_dtype=jnp.float32).reshape(q.shape)
    return _diff_decode_attention(q, *slab(), k, v, ctx.mask)


def _head_pairs(q, k, v, cfg):
    """A token's q [B, 1, H, hd], k, v [B, KV, hd] as a cache of PAIRS reads
    them (`cfg.kv_head_pairs`): k, v [B, KV / 2, 2 hd], adjacent heads side
    by side; a query head as [q | 0] where its key/value head is a pair's
    first and [0 | q] where it is the second, so that the pair's row scores
    its own half alone: a grouped-query layer of KV / 2 heads, 128 wide, to
    the kernel and to the contraction alike."""
    B, _, H, hd = q.shape
    KV = k.shape[1]
    q = q.reshape(B, 1, KV // 2, 2, H // KV, 1, hd) \
        * jnp.eye(2, dtype=q.dtype)[:, None, :, None]
    return (q.reshape(B, 1, H, 2 * hd),
            *(r.reshape(B, KV // 2, 2 * hd) for r in (k, v)))


def _own_halves(o, cfg):
    """What `_head_pairs`'s query rows read, o [B, 1, H, 2 hd] over a pair's
    [v1 | v2], -> [B, 1, H, hd]: each head its own value head's half."""
    B, _, H, wide = o.shape
    o = o.reshape(B, 1, cfg.kv_heads // 2, 2, H // cfg.kv_heads, 2, wide // 2)
    return jnp.concatenate([o[:, :, :, :1, :, 0], o[:, :, :, 1:, :, 1]],
                           axis=3).reshape(B, 1, H, wide // 2)


def _attention_step(x, lp, cfg, ctx):
    """Only READS the cache and hands back the layer's new K/V row, rounded
    as it is read back later: the token's one more key column."""
    slab = lambda: (ctx.slabs["k"][ctx.i], ctx.slabs["v"][ctx.i])  # noqa: E731
    if cfg.diff_attn:
        q, k, v = diff_qkv(block_norm(x, lp, "attn_norm", cfg), lp, cfg)
        k, v = (r[:, 0].astype(ctx.cache["k"].dtype) for r in (k, v))
        if "shared_k" in ctx.carry:
            ctx.carry["shared_k"], ctx.carry["shared_v"] = k, v
        o = _diff_attend(q, k, v, cfg, ctx, ctx.layer, slab)
        return residual(x, diff_out(o, lp, cfg, ctx.index()), cfg), (k, v)
    # a float32 mixer (`mixer_precision`) around the attention itself: the
    # kernel keeps its own arithmetic and hands back o in q's dtype
    with mixer_precision(cfg, lp) as wide:
        x = x.astype(wide)
        h = block_norm(x, lp, "attn_norm", cfg)
        q, k, v = qkv_proj(h, lp, cfg, ctx.positions)
    k, v = (r[:, 0].astype(ctx.cache["k"].dtype) for r in (k, v))
    scale = cfg.attn_scale
    if cfg.kv_head_pairs:   # (the scale is the HEAD's, not the pair's)
        q, k, v = _head_pairs(q, k, v, cfg)
        scale = cfg.head_dim ** -0.5 if scale is None else scale
    if ctx.kernel:          # [B, KV, hd]
        o = _kernel_attention(q, ctx.cache, k, v, ctx.active, ctx.layer,
                              ctx.mesh, scale=scale)
    else:
        o = _gqa_decode_attention(q, *slab(), k, v, ctx.mask, scale)
    if cfg.kv_head_pairs:
        o = _own_halves(o, cfg)
    with mixer_precision(cfg, lp):
        o = attention_out(o, h, lp, cfg)
    return residual(x, o, cfg), (k, v)


def _window_step(x, lp, cfg, ctx):
    """`_attention_step` against the layer's ring, by the masked contraction
    anywhere: a ring's valid places are not [start, pos) once it wraps."""
    q, k, v = diff_qkv(block_norm(x, lp, "attn_norm", cfg), lp, cfg)
    k, v = (r[:, 0].astype(ctx.cache["win_k"].dtype) for r in (k, v))
    o = _diff_decode_attention(q, ctx.slabs["win_k"][ctx.i],
                               ctx.slabs["win_v"][ctx.i], k, v, ctx.ring)
    return residual(x, diff_out(o, lp, cfg, ctx.index()), cfg), (k, v)


def _cross_step(x, lp, cfg, ctx):
    """Reads the leaf of the nearest attention layer before it and that
    layer's row of THIS token (not in the cache before the scans end)."""
    q, _, _ = diff_qkv(block_norm(x, lp, "attn_norm", cfg), lp, cfg)
    layer = ctx.seen["attention"] - 1
    o = _diff_attend(
        q, ctx.carry["shared_k"], ctx.carry["shared_v"], cfg, ctx, layer,
        lambda: (ctx.cache["k"][layer], ctx.cache["v"][layer]))
    return residual(x, diff_out(o, lp, cfg, ctx.index()), cfg), ()


def _state_step(x, lp, cfg, ctx, mixer, step_fn, state: str, tail: str,
                in_place: bool = True):
    """``step_fn`` updates the layer's blocks of the carried stack of states
    where they lie, the layer's slice of the stacked tails shifts by the
    token (not active: both kept). -> ``mixer``'s, the tail aside.
    ``in_place`` False (`Mixer.hands_back`): the stacks are ``ctx.cache``'s
    and only read; -> ``mixer``'s with the layer's (state, tail) in the
    tail's place."""
    carry, layer, active = ctx.carry if in_place else ctx.cache, ctx.layer, \
        ctx.active
    h = block_norm(x, lp, "attn_norm", cfg)
    made = []

    def step(*token):
        if in_place:
            carry[state], o = step_fn(carry[state], layer, *token, active)
        else:
            mine, o = step_fn(carry[state], layer, *token, active,
                              in_place=False)
            made.append(mine)
        return o
    old = jax.lax.dynamic_index_in_dim(carry[tail], layer, 0, keepdims=False)
    *out, new = mixer(h, lp, cfg, tail=old, step=step)
    new = jnp.where(jnp.expand_dims(active, tuple(range(1, new.ndim))), new,
                    old)
    if not in_place:
        return *out, (made[0], new)
    carry[tail] = jax.lax.dynamic_update_index_in_dim(carry[tail], new,
                                                      layer, 0)
    return out


def _kda_step(x, lp, cfg, ctx):
    o, = _state_step(x, lp, cfg, ctx, kda_mixer, kda_decode_step,
                     "kda_state", "kda_tail")
    return residual(x, o, cfg), ()


def _mamba_step(x, lp, cfg, ctx):
    o, y = _state_step(x, lp, cfg, ctx, mamba_mixer, mamba_decode_step,
                       "mamba_state", "mamba_tail")
    if "memory" in ctx.carry:   # of the gated memory units after it
        ctx.carry["memory"] = y
    return residual(x, o, cfg), ()


def _mamba2_step(x, lp, cfg, ctx):
    o, *row = _state_step(x, lp, cfg, ctx, mamba2_mixer, mamba2_decode_step,
                          "mamba2_state", "mamba2_tail", ctx.in_place)
    return residual(x, o, cfg), (row[0] if row else ())


def _gmu_step(x, lp, cfg, ctx):
    h = block_norm(x, lp, "attn_norm", cfg)
    return residual(x, gmu_mixer(h, ctx.carry["memory"], lp, cfg), cfg), ()


# (leaves, masks, carried entries and landings are traced in this order)
MIXERS = {
    "attention": Mixer(
        *_kv_leaves("attention", ("k", "v"), lambda cfg, max_len: max_len),
        "rows", _attention_prefill, _attention_step),
    "kda": Mixer(
        _kda_leaves, {"kda_state": ("layers", None, "heads", None, None),
                      "kda_tail": ("layers", None, None, None)},
        "slot", _kda_prefill, _kda_step),
    "mamba": Mixer(
        _mamba_leaves, {"mamba_state": ("layers", None, None, "mlp"),
                        "mamba_tail": ("layers", None, None, "mlp")},
        "slot", _mamba_prefill, _mamba_step),
    "mamba2": Mixer(
        _mamba2_leaves, {"mamba2_state": ("layers", None, None, "mlp"),
                         "mamba2_tail": ("layers", None, "mlp")},
        "slot", _mamba2_prefill, _mamba2_step, hands_back=True),
    "window": Mixer(
        *_kv_leaves("window", ("win_k", "win_v"),
                    lambda cfg, max_len: cfg.sliding_window),
        "ring", _window_prefill, _window_step),
    "gmu": Mixer(prefill=_gmu_prefill, step=_gmu_step, carry=_gmu_carry),
    "cross": Mixer(prefill=_cross_prefill, step=_cross_step,
                   carry=_cross_carry, reads="attention"),
}


def _prefill_hidden(params: Params, tokens: jax.Array,
                    cfg: TransformerConfig, max_len: int,
                    start: jax.Array):
    """Prompt pass returning final HIDDEN states + the filled cache: the
    hidden of the positions that passed EVERY layer, [B,P,d], or [B,1,d],
    the last position's alone, where the pattern ends in layers that
    need no other (below). The caller projects only the positions it
    reads to vocab space (a [B,P,V] float32 logits tensor is ~2 GB for
    llama3-8b at P=512), and every caller that serves reads ``[:, -1:]``:
    rows are padded on the left, so that is every row's last real token.
    The cache holds what each mixer kind leaves a slot (`MIXERS`), stacked
    over the layers of that kind; keys and values positions-major, [L, B,
    max_len, KV, hd]. A padded row is masked out of attention and writes
    nothing into a state.

    From `cfg.tail_segment()` on (a decoder-hybrid-decoder's cross-decoder,
    arXiv:2507.06607: trailing gated memory units and cross layers) the
    walk carries the LAST position only. Such a layer leaves a slot nothing
    and reads its own token's memory, or its own row's query against keys
    and values made earlier for every position: the stream and the memory
    narrow to ``[:, -1:]``, a cross layer's mask to the last query's row.
    The P - 1 rows not computed are rows no caller read."""
    B, P = tokens.shape
    refuse_unserved(cfg)
    if max_len < P:
        raise ValueError(f"max_len={max_len} < prompt length {P}")
    if not cfg.causal:
        # autoregressive decoding over a bidirectional encoder would
        # silently contradict the forward() the params were trained with
        raise ValueError("generation requires a causal (decoder) config; "
                         "this config has causal=False")
    x = embed_tokens(params, tokens, cfg)
    positions = jnp.arange(P)
    causal = jnp.arange(P)[:, None] >= jnp.arange(P)[None, :]
    valid = jnp.arange(P)[None, :] >= start[:, None]  # [B, S]
    prompt_mask = causal[None, :, None, None, :] & \
        valid[:, None, None, None, :]
    seen = causal[None] & valid[:, None, :]           # [B, T, S]
    near = seen & (jnp.arange(P)[:, None] - jnp.arange(P)[None, :]
                   < cfg.sliding_window)[None]
    pad = [(0, 0), (0, max_len - P), (0, 0), (0, 0)]

    def period(carry, scanned, kinds, first, seen):
        lps, rep = scanned
        carry, left = dict(carry), {}
        x = carry["x"]
        ctx = SimpleNamespace(
            positions=positions, valid=valid, prompt_mask=prompt_mask,
            seen=seen, near=near, pad=pad, carry=carry)
        for j, (kind, lp) in enumerate(zip(kinds, lps)):
            layer = first + rep * len(kinds) + j     # of the whole model
            ctx.index = lambda: layer
            with mixer_precision(cfg, lp) as dtype:
                x = x.astype(dtype)
                h = block_norm(x, lp, "attn_norm", cfg)
                o, new = MIXERS[kind].prefill(h, lp, cfg, ctx)
            x = residual(x, o, cfg)
            # inference drops the MoE aux loss
            down, _ = ffn_block(block_norm(x, lp, "mlp_norm", cfg), lp, cfg)
            x = residual(x, down, cfg).astype(cfg.dtype)
            for name, leaf in new.items():
                left.setdefault(name, []).append(leaf)
        return dict(carry, x=x), {name: tuple(leaves)
                                  for name, leaves in left.items()}

    carry, made, first = {"x": x}, {}, 0
    for kind, mixer in MIXERS.items():
        if kind in cfg.mixer_period:
            carry.update(mixer.carry(cfg, B, P, None))
    tail = cfg.tail_segment()
    for at, ((kinds, reps), stacks) in enumerate(zip(
            cfg.segments(), layer_segments(params["layers"]))):
        if at == tail:      # the last position's stream, memory and mask
            carry = dict(carry, x=carry["x"][:, -1:])
            if "memory" in carry:
                carry["memory"] = carry["memory"][:, -1:]
            seen = seen[:, -1:]
        carry, left = jax.lax.scan(
            functools.partial(period, kinds=kinds, first=first, seen=seen),
            carry, (stacks, jnp.arange(reps)))
        for name, leaves in left.items():
            made.setdefault(name, []).append(join_period(leaves))
        first += len(kinds) * reps
    cache = {name: join_segments(parts) for name, parts in made.items()}
    cache["pos"] = jnp.asarray(P, jnp.int32)
    return carry["x"], cache
