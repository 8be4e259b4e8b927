"""The building blocks of the serving programs: prompt pass and attention.

`ray_tpu.models.engine` compiles its two programs from these (prefill:
`_prefill_hidden` + `_final_logits`; decode: inside its own layer scan,
`ops/decode_attention.py`'s kernel on a chip and `_gqa_decode_attention`
on the CPU). There is no decode loop or cache of this module's own.
TPU-first design:

  - Static shapes everywhere: a prompt is left-padded to a bucket and
    its K/V padded out to ``max_len``, so each (rows, bucket) pair is one
    compiled program.
  - The layer dimension rides the same stacked-params ``lax.scan`` as
    training (`transformer.forward`: a period of mixer kinds a step, one
    stack a position of the period; a scan a segment of the layer pattern
    where it has several), so depth costs one trace and the
    prompt's K/V comes back as one [L, B, S, KV, hd] array per k/v —
    contiguous HBM, no per-layer Python lists. What a KDA layer leaves a
    slot comes back the same way: its final state and the last projected
    rows its convolutions reach back to, one entry a KDA layer.
  - The prompt pass computes what a slot keeps and the last position's
    logits, nothing else: where the layer pattern ends in layers that
    leave a slot nothing and read no other position of their own stream
    (a decoder-hybrid-decoder's cross-decoder: `cfg.tail_segment`), only
    a row's last position passes them, K rows and not K x P.
  - Keys/values are cached *post-RoPE* and *pre-GQA-expansion* (KV heads,
    not Q heads): memory scales with kv_heads, and the repeat to Q heads
    happens inside the attention contraction.
  - Decode attention at T=1 per step is HBM-bandwidth-bound: its time is
    the cache bytes it reads. `_gqa_decode_attention`, a dense masked
    contraction, reads every position of every slot whatever the mask
    says; it is the CPU path and the reference the decode kernel is held
    to, which reads only the blocks of positions a request owns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.transformer import (Params, attention_out, block_norm,
                                        diff_out, diff_qkv, ffn_block,
                                        gmu_mixer, kda_mixer, layer_segments,
                                        lm_head, mamba_mixer,
                                        mixer_precision, qkv_proj,
                                        refuse_unserved)

# Large-finite instead of -inf for masked scores: a fully-masked query row
# (a pad position in a left-padded batch) then softmaxes to uniform junk
# instead of NaN — junk at pad positions is never attended (their keys are
# masked) nor read (only real positions' logits are consumed), while NaN
# would propagate through 0*NaN in the value contraction.
_MASKED = jnp.float32(jnp.finfo(jnp.float32).min / 2)


def _gqa_attention(q, k, v, mask):
    """q [B,T,H,hd] vs keys/values [B,S,KV,hd] under a broadcastable
    mask [B,T,1,1,S]. GQA expansion happens by reshaping q into
    [KV, reps] groups — no materialized repeat of k/v."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    reps = H // KV
    qg = q.reshape(B, T, KV, reps, hd)
    scores = jnp.einsum("btkrh,bskh->btkrs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * (hd ** -0.5)
    scores = jnp.where(mask, scores, _MASKED)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("btkrs,bskh->btkrh", probs, v.astype(jnp.float32))
    return o.reshape(B, T, H, hd).astype(q.dtype)


def _gqa_decode_attention(q, k_cache, v_cache, k_new, v_new, mask):
    """One query per row, q [B,1,H,hd], against a cache it only READS:
    keys/values [B,KV,S,hd] (KV-major, the order this contraction walks)
    under ``mask`` [B,S], plus the token's own ``k_new``/``v_new``
    [B,KV,hd] as one more key column — one softmax over both, so the
    result is what `_gqa_attention` gives once the row is written. The
    caller rounds k_new/v_new to the cache's dtype first (attention then
    sees the values it would have read back)."""
    B, _, H, hd = q.shape
    KV = k_cache.shape[1]
    qg = q.reshape(B, KV, H // KV, hd).astype(jnp.float32)
    s_old = jnp.einsum("bkrh,bksh->bkrs", qg,
                       k_cache.astype(jnp.float32)) * (hd ** -0.5)
    s_old = jnp.where(mask[:, None, None, :], s_old, _MASKED)
    s_new = jnp.einsum("bkrh,bkh->bkr", qg,
                       k_new.astype(jnp.float32)) * (hd ** -0.5)
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


def _prefill_hidden(params: Params, tokens: jax.Array,
                    cfg: TransformerConfig, max_len: int,
                    start: jax.Array):
    """Prompt pass returning final HIDDEN states + the filled cache: the
    hidden of the positions that passed EVERY layer, [B,P,d], or [B,1,d],
    the last position's alone, where the pattern ends in layers that
    need no other (below). The caller projects only the positions it
    reads to vocab space (a [B,P,V] float32 logits tensor is ~2 GB for
    llama3-8b at P=512 and is pure waste on the serving hot path), and
    every caller that serves reads ``[:, -1:]``: rows are padded on the
    left, so that is every row's last real token. The cache holds
    what each mixer kind leaves a slot, stacked over the layers of that
    kind: ``k``/``v`` [L_attn, B, max_len, KV, hd]; ``kda_state`` [L_kda,
    B, H, dk, dv] float32 and ``kda_tail`` [L_kda, B, taps - 1, 3 x H x
    dk]; ``mamba_state`` [L_mamba, B, N, C] float32 and ``mamba_tail``
    [L_mamba, B, taps - 1, C]; ``win_k``/``win_v`` [L_window, B, window,
    KV, hd], the prompt's last positions where a decode step finds them
    (`window_ring`). Under differential attention keys and values are
    pairs of heads, [.., KV / 2, 2 hd]. Rows are padded on the left: a
    padded row is masked out of attention, and writes nothing into a KDA
    or mamba state (`kda_mixer`, `mamba_mixer`). The layers are walked a
    segment of the pattern at a time (`cfg.segments`), each a scan over
    its repeats; a gated memory unit's memory and a cross layer's keys
    and values ride the carry from the segment that makes them.

    From `cfg.tail_segment()` on (a decoder-hybrid-decoder's cross-
    decoder, arXiv:2507.06607: the trailing segments of gated memory
    units and cross layers) the walk carries the LAST position only. Such
    a layer leaves a slot nothing, a gated memory unit reads the same
    token's memory and a cross layer its own row's query against keys and
    values an earlier layer made for every position, so the last
    position's stream through them depends on no other position's: the
    stream and the memory narrow to ``[:, -1:]``, a cross layer's mask to
    the last query's row, the shared keys and values stay [B, P, ...].
    Every cache leaf is made before that segment and is what the walk of
    all positions makes; the P - 1 rows not computed are rows no caller
    read. A pattern that ends in any other kind (every pattern of one
    segment) has no such segment and is walked as it always was."""
    B, P = tokens.shape
    refuse_unserved(cfg)
    if max_len < P:
        raise ValueError(f"max_len={max_len} < prompt length {P}")
    if not cfg.causal:
        # autoregressive decoding over a bidirectional encoder would
        # silently contradict the forward() the params were trained with
        raise ValueError("generation requires a causal (decoder) config; "
                         "this config has causal=False")
    x = params["embed"][tokens].astype(cfg.dtype)
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
        for j, (kind, lp) in enumerate(zip(kinds, lps)):
            layer = first + rep * len(kinds) + j     # of the whole model
            with mixer_precision(cfg, lp) as dtype:
                x = x.astype(dtype)
                h = block_norm(x, lp, "attn_norm", cfg)
                if kind == "kda":
                    o, state, tail = kda_mixer(h, lp, cfg, valid=valid)
                    new = {"kda_state": state, "kda_tail": tail}
                elif kind == "mamba":
                    o, y, state, tail = mamba_mixer(h, lp, cfg, valid=valid)
                    new = {"mamba_state": state, "mamba_tail": tail}
                    if "memory" in carry:
                        carry["memory"] = y
                elif kind == "gmu":
                    o, new = gmu_mixer(h, carry["memory"], lp, cfg), {}
                elif cfg.diff_attn:
                    q, k, v = diff_qkv(h, lp, cfg)
                    new = {}
                    if kind == "cross":
                        k, v = carry["shared_k"], carry["shared_v"]
                    elif kind == "window":
                        new = {"win_k": window_ring(k, cfg.sliding_window),
                               "win_v": window_ring(v, cfg.sliding_window)}
                    else:
                        new = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
                        if "shared_k" in carry:
                            carry["shared_k"], carry["shared_v"] = k, v
                    o = diff_out(_diff_attention(
                        q, k, v, near if kind == "window" else seen),
                        lp, cfg, layer)
                else:
                    q, k, v = qkv_proj(h, lp, cfg, positions)
                    o = attention_out(
                        _gqa_attention(q, k, v, prompt_mask), h, lp, cfg)
                    # pad this layer's k/v out to max_len for the cache
                    new = {"k": jnp.pad(k.astype(cfg.dtype), pad),
                           "v": jnp.pad(v.astype(cfg.dtype), pad)}
            x = x + o
            # inference drops the MoE aux loss
            down, _ = ffn_block(block_norm(x, lp, "mlp_norm", cfg), lp, cfg)
            x = (x + down).astype(cfg.dtype)
            for name, leaf in new.items():
                left.setdefault(name, []).append(leaf)
        return dict(carry, x=x), {name: tuple(leaves)
                                  for name, leaves in left.items()}

    carry, made, first = {"x": x}, {}, 0
    kinds_all = set(cfg.mixer_period)
    if "gmu" in kinds_all:
        carry["memory"] = jnp.zeros((B, P, cfg.mamba_channels), jnp.float32)
    if "cross" in kinds_all:
        carry["shared_k"] = carry["shared_v"] = jnp.zeros(
            (B, P, cfg.kv_heads // 2, 2 * cfg.head_dim), cfg.dtype)
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
