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
    stack a position of the period), so depth costs one trace and the
    prompt's K/V comes back as one [L, B, S, KV, hd] array per k/v —
    contiguous HBM, no per-layer Python lists. What a KDA layer leaves a
    slot comes back the same way: its final state and the last projected
    rows its convolutions reach back to, one entry a KDA layer.
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

import jax
import jax.numpy as jnp

from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.transformer import (Params, attention_out, ffn_block,
                                        kda_mixer, lm_head, mixer_precision,
                                        qkv_proj, refuse_unserved, rms_norm)

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


def _final_logits(params, x, cfg):
    # shared final norm + head with the training path
    return lm_head(params, x, cfg, None)


def layer_stacks(params: Params) -> tuple:
    """``params["layers"]`` as a tuple of stacks, one a position of the
    period of mixer kinds (one stack for a model of one kind)."""
    layers = params["layers"]
    return (layers,) if isinstance(layers, dict) else tuple(layers)


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
    """Prompt pass returning final HIDDEN states [B,P,d] + the filled
    cache — the caller projects only the positions it reads to vocab
    space (a [B,P,V] float32 logits tensor is ~2 GB for llama3-8b at
    P=512 and is pure waste on the serving hot path). The cache holds
    what each mixer kind leaves a slot, stacked over the layers of that
    kind: ``k``/``v`` [L_attn, B, max_len, KV, hd]; ``kda_state`` [L_kda,
    B, H, dk, dv] float32 and ``kda_tail`` [L_kda, B, taps - 1, 3 x H x
    dk]. Rows are padded on the left: a padded row is masked out of
    attention, and writes nothing into a KDA state (`kda_mixer`)."""
    B, P = tokens.shape
    refuse_unserved(cfg)
    if max_len < P:
        raise ValueError(f"max_len={max_len} < prompt length {P}")
    if not cfg.causal:
        # autoregressive decoding over a bidirectional encoder would
        # silently contradict the forward() the params were trained with
        raise ValueError("generation requires a causal (decoder) config; "
                         "this config has causal=False")
    x = params["embed"].astype(cfg.dtype)[tokens]
    positions = jnp.arange(P)

    causal = jnp.arange(P)[:, None] >= jnp.arange(P)[None, :]
    valid = jnp.arange(P)[None, :] >= start[:, None]  # [B, S]
    prompt_mask = causal[None, :, None, None, :] & \
        valid[:, None, None, None, :]

    def period(x, lps):
        left = {}
        for lp in lps:
            with mixer_precision(cfg, lp) as dtype:
                x = x.astype(dtype)
                h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
                if "kda_wq" in lp:
                    o, state, tail = kda_mixer(h, lp, cfg, valid=valid)
                    new = {"kda_state": state, "kda_tail": tail}
                else:
                    q, k, v = qkv_proj(h, lp, cfg, positions)
                    o = attention_out(
                        _gqa_attention(q, k, v, prompt_mask), h, lp, cfg)
                    # pad this layer's k/v out to max_len for the cache
                    pad = [(0, 0), (0, max_len - P), (0, 0), (0, 0)]
                    new = {"k": jnp.pad(k.astype(cfg.dtype), pad),
                           "v": jnp.pad(v.astype(cfg.dtype), pad)}
            x = x + o
            # inference drops the MoE aux loss
            down, _ = ffn_block(rms_norm(x, lp["mlp_norm"], cfg.rms_eps),
                                lp, cfg)
            x = (x + down).astype(cfg.dtype)
            for name, leaf in new.items():
                left.setdefault(name, []).append(leaf)
        return x, {name: tuple(leaves) for name, leaves in left.items()}

    x, left = jax.lax.scan(period, x, layer_stacks(params))
    cache = {name: join_period(leaves) for name, leaves in left.items()}
    cache["pos"] = jnp.asarray(P, jnp.int32)
    return x, cache
