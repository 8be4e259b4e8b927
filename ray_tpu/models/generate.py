"""Autoregressive generation with a KV cache: prefill + decode.

The inference half of the model stack (ref analog: the vLLM-backed
``ray.serve`` LLM deployments and ``rayllm`` batched-generation path the
reference ships for "Serve Llama-3 inference" — BASELINE.json configs).
TPU-first design:

  - Static shapes everywhere: the cache is allocated at ``max_len`` up
    front and written with ``lax.dynamic_update_slice``; the decode loop
    is a ``lax.scan`` over step index, so the whole generation of N
    tokens is ONE compiled XLA program (no per-token Python dispatch).
  - The layer dimension rides the same stacked-params ``lax.scan`` as
    training (`transformer.forward`), so depth costs one trace and the
    cache is a single [L, B, S, KV, hd] array per k/v — contiguous HBM,
    no per-layer Python lists.
  - Keys/values are cached *post-RoPE* and *pre-GQA-expansion* (KV heads,
    not Q heads): memory scales with kv_heads, and the repeat to Q heads
    happens inside the attention contraction.
  - Decode attention is a dense masked contraction over the cache — at
    T=1 per step it is HBM-bandwidth-bound (reads the cache once), which
    is the TPU roofline for decode; batching raises MXU utilization.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.transformer import (Params, ffn_block, lm_head,
                                        qkv_proj, rms_norm)

KVCache = Dict[str, jax.Array]  # {"k": [L,B,S,KV,hd], "v": ..., "pos": []}

# Large-finite instead of -inf for masked scores: a fully-masked query row
# (a pad position in a left-padded batch) then softmaxes to uniform junk
# instead of NaN — junk at pad positions is never attended (their keys are
# masked) nor read (only real positions' logits are consumed), while NaN
# would propagate through 0*NaN in the value contraction.
_MASKED = jnp.float32(jnp.finfo(jnp.float32).min / 2)


def init_cache(cfg: TransformerConfig, batch: int, max_len: int) -> KVCache:
    shape = (cfg.n_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "pos": jnp.zeros((), jnp.int32)}


def _ffn(h, lp, cfg):
    # shared definition with the training path (transformer.ffn_block);
    # inference drops the MoE aux loss
    down, _ = ffn_block(h, lp, cfg, None)
    return down


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


def _cached_attention(q, k_cache, v_cache, valid_len, start):
    """Decode attention against the full cache, masking key positions
    outside [start[b], valid_len). ``start`` [B] supports left-padded
    batches (pad tokens are never attended; RoPE is relative, so the
    absolute offset is harmless)."""
    S = k_cache.shape[1]
    kpos = jnp.arange(S)[None, None, None, None, :]
    mask = (kpos < valid_len) & \
        (kpos >= start[:, None, None, None, None])
    return _gqa_attention(q, k_cache, v_cache, mask)


def _final_logits(params, x, cfg):
    # shared final norm + head with the training path
    return lm_head(params, x, cfg, None)


def _prefill_hidden(params: Params, tokens: jax.Array,
                    cfg: TransformerConfig, max_len: int,
                    start: jax.Array):
    """Prompt pass returning final HIDDEN states [B,P,d] + the filled
    cache — generate() projects only the last position to vocab space
    (a [B,P,V] float32 logits tensor is ~2 GB for llama3-8b at P=512
    and is pure waste on the serving hot path)."""
    B, P = tokens.shape
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

    def block(x, lp):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = qkv_proj(h, lp, cfg, positions)
        o = _gqa_attention(q, k, v, prompt_mask)
        o = jnp.einsum("bthk,hkd->btd", o, lp["wo"].astype(cfg.dtype))
        x = x + o
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        x = x + _ffn(h, lp, cfg)
        # pad this layer's k/v out to max_len for the cache
        pad = [(0, 0), (0, max_len - P), (0, 0), (0, 0)]
        return x, (jnp.pad(k, pad), jnp.pad(v, pad))

    x, (k_all, v_all) = jax.lax.scan(block, x, params["layers"])
    cache = {"k": k_all, "v": v_all,
             "pos": jnp.asarray(P, jnp.int32)}
    return x, cache


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill(params: Params, tokens: jax.Array, cfg: TransformerConfig,
            max_len: int, start: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, KVCache]:
    """Process the whole prompt [B, P] in one pass; -> (logits [B,P,V],
    cache filled at positions [0, P)). ``start`` [B] marks the first
    REAL token per row for left-padded batches (earlier positions are
    masked out of attention)."""
    if start is None:
        start = jnp.zeros((tokens.shape[0],), jnp.int32)
    x, cache = _prefill_hidden(params, tokens, cfg, max_len, start)
    return _final_logits(params, x, cfg), cache


@partial(jax.jit, static_argnames=("cfg",))
def decode_step(params: Params, cache: KVCache, tokens: jax.Array,
                cfg: TransformerConfig,
                start: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, KVCache]:
    """One token per sequence: tokens [B] at position cache['pos'];
    -> (logits [B, V], cache advanced by one)."""
    pos = cache["pos"]
    if start is None:
        start = jnp.zeros((tokens.shape[0],), jnp.int32)
    x = params["embed"].astype(cfg.dtype)[tokens[:, None]]  # [B,1,d]
    positions = pos[None]  # [1]

    def block(x, scanned):
        lp, k_layer, v_layer = scanned
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = qkv_proj(h, lp, cfg, positions)
        B = x.shape[0]
        k_layer = jax.lax.dynamic_update_slice(
            k_layer, k.astype(k_layer.dtype), (0, pos, 0, 0))
        v_layer = jax.lax.dynamic_update_slice(
            v_layer, v.astype(v_layer.dtype), (0, pos, 0, 0))
        o = _cached_attention(q, k_layer, v_layer, pos + 1, start)
        o = jnp.einsum("bthk,hkd->btd", o, lp["wo"].astype(cfg.dtype))
        x = x + o
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        x = x + _ffn(h, lp, cfg)
        return x, (k_layer, v_layer)

    x, (k_all, v_all) = jax.lax.scan(
        block, x, (params["layers"], cache["k"], cache["v"]))
    new_cache = {"k": k_all, "v": v_all, "pos": pos + 1}
    return _final_logits(params, x, cfg)[:, 0], new_cache


@partial(jax.jit,
         static_argnames=("cfg", "max_new_tokens", "max_len", "greedy"))
def generate(params: Params, prompt: jax.Array, cfg: TransformerConfig,
             *, max_new_tokens: int, max_len: Optional[int] = None,
             temperature: float = 1.0, greedy: bool = True,
             eos_id: int = -1, rng: Optional[jax.Array] = None,
             start: Optional[jax.Array] = None) -> jax.Array:
    """prompt [B, P] -> [B, P + max_new_tokens]. One compiled program:
    prefill, then a lax.scan of decode steps (greedy or temperature
    sampling). Sequences that hit ``eos_id`` keep emitting eos.
    ``start`` [B]: first real-token position per row (left-padded
    batches of unequal prompt lengths)."""
    B, P = prompt.shape
    S = max_len or (P + max_new_tokens)
    if S < P + max_new_tokens:
        # an undersized cache would silently clamp dynamic_update_slice
        # writes onto the last slot and corrupt attention — refuse
        raise ValueError(
            f"max_len={S} < prompt_len({P}) + max_new_tokens"
            f"({max_new_tokens}); the KV cache must hold every position")
    if rng is None:
        rng = jax.random.key(0)
    if start is None:
        start = jnp.zeros((B,), jnp.int32)
    if max_new_tokens == 0:  # static arg: a free Python-level branch
        if not cfg.causal:  # same contract as the nonzero path
            raise ValueError("generation requires a causal (decoder) "
                             "config; this config has causal=False")
        return prompt
    x, cache = _prefill_hidden(params, prompt, cfg, S, start)
    # only the LAST position's logits seed decoding: project [B,1,d]
    # instead of materializing the full [B,P,V] prompt logits
    last = _final_logits(params, x[:, -1:], cfg)[:, 0]

    def pick(logits, step_rng):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(prompt.dtype)
        return jax.random.categorical(
            step_rng, logits / jnp.maximum(temperature, 1e-6)
        ).astype(prompt.dtype)

    # The first token comes straight from the prefill logits; the scan
    # then runs max_new_tokens-1 decode steps, each decoding the PREVIOUS
    # token and sampling the next — so the final sampled token never pays
    # for a decode_step whose logits nobody reads.
    rngs = jax.random.split(rng, max_new_tokens)
    tok0 = pick(last, rngs[0])
    done0 = tok0 == eos_id

    def step(carry, step_rng):
        cache, prev_tok, done = carry
        logits, cache = decode_step(params, cache, prev_tok, cfg, start)
        tok = pick(logits, step_rng)
        tok = jnp.where(done, jnp.asarray(eos_id, tok.dtype), tok)
        done = done | (tok == eos_id)
        return (cache, tok, done), tok

    (_, _, _), toks = jax.lax.scan(step, (cache, tok0, done0), rngs[1:])
    toks = jnp.concatenate([tok0[None], toks], axis=0)  # [N, B]
    return jnp.concatenate([prompt, toks.T], axis=1)
