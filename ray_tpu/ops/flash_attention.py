"""Pallas TPU flash attention (fwd + bwd), with interpret mode off-TPU.

The reference has no fused attention of its own (torch SDPA/NCCL territory).
This kernel is the Pallas piece of the attention stack (SURVEY.md §7.6):
  - forward: grid over (batch*heads, q-blocks); each step streams its q block
    against K/V resident in VMEM, computing a numerically-stable softmax row
    and the logsumexp residual for the backward pass.
  - backward: FlashAttention-2 style two kernels — dq over q-blocks, dk/dv
    over k-blocks — recomputing probabilities from the saved logsumexp, so
    no O(T^2) tensor is ever materialized in HBM.
Layout is [batch, seq, heads, head_dim] at the API, transposed to
[batch*heads, seq, head_dim] for the MXU-friendly inner matmuls.
VMEM budget: K/V for one (batch, head) stay resident, which the TPU
compiler's scoped-VMEM limit refuses already at T=8192 with head_dim 128
(16.04 MB of 16.00 MB, compiled for a v5e); [4, 2048, 32, 64] bf16 compiles
and runs (tests/test_chip_compile.py, chip_smoke.py). Longer sequences need
the sequence axis (ring attention) or the kernel redesign of ROADMAP Q1.6.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30


def _use_interpret() -> bool:
    return jax.default_backend() == "cpu"


# ---- forward ---------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q):
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)            # [bq, d]
    k = k_ref[0].astype(jnp.float32)            # [T, d]
    v = v_ref[0].astype(jnp.float32)            # [T, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        t_k = k.shape[0]
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) / l
    o_ref[0] = o.astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def _fwd(q3, k3, v3, *, scale, causal, block_q):
    bh, t, d = q3.shape
    t_k = k3.shape[1]
    nq = pl.cdiv(t, block_q)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=block_q)
    o, lse = pl.pallas_call(
        kern,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t_k, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t_k, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        interpret=_use_interpret(),
    )(q3, k3, v3)
    return o, lse


# ---- backward --------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, causal, block_q):
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    p = jnp.exp(s - lse[:, None])
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    dq = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_k):
    ik = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)             # [T, d]
    k = k_ref[0].astype(jnp.float32)             # [bk, d]
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)           # [T, d]
    lse = lse_ref[0, 0]                          # [T]
    delta = delta_ref[0, 0]                      # [T]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    p = jnp.exp(s - lse[:, None])                # [T, bk]
    dv = jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])               # [T, bk]
    dk = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, res, do3):
    q3, k3, v3, o3, lse = res
    bh, t, d = q3.shape
    t_k = k3.shape[1]
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=False)[:, None, :]  # [bh, 1, t]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q),
        grid=(bh, pl.cdiv(t, block_q)),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t_k, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t_k, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
        interpret=_use_interpret(),
    )(q3, k3, v3, do3, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_k=block_k),
        grid=(bh, pl.cdiv(t_k, block_k)),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_k, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, t_k, d), v3.dtype),
        ],
        interpret=_use_interpret(),
    )(q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash3(q3, k3, v3, scale, causal, block_q, block_k):
    o, _ = _fwd(q3, k3, v3, scale=scale, causal=causal, block_q=block_q)
    return o


def _flash3_fwd(q3, k3, v3, scale, causal, block_q, block_k):
    o, lse = _fwd(q3, k3, v3, scale=scale, causal=causal, block_q=block_q)
    return o, (q3, k3, v3, o, lse)


def _flash3_bwd(scale, causal, block_q, block_k, res, do3):
    return _bwd(scale, causal, block_q, block_k, res, do3)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None, block_q: int = 256,
                    block_k: int = 256):
    """Fused causal attention. q, k, v: [B, T, H, D] -> [B, T, H, D]."""
    b, t, h, d = q.shape
    t_k = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, t)
    block_k = min(block_k, t_k)

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], x.shape[1], d)

    o3 = _flash3(to3(q), to3(k), to3(v), scale, causal, block_q, block_k)
    return o3.reshape(b, h, t, d).transpose(0, 2, 1, 3)
