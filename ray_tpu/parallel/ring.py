"""Ring attention: context parallelism over the ICI torus.

Absent from the reference (SURVEY.md §5 — no ring attention, Ulysses, or
sequence parallelism in-tree; its closest artifact is raw NCCL send/recv at
python/ray/util/collective/collective_group/nccl_collective_group.py:350).
Designed fresh for TPU: the sequence dimension is sharded over the `sequence`
mesh axis, K/V blocks rotate around the ring with `jax.lax.ppermute` (nearest
neighbour over ICI), and each step folds one block into a numerically-stable
online-softmax accumulator — so attention over a sequence of length S costs
each chip O(S/n * S) FLOPs and S/n-sized KV traffic, fully overlapped by XLA
with the matmuls.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import AxisType, Mesh, PartitionSpec as P


def shard_map(f, *, mesh, in_specs, out_specs):
    # Nested use only (e.g. ring attention inside a pipeline stage body):
    # when the ambient mesh has MANUAL axes we are inside an enclosing
    # shard_map, and jax requires the inner shard_map to see that context
    # mesh, not the original concrete one. A plain `jax.set_mesh` context
    # (all-auto) must NOT override an explicit mesh argument.
    am = jax.sharding.get_abstract_mesh()
    if not am.empty and AxisType.Manual in am.axis_types:
        mesh = am
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)


_NEG_INF = -1e30


def _block_attend(q, k, v, m, l, o, mask, scale):
    """Fold one K/V block into the (m, l, o) online-softmax state.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D]; m, l: [B, H, Tq]; o: [B, Tq, H, D].
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    # Rows that have seen nothing yet (m == -inf) contribute zero, not NaN.
    p = jnp.where((s <= _NEG_INF / 2), 0.0, p)
    corr = jnp.exp(m - m_new)
    corr = jnp.where(m <= _NEG_INF / 2, 0.0, corr)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool,
                          scale: float):
    """Body run per-device inside shard_map. Shapes are per-shard."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    qf = q.astype(jnp.float32)

    m0 = jnp.full((b, h, t_q), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t_q), jnp.float32)
    o0 = jnp.zeros((b, t_q, h, d), jnp.float32)

    q_pos = idx * t_q + jnp.arange(t_q)

    def step(s, carry):
        k_blk, v_blk, m, l, o = carry
        src = (idx - s) % n  # which global chunk this block came from
        if causal:
            k_pos = src * t_k + jnp.arange(t_k)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((t_q, t_k), bool)
        mask = mask[None, None, :, :]
        m, l, o = _block_attend(qf, k_blk, v_blk, m, l, o, mask, scale)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, o

    _, _, m, l, o = jax.lax.fori_loop(0, n, step, (k, v, m0, l0, o0))
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, axis_name: str = "sequence",
                   causal: bool = True, scale: Optional[float] = None,
                   batch_axes=None, head_axis: str = "tensor"):
    """Causal self-attention with the sequence dim sharded over `axis_name`.

    q, k, v: [batch, seq, heads, head_dim] (seq globally sharded).
    Degenerates to plain (still flash-style) attention when the sequence
    axis has size 1, so callers can use it unconditionally.

    ``batch_axes`` defaults to every data-like axis PRESENT in the mesh
    (slice/data/fsdp) — a hybrid multi-slice mesh must keep the batch
    sharded over DCN here, or shard_map would silently all-gather q/k/v
    across slices.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if batch_axes is None:
        batch_axes = tuple(a for a in ("slice", "data", "fsdp")
                           if a in mesh.axis_names)
    spec = P(batch_axes, axis_name, head_axis, None)
    fn = shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def reference_attention(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None):
    """Unsharded flash-free reference for tests: [B, T, H, D] -> [B, T, H, D]."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(
        q.dtype)
