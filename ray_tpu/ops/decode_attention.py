"""Pallas TPU decode attention over the slot cache, interpret mode off-TPU.

One query a slot against the engine's stacked cache `[L, B, KV, S, hd]`
(`models/engine.py`), which stays in HBM as it is: the kernel picks the
layer itself and fetches, for each slot, only the position blocks that
cross the rows the slot owns, `[start, pos)`, and nothing for a slot that
is not active (`block_bounds`; `rows_read` counts them). The masked
contraction it replaces (`generate._gqa_decode_attention`, still the CPU
path and the tests' reference) reads every position of every slot.
  - one program, no grid to step through: the slots' blocks are listed in
    SMEM from `pos`, `start` and `active` (scalar prefetch), then walked by
    one loop whose trip count is the number of blocks to read, with the
    fetches (`[KV, block, hd]` of K and of V, one DMA each) running
    `_DEPTH - 1` blocks ahead of the arithmetic. An empty slot costs a few
    scalar instructions and no DMA.
  - an online softmax across a slot's blocks (running max and sum, kept
    lane-replicated, and a float32 accumulator, all in VMEM scratch), begun
    from the token's own key column (`k_new`, `v_new`), so every row is
    finite whatever the slot holds: a slot with nothing cached, or not
    active, attends to its own token alone. A block's arithmetic is over
    all KV heads at once (products batched over the head): written a head
    at a time it moved 563 GB/s where this moves 710, and traced three
    times as long (PERF.md section 6, PR 33).
  - only a slot's first and last block can hold rows it does not own:
    their scores are masked and their value rows zeroed (what lies there is
    whatever the row's last tenant left).
q, K and V reach the MXU in the dtype they are stored in and every product
accumulates in float32; max, sum and the exponent's argument are float32.
The probabilities are NOT rounded to the cache dtype: every query row is
there twice, and before the value product the first copy's probabilities
become their rounding to that dtype and the second's what the rounding
left over, so one product of the same size gives both parts and their sum
is the float32 probabilities' product to about 16 bits (rounded once, the
serve check's decode error read 0.0424 where the masked contraction reads
0.0387: PERF.md section 6, PR 33).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (_LANES, _NEG_INF, _dot,
                                         _use_interpret)

# positions a fetch. Of the rows a full slot holds under the serve cells'
# lengths, blocks of 128 read 0.48 of the cache, 256 0.57, 512 0.74
# (ISSUE 33); PERF.md section 6, PR 33 has the timings that chose it.
_BLOCK = 128
_DEPTH = 4  # K/V buffers in flight: fetches run _DEPTH - 1 blocks ahead
_BNT = (((2,), (2,)), ((0,), (0,)))  # a @ b.T, a head at a time
_BNN = (((2,), (1,)), ((0,), (0,)))  # a @ b, a head at a time


def _lanes(x, n):
    """A lane-replicated [..., 128] statistic at width ``n``
    (`flash_attention._lanes` for any rank)."""
    if n % _LANES == 0:
        return x if n == _LANES else pltpu.repeat(x, n // _LANES, x.ndim - 1)
    return x[..., :n] if n < _LANES else jnp.broadcast_to(
        x[..., :1], x.shape[:-1] + (n,))


def pick_block(max_len: int, head_dim: int, dtype) -> int | None:
    """The largest block of at most `_BLOCK` positions that tiles
    ``max_len``, or None where the kernel does not take the cache and the
    caller keeps the XLA contraction. On the chip the DMA slices the cache
    as HBM tiles it: a block is whole sublane tiles of ``dtype`` and a head
    whole lanes (a head of 64 is stored padded to 128, and Mosaic refuses
    the slice). In interpret mode any divisor will do."""
    if _use_interpret():
        tile = 1
    elif head_dim % _LANES:
        return None
    else:
        tile = 32 // jnp.dtype(dtype).itemsize
    for block in range(min(_BLOCK, max_len) // tile * tile, 0, -tile):
        if max_len % block == 0:
            return block
    return None


# ---- which blocks are read -------------------------------------------------
# Python ints, numpy arrays and traced int32 scalars alike, so the kernel's
# walk and the engine's count of it cannot drift apart.

def block_bounds(start, pos, active, block, max_len):
    """(first, count): the position blocks a slot's walk fetches. Blocks
    before ``start // block`` and from ``ceil(pos / block)`` on are not
    read, nor any block of a slot that is not active or owns no row yet."""
    xp = jnp if isinstance(pos, jax.Array) else np
    pos = xp.minimum(pos, max_len)
    first = start // block
    count = xp.where(active & (pos > start),
                     (pos + block - 1) // block - first, 0)
    return first, count


def rows_read(start, pos, active, block, max_len):
    """Cache rows (a row: one position of one slot, every layer and head)
    the kernel fetches for these slots."""
    return int(np.sum(block_bounds(np.asarray(start), np.asarray(pos),
                                   np.asarray(active, bool), block,
                                   max_len)[1])) * block


# ---- the kernel ------------------------------------------------------------

def _kernel(layer_ref, pos_ref, start_ref, active_ref, q_ref, kn_ref, vn_ref,
            k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, work_slot, work_block,
            m_ref, l_ref, acc_ref, *, scale, block, max_len):
    slots, kv_heads, rows, d = q_ref.shape  # rows: a group's queries, twice
    reps = rows // 2
    depth = k_buf.shape[0]
    layer = layer_ref[0]

    # the token's own column starts every slot's softmax; its value goes to
    # the first copy of a row, the second collects roundings' remainders
    q = q_ref[...].astype(jnp.float32)                    # [B, KV, rows, d]
    s_new = jnp.sum(q * kn_ref[...].astype(jnp.float32), axis=-1,
                    keepdims=True) * scale
    m_ref[...] = jnp.broadcast_to(s_new, m_ref.shape)
    l_ref[...] = jnp.ones_like(l_ref)
    first = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 2) < reps
    acc_ref[...] = jnp.where(first, vn_ref[...].astype(jnp.float32), 0.0)

    def list_slot(b, n):
        first, count = block_bounds(start_ref[b], pos_ref[b],
                                    active_ref[b] != 0, block, max_len)

        def put(j, n):
            work_slot[n] = b
            work_block[n] = first + j
            return n + 1
        return jax.lax.fori_loop(0, count, put, n)

    n_work = jax.lax.fori_loop(0, slots, list_slot, 0)

    def fetch(i, buf):
        b = work_slot[i]
        rows = pl.ds(pl.multiple_of(work_block[i] * block, block), block)
        return (pltpu.make_async_copy(k_hbm.at[layer, b, :, rows, :],
                                      k_buf.at[buf], sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, b, :, rows, :],
                                      v_buf.at[buf], sems.at[1, buf]))

    def start_fetch(i):
        @pl.when(i < n_work)
        def _():
            for copy in fetch(i, i % depth):
                copy.start()

    def attend(b, buf, col0, lo, hi, masked):
        # every KV head of the slot at once: [KV, rows | block, ...]
        k, v = k_buf[buf], v_buf[buf]                     # [KV, block, d]
        s = _dot(q_ref[b], k, _BNT) * scale               # [KV, rows, block]
        if masked:
            col = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where((col >= lo) & (col < hi), s, _NEG_INF)
            row = col0 + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
            v = jnp.where((row >= lo) & (row < hi), v, jnp.zeros_like(v))
        m = m_ref[b]                                      # [KV, rows, 128]
        m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - _lanes(m_new, block))
        l_ref[b] = alpha * l_ref[b] + jnp.sum(p, axis=2, keepdims=True)
        rounded = p.astype(v.dtype).astype(jnp.float32)
        first = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) < reps
        p = jnp.where(first, rounded, p - rounded).astype(v.dtype)
        acc_ref[b] = _lanes(alpha, d) * acc_ref[b] + _dot(p, v, _BNN)
        m_ref[b] = m_new

    def body(i, carry):
        buf = i % depth
        start_fetch(i + depth - 1)
        for copy in fetch(i, buf):
            copy.wait()
        b = work_slot[i]
        col0 = work_block[i] * block
        lo, hi = start_ref[b], jnp.minimum(pos_ref[b], max_len)
        edge = (col0 < lo) | (col0 + block > hi)

        @pl.when(edge)
        def _():
            attend(b, buf, col0, lo, hi, True)

        @pl.when(jnp.logical_not(edge))
        def _():
            attend(b, buf, col0, lo, hi, False)
        return carry

    for i in range(depth - 1):
        start_fetch(i)
    jax.lax.fori_loop(0, n_work, body, 0)
    acc = acc_ref[...]
    acc = acc[:, :, :reps] + acc[:, :, reps:]
    o_ref[...] = (acc / jnp.broadcast_to(
        l_ref[...][:, :, :reps, :1], acc.shape)).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, k_new, v_new, pos, start, active,
                     layer, *, block: int | None = None,
                     scale: float | None = None, out_dtype=None):
    """q [B, 1, H, hd] against layer ``layer`` of the stacked cache
    k_cache / v_cache [L, B, KV, S, hd], rows ``[start[b], pos[b])`` of
    each active slot, plus the token's own k_new / v_new [B, KV, hd] as one
    more key column -> [B, 1, H, hd]: what
    `generate._gqa_decode_attention` gives for an active slot. ``block``
    left out is `pick_block`'s, ``scale`` (of the scores) ``hd ** -0.5``
    and ``out_dtype`` q's. Differential attention's pairs of heads are this
    call too (`generate._diff_decode_attention`: KV pairs of width 2 hd,
    the rows [q1 | 0] and [0 | q2] of the query pairs that read a key
    pair, the scores scaled by the HEAD's width and o float32, since the
    caller subtracts one softmax's output from the other's)."""
    B, _, H, d = q.shape
    _, _, KV, S, _ = k_cache.shape
    reps = H // KV
    block = block or pick_block(S, d, k_cache.dtype)
    dtype = k_cache.dtype
    scale = d ** -0.5 if scale is None else scale
    out_dtype = jnp.dtype(out_dtype or q.dtype)
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    q2 = q.reshape(B, KV, reps, d).astype(dtype)
    state = pltpu.VMEM((B, KV, 2 * reps, _LANES), jnp.float32)
    tile = KV * block * d * dtype.itemsize
    small = B * KV * max(2 * reps, 32 // dtype.itemsize) * max(d, _LANES)
    o = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block, max_len=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[in_vmem, in_vmem, in_vmem, in_hbm, in_hbm],
            out_specs=in_vmem,
            scratch_shapes=[
                pltpu.VMEM((_DEPTH, KV, block, d), dtype),
                pltpu.VMEM((_DEPTH, KV, block, d), dtype),
                pltpu.SemaphoreType.DMA((2, _DEPTH)),
                pltpu.SMEM((B * (S // block),), jnp.int32),
                pltpu.SMEM((B * (S // block),), jnp.int32),
                state, state,
                pltpu.VMEM((B, KV, 2 * reps, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, KV, reps, d), out_dtype),
        # the K/V buffers; q and the own column (6 bytes a number of
        # `small`), the float32 state (12) and the output, each padded to
        # whole tiles, and 4 to spare; room for spills
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * _DEPTH * tile
            + (22 + out_dtype.itemsize) * small + (16 << 20)),
        name="decode_attention",
        interpret=_use_interpret(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos.astype(jnp.int32),
      start.astype(jnp.int32), active.astype(jnp.int32),
      jnp.concatenate([q2, q2], axis=2),
      k_new[:, :, None].astype(dtype), v_new[:, :, None].astype(dtype),
      k_cache, v_cache)
    return o.reshape(B, 1, H, d)
