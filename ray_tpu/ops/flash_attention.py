"""Pallas TPU flash attention (fwd + bwd), with interpret mode off-TPU.

The reference has no fused attention of its own (torch SDPA/NCCL territory).
This kernel is the Pallas piece of the attention stack (SURVEY.md §7.6).
Both kernels walk the score matrix in [block_q, block_k] tiles and never
visit a tile the causal mask rules out (`_k_blocks`, `_q_blocks`;
`visited_blocks` counts them: 0.56 of the square at T=4096 with 512 x 512):
  - forward: grid over (batch*heads, q-blocks); a q block walks the k
    blocks up to its diagonal with an online softmax (running max and sum,
    kept lane-replicated, and a float32 accumulator, all in VMEM scratch),
    masks only the tiles the diagonal crosses, and saves the float32
    logsumexp for the backward pass.
  - backward: one kernel, grid over (batch*heads, k-blocks); a k block
    walks the q blocks from its diagonal on, recomputing the probabilities
    from the logsumexp (tiles are [k, q], so p.T and ds.T leave the MXU as
    dv and dk need them), and adds its share of dq into a [T, d] float32
    VMEM scratch that is written once per (batch, head): five products a
    tile and no O(T^2) tensor in HBM.
q, k, v and do reach the MXU in the dtype they arrive in (bf16 in
training) and every product accumulates in float32; p and ds are rounded
to that dtype before their product; max, sum, logsumexp, delta and the
exponent's argument are float32. float32 inputs stay float32 throughout.
Layout is [batch, seq, heads, head_dim] at the API, transposed to
[batch*heads, seq, head_dim] for the MXU-friendly inner matmuls.
VMEM budget: the side a kernel walks (K/V forward, Q/dO/dQ backward) of one
(batch, head) stays resident as it arrived, fetched once per (batch, head):
4 MB double-buffered at T=4096 x 128 bf16 forward, 8 MB backward, and each
kernel asks for the scoped VMEM that takes, so T=16384 compiles for a v5e
(tests/test_chip_compile.py); beyond about T=32768 x 128 the backward's
share outgrows a v5e's VMEM, which is the sequence axis' (ring
attention's) territory. Lengths that are no multiple of a block are
zero-padded to one outside the kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _use_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ---- which tiles are visited -----------------------------------------------
# Positions are absolute from the top left: q row r sees k column c when
# c <= r (causal) and c < t_k. The three functions take Python ints or
# traced int32 scalars alike, so the kernels' loop bounds and the count
# below cannot drift apart.

def _min(a, b):
    both_static = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if both_static else jnp.minimum(a, b)


def _k_blocks(iq, block_q, block_k, t_k, causal):
    """(full, visit) for q block ``iq``: k blocks [0, full) are visible
    whole, [full, visit) need the mask, the rest are never visited."""
    visit = -(-t_k // block_k)
    full = t_k // block_k  # a zero-padded last block is masked too
    if causal:
        visit = _min(visit, (iq * block_q + block_q - 1) // block_k + 1)
        full = _min(full, (iq * block_q + 1) // block_k)
    return full, visit


def _q_blocks(ik, block_q, block_k, n_q, causal):
    """(start, full) for k block ``ik``: q blocks [start, full) need the
    mask, [full, n_q) are visible whole, those before ``start`` see none
    of it."""
    if not causal:
        return 0, 0
    start = _min(n_q, ik * block_k // block_q)
    full = _min(n_q, ((ik + 1) * block_k - 1 + block_q - 1) // block_q)
    return start, full


def visited_blocks(t_q, t_k, block_q, block_k, causal):
    """(visited, total): the (q block, k block) tiles the kernels compute
    and the tiles of the whole [t_q, t_k] score matrix."""
    n_q, n_k = -(-t_q // block_q), -(-t_k // block_k)
    visited = sum(_k_blocks(i, block_q, block_k, t_k, causal)[1]
                  for i in range(n_q))
    return visited, n_q * n_k


def _pick_block(t):
    """The block when the caller names none: T / 512 blocks to the nearest
    whole number, each rounded up to whole 128s (512 at 4096, one block up
    to 767, 640 at 1280). Few tiles beat little padding: timed forward +
    backward at [4, T, 16, 128] bf16 on a v5e, T = 520 to 2000, this is the
    fastest named block of seven or within 3% of it, and at T=4096 512 x 512
    is the fastest pair of twelve in both kernels (PERF.md, PR 30). Wider
    tiles visit more of the masked half (0.625 at 1024); narrower ones pay
    the softmax's per-tile bookkeeping and the loop's pipeline fill more
    often (128-wide ones are slower than computing the whole square).
    head_dim and dtype do not enter: 64 and non-causal ranked the same."""
    n = max(1, (t + 255) // 512)
    return -(-t // (n * _LANES)) * _LANES


def _visible(row0, col0, shape, t_k, causal, *, rows_are_k=False):
    """Mask of one tile whose first q row is ``row0`` and first k column
    ``col0``; ``rows_are_k``: the tile is transposed ([k, q])."""
    q_axis, k_axis = (1, 0) if rows_are_k else (0, 1)
    k_pos = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
    mask = k_pos < t_k
    if causal:
        q_pos = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
        mask &= q_pos >= k_pos
    return mask


def _params(resident_bytes):
    # the resident side is double-buffered; the rest is tiles and spills
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=2 * resident_bytes + (24 << 20))


def _lanes(x, n):
    """A lane-replicated [rows, 128] statistic at width ``n``, without a
    lane broadcast per tile row when ``n`` is whole vregs."""
    if n % _LANES == 0:
        return x if n == _LANES else pltpu.repeat(x, n // _LANES, 1)
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


# ---- forward ---------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale, causal, block_k, t_k):
    block_q, d = q_ref.shape[1:]
    iq = pl.program_id(1)
    q = q_ref[0]                                          # [bq, d]
    # every row sees column 0, so m is a real score after the first tile
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def walk(masked):
        def body(ik, carry):
            col0 = pl.multiple_of(ik * block_k, block_k)
            k = k_ref[0, pl.ds(col0, block_k), :]         # [bk, d]
            v = v_ref[0, pl.ds(col0, block_k), :]
            s = _dot(q, k, _NT) * scale                   # [bq, bk] f32
            if masked:
                s = jnp.where(_visible(iq * block_q, col0, s.shape, t_k,
                                       causal), s, _NEG_INF)
            m = m_ref[...]                                # [bq, 128]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - _lanes(m_new, block_k))
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_ref[...] = _lanes(alpha, d) * acc_ref[...] + _dot(
                p.astype(v.dtype), v, _NN)
            m_ref[...] = m_new
            return carry
        return body

    full, visit = _k_blocks(iq, block_q, block_k, t_k, causal)
    jax.lax.fori_loop(0, full, walk(False), 0)
    jax.lax.fori_loop(full, visit, walk(True), 0)
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / _lanes(l, d)).astype(o_ref.dtype)
    lse_ref[0, 0] = (m_ref[...] + jnp.log(l))[:, 0]


def _fwd(q3, k3, v3, *, scale, causal, block_q, block_k, t_k):
    bh, t, d = q3.shape
    rows_k = k3.shape[1]
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_k=block_k, t_k=t_k)
    o, lse = pl.pallas_call(
        kern,
        grid=(bh, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, rows_k, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, rows_k, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_params(2 * rows_k * d * k3.dtype.itemsize),
        interpret=_use_interpret(),
    )(q3, k3, v3)
    return o, lse


# ---- backward --------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale,
                causal, block_q, t_k):
    # tiles are [k, q] here: p.T and ds.T come out of the MXU as dv and dk
    # need them, and lse / delta broadcast along sublanes as they are stored
    block_k, d = k_ref.shape[1:]
    ik = pl.program_id(1)
    k = k_ref[0]                                          # [bk, d]
    v = v_ref[0]
    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(ik == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def walk(masked):
        def body(iq, carry):
            row0 = pl.multiple_of(iq * block_q, block_q)
            q = q_ref[0, pl.ds(row0, block_q), :]         # [bq, d]
            do = do_ref[0, pl.ds(row0, block_q), :]
            lse = lse_ref[0, :, pl.ds(row0, block_q)]     # [1, bq]
            delta = delta_ref[0, :, pl.ds(row0, block_q)]
            s = _dot(k, q, _NT) * scale                   # [bk, bq] f32
            if masked:
                s = jnp.where(_visible(row0, ik * block_k, s.shape, t_k,
                                       causal, rows_are_k=True),
                              s, _NEG_INF)
            p = jnp.exp(s - lse)
            dv_acc[...] += _dot(p.astype(do.dtype), do, _NN)
            ds = (p * (_dot(v, do, _NT) - delta)).astype(q.dtype)
            dk_acc[...] += _dot(ds, q, _NN)
            dq_acc[pl.ds(row0, block_q), :] += _dot(ds, k, _TN)
            return carry
        return body

    n_q = q_ref.shape[1] // block_q
    start, full = _q_blocks(ik, block_q, block_k, n_q, causal)
    jax.lax.fori_loop(start, full, walk(True), 0)
    jax.lax.fori_loop(full, n_q, walk(False), 0)
    dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(ik == pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd(scale, causal, block_q, block_k, t_k, res, do3):
    q3, k3, v3, o3, lse = res
    bh, t, d = q3.shape
    rows_k = k3.shape[1]
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=False)[:, None, :]  # [bh, 1, t]
    whole_q = pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0))
    whole_row = pl.BlockSpec((1, 1, t), lambda b, i: (b, 0, 0))
    k_block = pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, t_k=t_k),
        grid=(bh, rows_k // block_k),
        in_specs=[whole_q, k_block, k_block, whole_q, whole_row, whole_row],
        out_specs=[whole_q, k_block, k_block],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, rows_k, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, rows_k, d), v3.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        # q, do and dq resident (double-buffered), dq's float32 scratch
        compiler_params=_params(t * d * (3 * q3.dtype.itemsize + 2)),
        interpret=_use_interpret(),
    )(q3, k3, v3, do3, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash3(q3, k3, v3, scale, causal, block_q, block_k, t_k):
    return _flash3_fwd(q3, k3, v3, scale, causal, block_q, block_k, t_k)[0]


def _flash3_fwd(q3, k3, v3, scale, causal, block_q, block_k, t_k):
    o, lse = _fwd(q3, k3, v3, scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, t_k=t_k)
    return o, (q3, k3, v3, o, lse)


_flash3.defvjp(_flash3_fwd, _bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """Fused attention. q: [B, T, H, D], k, v: [B, Tk, H, D] -> [B, T, H, D].

    ``block_q`` / ``block_k`` left out are picked from the shape."""
    b, t, h, d = q.shape
    t_k = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    # Mosaic slices the resident side by the block (sublanes; lanes in lse
    # and delta), so on the chip a block is whole 128s; the pad below fills it
    tile = 1 if _use_interpret() else _LANES

    def whole(named, length):
        size = min(named, length) if named else _pick_block(length)
        return -(-size // tile) * tile

    block_q, block_k = whole(block_q, t), whole(block_k, t_k)

    def to3(x, block):
        x = x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)
        pad = -x.shape[1] % block
        # zero rows change nothing once the forward masks columns >= t_k
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    o3 = _flash3(to3(q, block_q), to3(k, block_k), to3(v, block_k), scale,
                 causal, block_q, block_k, t_k)
    return o3[:, :t].reshape(b, h, t, d).transpose(0, 2, 1, 3)
