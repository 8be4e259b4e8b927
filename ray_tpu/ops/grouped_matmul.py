"""Pallas TPU grouped matmul of a SHORT row buffer, interpret mode off-TPU.

`jax.lax.ragged_dot`'s contract: xs [rows, d_in] sorted by group, w [G,
d_in, d_out], group_sizes [G] -> [rows, d_out], row r times the matrix of
the group it lies in, rows past the groups' sum zero. XLA's own kernels
tile for groups of hundreds of rows, the MXU's case. A decode substep hands
an expert three rows: nothing there is arithmetic, the time is the touched
experts' weights crossing from HBM once, and at three rows a group those
tiles fetch them at a third of the memory roofline (PERF.md section 6,
PR 43). This kernel is the same product shaped for that case:
  - xs and a [rows, tile_n] block of the output stay in VMEM; the grid
    walks (output column tiles) x (groups), groups innermost, and each step
    fetches one group's [d_in, tile_n] block of w, megabytes at a time
    (`_BLOCK_BYTES`), while the step before multiplies (the pipeline's two
    buffers).
  - the groups WITH rows are listed first (scalar prefetch: `_touched`);
    the steps past them name the last listed group's block again, so the
    pipeline issues no copy for them and a group with no row is never
    fetched.
  - a group's rows are read as windows of one sublane tile of the resident
    xs, from the tile its first row lies in on, as many as it needs; a
    window's product is kept for the rows of the group and the block's
    other rows stay what they were: each row belongs to one group and is
    written once, rounded once from the float32 sum.
The backward pass is `ragged_dot`'s own: training reaches this shape only
at toy sizes, and its gradients are then what they were.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import _LANES, _dot, _use_interpret

_WINDOW = 16            # rows a product: bf16's sublane tile
_BLOCK_BYTES = 6 << 20  # the most a fetched block of w holds
_NN = (((1,), (0,)), ((), ()))


def takes(rows: int, d_in: int, d_out: int, dtype) -> bool:
    """Whether the kernel takes xs [rows, d_in] and w [G, d_in, d_out] of
    ``dtype``: bf16, whole windows of rows, whole lanes of columns both
    ways (so [rows, d_out] x [G, d_out, d_in], the way back, is taken
    too). A caller keeps `jax.lax.ragged_dot` for the rest."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and rows % _WINDOW == 0
            and d_in % _LANES == 0 and d_out % _LANES == 0)


def _tile_n(d_in: int, d_out: int, itemsize: int) -> int:
    """Columns of w a step fetches: the widest whole-lane divisor of
    ``d_out`` whose [d_in, tile] block is within `_BLOCK_BYTES` (one lane
    tile where none is)."""
    lanes = d_out // _LANES
    fit = [n for n in range(1, lanes + 1) if lanes % n == 0
           and d_in * n * _LANES * itemsize <= _BLOCK_BYTES]
    return max(fit, default=1) * _LANES


def _touched(group_sizes, rows):
    """(ids [G], n [1], first [G], end [G]) int32: the groups that have a
    row, in order, then the last of them repeated (group 0 where none has
    one); how many have one; each GROUP's first row and the row after its
    last, no further than ``rows``."""
    sizes = group_sizes.astype(jnp.int32)
    G = sizes.shape[0]
    has = sizes > 0
    n = jnp.sum(has, dtype=jnp.int32)
    ids = jnp.arange(G, dtype=jnp.int32)
    place = jnp.cumsum(has, dtype=jnp.int32) - 1        # among the touched
    listed = jnp.sum(jnp.where(has[None, :] & (place[None, :] == ids[:, None]),
                               ids[None, :], 0), axis=1, dtype=jnp.int32)
    last = jnp.max(jnp.where(has, ids, 0))
    end = jnp.cumsum(sizes, dtype=jnp.int32)
    return (jnp.where(ids < n, listed, last), n.reshape(1),
            jnp.minimum(end - sizes, rows), jnp.minimum(end, rows))


def _kernel(ids_ref, n_ref, first_ref, end_ref, x_ref, w_ref, o_ref):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _():
        g = ids_ref[i]
        lo, hi = first_ref[g], end_ref[g]
        tile0 = lo // _WINDOW

        def window(t, carry):
            at = pl.ds(pl.multiple_of((tile0 + t) * _WINDOW, _WINDOW),
                       _WINDOW)
            y = _dot(x_ref[at, :], w_ref[...], _NN).astype(o_ref.dtype)
            row = (tile0 + t) * _WINDOW + jax.lax.broadcasted_iota(
                jnp.int32, y.shape, 0)
            o_ref[at, :] = jnp.where((row >= lo) & (row < hi), y,
                                     o_ref[at, :])
            return carry
        jax.lax.fori_loop(0, (hi + _WINDOW - 1) // _WINDOW - tile0, window,
                          0)


@functools.partial(jax.jit, static_argnames="interpret")
def _rows(xs, w, group_sizes, interpret):
    """Jitted for its cache alone: a program calls this a dozen times at
    two shapes, and a call traced and lowered once a shape, not once a
    call, is what keeps a replica's warm-up where it was (PERF.md section
    6, PR 43)."""
    rows, d_in = xs.shape
    G, _, d_out = w.shape
    item = w.dtype.itemsize
    tn = _tile_n(d_in, d_out, item)
    return pl.pallas_call(
        _kernel, name="ragged-dot-rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(d_out // tn, G),
            in_specs=[
                pl.BlockSpec((rows, d_in), lambda j, i, *_: (0, 0)),
                pl.BlockSpec((None, d_in, tn),
                             lambda j, i, ids, *_: (ids[i], 0, j))],
            out_specs=pl.BlockSpec((rows, tn), lambda j, i, *_: (0, j))),
        out_shape=jax.ShapeDtypeStruct((rows, d_out), xs.dtype),
        interpret=interpret,
        # two buffers each of xs, a block of w and a block of the output;
        # room for a window's float32 product and spills
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * item * (rows * d_in + d_in * tn + rows * tn)
            + (16 << 20)),
    )(*_touched(group_sizes, rows), xs, w)


@jax.custom_vjp
def grouped_matmul_rows(xs, w, group_sizes):
    """`jax.lax.ragged_dot(xs, w, group_sizes)` for the operands `takes`
    names: bf16 in, float32 sums, one rounding to bf16 out."""
    return _rows(xs, w, group_sizes, _use_interpret())


def _fwd(xs, w, group_sizes):
    return (_rows(xs, w, group_sizes, _use_interpret()),
            (xs, w, group_sizes))


def _bwd(res, g):
    xs, w, group_sizes = res
    pull = jax.vjp(lambda xs, w: jax.lax.ragged_dot(xs, w, group_sizes),
                   xs, w)[1]
    return (*pull(g), None)


grouped_matmul_rows.defvjp(_fwd, _bwd)
