"""The Mamba-1 selective state-space recurrence (arXiv:2312.00752), the two
forms serving needs: over a prompt, and one token a slot where the slots'
states lie.

A channel c of a layer keeps N states: with a step dt_t[c] > 0, a fixed A[n,
c] < 0, the token's B_t[n], C_t[n] and its input a_t[c],

    s_t[n, c] = exp(dt_t[c] A[n, c]) s_(t-1)[n, c] + dt_t[c] B_t[n] a_t[c]
    y_t[c]    = sum_n C_t[n] s_t[n, c]

float32 throughout. (The skip D a_t and the gate belong to the mixer:
`models/transformer.py:mamba_mixer`.) States are held STATE-MAJOR, [.., N,
channels]: the channels fill the lanes and the N = 16 states two float32
sublane tiles; [.., channels, 16] would be stored padded to 128 lanes, eight
times the bytes.

  `mamba_scan`         a prompt: plain JAX, a `lax.scan` a token over the
                       row with the state carried (one [B, N, C] read and
                       write a token: an associative scan over a chunk's
                       [B, R, N, C] reads and writes some fifteen times
                       that a token). A token with dt = 0 writes nothing
                       and decays nothing: a row's left padding.
  `mamba_decode_step`  one token a slot, in place on the stacked states of
                       every mamba layer [L, slots, N, C]: on a chip a
                       Mosaic kernel (`mamba_decode_step`) over (slot,
                       channel block) blocks of the one aliased buffer,
                       the layer picked by a prefetched scalar in the index
                       map, as `ops/kda.py:kda_decode_step` is; `jax.numpy`
                       on the CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _use_interpret() -> bool:
    return jax.default_backend() == "cpu"


def mamba_scan(dt, a, Bm, Cm, A, *, unroll: int = 8):
    """dt, a [B, T, C] (dt float32; a in the compute dtype), Bm, Cm [B, T,
    N] float32, A [N, C] float32 (negative) -> (y [B, T, C] float32, the
    state after the row [B, N, C] float32), from a zero state."""
    with jax.named_scope("mamba.scan"):
        f32 = jnp.float32
        B, T, C = dt.shape

        def token(s, t):
            dt_t, a_t, b_t, c_t = t        # [B, C], [B, C], [B, N], [B, N]
            s = jnp.exp(dt_t[:, None, :] * A) * s \
                + (dt_t * a_t.astype(f32))[:, None, :] * b_t[:, :, None]
            return s, jnp.sum(s * c_t[:, :, None], axis=1)

        rows = tuple(jnp.swapaxes(x, 0, 1) for x in (
            dt.astype(f32), a, Bm.astype(f32), Cm.astype(f32)))
        state, y = jax.lax.scan(
            token, jnp.zeros((B, A.shape[0], C), f32), rows,
            unroll=min(unroll, T))
        return jnp.swapaxes(y, 0, 1), state


# ---- one token a slot: serving's decode step --------------------------------

def _channel_block(C: int) -> int:
    """Channels a grid step of the decode kernel: whole lanes, the most
    that divide C up to 8192 (a float32 block of 16 states x 5120 channels
    is 320 KB, read and written once); a toy width that is no multiple of
    128 goes whole."""
    if C % 128:
        return C
    return max(c for c in range(128, min(C, 8192) + 1, 128) if C % c == 0)


def _decode_kernel(layer_ref, active_ref, s_ref, dt_ref, a_ref, b_ref,
                   c_ref, A_ref, s_out, y_ref):
    """Grid (slots, C / cb). s_ref / s_out: the slot's states of cb
    channels, [1, 1, N, cb] of the one aliased buffer; dt, a rows [1, 1,
    cb]; B, C COLUMNS [1, N, 1] (N on sublanes, as the state's rows are);
    A [N, cb]."""
    del layer_ref    # the index maps read it
    live = active_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        dt = dt_ref[0]                                        # [1, cb]
        s = jnp.exp(dt * A_ref[...]) * s_ref[0, 0] \
            + (dt * a_ref[0]) * b_ref[0]                      # [N, cb]
        s_out[0, 0] = s
        y_ref[0] = jnp.sum(s * c_ref[0], axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():    # bit for bit what it was
        s_out[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def _decode_step_kernel(state, layer, dt, a, Bm, Cm, A, active):
    L, slots, N, C = state.shape
    cb = _channel_block(C)
    row = pl.BlockSpec((1, 1, cb), lambda b, c, *_: (b, 0, c))
    col = pl.BlockSpec((1, N, 1), lambda b, c, *_: (b, 0, 0))
    slab = pl.BlockSpec((1, 1, N, cb),
                        lambda b, c, layer, active: (layer[0], b, 0, c))
    return pl.pallas_call(
        _decode_kernel, name="mamba_decode_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(slots, C // cb),
            in_specs=[slab, row, row, col, col,
                      pl.BlockSpec((N, cb), lambda b, c, *_: (0, c))],
            out_specs=[slab, row]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((slots, 1, C), jnp.float32)],
        # the states are updated where they lie (operand 2, after the two
        # prefetched scalars)
        input_output_aliases={2: 0},
        interpret=_use_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), active.astype(jnp.int32),
      state, dt[:, None], a[:, None], Bm[:, :, None], Cm[:, :, None], A)


def _decode_step_xla(state, layer, dt, a, Bm, Cm, A, active):
    s0 = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    s = jnp.exp(dt[:, None, :] * A) * s0 \
        + (dt * a)[:, None, :] * Bm[:, :, None]
    y = jnp.sum(s * Cm[:, :, None], axis=1)
    s = jnp.where(active[:, None, None], s, s0)
    return jax.lax.dynamic_update_index_in_dim(state, s, layer, 0), y


def mamba_decode_step(state, layer, dt, a, Bm, Cm, A, active, *,
                      kernel=None):
    """The recurrence's one-token form on the slots' states, in place:
    ``state`` [L, slots, N, C] float32 (every mamba layer's, stacked),
    ``layer`` which of them; dt, a [slots, C], Bm, Cm [slots, N], A [N, C],
    active [slots] bool -> (state, y [slots, C] float32). A slot that is
    not active keeps its state bit for bit (its y is junk). On a chip the
    Mosaic kernel `mamba_decode_step`; on the CPU the same step in
    `jax.numpy` (``kernel`` forces either, for the tests)."""
    f32 = jnp.float32
    dt, a, Bm, Cm, A = (x.astype(f32) for x in (dt, a, Bm, Cm, A))
    kernel = not _use_interpret() if kernel is None else kernel
    with jax.named_scope("mamba.step"):
        if not kernel:
            return _decode_step_xla(state, jnp.asarray(layer, jnp.int32),
                                    dt, a, Bm, Cm, A, active)
        state, y = _decode_step_kernel(state, jnp.asarray(layer, jnp.int32),
                                       dt, a, Bm, Cm, A, active)
        return state, y[:, 0]
