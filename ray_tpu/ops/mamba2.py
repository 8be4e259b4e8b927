"""The Mamba-2 state-space recurrence (SSD, arXiv:2405.21060), the two forms
serving needs: over a prompt in chunks, and one token a slot where the
slots' states lie.

A head h of a layer (P channels wide) keeps N states a channel: with a step
dt_t[h] > 0, a fixed SCALAR A[h] < 0, the token's B_t[n], C_t[n] (one B and
one C a token, shared by every head) and its input x_t[h, p],

    S_t[h, p, n] = exp(dt_t[h] A[h]) S_(t-1)[h, p, n] + dt_t[h] x_t[h, p] B_t[n]
    y_t[h, p]    = sum_n C_t[n] S_t[h, p, n]

float32 throughout. (The skip D x_t, the gate and its norm belong to the
mixer: `models/transformer.py:mamba2_mixer`.) States are held STATE-MAJOR,
[.., N, H x P], as `ops/mamba.py` holds Mamba-1's: the H x P channels fill
the lanes and the N = 128 states sixteen float32 sublane tiles. N would fill
the lanes too ([.., H, P, N]), but the step then pays, a vector register of
state, one lane broadcast (x's column) and one lane reduction (the read-out
over n), both on the cross-lane unit; state-major the token's B and C are
columns broadcast ONCE a block, x, dt and the decay are rows, and the
read-out adds registers: nothing but multiply-adds stands beside the bytes.

  `mamba2_scan`         a prompt, in the chunked (SSD) form, plain JAX: a
                        chunk of `chunk` positions is matmuls, (L o (C B^T))
                        X with L the lower-triangular products of the head's
                        decays; between chunks the state is carried, [B, N,
                        H x P] float32, by a `lax.scan` a CHUNK. (A scan a
                        token reads and writes 2 MB a row and layer a token.)
                        A token with dt = 0 and x = 0 writes nothing and
                        decays nothing: a row's left padding, and the
                        positions that fill the last chunk.
  `mamba2_decode_step`  one token a slot, in place on the stacked states of
                        every mamba2 layer [L, slots, N, H x P]: on a chip a
                        Mosaic kernel (`mamba2_decode_step`) over (slot,
                        channel block) blocks of the one aliased buffer, the
                        layer picked by a prefetched scalar in the index map,
                        as `ops/mamba.py:mamba_decode_step` is; `jax.numpy`
                        on the CPU. The decay is one `exp` a head, made
                        before the call, not an [N, C] table inside it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _use_interpret() -> bool:
    return jax.default_backend() == "cpu"


def mamba2_scan(dt, x, Bm, Cm, A, *, chunk: int = 256):
    """dt [B, T, H] float32, x [B, T, H, P] (the compute dtype), Bm, Cm [B,
    T, N] float32, A [H] float32 (negative) -> (y [B, T, H, P] float32, the
    state after the row [B, N, H x P] float32), from a zero state.

    What touches the carried state (a chunk's contribution, the read-out of
    the chunks before) multiplies float32 at the highest precision; the
    chunk's own square, [Q, Q] a head, is rounded to x's dtype for its one
    matmul with x, accumulated in float32."""
    with jax.named_scope("mamba2.scan"):
        f32, hp = jnp.float32, jax.lax.Precision.HIGHEST
        B, T, H, P = x.shape
        N = Bm.shape[-1]
        Q = min(chunk, T)
        short = -T % Q        # filled with dt = 0, x = 0
        if short:
            dt, x, Bm, Cm = (jnp.pad(a, [(0, 0), (0, short)]
                                     + [(0, 0)] * (a.ndim - 2))
                             for a in (dt, x, Bm, Cm))
        n = (T + short) // Q
        x = x.reshape(B, n, Q, H, P)
        Bm, Cm = (a.astype(f32).reshape(B, n, Q, N) for a in (Bm, Cm))
        dt = jnp.swapaxes(dt.astype(f32).reshape(B, n, Q, H), 2, 3)
        # the log of the head's decay from the chunk's start THROUGH position i
        through = jnp.cumsum(dt * A[:, None], axis=-1)        # [B, n, H, Q]
        # inside a chunk: y_i += sum_(j <= i) (C_i . B_j) exp(through_i -
        # through_j) dt_j x_j; C B^T is ONE square a chunk for all heads
        square = jnp.einsum("bcin,bcjn->bcij", Cm, Bm, precision=hp)
        span = through[..., :, None] - through[..., None, :]  # [B, n, H, i, j]
        seen = jnp.tril(jnp.ones((Q, Q), bool))
        weights = square[:, :, None] * dt[..., None, :] \
            * jnp.exp(jnp.where(seen, span, -jnp.inf))
        y = jnp.einsum("bchij,bcjhp->bcihp", weights.astype(x.dtype), x,
                       preferred_element_type=f32)
        # what a chunk adds to the state, from zero: sum_j exp(through_last -
        # through_j) dt_j x_j (x) B_j, state-major
        kept = jnp.exp(through[..., -1:] - through) * dt      # [B, n, H, Q]
        adds = jnp.einsum(
            "bcjn,bcjhp->bcnhp", Bm,
            x.astype(f32) * jnp.swapaxes(kept, 2, 3)[..., None], precision=hp)
        if n == 1:
            state = adds[:, 0]
        else:
            # between chunks the state is carried: decayed by the chunk's
            # whole product, plus what the chunk adds
            whole = jnp.exp(through[..., -1])                 # [B, n, H]

            def carry(S, c):          # -> the state BEFORE the chunk too
                add, decay = c
                return decay[:, None, :, None] * S + add, S
            state, before = jax.lax.scan(
                carry, jnp.zeros((B, N, H, P), f32),
                (jnp.swapaxes(adds, 0, 1), jnp.swapaxes(whole, 0, 1)))
            # and read by the chunk's positions: y_i += exp(through_i) C_i . S
            y = y + jnp.einsum("bcin,cbnhp->bcihp", Cm, before, precision=hp) \
                * jnp.swapaxes(jnp.exp(through), 2, 3)[..., None]
        return y.reshape(B, n * Q, H, P)[:, :T], state.reshape(B, N, H * P)


# ---- one token a slot: serving's decode step --------------------------------

# Channels a grid step of the decode kernel: a float32 block of 128 states x
# 2048 channels is 1 MB, read and written once, two in flight each way
# (PERF.md section 6, PR 49, has the blocks tried on the chip).
_CHANNEL_BLOCK = 2048


def _channel_block(C: int) -> int:
    """Whole lanes, the most that divide C up to `_CHANNEL_BLOCK`; a toy
    width that is no multiple of 128 goes whole."""
    if C % 128:
        return C
    return max(c for c in range(128, min(C, _CHANNEL_BLOCK) + 1, 128)
               if C % c == 0)


def _decode_kernel(layer_ref, active_ref, s_ref, a_ref, u_ref, b_ref, c_ref,
                   s_out, y_ref):
    """Grid (slots, C / cb). s_ref / s_out: the slot's states of cb
    channels, [1, 1, N, cb] of the one aliased buffer; the decay a and the
    input u = dt x as rows [1, 1, cb]; B, C COLUMNS [1, N, 1] (N on
    sublanes, as the state's rows are)."""
    del layer_ref    # the index maps read it
    live = active_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        s = a_ref[0] * s_ref[0, 0] + u_ref[0] * b_ref[0]      # [N, cb]
        s_out[0, 0] = s
        y_ref[0] = jnp.sum(s * c_ref[0], axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():    # bit for bit what it was
        s_out[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def _decode_step_kernel(state, layer, a, u, Bm, Cm, active, in_place):
    L, slots, N, C = state.shape
    cb = _channel_block(C)
    row = pl.BlockSpec((1, 1, cb), lambda b, c, *_: (b, 0, c))
    col = pl.BlockSpec((1, N, 1), lambda b, c, *_: (b, 0, 0))
    slab = pl.BlockSpec((1, 1, N, cb),
                        lambda b, c, layer, active: (layer[0], b, 0, c))
    # in place: the states are updated where they lie (operand 2, after the
    # two prefetched scalars); else the layer's new states alone come back
    out = slab if in_place else pl.BlockSpec(
        (1, 1, N, cb), lambda b, c, *_: (0, b, 0, c))
    return pl.pallas_call(
        _decode_kernel, name="mamba2_decode_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(slots, C // cb),
            in_specs=[slab, row, row, col, col],
            out_specs=[out, row]),
        out_shape=[jax.ShapeDtypeStruct(
            state.shape if in_place else (1,) + state.shape[1:],
            state.dtype), jax.ShapeDtypeStruct((slots, 1, C), jnp.float32)],
        input_output_aliases={2: 0} if in_place else {},
        interpret=_use_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=32 << 20),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), active.astype(jnp.int32),
      state, a[:, None], u[:, None], Bm[:, :, None], Cm[:, :, None])


def _decode_step_xla(state, layer, a, u, Bm, Cm, active, in_place):
    s0 = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    s = a[:, None, :] * s0 + u[:, None, :] * Bm[:, :, None]
    y = jnp.sum(s * Cm[:, :, None], axis=1)
    s = jnp.where(active[:, None, None], s, s0)
    if not in_place:
        return s[None], y
    return jax.lax.dynamic_update_index_in_dim(state, s, layer, 0), y


def mamba2_decode_step(state, layer, dt, x, Bm, Cm, A, active, *,
                       kernel=None, in_place: bool = True):
    """The recurrence's one-token form on the slots' states, in place:
    ``state`` [L, slots, N, H x P] float32 (every mamba2 layer's, stacked),
    ``layer`` which of them; dt [slots, H], x [slots, H, P], Bm, Cm [slots,
    N], A [H], active [slots] bool -> (state, y [slots, H, P] float32). A
    slot that is not active keeps its state bit for bit (its y is junk). On
    a chip the Mosaic kernel `mamba2_decode_step`; on the CPU the same step
    in `jax.numpy` (``kernel`` forces either, for the tests). ``in_place``
    False: the same arithmetic, the stack only READ: -> (the layer's new
    states [slots, N, H x P], y), for a caller that may not overwrite the
    stack it was given (a program must copy a whole stack before it may
    write there: 4.8 GB at the Granite cell's sizes)."""
    f32 = jnp.float32
    slots, H, P = x.shape
    dt, Bm, Cm = (v.astype(f32) for v in (dt, Bm, Cm))
    with jax.named_scope("mamba2.step"):
        # a head's decay and its step, a channel each: rows of H x P
        a = jnp.repeat(jnp.exp(dt * A.astype(f32)), P, axis=-1)
        u = (dt[..., None] * x.astype(f32)).reshape(slots, H * P)
        kernel = not _use_interpret() if kernel is None else kernel
        layer = jnp.asarray(layer, jnp.int32)
        if not kernel:
            state, y = _decode_step_xla(state, layer, a, u, Bm, Cm, active,
                                        in_place)
        else:
            state, y = _decode_step_kernel(state, layer, a, u, Bm, Cm,
                                           active, in_place)
            y = y[:, 0]
        return (state if in_place else state[0]), y.reshape(slots, H, P)
