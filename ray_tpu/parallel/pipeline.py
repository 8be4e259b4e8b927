"""Pipeline parallelism: GPipe-style microbatched execution of a stacked
layer scan over the ``pipeline`` mesh axis.

The reference has no in-tree pipeline parallelism — it delegates to
Alpa-on-Ray (release/alpa_tests/train_opt_2_7b_minimum.py). This is the
TPU-native design (SURVEY.md §2.3): the transformer already stores its L
layers *stacked* on a leading axis and runs them with one `lax.scan`, so
pipelining is a re-partition of exactly that structure:

  - The stack [L, ...] becomes [S, L/S, ...] with the leading (stages) axis
    sharded over the ``pipeline`` mesh axis — each device group holds one
    stage's contiguous block of layers.
  - The batch is split into M microbatches. A `jax.shard_map` manual only
    over the ``pipeline`` axis (every other mesh axis stays auto/GSPMD, so
    tensor/fsdp/sequence sharding inside the block is untouched) runs the
    classic M+S-1-tick schedule: each tick every stage runs its layer block
    on its current activation and hands the result to the next stage with a
    single `ppermute` hop over ICI.
  - The whole schedule is a `lax.scan` over ticks, so `jax.grad` through it
    yields the reverse pipeline automatically — no hand-written backward
    schedule.

Bubble fraction is (S-1)/(M+S-1); pick num_microbatches >= 4*S to amortize.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def pipeline_axis_size(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    return int(mesh.shape.get("pipeline", 1))


def pipeline_scan(body: Callable[[jax.Array, PyTree], Any],
                  x: jax.Array,
                  stacked_params: PyTree,
                  mesh: Mesh,
                  num_microbatches: Optional[int] = None) -> jax.Array:
    """Run ``lax.scan(body, x, stacked_params)`` pipelined over stages.

    ``body(activation, layer_params) -> (activation, _)`` is the SAME block
    function the un-pipelined scan uses. ``stacked_params`` leaves carry a
    leading layer axis of size L; ``x`` is [B, ...] activations. Returns the
    final activations [B, ...], numerically identical to the plain scan
    (tests/test_parallel.py parity test).
    """
    S = pipeline_axis_size(mesh)
    if S <= 1:
        out, _ = jax.lax.scan(lambda c, lp: body(c, lp), x, stacked_params)
        return out

    L = jax.tree.leaves(stacked_params)[0].shape[0]
    if L % S:
        raise ValueError(f"n_layers {L} not divisible by pipeline size {S}")
    M = num_microbatches or 2 * S
    B = x.shape[0]
    if B % M:
        # fall back to the largest microbatch count that divides B
        M = next((m for m in range(min(M, B), 0, -1) if B % m == 0), 1)

    staged = jax.tree.map(
        lambda p: p.reshape((S, L // S) + p.shape[1:]), stacked_params)
    mb = x.reshape((M, B // M) + x.shape[1:])

    def inner(staged_local: PyTree, mb: jax.Array) -> jax.Array:
        # staged_local leaves: [1, L/S, ...] — this device group's stage.
        stage_params = jax.tree.map(lambda p: p[0], staged_local)
        p_idx = jax.lax.axis_index("pipeline")

        def run_stage(act):
            out, _ = jax.lax.scan(lambda c, lp: body(c, lp), act,
                                  stage_params)
            return out

        buf = jnp.zeros(mb.shape[1:], mb.dtype)
        outs = jnp.zeros(mb.shape, mb.dtype)

        def tick(carry, t):
            buf, outs = carry
            inp = jax.lax.dynamic_index_in_dim(
                mb, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            act = jnp.where((p_idx == 0) & (t < M), inp, buf)
            y = run_stage(act)
            emit = t - (S - 1)
            outs = jax.lax.cond(
                (p_idx == S - 1) & (emit >= 0),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(emit, 0, M - 1), 0),
                lambda o: o, outs)
            nxt = jax.lax.ppermute(
                y, "pipeline", [(i, (i + 1) % S) for i in range(S)])
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs),
                                    jnp.arange(M + S - 1))
        # Only the last stage holds real outputs; psum broadcasts them to
        # every pipeline rank (one activation-sized all-reduce per step).
        outs = jax.lax.psum(
            jnp.where(p_idx == S - 1, outs, jnp.zeros_like(outs)),
            "pipeline")
        return outs

    out = _shard_map(
        inner, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P("pipeline"), staged), P()),
        out_specs=P(),
        axis_names={"pipeline"}, check_vma=False)(staged, mb)
    return out.reshape((B,) + x.shape[1:])
