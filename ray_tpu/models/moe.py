"""Mixture-of-Experts FFN: dropless top-k routing with a sorted dispatch.

Absent from the reference (SURVEY.md §2.3 marks EP as greenfield-mandatory).
One path, four stages, each under a `jax.named_scope` a profile groups by:

  moe.route     router logits in float32 at the highest matmul precision,
                softmax over ALL experts, the k largest probabilities kept
                (renormalised to sum to 1 when `cfg.moe_norm_topk`:
                Mixtral; left as they are when not: OLMoE)
  moe.dispatch  the N*k (token, expert) assignments ordered by expert (a
                stable sort), group sizes by a count per expert, the
                tokens' rows gathered into that order
  moe.experts   SwiGLU per expert as three grouped matmuls over the ragged
                groups (`jax.lax.ragged_dot`)
  moe.combine   rows gathered back into token order and summed over the k
                slots, weighted by the routing weights, in float32

No token is ever dropped, whatever the imbalance, and no shape depends on
the routing: every buffer is [N*k, ...] or smaller (no [.., E, capacity]
tensor). Both permutations are gathers in the forward AND the backward
pass (the transpose of a gather by a permutation is the gather by its
inverse), so the step holds no scatter-add of rows.

With all experts initialised identically and `moe_norm_topk` the layer is
numerically EQUAL to the dense FFN it replaces: the parity tests exploit
this. The expert weights keep their `experts` logical axis (sharded over
the `expert` mesh axis); how fast that is is not settled here.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.parallel.sharding import with_logical_constraint as _wlc

Params = Dict[str, Any]


def moe_param_logical_axes() -> Dict[str, tuple]:
    """Logical axes for one layer-stack of MoE parameters (leading layers
    axis; experts axis sharded over the ``expert`` mesh axis)."""
    return {
        "router": ("layers", "embed", "experts"),
        "w_gate": ("layers", "experts", "embed", "mlp"),
        "w_up": ("layers", "experts", "embed", "mlp"),
        "w_down": ("layers", "experts", "mlp", "embed"),
    }


def init_moe_params(rng: jax.Array, cfg) -> Params:
    """Stacked per-layer MoE params: router [L,d,E] + expert FFNs [L,E,...]."""
    L, d, ff, E = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.moe_experts
    pd = cfg.param_dtype
    k = iter(jax.random.split(rng, 8))

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(pd)

    in_scale = d ** -0.5
    out_scale = (2 * L) ** -0.5 * d ** -0.5 * (ff / d) ** 0.5
    return {
        "router": normal(next(k), (L, d, E), in_scale),
        "w_gate": normal(next(k), (L, E, d, ff), in_scale),
        "w_up": normal(next(k), (L, E, d, ff), in_scale),
        "w_down": normal(next(k), (L, E, ff, d), out_scale),
    }


# ---- the two permutations, gathers both ways --------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch_rows(k, x, order, inv):
    """x [N, d] -> [N*k, d]: row i is the token of assignment order[i]
    (assignment a belongs to token a // k). Backward: the rows gathered
    back by ``inv`` (the inverse permutation) and summed over the k slots
    of each token."""
    return x[order // k]


def _dispatch_fwd(k, x, order, inv):
    return x[order // k], inv


def _dispatch_bwd(k, inv, g):
    return g[inv].reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute_rows(x, idx, inv):
    """x[idx] for a permutation ``idx`` whose inverse is ``inv``; backward
    is the gather by ``inv``."""
    return x[idx]


def _permute_fwd(x, idx, inv):
    return x[idx], inv


def _permute_bwd(inv, g):
    return g[inv], None, None


_permute_rows.defvjp(_permute_fwd, _permute_bwd)


# the leaves `route` reads through ``.astype(float32)``: a serving replica
# keeps them float32 (transformer.read_in_float32 / serving_params)
READ_IN_FLOAT32 = ("router",)


def route(x: jax.Array, router: jax.Array, cfg):
    """x [N, d], router [d, E] -> (probs [N, E], top_p [N, k], top_i
    [N, k]), all in float32 at the highest matmul precision (2048 x 64 a
    token costs nothing, and a routing choice then differs from a float32
    reference's only on a true near-tie)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.moe_norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_i


def moe_layer(h: jax.Array, lp: Params, cfg, mesh: Optional[Mesh] = None
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One MoE FFN layer. h: [B, T, d] -> (out [B, T, d], stats).

    lp: per-layer params {router [d,E], w_gate/w_up [E,d,f], w_down [E,f,d]}.
    stats["aux"] is the load-balance term E * sum_e f_e * p_e with f_e the
    assignments to expert e per token (summing to k over the experts) and
    p_e the mean router probability: k at perfect balance; weight it into
    the train loss via cfg.moe_aux_weight. stats["load"] is the largest
    group over the mean group (1.0 at perfect balance).
    """
    B, T, d = h.shape
    E, k = cfg.moe_experts, cfg.moe_top_k
    N = B * T
    dtype = h.dtype
    x = h.reshape(N, d)

    with jax.named_scope("moe.route"):
        probs, top_p, top_i = route(x, lp["router"], cfg)

    with jax.named_scope("moe.dispatch"):
        expert_of = top_i.reshape(N * k)
        order = jnp.argsort(expert_of, stable=True)      # by expert
        inv = jnp.argsort(order)                         # its inverse
        group_sizes = jnp.sum(
            expert_of[:, None] == jnp.arange(E, dtype=expert_of.dtype),
            axis=0, dtype=jnp.int32)                     # [E]
        xs = _dispatch_rows(k, x, order, inv)            # [N*k, d]

    with jax.named_scope("moe.experts"):
        gate = jax.lax.ragged_dot(xs, lp["w_gate"].astype(dtype),
                                  group_sizes)
        up = jax.lax.ragged_dot(xs, lp["w_up"].astype(dtype), group_sizes)
        act = jax.nn.silu(gate) * up                     # [N*k, f]
        out = jax.lax.ragged_dot(act, lp["w_down"].astype(dtype),
                                 group_sizes)            # [N*k, d]

    with jax.named_scope("moe.combine"):
        back = _permute_rows(out, inv, order).reshape(N, k, d)
        y = jnp.einsum("nkd,nk->nd", back.astype(jnp.float32), top_p)
        y = y.astype(dtype).reshape(B, T, d)
    y = _wlc(y, ("batch", "seq", "embed"), mesh=mesh)

    per_expert = group_sizes.astype(jnp.float32)
    aux = E * jnp.sum(per_expert / N * probs.mean(axis=0))
    load = per_expert.max() * E / (N * k)
    return y, {"aux": aux, "load": load}

