"""Mixture-of-Experts FFN: dropless top-k routing with a sorted dispatch.

Absent from the reference (SURVEY.md §2.3 marks EP as greenfield-mandatory).
One path, four stages, each under a `jax.named_scope` a profile groups by:

  moe.route     router logits in float32 at the highest matmul precision,
                scores a softmax or a sigmoid (`cfg.moe_scoring`) over ALL
                experts, the k largest kept (by score plus a per-expert
                bias that takes no gradient where `cfg.moe_select_bias`;
                the weights are the unbiased scores), renormalised to sum
                to 1 when `cfg.moe_norm_topk` (Mixtral; left as they are
                when not: OLMoE) and scaled by `cfg.moe_route_scale`
  moe.dispatch  the N*k (token, expert) assignments ordered by expert (a
                stable sort), group sizes by a count per expert, the
                tokens' rows gathered into that order
  moe.experts   SwiGLU per expert as three grouped matmuls over the ragged
                groups: `jax.lax.ragged_dot`, whose tiles are the MXU's
                (hundreds of rows a group: training, prefill), except on a
                row buffer of at most one ROW_TILE on the chip (a decode
                substep's front, a short prefill's: a dozen rows a group
                at most, the time is the weights' fetch), which
                `ops.grouped_matmul` streams each touched expert's
                weights through once (`_rows_kernel`: static shapes
                alone decide)
  moe.combine   rows gathered back into token order and summed over the k
                slots, weighted by the routing weights, in float32
  moe.shared    where `cfg.moe_shared_d_ff`: a dense SwiGLU every token
                passes, added to the routed sum

One chip's share (`cfg.moe_held_experts` of the `cfg.moe_experts` the
router scores, from `cfg.moe_first_expert` on): the layer routes over ALL
experts, orders the assignments that fall on its own experts first (by
expert) and the others after them, and computes its experts' part of the
result; what the absent experts would add is left out. Such a chip works
on the FRONT of the sorted buffer: `front_rows` = FRONT_OVER_EXPECTED
times the load it expects (N * k * held / E), a static shape. Where the
rows that fell on the held experts fit the front (a count the dispatch
makes anyway), every stage between the two sorts is `front` rows long:
the gather into the buffer's order, the mask of the rows past the groups'
sum (they belong to no group: zeros in, zeros out, forward and backward),
the grouped matmuls, the SwiGLU. Only two gathers stay N*k INDICES long,
and they read a front-sized source: the combine's (an assignment whose
place is past the front has a routing weight of 0) and the dispatch's
backward. Where the rows do not fit, the same stage runs on the whole
[N*k, d] buffer, the most that can fall on the held experts, so no
assignment to a held expert is ever dropped: one `lax.cond` forward and
one backward, on the device (`_front_or_whole`; stats["compact"] says
which way a layer went). A layer that holds every expert, or whose front
would be the whole buffer, has no `cond`.

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

from ray_tpu.ops import grouped_matmul
from ray_tpu.parallel.sharding import with_logical_constraint as _wlc

Params = Dict[str, Any]


def moe_param_logical_axes(cfg) -> Dict[str, tuple]:
    """Logical axes for one layer-stack of MoE parameters (leading layers
    axis; experts axis sharded over the ``expert`` mesh axis)."""
    axes = {
        "router": ("layers", "embed", "experts"),
        "w_gate": ("layers", "experts", "embed", "mlp"),
        "w_up": ("layers", "experts", "embed", "mlp"),
        "w_down": ("layers", "experts", "mlp", "embed"),
    }
    if cfg.moe_select_bias:
        axes["router_bias"] = ("layers", "experts")
    if cfg.moe_shared_d_ff:
        axes.update({"ws_gate": ("layers", "embed", "mlp"),
                     "ws_up": ("layers", "embed", "mlp"),
                     "ws_down": ("layers", "mlp", "embed")})
    return axes


def init_moe_params(rng: jax.Array, cfg, n_layers=None,
                    normal=None) -> Params:
    """Stacked per-layer MoE params: router [L,d,E] + the held experts'
    FFNs [L,held,...] (+ the selection bias [L,E], zeros; + the shared
    expert's FFN [L,...]). ``normal(key, shape, scale)`` draws a matrix
    (left out: `scaled_normal` in ``cfg.param_dtype``)."""
    L = cfg.n_layers if n_layers is None else n_layers
    d, ff, E, held = cfg.d_model, cfg.d_ff, cfg.moe_experts, cfg.held_experts
    k = iter(jax.random.split(rng, 8))
    normal = normal or functools.partial(scaled_normal,
                                         dtype=cfg.param_dtype)
    in_scale = d ** -0.5
    out_scale = (2 * (cfg.init_depth or cfg.n_layers)) ** -0.5 \
        * d ** -0.5                                      # times (f/d)^0.5
    lay = {
        "router": normal(next(k), (L, d, E), in_scale),
        "w_gate": normal(next(k), (L, held, d, ff), in_scale),
        "w_up": normal(next(k), (L, held, d, ff), in_scale),
        "w_down": normal(next(k), (L, held, ff, d),
                         out_scale * (ff / d) ** 0.5),
    }
    if cfg.moe_select_bias:
        lay["router_bias"] = jnp.zeros((L, E), cfg.param_dtype)
    if cfg.moe_shared_d_ff:
        fs = cfg.moe_shared_d_ff
        lay.update({
            "ws_gate": normal(next(k), (L, d, fs), in_scale),
            "ws_up": normal(next(k), (L, d, fs), in_scale),
            "ws_down": normal(next(k), (L, fs, d),
                              out_scale * (fs / d) ** 0.5),
        })
    return lay


def scaled_normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def swiglu(h, w_gate, w_up, w_down, cfg, mesh: Optional[Mesh] = None):
    """A dense SwiGLU: h [B, T, d] -> [B, T, d] (the shared expert here,
    a dense layer's feed-forward in transformer.ffn_block)."""
    gate = jnp.einsum("btd,df->btf", h, w_gate.astype(cfg.dtype))
    up = jnp.einsum("btd,df->btf", h, w_up.astype(cfg.dtype))
    ff = jax.nn.silu(gate) * up
    ff = _wlc(ff, ("batch", "seq", "mlp"), mesh=mesh)
    return jnp.einsum("btf,fd->btd", ff, w_down.astype(cfg.dtype))


# ---- the two permutations, gathers both ways --------------------------------

def _clip(inv, rows):
    """Places in the sorted buffer as indices into its first ``rows``."""
    return inv if rows == inv.shape[0] else jnp.minimum(inv, rows - 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _dispatch_rows(k, rows, x, order, inv):
    """x [N, d] -> [rows, d], the front of the sorted buffer: row i is the
    token of assignment order[i] (assignment a belongs to token a // k).
    Backward: the rows gathered back by ``inv`` (the inverse permutation;
    an assignment whose place is past the front takes nothing) and summed
    over the k slots of each token."""
    return x[order[:rows] // k]


def _dispatch_fwd(k, rows, x, order, inv):
    return _dispatch_rows(k, rows, x, order, inv), inv


def _dispatch_bwd(k, rows, inv, g):
    g = g[_clip(inv, rows)]
    if rows < inv.shape[0]:
        g = jnp.where((inv < rows)[:, None], g, 0)
    return g.reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine_rows(out, top_p, inv, order):
    """out [rows, d], the front of the sorted buffer, top_p [N, k] -> y
    [N, d]: each token's k rows gathered back by ``inv``, weighted and
    summed in float32. An assignment whose place is past the front reads
    the front's last row at a weight of 0 (it fell on no held expert).
    Backward: the cotangent's rows gathered INTO the buffer's order, as the
    dispatch gathers the tokens', so nothing [N*k, d] is made there."""
    rows = out.shape[0]
    back = out[_clip(inv, rows)].reshape(top_p.shape + out.shape[-1:])
    y = jnp.einsum("nkd,nk->nd", back.astype(jnp.float32), top_p)
    return y.astype(out.dtype)


def _combine_fwd(out, top_p, inv, order):
    return _combine_rows(out, top_p, inv, order), (out, top_p, inv, order)


def _combine_bwd(res, g):
    out, top_p, inv, order = res
    rows, front = out.shape[0], order[:out.shape[0]]
    g_rows = g[front // top_p.shape[1]].astype(jnp.float32)   # [rows, d]
    d_out = (top_p.reshape(-1)[front][:, None] * g_rows).astype(out.dtype)
    d_p = jnp.sum(out.astype(jnp.float32) * g_rows, axis=-1)[
        _clip(inv, rows)]
    if rows < inv.shape[0]:
        d_p = jnp.where(inv < rows, d_p, 0)
    return d_out, d_p.reshape(top_p.shape), None, None


_combine_rows.defvjp(_combine_fwd, _combine_bwd)


# ---- the expert stage: dispatch, grouped SwiGLU, combine --------------------

# the front of the sorted buffer a chip that holds a share of the experts
# works on, as a multiple of the load it expects (N * k * held / E)
FRONT_OVER_EXPECTED = 2
ROW_TILE = 512      # the front is whole row tiles of the grouped matmuls


def front_rows(assignments: int, held: int, experts: int) -> int:
    """Rows of the sorted buffer's compact front for ``assignments`` = N*k
    (token, expert) pairs of which ``held`` of ``experts`` fall here:
    FRONT_OVER_EXPECTED times the expected load, in whole row tiles, never
    more than there are assignments."""
    expected = -(-assignments * held // experts)
    rows = -(-FRONT_OVER_EXPECTED * expected // ROW_TILE) * ROW_TILE
    return min(rows, assignments)


def _on_chip() -> bool:
    return jax.default_backend() != "cpu"


def _rows_kernel(rows: int, d: int, f: int, dtype) -> bool:
    """Whether the three grouped matmuls of a buffer of ``rows`` rows,
    [rows, d] x [held, d, f] twice and the way back, run
    `grouped_matmul_rows` and not `jax.lax.ragged_dot`: the buffer is one
    row tile at most, so whatever the held count a group has few rows and
    the weights' fetch is the time, and the kernel takes the shapes. Off
    the chip `ragged_dot` throughout (the kernel's tests run it in the
    interpreter)."""
    return (rows <= ROW_TILE and _on_chip()
            and grouped_matmul.takes(rows, d, f, dtype))


def _expert_rows(k, rows, masked, x, order, inv, group_sizes, top_p,
                 w_gate, w_up, w_down):
    """x [N, d] -> y [N, d]: the held experts' SwiGLU of every token,
    weighted by ``top_p`` [N, k] and summed over the k slots, on the first
    ``rows`` rows of the sorted buffer (all N*k, or a front the live rows
    fit in). ``masked``: rows past the groups' sum belong to no group."""
    dtype = x.dtype
    with jax.named_scope("moe.dispatch"):
        xs = _dispatch_rows(k, rows, x, order, inv)       # [rows, d]
        if masked:
            in_group = (jnp.arange(rows) < group_sizes.sum())[:, None]
            xs = jnp.where(in_group, xs, 0)
    with jax.named_scope("moe.experts"):
        dot = grouped_matmul.grouped_matmul_rows if _rows_kernel(
            rows, x.shape[1], w_gate.shape[2], dtype) else jax.lax.ragged_dot
        gate = dot(xs, w_gate.astype(dtype), group_sizes)
        up = dot(xs, w_up.astype(dtype), group_sizes)
        act = jax.nn.silu(gate) * up                      # [rows, f]
        out = dot(act, w_down.astype(dtype), group_sizes)  # [rows, d]
    with jax.named_scope("moe.combine"):
        if masked:
            out = jnp.where(in_group, out, 0)
        return _combine_rows(out, top_p, inv, order)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _front_or_whole(k, rows, fits, x, order, inv, group_sizes, top_p,
                    w_gate, w_up, w_down):
    """`_expert_rows` on the front of ``rows`` rows where ``fits`` (the
    live rows fit it), else on the whole buffer: one `lax.cond` forward and
    one backward (each backward branch the vjp of its own forward), the
    stage's inputs the only residuals, so neither branch pays for the
    other's."""
    return jax.lax.cond(
        fits, functools.partial(_expert_rows, k, rows, True),
        functools.partial(_expert_rows, k, order.shape[0], True),
        x, order, inv, group_sizes, top_p, w_gate, w_up, w_down)


def _front_or_whole_fwd(k, rows, *args):
    return _front_or_whole(k, rows, *args), args


def _front_or_whole_bwd(k, rows, args, g):
    fits, x, order, inv, group_sizes, top_p, *w = args

    def pull(n):
        def stage(x, top_p, *w):
            return _expert_rows(k, n, True, x, order, inv, group_sizes,
                                top_p, *w)
        return lambda g, *primals: jax.vjp(stage, *primals)[1](g)
    dx, dp, *dw = jax.lax.cond(fits, pull(rows), pull(order.shape[0]),
                               g, x, top_p, *w)
    return (None, dx, None, None, None, dp, *dw)


_front_or_whole.defvjp(_front_or_whole_fwd, _front_or_whole_bwd)


# the leaves `route` reads through ``.astype(float32)``: a serving replica
# keeps them float32 (transformer.read_in_float32 / serving_params)
READ_IN_FLOAT32 = ("router", "router_bias")
# the leaves that take no gradient and no optimiser update (weight decay
# included): `route` reads the bias under `stop_gradient`, and
# training.make_train_step leaves them as they are. (What moves a bias in
# training is a balancing rule outside the gradient, which is not here.)
NO_UPDATE = ("router_bias",)


def hold_no_update_leaves(updates):
    """An optimiser's updates with zeros for the leaves NO_UPDATE names."""
    return jax.tree_util.tree_map_with_path(
        lambda path, u: jnp.zeros_like(u)
        if path[-1].key in NO_UPDATE else u, updates)


def route(x: jax.Array, router: jax.Array, cfg, bias=None):
    """x [N, d], router [d, E], bias [E] or None -> (probs [N, E], top_p
    [N, k], top_i [N, k]), all in float32 at the highest matmul precision
    (2048 x 64 a token costs nothing, and a routing choice then differs
    from a float32 reference's only on a true near-tie). ``probs`` sum to
    1 over the experts (a sigmoid's scores divided by their sum): the
    load-balance statistic's."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        scores = probs = jax.nn.softmax(logits, axis=-1)
    if bias is None:
        top_p, top_i = jax.lax.top_k(scores, cfg.moe_top_k)
    else:   # chosen by score + bias, weighted by the score alone
        _, top_i = jax.lax.top_k(scores + jax.lax.stop_gradient(
            bias.astype(jnp.float32)), cfg.moe_top_k)
        top_p = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.moe_norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    if cfg.moe_route_scale != 1.0:
        top_p = top_p * cfg.moe_route_scale
    return probs, top_p, top_i


def moe_layer(h: jax.Array, lp: Params, cfg, mesh: Optional[Mesh] = None
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One MoE FFN layer. h: [B, T, d] -> (out [B, T, d], stats).

    lp: per-layer params {router [d,E], w_gate/w_up [held,d,f], w_down
    [held,f,d]} (+ router_bias [E], ws_gate/ws_up/ws_down).
    stats["aux"] is the load-balance term E * sum_e f_e * p_e with f_e the
    assignments to expert e per token (summing to k over the experts) and
    p_e the mean router probability: k at perfect balance; weight it into
    the train loss via cfg.moe_aux_weight (over the held experts alone
    where a share is held: that chip's part of the sum). stats["load"] is
    the largest group over the mean group of the held experts (1.0 at
    perfect balance), stats["held"] the share of the N*k assignments that
    fall on held experts (1.0 when all are held), stats["compact"] 1.0
    where the stage ran on the sorted buffer's front (or there is no
    front to miss), 0.0 where the rows overflowed it, stats["fetched"] the
    held experts that got at least one row (the weights the grouped matmuls
    had to fetch: what a decode step of a few rows a chip costs),
    stats["rows_kernel"] 1.0 where the grouped matmuls that ran were
    `ops.grouped_matmul`'s (`_rows_kernel`).
    """
    B, T, d = h.shape
    E, k = cfg.moe_experts, cfg.moe_top_k
    held, first = cfg.held_experts, cfg.moe_first_expert
    share = held < E          # some of the router's experts are not here
    N = B * T
    x = h.reshape(N, d)

    with jax.named_scope("moe.route"):
        probs, top_p, top_i = route(x, lp["router"], cfg,
                                    lp.get("router_bias"))
    # the router reads h as it comes (float32 after an `attn_float32`
    # mixer), the experts what a layer of cfg.dtype would hand them
    h = h.astype(cfg.dtype)
    x = x.astype(cfg.dtype)

    with jax.named_scope("moe.dispatch"):
        expert_of = top_i.reshape(N * k)
        if share:
            # held experts numbered from 0, every absent one `held`: the
            # stable sort puts their assignments last, past every group
            local = expert_of - first
            expert_of = jnp.where((local >= 0) & (local < held), local,
                                  held)
            top_p = jnp.where(expert_of.reshape(N, k) < held, top_p, 0.0)
        order = jnp.argsort(expert_of, stable=True)      # by expert
        inv = jnp.argsort(order)                         # its inverse
        group_sizes = jnp.sum(
            expert_of[:, None] == jnp.arange(held, dtype=expert_of.dtype),
            axis=0, dtype=jnp.int32)                     # [held]

    stage = (x, order, inv, group_sizes, top_p, lp["w_gate"], lp["w_up"],
             lp["w_down"])
    rows = front_rows(N * k, held, E) if share else N * k
    if rows < N * k:      # the front where the live rows fit it
        fits = group_sizes.sum() <= rows
        y = _front_or_whole(k, rows, fits, *stage)
        compact = fits.astype(jnp.float32)
    else:
        y = _expert_rows(k, N * k, share, *stage)
        compact = jnp.ones((), jnp.float32)
    y = y.reshape(B, T, d)
    if cfg.moe_shared_d_ff:
        with jax.named_scope("moe.shared"):
            y = y + swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                           cfg, mesh)
    y = _wlc(y, ("batch", "seq", "embed"), mesh=mesh)

    per_expert = group_sizes.astype(jnp.float32)
    # the assignments on held experts: all N*k where every expert is held
    on_held = per_expert.sum() if share else float(N * k)
    aux = E * jnp.sum(per_expert / N
                      * probs.mean(axis=0)[first:first + held])
    load = per_expert.max() * held / jnp.maximum(on_held, 1.0)
    # a front is whole row tiles, so the buffer it overflows into is longer
    # than one: where a layer takes the kernel, it takes it on `rows`
    kernel = compact if _rows_kernel(rows, d, cfg.d_ff, cfg.dtype) \
        else jnp.zeros((), jnp.float32)
    return y, {"aux": aux, "load": load, "compact": compact,
               "rows_kernel": kernel,
               "held": jnp.asarray(on_held / (N * k), jnp.float32),
               "fetched": jnp.sum(group_sizes > 0).astype(jnp.float32)}
