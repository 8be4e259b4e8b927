"""Train-step factory: optax + jit with sharded, donated state.

The reference's training loop lives in user code wrapped by DDP (ref:
python/ray/train/torch/train_loop_utils.py:75); here the framework owns a
canonical SPMD step: grads/optimizer fused into one XLA program, state
donated (no HBM copy), shardings inferred from the model's logical axes so
ZeRO-3 (fsdp), TP, and CP fall out of the rule table.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.moe import hold_no_update_leaves
from ray_tpu.models.transformer import (
    init_params,
    loss_fn,
    param_logical_axes,
)
from ray_tpu.parallel.sharding import logical_sharding

TrainState = Dict[str, Any]  # {"step", "params", "opt_state"}


def make_optimizer(learning_rate: float = 3e-4, *, weight_decay: float = 0.1,
                   b1: float = 0.9, b2: float = 0.95, grad_clip: float = 1.0,
                   warmup_steps: int = 0, total_steps: Optional[int] = None,
                   mu_dtype=None):
    """AdamW + global-norm clip (+ optional warmup-cosine schedule).

    ``mu_dtype=jnp.bfloat16`` halves the first-moment buffer — with fp32
    master params + fp32 nu that's params x 10 bytes instead of x 12,
    which is what lets the 1B flagship train on a single 16 GiB chip.
    """
    if warmup_steps or total_steps:
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, learning_rate, max(warmup_steps, 1),
            max(total_steps or warmup_steps * 10, warmup_steps + 1))
    else:
        schedule = learning_rate
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay,
                    mu_dtype=mu_dtype),
    )


def make_init_fn(cfg: TransformerConfig, tx):
    def init(rng) -> TrainState:
        params = init_params(rng, cfg)
        return {"step": jnp.zeros((), jnp.int32), "params": params,
                "opt_state": tx.init(params)}
    return init


def _is_axes(x):
    return isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                        for a in x)


def state_shardings(cfg: TransformerConfig, tx, mesh: Mesh, rules=None):
    """Sharding pytree for the whole TrainState.

    Optimizer moments mirror the params tree, so a leaf whose tree path ends
    in a parameter's path, with that parameter's shape, is sharded exactly
    like it (ZeRO: moments shard like their params). Anything else (step
    counts, scalars, a factored moment) is replicated.
    """
    init = make_init_fn(cfg, tx)
    shapes = jax.eval_shape(init, jax.random.key(0))
    flat = jax.tree_util.tree_flatten_with_path
    axes = dict(flat(param_logical_axes(cfg), is_leaf=_is_axes)[0])
    of_param = {path: (leaf.shape, logical_sharding(mesh, axes[path], rules))
                for path, leaf in flat(shapes["params"])[0]}
    repl = NamedSharding(mesh, P())

    def pick(path, leaf):
        for n in range(1, len(path) + 1):
            shape, sharding = of_param.get(path[-n:], (None, None))
            if shape == leaf.shape:
                return sharding
        return repl

    return jax.tree_util.tree_map_with_path(pick, shapes)


def batch_sharding(mesh: Mesh, rules=None):
    """Per-key sharding for a token batch dict ([B, T] arrays).

    Note: under sequence parallelism use the {"inputs", "targets"} batch
    format with T divisible by the sequence axis — a raw {"tokens": [B, T+1]}
    batch generally isn't evenly shardable on the seq dim.
    """
    # Returned as a single sharding: jit treats it as a pytree prefix that
    # applies to every [B, T] leaf of the batch dict.
    return logical_sharding(mesh, ("batch", "seq"), rules)


def init_train_state(rng, cfg: TransformerConfig, tx,
                     mesh: Optional[Mesh] = None, rules=None) -> TrainState:
    """Initialize params/opt state directly into their shards (no host copy)."""
    init = make_init_fn(cfg, tx)
    if mesh is None:
        return jax.jit(init)(rng)
    shardings = state_shardings(cfg, tx, mesh, rules)
    return jax.jit(init, out_shardings=shardings)(rng)


def make_train_step(cfg: TransformerConfig, tx, mesh: Optional[Mesh] = None,
                    rules=None):
    """Returns jitted `(state, batch) -> (state, metrics)`; state donated."""

    def step(state: TrainState, batch):
        grad_fn = jax.value_and_grad(
            functools.partial(loss_fn, cfg=cfg, mesh=mesh), has_aux=True)
        (_, metrics), grads = grad_fn(state["params"], batch)
        updates, new_opt = tx.update(grads, state["opt_state"],
                                     state["params"])
        if cfg.moe_select_bias:   # no weight decay on a leaf held as it is
            updates = hold_no_update_leaves(updates)
        new_params = optax.apply_updates(state["params"], updates)
        metrics = dict(metrics,
                       grad_norm=optax.global_norm(grads),
                       step=state["step"] + 1)
        return {"step": state["step"] + 1, "params": new_params,
                "opt_state": new_opt}, metrics

    if mesh is None:
        return jax.jit(step, donate_argnums=0)
    shardings = state_shardings(cfg, tx, mesh, rules)
    return jax.jit(
        step,
        in_shardings=(shardings, batch_sharding(mesh, rules)),
        out_shardings=(shardings, None),
        donate_argnums=0)


def make_eval_step(cfg: TransformerConfig, mesh: Optional[Mesh] = None):
    def step(params, batch):
        _, metrics = loss_fn(params, batch, cfg, mesh)
        return metrics
    return jax.jit(step)
