"""Built-in model family: Llama-style decoders, TPU-first (SURVEY.md §7.6)."""

from ray_tpu.models.config import (
    PRESETS,
    TransformerConfig,
    bert_base_config,
    get_config,
    gpt2_small_config,
    llama3_8b_config,
    llama3_70b_config,
    tiny_config,
)
from ray_tpu.models.mlm import mask_tokens
from ray_tpu.models.transformer import (
    forward,
    init_params,
    loss_fn,
    param_logical_axes,
)
from ray_tpu.models.training import (
    batch_sharding,
    init_train_state,
    make_eval_step,
    make_optimizer,
    make_train_step,
    state_shardings,
)

__all__ = [
    "TransformerConfig", "get_config", "PRESETS", "tiny_config",
    "gpt2_small_config", "llama3_8b_config", "llama3_70b_config",
    "bert_base_config", "mask_tokens",
    "forward", "init_params", "loss_fn", "param_logical_axes",
    "make_optimizer", "make_train_step", "make_eval_step",
    "init_train_state", "state_shardings", "batch_sharding",
]
