"""Model configurations for the built-in transformer family.

The reference ships no LLM definitions (its model zoo is RLlib's small
policy nets, rllib/models/ — SURVEY.md §2.4); the flagship LLM family here
serves the north-star workloads (GPT-2-small data-parallel, Llama-3-8B FSDP
pretrain, Llama-3-8B serving).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """A Llama-3-style decoder-only transformer (RMSNorm, RoPE, GQA, SwiGLU)."""

    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # None -> MHA
    d_ff: int = 1408
    max_seq_len: int = 2048
    rope_theta: float = 500_000.0
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16        # activation/compute dtype
    # the trainer's master weights and a checkpoint's dtype; a serving
    # replica holds each leaf as the forward reads it
    # (transformer.serving_params: `dtype`, the head float32)
    param_dtype: jnp.dtype = jnp.float32
    tie_embeddings: bool = False
    # False -> bidirectional (encoder / BERT-class) attention; the same
    # blocks, RoPE, and loss_fn (inputs/targets/mask form = MLM) apply.
    causal: bool = True
    remat: bool = True                     # checkpoint each layer (HBM <-> FLOPs)
    # "nothing": rematerialize everything (min HBM); "dots": save matmul
    # outputs, recompute elementwise only (less recompute FLOPs -> higher
    # MFU when the saved activations still fit HBM)
    remat_policy: str = "nothing"
    # "auto": ring attention iff mesh's sequence axis > 1, else pallas flash
    # on TPU, else plain XLA attention.
    attention_impl: str = "auto"
    # Microbatches for pipeline parallelism (mesh pipeline axis > 1);
    # None -> 2 * n_stages. Bubble fraction is (S-1)/(M+S-1).
    pipeline_microbatches: Optional[int] = None
    # Mixture-of-Experts FFN (models/moe.py): 0 = dense, else the number
    # of experts, each a SwiGLU of width d_ff. Dropless top-k routing over
    # a softmax of all experts; the k kept weights are renormalised to sum
    # to 1 (Mixtral) or left as they are (OLMoE: moe_norm_topk=False).
    # Experts shard over the `expert` mesh axis.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_norm_topk: bool = True
    moe_aux_weight: float = 0.01
    # RMSNorm (with a learned gain) over the whole q and the whole k
    # projection, before the split into heads and before RoPE (OLMoE).
    qk_norm: bool = False

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def num_params(self) -> int:
        d, v, L = self.d_model, self.vocab_size, self.n_layers
        hd, H, KV, ff = self.head_dim, self.n_heads, self.kv_heads, self.d_ff
        E = self.moe_experts
        ffn = E * 3 * d * ff + d * E if E else 3 * d * ff   # experts+router
        per_layer = (d * H * hd + 2 * d * KV * hd + H * hd * d  # attn
                     + ffn                                      # swiglu(s)
                     + 2 * d                                    # norms
                     + (H * hd + KV * hd if self.qk_norm else 0))
        head = 0 if self.tie_embeddings else d * v
        return v * d + L * per_layer + d + head


# ---- presets ---------------------------------------------------------------

def tiny_config(**kw) -> TransformerConfig:
    """Unit-test sized; runs in milliseconds on CPU."""
    base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=128, max_seq_len=128,
                dtype=jnp.float32, remat=False)
    base.update(kw)
    return TransformerConfig(**base)


def gpt2_small_config(**kw) -> TransformerConfig:
    """124M-class decoder (GPT-2-small scale, modern Llama-style blocks)."""
    base = dict(vocab_size=50304, d_model=768, n_layers=12, n_heads=12,
                n_kv_heads=12, d_ff=3072, max_seq_len=1024,
                tie_embeddings=True)
    base.update(kw)
    return TransformerConfig(**base)


def llama3_1b_config(**kw) -> TransformerConfig:
    """~1.2B-param Llama-3.2-1B-class geometry; single-chip bench flagship."""
    base = dict(vocab_size=128_256, d_model=2048, n_layers=16, n_heads=32,
                n_kv_heads=8, d_ff=8192, max_seq_len=4096,
                rope_theta=500_000.0, tie_embeddings=True)
    base.update(kw)
    return TransformerConfig(**base)


def llama3_8b_config(**kw) -> TransformerConfig:
    """Llama-3-8B geometry (the north-star pretrain target)."""
    base = dict(vocab_size=128_256, d_model=4096, n_layers=32, n_heads=32,
                n_kv_heads=8, d_ff=14336, max_seq_len=8192,
                rope_theta=500_000.0)
    base.update(kw)
    return TransformerConfig(**base)


def llama3_70b_config(**kw) -> TransformerConfig:
    base = dict(vocab_size=128_256, d_model=8192, n_layers=80, n_heads=64,
                n_kv_heads=8, d_ff=28672, max_seq_len=8192)
    base.update(kw)
    return TransformerConfig(**base)


def bert_base_config(**kw) -> TransformerConfig:
    """BERT-base-scale bidirectional encoder (110M class): same blocks
    as the decoders but ``causal=False``; train with ``loss_fn`` in its
    inputs/targets/mask form (= masked-language-model objective, see
    models.mlm). Ref analog: the reference's BERT-base data-parallel
    TorchTrainer benchmark config."""
    # d_ff=2048 keeps the 3-matrix SwiGLU FFN at BERT's 2-matrix-GELU
    # parameter budget (3*768*2048 ≈ 2*768*3072), so the preset stays
    # a 110M-class model
    base = dict(vocab_size=30_522, d_model=768, n_layers=12, n_heads=12,
                d_ff=2048, max_seq_len=512, causal=False,
                tie_embeddings=True)
    base.update(kw)
    return TransformerConfig(**base)


PRESETS = {
    "tiny": tiny_config,
    "bert-base": bert_base_config,
    "gpt2-small": gpt2_small_config,
    "llama3-1b": llama3_1b_config,
    "llama3-8b": llama3_8b_config,
    "llama3-70b": llama3_70b_config,
}


def get_config(name: str, **kw) -> TransformerConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown model preset {name!r}; have {list(PRESETS)}")
    return PRESETS[name](**kw)
