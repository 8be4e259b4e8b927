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

# The mixer kinds that hold nothing a slot keeps and read no other position
# of their own stream: a gated memory unit reads the SAME token's memory, a
# cross layer its own row's query against another layer's keys and values
# (`TransformerConfig.layer_pattern`, `tail_segment`).
LAST_ROW_KINDS = frozenset({"gmu", "cross"})


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
    # The depth the initialiser scales the residual outputs by (each
    # layer's output projections: (2 x depth) ** -0.5); None -> n_layers.
    # A configuration that runs a SLICE of a deeper model (the first
    # period of 48 layers on one pipeline stage) states the whole model's
    # depth, so that its seeded layers weigh in the residual stream what
    # they would there and not twelve times as much.
    init_depth: Optional[int] = None
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
    # Width of a query/key head. None -> d_model / n_heads, resolved when
    # the config is built (a `dataclasses.replace` that changes d_model
    # or n_heads passes head_dim=None and v_head_dim=None too).
    head_dim: Optional[int] = None
    # Latent attention (models/transformer.py:qkv_proj; `kv_lora_rank` 0 =
    # ordinary projections): queries through a latent of `q_lora_rank`
    # and keys/values through one of `kv_lora_rank`, each with its own
    # RMSNorm. Of a query/key head's `head_dim`, the last `rope_head_dim`
    # carry the rotary embedding (the key's rotary part is ONE vector a
    # token, shared by all heads) and the rest none; a value head is
    # `v_head_dim` wide (None -> head_dim). Scores are scaled by
    # head_dim ** -0.5. The rotary columns of the stored latent projections
    # are paired (2i, 2i+1), as this attention's published weights are
    # (the ordinary projections pair by halves). `q_lora_rank` 0 under
    # latent attention: queries straight from the hidden state (`wq`).
    # `nope_head_dim` states the unrotated width in place of `head_dim`
    # (head_dim = nope_head_dim + rope_head_dim) for a configuration whose
    # published `head_dim` is another thing.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    rope_head_dim: int = 0
    v_head_dim: Optional[int] = None
    nope_head_dim: Optional[int] = None
    # False: no rotary embedding anywhere (under latent attention the
    # `rope_head_dim` columns stay, unrotated; positions then enter the
    # model through its recurrent layers alone).
    use_rope: bool = True
    # An elementwise output gate on ordinary attention: the softmax's
    # output times sigmoid(W_g n), n the layer's normed input, W_g a leaf
    # `wg` [d, heads, head_dim] of the layer, before the output projection
    # (arXiv:2505.06708), in training, prefill and decode alike.
    attn_output_gate: bool = False
    # An ordinary attention layer's mixer (projections, softmax, gate,
    # output projection: matmuls at the highest precision), the residual
    # sum after it, the FFN's norm and the router's input in float32,
    # whatever `dtype` is, in training, prefill and decode alike; the
    # experts read that norm rounded to `dtype`, the K/V cache and the
    # layer's result keep `dtype`. For a model whose FIRST layer is such
    # a layer with routed experts: nothing upstream has rounded yet, the
    # stream is at its smallest there, and a near-tie of the router that
    # falls the other way costs the token a whole expert (PERF.md
    # section 6, PR 42, has what that did to a served hybrid's logits).
    attn_float32: bool = False
    # The kinds of token mixer, a period applied from the first layer on
    # (layer i has kind i mod the period's length; the leading dense
    # layers take theirs from the same count): "attention" (whichever the
    # fields above configure) or "kda", gated delta-rule linear attention
    # with a per-channel decay (ops/kda.py; transformer.kda_mixer):
    # `kda_heads` heads of `kda_head_dim` (keys, queries and values
    # alike), a causal depthwise convolution of `kda_conv` taps on each of
    # q, k, v, the decay's and the output gate's projections through a
    # width of `kda_gate_rank`.
    mixer_period: tuple = ("attention",)
    # The same statement for a pattern that is no single period: segments
    # ((kinds of one period, repeats), ...) from the first layer on, e.g.
    # ((("mamba", "window"), 8), (("mamba", "attention"), 1), (("gmu",
    # "cross"), 7)). None: ONE segment, `mixer_period` over the stack
    # (what every configuration with one period states); given, it is the
    # one statement and `mixer_period` is derived from it (a single
    # segment's period, else every layer's kind in order). The further
    # kinds a segment may name, beside "attention" and "kda":
    #   "mamba"   a Mamba-1 selective scan (transformer.mamba_mixer; ops/
    #             mamba.py): `mamba_d_state` states a channel, `mamba_expand`
    #             x d_model channels, a causal depthwise convolution of
    #             `mamba_d_conv` taps, dt through a rank of `mamba_dt_rank`
    #             (None: ceil(d_model / 16))
    #   "mamba2"  a Mamba-2 state-space layer with a SCALAR decay a head
    #             (arXiv:2405.21060; transformer.mamba2_mixer; ops/mamba2.py):
    #             `mamba_heads` heads of `mamba_head_dim` (= `mamba_expand` x
    #             d_model together), `mamba_d_state` states a channel, ONE B
    #             and C a token for all heads (`mamba_groups` 1), the same
    #             convolution over [x | B | C], a gated RMSNorm before the
    #             output projection; a prompt's scan in chunks of
    #             `mamba_chunk` positions
    #   "gmu"     a gated memory unit: W_out (m . silu(W_in n)), m the SAME
    #             token's scan output (before the gate) of the nearest
    #             mamba layer before it; no state
    #   "window"  attention over the `sliding_window` positions that end at
    #             the token itself
    #   "cross"   queries of its own onto the keys and values of the
    #             nearest "attention" layer before it; no K/V of its own
    layer_pattern: Optional[tuple] = None
    sliding_window: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_groups: int = 1
    mamba_chunk: int = 256
    # Differential attention (arXiv:2410.05258, as the SambaY decoders of
    # arXiv:2507.06607 use it) on every attention, window and cross layer:
    # query, key and value heads are taken in adjacent pairs; o1 = softmax(
    # q1 k1^T) [v1 | v2], o2 = softmax(q2 k2^T) [v1 | v2]; the layer's
    # output is RMSNorm(o1 - lam o2) (1 - lam0), lam = exp(lq1 . lk1) -
    # exp(lq2 . lk2) + lam0, lam0 = 0.8 - 0.6 exp(-0.3 layer), the
    # difference and the norm in float32. A query pair reads key/value
    # pair (pair // (query pairs / key pairs)); the serving cache holds a
    # token's keys and values as those pairs, [kv_heads / 2, 2 head_dim].
    diff_attn: bool = False
    # An ordinary attention layer's keys and values held in the serving cache
    # as PAIRS of adjacent heads, [kv_heads / 2, 2 head_dim] a token (as
    # differential attention's are): a head of 64 then fills the 128 lanes,
    # nothing is stored padded, and the decode kernel takes the cache, a
    # query head reading its pair as the row [q | 0] or [0 | q]. The
    # mathematics is the unpaired layer's.
    kv_head_pairs: bool = False
    # A bias on the attention projections (q, k, v and the output).
    attn_bias: bool = False
    # "rms": RMSNorm with a gain; "layer": LayerNorm with a gain and a bias
    # (`rms_eps` is its eps), for the two norms of a layer and the final one.
    norm: str = "rms"
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_gate_rank: int = 0
    # True: a kda layer's write strength is 2 sigmoid(W_beta n), in (0, 2),
    # so that I - b k k^T has an eigenvalue in (-1, 1) (False: in (0, 1)).
    kda_allow_neg_eigval: bool = False
    # Of the `n_layers` layers of an MoE model, the first
    # `moe_dense_layers` have a dense SwiGLU of width `moe_dense_d_ff`.
    moe_dense_layers: int = 0
    moe_dense_d_ff: int = 0
    # The router (models/moe.py:route): scores are a "softmax" or a
    # "sigmoid" of the logits; `moe_select_bias`: the k experts are chosen
    # by score plus a per-expert bias, a leaf that takes no gradient and
    # no optimiser update, and weighted by the unbiased scores; the kept
    # weights (renormalised where `moe_norm_topk`) times `moe_route_scale`.
    moe_scoring: str = "softmax"
    moe_select_bias: bool = False
    moe_route_scale: float = 1.0
    # A shared expert: a dense SwiGLU of this width that every token
    # passes, added to the routed sum (0 = none).
    moe_shared_d_ff: int = 0
    # One chip's share of an expert-parallel deployment: the layer holds
    # experts [moe_first_expert, moe_first_expert + moe_held_experts) of
    # the `moe_experts` the router scores (None = all) and computes their
    # part of the result; what the absent experts would add is left out.
    moe_held_experts: Optional[int] = None
    moe_first_expert: int = 0
    # Multi-token prediction: `mtp_layers` (0 or 1) further block(s) that
    # predict the token after next from the main stack's last hidden
    # state and the next token's embedding (transformer.loss_fn);
    # `mtp_weight` x its cross entropy joins the total loss.
    mtp_layers: int = 0
    mtp_weight: float = 0.0
    # Four fixed multipliers on the stream (the muP scalars a published
    # config states: `embedding_multiplier`, `residual_multiplier`,
    # `attention_multiplier`, `logits_scaling`), each None = absent, and
    # absent means NO operation in any program: the embedding rows times
    # `embed_scale`; a mixer's and a feed-forward's output times
    # `residual_scale` before it joins the stream; an ordinary attention
    # layer's scores times `attn_scale` in place of head_dim ** -0.5; the
    # logits over `logit_divisor`. Serving only (`refuse_untrained`).
    embed_scale: Optional[float] = None
    residual_scale: Optional[float] = None
    attn_scale: Optional[float] = None
    logit_divisor: Optional[float] = None

    def __post_init__(self):
        def need(ok, why):
            if not ok:
                raise ValueError(f"TransformerConfig: {why}")

        if self.layer_pattern is not None:
            pattern = tuple((tuple(kinds), int(reps))
                            for kinds, reps in self.layer_pattern)
            derived = pattern[0][0] if len(pattern) == 1 else tuple(
                kind for kinds, reps in pattern for kind in kinds * reps)
            # (`dataclasses.replace` hands the derived period back in)
            need(pattern and all(kinds and reps >= 1
                                 for kinds, reps in pattern)
                 and sum(len(kinds) * reps for kinds, reps in pattern)
                 == self.n_layers and not self.moe_dense_layers
                 and tuple(self.mixer_period) in (("attention",), derived),
                 "layer_pattern is segments (kinds, repeats >= 1) that add "
                 "up to n_layers, in place of mixer_period and of leading "
                 "dense layers")
            object.__setattr__(self, "layer_pattern", pattern)
            object.__setattr__(self, "mixer_period", derived)
        object.__setattr__(self, "mixer_period", tuple(self.mixer_period))
        for name in ("dtype", "param_dtype"):    # a data file names them
            if isinstance(getattr(self, name), str):
                object.__setattr__(self, name, jnp.dtype(getattr(self, name)))
        if self.nope_head_dim is not None:
            width = self.nope_head_dim + self.rope_head_dim
            need(self.kv_lora_rank and self.head_dim in (None, width),
                 "nope_head_dim belongs to latent attention and head_dim "
                 "is nope_head_dim + rope_head_dim")
            object.__setattr__(self, "head_dim", width)
        if self.head_dim is None:
            need(self.d_model % self.n_heads == 0,
                 "d_model is no multiple of n_heads and no head_dim is given")
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.v_head_dim is None:
            object.__setattr__(self, "v_head_dim", self.head_dim)
        if self.kv_lora_rank:
            need(0 < self.rope_head_dim < self.head_dim,
                 "latent attention needs 0 < rope_head_dim < head_dim")
            need(self.kv_heads == self.n_heads and not self.qk_norm,
                 "latent attention has one key/value head a query head and "
                 "no q/k norms")
        else:
            need(self.v_head_dim == self.head_dim,
                 "v_head_dim differs from head_dim without latent attention")
        period = self.mixer_period
        need(period and set(period) <= {"attention", "kda", "mamba", "mamba2",
                                        "gmu", "window", "cross"},
             f"mixer_period {period!r}")
        if {"window", "cross"} & set(period):
            need(self.diff_attn and not self.moe_experts,
                 f"mixer_period {period!r}: window and cross layers are "
                 "built for differential attention and a dense feed-forward")
            need("window" not in period or self.sliding_window >= 1,
                 "a window layer needs sliding_window >= 1")
        need(self.norm in ("rms", "layer"), f"norm {self.norm!r}")
        need(not self.kv_head_pairs or (
            self.kv_heads % 2 == 0 and not self.diff_attn
            and not self.kv_lora_rank),
            "kv_head_pairs pairs an even number of ordinary key/value heads")
        need(self.attn_scale is None or not self.diff_attn,
             "attn_scale is the scale of an ordinary attention layer's "
             "scores")
        kinds = [self.mixer_kind(i) for i in range(self.n_layers)]
        if "mamba" in period:
            need(self.mamba_d_state and self.mamba_d_conv >= 1
                 and self.mamba_expand >= 1 and self.causal,
                 "a mamba layer needs mamba_d_state, mamba_d_conv >= 1, "
                 "mamba_expand >= 1 and a causal model")
            if self.mamba_dt_rank is None:
                object.__setattr__(self, "mamba_dt_rank",
                                   -(-self.d_model // 16))
        if "mamba2" in period:
            need(self.mamba_d_state and self.mamba_d_conv >= 1 and self.causal
                 and self.mamba_heads * self.mamba_head_dim
                 == self.mamba_channels > 0 and self.mamba_groups == 1
                 and self.mamba_chunk >= 1,
                 "a mamba2 layer needs mamba_d_state, mamba_d_conv >= 1, "
                 "mamba_heads x mamba_head_dim = mamba_expand x d_model, ONE "
                 "group of B and C (mamba_groups 1), mamba_chunk >= 1 and a "
                 "causal model")
        for kind, source in (("gmu", "mamba"), ("cross", "attention")):
            need(all(source in kinds[:i] for i, k in enumerate(kinds)
                     if k == kind),
                 f"a {kind} layer reads the nearest {source} layer BEFORE it")
        need(self.diff_attn or not self.attn_bias,
             "attn_bias is built for differential attention")
        if self.diff_attn:
            need(self.n_heads % 2 == 0 and self.kv_heads % 2 == 0
                 and (self.n_heads // 2) % (self.kv_heads // 2) == 0
                 and self.causal and not self.use_rope
                 and not (self.kv_lora_rank or self.qk_norm
                          or self.attn_output_gate or self.attn_float32),
                 "differential attention pairs adjacent heads (even n_heads "
                 "and kv_heads) of a causal model without rotary positions, "
                 "latents, q/k norms, an output gate or attn_float32")
        if "kda" in period:
            need(self.kda_heads and self.kda_head_dim and self.kda_gate_rank
                 and self.kda_conv >= 1 and self.causal,
                 "a kda layer needs kda_heads, kda_head_dim, kda_gate_rank, "
                 "kda_conv >= 1 and a causal model")
        if len(period) > 1:
            stack = self.n_layers - self.moe_dense_layers
            need(stack % len(period) == 0 and not self.mtp_layers,
                 "the stack after the leading dense layers is whole "
                 "periods of mixer_period, and a prediction module has "
                 "one kind of layer")
        need(not ((self.attn_output_gate or self.attn_float32)
                  and self.kv_lora_rank),
             "the output gate and attn_float32 belong to ordinary attention")
        need(self.moe_scoring in ("softmax", "sigmoid"),
             f"moe_scoring {self.moe_scoring!r}")
        need(self.mtp_layers in (0, 1), "one prediction module at most")
        need(self.moe_experts or not (self.moe_dense_layers
                                      or self.moe_shared_d_ff),
             "moe_dense_layers and moe_shared_d_ff belong to an MoE model")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def held_experts(self) -> int:
        return self.moe_experts if self.moe_held_experts is None \
            else self.moe_held_experts

    def mixer_kind(self, layer: int) -> str:
        return self.mixer_period[layer % len(self.mixer_period)]

    def segments(self, first: int = 0, count: Optional[int] = None) -> tuple:
        """Layers ``first`` .. ``first + count`` (default: all) as ((the
        kinds of one repetition, repetitions), ...), the stacks the
        parameters are built in and the programs scan: `layer_pattern`
        where the configuration states one, else whole periods of
        `mixer_period` where the count is whole periods, else every layer
        once."""
        count = self.n_layers - first if count is None else count
        if self.layer_pattern is not None:
            assert (first, count) == (0, self.n_layers)
            return self.layer_pattern
        kinds = tuple(self.mixer_kind(first + j) for j in range(count))
        period = len(self.mixer_period)
        return ((kinds[:period], count // period),) \
            if count % period == 0 else ((kinds, 1),)

    def tail_segment(self) -> int:
        """The index in `segments()` of the first of the pattern's TRAILING
        segments made of `LAST_ROW_KINDS` alone (`len(segments())` where
        the pattern ends in any other kind, as every pattern of one
        segment does). From that segment to the last layer a token's
        stream depends on nothing later layers computed at OTHER
        positions and no slot keeps anything, so a prompt pass whose
        reader takes the last position's logits carries that one
        position through them (`generate._prefill_hidden`)."""
        segments = self.segments()
        at = len(segments)
        while at and set(segments[at - 1][0]) <= LAST_ROW_KINDS:
            at -= 1
        return at

    @property
    def mamba_channels(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def mamba2_conv_width(self) -> int:
        """[x | B | C] side by side: what a mamba2 layer's convolution runs
        over and its slot's tail keeps."""
        return self.mamba_channels \
            + 2 * self.mamba_groups * self.mamba_d_state

    def layers_of_kind(self, kind: str) -> int:
        """How many of the `n_layers` layers have mixer ``kind``."""
        return sum(self.mixer_kind(i) == kind for i in range(self.n_layers))

    def _layer_params(self, moe: bool, kind: str = "attention") -> int:
        d, hd, H, KV = self.d_model, self.head_dim, self.n_heads, \
            self.kv_heads
        bias = self.attn_bias
        # differential attention: four lambda vectors, the pairs' norm
        diff = 4 * hd + 2 * hd if self.diff_attn else 0
        if kind == "mamba":
            C, N, R = self.mamba_channels, self.mamba_d_state, \
                self.mamba_dt_rank
            attn = (d * 2 * C + (self.mamba_d_conv + 1) * C   # in, conv
                    + C * (R + 2 * N) + R * C + C             # x, dt
                    + N * C + C + C * d)                      # A_log, D, out
        elif kind == "mamba2":
            C, H, wide = self.mamba_channels, self.mamba_heads, \
                self.mamba2_conv_width
            attn = (d * (C + wide + H) + (self.mamba_d_conv + 1) * wide
                    + 3 * H + C + C * d)    # dt_b, A_log, D; the norm; out
        elif kind == "gmu":
            attn = 2 * d * self.mamba_channels
        elif kind == "cross":
            attn = 2 * d * H * hd + bias * (H * hd + d) + diff
        elif kind == "kda":
            width, r = self.kda_heads * self.kda_head_dim, self.kda_gate_rank
            attn = (4 * d * width                    # q, k, v, out
                    + 3 * self.kda_conv * width      # their convolutions
                    + 2 * (d * r + r * width)        # decay, output gate
                    + d * self.kda_heads             # beta
                    + self.kda_heads + width         # A_log, dt_bias
                    + self.kda_head_dim)             # the heads' norm
        elif self.kv_lora_rank:
            q, kv, rope = self.q_lora_rank, self.kv_lora_rank, \
                self.rope_head_dim
            attn = ((d * q + q + q * H * hd if q else d * H * hd)
                    + d * (kv + rope) + kv
                    + kv * H * (hd - rope + self.v_head_dim)
                    + H * self.v_head_dim * d)
        else:
            attn = (d * H * hd + 2 * d * KV * hd + H * hd * d
                    + bias * ((H + 2 * KV) * hd + d) + diff
                    + (d * H * hd if self.attn_output_gate else 0)
                    + (H * hd + KV * hd if self.qk_norm else 0))
        if moe:
            ffn = (d * self.moe_experts                        # router
                   + (self.moe_experts if self.moe_select_bias else 0)
                   + self.held_experts * 3 * d * self.d_ff
                   + 3 * d * self.moe_shared_d_ff)
        else:
            ffn = 3 * d * (self.moe_dense_d_ff if self.moe_experts
                           else self.d_ff)
        # + norms (a LayerNorm's bias beside its gain)
        return attn + ffn + (4 if self.norm == "layer" else 2) * d

    @property
    def num_params(self) -> int:
        """Leaves of `init_params`, counted from shapes."""
        d, v = self.d_model, self.vocab_size
        dense = self.moe_dense_layers if self.moe_experts else self.n_layers
        layers = sum(self._layer_params(i >= dense, self.mixer_kind(i))
                     for i in range(self.n_layers))
        mtp = self.mtp_layers * (2 * d + 2 * d * d
                                 + self._layer_params(bool(self.moe_experts)))
        head = 0 if self.tie_embeddings else d * v
        return v * d + layers + mtp + (2 if self.norm == "layer" else 1) * d \
            + head


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
