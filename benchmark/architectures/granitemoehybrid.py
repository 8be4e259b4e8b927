"""The Granite 4.0-H decoder (ibm-granite/granite-4.0-h-micro, `model_type`
`granitemoehybrid`; Mamba-2 layers, arXiv:2405.21060, beside NoPE GQA
layers, dense: `num_local_experts` 0): its plain reference and the work its
forward pass requires. Nothing is cut: all 40 layers and all 100,352
vocabulary rows on one chip.

Written from the published description, not from `ray_tpu/models/`: the
keys of the model's `config.json` (the catalog row of the `model-configs`
guide) and, for what the row lacks (the config file's `assumed`), the public
`modeling_granitemoehybrid.py` / the Bamba Mamba-2 mixer and the paper.
RMS = RMSNorm with a gain, eps `rms_norm_eps`; e, r, a, l the four published
multipliers (`embedding_multiplier` 12, `residual_multiplier` 0.22,
`attention_multiplier` 1/64, `logits_scaling` 8):

    x = e . embed[tokens]
    every layer i:  x = x + r . mixer_i(RMS(x));   x = x + r . W2 (silu(g) . u),
                    [g, u] = W1 RMS'(x)   (the shared MLP is the whole
                    feed-forward: `shared_intermediate_size`, no experts)
    logits = (RMS_final(x) embed^T) / l      (tied head)

The mixer by `layer_types[i]`:

  attention(h): q [H, 64], k, v [KV, 64] = W h, no bias, NO positions
             (`position_embedding_type` "nope"); query head j reads key /
             value head j // (H / KV); causal softmax(q k^T . a) v, a the
             `attention_multiplier` itself (NOT 64 ** -0.5); W_o o
  mamba(h):  [z | xBC | dt] = W_in h, widths C | C + 2 G N | H_m (C =
             `mamba_expand` x hidden = `mamba_n_heads` x `mamba_d_head`, G
             = `mamba_n_groups` = 1, N = `mamba_d_state`), no bias;
             xBC = silu(conv(xBC) + b_conv), causal depthwise,
             `mamba_d_conv` taps, zeros before the first token;
             [x | B | C] = xBC: x [H_m, P] a head, ONE B and one C [N] a
             token, shared by every head;
             dt_h = softplus(dt_h + b_dt[h]);  A_h = -exp(A_log[h]), a
             SCALAR a head;
             S_t[h] = exp(dt_h A_h) S_(t-1)[h] + dt_h x_t[h] (x) B_t   [P, N]
             y_t[h] = S_t[h] C_t + D[h] x_t[h]
             y = RMS_C(y . silu(z); gain): the gate FIRST, then the norm,
             over all C channels (one group);  W_out y

What is written here otherwise than the program computes it: the whole row
at once under an explicit causal mask (no cache); the recurrence a token at
a time with the state [H_m, P, N] as the equations index it (the program
scans a prompt in chunks of `mamba_chunk_size` as matmuls and holds a
slot's state state-major [N, C]: the two must agree, that is the test); the
input projection as ONE matrix split by columns, as published; a Python
loop over the 40 layers.

It reads the program's parameter pytree because the weights ARE the
program's, made from the seed: `layers` is a tuple of stacks, one a
position of the pattern's period (ten: m m m m m a m m m m), each with a
leading axis of repeats (a period of one kind: the stack itself). Leaves:
every layer `attn_norm`, `mlp_norm`, `w_gate`, `w_up` [d, f], `w_down` [f,
d]; mamba `mamba2_in` [d, C + (C + 2 N)] and `mamba2_dt` [d, H_m] (the
published matrix [z | xBC | dt] in two: columns 0 .. 8447 and 8448 .. 8511),
`mamba2_conv` [taps, C + 2 N], `mamba2_conv_b`, `mamba2_dt_b`,
`mamba2_A_log`, `mamba2_D` [H_m], `mamba2_norm` [C], `mamba2_out` [C, d];
attention `wq` [d, H, 64], `wk`, `wv` [d, KV, 64], `wo` [H, 64, d]. RULES
come from ``conf`` (published and assumed keys), SIZES from ``fields``. JAX
is imported inside the functions that compute.
"""

from __future__ import annotations

import functools

KINDS = {"mamba": "mamba2", "attention": "attention"}


def period(conf: dict, n_layers: int) -> tuple:
    """The shortest run of `layer_types` that, repeated, gives the first
    ``n_layers`` of them."""
    types = list(conf["layer_types"])[:n_layers]
    if len(types) != n_layers:
        raise ValueError("fewer layer_types than layers")
    for p in range(1, n_layers + 1):
        if n_layers % p == 0 and types == types[:p] * (n_layers // p):
            return tuple(types[:p])
    raise AssertionError("unreachable: the whole list is a period")


def fields(conf: dict) -> dict:
    """Published (and assumed) keys -> TransformerConfig fields."""
    if not conf["tie_word_embeddings"] or conf["attention_bias"] \
            or conf["hidden_act"] != "silu" \
            or conf["normalization_function"] != "rmsnorm" \
            or conf["position_embedding_type"] != "nope" \
            or conf["num_local_experts"] or conf["mamba_proj_bias"] \
            or not conf["mamba_conv_bias"]:
        raise ValueError("a tied head, no attention bias, SiLU, RMSNorm, no "
                         "positions, no experts, a convolution bias and no "
                         "projection bias")
    heads, hidden = conf["num_attention_heads"], conf["hidden_size"]
    if conf["mamba_n_heads"] * conf["mamba_d_head"] \
            != conf["mamba_expand"] * hidden or conf["mamba_n_groups"] != 1:
        raise ValueError("mamba_n_heads x mamba_d_head is mamba_expand x "
                         "hidden_size, and B and C are one group")
    n_layers = conf["num_hidden_layers"]
    return {
        "vocab_size": conf["vocab_size"],
        "d_model": hidden,
        "n_layers": n_layers,
        "n_heads": heads,
        "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": hidden // heads,
        "d_ff": conf["shared_intermediate_size"],
        "rms_eps": conf["rms_norm_eps"],
        "tie_embeddings": True,
        "use_rope": False,
        "mixer_period": tuple(KINDS[t] for t in period(conf, n_layers)),
        "mamba_d_state": conf["mamba_d_state"],
        "mamba_d_conv": conf["mamba_d_conv"],
        "mamba_expand": conf["mamba_expand"],
        "mamba_heads": conf["mamba_n_heads"],
        "mamba_head_dim": conf["mamba_d_head"],
        "mamba_groups": conf["mamba_n_groups"],
        "mamba_chunk": conf["mamba_chunk_size"],
        "embed_scale": conf["embedding_multiplier"],
        "residual_scale": conf["residual_multiplier"],
        "attn_scale": conf["attention_multiplier"],
        "logit_divisor": conf["logits_scaling"],
        # how the program HOLDS the keys and values, not a rule: two heads
        # of 64 side by side fill the chip's 128 lanes (`assumed.attention`)
        "kv_head_pairs": True,
        # the checkpoint's dtype: the initialiser draws bf16 weights, which
        # a replica holds as they are (`assumed.checkpoint_dtype`)
        "param_dtype": "bfloat16",
    }


# ---- the plain reference ---------------------------------------------------


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _mm(eq, a, w):
    import jax
    import jax.numpy as jnp

    return jnp.einsum(eq, a, _f32(w), precision=jax.lax.Precision.HIGHEST)


def _rms(x, gain, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(gain)


def _mlp(x, lp, eps, r):
    import jax

    n = _rms(x, lp["mlp_norm"], eps)
    return x + r * _mm("tf,fd->td", jax.nn.silu(
        _mm("td,df->tf", n, lp["w_gate"])) * _mm("td,df->tf", n, lp["w_up"]),
        lp["w_down"])


def _mamba(x, lp, *, eps, r, heads, states):
    """x [T, d] float32 -> the layer's output: the recurrence a token at a
    time, the state [H, P, N]."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    T = x.shape[0]
    C = lp["mamba2_out"].shape[0]
    n = _rms(x, lp["attn_norm"], eps)
    # the published input projection, ONE matrix [z | xBC | dt], which the
    # program holds as two leaves
    zxd = _mm("td,dc->tc", n, jnp.concatenate(
        [lp["mamba2_in"], lp["mamba2_dt"]], axis=-1))
    z, xbc, dt = zxd[:, :C], zxd[:, C:-heads], zxd[:, -heads:]
    w = _f32(lp["mamba2_conv"])
    taps = w.shape[0]
    conv = jnp.zeros_like(xbc) + _f32(lp["mamba2_conv_b"])
    for i in range(taps):
        back = taps - 1 - i                       # tokens before t
        conv = conv + w[i] * jnp.concatenate(
            [jnp.zeros_like(xbc[:back]), xbc[:T - back]], axis=0)
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :C].reshape(T, heads, C // heads)
    Bt, Ct = xbc[:, C:C + states], xbc[:, C + states:]
    dt = jax.nn.softplus(dt + _f32(lp["mamba2_dt_b"]))
    A = -jnp.exp(_f32(lp["mamba2_A_log"]))        # [H], a scalar a head

    def token(S, t):                              # S [H, P, N]
        dt_t, x_t, b_t, c_t = t
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return S, jnp.einsum("hpn,n->hp", S, c_t, precision=hp)
    _, y = jax.lax.scan(
        token, jnp.zeros((heads, C // heads, states), jnp.float32),
        (dt, xs, Bt, Ct))
    y = (y + _f32(lp["mamba2_D"])[:, None] * xs).reshape(T, C)
    y = _rms(y * jax.nn.silu(z), lp["mamba2_norm"], eps)
    return _mlp(x + r * _mm("tc,cd->td", y, lp["mamba2_out"]), lp, eps, r)


def _attention(x, lp, *, eps, r, scale):
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    T = x.shape[0]
    n = _rms(x, lp["attn_norm"], eps)
    q = _mm("td,dhk->thk", n, lp["wq"])
    k = _mm("td,dhk->thk", n, lp["wk"])
    v = _mm("td,dhk->thk", n, lp["wv"])
    reads = jnp.arange(q.shape[1]) // (q.shape[1] // k.shape[1])
    s = jnp.einsum("qhk,shk->hqs", q, k[:, reads], precision=hp) * scale
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqs,shk->qhk", p, v[:, reads], precision=hp)
    out = x + r * _mm("tk,kd->td", o.reshape(T, -1),
                      lp["wo"].reshape(-1, lp["wo"].shape[-1]))
    return _mlp(out, lp, eps, r)


@functools.lru_cache(maxsize=None)
def _jitted(eps: float, r: float, scale: float, divisor: float, heads: int,
            states: int):
    import jax

    def head(x, gain, table):
        return _mm("td,vd->tv", _rms(x, gain, eps), table) / divisor
    return {
        "mamba": jax.jit(functools.partial(_mamba, eps=eps, r=r, heads=heads,
                                           states=states)),
        "attention": jax.jit(functools.partial(_attention, eps=eps, r=r,
                                               scale=scale)),
        "head": jax.jit(head),
    }


def _stack_layer(layers, p: int, i: int):
    """Layer ``i`` of the program's tree: a tuple of the pattern's segments
    (one segment: the segment itself), each a tuple of stacks, one a
    position of the period (a period of one kind: the stack), each stack
    with a leading axis of the segment's repeats."""
    from benchmark.harness.reference import layer

    if isinstance(layers, dict):
        return layer(layers, i)
    if isinstance(layers[0], dict):
        return layer(layers[i % p], i // p)
    for stacks in layers:
        held = p * len(next(iter(stacks[0].values())))
        if i < held:
            return layer(stacks[i % p], i // p)
        i -= held
    raise IndexError(i)


def reference_logits(params, tokens, fields: dict, conf: dict,
                     last: int = 0):
    """tokens [T] int -> float32 logits [T, V] (or the last ``last``
    positions). ``params``: device arrays, or numpy arrays on the host,
    whose leaves are then on the device one layer at a time
    (`harness/reference.py`)."""
    from benchmark.harness.reference import embedding_rows, on_device

    n_layers = fields["n_layers"]
    kinds = period(conf, n_layers)
    fn = _jitted(float(fields["rms_eps"]), float(conf["residual_multiplier"]),
                 float(conf["attention_multiplier"]),
                 float(conf["logits_scaling"]), int(fields["mamba_heads"]),
                 int(fields["mamba_d_state"]))
    x = embedding_rows(params["embed"], tokens) \
        * float(conf["embedding_multiplier"])
    for i in range(n_layers):
        lp = _stack_layer(params["layers"], len(kinds), i)
        x = on_device(lp, lambda lp: fn[kinds[i % len(kinds)]](x, lp))
    x = x[-last:] if last else x
    return on_device((params["final_norm"], params["embed"]),
                     lambda w: fn["head"](x, *w))


# ---- the work the forward pass requires -------------------------------------


def mamba2_step_cost(channels: int, states: int) -> dict:
    """What the recurrence REQUIRES of one token and layer: a state decayed
    (a product; the exponential is one a head), written (a product and a
    sum) and read out (a product and a sum): 5 x N x C FLOPs; in a decode
    step the float32 state is read and written once (it lives in HBM
    between a slot's tokens): 2 x 4 x N x C bytes. Over a prompt the state
    need never leave the chip's fast memory."""
    return {"flops": 5.0 * states * channels,
            "decode_bytes": 8.0 * states * channels}


def _sizes(f: dict) -> tuple:
    C = f["mamba_expand"] * f["d_model"]
    return C, C + 2 * f["mamba_groups"] * f["mamba_d_state"], f["mamba_heads"]


def _mixer_matmul_params(f: dict, kind: str) -> int:
    d, H, KV, hd = f["d_model"], f["n_heads"], f["n_kv_heads"], f["head_dim"]
    if kind == "mamba":
        C, wide, heads = _sizes(f)
        return d * (C + wide + heads) + f["mamba_d_conv"] * wide + C * d
    return d * (H + 2 * KV) * hd + H * hd * d


def _mixer_small_params(f: dict, kind: str) -> int:
    """The convolution's bias, dt's bias, A_log, D and the gated norm's
    gain: counted, never multiplied as matrices."""
    if kind != "mamba":
        return 0
    C, wide, heads = _sizes(f)
    return wide + 3 * heads + C


def layer_flops_per_token(f: dict, seq_len: int, kind: str) -> float:
    """One layer's forward FLOPs a token: 2 a weight that multiplies, plus
    the mixer's own: the recurrence's required work (`mamba2_step_cost`),
    or causal attention's score and value rows over (T + 1) / 2 keys."""
    flops = 2.0 * (_mixer_matmul_params(f, kind) + 3 * f["d_model"] * f["d_ff"])
    if kind == "mamba":
        return flops + mamba2_step_cost(_sizes(f)[0],
                                        f["mamba_d_state"])["flops"]
    return flops + f["n_heads"] * 4.0 * f["head_dim"] * (seq_len + 1) / 2


def forward_flops_per_token(fields: dict, conf: dict,
                            seq_len: int) -> float:
    kinds = period(conf, fields["n_layers"])
    return sum(layer_flops_per_token(fields, seq_len, kinds[i % len(kinds)])
               for i in range(fields["n_layers"])) \
        + 2.0 * fields["d_model"] * fields["vocab_size"]


def num_params(fields: dict, conf: dict) -> int:
    """What the program's initialiser makes: the tied table once; a layer's
    mixer with its small leaves, two RMSNorm gains and the SwiGLU; the
    final norm's gain."""
    f = fields
    d = f["d_model"]
    kinds = period(conf, f["n_layers"])
    layers = sum(_mixer_matmul_params(f, kinds[i % len(kinds)])
                 + _mixer_small_params(f, kinds[i % len(kinds)])
                 + 2 * d + 3 * d * f["d_ff"] for i in range(f["n_layers"]))
    return f["vocab_size"] * d + layers + d
