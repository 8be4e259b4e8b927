"""The Phi-4-mini-flash decoder (microsoft/Phi-4-mini-flash-reasoning,
`model_type` `phi4flash`; the decoder-hybrid-decoder of arXiv:2507.06607):
its plain reference and the work its forward pass requires. Nothing is cut:
all 32 layers and all 200,064 vocabulary rows on one chip.

Written from the published description, not from `ray_tpu/models/`: the
keys of the model's `config.json` (the catalog row of the `model-configs`
guide) and, for what the row lacks (the config file's `assumed`), the
model's public `modeling_phi4flash.py` and the paper. Every layer i
(0-based), LN = LayerNorm with weight and bias, eps `layer_norm_eps`:

    x = x + mixer_i(LN(x));   x = x + W2 (silu(g) . u),  [g, u] = W1 LN'(x)

and after the last layer a final LayerNorm and the TIED head (the embedding
table transposed). The mixer by index, b = `decoder_boundary` (16), every
`mb_per_layer`-th layer (2) a state-space or memory layer:

    i < b        even: mamba          odd: window attention (`sliding_window`)
    i = b        mamba, whose scan output is the MEMORY m
    i = b + 1    full attention, whose K and V are THE stored cache
    i > b + 1    even: gmu            odd: cross attention

  mamba(h):  [a, z] = W_in h;  a = silu(conv(a) + b_conv), causal depthwise,
             `mamba_d_conv` taps, zeros before the first token;
             [r, B, C] = W_x a  (`mamba_dt_rank`, N, N);
             dt = softplus(W_dt r + b_dt);  A = -exp(A_log);
             s_t[c, n] = exp(dt_t[c] A[c, n]) s_(t-1)[c, n] + dt_t[c] B_t[n] a_t[c]
             y_t[c] = sum_n C_t[n] s_t[c, n] + D[c] a_t[c];   m_t = y_t
             out = W_out (y . silu(z))
  gmu(h):    W_out (m_t . silu(W_in h)), m_t the same token's memory
  differential attention (window, full and cross alike): q [H, 64], k, v
             [KV, 64] from W h + b (cross: q alone; k, v are layer b + 1's).
             Adjacent heads pair: q1, q2 = heads 2j, 2j + 1; k1, k2 and v1,
             v2 likewise; query pair j reads key/value pair j // (H / KV).
             o1 = softmax(q1 k1^T / sqrt(64)) [v1 | v2], o2 likewise from
             q2, k2; causal, a window layer over the `sliding_window`
             positions that END at the token itself.
             lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(i),
             lam0(i) = 0.8 - 0.6 exp(-0.3 i);
             o = RMSNorm_128(o1 - lam o2; gain) (1 - lam0(i));  W_o o + b_o
  No positional encoding anywhere.

What is written here otherwise than the program computes it: the whole row
at once under explicit masks (no cache, no ring, no stored K/V: a cross
layer is handed layer b + 1's k and v of this same pass); the scan a token
at a time with the state [C, N] as the equations index it; heads paired by
strided slices of [T, H, 64], never stored as pairs; a Python loop over the
32 layers.

It reads the program's parameter pytree because the weights ARE the
program's, made from the seed: `layers` is a tuple of the pattern's three
segments, each a tuple of stacks, one a position of the segment's period,
with a leading axis of repeats. Leaves: every layer `attn_norm`, `mlp_norm`
(+ `_b`), `w_gate`, `w_up` [d, f], `w_down` [f, d]; mamba `mamba_in` [2, d,
C] (a's matrix, then z's), `mamba_conv` [taps, C], `mamba_conv_b`, `mamba_x` [C, R + 2N]
([r | B | C]), `mamba_dt` [R, C], `mamba_dt_b`, `mamba_A_log` [N, C]
(state-major), `mamba_D`, `mamba_out` [C, d]; gmu `gmu_in` [d, C],
`gmu_out` [C, d]; attention `wq` [d, H, 64], `wk`, `wv` [d, KV, 64], `bq`,
`bk`, `bv`, `wo` [H, 64, d], `bo`, `diff_lambda` [4, 64] (lq1, lk1, lq2,
lk2), `diff_norm` [128]; a cross layer has no `wk`, `wv`, `bk`, `bv`.
RULES come from ``conf`` (published and assumed keys), SIZES from
``fields``. JAX is imported inside the functions that compute.
"""

from __future__ import annotations

import functools
import math


def layer_kinds(conf: dict, n_layers: int) -> list:
    """'mamba', 'window', 'attention', 'gmu' or 'cross' for layers 0 ..
    n_layers - 1."""
    b, every = conf["decoder_boundary"], conf["mb_per_layer"]
    kinds = []
    for i in range(n_layers):
        state_space = i % every == 0
        if i <= b:
            kinds.append("mamba" if state_space or i == b else "window")
        elif i == b + 1:
            kinds.append("attention")
        else:
            kinds.append("gmu" if state_space else "cross")
    return kinds


def segments(conf: dict, n_layers: int) -> tuple:
    """The kinds as ((one period, repeats), ...): a new segment wherever
    the next period differs from the last."""
    kinds, every = layer_kinds(conf, n_layers), conf["mb_per_layer"]
    if n_layers % every:
        raise ValueError("num_hidden_layers is not whole periods of "
                         "mb_per_layer")
    out = []
    for at in range(0, n_layers, every):
        period = tuple(kinds[at:at + every])
        if out and out[-1][0] == period:
            out[-1][1] += 1
        else:
            out.append([period, 1])
    return tuple((period, reps) for period, reps in out)


def fields(conf: dict) -> dict:
    """Published (and assumed) keys -> TransformerConfig fields."""
    if not conf["tie_word_embeddings"] or conf["mlp_bias"] \
            or conf["lm_head_bias"] or conf["hidden_act"] != "silu":
        raise ValueError("a tied head, no MLP or head bias, SiLU")
    heads = conf["num_attention_heads"]
    if conf["mamba_d_inner"] != conf["mamba_expand"] * conf["hidden_size"] \
            or conf["mamba_dt_rank"] != math.ceil(conf["hidden_size"] / 16):
        raise ValueError("mamba_d_inner is expand x hidden_size and "
                         "mamba_dt_rank ceil(hidden_size / 16)")
    return {
        "vocab_size": conf["vocab_size"],
        "d_model": conf["hidden_size"],
        "n_layers": conf["num_hidden_layers"],
        "n_heads": heads,
        "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["hidden_size"] // heads,
        "d_ff": conf["intermediate_size"],
        "rms_eps": conf["layer_norm_eps"],
        "norm": "layer",
        "tie_embeddings": True,
        "use_rope": False,
        "attn_bias": True,
        "diff_attn": True,
        "sliding_window": conf["sliding_window"],
        "layer_pattern": segments(conf, conf["num_hidden_layers"]),
        "mamba_d_state": conf["mamba_d_state"],
        "mamba_d_conv": conf["mamba_d_conv"],
        "mamba_expand": conf["mamba_expand"],
        "mamba_dt_rank": conf["mamba_dt_rank"],
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


def _layer_norm(x, gain, bias, eps):
    import jax
    import jax.numpy as jnp

    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) \
        * _f32(gain) + _f32(bias)


def _mlp(x, lp, eps):
    import jax

    n = _layer_norm(x, lp["mlp_norm"], lp["mlp_norm_b"], eps)
    return x + _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", n, lp["w_gate"]))
                   * _mm("td,df->tf", n, lp["w_up"]), lp["w_down"])


def _mamba(x, lp, *, eps, rank, states):
    """x [T, d] float32 -> (the layer's output, the memory y [T, C])."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    T = x.shape[0]
    n = _layer_norm(x, lp["attn_norm"], lp["attn_norm_b"], eps)
    a = _mm("td,dc->tc", n, lp["mamba_in"][0])
    z = _mm("td,dc->tc", n, lp["mamba_in"][1])
    w = _f32(lp["mamba_conv"])
    taps = w.shape[0]
    conv = jnp.zeros_like(a) + _f32(lp["mamba_conv_b"])
    for i in range(taps):
        back = taps - 1 - i                       # tokens before t
        conv = conv + w[i] * jnp.concatenate(
            [jnp.zeros_like(a[:back]), a[:T - back]], axis=0)
    a = jax.nn.silu(conv)
    rbc = _mm("tc,cr->tr", a, lp["mamba_x"])
    r, Bt, Ct = rbc[:, :rank], rbc[:, rank:rank + states], \
        rbc[:, rank + states:]
    dt = jax.nn.softplus(_mm("tr,rc->tc", r, lp["mamba_dt"])
                         + _f32(lp["mamba_dt_b"]))
    A = -jnp.exp(_f32(lp["mamba_A_log"])).T       # [C, N], as the equations

    def token(s, t):                              # s [C, N]
        dt_t, a_t, b_t, c_t = t
        s = jnp.exp(dt_t[:, None] * A) * s \
            + (dt_t * a_t)[:, None] * b_t[None, :]
        return s, jnp.einsum("cn,n->c", s, c_t, precision=hp)
    _, y = jax.lax.scan(token, jnp.zeros_like(A), (dt, a, Bt, Ct))
    y = y + _f32(lp["mamba_D"]) * a
    out = x + _mm("tc,cd->td", y * jax.nn.silu(z), lp["mamba_out"])
    return _mlp(out, lp, eps), y


def _gmu(x, memory, lp, *, eps):
    import jax

    n = _layer_norm(x, lp["attn_norm"], lp["attn_norm_b"], eps)
    out = x + _mm("tc,cd->td", memory * jax.nn.silu(
        _mm("td,dc->tc", n, lp["gmu_in"])), lp["gmu_out"])
    return _mlp(out, lp, eps)


def _attention(x, lp, layer, kv, *, eps, window):
    """x [T, d] -> (the layer's output, its k and v [T, KV, 64]): a window
    layer where ``window`` > 0; a cross layer where ``kv`` (another layer's
    k, v) is given."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    T = x.shape[0]
    n = _layer_norm(x, lp["attn_norm"], lp["attn_norm_b"], eps)
    q = _mm("td,dhk->thk", n, lp["wq"]) + _f32(lp["bq"])
    if kv is None:
        k = _mm("td,dhk->thk", n, lp["wk"]) + _f32(lp["bk"])
        v = _mm("td,dhk->thk", n, lp["wv"]) + _f32(lp["bv"])
    else:
        k, v = kv
    width = q.shape[-1]
    pairs, kv_pairs = q.shape[1] // 2, k.shape[1] // 2
    values = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)  # [v1 | v2]
    reads = jnp.arange(pairs) // (pairs // kv_pairs)   # pair j's kv pair
    at, key = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = key <= at
    if window:
        seen = seen & (at - key < window)

    def half(e):     # softmax(q_e k_e^T / sqrt(width)) [v1 | v2]
        s = jnp.einsum("qjc,sjc->jqs", q[:, e::2], k[:, e::2][:, reads],
                       precision=hp) * width ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("jqs,sjv->qjv", p, values[:, reads], precision=hp)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * layer)
    lq1, lk1, lq2, lk2 = _f32(lp["diff_lambda"])
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    o = half(0) - lam * half(1)                         # [T, pairs, 128]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * _f32(lp["diff_norm"]) * (1.0 - lam0)
    out = x + _mm("tk,kd->td", o.reshape(T, -1),
                  lp["wo"].reshape(-1, lp["wo"].shape[-1])) + _f32(lp["bo"])
    return _mlp(out, lp, eps), (k, v)


@functools.lru_cache(maxsize=None)
def _jitted(eps: float, window: int, rank: int, states: int):
    import jax

    def head(x, gain, bias, table):
        return _mm("td,vd->tv", _layer_norm(x, gain, bias, eps), table)
    return {
        "mamba": jax.jit(functools.partial(_mamba, eps=eps, rank=rank,
                                           states=states)),
        "gmu": jax.jit(functools.partial(_gmu, eps=eps)),
        "window": jax.jit(functools.partial(_attention, eps=eps,
                                            window=window)),
        "attention": jax.jit(functools.partial(_attention, eps=eps,
                                               window=0)),
        "head": jax.jit(head),
    }


def _stack_layer(layers, conf: dict, n_layers: int, i: int):
    """Layer ``i`` of the program's tree: repeat (i - first) // period of
    the stack at position (i - first) mod period of its segment."""
    from benchmark.harness.reference import layer

    first = 0
    for (period, reps), stacks in zip(segments(conf, n_layers), layers):
        if i < first + len(period) * reps:
            j = i - first
            return layer(stacks[j % len(period)], j // len(period))
        first += len(period) * reps
    raise IndexError(i)


def reference_logits(params, tokens, fields: dict, conf: dict,
                     last: int = 0):
    """tokens [T] int -> float32 logits [T, V] (or the last ``last``
    positions). ``params``: device arrays, or numpy arrays on the host,
    whose leaves are then on the device one layer at a time
    (`harness/reference.py`)."""
    from benchmark.harness.reference import embedding_rows, on_device

    n_layers = fields["n_layers"]
    fn = _jitted(float(fields["rms_eps"]), int(fields["sliding_window"]),
                 int(fields["mamba_dt_rank"]), int(fields["mamba_d_state"]))
    x = embedding_rows(params["embed"], tokens)
    memory = stored = None
    for i, kind in enumerate(layer_kinds(conf, n_layers)):
        lp = _stack_layer(params["layers"], conf, n_layers, i)
        if kind == "mamba":
            x, memory = on_device(lp, lambda lp: fn["mamba"](x, lp))
        elif kind == "gmu":
            x = on_device(lp, lambda lp: fn["gmu"](x, memory, lp))
        elif kind == "cross":
            x, _ = on_device(lp, lambda lp: fn["attention"](
                x, lp, float(i), stored))
        else:
            x, kv = on_device(lp, lambda lp: fn[kind](x, lp, float(i), None))
            if kind == "attention":
                stored = kv
    x = x[-last:] if last else x
    return on_device(
        (params["final_norm"], params["final_norm_b"], params["embed"]),
        lambda w: fn["head"](x, *w))


# ---- the work the forward pass requires -------------------------------------


def mamba_step_cost(channels: int, states: int) -> dict:
    """What the selective scan REQUIRES of one token and layer: a state
    decayed (an exponential and a product), written (two products and a
    sum) and read out (a product and a sum): 7 x N x C FLOPs; in a decode
    step the float32 state is read and written once (it lives in HBM
    between a slot's tokens): 2 x 4 x N x C bytes. Over a prompt the state
    need never leave the chip's fast memory."""
    return {"flops": 7.0 * states * channels,
            "decode_bytes": 8.0 * states * channels}


def _mixer_matmul_params(f: dict, kind: str) -> int:
    d, H, KV, hd = f["d_model"], f["n_heads"], f["n_kv_heads"], f["head_dim"]
    C = f["mamba_expand"] * d
    if kind == "mamba":
        N, R = f["mamba_d_state"], f["mamba_dt_rank"]
        return d * 2 * C + f["mamba_d_conv"] * C + C * (R + 2 * N) + R * C \
            + C * d
    if kind == "gmu":
        return 2 * d * C
    if kind == "cross":
        return 2 * d * H * hd
    return d * (H + 2 * KV) * hd + H * hd * d


def _mixer_small_params(f: dict, kind: str) -> int:
    """Biases, gains and the scan's own leaves: counted, never multiplied
    as matrices."""
    d, H, KV, hd = f["d_model"], f["n_heads"], f["n_kv_heads"], f["head_dim"]
    C = f["mamba_expand"] * d
    if kind == "mamba":     # conv bias, dt bias, A_log, D
        return 3 * C + f["mamba_d_state"] * C
    if kind == "gmu":
        return 0
    heads = H if kind == "cross" else H + 2 * KV
    return heads * hd + d + 4 * hd + 2 * hd


def keys_per_query(seq_len: int, window: int = 0) -> float:
    """Mean visible keys a query over a causal row of ``seq_len``: (T + 1)
    / 2, or under a window min(position + 1, window) averaged."""
    if not window or window >= seq_len:
        return (seq_len + 1) / 2
    return (window * (window + 1) / 2 + (seq_len - window) * window) / seq_len


def layer_flops_per_token(f: dict, conf: dict, seq_len: int,
                          kind: str) -> float:
    """One layer's forward FLOPs a token: 2 a weight that multiplies, plus
    the mixer's own: the scan's required work (`mamba_step_cost`), or
    differential attention: a pair's two score rows (64 wide) and its two
    value rows (128 wide: each softmax weighs [v1 | v2]) per visible key."""
    d = f["d_model"]
    flops = 2.0 * (_mixer_matmul_params(f, kind) + 3 * d * f["d_ff"])
    if kind == "mamba":
        flops += mamba_step_cost(f["mamba_expand"] * d,
                                 f["mamba_d_state"])["flops"]
    elif kind != "gmu":
        keys = keys_per_query(
            seq_len, conf["sliding_window"] if kind == "window" else 0)
        flops += f["n_heads"] * 2.0 * (f["head_dim"] + 2 * f["head_dim"]) \
            * keys
    return flops


def forward_flops_per_token(fields: dict, conf: dict,
                            seq_len: int) -> float:
    return sum(layer_flops_per_token(fields, conf, seq_len, kind)
               for kind in layer_kinds(conf, fields["n_layers"])) \
        + 2.0 * fields["d_model"] * fields["vocab_size"]


def num_params(fields: dict, conf: dict) -> int:
    """What the program's initialiser makes: the tied table once; a layer's
    mixer with its biases, gains and scan leaves, two LayerNorms (gain and
    bias) and the SwiGLU; the final LayerNorm."""
    f = fields
    d = f["d_model"]
    layers = sum(_mixer_matmul_params(f, kind) + _mixer_small_params(f, kind)
                 + 4 * d + 3 * d * f["d_ff"]
                 for kind in layer_kinds(conf, f["n_layers"]))
    return f["vocab_size"] * d + layers + 2 * d
