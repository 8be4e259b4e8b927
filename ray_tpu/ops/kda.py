"""Gated delta-rule linear attention with a per-channel decay (KDA, Kimi
Linear), chunked, forward and backward.

Per head, with q_t, k_t in R^dk, v_t in R^dv, log-decays g_t <= 0 in R^dk
(a_t = exp(g_t)), a write strength b_t in (0, 1) and a state S in
R^{dk x dv} that starts at zero:

    S'  = Diag(a_t) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T        o_t = S_t^T q_t

`kda_recurrent` is that recurrence a token at a time (a `lax.scan` over
T; the tests' ground truth, and nothing the trainer runs). `kda_scan` is
the chunked form (chunks of C = 64). With G_r the decays cumulated from
the chunk's start through row r, and u_r = b_r (v_r - S'_r^T k_r) the
row's write, the rows of one chunk obey

    (I + Diag(b) tril(A, -1)) U = Diag(b) (V - (K . e^G) S_0)
    A[r, i] = sum_c k_r[c] k_i[c] e^(G_r[c] - G_i[c])             (i < r)

so the triangular system, solved once a chunk (`_unit_lower_inverse`: by
products, no row-by-row substitution), gives U = W_v - W_k S_0 with W_v
and W_k free of the state, and the state enters by three products:

    O   = (Q . e^G) S_0 + tril(A_q) U      A_q as A with q_r for k_r, i <= r
    S_C = Diag(e^G_C) S_0 + (K . e^(G_C - G))^T U

No exponent of a positive sum is ever taken: every decay above is a
difference G_r - G_i with i <= r. A and A_q are built from sub-blocks of
16 rows: a block below the diagonal is one product of rows decayed FROM
the sub-block's start (e^(G_r - G_start) <= 1) with columns decayed TO it
(e^(G_start - G_i) <= 1); a block on the diagonal takes the differences
themselves, [16, 16, dk] numbers. So a channel that decays by e^-20 a
token stays finite, where K . e^-G would not.

The chunks are walked by a `lax.scan` that carries S; each step takes
`group` chunks (their state-free part in one batch, then the three
products chunk after chunk) and is checkpointed, so the backward pass is
the same walk in reverse: it keeps one state a step (T / (64 x group) of
them, for the layer being differentiated alone) and never a [16, 16, dk]
block. Gates and cumulated decays are float32; q, k, v reach the MXU in
the dtype they arrive in with float32 accumulation; the triangular system
is inverted in float32 and applied in that dtype.

Everything is under `jax.named_scope("kda.scan")`: a profile groups the
scan's device time by it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 64
SUB = 16          # rows of a sub-block of A; CHUNK is a multiple


def kda_recurrent(q, k, v, g, beta):
    """The recurrence a token at a time. q, k, g: [B, T, H, dk]; v: [B, T,
    H, dv]; beta: [B, T, H] -> o [B, T, H, dv] float32. Float32 at the
    highest matmul precision throughout."""
    hp = jax.lax.Precision.HIGHEST
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    B, _, H, dk = q.shape

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                               precision=hp))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=hp)

    S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def _decayed_products(x, k, G, dtype):
    """A_x[r, i] = sum_c x_r[c] k_i[c] e^(G_r[c] - G_i[c]) for i <= r (0
    above the diagonal), for every x in ``x``. x, k: [..., C, dk] float32;
    G [..., C, dk] float32, the decays cumulated through each row.
    -> [..., C, C] float32 each."""
    *lead, C, dk = k.shape
    n = C // SUB
    blocks = lambda a: a.reshape(*lead, n, SUB, dk)   # noqa: E731
    kb, Gb = blocks(k), blocks(G)
    below = jnp.arange(n)[:, None] > jnp.arange(n)[None, :]   # J < I
    lower = jnp.arange(SUB)[:, None] >= jnp.arange(SUB)[None, :]
    # the decays cumulated BEFORE each sub-block's first row
    start = jnp.concatenate(
        [jnp.zeros_like(Gb[..., :1, :1, :]), Gb[..., :-1, -1:, :]], axis=-3)
    # columns decayed to the start of a LATER sub-block I: e^(start_I -
    # G_i) for i in J < I (the other blocks are masked, before the
    # exponent and after the product)
    to_start = jnp.exp(jnp.where(
        below[:, :, None, None],
        start[..., :, None, :, :] - Gb[..., None, :, :, :], 0.0))
    cols = (kb[..., None, :, :, :] * to_start).astype(dtype)  # [I, J, i, c]
    from_start = jnp.exp(Gb - start)                          # [I, r, c]
    # within a sub-block: the differences themselves
    within = jnp.exp(jnp.where(
        lower[:, :, None],
        Gb[..., :, None, :] - Gb[..., None, :, :], 0.0))      # [I, r, i, c]
    kin = kb[..., None, :, :] * within
    out = []
    for xs in x:
        xb = blocks(xs)
        off = _mm("...Irc,...IJic->...IrJi",
                  (xb * from_start).astype(dtype), cols)
        off = jnp.where(below[:, None, :, None], off, 0.0)
        diag = jnp.sum(xb[..., :, None, :] * kin, axis=-1)    # [I, r, i]
        diag = jnp.where(lower, diag, 0.0)
        eye = jnp.eye(n, dtype=diag.dtype)
        full = off + diag[..., :, :, None, :] * eye[:, None, :, None]
        out.append(full.reshape(*lead, C, C))
    return out


def _unit_lower_inverse(L):
    """(I + L)^-1 for strictly lower triangular L [..., C, C] float32, by
    products alone: within a sub-block of 16 rows L^16 = 0, so the inverse
    is (I - L)(I + L^2)(I + L^4)(I + L^8); two sub-blocks' inverses A, B
    with the block C below A join to [[A, 0], [-B C A, B]], 16 -> 32 ->
    64. Float32 at the highest matmul precision (the products are small)."""
    hp = jax.lax.Precision.HIGHEST
    mm = functools.partial(jnp.matmul, precision=hp)
    *lead, C, _ = L.shape

    def diagonal(x, offset=0):
        """x [..., n, s, n, s] -> the blocks (j + offset, j), stacked."""
        n = x.shape[-2]
        return jnp.stack([x[..., j + offset, :, j, :]
                          for j in range(n - offset)], axis=-3)
    D = diagonal(L.reshape(*lead, C // SUB, SUB, C // SUB, SUB))
    inv = jnp.eye(SUB, dtype=L.dtype) - D
    power = mm(D, D)
    for _ in range(3):              # (I + L^2)(I + L^4)(I + L^8)
        inv = inv + mm(inv, power)
        power = mm(power, power)
    size = SUB
    while size < C:
        half = C // (2 * size)
        under = diagonal(L.reshape(*lead, 2 * half, size, 2 * half, size),
                         1)[..., 0::2, :, :]
        pair = inv.reshape(*lead, half, 2, size, size)
        A, B = pair[..., 0, :, :], pair[..., 1, :, :]
        off = -mm(mm(B, under), A)
        inv = jnp.concatenate(
            [jnp.concatenate([A, jnp.zeros_like(A)], axis=-1),
             jnp.concatenate([off, B], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def _group_step(S, x, *, dtype):
    """``group`` chunks: S [B, H, dk, dv] float32 and the chunks' q, k, v,
    g [B, H, m, C, d], beta [B, H, m, C] -> (S after them, o [B, H, m, C,
    dv])."""
    q, k, v, g, beta = x
    m, C = q.shape[2:4]
    q32, k32 = q.astype(jnp.float32), k.astype(jnp.float32)
    G = jnp.cumsum(g, axis=-2)                       # through each row
    a_kk, a_qk = _decayed_products((k32, q32), k32, G, dtype)
    strict = jnp.arange(C)[:, None] > jnp.arange(C)[None, :]
    solved = _unit_lower_inverse(jnp.where(
        strict, beta[..., None] * a_kk, 0.0)).astype(dtype)
    decay = jnp.exp(G)
    rhs = beta[..., None] * jnp.concatenate(
        [v.astype(jnp.float32), k32 * decay], axis=-1)
    w = _mm("...ri,...iv->...rv", solved, rhs.astype(dtype))
    w_v, w_k = w[..., :v.shape[-1]], w[..., v.shape[-1]:].astype(dtype)
    q_in = (q32 * decay).astype(dtype)               # reads the old state
    k_out = (k32 * jnp.exp(G[..., -1:, :] - G)).astype(dtype)
    a_qk = a_qk.astype(dtype)
    last = decay[..., -1, :]                         # [B, H, m, dk]
    out = []
    for c in range(m):                               # chunk after chunk
        Sd = S.astype(dtype)
        u = w_v[:, :, c] - _mm("bhrk,bhkv->bhrv", w_k[:, :, c], Sd)
        ud = u.astype(dtype)
        out.append(_mm("bhrk,bhkv->bhrv", q_in[:, :, c], Sd)
                   + _mm("bhri,bhiv->bhrv", a_qk[:, :, c], ud))
        S = last[:, :, c, :, None] * S \
            + _mm("bhik,bhiv->bhkv", k_out[:, :, c], ud)
    return S, jnp.stack(out, axis=2)


def kda_scan(q, k, v, g, beta, *, group: int = 2):
    """The chunked form. q, k: [B, T, H, dk] and v: [B, T, H, dv] in the
    compute dtype; g: [B, T, H, dk] float32 log-decays (<= 0); beta: [B,
    T, H] float32 -> o [B, T, H, dv] in v's dtype. Any T: rows past it are
    padded with tokens that write nothing and decay nothing. ``group``
    chunks a step of the walk (the state-free part of a step is one
    batch; the walk keeps T / (64 x group) states for its backward)."""
    with jax.named_scope("kda.scan"):
        B, T, H, dk = q.shape
        dtype = v.dtype
        span = CHUNK * max(1, min(group, -(-T // CHUNK)))
        pad = -T % span
        n = (T + pad) // span

        def steps(x):   # [B, T, H, ...] -> [n, B, H, m, C, ...]
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            x = x.reshape(B, n, span // CHUNK, CHUNK, *x.shape[2:])
            return jnp.moveaxis(x, (1, 4), (0, 2))

        xs = tuple(steps(x) for x in (q, k, v, g.astype(jnp.float32),
                                      beta.astype(jnp.float32)))
        body = jax.checkpoint(functools.partial(_group_step, dtype=dtype))
        S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
        _, o = jax.lax.scan(body, S0, xs)            # [n, B, H, m, C, dv]
        o = jnp.moveaxis(o, (0, 2), (1, 4)).reshape(B, T + pad, H, -1)
        return o[:, :T].astype(dtype)
