"""Gated delta-rule linear attention with a per-channel decay (KDA, Kimi
Linear), chunked, as two Mosaic (Pallas TPU) kernels under one
`jax.custom_vjp`.

Per head, with q_t, k_t in R^dk, v_t in R^dv, log-decays g_t <= 0 in R^dk
(a_t = exp(g_t)), a write strength b_t in (0, 1), or in (0, 2) where the model
allows a negative eigenvalue of I - b k k^T, and a state S in
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

so the unit-lower system, inverted once a chunk by products alone
(`_inverses`), gives U = W_v - W_k S_0 with W_v and W_k free of the
state, and the state enters by three products:

    O   = (Q . e^G) S_0 + tril(A_q) U      A_q as A with q_r for k_r, i <= r
    S_C = Diag(e^G_C) S_0 + (K . e^(G_C - G))^T U

No exponent of a positive sum is ever taken. A pair i < r of a chunk
belongs to one LEVEL l: split the chunk in blocks of 2s rows (s = 2^l);
at exactly one l the two fall in the two halves of one block. There
e^(G_r - G_i) = e^(G_r - G_m) e^(G_m - G_i) with m the upper half's last
row, both factors <= 1, so the level's pairs are one [2C, dk] x [dk, C]
product of rows scaled by e^-|G - G_m| (masked to the level; the six
levels cover every pair, the diagonal of A_q is q_r . k_r). A channel
that decays by e^-80 a token stays finite, where K . e^-G would not.

The kernels (`kda_scan_fwd`, `kda_scan_bwd`; grid (B, H / P, T / R), the
last axis in order) take `group` chunks of P heads a grid step (P = 2
where H is even), R = 64 x group rows: q, k, v, g as blocks (1, R, P x
d) of [B, T, H x d] (the heads are the column block: no transpose), beta
as (1, R, H). A step's operands, decays, level products, the C x C
systems, their inverses and the float32 states [P, dk, dv] (scratch,
carried across the steps of the heads) stay in VMEM; o is written once.
The chunks' state-free stages are emitted stage by stage ACROSS the
step's chunks and heads (their chains of dependent small products
overlap on the MXUs; chunk after chunk they ran twice as long), then the
three state products chunk after chunk, the heads' chains side by side.
What Mosaic lacks is not used: cumulated decays are a product with a 0 /
1 table (the float32 operand in three bfloat16 pieces, exact), masks are
`iota` comparisons, a column becomes a row on a diagonal.

Under differentiation the forward kernel also writes the state at the
start of every grid step (T / R states a head, for the layer being
differentiated alone); what it leaves for the backward is its inputs and
those. The backward kernel walks the steps in reverse with dS in VMEM:
from a step's start state it takes `jax.vjp` of the same pure step
function on the loaded tiles (forward, then back) and writes dq, dk, dv,
dg, dbeta once. Nothing of the walk is an XLA loop and nothing is
checkpointed.

Precision: float32 gates, cumulated decays, state, systems and inverses
(float32 products at the MXU's highest precision), and so are the level
products of the pairs within NEAR = 16 rows (levels 0-3: the least
decayed pairs, which weigh most in the system; six passes a product
where bfloat16 takes one, 7 ms a forward pass and 16 a backward at [2,
16384, 32, 128] on a v5e); q, k, v and the other products' operands in
the dtype they arrive in, float32 accumulation. On the CPU backend the
kernels run through the Pallas interpreter.

Everything is under `jax.named_scope("kda.scan")` and both kernels'
names begin with `kda`: a profile groups the scan's device time by the
scope, and tells these kernels from the attention kernels by the name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
LEVELS = 6        # log2(CHUNK): the halvings from the chunk to one row
NEAR = 16         # pairs within blocks of so many rows: float32 products


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


def _use_interpret() -> bool:
    return jax.default_backend() == "cpu"


_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims=_NN):
    """Float32 accumulation; float32 operands at the highest precision
    (the MXU's passes for float32), others as they arrive."""
    hp = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, dims, precision=hp,
                               preferred_element_type=jnp.float32)


def _tables():
    """The chunk's two constant tables. ``sums`` [3C, C] bfloat16 of 0 /
    1, three blocks of C rows: row r of the first cumulates g through row
    r (G); of the next two the exponent of level 0 and 1 (blocks of 2 and
    4 rows: from the lower half's first row through r, or from r's
    successor through the upper half's last row; wider blocks take theirs
    from G). ``level`` [C, C] int32: for a pair i < r the l at which the
    two fall in the two halves of one block (the highest bit in which r
    and i differ), else -1."""
    r = np.arange(CHUNK)[:, None]
    j = np.arange(CHUNK)[None, :]
    blocks = [j <= r]
    for s in (1, 2):
        last = r // (2 * s) * (2 * s) + s - 1       # of the upper half
        blocks.append(np.where(r % (2 * s) >= s, (j > last) & (j <= r),
                               (j > r) & (j <= last)))
    level = np.where(j < r, np.floor(np.log2(np.maximum(r ^ j, 1))), -1)
    return (jnp.asarray(np.concatenate(blocks, 0), jnp.bfloat16),
            level.astype(np.int32))


def _pieces(x):
    """x float32 as three bfloat16 whose sum is x to 2^-24."""
    out = []
    for _ in range(3):
        out.append(x.astype(jnp.bfloat16))
        x = x - out[-1].astype(jnp.float32)
    return out


@jax.custom_vjp
def _table_dot(table, x):
    """table [R, C] bfloat16 of 0 / 1 times x [C, d] float32, exact to
    float32 in three passes (the highest precision takes six)."""
    return sum(_dot(table, p) for p in _pieces(x))


def _table_dot_fwd(table, x):
    return _table_dot(table, x), table


def _table_dot_bwd(table, dz):
    return None, sum(_dot(table, p, _TN) for p in _pieces(dz))


_table_dot.defvjp(_table_dot_fwd, _table_dot_bwd)


def _eye(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


@jax.custom_vjp
def _inverses(Ls, level):
    """(I + L)^-1 for every strictly lower triangular L [C, C] float32 of
    the tuple, by products alone: the inverses X of the diagonal blocks of
    s rows join to those of 2s rows, [[A, 0], [-B U A, B]] = X - X U X
    with U the part of L under the diagonal blocks (the pairs of ``level``
    log2 s), 1 -> 2 -> ... -> C; level after level across the tuple."""
    Xs = [jnp.where(_eye(L.shape[0]), 1.0, 0.0)
          - jnp.where(level == 0, L, 0.0) for L in Ls]
    for l in range(1, LEVELS):
        XU = [_dot(X, jnp.where(level == l, L, 0.0)) for X, L in zip(Xs, Ls)]
        Xs = [X - _dot(xu, X) for X, xu in zip(Xs, XU)]
    return tuple(Xs)


def _inverses_fwd(Ls, level):
    Xs = _inverses(Ls, level)
    return Xs, Xs


def _inverses_bwd(Xs, dXs):          # dL = -X^T dX X^T
    right = [_dot(dX, X, _NT) for X, dX in zip(Xs, dXs)]
    return tuple(-_dot(X, r, _TN) for X, r in zip(Xs, right)), None


_inverses.defvjp(_inverses_fwd, _inverses_bwd)


def _step(S, q, k, v, g, beta, *, sums, level):
    """The chunks of one grid step on tiles, for the P heads the step
    takes: S [P, dk, dv] float32, q, k [R, P x dk] and v [R, P x dv] in
    the compute dtype, g [R, P x dk] and beta [R, P] float32 -> (S after
    them, o [R, P x dv] float32). Pure, so the backward kernel takes
    `jax.vjp` of it. Every list below holds one entry a head and chunk."""
    C, dtype, f32 = CHUNK, v.dtype, jnp.float32
    P, chunks = S.shape[0], q.shape[0] // C

    def cut(x):     # [R, P x d] -> [C, d] a head and chunk, heads outermost
        d = x.shape[1] // P
        return [x[c * C:(c + 1) * C, h * d:(h + 1) * d]
                for h in range(P) for c in range(chunks)]
    q32, k32, v32 = (cut(x.astype(f32)) for x in (q, k, v))
    betas = cut(beta)
    Zs = [_table_dot(sums, x) for x in cut(g)]
    Gs = [Z[:C] for Z in Zs]
    decay = [jnp.exp(G) for G in Gs]
    tail = [jnp.exp(G[C - 1:C] - G) for G in Gs]     # e^(G_C - G)
    row = jax.lax.broadcasted_iota(jnp.int32, Gs[0].shape, 0)
    a_qk = [jnp.where(_eye(C), jnp.sum(a * b, axis=1, keepdims=True), 0.0)
            for a, b in zip(q32, k32)]
    a_kk = [jnp.zeros((C, C), f32)] * len(Gs)
    for l in range(LEVELS):
        s = 1 << l

        def exponent(Z, G):
            """-|G - G_m|, m the last row of the upper half of the row's
            block of 2s rows."""
            if l < 2:
                return Z[(1 + l) * C:(2 + l) * C]
            m = G.reshape(C // (2 * s), 2 * s, -1)[:, s - 1:s]
            m = jnp.broadcast_to(m, (C // (2 * s), 2 * s, G.shape[1]))
            m = m.reshape(G.shape)
            return jnp.where((row & s) != 0, G - m, m - G)
        e = [jnp.exp(exponent(Z, G)) for Z, G in zip(Zs, Gs)]
        # the pairs within NEAR rows decay least and weigh most in the
        # system: their products keep float32 operands
        lp = f32 if 2 * s <= NEAR else dtype
        kl = [(a * x).astype(lp) for a, x in zip(k32, e)]
        ql = [(a * x).astype(lp) for a, x in zip(q32, e)]
        # q's and k's rows against k's: the pairs of another level are
        # masked after the product
        both = [_dot(jnp.concatenate([a, b], 0), b, _NT)
                for a, b in zip(ql, kl)]
        a_qk = [jnp.where(level == l, x[:C], a) for x, a in zip(both, a_qk)]
        a_kk = [jnp.where(level == l, x[C:], a) for x, a in zip(both, a_kk)]
    solved = [X.astype(dtype) for X in _inverses(
        tuple(b * a for b, a in zip(betas, a_kk)), level)]
    w_v = [_dot(X, (b * a).astype(dtype))
           for X, b, a in zip(solved, betas, v32)]
    w_k = [_dot(X, (b * (a * d)).astype(dtype)).astype(dtype)
           for X, b, a, d in zip(solved, betas, k32, decay)]
    q_in = [(a * d).astype(dtype) for a, d in zip(q32, decay)]
    k_out = [(a * t).astype(dtype) for a, t in zip(k32, tail)]
    a_qk = [a.astype(dtype) for a in a_qk]
    # e^G_C as a column: the last row of ``decay`` laid on a diagonal
    last = [jnp.sum(jnp.where(_eye(d.shape[1]), d[C - 1:C], 0.0), axis=1,
                    keepdims=True) for d in decay]
    S, out = [S[h] for h in range(P)], [[] for _ in range(P)]
    for c in range(chunks):              # chunk after chunk, head by head
        for h in range(P):
            i = h * chunks + c
            Sd = S[h].astype(dtype)
            ud = (w_v[i] - _dot(w_k[i], Sd)).astype(dtype)   # the writes
            out[h].append(_dot(q_in[i], Sd) + _dot(a_qk[i], ud))
            S[h] = last[i] * S[h] + _dot(k_out[i], ud, _TN)
    return jnp.stack(S), jnp.concatenate(
        [jnp.concatenate(x, axis=0) for x in out], axis=1)


def _heads_beta(beta_ref, P):
    """beta_ref [1, R, H] -> the step's P heads' columns [R, P]."""
    x = beta_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    first = pl.program_id(1) * P
    return jnp.concatenate(
        [jnp.sum(jnp.where(lane == first + j, x, 0.0), axis=1, keepdims=True)
         for j in range(P)], axis=1)


def _fwd_kernel(sums_ref, level_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                o_ref, *rest, save, final):
    """Grid (B, H / P, steps), the steps in order: ``rest`` is the state
    scratch [P, dk, dv], before it the start states' output when the pass
    is being differentiated (``save``) or the state after the last step
    when a caller keeps it (``final``: serving's prefill)."""
    S_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        S_ref[...] = jnp.zeros_like(S_ref)

    if save:
        rest[0][0, :, 0] = S_ref[...]
    S_ref[...], o = _step(
        S_ref[...], q_ref[0], k_ref[0], v_ref[0], g_ref[0],
        _heads_beta(beta_ref, S_ref.shape[0]), sums=sums_ref[...],
        level=level_ref[...])
    o_ref[0] = o.astype(o_ref.dtype)
    if final:   # the block stays put over the steps: written back once
        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _():
            rest[0][0] = S_ref[...]


def _bwd_kernel(sums_ref, level_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                start_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                dbeta_ref, dS_ref):
    """Grid (B, H / P, steps), the steps in REVERSE (the index maps turn
    them round): from the step's start state its chunks are walked
    forward, what their transpose needs staying in VMEM, then back with
    dS."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dS_ref[...] = jnp.zeros_like(dS_ref)

    _, pull = jax.vjp(
        functools.partial(_step, sums=sums_ref[...], level=level_ref[...]),
        start_ref[0, :, 0], q_ref[0], k_ref[0], v_ref[0], g_ref[0],
        _heads_beta(beta_ref, dS_ref.shape[0]))
    (dS_ref[...], dq_ref[0], dk_ref[0], dv_ref[0], dg_ref[0],
     dbeta) = pull((dS_ref[...], do_ref[0].astype(jnp.float32)))
    # a head's dbeta column laid along lanes: on a diagonal, summed
    for j in range(dbeta.shape[1]):
        dbeta_ref[0, j, 0] = jnp.sum(jnp.where(
            _eye(dbeta.shape[0]), dbeta[:, j:j + 1], 0.0), axis=0,
            keepdims=True)


def _call(kernel, name, n, R, shapes, step, extra_in, out):
    """`pallas_call` over the grid (B, H / P, n), P = 2 heads a grid step
    where H is even (their chains of state products overlap: alone a
    head's left the MXUs waiting a tenth of the time). ``step`` maps the
    grid's third index to the step of the walk; ``shapes`` is (B, H, dk,
    dv); ``extra_in`` and ``out`` list (kind of block, array or its
    shape) after the tables and q, k, v, g, beta."""
    B, H, dk, dv = shapes
    P = 2 - H % 2

    def whole(shape):
        return pl.BlockSpec(shape, lambda b, h, i: (0,) * len(shape))
    spec = {
        # [B, T, H x d]: the step's rows, its heads' columns
        "k": pl.BlockSpec((1, R, P * dk), lambda b, h, i: (b, step(i), h)),
        "v": pl.BlockSpec((1, R, P * dv), lambda b, h, i: (b, step(i), h)),
        "beta": pl.BlockSpec((1, R, H), lambda b, h, i: (b, step(i), 0)),
        "state": pl.BlockSpec((1, P, 1, dk, dv),
                              lambda b, h, i: (b, h, step(i), 0, 0)),
        "final": pl.BlockSpec((1, P, dk, dv), lambda b, h, i: (b, h, 0, 0)),
        "lanes": pl.BlockSpec((1, P, 1, 1, R),
                              lambda b, h, i: (b, h, step(i), 0, 0)),
    }
    return pl.pallas_call(
        kernel, grid=(B, H // P, n), name=name, interpret=_use_interpret(),
        in_specs=[whole((3 * CHUNK, CHUNK)), whole((CHUNK, CHUNK)),
                  spec["k"], spec["k"], spec["v"], spec["k"], spec["beta"]]
        + [spec[kind] for kind, _ in extra_in],
        out_specs=[spec[kind] for kind, _ in out],
        out_shape=[x for _, x in out],
        scratch_shapes=[pltpu.VMEM((P, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20))


def _forward(q, k, v, g, beta, R, save, final=False):
    """q, k, g [B, T, H x dk], v [B, T, H x dv], beta [B, T, H], T a
    multiple of R -> [o [B, T, H x dv]] and, with ``save``, the state at
    the start of every step, [B, H, T / R, dk, dv] float32, or, with
    ``final``, the state after the last, [B, H, dk, dv] float32."""
    B, T, H = beta.shape
    dk, dv, n = q.shape[2] // H, v.shape[2] // H, T // R
    out = [("v", jax.ShapeDtypeStruct(v.shape, v.dtype))]
    if save:
        out.append(("state", jax.ShapeDtypeStruct((B, H, n, dk, dv),
                                                  jnp.float32)))
    if final:
        out.append(("final", jax.ShapeDtypeStruct((B, H, dk, dv),
                                                  jnp.float32)))
    kernel = functools.partial(_fwd_kernel, save=save, final=final)
    return _call(kernel, "kda_scan_fwd", n, R, (B, H, dk, dv),
                 lambda i: i, [], out)(*_tables(), q, k, v, g, beta)


def _backward(q, k, v, g, beta, starts, do, R):
    B, T, H = beta.shape
    dk, dv, n = q.shape[2] // H, v.shape[2] // H, T // R
    f32 = jnp.float32
    out = [("k", jax.ShapeDtypeStruct(q.shape, q.dtype)),
           ("k", jax.ShapeDtypeStruct(k.shape, k.dtype)),
           ("v", jax.ShapeDtypeStruct(v.shape, v.dtype)),
           ("k", jax.ShapeDtypeStruct(g.shape, f32)),
           ("lanes", jax.ShapeDtypeStruct((B, H, n, 1, R), f32))]
    *grads, dbeta = _call(
        _bwd_kernel, "kda_scan_bwd", n, R, (B, H, dk, dv),
        lambda i: n - 1 - i, [("state", starts), ("v", do)], out)(
            *_tables(), q, k, v, g, beta, starts, do)
    return (*grads, jnp.moveaxis(dbeta.reshape(B, H, T), 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(q, k, v, g, beta, R):
    return _forward(q, k, v, g, beta, R, save=False)[0]


def _scan_fwd(q, k, v, g, beta, R):
    o, starts = _forward(q, k, v, g, beta, R, save=True)
    return o, (q, k, v, g, beta, starts)


def _scan_bwd(R, saved, do):     # traced under the caller's scopes too
    return _backward(*saved, do, R)


_scan.defvjp(_scan_fwd, _scan_bwd)


def kda_scan(q, k, v, g, beta, *, group: int = 2, final_state: bool = False):
    """The chunked form. q, k: [B, T, H, dk] and v: [B, T, H, dv] in the
    compute dtype; g: [B, T, H, dk] float32 log-decays (<= 0); beta: [B,
    T, H] float32 -> o [B, T, H, dv] in v's dtype. Any T: rows past it are
    padded with tokens that write nothing and decay nothing.
    ``final_state``: -> (o, the state after row T, [B, H, dk, dv] float32,
    from the forward kernel's last grid step): serving's prefill, which
    takes no gradient; the trainer's call and its kernel are unchanged. ``group``
    chunks a grid step of the kernels (the backward keeps T / (64 x group)
    states, for the layer being differentiated alone). The trainer takes
    the default: a larger step ran no faster on a v5e and its kernels
    compile twice as long. The tests set it, to reach one chunk a step
    and a padded last step at small T."""
    with jax.named_scope("kda.scan"):
        B, T, H, _ = q.shape
        R = CHUNK * max(1, min(group, -(-T // CHUNK)))
        pad = -T % R

        def rows(x):    # [B, T, H, d] -> [B, T + pad, H x d]
            x = x.reshape(B, T, -1)
            return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
        args = (rows(q), rows(k), rows(v), rows(g.astype(jnp.float32)),
                rows(beta.astype(jnp.float32)))
        if final_state:
            o, S = _forward(*args, R, save=False, final=True)
            return o[:, :T].reshape(B, T, H, -1), S
        return _scan(*args, R)[:, :T].reshape(B, T, H, -1)


# ---- one token a slot: serving's decode step --------------------------------

def _decode_heads(H: int) -> int:
    """Heads a grid step of the decode kernel (a state block of 16 heads is
    1 MB: 512 steps a layer at 128 slots x 64 heads)."""
    return next(n for n in (16, 8, 4, 2, 1) if H % n == 0)


def _decode_kernel(layer_ref, active_ref, s_ref, a_ref, k_ref, q_ref, v_ref,
                   b_ref, s_out, o_ref):
    """Grid (slots, H / hb). s_ref / s_out: the slot's states of hb heads,
    [1, 1, hb, dk, dv] of the one aliased buffer; a, k, q as COLUMNS [1, 1,
    dk, hb] (dk on sublanes, as the state's rows are); v and the write
    strength (repeated along the row) as rows [1, hb, dv]."""
    del layer_ref    # the index maps read it
    live = active_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        for j in range(s_ref.shape[2]):
            k = k_ref[0, 0, :, j:j + 1]                       # [dk, 1]
            S = s_ref[0, 0, j] * a_ref[0, 0, :, j:j + 1]      # S' = a . S
            read = jnp.sum(S * k, axis=0, keepdims=True)      # S'^T k
            S = S + k * (b_ref[0, j:j + 1] * (v_ref[0, j:j + 1] - read))
            s_out[0, 0, j] = S
            o_ref[0, j:j + 1] = jnp.sum(S * q_ref[0, 0, :, j:j + 1], axis=0,
                                        keepdims=True)        # S^T q

    @pl.when(jnp.logical_not(live))
    def _():    # bit for bit what it was
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _decode_step_kernel(state, layer, q, k, v, a, beta, active):
    L, B, H, dk, dv = state.shape
    hb = _decode_heads(H)

    def columns(x):     # [B, H, dk] -> [B, H / hb, dk, hb]
        return jnp.swapaxes(x.reshape(B, H // hb, hb, dk), 2, 3)
    col = pl.BlockSpec((1, 1, dk, hb), lambda b, h, *_: (b, h, 0, 0))
    row = pl.BlockSpec((1, hb, dv), lambda b, h, *_: (b, h, 0))
    slab = pl.BlockSpec((1, 1, hb, dk, dv),
                        lambda b, h, layer, active: (layer[0], b, h, 0, 0))
    return pl.pallas_call(
        _decode_kernel, name="kda_decode_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H // hb),
            in_specs=[slab, col, col, col, row, row],
            out_specs=[slab, row]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, dv), jnp.float32)],
        # the states are updated where they lie (operand 2, after the two
        # prefetched scalars)
        input_output_aliases={2: 0},
        interpret=_use_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), active.astype(jnp.int32),
      state, columns(a), columns(k), columns(q), v,
      jnp.broadcast_to(beta[..., None], v.shape))


def _decode_step_xla(state, layer, q, k, v, a, beta, active):
    S = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    S1 = a[..., None] * S
    u = beta[..., None] * (v - jnp.sum(S1 * k[..., None], axis=2))
    S1 = S1 + k[..., None] * u[:, :, None, :]
    o = jnp.sum(S1 * q[..., None], axis=2)
    S1 = jnp.where(active[:, None, None, None], S1, S)
    return jax.lax.dynamic_update_index_in_dim(state, S1, layer, 0), o


def kda_decode_step(state, layer, q, k, v, g, beta, active, *,
                    kernel=None):
    """The recurrence's one-token form on the slots' states, in place:
    ``state`` [L, slots, H, dk, dv] float32 (every KDA layer's, stacked),
    ``layer`` which of them; q, k, g [slots, H, dk], v [slots, H, dv],
    beta [slots, H], active [slots] bool -> (state, o [slots, H, dv]
    float32): S' = exp(g) . S, u = b (v - S'^T k), S = S' + k u^T, o = S^T
    q, float32 throughout. A slot that is not active keeps its state bit
    for bit (its o is junk). On a chip a Mosaic kernel (`kda_decode_step`)
    that walks (slot, 16 heads) blocks of the one buffer, read and written
    once where they lie (``input_output_aliases``), the layer picked by
    the block index, as `decode_attention` picks its layer; on the CPU the
    same step in `jax.numpy` (``kernel`` forces either, for the tests)."""
    f32 = jnp.float32
    q, k, v, beta = (x.astype(f32) for x in (q, k, v, beta))
    a = jnp.exp(g.astype(f32))
    kernel = not _use_interpret() if kernel is None else kernel
    step = _decode_step_kernel if kernel else _decode_step_xla
    with jax.named_scope("kda.step"):
        return step(state, jnp.asarray(layer, jnp.int32), q, k, v, a, beta,
                    active)
