"""Flash-attention kernel vs XLA reference (interpret mode on CPU)."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention
from ray_tpu.parallel import reference_attention


def _qkv(b=2, t=64, h=4, kv=None, d=16, seed=0):
    rng = np.random.RandomState(seed)
    kv = kv or h
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, kv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, kv, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_uneven_blocks():
    q, k, v = _qkv(t=48)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_grad_matches_reference():
    q, k, v = _qkv(b=1, t=32, h=2, d=8)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_k=16) ** 2).sum()

    def f_ref(q, k, v):
        return (reference_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_flash_bf16():
    q, k, v = _qkv(t=32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(q, k, v)
    ref = reference_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---- the blocked kernels: every tile shape the walk can meet ---------------

fa = importlib.import_module("ray_tpu.ops.flash_attention")

# (t_q, t_k, block_q, block_k): T equal to, a multiple of and no multiple of
# the blocks, block_q != block_k in both orders (the diagonal crosses tiles
# unevenly), and t_k != t_q (never with a mask)
_WALKS = [
    (32, 32, 32, 32),
    (64, 64, 16, 16),
    (64, 64, 16, 32),
    (64, 64, 32, 16),
    (48, 48, 32, 32),
    (40, 40, 16, 32),
    (40, 40, 32, 16),
    (32, 80, 16, 32),
    (80, 48, 32, 16),
]
_WALK_CASES = [(w, causal) for w in _WALKS for causal in (True, False)
               if not (causal and w[0] != w[1])]


def _tol(dtype):
    return dict(rtol=2e-4, atol=2e-4) if dtype == jnp.float32 \
        else dict(rtol=5e-2, atol=5e-2)


def _walk_inputs(t_q, t_k, d, dtype, seed=0):
    rng = np.random.RandomState(seed)
    q, k, v, w = (jnp.asarray(rng.randn(1, t, 2, d), jnp.float32)
                  for t in (t_q, t_k, t_k, t_q))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (w,)


@pytest.mark.parametrize("walk,causal", _WALK_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_blocked_forward(walk, causal, dtype):
    t_q, t_k, bq, bk = walk
    q, k, v, _ = _walk_inputs(t_q, t_k, 8, dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = reference_attention(q, k, v, causal=causal)
    assert out.dtype == dtype and out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("walk,causal", _WALK_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_blocked_gradient(walk, causal, dtype):
    t_q, t_k, bq, bk = walk
    q, k, v, w = _walk_inputs(t_q, t_k, 8, dtype)

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v).astype(jnp.float32) * w).sum()

    got = jax.grad(loss(functools.partial(
        flash_attention, causal=causal, block_q=bq, block_k=bk)),
        argnums=(0, 1, 2))(q, k, v)
    # the reference differentiates in float32 on the same (rounded) inputs
    want = jax.grad(loss(functools.partial(reference_attention,
                                           causal=causal)),
                    argnums=(0, 1, 2))(*(x.astype(jnp.float32)
                                         for x in (q, k, v)))
    tol = dict(rtol=2e-3, atol=2e-3) if dtype == jnp.float32 \
        else dict(rtol=5e-2, atol=8e-2)
    for g, r, x in zip(got, want, (q, k, v)):
        assert g.dtype == dtype and g.shape == x.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r), **tol)


@pytest.mark.parametrize("t_q, t_k, causal", [
    (300, 300, True),    # one block of 384, 84 rows of it padding
    (600, 600, True),    # one of 640
    (900, 900, True),    # two of 512, the second ragged
    (197, 197, False),   # an encoder: one block of 256
    (200, 600, False),
])
def test_flash_picked_blocks_at_ragged_lengths(t_q, t_k, causal):
    """Blocks the kernel picks are whole 128s, so a length that is not is
    padded up to them, here as on the chip."""
    q, k, v, w = _walk_inputs(t_q, t_k, 8, jnp.float32, seed=t_q)

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v, causal=causal) * w).sum()

    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=causal)),
        np.asarray(reference_attention(q, k, v, causal=causal)),
        rtol=2e-4, atol=2e-4)
    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("d", [8, 64, 128])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_head_dims(d, causal):
    """Forward and gradient at the head sizes the presets use, blocks
    picked by the kernel (none passed)."""
    q, k, v, w = _walk_inputs(40, 40, d, jnp.float32, seed=d)

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v) * w).sum()

    flash = functools.partial(flash_attention, causal=causal)
    ref = functools.partial(reference_attention, causal=causal)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               rtol=2e-4, atol=2e-4)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-3, atol=2e-3)


def _tiles_with_a_visible_score(t_q, t_k, bq, bk, causal):
    r = np.arange(t_q)[:, None]
    c = np.arange(t_k)[None, :]
    seen = (c <= r) if causal else np.ones((t_q, t_k), bool)
    return {(i, j) for i in range(-(-t_q // bq)) for j in range(-(-t_k // bk))
            if seen[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()}, seen


@pytest.mark.parametrize("causal", [True, False])
def test_visited_blocks_are_the_tiles_the_loops_walk(causal):
    """`visited_blocks` against brute force, and against the loop bounds
    both kernels take (`_k_blocks` forward, `_q_blocks` backward): they
    visit exactly the tiles that hold a visible score, and mask exactly
    those that also hold a hidden one."""
    shapes = [(t, t, bq, bk) for t in (16, 40, 48, 64, 96)
              for bq in (8, 16, 32, 48) for bk in (8, 16, 32, 48)]
    if not causal:
        shapes += [(32, 80, 16, 32), (80, 48, 32, 16)]
    for t_q, t_k, bq, bk in shapes:
        want, seen = _tiles_with_a_visible_score(t_q, t_k, bq, bk, causal)
        n_q, n_k = -(-t_q // bq), -(-t_k // bk)
        assert fa.visited_blocks(t_q, t_k, bq, bk, causal) \
            == (len(want), n_q * n_k)
        forward, unmasked = set(), set()
        for i in range(n_q):
            full, visit = fa._k_blocks(i, bq, bk, t_k, causal)
            assert 0 <= full <= visit <= n_k
            forward |= {(i, j) for j in range(visit)}
            unmasked |= {(i, j) for j in range(full)}
        assert forward == want
        for i, j in unmasked:  # no mask: every score real and visible
            assert (j + 1) * bk <= t_k
            assert seen[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].all()
        for i, j in forward - unmasked:
            tile = seen[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            assert not tile.all() or (j + 1) * bk > t_k
        backward = set()
        for j in range(n_k):
            start, full = fa._q_blocks(j, bq, bk, n_q, causal)
            assert 0 <= start <= max(start, full) <= n_q
            backward |= {(i, j) for i in range(start, n_q)}
            for i in range(max(start, full), n_q):  # walked without a mask
                assert seen[i * bq:min((i + 1) * bq, t_q),
                            j * bk:min((j + 1) * bk, t_k)].all()
        assert backward == want


def test_visited_share_at_the_cells_shape():
    blocks = fa._pick_block(4096), fa._pick_block(4096)
    visited, total = fa.visited_blocks(4096, 4096, *blocks, True)
    assert 0.5 < visited / total <= 0.63
    assert fa.visited_blocks(4096, 4096, 512, 512, True) == (36, 64)
    assert fa.visited_blocks(4096, 4096, 256, 256, True) == (136, 256)
    assert fa.visited_blocks(4096, 4096, *blocks, False) == (total, total)


@pytest.mark.parametrize("t, block", [
    (16384, 512), (4096, 512), (2048, 512), (2000, 512), (1664, 640),
    (1536, 512), (1280, 640), (1279, 640), (1152, 640), (1000, 512),
    (896, 512), (768, 768), (767, 768), (640, 640), (600, 640), (520, 640),
    (300, 384), (257, 384), (197, 256), (128, 128), (77, 128), (1, 128)])
def test_picked_block_is_whole_tiles_and_few(t, block):
    """A block the kernel picks is whole 128s (Mosaic slices the resident
    side by it), T / 512 of them to the nearest (the fastest walk on the
    chip at every length timed, PERF.md PR 30), none of them all padding."""
    assert fa._pick_block(t) == block
    n = -(-t // block)
    assert block % 128 == 0 and n * block - t < block
    assert n == max(1, (2 * t + 511) // 1024)  # T / 512 to the nearest
