"""The decode-attention kernel (interpret mode) against the masked
contraction it replaces on a chip: `generate._gqa_decode_attention`, and
`generate._diff_decode_attention` for differential attention's pairs of
heads (the same call with the head's scale and a float32 output).

The kernel gets a cache in which every row a slot does not own, and every
other layer, is NaN: what it does not read cannot reach its output. The
reference gets the clean cache and a mask.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.generate import (_diff_decode_attention,
                                     _gqa_decode_attention)
from ray_tpu.ops.decode_attention import (block_bounds, decode_attention,
                                          pick_block, rows_read)

_S, _BLOCK, _KV, _HD = 32, 8, 2, 16
_LAYERS, _LAYER = 3, 1

# a slot is (start, pos, active); blocks are [0, 8), [8, 16), ...
_CASES = {
    # start inside a block, on a block's edge, and pos one past start
    "left_padding_inside_and_on_an_edge": [
        (3, 20, True), (8, 29, True), (16, 17, True), (21, 22, True)],
    # pos on a block's edge, at the end, and past it (clamped to S)
    "pos_on_an_edge_at_the_end_and_past_it": [
        (0, 16, True), (5, _S, True), (9, _S + 3, True), (0, 8, True)],
    "nothing_cached_yet": [(4, 4, True), (0, 9, True), (16, 16, True)],
    "inactive_between_active": [
        (2, 13, True), (0, 30, False), (7, 25, True), (0, 0, False),
        (24, 31, True)],
    "full_beside_empty_and_parked": [
        (0, _S, True), (0, 0, True), (0, _S, False), (1, _S - 1, True)],
}
# (KV heads, query rows a KV head, head width). The last is differential
# attention as the Phi cell holds it: 10 key pairs of 128 lanes ([k1 | k2],
# heads of 64), two query pairs a key pair, each [q1 | 0] and [0 | q2]: four
# query rows a key pair, the scores scaled by the HEAD's width, o float32
_LAYOUTS = {"group_of_1": (_KV, 1, _HD), "group_of_2": (_KV, 2, _HD),
            "pairs_of_heads": (10, 4, 128)}


def _slots(case):
    slots = _CASES[case]
    return (len(slots),) + tuple(np.array(x) for x in zip(*slots))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", list(_LAYOUTS))
@pytest.mark.parametrize("case", list(_CASES))
def test_kernel_equals_the_masked_contraction(case, layout, dtype):
    B, start, pos, active = _slots(case)
    KV, reps, hd = _LAYOUTS[layout]
    pairs = layout == "pairs_of_heads"
    rng = np.random.RandomState(len(case) + reps)
    dt = jnp.dtype(dtype)

    def normal(*shape):
        return jnp.asarray(rng.randn(*shape), dt)

    k = normal(_LAYERS, B, KV, _S, hd)
    v = normal(_LAYERS, B, KV, _S, hd)
    q = normal(B, 1, KV * reps, hd)
    k_new, v_new = normal(B, KV, hd), normal(B, KV, hd)
    kpos = np.arange(_S)[None, :]
    owned = jnp.asarray((kpos >= start[:, None]) & (kpos < pos[:, None]))
    if pairs:   # rows [q1 | 0], [0 | q2], as `transformer.diff_qkv` has them
        first = (np.arange(hd) < hd // 2)[None, :]
        second = (np.arange(KV * reps) % 2 == 1)[:, None]
        q = q * jnp.asarray(first != second, dt)
        how = dict(scale=(hd // 2) ** -0.5, out_dtype=jnp.float32)
        want = _diff_decode_attention(
            q.reshape(B, 1, -1, 2, hd), k[_LAYER], v[_LAYER], k_new, v_new,
            owned).reshape(q.shape)
    else:
        how = {}
        want = _gqa_decode_attention(q, k[_LAYER], v[_LAYER], k_new, v_new,
                                     owned)

    # every row a slot does not own, and every other layer, is NaN
    poison = np.ones((_LAYERS, B, 1, _S, 1), bool)
    poison[_LAYER] = ~np.asarray(owned)[:, None, :, None]
    got = decode_attention(
        q, jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v),
        k_new, v_new, jnp.asarray(pos), jnp.asarray(start),
        jnp.asarray(active), jnp.asarray(_LAYER), block=_BLOCK, **how)
    assert got.shape == q.shape and got.dtype == want.dtype == (
        jnp.float32 if pairs else q.dtype)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()  # an inactive slot's row too
    # bf16 out: an output ulp; float32 out against a bf16 cache: both sides
    # keep the probabilities' 16 bits (`generate._weighted_values`)
    tol = 2e-6 if dtype == "float32" else 2e-4 if pairs else 8e-3
    np.testing.assert_allclose(got[active], want[active], atol=tol, rtol=tol)
    if pairs:   # what `diff_out` goes on with: o1 - lam o2 of a query pair
        np.testing.assert_allclose(
            (got[:, :, 0::2] - 0.7 * got[:, :, 1::2])[active],
            (want[:, :, 0::2] - 0.7 * want[:, :, 1::2])[active],
            atol=tol, rtol=tol)
    # a slot with nothing cached, or not active, sees its own token alone
    alone = ~active | (pos <= start)
    own = np.repeat(np.asarray(v_new, np.float32), reps, axis=1)[:, None]
    np.testing.assert_allclose(got[alone], own[alone], atol=tol, rtol=tol)
    _, count = block_bounds(start, pos, active, _BLOCK, _S)
    assert (count[alone] == 0).all() and (count[~alone] > 0).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_defaults_are_the_grouped_query_call(dtype):
    """Scale and output dtype left out are `hd ** -0.5` and q's: the same
    bits as stating them, and a float32 output is that result before its
    one rounding."""
    B, start, pos, active = _slots("left_padding_inside_and_on_an_edge")
    rng = np.random.RandomState(7)
    dt = jnp.dtype(dtype)

    def normal(*shape):
        return jnp.asarray(rng.randn(*shape), dt)

    k, v = normal(_LAYERS, B, _KV, _S, _HD), normal(_LAYERS, B, _KV, _S, _HD)
    q = normal(B, 1, _KV * 2, _HD)
    args = (q, k, v, normal(B, _KV, _HD), normal(B, _KV, _HD),
            jnp.asarray(pos), jnp.asarray(start), jnp.asarray(active),
            jnp.asarray(_LAYER))
    plain = decode_attention(*args, block=_BLOCK)
    stated = decode_attention(*args, block=_BLOCK, scale=_HD ** -0.5,
                              out_dtype=dt)
    assert plain.dtype == stated.dtype == dt
    np.testing.assert_array_equal(np.asarray(plain, np.float32),
                                  np.asarray(stated, np.float32))
    wide = decode_attention(*args, block=_BLOCK, out_dtype=jnp.float32)
    assert wide.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(wide.astype(dt), np.float32),
                                  np.asarray(plain, np.float32))


def test_the_bounds_count_the_rows_of_the_blocks_a_slot_crosses():
    # by hand, blocks of 128 over 1280 positions: [200, 517) crosses
    # blocks 1..4 = 4 blocks; [0, 1024) is blocks 0..7 = 8; [896, 897) is
    # block 7 alone; an inactive slot and one with pos == start read none
    start = np.array([200, 0, 896, 0, 300])
    pos = np.array([517, 1024, 897, 640, 300])
    active = np.array([True, True, True, False, True])
    first, count = block_bounds(start, pos, active, 128, 1280)
    assert list(first[:3]) == [1, 0, 7]
    assert list(count) == [4, 8, 1, 0, 0]
    assert rows_read(start, pos, active, 128, 1280) == 13 * 128
    # blocks of 256: [200, 517) crosses 0..2, [0, 1024) 0..3, [896, 897) 3
    assert rows_read(start, pos, active, 256, 1280) == (3 + 4 + 1) * 256
    # a pos past the end reads to the end and no further
    assert rows_read(0, 1500, True, 128, 1280) == 1280
    # plain ints and arrays agree
    assert block_bounds(200, 517, True, 128, 1280) == (1, 4)


def test_a_block_tiles_the_cache_or_there_is_none(monkeypatch):
    import ray_tpu.ops.decode_attention as da

    assert pick_block(1280, 128, jnp.bfloat16) == 128  # interpret: any
    assert pick_block(20, 16, jnp.float32) == 20
    monkeypatch.setattr(da, "_use_interpret", lambda: False)
    # on the chip: whole sublane tiles of the dtype, whole lanes a head
    assert pick_block(1280, 128, jnp.bfloat16) == 128
    assert pick_block(640, 128, jnp.bfloat16) == 128
    assert pick_block(96, 128, jnp.bfloat16) == 96
    assert pick_block(96, 128, jnp.float32) == 96
    assert pick_block(72, 128, jnp.bfloat16) is None  # 72 = 4.5 tiles of 16
    assert pick_block(72, 128, jnp.float32) == 72
    assert pick_block(640, 64, jnp.bfloat16) is None  # a head of 64
