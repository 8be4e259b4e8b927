"""`ops.grouped_matmul`: the short row buffer's grouped matmul against
`jax.lax.ragged_dot` on the same bf16 operands (the interpreter on the
CPU), its gradients, and where `models.moe` picks it."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.ops import grouped_matmul
from ray_tpu.ops.grouped_matmul import grouped_matmul_rows

ROWS, D_IN, D_OUT = 64, 256, 384


def _operands(rows, d_in, d_out, sizes, seed=0):
    """Rows sorted by group, zeros past the groups' sum, as the layer
    hands them over."""
    kx, kw = jax.random.split(jax.random.key(seed))
    live = (jnp.arange(rows) < sum(sizes))[:, None]
    xs = jnp.where(live, jax.random.normal(kx, (rows, d_in)), 0).astype(
        jnp.bfloat16)
    w = (jax.random.normal(kw, (len(sizes), d_in, d_out))
         * d_in ** -0.5).astype(jnp.bfloat16)
    return xs, w, jnp.asarray(sizes, jnp.int32), live


def _one_rounding_apart(got, want):
    """Equal, or the two roundings of float32 sums that differ in their
    order: a step of bf16's 8 bits at the output's size."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.all(np.abs(got - want)
                  <= 2.0 ** -7 * np.maximum(np.abs(want), 2.0 ** -7))
    # and nearly every number is the same number
    assert np.mean(got != want) < 0.01


CASES = {
    "empty_groups_between_full_ones": [16, 0, 0, 32, 0, 16],
    "one_group_holds_every_row": [0, ROWS, 0],
    "groups_start_and_end_inside_a_tile": [3, 0, 5, 17, 0, 2, 1, 11],
    "a_group_of_one_row_a_tile_apart": [15, 1, 16, 1],
    "no_live_row": [0, 0, 0, 0],
    "rows_past_the_sum": [5, 0, 7],
    "first_group_empty": [0, 0, 9, 30],
}


@pytest.mark.parametrize("sizes", CASES.values(), ids=CASES.keys())
def test_equals_ragged_dot(sizes):
    xs, w, gs, live = _operands(ROWS, D_IN, D_OUT, sizes)
    want = jnp.where(live, jax.lax.ragged_dot(xs, w, gs), 0)
    got = jax.jit(grouped_matmul_rows)(xs, w, gs)
    assert got.dtype == jnp.bfloat16 and got.shape == (ROWS, D_OUT)
    _one_rounding_apart(got, want)
    # rows past the groups' sum are zero whatever lay in xs there
    assert not np.any(np.asarray(got, np.float32)[sum(sizes):])


def test_rows_past_the_sum_are_zero_for_any_input():
    xs, w, gs, _ = _operands(ROWS, D_IN, D_OUT, [5, 0, 7])
    xs = xs.at[12:].set(1.0)
    got = np.asarray(grouped_matmul_rows(xs, w, gs), np.float32)
    assert np.any(got[:12]) and not np.any(got[12:])


@pytest.mark.parametrize("d_in,d_out", [(4096, 1280), (1280, 4096)],
                         ids=["in", "back"])
def test_the_cells_widths_cut_in_depth_only(d_in, d_out):
    """[512, 4096] x [G, 4096, 1280] and the way back at G = 3, a few rows
    a group as a decode substep has them: the blocks the cell fetches
    ([4096, 640] and [1280, 2048]), two column tiles each."""
    assert grouped_matmul._tile_n(d_in, d_out, 2) == d_out // 2
    assert grouped_matmul.takes(512, d_in, d_out, jnp.bfloat16)
    xs, w, gs, live = _operands(512, d_in, d_out, [3, 0, 14], seed=1)
    want = jnp.where(live, jax.lax.ragged_dot(xs, w, gs), 0)
    _one_rounding_apart(jax.jit(grouped_matmul_rows)(xs, w, gs), want)


def test_gradients_are_ragged_dots():
    xs, w, gs, _ = _operands(ROWS, D_IN, D_OUT, [3, 0, 5, 17, 0, 2, 1, 11])
    cot = jax.random.normal(jax.random.key(2), (ROWS, D_OUT))

    def pull(dot):
        return jax.jit(jax.grad(
            lambda xs, w: jnp.sum(dot(xs, w, gs).astype(jnp.float32) * cot),
            argnums=(0, 1)))(xs, w)
    for got, want in zip(pull(grouped_matmul_rows), pull(jax.lax.ragged_dot)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize("sizes,ids,n", [
    ([0, 3, 0, 2], [1, 3, 3, 3], 2), ([0, 0, 0], [0, 0, 0], 0),
    ([4, 4], [0, 1], 2), ([0, 0, 40], [2, 2, 2], 1)])
def test_touched_groups_are_listed_first_and_the_last_repeats(sizes, ids, n):
    """What the index map reads: a step past the touched groups names the
    block the step before it named, so no copy is issued for it."""
    got_ids, got_n, first, end = grouped_matmul._touched(
        jnp.asarray(sizes), 32)
    assert list(np.asarray(got_ids)) == ids and int(got_n[0]) == n
    ends = np.minimum(np.cumsum(sizes), 32)
    assert list(np.asarray(end)) == list(ends)
    assert list(np.asarray(first)) == list(
        np.minimum(np.cumsum(sizes) - sizes, 32))


@pytest.mark.parametrize("shape,taken", [
    ((512, 4096, 1280, jnp.bfloat16), True),
    ((512, 1280, 4096, jnp.bfloat16), True),
    ((512, 4096, 1280, jnp.float32), False),    # bf16 operands alone
    ((24, 4096, 1280, jnp.bfloat16), False),    # no whole window of rows
    ((512, 64, 32, jnp.bfloat16), False),       # no whole lanes
])
def test_takes_whole_tiles_of_bf16(shape, taken):
    assert grouped_matmul.takes(*shape) is taken


@pytest.mark.parametrize("rows,on_chip,kernel", [
    (512, True, True), (1024, True, False), (512, False, False),
    (64, True, True)])
def test_expert_rows_picks_the_kernel_by_the_buffers_length(
        monkeypatch, rows, on_chip, kernel):
    """`_expert_rows` on a buffer of one row tile runs the rows kernel,
    three times; on a longer one, and anywhere off the chip, `ragged_dot`:
    static shapes alone decide."""
    monkeypatch.setattr(moe, "_on_chip", lambda: on_chip)
    k, held, d, f = 2, 4, 128, 256
    n = rows // k

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)
    text = str(jax.make_jaxpr(
        lambda *a: moe._expert_rows(k, rows, True, *a))(
            s((n, d)), s((rows,), jnp.int32), s((rows,), jnp.int32),
            s((held,), jnp.int32), s((n, k), jnp.float32),
            s((held, d, f)), s((held, d, f)), s((held, f, d))))
    assert text.count("ragged-dot-rows") == (3 if kernel else 0)
    assert len(re.findall(r"= ragged_dot(?:_general)?\[", text)) == (
        0 if kernel else 3)
    assert moe._rows_kernel(rows, d, f, jnp.bfloat16) is kernel


def test_the_layer_says_which_kernel_ran(monkeypatch):
    """stats["rows_kernel"]: 1.0 where the layer's grouped matmuls were
    the rows kernel's, and the layer's result is `ragged_dot`'s to a
    rounding."""
    from ray_tpu.models.config import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, d_model=128, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=128, max_seq_len=16, moe_experts=8, moe_top_k=2,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    lp = jax.tree.map(lambda a: a[0],
                      moe.init_moe_params(jax.random.key(0), cfg))
    h = jax.random.normal(jax.random.key(1), (2, 16, 128)).astype(
        jnp.bfloat16)
    want, stats = moe.moe_layer(h, lp, cfg)
    assert float(stats["rows_kernel"]) == 0.0
    monkeypatch.setattr(moe, "_on_chip", lambda: True)
    got, stats = moe.moe_layer(h, lp, cfg)
    assert float(stats["rows_kernel"]) == 1.0 == float(stats["compact"])
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2.0 ** -7, rtol=2.0 ** -6)


def test_the_chip_timing_of_the_kernel_alone_runs_at_toy_size(capsys):
    """`chip_expert_layer.py --rows` (the Solar cell's widths, on the chip)
    at a toy size here: every case within a rounding of `ragged_dot`, and
    a difference of more than one is told."""
    import chip_expert_layer as script

    assert script.main(["--rows", "--toy", "--seeds", "7"]) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[0])
    assert sorted(line["cases"]) == ["back:decode", "back:prefill64",
                                     "in:decode", "in:prefill64"]
    assert all(line["checks"].values())
    case = line["cases"]["in:decode"]
    assert (case["live"], case["touched"]) == (8, 5)
    bad = {"cases": {"x": dict(case, largest_difference=0.1,
                               largest_output=1.0)}}
    assert not script.holds_rows(bad)["x:one_rounding_apart"]
