"""`chip_head_copy.py`'s plumbing at toy widths on the CPU: its lines are
whole, the tree it compares against is the held tree less the copy, and
what differs here is the rounding alone (on the CPU a float32 matmul is
one; the verdict belongs to the chip: PERF.md section 6, PR 57)."""

import json

import jax.numpy as jnp
import pytest

import chip_head_copy
from benchmark.harness import spec


def test_one_serve_cell_a_served_configuration():
    bench = spec.load_benchmark()
    cells = chip_head_copy.served_cells(bench)
    configs = [spec.find_cell(bench, name)["config"] for name in cells]
    assert len(set(configs)) == len(configs) == 4
    assert "internlm2-1.8b.chat-steady" in cells
    assert not any("train" in name for name in cells)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_toy_compare_reads_both_trees(tied):
    conf = spec.load_config(spec.load_benchmark(), "internlm2-1.8b")
    cfg = spec.build_transformer_config(
        conf, **dict(chip_head_copy.TOY, tie_embeddings=tied))
    line = chip_head_copy.compare(cfg, slots=4, max_len=24, bucket=16,
                                  seed=3, timed_chunks=1)
    assert line["copy"] and line["copy_bytes"] == 2 * 96 * 32
    assert line["copy_shape"] == ([96, 32] if tied else [32, 96])
    assert line["rows"] == line["slots"] == 4
    assert set(line["prefill_logits_equal"]) == {"1", "2", "4"}
    # bf16 operands against float32 ones: the rounding, and no more
    assert 0 < line["decode_logits_max_abs_diff"] < 0.05
    assert not line["decode_logits_equal"]
    assert all(0 < d < 0.05
               for d in line["prefill_logits_max_abs_diff"].values())
    assert sorted(line["chunk_ms"]) == ["held", "leaf"]
    assert all(len(v) == 2 and min(v) > 0 for v in line["chunk_ms"].values())
    json.dumps(line)


def test_float32_compute_has_no_copy_to_compare():
    conf = spec.load_config(spec.load_benchmark(), "internlm2-1.8b")
    cfg = spec.build_transformer_config(
        conf, **dict(chip_head_copy.TOY, dtype="float32"))
    assert chip_head_copy.compare(cfg, 4, 24, 16, seed=3) == {
        "copy": False, "dtype": "float32"}
    assert jnp.dtype(cfg.dtype) == jnp.float32


def test_refuses_without_a_tpu(capsys):
    assert chip_head_copy.main(
        ["--cell", "internlm2-1.8b.batch-closed"]) == 1
    assert json.loads(capsys.readouterr().out.strip())["error"] \
        .startswith("no TPU")
