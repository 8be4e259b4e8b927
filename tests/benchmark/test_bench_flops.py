"""FLOP and byte functions against counts made by hand: the dense GQA
block's own counts (`benchmark/architectures/dense_gqa.py`, found as a
config that names no architecture finds it) and what no architecture owns
(`benchmark/harness/flops.py`)."""

import pytest

import bench_paths  # noqa: F401
from benchmark.harness import flops, spec

BENCH = spec.load_benchmark()
PEAKS = spec.device_peaks("TPU v5 lite")
DENSE = spec.load_architecture({})


def _fields(name, layers=None):
    f = spec.transformer_fields(spec.load_config(BENCH, name))
    if layers:
        f["n_layers"] = layers
    return f


@pytest.mark.parametrize("name,layers,per_layer,total_params", [
    # q 2048x2048, k and v 2048x1024 each, o 2048x2048, three 2048x8192
    ("internlm2-1.8b", 24, 62_914_560, 1_889_110_016),
    # q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    ("mistral-7b-v0.3", 32, 218_103_808, 7_248_023_552)])
def test_parameter_counts_at_published_depth(name, layers, per_layer,
                                             total_params):
    f = _fields(name, layers)
    mm = DENSE.matmul_params(f)
    assert mm["per_layer"] == per_layer
    assert mm["head"] == f["d_model"] * f["vocab_size"]
    assert DENSE.num_params(f, {}) == total_params


@pytest.mark.parametrize("name", ["internlm2-1.8b", "mistral-7b-v0.3"])
def test_num_params_agrees_with_the_programs_own_count(name):
    conf = spec.load_config(BENCH, name)
    cfg = spec.build_transformer_config(conf)
    assert spec.load_architecture(conf).num_params(
        spec.transformer_fields(conf), conf) == cfg.num_params


@pytest.mark.parametrize("seq", [1, 2048, 4096])
def test_forward_flops_by_hand_on_a_one_layer_model(seq):
    f = dict(d_model=8, n_layers=1, n_heads=2, n_kv_heads=1, d_ff=16,
             vocab_size=10)
    # q 8x8 + k 8x4 + v 8x4 + o 8x8 + 3 x 8x16 = 576; head 80
    matmul = 2 * (576 + 80)
    # per query: QK^T and PV, 2 * hd(4) * heads(2) each per key, (seq+1)/2
    attn = 2 * (2 * 4 * 2) * (seq + 1) / 2
    forward = DENSE.forward_flops_per_token(f, {}, seq)
    assert forward == pytest.approx(matmul + attn)
    assert flops.train_from_forward(forward) == pytest.approx(
        3 * (matmul + attn))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("backward", [False, True])
def test_attention_kernel_cost_by_hand(causal, backward):
    B, H, T, D = 2, 3, 8, 4
    c = flops.flash_attention_cost(B, H, T, T, D, causal=causal,
                                   backward=backward)
    product = 2 * T * T * D * (0.5 if causal else 1.0)
    assert c["flops"] == (5 if backward else 2) * product * B * H
    elems = B * H * T * D
    if backward:  # reads q k v o do + 2 rows; writes dq dk dv
        assert c["bytes"] == 8 * elems * 2 + 2 * B * H * T * 4
    else:         # reads q k v; writes o + lse row
        assert c["bytes"] == 4 * elems * 2 + B * H * T * 4


@pytest.mark.parametrize("flops_,nbytes,bound", [
    (197e12, 1.0, "compute"), (1.0, 819e9, "memory"),
    (197e12, 819e9, "compute")])
def test_roofline_takes_the_larger_bound(flops_, nbytes, bound):
    r = flops.roofline_seconds(flops_, nbytes, PEAKS)
    assert r["bound"] == bound and r["seconds"] == pytest.approx(1.0)


def test_attention_at_the_cells_shape_is_compute_bound():
    f = _fields("internlm2-1.8b")
    c = flops.flash_attention_cost(4, f["n_heads"], 4096, 4096, 128)
    assert flops.roofline_seconds(c["flops"], c["bytes"],
                                  PEAKS)["bound"] == "compute"


@pytest.mark.parametrize("chips", [1, 4])
def test_mfu_is_a_share_of_chips_times_peak(chips):
    assert flops.mfu_percent(1e9, 197e3 * chips, chips, PEAKS) == \
        pytest.approx(100.0)
    assert flops.mfu_percent(1e9, 98.5e3 * chips, chips, PEAKS) == \
        pytest.approx(50.0)


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(spec.SpecError, match="not in benchmark/peaks.json"):
        spec.device_peaks("TPU v9 imaginary")
    with pytest.raises(spec.SpecError):
        spec.device_peaks("cpu")
