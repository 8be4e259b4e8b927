"""The OLMoE configuration, its architecture file, its cell and its
per-layer metrics (PR 27): the cell at toy size through the harness's own
functions on the CPU, the required work against hand counts at the
published widths, the grouped-matmul reader on a made-up trace, and, for
this benchmark as it stands, what two older tests of this directory meant
(`test_bench_spec.py::test_every_config_names_an_architecture_file_with_the_interface`
holds every config to name no architecture, and `test_bench_engine_spans.py::
test_the_thirteen_are_appended_and_change_nothing_that_was_there` holds PR 24's
thirteen to be the LAST per-layer entries: both FAIL since this configuration
and its metrics were appended, and only a `benchmark` PR may edit them)."""

import argparse
import os
import re
import subprocess
import sys

import pytest

import bench_paths
import ray_tpu
from benchmark.harness import spec
from test_bench_engine_spans import BATCH, CHAT, NEW as ENGINE_THIRTEEN

BENCH = spec.load_benchmark()
RUN = bench_paths.load_run_module()
CELL = "olmoe-1b-7b.train-4k"
CONF = spec.load_config(BENCH, "olmoe-1b-7b")
ARCH = spec.load_architecture(CONF)
# the catalog row's `config` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}
TINY = dict(vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
            d_ff=32, moe_experts=8, moe_top_k=2, dtype="float32")
NEW_METRICS = ["train_step_device_ms.olmoe", "train_mfu.olmoe",
               "peak_hbm_gb.olmoe", "moe_grouped_matmul_step_share.olmoe",
               "moe_grouped_matmul_roofline",
               "flash_attention_step_share.olmoe",
               "olmoe_flash_attention_roofline", "chip_worker_ready_s.olmoe"]


@pytest.fixture(scope="module")
def cpu_cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


# ---- the configuration -------------------------------------------------------

def test_config_file_holds_every_published_key_and_cuts_depth_only():
    for key, value in PUBLISHED.items():
        if key != "num_hidden_layers":
            assert key in CONF and CONF[key] == value, key
    assert list(CONF["reduced"]) == ["num_hidden_layers"]
    cut = CONF["reduced"]["num_hidden_layers"]
    assert cut["published"] == 16 and cut["run"] == CONF[
        "num_hidden_layers"] == 3
    assert "RESOURCE_EXHAUSTED" in cut["why"] or "refused" in cut["why"]
    assert CONF["architecture"] == "olmoe"
    assert CONF["deployment"]["chips"] == 1 and not CONF["deployment"]["mesh"]


def test_fields_map_the_published_keys_onto_the_programs():
    f = spec.transformer_fields(CONF)
    assert f == {
        "vocab_size": 50304, "d_model": 2048, "n_layers": 3, "n_heads": 16,
        "n_kv_heads": 16, "d_ff": 1024, "rope_theta": 10000.0,
        "rms_eps": 1e-05, "tie_embeddings": False, "moe_experts": 64,
        "moe_top_k": 8, "moe_norm_topk": False, "moe_aux_weight": 0.0,
        "qk_norm": True}
    # the auxiliary loss is in the training loss only where the published
    # forward would add it
    on = ARCH.fields(dict(CONF, output_router_logits=True))
    assert on["moe_aux_weight"] == 0.01
    cfg = spec.build_transformer_config(CONF, param_dtype="bfloat16")
    assert cfg.head_dim == CONF["head_dim"] == 128


def test_dense_configs_name_no_architecture_and_olmoe_names_its_own():
    """Every assertion of `test_every_config_names_an_architecture_file_
    with_the_interface`, with the architecture each config should have."""
    for entry in BENCH["configs"]:
        conf = spec.load_config(BENCH, entry["name"])
        want = "olmoe" if entry["name"] == "olmoe-1b-7b" else "dense_gqa"
        assert ("architecture" in conf) == (want == "olmoe")
        assert spec.architecture_name(conf) == want
        mod = spec.load_architecture(conf)
        assert os.path.basename(mod.__file__) == want + ".py"
        for fn in spec.ARCHITECTURE_INTERFACE:
            assert callable(getattr(mod, fn))
        assert mod is spec.load_architecture(conf)   # once per process
    assert callable(ARCH.fields) and callable(ARCH.reference_aux_loss)


def test_new_layer_metrics_are_appended_after_the_engines_thirteen():
    """Every assertion of `test_the_thirteen_are_appended_and_change_
    nothing_that_was_there` on PR 24's thirteen, found where they stand
    (in their order, straight before this PR's), and this PR's last."""
    names = [m["name"] for m in BENCH["per_layer"]]
    n = len(NEW_METRICS)
    assert names[-n:] == NEW_METRICS
    thirteen = BENCH["per_layer"][-n - 13:-n]
    assert {m["name"] for m in thirteen} == set(
        ENGINE_THIRTEEN[CHAT] + ENGINE_THIRTEEN[BATCH])
    for m in thirteen:
        chat = m["name"].endswith(".chat")
        assert m["workloads"] == [CHAT if chat else BATCH]
        assert m["moves"] == ("tpot_p50_ms" if chat
                              else "serve_tokens_per_s")
        f = spec.load_layer_metric(m["name"])
        assert ("moves_note" in f) == chat
        assert f["reader"] in ("engine_ratio", "out_field")
    for m in BENCH["per_layer"][-n:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == ("setup_s" if m["name"].startswith("chip_worker")
                              else "train_tokens_per_s")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["train_tokens_per_s"]["workloads"][-1] == CELL
    assert e2e["train_tokens_per_s"]["bound"] == 0.01


def test_loading_the_olmoe_architecture_imports_no_jax():
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark.harness import spec; "
            "b = spec.load_benchmark(); "
            "c = spec.load_config(b, 'olmoe-1b-7b'); "
            "a = spec.load_architecture(c); "
            "f = spec.transformer_fields(c); "
            "print(a.forward_flops_per_token(f, c, 4096), "
            "a.num_params(f, c), 'jax' in sys.modules)"
            % bench_paths.REPO)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True).stdout.split()
    assert out[2] == "False" and float(out[0]) > 6e8 and int(out[1]) > 1e9


# ---- required work, by hand --------------------------------------------------

def test_forward_flops_per_token_against_a_hand_count():
    f = spec.transformer_fields(CONF)
    attn_proj = 4 * 2048 * 2048                 # q, k, v, o
    router = 2048 * 64
    routed = 8 * 3 * 2048 * 1024                # 8 experts, 3 matrices
    causal = 2 * 2 * 2048 * (4096 + 1) / 2      # QK^T and PV, half
    per_layer = 2 * (attn_proj + router + routed) + causal
    head = 2 * 2048 * 50304
    assert 2 * routed == 100_663_296            # 100.7 MFLOP a token a layer
    assert per_layer == pytest.approx(151.3e6, rel=1e-3)
    assert head == pytest.approx(206.0e6, rel=1e-3)
    for layers in (3, 16):
        got = ARCH.forward_flops_per_token(dict(f, n_layers=layers), CONF,
                                           4096)
        assert got == layers * per_layer + head
    # the experts' share at the cell's depth, and the head's
    total = 3 * per_layer + head
    assert 3 * 2 * routed / total == pytest.approx(0.457, abs=0.005)
    assert head / total == pytest.approx(0.312, abs=0.005)
    # the dense count would take one expert's worth of eight
    dense = spec.load_architecture({}).forward_flops_per_token(f, CONF, 4096)
    assert dense < 0.6 * total


def test_num_params_against_the_published_sizes():
    f = dict(spec.transformer_fields(CONF), n_layers=16)
    per_layer = 4 * 2048 * 2048 + 2048 * 64 + 64 * 3 * 2048 * 1024 \
        + 2 * 2048 + 2 * 2048
    want = 2 * 50304 * 2048 + 16 * per_layer + 2048
    assert ARCH.num_params(f, CONF) == want
    assert want == pytest.approx(6.92e9, rel=2e-3)
    assert ARCH.active_params(f, CONF) == pytest.approx(1.28e9, rel=3e-3)
    run = ARCH.num_params(spec.transformer_fields(CONF), CONF)
    assert run == pytest.approx(1.465e9, rel=1e-3)      # the cell's 3 layers


# ---- the readers on a made-up trace ------------------------------------------

def _evidence(op_seconds, steps=4, **fields):
    f = dict(spec.transformer_fields(CONF), **fields)
    return {"trace": {"op_seconds": op_seconds, "busy_s": 2.0,
                      "window_s": 2.0},
            "out": {"trace_steps": steps}, "fields": f, "conf": CONF,
            "traffic": spec.load_traffic("train-4k"),
            "cell": spec.find_cell(BENCH, CELL),
            "peaks": spec.device_peaks("TPU v5 lite")}


def test_grouped_matmul_roofline_reads_a_made_up_trace():
    metric = spec.load_layer_metric("moe_grouped_matmul_roofline")
    read = spec.load_reader(metric)
    m = 4 * 4096 * 8
    flops = 3 * 3 * 2 * m * 2048 * 1024             # a layer and step
    nbytes = 3 * 3 * 2 * (64 * 2048 * 1024 + m * 2048 + m * 1024)
    assert flops / nbytes == pytest.approx(512, rel=0.01)   # over the ridge
    least = 3 * 4 * flops / 197e12                  # 3 layers, 4 steps
    ops = {"tpu_custom_call:ragged-dot-none": 0.2,
           "tpu_custom_call:ragged-dot-none.7": 0.3,
           "tpu_custom_call:ragged-dot-metadata.1": 0.1,
           "tpu_custom_call:closed_call.9": 5.0,    # attention: not matched
           "fusion.412": 5.0}
    assert read(_evidence(ops), metric) == pytest.approx(
        100 * least / 0.6, rel=1e-9)
    assert 0 < read(_evidence(ops), metric) < 100
    # nothing matched, no peaks, no steps, no experts: nothing to read
    assert read(_evidence({"fusion.1": 1.0}), metric) is None
    assert read(dict(_evidence(ops), peaks=None), metric) is None
    assert read(_evidence(ops, steps=0), metric) is None
    assert read(_evidence(ops, moe_experts=0), metric) is None
    # a peaks table under which the bytes would bound is an error, not a
    # number under the wrong name
    slow = dict(_evidence(ops), peaks={"bf16_flops_per_s": 197e12,
                                       "hbm_bytes_per_s": 1e9})
    with pytest.raises(ValueError, match="bound"):
        read(slow, metric)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_files_and_what_their_patterns_match(name):
    metric = spec.load_layer_metric(name)
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    assert metric["workloads"] == entry["workloads"] == [CELL]
    read = spec.load_reader(metric)
    # on a parent's evidence (no trace of such operations, no program
    # sizes) every reader returns nothing and does not raise
    assert read({"trace": {}, "out": {}, "fields": {}, "conf": {},
                 "traffic": {}, "cell": {"chips": 1}, "peaks": None},
                metric) is None
    if "op_pattern" not in metric:
        return
    rx = re.compile(metric["op_pattern"])
    attention = ["tpu_custom_call:closed_call.9",
                 "tpu_custom_call:checkpoint.20",
                 "tpu_custom_call:rematted_computation.10"]
    grouped = ["tpu_custom_call:ragged-dot-none",
               "tpu_custom_call:ragged-dot-none.11",
               "tpu_custom_call:ragged-dot-metadata.2"]
    mine, other = (grouped, attention) if "grouped_matmul" in name \
        else (attention, grouped)
    assert all(rx.search(k) for k in mine)
    assert not any(rx.search(k) for k in other)
    assert not rx.search("fusion.1") and not rx.search("copy.3")
    # the two families together are every Mosaic kernel of the step, as
    # the accepted flash_attention_* pattern would take them
    every = re.compile(spec.load_layer_metric(
        "flash_attention_step_share")["op_pattern"])
    assert all(every.search(k) for k in attention + grouped)


# ---- the cell at toy size ------------------------------------------------------

def _run(trace, seconds=2.0, seed=2 ** 31 + 27, **fields):
    cell = spec.find_cell(BENCH, CELL)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    return bench_paths.run_cell_with_info(
        RUN, BENCH, cell, args, platform="cpu",
        field_overrides=dict(TINY, **fields),
        traffic_overrides={"seq_len": 64, "rows": 2})


def test_cell_runs_end_to_end_at_toy_size_judged_by_olmoe(cpu_cluster):
    line, info = _run(trace=0)
    assert line["correct"] is True, line
    check = info["check"]
    assert check["reference"] == "olmoe" and check["ok"]
    assert check["logits"]["rel_rms_error"] < 2e-4
    assert check["loss_abs_diff"] < 1e-4
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_a_wrong_routing_rule_is_not_correct(cpu_cluster):
    """The program renormalising the top-k weights where the published
    config says not to: the reference does not, and the check says so."""
    line, info = _run(trace=0, moe_norm_topk=True)
    assert line["correct"] is False
    assert info["check"]["reference"] == "olmoe"
    assert info["check"]["logits"]["rel_rms_error"] > 2e-4


def test_traced_toy_run_reports_only_what_the_cpu_can(cpu_cluster):
    line, _ = _run(trace=1)
    # readers that need a device trace return nothing on the CPU
    assert set(line["metrics"]) == {"peak_hbm_gb.olmoe",
                                    "chip_worker_ready_s.olmoe"}
    assert line["correct"] is False and not line["device"]["busy_s"]
