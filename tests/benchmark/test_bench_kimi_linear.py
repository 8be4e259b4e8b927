"""The Kimi-Linear configuration, its architecture file, its cell and its
per-layer metrics (PR 36): the config file against the catalog row, the
required work against hand counts at the published widths, the cell at toy
size through the harness's own functions on the CPU (judged `correct`, and
NOT when the program computes one of the row's rules otherwise), the two
new readers on made-up evidence, and the proof that the cell came as new
files and list entries: every benchmark file of the parent commit is what
it was.

Everything here is found BY NAME: no count of configurations, cells or
metrics, no position in a list, no last place is pinned, so that the next
cell's addition fails none of these."""

import argparse
import hashlib
import json
import os
import subprocess
import sys

import pytest

import bench_paths
import ray_tpu
from benchmark.harness import spec

BENCH = spec.load_benchmark()
RUN = bench_paths.load_run_module()
NAME = "kimi-linear-48b-a3b"
CELL = "kimi-linear-48b-a3b.train-16k-2rows"
TRAFFIC = "train-16k-2rows"
CONF = spec.load_config(BENCH, NAME)
ARCH = spec.load_architecture(CONF)
# the catalog row's `config` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
CUT = {"num_hidden_layers", "num_experts", "vocab_size"}
TINY = dict(vocab_size=96, d_model=32, n_layers=5, n_heads=4, n_kv_heads=4,
            d_ff=24, nope_head_dim=8, rope_head_dim=4, v_head_dim=8,
            kv_lora_rank=8, kda_heads=4, kda_head_dim=8, kda_gate_rank=6,
            moe_experts=32, moe_held_experts=8, moe_first_expert=8,
            moe_top_k=8, moe_shared_d_ff=24, moe_dense_d_ff=40,
            dtype="float32")
JOINED = ["chip_worker_ready_s", "train_step_device_ms", "train_mfu",
          "peak_hbm_gb.train", "moe_dispatch_step_share",
          "moe_load_max_over_mean", "moe_grouped_matmul_step_share.olmoe"]
OWN = ["kda_scan_step_share", "kda_scan_roofline",
       "kda_projection_step_share", "nope_mla_attention_step_share",
       "nope_mla_attention_roofline", "moe_held_assignment_share.1of32"]
NOT_JOINED = ["flash_attention_step_share", "flash_attention_roofline",
              "mla_flash_attention_roofline", "mtp_step_share",
              "moe_held_assignment_share", "moe_grouped_matmul_roofline",
              # the GLM cell's accepted test holds these two to that cell
              # alone, and this PR may edit no test the benchmark had
              "mla_projection_step_share",
              # its reader raises when the step's own held share falls so
              # low that the bound turns to memory (under 0.011 here), and
              # a seeded router's share swings by the seed (PERF.md)
              "moe_held_grouped_matmul_roofline"]
# what this PR added under the benchmark's paths: files, nothing else
ADDED = {
    "benchmark/architectures/kimi_linear.py",
    "benchmark/configs/kimi-linear-48b-a3b.json",
    "benchmark/traffic/train-16k-2rows.json",
    "benchmark/readers/kda_scan_roofline.py",
    "benchmark/readers/attention_roofline_by_kind.py",
    "tests/benchmark/test_bench_kimi_linear.py",
} | {f"benchmark/layer_metrics/{m}.json" for m in OWN}
# sha256 (first 16 hex digits) of every file under the benchmark's paths
# AT THE PARENT COMMIT (a79edfc), and of its BENCHMARK.json as
# `json.dumps(..., sort_keys=True)`: its 4 configurations, 6 cells, 4
# end-to-end and 48 per-layer metrics
PARENT_BENCHMARK = "814970941da5c80b50b7cbb48e417f07a2c26c6c746d3684a6afbe2bf48efc05"
PARENT_COUNTS = {"configs": 4, "workloads": 6, "end_to_end": 4,
                 "per_layer": 48}
PARENT_FILES = json.loads(r"""{"benchmark/README.md": "b908979af41bb3ea", "benchmark/__init__.py":
"e3b0c44298fc1c14", "benchmark/architectures/dense_gqa.py":
"53e288c623944cfb", "benchmark/architectures/glm4_moe_lite.py":
"7ad392cd851e61eb", "benchmark/architectures/olmoe.py": "c5e33fe0d813ca97",
"benchmark/configs/glm-4.7-flash.json": "8c8d89ac4fa4feeb",
"benchmark/configs/internlm2-1.8b.json": "ce8c8deb40365cf7",
"benchmark/configs/mistral-7b-v0.3.json": "16d74a3fe947befa",
"benchmark/configs/olmoe-1b-7b.json": "45f9b3f1e3d82a5e",
"benchmark/fixtures/train_tiny_v5e.facts.json": "f98aef302b6b29b1",
"benchmark/fixtures/train_tiny_v5e.xplane.pb.gz": "970d604cd8a3a552",
"benchmark/harness/__init__.py": "e3b0c44298fc1c14",
"benchmark/harness/flops.py": "0d524519114c7bb6",
"benchmark/harness/probes.py": "0f68edd762504afa",
"benchmark/harness/reference.py": "e26b7316061a8daa",
"benchmark/harness/serve_cell.py": "f417f73f7aca48ea",
"benchmark/harness/spec.py": "0e1b1e8965222e2f",
"benchmark/harness/stats.py": "f4c456201ca11bde",
"benchmark/harness/traffic.py": "b05f83fa3ccda644",
"benchmark/harness/train_cell.py": "bc6156348bbaaeea",
"benchmark/harness/xplane.py": "826b4017797bc54b",
"benchmark/layer_metrics/batch.decode_attention_roofline.json":
"4b9a4e86af5cb9c3",
"benchmark/layer_metrics/chat.decode_attention_roofline.json":
"677f2526298a4457", "benchmark/layer_metrics/chat_ttft_mean_ms.json":
"365442428959a11b", "benchmark/layer_metrics/chip_worker_ready_s.json":
"bdf42d432d9915bb",
"benchmark/layer_metrics/chunks_ahead_at_admit.chat.json":
"9baa5348bb6c9874", "benchmark/layer_metrics/chunks_per_fetch.chat.json":
"c61ad05b1bfdaa62",
"benchmark/layer_metrics/collective_exposed_ms_per_step.json":
"b062d708b9dbd946",
"benchmark/layer_metrics/decode_kv_read_share.batch.json":
"71a727f1294ba57f",
"benchmark/layer_metrics/decode_kv_read_share.chat.json":
"c09629a28bdc4d59", "benchmark/layer_metrics/decode_occupancy.batch.json":
"314ad4ef650b63a6", "benchmark/layer_metrics/decode_occupancy.chat.json":
"cdd14a55edba12f8", "benchmark/layer_metrics/decode_substep_ms.batch.json":
"b30573f30c657b19", "benchmark/layer_metrics/decode_substep_ms.chat.json":
"532336848ebdbf5f",
"benchmark/layer_metrics/engine_first_token_ms.chat.json":
"7305b0521af895b6",
"benchmark/layer_metrics/engine_queue_wait_ms.chat.json":
"7f9575cc935d3057", "benchmark/layer_metrics/engine_stall_s.batch.json":
"92068f66919cf46d", "benchmark/layer_metrics/engine_stall_s.chat.json":
"1039cdbf872bc42e",
"benchmark/layer_metrics/fetch_lock_wait_ms_per_fetch.chat.json":
"2dd9efee4fb9403b",
"benchmark/layer_metrics/fetch_wait_ms_per_fetch.batch.json":
"afc27322f43edd9d",
"benchmark/layer_metrics/fetch_wait_ms_per_fetch.chat.json":
"622c804cdbb18f3f", "benchmark/layer_metrics/flash_attention_roofline.json":
"a8a679599ab06404",
"benchmark/layer_metrics/flash_attention_step_share.json":
"4ec1a22cc7d95db1", "benchmark/layer_metrics/generator_late_p99_ms.json":
"67d3ad227c5ca7a2", "benchmark/layer_metrics/handle_rtt_p50_ms.json":
"641829362e87ef48",
"benchmark/layer_metrics/mla_flash_attention_roofline.json":
"b1d20ba5aaa85cb6",
"benchmark/layer_metrics/mla_projection_step_share.json":
"659f90505a9e651c", "benchmark/layer_metrics/moe_dispatch_step_share.json":
"76f13db4a8f0732c",
"benchmark/layer_metrics/moe_grouped_matmul_roofline.json":
"7259be3610e46334",
"benchmark/layer_metrics/moe_grouped_matmul_step_share.olmoe.json":
"19a74b3e84918a4b",
"benchmark/layer_metrics/moe_held_assignment_share.json":
"18c70a1d8d8e6ee4",
"benchmark/layer_metrics/moe_held_grouped_matmul_roofline.json":
"368a7a1a01611f59", "benchmark/layer_metrics/moe_load_max_over_mean.json":
"76e283274796efa7", "benchmark/layer_metrics/mtp_step_share.json":
"e90317d30f867fb7", "benchmark/layer_metrics/peak_hbm_gb.batch.json":
"fbfcf3c4107fc5a6", "benchmark/layer_metrics/peak_hbm_gb.chat.json":
"5b1b70b06aedcc2f", "benchmark/layer_metrics/peak_hbm_gb.train.json":
"616e0bf170cb870a", "benchmark/layer_metrics/prefill_group_size.chat.json":
"295e5cc56cb39ef1", "benchmark/layer_metrics/prefill_ms_per_ktok.json":
"4da07fc97f7737f4",
"benchmark/layer_metrics/prefill_useful_share.batch.json":
"4579985389c9cd3c",
"benchmark/layer_metrics/prefill_useful_share.chat.json":
"f17f158476c1f086",
"benchmark/layer_metrics/sched_dispatch_share.batch.json":
"139c3fab5734be2c",
"benchmark/layer_metrics/sched_dispatch_share.chat.json":
"219287af0fbae70b",
"benchmark/layer_metrics/sched_park_cap_share.chat.json":
"8797d9dd05be8668", "benchmark/layer_metrics/train_mfu.json":
"dade73ff2bb029fb", "benchmark/layer_metrics/train_step_device_ms.json":
"bf9bf025273f58c3", "benchmark/layer_metrics/ttft_p50_ms.json":
"58f5558bd1b08773", "benchmark/layer_metrics/ttft_p90_ms.json":
"a6b53f27f8afb3d7", "benchmark/layer_metrics/ttft_p95_ms.json":
"67e7789977201c67", "benchmark/peaks.json": "87ff8d69ce29113d",
"benchmark/readers/attention_roofline_with_mtp.py": "2b04a3a153c29d99",
"benchmark/readers/collective_exposed.py": "5120b97e1e219bb7",
"benchmark/readers/counter_bytes_roofline.py": "4180a8f9d6e99446",
"benchmark/readers/decode_occupancy.py": "d64553f9c6ea2ab7",
"benchmark/readers/engine_ratio.py": "f6ae5d53d9fe83f0",
"benchmark/readers/grouped_matmul_roofline.py": "93fee09910c0b5a8",
"benchmark/readers/held_grouped_matmul_roofline.py": "a700141ffaaa8161",
"benchmark/readers/kernel_roofline.py": "817cff4019a37267",
"benchmark/readers/kernel_share.py": "15cfabc1d672d0b8",
"benchmark/readers/module_time.py": "24c05ad8d0a810f3",
"benchmark/readers/out_field.py": "3d188d5ddc59d8d0",
"benchmark/readers/program_hbm.py": "a0bc2a006f64bd9c",
"benchmark/readers/sample_mean.py": "a140f87b01391715",
"benchmark/readers/sample_percentile.py": "1be68c630da41b66",
"benchmark/readers/scope_share.py": "b95c81d8057c5845",
"benchmark/readers/train_mfu.py": "39ba8cc79133b82a",
"benchmark/readers/train_step_device.py": "c9d6c43386ac6fcc",
"benchmark/record_fixture.py": "f448bf67c2c4e0ae", "benchmark/rehearse.py":
"68c5f445df3cce82", "benchmark/run.py": "9833d1b085faa66f",
"benchmark/sweep.py": "57ad9ad60c5e657f", "benchmark/term_limits.py":
"b61a70a1272fdd94", "benchmark/traffic/batch-closed.json":
"35a0010f7a4287fd", "benchmark/traffic/chat-steady.json":
"f5f809afba5aa2f1", "benchmark/traffic/train-4k-8rows.json":
"ec4bade0d377e0b6", "benchmark/traffic/train-4k.json": "f7415070e5f66142",
"benchmark/traffic/train-fsdp2tp2.json": "aa8aae94dcaebe87",
"tests/benchmark/bench_paths.py": "7ae2ca7969fcfdd9",
"tests/benchmark/test_bench_additions.py": "1054b319070ef7c9",
"tests/benchmark/test_bench_cells_cpu.py": "82196c88f3af6e01",
"tests/benchmark/test_bench_engine_spans.py": "c2a0ec095d21f6a8",
"tests/benchmark/test_bench_flops.py": "ee8075a6223acfb3",
"tests/benchmark/test_bench_glm4_moe_lite.py": "273badb51990d066",
"tests/benchmark/test_bench_olmoe.py": "d0ee1b82f33fdab3",
"tests/benchmark/test_bench_reference.py": "45e19703e189262e",
"tests/benchmark/test_bench_run_cpu.py": "9b418b66a5c385c8",
"tests/benchmark/test_bench_serve_seam.py": "11230a634ae70bf9",
"tests/benchmark/test_bench_spec.py": "a7e9fc3b793a7562",
"tests/benchmark/test_bench_stats.py": "684b2859b6ee7f13",
"tests/benchmark/test_bench_traffic.py": "4a60585fbe008bf0",
"tests/benchmark/test_bench_xplane.py": "cfec4cca08538b4f"}""")


@pytest.fixture(scope="module")
def cpu_cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


# ---- added, not edited ---------------------------------------------------------

def _sha(path):
    with open(os.path.join(bench_paths.REPO, path), "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:16]


def test_no_file_the_benchmark_had_is_edited():
    """Every file the parent commit had under `paths` is byte for byte what
    it was; what this PR brought there is new files."""
    assert not ADDED & set(PARENT_FILES)
    for path, was in PARENT_FILES.items():
        assert _sha(path) == was, path
    for path in ADDED:
        assert os.path.exists(os.path.join(bench_paths.REPO, path)), path


def test_benchmark_json_gained_entries_and_list_members_only():
    """`BENCHMARK.json` as the parent had it is still there: the entries
    it had, in their places, each unchanged but for cells appended to
    `workloads` lists. (Whatever later PRs appended is cut off the same
    way, so this holds after them.)"""
    was_cells = None
    view = {k: v for k, v in BENCH.items() if k not in PARENT_COUNTS}
    for group, n in PARENT_COUNTS.items():
        view[group] = [dict(e) for e in BENCH[group][:n]]
        if group == "workloads":
            was_cells = {c["name"] for c in view[group]}
    for group in ("end_to_end", "per_layer"):
        for entry in view[group]:
            if "workloads" in entry:
                kept = [c for c in entry["workloads"] if c in was_cells]
                # appended at the END of the list, nothing moved
                assert entry["workloads"][:len(kept)] == kept, entry["name"]
                entry["workloads"] = kept
    assert hashlib.sha256(json.dumps(view, sort_keys=True).encode()
                          ).hexdigest() == PARENT_BENCHMARK
    # and what came: one configuration, one cell, this PR's metrics
    assert NAME not in {c["name"] for c in view["configs"]}
    assert CELL not in was_cells
    assert not set(OWN) & {m["name"] for m in view["per_layer"]}
    assert BENCH["run_seconds"] == 51


# ---- the configuration -------------------------------------------------------

def test_config_file_holds_every_published_key_and_cuts_exactly_three():
    for key, value in PUBLISHED.items():
        assert key in CONF, key
        if key not in CUT:
            assert CONF[key] == value, key
    assert set(CONF["reduced"]) == CUT
    for key, cut in CONF["reduced"].items():
        assert cut["published"] == PUBLISHED[key] != cut["run"] == CONF[key]
        assert cut["why"]
    entry = {c["name"]: c for c in BENCH["configs"]}[NAME]
    assert sorted(entry["reduced"]) == sorted(CUT)
    assert entry["source"] == CONF["source"] and CONF["source"].endswith(
        "moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert CONF["architecture"] == "kimi_linear"
    # the floors of the guide's section 4: the dense layer and whole
    # periods of four after it, 8 routed experts, an eighth of the rows
    layers = CONF["num_hidden_layers"]
    assert layers >= 1 + 4 and (layers - 1) % 4 == 0
    assert CONF["num_experts"] == 8
    assert CONF["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the layers kept are 1 .. 1 + 4k of the published numbering: the
    # pattern and the 3 : 1 ratio are the model's
    kinds = ARCH.layer_kinds(CONF, layers)
    assert kinds[:5] == ["kda", "kda", "kda", "attention", "kda"]
    assert kinds[1:].count("kda") == 3 * kinds[1:].count("attention")
    dep = CONF["deployment"]
    assert dep["chips_a_layer"] == 32 and dep["router_experts"] == 256
    assert dep["first_expert"] == 0 and dep["vocab_rows"] == [0, 20480]
    assert CONF["objective"] == {"loss": 1.0}
    for key in ("kda_biases", "l2norm_eps", "A_log_and_dt_bias",
                "router_bias", "initializer", "weights", "head_dim"):
        assert CONF["assumed"][key], key
    assert "rehears" in CONF["reduced"]["num_hidden_layers"]["why"]


def test_fields_map_the_published_keys_onto_the_programs():
    f = spec.transformer_fields(CONF)
    # the published head_dim (72 = hidden / heads) is carried to no field
    assert "head_dim" not in f and CONF["head_dim"] * 32 == 2304
    assert (f["nope_head_dim"], f["rope_head_dim"], f["v_head_dim"]) == (
        128, 64, 128)
    assert (f["q_lora_rank"], f["kv_lora_rank"], f["n_heads"]) == (0, 512,
                                                                   32)
    assert f["use_rope"] is False
    assert f["mixer_period"] == ("kda", "kda", "kda", "attention")
    assert (f["kda_heads"], f["kda_head_dim"], f["kda_conv"],
            f["kda_gate_rank"]) == (32, 128, 4, 128)
    assert (f["moe_experts"], f["moe_held_experts"], f["moe_top_k"]) == (
        256, 8, 8)
    assert (f["d_ff"], f["moe_shared_d_ff"], f["moe_dense_d_ff"]) == (
        1024, 1024, 9216)
    assert f["moe_scoring"] == "sigmoid" and f["moe_select_bias"]
    assert f["moe_norm_topk"] and f["moe_route_scale"] == 2.446
    assert f["moe_aux_weight"] == 0.0 and f["moe_dense_layers"] == 1
    assert f["n_layers"] == CONF["num_hidden_layers"]
    assert f["vocab_size"] == 20480 and f["d_model"] == 2304
    cfg = spec.build_transformer_config(CONF)
    assert (cfg.head_dim, cfg.v_head_dim) == (192, 128)
    assert cfg.num_params == ARCH.num_params(f, CONF)
    for key, value, why in (("num_expert_group", 8, "group-limited"),
                            ("mla_use_nope", False, "without positions"),
                            ("q_lora_rank", 768, "without positions"),
                            ("moe_router_activation_func", "softmax",
                             "sigmoid"),
                            ("num_nextn_predict_layers", 1, "prediction")):
        with pytest.raises(ValueError, match=why):
            ARCH.fields(dict(CONF, **{key: value}))
    lin = dict(CONF["linear_attn_config"], kda_layers=[1, 2, 3, 4])
    with pytest.raises(ValueError, match="kda_layers and full_attn_layers"):
        ARCH.fields(dict(CONF, linear_attn_config=lin))


def test_the_cell_and_the_metrics_it_reports_are_found_by_name():
    cell = spec.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, TRAFFIC, 1)
    t, was = spec.load_traffic(TRAFFIC), spec.load_traffic("train-4k-8rows")
    assert (t["seq_len"], t["rows"]) == (16384, 2)
    assert t["rows"] * t["seq_len"] == was["rows"] * was["seq_len"]
    assert t["learning_rate"] == 1e-5 and "1e-5" in t["why"]
    assert t["check"] == {"rows": 1} and t["mesh"] is None
    differs = ("rows", "seq_len", "why", "name", "weights_seed",
               "weights_seed_why")   # one learning rate since PR 56
    assert "0.03125" in t["weights_seed_why"]
    assert {k: v for k, v in t.items() if k not in differs} \
        == {k: v for k, v in was.items() if k not in differs}
    e2e = {m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    reported = {m["name"]: m for m in spec.metrics_for(BENCH, CELL,
                                                       "per_layer")}
    assert set(reported) == set(JOINED + OWN)
    assert not set(NOT_JOINED) & set(reported)
    for name in OWN:
        entry, metric = reported[name], spec.load_layer_metric(name)
        assert entry["workloads"] == [CELL] and "workloads" not in metric
        assert entry["moves"] == metric["moves"] == "train_tokens_per_s"
        for key in ("unit", "better", "source", "layer"):
            assert entry[key] == metric[key], (name, key)
        assert callable(spec.load_reader(metric))
    assert spec.load_layer_metric("kda_scan_step_share")[
        "scope_pattern"] == "kda\\.scan"
    held = spec.load_layer_metric("moe_held_assignment_share.1of32")
    assert held["field"] == "step_metrics.moe_held_share"
    assert held["balance"] == 8 / 256
    scan = spec.load_layer_metric("kda_scan_roofline")
    assert (scan["unit"], scan["bound"]) == ("%", "memory")
    attn = spec.load_layer_metric("nope_mla_attention_roofline")
    assert (attn["unit"], attn["bound"]) == ("%", "compute")
    # the attention pattern leaves out the grouped matmuls and a scan
    # kernel's name, should one be written
    import re
    rx = re.compile(attn["op_pattern"])
    assert rx.search("tpu_custom_call:checkpoint.3")
    assert not rx.search("tpu_custom_call:ragged-dot-none.2")
    assert not rx.search("tpu_custom_call:kda_scan.1")
    assert attn["op_pattern"] == spec.load_layer_metric(
        "nope_mla_attention_step_share")["op_pattern"]


def test_loading_the_architecture_imports_no_jax_and_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark.harness import spec; "
            "b = spec.load_benchmark(); "
            "c = spec.load_config(b, %r); "
            "a = spec.load_architecture(c); "
            "f = spec.transformer_fields(c); "
            "print(a.forward_flops_per_token(f, c, 16384), "
            "a.num_params(f, c), 'jax' in sys.modules, "
            "any(m.startswith('ray_tpu') for m in sys.modules))"
            % (bench_paths.REPO, NAME))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True).stdout.split()
    assert out[2] == out[3] == "False"
    assert float(out[0]) > 8e8 and int(out[1]) > 6e8
    with open(ARCH.__file__) as f:
        assert "ray_tpu" not in f.read().replace("`ray_tpu/models/`", "")


# ---- required work, by hand --------------------------------------------------

def test_forward_flops_per_token_against_a_hand_count():
    f = dict(spec.transformer_fields(CONF), n_layers=9)
    kda = 3 * 2304 * 4096 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096) \
        + 2304 * 32 + 3 * 4 * 4096
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
    assert kda == pytest.approx(39.5e6, rel=2e-3)
    assert mla == pytest.approx(29.1e6, rel=2e-3)
    scan = 7 * 128 * 128 * 32                   # the recurrence, a token
    causal = 32 * 2 * (192 + 128) * (16384 + 1) / 2
    routed = 8 * 3 * 2304 * 1024 * 8 / 256      # EXPECTED on this chip
    ffn = 2 * (2304 * 256 + 3 * 2304 * 1024 + routed)
    dense_ffn = 2 * 3 * 2304 * 9216
    head = 2 * 2304 * 20480
    assert causal == pytest.approx(168e6, rel=2e-3)
    assert 2 * mla + ffn == pytest.approx(77e6, rel=5e-3)
    assert 2 * kda + scan + ffn == pytest.approx(101.6e6, rel=2e-3)
    want = (2 * kda + scan + dense_ffn) + 6 * (2 * kda + scan + ffn) \
        + 2 * (2 * mla + causal + ffn) + head
    got = ARCH.forward_flops_per_token(f, CONF, 16384)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(1.40e9, rel=5e-3)       # the issue's 1.41
    # the KDA layers about 44%, latent attention's square 24%
    assert 6 * (2 * kda + scan + ffn) / got == pytest.approx(0.44, abs=0.01)
    assert 2 * causal / got == pytest.approx(0.24, abs=0.01)
    # holding all 256 would be 32 times the routed work and nothing else
    whole = ARCH.layer_flops_per_token(dict(f, moe_held_experts=256), 16384,
                                       "kda", True)
    assert whole - (2 * kda + scan + ffn) == pytest.approx(2 * 31 * routed)


def test_num_params_is_what_this_chip_holds():
    f = dict(spec.transformer_fields(CONF), n_layers=9)
    kda = 39_510_016 + 32 + 4096 + 128           # + A_log, dt_bias, norm
    mla = 29_114_368 + 512
    expert_ffn = 2304 * 256 + 256 + 9 * 3 * 2304 * 1024
    assert kda + 2 * 2304 + expert_ffn == pytest.approx(103.8e6, rel=1e-3)
    assert mla + 2 * 2304 + expert_ffn == pytest.approx(93.4e6, rel=1e-3)
    dense = kda + 2 * 2304 + 3 * 2304 * 9216
    assert dense == pytest.approx(103.2e6, rel=1e-3)
    slices = 2 * 20480 * 2304
    want = slices + dense + 6 * (kda + 2 * 2304 + expert_ffn) \
        + 2 * (mla + 2 * 2304 + expert_ffn) + 2304
    assert ARCH.num_params(f, CONF) == want
    assert want == pytest.approx(1007e6, rel=1e-3)      # the issue's 1 + 8
    assert ARCH.num_params(dict(f, n_layers=5), CONF) == pytest.approx(
        602e6, rel=1e-3)                                # ... and 1 + 4


def test_the_scans_required_work_is_the_recurrences():
    fwd = ARCH.kda_scan_cost(2, 32, 16384, 128, 128)
    bwd = ARCH.kda_scan_cost(2, 32, 16384, 128, 128, backward=True)
    rows = 2 * 16384 * 32
    assert fwd["flops"] == 7 * 128 * 128 * rows
    assert bwd["flops"] == 2 * fwd["flops"]
    # q, k, v, o in bf16, g in float32 a channel, beta a head
    assert fwd["bytes"] == rows * (4 * 128 * 2 + 4 * 128 + 4)
    assert bwd["bytes"] == rows * (2 * (3 * 128 * 2 + 4 * 128 + 4)
                                   + 128 * 2)
    total = (fwd["flops"] + bwd["flops"]) / (fwd["bytes"] + bwd["bytes"])
    assert total < 197e12 / 819e9                # under the ridge: memory


# ---- the readers on made-up evidence -----------------------------------------

def _evidence(op_seconds, steps=4, scopes=None, **fields):
    f = dict(spec.transformer_fields(CONF), **{"n_layers": 9, **fields})
    return {"trace": {"op_seconds": op_seconds, "busy_s": 2.0,
                      "window_s": 2.0},
            "out": {"trace_steps": steps, "op_scopes": scopes or {},
                    "step_metrics": {"moe_held_share": 0.03125}},
            "fields": f, "conf": CONF,
            "traffic": spec.load_traffic(TRAFFIC),
            "cell": spec.find_cell(BENCH, CELL),
            "peaks": spec.device_peaks("TPU v5 lite")}


SCOPES = {
    "fusion.1": "jit(step)/jvp(kda.scan)/while/body/checkpoint/dot_general",
    "fusion.2": "jit(step)/transpose(jvp(kda.scan))/while/body/exp",
    "fusion.3": "jit(step)/jvp(kda.proj)/dot_general",
    "fusion.4": "jit(step)/transpose(jvp(kda.gate))/softplus",
    "fusion.5": "jit(step)/jvp(mla.kv)/dot_general",
    "fusion.6": "jit(step)/moe.shared/dot_general"}


def test_scan_roofline_counts_the_kda_layers_and_names_its_bound():
    metric = spec.load_layer_metric("kda_scan_roofline")
    read = spec.load_reader(metric)
    ops = {"fusion.1": 0.5, "fusion.2": 1.0, "fusion.3": 7.0,
           "tpu_custom_call:checkpoint.3": 9.0}
    rows = 2 * 16384 * 32
    nbytes = rows * ((4 * 128 * 2 + 516) + 2 * (3 * 128 * 2 + 516) + 256)
    least = 4 * 7 * nbytes / 819e9        # 7 KDA layers of 9, 4 steps
    assert read(_evidence(ops, scopes=SCOPES), metric) == pytest.approx(
        100 * least / 1.5, rel=1e-9)
    # 1 + 4 layers hold 4 KDA layers
    assert read(_evidence(ops, scopes=SCOPES, n_layers=5), metric) \
        == pytest.approx(100 * least * 4 / 7 / 1.5, rel=1e-9)
    # nothing under the scope (the parent's program), no scopes, no peaks,
    # no steps: nothing to read, and no error
    assert read(_evidence({"fusion.3": 1.0}, scopes=SCOPES), metric) is None
    assert read(_evidence(ops), metric) is None
    assert read(dict(_evidence(ops, scopes=SCOPES), peaks=None),
                metric) is None
    assert read(_evidence(ops, steps=0, scopes=SCOPES), metric) is None
    # an architecture without the two functions gives nothing either
    glm = spec.load_config(BENCH, "glm-4.7-flash")
    other = dict(_evidence(ops, scopes=SCOPES), conf=glm,
                 fields=spec.transformer_fields(glm))
    assert read(other, metric) is None
    fast = dict(_evidence(ops, scopes=SCOPES),
                peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 819e9})
    with pytest.raises(ValueError, match="bound"):
        read(fast, metric)


def test_attention_roofline_counts_the_full_attention_layers_alone():
    from benchmark.harness import flops

    metric = spec.load_layer_metric("nope_mla_attention_roofline")
    read = spec.load_reader(metric)
    call = sum(flops.flash_attention_cost(
        2, 32, 16384, 16384, 192, causal=True, backward=b,
        v_head_dim=128)["flops"] for b in (False, True))
    # forward QK^T + PV, backward 3 x QK^T-sized + 2 x PV-sized products
    assert call == pytest.approx(
        (4 * 192 + 3 * 128) * 2 * 16384 * 16384 / 2 * 64, rel=1e-12)
    ops = {"tpu_custom_call:checkpoint.10": 1.5,
           "tpu_custom_call:closed_call.3": 0.5,
           "tpu_custom_call:ragged-dot-none.3": 9.0,    # not attention
           "tpu_custom_call:kda_scan.2": 9.0,           # nor a scan kernel
           "fusion.1": 9.0}
    least = 4 * 2 * call / 197e12         # 2 full-attention layers of 9
    assert read(_evidence(ops), metric) == pytest.approx(
        100 * least / 2.0, rel=1e-9)
    assert read(_evidence(ops, n_layers=5), metric) == pytest.approx(
        100 * least / 2 / 2.0, rel=1e-9)
    # `kernel_roofline` would count all 9 layers, at hidden / heads = 72
    plain = spec.load_reader({"reader": "kernel_roofline"})(
        _evidence(ops), dict(metric, op_pattern="^tpu_custom_call:check"))
    assert plain != pytest.approx(read(_evidence(ops), metric))
    assert read(_evidence({"fusion.1": 1.0}), metric) is None
    assert read(dict(_evidence(ops), peaks=None), metric) is None
    assert read(_evidence(ops, steps=0), metric) is None
    glm = spec.load_config(BENCH, "glm-4.7-flash")
    assert read(dict(_evidence(ops), conf=glm), metric) is None
    slow = dict(_evidence(ops), peaks={"bf16_flops_per_s": 197e12,
                                       "hbm_bytes_per_s": 1e9})
    with pytest.raises(ValueError, match="bound"):
        read(slow, metric)


def test_the_scope_and_counter_metrics_read_made_up_evidence():
    ev = _evidence({"fusion.1": 0.3, "fusion.2": 0.2, "fusion.3": 0.5,
                    "fusion.4": 0.1, "fusion.5": 0.4, "fusion.6": 0.3,
                    "tpu_custom_call:checkpoint.7": 0.2}, scopes=SCOPES)

    def value(name):
        m = spec.load_layer_metric(name)
        return spec.load_reader(m)(ev, m)
    assert value("kda_scan_step_share") == pytest.approx(25.0)
    assert value("kda_projection_step_share") == pytest.approx(30.0)
    assert value("nope_mla_attention_step_share") == pytest.approx(10.0)
    assert value("moe_held_assignment_share.1of32") == 0.03125
    ev["out"]["op_scopes"] = {}
    assert value("kda_scan_step_share") is None


# ---- the cell at toy size ------------------------------------------------------

def _run(trace, seconds=2.0, seed=2 ** 31 + 36, **fields):
    cell = spec.find_cell(BENCH, CELL)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    return bench_paths.run_cell_with_info(
        RUN, BENCH, cell, args, platform="cpu",
        field_overrides=dict(TINY, **fields),
        traffic_overrides={"seq_len": 72, "rows": 2})


def test_cell_runs_end_to_end_at_toy_size_judged_by_its_reference(
        cpu_cluster):
    line, info = _run(trace=0)
    assert line["correct"] is True, line
    check = info["check"]
    assert check["reference"] == "kimi_linear" and check["ok"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    terms = check["objective"]["terms"]
    assert sorted(terms) == ["loss"]
    assert terms["loss"]["ok"] and terms["loss"]["abs_diff"] <= 1e-4
    assert check["objective"]["weighted_sum"]["ok"]
    assert check["objective"]["weighted_sum"]["weights"] == {"loss": 1.0}
    assert all(v <= lim for v, lim in line["compared"].values())
    counters = info["step_metrics"]
    assert {"loss", "moe_held_share", "moe_load_max_over_mean",
            "grad_norm"} <= set(counters)
    assert 0.0 < counters["moe_held_share"] < 1.0


def test_a_wrong_rule_is_not_correct_through_the_whole_path(cpu_cluster):
    line, info = _run(trace=0, use_rope=True)
    assert line["correct"] is False
    assert info["check"]["reference"] == "kimi_linear"
    assert info["check"]["logits"]["rel_rms_error"] > 2e-4


def test_traced_toy_run_reports_only_what_the_cpu_can(cpu_cluster):
    line, _ = _run(trace=1)
    assert {"chip_worker_ready_s", "moe_held_assignment_share.1of32",
            "moe_load_max_over_mean"} <= set(line["metrics"])
    for name in ("kda_scan_roofline", "kda_scan_step_share",
                 "nope_mla_attention_roofline", "train_mfu"):
        assert name not in line["metrics"]       # no device trace here
    assert set(line["metrics"]) <= set(JOINED + OWN)
    assert line["correct"] is False              # no operation on a TPU


def _check(seed=36, **fields):
    """`train_cell.check_against_reference` in this process, at toy size,
    on weights from the program's initialiser."""
    import jax

    from benchmark.harness import train_cell
    from ray_tpu.models.transformer import init_params

    over = dict(TINY, **fields)
    cfg = spec.build_transformer_config(CONF, max_seq_len=72, **over)
    params = init_params(jax.random.key(seed), cfg)
    return train_cell.check_against_reference(
        params, cfg, dict(spec.transformer_fields(CONF), **over), CONF,
        ARCH, None, seed, 1, 72)


@pytest.mark.parametrize("rule,fields", [
    ("the key vector rotated", {"use_rope": True}),
    ("softmax for sigmoid", {"moe_scoring": "softmax"}),
    ("no renormalisation", {"moe_norm_topk": False}),
    ("no scaling factor", {"moe_route_scale": 1.0}),
])
def test_a_rule_computed_otherwise_is_not_correct(rule, fields):
    """The harness's own comparison (`check_against_reference`, what a
    run's `correct` rests on) in this process, on a program configured to
    another rule than the published one."""
    assert _check()["ok"]
    check = _check(**fields)
    assert not check["ok"], rule
    assert check["logits"]["rel_rms_error"] > 2e-4


def test_a_scalar_decay_a_head_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    real = kda.kda_scan
    monkeypatch.setattr(kda, "kda_scan", lambda q, k, v, g, beta, **kw: real(
        q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta,
        **kw))
    bad = _check(seed=37)
    assert not bad["ok"] and bad["logits"]["rel_rms_error"] > 2e-4
