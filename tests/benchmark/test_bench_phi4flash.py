"""The `phi-4-mini-flash-reasoning` configuration and its serve cell as the
benchmark holds them: found by name, the published widths uncut and
`reduced` empty, nothing the benchmark had edited, the cell a member of the
lists it joined and of none that an accepted test pins to one cell, its new
metrics with their files and readers, its architecture module's counts
equal to what the program's initialiser makes, and the cell through the
harness's own functions at toy size on the CPU (the check against the plain
reference included). Membership, never equality with a list another PR may
join."""

import argparse
import hashlib
import json
import os

import numpy as np
import pytest

import bench_paths
import ray_tpu
from benchmark.harness import spec

BENCH = spec.load_benchmark()
RUN = bench_paths.load_run_module(name="benchmark_run_py_phi4flash")
NAME = "phi-4-mini-flash-reasoning"
CELL = "phi-4-mini-flash-reasoning.reason-closed-64"
CONF = spec.load_config(BENCH, NAME)
ARCH = spec.load_architecture(CONF)
JOINED = ["chip_worker_ready_s", "fetch_wait_ms_per_fetch.batch",
          "decode_occupancy.batch", "decode_substep_ms.batch",
          "peak_hbm_gb.batch", "decode_kv_read_share.batch"]
OWN = ["mamba_decode_step_share", "mamba_decode_step_roofline",
       "prefill_ms_per_ktok.batch"]
# lists an accepted test pins to one cell, or whose bytes count every
# layer (`n_layers`) where one layer of this model has full-length keys
NOT_JOINED = ["prefill_useful_share.batch", "sched_dispatch_share.batch",
              "engine_stall_s.batch", "batch.decode_attention_roofline"]
# the catalog row's `config` (model-configs guide): every key as published
PUBLISHED = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
             "intermediate_size": 10240, "layer_norm_eps": 1e-05,
             "max_position_embeddings": 262144, "mb_per_layer": 2,
             "model_type": "phi4flash", "num_attention_heads": 40,
             "num_hidden_layers": 32, "num_key_value_heads": 20,
             "resid_pdrop": 0, "sliding_window": 512,
             "tie_word_embeddings": True, "mlp_bias": False,
             "lm_head_bias": False, "vocab_size": 200064}
ASSUMED_SIZES = {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
                 "mamba_d_inner": 5120, "mamba_dt_rank": 160,
                 "decoder_boundary": 16}
KINDS = ["mamba", "window"] * 8 + ["mamba", "attention"] + ["gmu", "cross"] * 7
# all 32 layers at toy widths (the pattern is the model's), a window of 8
TOY_FIELDS = dict(vocab_size=96, d_model=32, n_heads=4, n_kv_heads=2,
                  head_dim=8, d_ff=48, mamba_d_state=4, mamba_dt_rank=3,
                  sliding_window=8, dtype="float32", param_dtype="float32")
TOY_TRAFFIC = {
    "deployment": {"slots": 4, "max_concurrency": 8, "max_prompt_len": 64,
                   "max_new_tokens": 16, "eos_id": -1, "greedy": True,
                   "weights_seed": 0},
    "prompt_len": {"median": 20, "sigma": 0.9, "min": 4, "max": 64},
    "output_len": {"median": 8, "sigma": 0.7, "min": 2, "max": 16},
    "ramp_s": 1.5, "clients": 8, "client_threads": 8, "pool": 24,
    "check": {"prompt_lens": [40, 33, 50, 64]},
    "trace_at_s": 0.5, "trace_s": 1.0}
ADDED = {
    "benchmark/architectures/phi4flash.py",
    "benchmark/configs/phi-4-mini-flash-reasoning.json",
    "benchmark/traffic/reason-closed-64.json",
    "tests/benchmark/test_bench_phi4flash.py",
} | {f"benchmark/layer_metrics/{m}.json" for m in OWN}
# sha256 (first 16 hex digits) of every file under the benchmark's paths
# AT THE PARENT COMMIT (73d4318), and of its BENCHMARK.json as
# `json.dumps(..., sort_keys=True)`: its 6 configurations, 8 cells, 4
# end-to-end and 67 per-layer metrics
PARENT_BENCHMARK = \
    "d2fa914f01bd992b43c7db266b32e359865f2157b9f85d26bd5f8285eff398db"
PARENT_COUNTS = {"configs": 6, "workloads": 8, "end_to_end": 4,
                 "per_layer": 67}
PARENT_FILES = json.loads(r"""{"benchmark/README.md": "b908979af41bb3ea", "benchmark/__init__.py":
"e3b0c44298fc1c14", "benchmark/architectures/dense_gqa.py":
"53e288c623944cfb", "benchmark/architectures/glm4_moe_lite.py":
"7ad392cd851e61eb", "benchmark/architectures/kimi_linear.py":
"9413f3ff59a3d35e", "benchmark/architectures/olmoe.py": "c5e33fe0d813ca97",
"benchmark/architectures/solar_open2.py": "78da82964166d9c1",
"benchmark/configs/glm-4.7-flash.json": "8c8d89ac4fa4feeb",
"benchmark/configs/internlm2-1.8b.json": "ce8c8deb40365cf7",
"benchmark/configs/kimi-linear-48b-a3b.json": "637f337e53c884d7",
"benchmark/configs/mistral-7b-v0.3.json": "16d74a3fe947befa",
"benchmark/configs/olmoe-1b-7b.json": "45f9b3f1e3d82a5e",
"benchmark/configs/solar-open2-250b.json": "94840f9f36a188f9",
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
"622c804cdbb18f3f",
"benchmark/layer_metrics/first_token_pickup_ms.chat.json":
"a73920d4f0170745", "benchmark/layer_metrics/flash_attention_roofline.json":
"a8a679599ab06404",
"benchmark/layer_metrics/flash_attention_step_share.json":
"4ec1a22cc7d95db1", "benchmark/layer_metrics/generator_late_p99_ms.json":
"67d3ad227c5ca7a2",
"benchmark/layer_metrics/gqa_layer_decode_attention_roofline.json":
"40aa06b2c1e9a3f5", "benchmark/layer_metrics/handle_rtt_p50_ms.json":
"641829362e87ef48", "benchmark/layer_metrics/kda_decode_step_roofline.json":
"74ed3d99b3ec0d68", "benchmark/layer_metrics/kda_decode_step_share.json":
"a1455e39fccdeb1b", "benchmark/layer_metrics/kda_prefill_scan_share.json":
"375302287711de69",
"benchmark/layer_metrics/kda_projection_step_share.json":
"1604f76d130f4f5c", "benchmark/layer_metrics/kda_scan_roofline.json":
"ad13ce0b0382c7e9", "benchmark/layer_metrics/kda_scan_step_share.json":
"47b82f3e7eae1af4",
"benchmark/layer_metrics/mla_flash_attention_roofline.json":
"b1d20ba5aaa85cb6",
"benchmark/layer_metrics/mla_projection_step_share.json":
"659f90505a9e651c", "benchmark/layer_metrics/moe_dispatch_step_share.json":
"76f13db4a8f0732c",
"benchmark/layer_metrics/moe_expert_fetch_roofline.json":
"c3033a04f39421b5", "benchmark/layer_metrics/moe_expert_touched_share.json":
"cfa462614b6eb95b",
"benchmark/layer_metrics/moe_grouped_matmul_roofline.json":
"7259be3610e46334",
"benchmark/layer_metrics/moe_grouped_matmul_step_share.olmoe.json":
"19a74b3e84918a4b",
"benchmark/layer_metrics/moe_held_assignment_share.1of32.json":
"a590ca46d4c35e70",
"benchmark/layer_metrics/moe_held_assignment_share.json":
"18c70a1d8d8e6ee4",
"benchmark/layer_metrics/moe_held_assignment_share.serve.json":
"c1a2ab1c70c9161d",
"benchmark/layer_metrics/moe_held_grouped_matmul_roofline.json":
"368a7a1a01611f59", "benchmark/layer_metrics/moe_load_max_over_mean.json":
"76e283274796efa7", "benchmark/layer_metrics/mtp_step_share.json":
"e90317d30f867fb7",
"benchmark/layer_metrics/nope_mla_attention_roofline.json":
"a64c6e66179996a0",
"benchmark/layer_metrics/nope_mla_attention_step_share.json":
"4fcea774ab585aab", "benchmark/layer_metrics/peak_hbm_gb.batch.json":
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
"8797d9dd05be8668", "benchmark/layer_metrics/serve_entry_leg_ms.chat.json":
"5a90e63f13ceacfc",
"benchmark/layer_metrics/stream_held_ms_per_pull.chat.json":
"120ecd09b3bbd273",
"benchmark/layer_metrics/stream_ready_pull_share.chat.json":
"5b4c6e81edd59d0d", "benchmark/layer_metrics/stream_wait_share.chat.json":
"a0f2a0727640a07f", "benchmark/layer_metrics/token_pickup_lag_ms.chat.json":
"1078f36ee4e41b0e", "benchmark/layer_metrics/train_mfu.json":
"dade73ff2bb029fb", "benchmark/layer_metrics/train_step_device_ms.json":
"bf9bf025273f58c3", "benchmark/layer_metrics/ttft_p50_ms.json":
"58f5558bd1b08773", "benchmark/layer_metrics/ttft_p90_ms.json":
"a6b53f27f8afb3d7", "benchmark/layer_metrics/ttft_p95_ms.json":
"67e7789977201c67", "benchmark/peaks.json": "87ff8d69ce29113d",
"benchmark/readers/attention_roofline_by_kind.py": "909ff735fb0f362b",
"benchmark/readers/attention_roofline_with_mtp.py": "2b04a3a153c29d99",
"benchmark/readers/collective_exposed.py": "5120b97e1e219bb7",
"benchmark/readers/counter_bytes_roofline.py": "4180a8f9d6e99446",
"benchmark/readers/decode_occupancy.py": "d64553f9c6ea2ab7",
"benchmark/readers/engine_ratio.py": "f6ae5d53d9fe83f0",
"benchmark/readers/grouped_matmul_roofline.py": "93fee09910c0b5a8",
"benchmark/readers/held_grouped_matmul_roofline.py": "a700141ffaaa8161",
"benchmark/readers/kda_scan_roofline.py": "e251fb7c5814b76e",
"benchmark/readers/kernel_roofline.py": "817cff4019a37267",
"benchmark/readers/kernel_share.py": "15cfabc1d672d0b8",
"benchmark/readers/module_op_bytes_roofline.py": "62ce686e296275e5",
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
"b61a70a1272fdd94", "benchmark/traffic/batch-closed-128.json":
"e853d6ea577e2d71", "benchmark/traffic/batch-closed.json":
"35a0010f7a4287fd", "benchmark/traffic/chat-steady.json":
"f5f809afba5aa2f1", "benchmark/traffic/train-16k-2rows.json":
"8db2f31473646315", "benchmark/traffic/train-4k-8rows.json":
"ec4bade0d377e0b6", "benchmark/traffic/train-4k.json": "f7415070e5f66142",
"benchmark/traffic/train-fsdp2tp2.json": "aa8aae94dcaebe87",
"tests/benchmark/bench_paths.py": "7ae2ca7969fcfdd9",
"tests/benchmark/test_bench_additions.py": "1054b319070ef7c9",
"tests/benchmark/test_bench_cells_cpu.py": "82196c88f3af6e01",
"tests/benchmark/test_bench_engine_spans.py": "c2a0ec095d21f6a8",
"tests/benchmark/test_bench_flops.py": "ee8075a6223acfb3",
"tests/benchmark/test_bench_glm4_moe_lite.py": "273badb51990d066",
"tests/benchmark/test_bench_kimi_linear.py": "6bf2d87b03e88238",
"tests/benchmark/test_bench_olmoe.py": "d0ee1b82f33fdab3",
"tests/benchmark/test_bench_reference.py": "45e19703e189262e",
"tests/benchmark/test_bench_run_cpu.py": "9b418b66a5c385c8",
"tests/benchmark/test_bench_serve_seam.py": "11230a634ae70bf9",
"tests/benchmark/test_bench_solar_open2.py": "a9592e8c669b61e2",
"tests/benchmark/test_bench_spec.py": "a7e9fc3b793a7562",
"tests/benchmark/test_bench_stats.py": "684b2859b6ee7f13",
"tests/benchmark/test_bench_stream_ledger.py": "a00072d265bda511",
"tests/benchmark/test_bench_traffic.py": "4a60585fbe008bf0",
"tests/benchmark/test_bench_xplane.py": "cfec4cca08538b4f"}""")


def _metric(group, name):
    return next(m for m in BENCH[group] if m["name"] == name)


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


# ---- the files and the entries ---------------------------------------------

def test_the_configuration_is_the_published_one_uncut():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] == CONF["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
        "main/config.json")
    assert CONF["architecture"] == "phi4flash"
    for key, value in PUBLISHED.items():
        assert CONF[key] == value, key
    # nothing is cut: neither depth nor vocabulary
    assert entry["reduced"] == [] and CONF["reduced"] == {}
    for key, value in ASSUMED_SIZES.items():
        assert CONF[key] == value, key
    for key in ("source_of_the_assumed", "mamba_sizes", "decoder_boundary",
                "attention_bias", "differential_attention", "no_positions",
                "window", "state_dtype", "checkpoint_dtype", "initializer"):
        assert CONF["assumed"][key], key
    dep = CONF["deployment"]
    assert (dep["chips"], dep["mesh"]) == (1, None)
    assert "2,048" in dep["what_the_cell_cannot_see"] \
        and "cross-decoder" in dep["what_the_cell_cannot_see"]


def test_fields_are_the_published_rules_and_the_counts_the_programs():
    f = spec.transformer_fields(CONF)
    assert f["layer_pattern"] == ((("mamba", "window"), 8),
                                  (("mamba", "attention"), 1),
                                  (("gmu", "cross"), 7))
    assert ARCH.layer_kinds(CONF, 32) == KINDS
    assert (f["n_layers"], f["d_model"], f["n_heads"], f["n_kv_heads"],
            f["head_dim"], f["d_ff"], f["vocab_size"]) == (
                32, 2560, 40, 20, 64, 10240, 200064)
    assert (f["sliding_window"], f["mamba_d_state"], f["mamba_d_conv"],
            f["mamba_expand"], f["mamba_dt_rank"]) == (512, 16, 4, 2, 160)
    assert f["diff_attn"] and f["attn_bias"] and f["tie_embeddings"] \
        and not f["use_rope"] and f["norm"] == "layer"
    cfg = spec.build_transformer_config(CONF)
    # what the initialiser makes is what the architecture counts: the
    # check sizes the reference's tree by it
    assert ARCH.num_params(f, CONF) == cfg.num_params == 3_852_562_944
    assert [cfg.mixer_kind(i) for i in range(32)] == KINDS
    flops = ARCH.forward_flops_per_token(f, CONF, 1024)
    # 2 x the 3.34 G weights that multiply + the tied head's 1.02 G, plus
    # attention over 1,024 positions: a little over 7.8 GFLOP a token
    assert 7.7e9 < flops < 8.3e9
    assert ARCH.keys_per_query(1024) == 512.5
    assert 380 < ARCH.keys_per_query(1024, 512) < 390
    assert ARCH.keys_per_query(100, 512) == 50.5


def test_the_cell_and_its_traffic():
    cell = spec.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "reason-closed-64", 1)
    assert len(cell["why"]) <= 200
    traffic = spec.load_traffic("reason-closed-64")
    assert traffic["kind"] == "closed_loop"
    dep = dict(traffic["deployment"])
    assert "ONE checkpoint" in dep.pop("weights_seed_why")
    assert dep == {
        "slots": 64, "max_concurrency": 128, "max_prompt_len": 1024,
        "max_new_tokens": 1024, "eos_id": -1, "greedy": True,
        "weights_seed": 0}
    assert (traffic["clients"], traffic["client_threads"],
            traffic["pool"], traffic["ramp_s"], traffic["trace_at_s"],
            traffic["trace_s"]) == (128, 128, 214, 20, 10, 5)
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 256,
                                     "sigma": 0.9, "min": 32, "max": 1024}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 320,
                                     "sigma": 0.5, "min": 96, "max": 1024}
    # two prompts longer than the window, one bucket
    assert traffic["check"] == {"prompt_lens": [200, 517, 64, 1000]}
    assert "rate_per_s" not in traffic
    e2e = [m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")]
    assert sorted(e2e) == ["serve_tokens_per_s", "setup_s"]
    assert _metric("end_to_end", "serve_tokens_per_s")["bound"] == 0.06


def test_the_lists_the_cell_joined_and_those_it_must_not():
    reported = {m["name"] for m in spec.metrics_for(BENCH, CELL,
                                                    "per_layer")}
    for name in JOINED + OWN:
        assert CELL in _metric("per_layer", name)["workloads"], name
        assert name in reported
    for name in NOT_JOINED:
        assert CELL not in _metric("per_layer", name)["workloads"], name
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] in ("serve_tokens_per_s", "setup_s"), m["name"]


@pytest.mark.parametrize("name", OWN)
def test_a_new_metric_has_its_file_its_reader_and_its_entry(name):
    entry = _metric("per_layer", name)
    f = spec.load_layer_metric(name)
    assert "workloads" not in f and f["what"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == f[key], key
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["workloads"][0] == CELL
    assert callable(spec.load_reader(f))
    reader = {"mamba_decode_step_share": "kernel_share",
              "mamba_decode_step_roofline": "counter_bytes_roofline",
              "prefill_ms_per_ktok.batch": "module_time"}[name]
    assert f["reader"] == reader
    if name.startswith("mamba"):
        assert f["op_pattern"] == "^tpu_custom_call:mamba_decode_step"
    else:
        assert (f["module_pattern"], f["per"]) == ("prefill_slots",
                                                   "padded_ktok")


def test_the_roofline_counts_the_bytes_the_architecture_states():
    """One unit of the counter, in bytes, from the metric file's factors
    and the configuration's numbers: nine layers' float32 states of one
    slot, read once and written once."""
    per_unit = spec._load_module("readers", "counter_bytes_roofline",
                                 spec.ROOT).per_unit
    f = spec.transformer_fields(CONF)
    metric = spec.load_layer_metric("mamba_decode_step_roofline")
    assert (metric["unit"], metric["bound"], metric["counter"]) == (
        "%", "memory", "mamba_state_updates")
    layers = ARCH.layer_kinds(CONF, f["n_layers"]).count("mamba")
    assert per_unit(metric["bytes_per_unit"], f, CONF) == layers \
        * ARCH.mamba_step_cost(5120, 16)["decode_bytes"] == 9 * 16 * 5120 * 8
    # nothing to read is None, never a raise: a program without the
    # counter (the parent), a trace without the kernel
    reader = spec.load_reader(metric)
    evidence = {"trace": {"engine_in_trace": {}}, "peaks": {}, "out": {},
                "fields": f, "conf": CONF}
    assert reader(evidence, metric) is None
    evidence["trace"]["engine_in_trace"] = {"mamba_state_updates": 10}
    evidence["peaks"] = spec.device_peaks("TPU v5 lite")
    assert reader(evidence, metric) is None      # no kernel in the trace


def test_loading_the_architecture_imports_no_jax():
    import subprocess
    import sys

    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark.harness import spec; "
            "b = spec.load_benchmark(); "
            "c = spec.load_config(b, %r); a = spec.load_architecture(c); "
            "a.num_params(spec.transformer_fields(c), c); "
            "a.forward_flops_per_token(spec.transformer_fields(c), c, 512); "
            "assert 'jax' not in sys.modules" % (spec.ROOT, NAME))
    subprocess.run([sys.executable, "-c", code], check=True)


# ---- the reference reads a host tree a layer at a time ------------------------

def test_the_reference_gives_the_same_logits_from_a_host_tree():
    import jax

    from ray_tpu.models.transformer import init_params

    fields = dict(spec.transformer_fields(CONF), **TOY_FIELDS)
    cfg = spec.build_transformer_config(CONF, **TOY_FIELDS)
    params = init_params(jax.random.key(4), cfg)
    tokens = list(np.arange(37) * 5 % 96)
    on_device = ARCH.reference_logits(params, tokens, fields, CONF, last=3)
    on_host = ARCH.reference_logits(jax.tree.map(np.asarray, params),
                                    tokens, fields, CONF, last=3)
    assert on_device.shape == (3, 96)
    np.testing.assert_array_equal(np.asarray(on_device), np.asarray(on_host))


# ---- the cell through the harness at toy size ---------------------------------

@pytest.fixture(scope="module")
def cpu_cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


def _run(trace):
    cell = spec.find_cell(BENCH, CELL)
    args = argparse.Namespace(seed=2 ** 31 + 44, seconds=2.0, trace=trace)
    return bench_paths.run_cell_with_info(
        RUN, BENCH, cell, args, platform="cpu", field_overrides=TOY_FIELDS,
        traffic_overrides=TOY_TRAFFIC)


def test_the_cell_runs_end_to_end_at_toy_size(cpu_cluster):
    line, info = _run(trace=0)
    assert line["correct"] is True, line
    assert info["check"]["reference"] == "phi4flash" and info["check"]["ok"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert all(v <= limit for v, limit in line["compared"].values())
    leaves = info["info"]["cache_leaves"]
    # ONE full-length layer of keys and values, as pairs of heads; eight
    # rings of the window's 8 places; nine float32 states and their tails
    assert leaves["k"] == {"shape": [1, 4, 1, 80, 16], "dtype": "float32"}
    assert leaves["win_k"]["shape"] == [8, 4, 1, 8, 16]
    assert leaves["mamba_state"] == {"shape": [9, 4, 4, 64],
                                     "dtype": "float32"}
    assert leaves["mamba_tail"]["shape"] == [9, 4, 3, 64]
    eng = info["engine"]
    assert eng["mamba_state_updates"] > 0
    assert eng["window_kv_rows_read"] == eng["decode_steps"] * 4 * 8
    # the masked contraction reads the full-length leaf whole
    assert eng["decode_kv_rows_read"] == eng["decode_kv_rows_cache"] > 0


def test_a_traced_run_reports_only_what_a_cpu_can(cpu_cluster):
    line, _ = _run(trace=1)
    for name in ("decode_occupancy.batch", "decode_kv_read_share.batch",
                 "chip_worker_ready_s", "peak_hbm_gb.batch"):
        assert name in line["metrics"], name
    assert line["metrics"]["decode_kv_read_share.batch"]["value"] == 100.0
    # the kernel's metrics need a device: left out here, never a raise
    for name in ("mamba_decode_step_roofline", "mamba_decode_step_share"):
        assert name not in line["metrics"], name
