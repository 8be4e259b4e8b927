"""The `solar-open2-250b` configuration and its serve cell as the benchmark
holds them: found by name, the published widths uncut, the cell a member
of the lists it joined and of none that an accepted test pins to one
cell, its new metrics with their files and readers, its architecture
module's counts equal to what the program's initialiser makes, and the
cell through the harness's own functions at toy size on the CPU (the
check against the plain reference included). Membership, never equality
with a list another PR may join."""

import argparse
import json
import os

import numpy as np
import pytest

import bench_paths
import ray_tpu
from benchmark.harness import spec

BENCH = spec.load_benchmark()
RUN = bench_paths.load_run_module(name="benchmark_run_py_solar")
NAME, CELL = "solar-open2-250b", "solar-open2-250b.batch-closed-128"
CONF = spec.load_config(BENCH, NAME)
ARCH = spec.load_architecture(CONF)
JOINED = ["chip_worker_ready_s", "fetch_wait_ms_per_fetch.batch",
          "decode_occupancy.batch", "decode_substep_ms.batch",
          "peak_hbm_gb.batch", "decode_kv_read_share.batch"]
OWN = ["kda_decode_step_roofline", "kda_decode_step_share",
       "kda_prefill_scan_share", "moe_expert_fetch_roofline",
       "moe_expert_touched_share", "moe_held_assignment_share.serve",
       "gqa_layer_decode_attention_roofline"]
# lists an accepted test pins to one cell, or whose bytes count every
# layer (`n_layers`) where one layer of this model has keys
NOT_JOINED = ["prefill_useful_share.batch", "sched_dispatch_share.batch",
              "engine_stall_s.batch", "batch.decode_attention_roofline"]
# the catalog row's numbers (model-configs guide): no width is cut
PUBLISHED = {"hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
             "num_key_value_heads": 8, "intermediate_size": 10240,
             "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
             "rope_theta": 10000, "max_position_embeddings": 1048576,
             "first_k_dense_replace": 0, "gqa_interval": 3,
             "n_shared_experts": 1, "routed_scaling_factor": 1,
             "num_experts_per_tok": 8, "partial_rotary_factor": 1}
CUTS = {"num_hidden_layers": (48, 4), "n_routed_experts": (320, 40),
        "vocab_size": (196608, 24576)}
TOY_FIELDS = dict(vocab_size=256, d_model=32, n_heads=4, n_kv_heads=2,
                  head_dim=8, d_ff=24, kda_heads=4, kda_head_dim=8,
                  kda_gate_rank=6, moe_experts=16, moe_held_experts=4,
                  moe_top_k=4, moe_shared_d_ff=24, dtype="float32")
TOY_TRAFFIC = {
    "deployment": {"slots": 4, "max_concurrency": 8, "max_prompt_len": 64,
                   "max_new_tokens": 16, "eos_id": -1, "greedy": True,
                   "weights_seed": 0},
    "prompt_len": {"median": 20, "sigma": 0.9, "min": 4, "max": 64},
    "output_len": {"median": 8, "sigma": 0.7, "min": 2, "max": 16},
    "ramp_s": 1.5, "clients": 8, "client_threads": 8, "pool": 24,
    "check": {"prompt_lens": [40, 33, 50, 64]},
    "trace_at_s": 0.5, "trace_s": 1.0}


def _metric(group, name):
    return next(m for m in BENCH[group] if m["name"] == name)


# ---- the files and the entries ---------------------------------------------

def test_the_configuration_states_source_cuts_and_no_cut_width():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] == CONF["source"] == (
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/"
        "config.json")
    assert CONF["architecture"] == "solar_open2"
    for key, number in PUBLISHED.items():
        assert CONF[key] == number, key
    assert CONF["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert CONF["gqa_layers"] == list(range(0, 48, 4))
    assert (CONF["use_rope"], CONF["use_gqa_gate"], CONF["norm_topk_prob"],
            CONF["kda_use_full_proj"], CONF["kda_allow_neg_eigval"],
            CONF["tie_word_embeddings"]) == (False, True, True, False, True,
                                             False)
    assert sorted(entry["reduced"]) == sorted(CONF["reduced"]) == sorted(CUTS)
    for key, (published, run) in CUTS.items():
        cut = CONF["reduced"][key]
        assert (cut["published"], cut["run"], CONF[key]) == (published, run,
                                                             run)
        assert "rehearse" in cut["why"] or "deployment" in cut["why"]
    dep = CONF["deployment"]
    assert (dep["chips"], dep["chips_a_layer"], dep["router_experts"],
            dep["first_expert"], dep["vocab_rows"]) == (1, 8, 320, 0,
                                                        [0, 24576])
    for key in ("router", "gqa_gate", "l2norm_eps", "kda_biases",
                "state_dtype"):
        assert CONF["assumed"][key]
    assert "exchange" in dep["what_the_cell_cannot_see"]


def test_fields_are_the_published_rules_at_the_share():
    f = spec.transformer_fields(CONF)
    assert f["mixer_period"] == ("attention", "kda", "kda", "kda")
    assert (f["n_layers"], f["d_model"], f["n_heads"], f["n_kv_heads"],
            f["head_dim"], f["d_ff"]) == (4, 4096, 64, 8, 128, 1280)
    assert (f["moe_experts"], f["moe_held_experts"], f["moe_first_expert"],
            f["moe_top_k"], f["moe_shared_d_ff"]) == (320, 40, 0, 8, 1280)
    assert (f["kda_heads"], f["kda_head_dim"], f["kda_conv"],
            f["kda_gate_rank"]) == (64, 128, 4, 128)
    assert f["kda_allow_neg_eigval"] and f["attn_output_gate"] \
        and not f["use_rope"] and f["moe_scoring"] == "sigmoid"
    cfg = spec.build_transformer_config(CONF)
    # what the initialiser makes is what the architecture counts: the
    # check sizes the reference's tree by it
    assert ARCH.num_params(f, CONF) == cfg.num_params == 3_308_353_344
    assert ARCH.layer_kinds(CONF, 8) == ["attention", "kda", "kda",
                                         "kda"] * 2
    flops = ARCH.forward_flops_per_token(f, CONF, 400)
    assert 1.2e9 < flops < 1.6e9     # the issue's 1.36 GFLOP a token


def test_the_cell_and_its_traffic():
    cell = spec.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "batch-closed-128", 1)
    traffic = spec.load_traffic("batch-closed-128")
    base = spec.load_traffic("batch-closed")
    assert traffic["kind"] == "closed_loop"
    dep, was = dict(traffic["deployment"]), dict(base["deployment"])
    # the one serve cell whose speed follows the draw of its weights: its
    # number is the first of 0..7 at which the held share reads within 8%
    # of the 0.125 a balanced router gives (the file lists the readings);
    # a dense model's is 0
    assert dep.pop("weights_seed") in range(8)
    assert was.pop("weights_seed") == 0
    assert "0.125" in dep.pop("weights_seed_why") and was.pop(
        "weights_seed_why")
    assert dep == dict(was, slots=128, max_concurrency=256)
    assert (traffic["clients"], traffic["client_threads"]) == (256, 256)
    for key in ("prompt_len", "output_len", "pool", "ramp_s", "trace_at_s",
                "trace_s", "check"):
        assert traffic[key] == base[key], key
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
    assert callable(spec.load_reader(f))
    if name.endswith("_roofline"):
        assert (f["unit"], f["bound"]) == ("%", "memory")
        assert f["counter"] in ("kda_state_updates", "moe_expert_fetches",
                                "decode_kv_rows_read")


def test_the_rooflines_count_the_bytes_the_architecture_states():
    """One unit of each counter, in bytes, from the metric file's factors
    and the configuration's numbers."""
    per_unit = spec._load_module("readers", "counter_bytes_roofline",
                                 spec.ROOT).per_unit
    f = spec.transformer_fields(CONF)

    def unit_bytes(name):
        return per_unit(spec.load_layer_metric(name)["bytes_per_unit"], f,
                        CONF)
    kda_layers = ARCH.layer_kinds(CONF, f["n_layers"]).count("kda")
    assert unit_bytes("kda_decode_step_roofline") == kda_layers \
        * ARCH.kda_step_cost(64, 128, 128)["decode_bytes"] == 3 * 8 * 2 ** 20
    assert unit_bytes("moe_expert_fetch_roofline") == 3 * 4096 * 1280 * 2
    # ONE layer has keys and values: not n_layers of them
    assert unit_bytes("gqa_layer_decode_attention_roofline") \
        == 1 * 8 * 128 * 2 * 2
    batch = spec.load_layer_metric("batch.decode_attention_roofline")
    assert "n_layers" in batch["bytes_per_unit"]


def test_the_module_scoped_reader_reads_a_trace_file_by_program():
    """`module_op_bytes_roofline` on the recorded fixture: operations
    inside the executions of a program that matches, none inside one that
    does not, and the driver's process stays without JAX's profiler."""
    reader = spec._load_module("readers", "module_op_bytes_roofline",
                               spec.ROOT)
    path = os.path.join(spec.ROOT, "benchmark", "fixtures",
                        "train_tiny_v5e.xplane.pb.gz")
    inside = reader.seconds_in_modules(path, r"^fusion", r"^jit_step")
    assert inside is not None and 0 < inside < 1
    assert reader.seconds_in_modules(path, r"^fusion", r"^jit_other") == 0
    assert reader.seconds_in_modules(path + ".none", r".", r".") is None
    facts = json.load(open(path.replace(".xplane.pb.gz", ".facts.json")))
    assert inside <= facts.get("busy_s", 1.0)
    # nothing to read is None, never a raise: a program without the
    # counter (the parent), a run without a trace file
    metric = spec.load_layer_metric("moe_expert_fetch_roofline")
    evidence = {"trace": {"engine_in_trace": {}}, "peaks": {}, "out": {},
                "root": spec.ROOT, "cell": {"name": CELL}, "fields": {},
                "conf": {}}
    assert reader.read(evidence, metric) is None
    evidence["trace"]["engine_in_trace"] = {"moe_expert_fetches": 10}
    evidence["peaks"] = spec.device_peaks("TPU v5 lite")
    assert reader.read(evidence, metric) is None    # no trace file here


def test_loading_the_architecture_imports_no_jax():
    import subprocess
    import sys

    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark.harness import spec; "
            "b = spec.load_benchmark(); "
            "c = spec.load_config(b, %r); a = spec.load_architecture(c); "
            "a.num_params(spec.transformer_fields(c), c); "
            "spec._load_module('readers', 'module_op_bytes_roofline', "
            "spec.ROOT); assert 'jax' not in sys.modules" % (spec.ROOT, NAME))
    subprocess.run([sys.executable, "-c", code], check=True)


# ---- the reference reads a host tree a layer at a time ------------------------

def test_the_reference_gives_the_same_logits_from_a_host_tree():
    import jax

    from ray_tpu.models.transformer import init_params

    fields = dict(spec.transformer_fields(CONF), **TOY_FIELDS)
    cfg = spec.build_transformer_config(CONF, **TOY_FIELDS)
    params = init_params(jax.random.key(4), cfg)
    tokens = list(np.arange(37) * 5 % 256)
    on_device = ARCH.reference_logits(params, tokens, fields, CONF, last=3)
    on_host = ARCH.reference_logits(jax.tree.map(np.asarray, params),
                                    tokens, fields, CONF, last=3)
    assert on_device.shape == (3, 256)
    np.testing.assert_array_equal(np.asarray(on_device), np.asarray(on_host))


# ---- the cell through the harness at toy size ---------------------------------

@pytest.fixture(scope="module")
def cpu_cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


def _run(trace):
    cell = spec.find_cell(BENCH, CELL)
    args = argparse.Namespace(seed=2 ** 31 + 42, seconds=2.0, trace=trace)
    return bench_paths.run_cell_with_info(
        RUN, BENCH, cell, args, platform="cpu", field_overrides=TOY_FIELDS,
        traffic_overrides=TOY_TRAFFIC)


def test_the_cell_runs_end_to_end_at_toy_size(cpu_cluster):
    line, info = _run(trace=0)
    assert line["correct"] is True, line
    assert info["check"]["reference"] == "solar_open2" and info["check"]["ok"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert all(v <= limit for v, limit in line["compared"].values())
    leaves = info["info"]["cache_leaves"]
    assert leaves["kda_state"] == {"shape": [3, 4, 4, 8, 8],
                                   "dtype": "float32"}
    assert leaves["kda_tail"]["shape"] == [3, 4, 3, 3 * 4 * 8]
    assert leaves["k"]["shape"][0] == 1 and "moe_counts" in leaves
    eng = info["engine"]
    assert eng["kda_state_updates"] > 0
    assert 0 < eng["moe_expert_fetches"] <= eng["moe_expert_calls"]
    assert 0 < eng["moe_held_assignments"] <= eng["moe_assignments"]


def test_a_traced_run_reports_the_counters_metrics(cpu_cluster):
    line, _ = _run(trace=1)
    touched = line["metrics"]["moe_expert_touched_share"]
    assert touched["unit"] == "%" and 0 < touched["value"] <= 100
    held = line["metrics"]["moe_held_assignment_share.serve"]["value"]
    assert 0 < held < 1
    for name in ("decode_occupancy.batch", "decode_kv_read_share.batch",
                 "chip_worker_ready_s", "peak_hbm_gb.batch"):
        assert name in line["metrics"], name
    # the device metrics need a device: left out here, never a raise
    for name in ("kda_decode_step_roofline", "moe_expert_fetch_roofline",
                 "gqa_layer_decode_attention_roofline",
                 "kda_decode_step_share"):
        assert name not in line["metrics"], name
