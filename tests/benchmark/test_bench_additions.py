"""The harness takes additions as data: in a temporary copy, a new cell is
one config file, one traffic file, one layer-metric file with its reader
and entries in `BENCHMARK.json`; no file that was there is touched, and
the new cell runs through the harness's own functions at toy size."""

import argparse
import hashlib
import json
import os
import shutil

import pytest

import bench_paths
import ray_tpu
from benchmark.harness import spec


def _digest(root):
    out = {}
    for d, dirs, files in os.walk(os.path.join(root, "benchmark")):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy_with_additions(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_copy"))
    shutil.copy(os.path.join(bench_paths.REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(bench_paths.REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(root)
    b = os.path.join(root, "benchmark")
    donor = spec.load_config(spec.load_benchmark(), "internlm2-1.8b")
    toy = {"source": "https://example.org/toy-dense/config.json",
           "hidden_size": 64, "num_hidden_layers": 2,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "intermediate_size": 128, "vocab_size": 512,
           "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
           "tie_word_embeddings": False, "reduced": {}, "assumed": {},
           "deployment": {"chips": 1}, "mapping": donor["mapping"]}
    with open(os.path.join(b, "configs", "toy-dense.json"), "w") as f:
        json.dump(toy, f)
    with open(os.path.join(b, "traffic", "train-toy.json"), "w") as f:
        json.dump({"kind": "train", "seq_len": 32, "rows": 2,
                   "param_dtype": "float32", "mu_dtype": "float32",
                   "learning_rate": 1e-3, "attention_impl": "auto",
                   "mesh": None, "check": {"rows": 1}, "trace_steps": 2,
                   "why": "a toy"}, f)
    with open(os.path.join(b, "layer_metrics", "toy_steps_per_s.json"),
              "w") as f:
        json.dump({"unit": "steps/s", "better": "higher",
                   "source": "host_clock", "layer": "trainer loop",
                   "moves": "train_tokens_per_s",
                   "workloads": ["toy-dense.train-toy"],
                   "reader": "toy_steps", "what": "steps per second"}, f)
    with open(os.path.join(b, "readers", "toy_steps.py"), "w") as f:
        f.write("def read(evidence, metric):\n"
                "    out = evidence['out']\n"
                "    return out['steps'] / out['window_s']\n")
    bench = spec.load_benchmark(root)
    bench["configs"].append({
        "name": "toy-dense", "source": toy["source"],
        "file": "benchmark/configs/toy-dense.json", "reduced": [],
        "why": "a toy"})
    bench["workloads"].append({
        "name": "toy-dense.train-toy", "config": "toy-dense",
        "traffic": "train-toy", "chips": 1, "why": "a toy"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("toy-dense.train-toy")
    bench["per_layer"].append({
        "name": "toy_steps_per_s", "unit": "steps/s", "better": "higher",
        "source": "host_clock", "layer": "trainer loop",
        "moves": "train_tokens_per_s",
        "workloads": ["toy-dense.train-toy"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, before


def test_nothing_that_was_there_is_edited(copy_with_additions):
    root, before = copy_with_additions
    after = _digest(root)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmark/configs/toy-dense.json",
        "benchmark/layer_metrics/toy_steps_per_s.json",
        "benchmark/readers/toy_steps.py",
        "benchmark/traffic/train-toy.json"]


def test_the_added_cell_is_found_by_name(copy_with_additions):
    root, _ = copy_with_additions
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, "toy-dense.train-toy")
    conf = spec.load_config(bench, cell["config"], root)
    assert spec.transformer_fields(conf)["d_model"] == 64
    assert spec.load_traffic(cell["traffic"], root)["rows"] == 2
    names = [m["name"] for m in spec.metrics_for(
        bench, cell["name"], "per_layer")]
    assert "toy_steps_per_s" in names and "chip_worker_ready_s" not in names
    # ... and the cells that were there do not see the new metric
    assert "toy_steps_per_s" not in [m["name"] for m in spec.metrics_for(
        bench, "internlm2-1.8b.train-4k", "per_layer")]


def test_the_added_cell_runs_through_the_harness(copy_with_additions):
    root, _ = copy_with_additions
    run = bench_paths.load_run_module()
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, "toy-dense.train-toy")
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        args = argparse.Namespace(seed=5, seconds=1.5, trace=1)
        line = run.run_cell(bench, cell, args, root=root, platform="cpu",
                            field_overrides={"dtype": "float32"})
        args.trace = 0
        plain = run.run_cell(bench, cell, args, root=root, platform="cpu",
                             field_overrides={"dtype": "float32"})
    finally:
        ray_tpu.shutdown()
    assert line["metrics"]["toy_steps_per_s"]["value"] > 0
    assert line["metrics"]["toy_steps_per_s"]["unit"] == "steps/s"
    assert plain["correct"] is True
    assert set(plain["metrics"]) == {"train_tokens_per_s", "setup_s"}
