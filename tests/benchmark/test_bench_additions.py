"""The harness takes additions as data: in a temporary copy, a new cell is
one config file, one traffic file, one layer-metric file with its reader
and entries in `BENCHMARK.json`; a new ARCHITECTURE is one more file,
`architectures/<name>.py` (its plain reference and the work its forward
requires), named by its config file. No file that was there is touched,
the new cells run through the harness's own functions at toy size, and
the named module is what judges and what counts."""

import argparse
import hashlib
import json
import os
import shutil

import pytest

import bench_paths
import ray_tpu
from benchmark.harness import spec

# A new architecture as files. It delegates to the dense module (found by
# name in its own checkout), so nothing here depends on model code of the
# program that a later PR rewrites; it brings its own `fields(conf)`, as
# an architecture whose published keys do not map one to one does.
TOY_ARCH = '''"""A toy architecture: the dense block under another name."""
import os

from benchmark.harness import spec

_dense = spec.load_architecture({}, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
num_params = _dense.num_params


def fields(conf):
    return {"vocab_size": conf["vocab_size"], "d_model": conf["width"],
            "n_layers": conf["depth"], "n_heads": conf["heads"],
            "n_kv_heads": conf["kv_heads"], "d_ff": conf["ff"],
            "rope_theta": conf["rope_theta"], "rms_eps": conf["eps"],
            "tie_embeddings": False}


def reference_logits(params, tokens, fields, conf, last=0):
    return LOGIT_SCALE * _dense.reference_logits(params, tokens, fields,
                                                 conf, last)


def forward_flops_per_token(fields, conf, seq_len):
    return FLOP_SCALE * _dense.forward_flops_per_token(fields, conf,
                                                       seq_len)
'''
# name -> (logit scale, FLOP scale): the reference that agrees, one whose
# logits are off by a tenth, one that requires twice the work
TOY_ARCHS = {"toy_arch": (1.0, 1.0), "toy_arch_scaled": (1.1, 1.0),
             "toy_arch_double": (1.0, 2.0)}


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
    for arch, (logit_scale, flop_scale) in TOY_ARCHS.items():
        with open(os.path.join(b, "architectures", arch + ".py"), "w") as f:
            f.write(TOY_ARCH + f"\n\nLOGIT_SCALE = {logit_scale}\n"
                    f"FLOP_SCALE = {flop_scale}\n")
        with open(os.path.join(b, "configs", arch + ".json"), "w") as f:
            json.dump({"source": "https://example.org/toy-arch/config.json",
                       "architecture": arch, "width": 64, "depth": 2,
                       "heads": 4, "kv_heads": 2, "head_dim": 16, "ff": 128,
                       "vocab_size": 512, "rope_theta": 10000.0,
                       "eps": 1e-5, "reduced": {}, "assumed": {},
                       "deployment": {"chips": 1}}, f)
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
    for arch in TOY_ARCHS:
        bench["configs"].append({
            "name": arch, "source": "https://example.org/toy-arch/"
            "config.json", "file": f"benchmark/configs/{arch}.json",
            "reduced": [], "why": "a toy architecture"})
        bench["workloads"].append({
            "name": arch + ".train-toy", "config": arch,
            "traffic": "train-toy", "chips": 1, "why": "a toy"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"] += ["toy-dense.train-toy"] + [
                a + ".train-toy" for a in TOY_ARCHS]
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
    assert sorted(set(after) - set(before)) == sorted(
        [f"benchmark/architectures/{a}.py" for a in TOY_ARCHS]
        + [f"benchmark/configs/{a}.json" for a in TOY_ARCHS]
        + ["benchmark/configs/toy-dense.json",
           "benchmark/layer_metrics/toy_steps_per_s.json",
           "benchmark/readers/toy_steps.py",
           "benchmark/traffic/train-toy.json"])


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


@pytest.fixture(scope="module")
def cpu_cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


def _run(root, cell_name, trace):
    """-> (last line, the information line) of one toy-size run."""
    run = bench_paths.load_run_module()
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, cell_name)
    args = argparse.Namespace(seed=5, seconds=1.5, trace=trace)
    return bench_paths.run_cell_with_info(
        run, bench, cell, args, root=root, platform="cpu",
        field_overrides={"dtype": "float32"})


def test_the_added_cell_runs_through_the_harness(copy_with_additions,
                                                 cpu_cluster):
    root, _ = copy_with_additions
    line, info = _run(root, "toy-dense.train-toy", trace=1)
    plain, _ = _run(root, "toy-dense.train-toy", trace=0)
    assert line["metrics"]["toy_steps_per_s"]["value"] > 0
    assert line["metrics"]["toy_steps_per_s"]["unit"] == "steps/s"
    assert plain["correct"] is True
    assert set(plain["metrics"]) == {"train_tokens_per_s", "setup_s"}
    # a config that names no architecture is judged by the dense block's
    assert info["check"]["reference"] == "dense_gqa"


@pytest.mark.parametrize("arch,correct", [("toy_arch", True),
                                          ("toy_arch_scaled", False)])
def test_a_new_architecture_is_judged_by_the_reference_it_names(
        copy_with_additions, cpu_cluster, arch, correct):
    """The cell of a config that names `architectures/<arch>.py` runs, its
    check says which module judged it, and that module decides: the same
    program against a reference whose logits are a tenth larger is not
    `correct`."""
    root, _ = copy_with_additions
    line, info = _run(root, arch + ".train-toy", trace=0)
    assert info["check"]["reference"] == arch
    assert line["correct"] is correct and info["check"]["ok"] is correct
    assert (info["check"]["logits"]["rel_rms_error"] < 2e-4) is correct
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_a_new_architecture_is_counted_by_its_own_flops(
        copy_with_additions):
    """`train_mfu` on the same evidence reads exactly twice under a module
    that requires twice the work, and the dense count under one that
    delegates to it."""
    root, _ = copy_with_additions
    bench = spec.load_benchmark(root)
    read = spec.load_reader({"reader": "train_mfu"}, root)
    got = {}
    for name in ("toy_arch", "toy_arch_double"):
        conf = spec.load_config(bench, name, root)
        got[name] = read({
            "trace": {"window_s": 2.0}, "out": {"trace_steps": 3},
            "traffic": {"rows": 2, "seq_len": 32}, "conf": conf,
            "fields": spec.transformer_fields(conf, root), "root": root,
            "peaks": spec.device_peaks("TPU v5 lite"),
            "cell": {"chips": 1}}, {})
    assert got["toy_arch_double"] == 2 * got["toy_arch"] > 0
    conf = spec.load_config(bench, "toy_arch", root)
    fields = spec.transformer_fields(conf, root)
    assert fields["d_model"] == 64 and fields["n_kv_heads"] == 2
    dense = spec.load_architecture({}, root)
    assert spec.load_architecture(conf, root).forward_flops_per_token(
        fields, conf, 32) == dense.forward_flops_per_token(fields, {}, 32)


def test_a_config_that_names_a_missing_architecture_is_refused(
        copy_with_additions):
    root, _ = copy_with_additions
    conf = {"name": "x", "architecture": "not_there", "mapping": {}}
    path = os.path.join(root, "benchmark", "architectures", "not_there.py")
    with pytest.raises(spec.SpecError, match=path):
        spec.load_architecture(conf, root)
    with pytest.raises(spec.SpecError, match=path):
        spec.transformer_fields(conf, root)
    with pytest.raises(spec.SpecError, match="bad name"):
        spec.load_architecture({"architecture": "../harness/spec"}, root)
