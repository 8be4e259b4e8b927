"""The harness takes additions as data: in a temporary copy, a new cell is
one config file, one traffic file, one layer-metric file with its reader
and entries in `BENCHMARK.json`; a new ARCHITECTURE is one more file,
`architectures/<name>.py` (its plain reference and the work its forward
requires), named by its config file. An OBJECTIVE of more than one term is
the architecture file's `reference_terms` and the config file's
`objective`; a cell JOINS a metric that is there by a list entry in
`BENCHMARK.json`. No file that was there is touched, the new cells run
through the harness's own functions at toy size, and the named module is
what judges and what counts."""

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
# An architecture whose objective has two terms beside the cross entropy,
# under names of its own: `skip`, the cross entropy of each position's
# logits against the token AFTER next, and `z`, the mean log partition.
TOY_TERMS = TOY_ARCH + '''

LOGIT_SCALE = FLOP_SCALE = 1.0


def reference_terms(params, tokens, fields, conf):
    import jax
    import jax.numpy as jnp

    logits = reference_logits(params, tokens[:-1], fields, conf)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits[:-1], jnp.asarray(tokens[2:])[:, None], axis=-1)[:, 0]
    return {"skip": float(jnp.mean(logz[:-1] - gold)),
            "z": float(jnp.mean(logz))}
'''
TOY_TERMS_OBJECTIVE = {"loss": 1.0, "skip": 0.3, "z": 0.01}
# A sparse architecture as files: the OLMoE block under another name, at
# toy widths, trained as its recipe has it: cross entropy plus 0.01 x the
# load-balance loss. The program reports that term as `moe_aux`.
TOY_MOE = '''"""A toy sparse architecture: the OLMoE block by another name."""
import os

from benchmark.harness import spec

_olmoe = spec.load_architecture({"architecture": "olmoe"}, os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
fields = _olmoe.fields
reference_logits = _olmoe.reference_logits
reference_terms = _olmoe.reference_terms
forward_flops_per_token = _olmoe.forward_flops_per_token
num_params = _olmoe.num_params
'''
TOY_MOE_CONF = {
    "source": "https://example.org/toy-moe/config.json",
    "architecture": "toy_moe", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 32, "vocab_size": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": False,
    "output_router_logits": True, "router_aux_loss_coef": 0.01,
    "objective": {"loss": 1.0, "moe_aux": 0.01},
    "reduced": {}, "assumed": {}, "deployment": {"chips": 1}}


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
    toy_arch_conf = {
        "source": "https://example.org/toy-arch/config.json", "width": 64,
        "depth": 2, "heads": 4, "kv_heads": 2, "head_dim": 16, "ff": 128,
        "vocab_size": 512, "rope_theta": 10000.0, "eps": 1e-5,
        "reduced": {}, "assumed": {}, "deployment": {"chips": 1}}
    for name, text, conf in (
            ("toy_terms", TOY_TERMS, dict(
                toy_arch_conf, architecture="toy_terms",
                objective=TOY_TERMS_OBJECTIVE)),
            ("toy_moe", TOY_MOE, TOY_MOE_CONF)):
        with open(os.path.join(b, "architectures", name + ".py"), "w") as f:
            f.write(text)
        with open(os.path.join(b, "configs", name + ".json"), "w") as f:
            json.dump(conf, f)
    with open(os.path.join(b, "traffic", "train-toy.json"), "w") as f:
        json.dump({"kind": "train", "seq_len": 32, "rows": 2,
                   "param_dtype": "float32", "mu_dtype": "float32",
                   "learning_rate": 1e-3, "weights_seed": 0,
                   "attention_impl": "auto",
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
    for name in ("toy_terms", "toy_moe"):
        bench["configs"].append({
            "name": name, "source": f"https://example.org/{name}/"
            "config.json", "file": f"benchmark/configs/{name}.json",
            "reduced": [], "why": "a toy objective of more than one term"})
    bench["workloads"].append({
        "name": "toy_moe.train-toy", "config": "toy_moe",
        "traffic": "train-toy", "chips": 1, "why": "a toy"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"] += ["toy-dense.train-toy", "toy_moe.train-toy"] \
                + [a + ".train-toy" for a in TOY_ARCHS]
    # the toy cells JOIN metrics that are there: a list entry each
    for m in bench["per_layer"]:
        if m["name"] in ("train_mfu", "train_step_device_ms",
                         "peak_hbm_gb.train"):
            m["workloads"].append("toy-dense.train-toy")
        if m["name"] in ("moe_load_max_over_mean", "peak_hbm_gb.train"):
            m["workloads"].append("toy_moe.train-toy")
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
        [f"benchmark/architectures/{a}.py"
         for a in list(TOY_ARCHS) + ["toy_terms", "toy_moe"]]
        + [f"benchmark/configs/{a}.json"
           for a in list(TOY_ARCHS) + ["toy_terms", "toy_moe"]]
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


def _run(root, cell_name, trace, **fields):
    """-> (last line, the information line) of one toy-size run."""
    run = bench_paths.load_run_module()
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, cell_name)
    args = argparse.Namespace(seed=5, seconds=1.5, trace=trace)
    return bench_paths.run_cell_with_info(
        run, bench, cell, args, root=root, platform="cpu",
        field_overrides=dict({"dtype": "float32"}, **fields))


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
    # ... whose objective is the cross entropy and nothing else
    assert list(info["check"]["objective"]["terms"]) == ["loss"]
    assert "weighted_sum" not in info["check"]["objective"]
    # the step's own counters reach the line: means over the window
    assert info["steps"] >= 1 and {"loss", "perplexity", "grad_norm"} <= set(
        info["step_metrics"])
    assert info["step_metrics"]["loss"] == pytest.approx(6.2, abs=0.5)
    # of the metrics the cell joined by a list entry, a traced CPU run
    # reports the one that needs no device trace
    assert "peak_hbm_gb.train" in line["metrics"]


def test_an_added_cell_joins_a_metric_by_a_list_entry_alone(
        copy_with_additions):
    """`train_mfu` and `train_step_device_ms` for the toy cell: no metric
    file was added or edited for it (`test_nothing_that_was_there_is_
    edited`), the entry's list names it, and the readers read its
    evidence."""
    root, _ = copy_with_additions
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, "toy-dense.train-toy")
    names = [m["name"] for m in spec.metrics_for(bench, cell["name"],
                                                 "per_layer")]
    assert names == ["train_step_device_ms", "train_mfu",
                     "peak_hbm_gb.train", "toy_steps_per_s"]
    for name in names[:3]:
        assert "workloads" not in spec.load_layer_metric(name, root)
    conf = spec.load_config(bench, "toy-dense", root)
    got = spec.read_layer_metrics(bench, cell["name"], {
        "trace": {"window_s": 2.0, "busy_s": 1.8},
        "out": {"trace_steps": 3, "steps": 9, "window_s": 3.0,
                "program_argument_bytes": 10 ** 9,
                "program_temp_bytes": 10 ** 9},
        "traffic": {"rows": 2, "seq_len": 32}, "conf": conf,
        "fields": spec.transformer_fields(conf, root), "root": root,
        "peaks": spec.device_peaks("TPU v5 lite"), "cell": cell}, root)
    assert got["train_step_device_ms"] == {"value": 600.0, "unit": "ms"}
    assert got["peak_hbm_gb.train"]["value"] == 2.0
    assert 0 < got["train_mfu"]["value"] < 100
    assert got["toy_steps_per_s"]["value"] == 3.0
    # the cells that were there still report it, and only they and the new
    listed = {m["name"]: m["workloads"] for m in bench["per_layer"]}
    was = {m["name"]: m["workloads"]
           for m in spec.load_benchmark()["per_layer"]}
    assert listed["train_mfu"] == was["train_mfu"] + ["toy-dense.train-toy"]


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


# ---- an objective of more than one term ------------------------------------

def test_a_two_term_objective_is_files_and_list_entries(copy_with_additions,
                                                        cpu_cluster):
    """`toy_moe.train-toy`: an architecture file, a config file that states
    `objective`, the traffic file and the list entries are ALL that was
    added. It trains on cross entropy + 0.01 x the load-balance loss and
    is `correct`: each term is held to the reference's, the total to the
    weighted sum. With the second term's weight wrong in the program
    (0.02) it is not."""
    root, _ = copy_with_additions
    line, info = _run(root, "toy_moe.train-toy", trace=0)
    obj = info["check"]["objective"]
    assert info["check"]["reference"] == "toy_moe"
    assert line["correct"] is True and info["check"]["ok"]
    assert sorted(obj["terms"]) == ["loss", "moe_aux"]
    assert obj["weighted_sum"]["weights"] == {"loss": 1.0, "moe_aux": 0.01}
    assert obj["total"] > obj["terms"]["loss"]["program"] + 0.01
    assert "moe_load_max_over_mean" in info["step_metrics"]
    wrong, winfo = _run(root, "toy_moe.train-toy", trace=0,
                        moe_aux_weight=0.02)
    wobj = winfo["check"]["objective"]
    assert wrong["correct"] is False and not wobj["weighted_sum"]["ok"]
    assert all(t["ok"] for t in wobj["terms"].values())   # the terms agree
    assert wrong["compared"]["total_minus_weighted_sum_rel"][0] > 1e-3
    traced, _ = _run(root, "toy_moe.train-toy", trace=1)
    assert set(traced["metrics"]) == {"peak_hbm_gb.train",
                                      "moe_load_max_over_mean"}


def _toy_loss_fn(weights=(0.3, 0.01), report=("skip", "z"), skew=0.0):
    """A program's `loss_fn` with two further terms: what it adds to the
    total, which of them it reports, and by how much `skip` is off."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer

    real = transformer.loss_fn

    def loss_fn(params, batch, cfg, mesh=None):
        total, metrics = real(params, batch, cfg, mesh)
        toks = batch["tokens"]
        logits = transformer.forward(params, toks[:, :-1], cfg, mesh)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits[:, :-1], toks[:, 2:, None],
                                   axis=-1)[..., 0]
        terms = {"skip": jnp.mean(logz[:, :-1] - gold) + skew,
                 "z": jnp.mean(logz)}
        total = total + weights[0] * terms["skip"] + weights[1] * terms["z"]
        return total, dict(metrics, **{k: terms[k] for k in report})

    return loss_fn


@pytest.mark.parametrize("program,correct,fault", [
    ({}, True, None),
    ({"weights": (0.1, 0.01)}, False, "weighted_sum"),    # a wrong weight
    ({"report": ("skip",)}, False, "z"),      # a term it does not report
    ({"skew": 1e-3}, False, "skip")])         # a term off by ten tolerances
def test_an_objective_is_judged_by_its_named_terms(copy_with_additions,
                                                   monkeypatch, program,
                                                   correct, fault):
    """`toy_terms`: `reference_terms` of two names, a toy `loss_fn` metric
    for each. The check compares `loss`, `skip` and `z` each with the
    reference's and the total with the weighted sum the file states."""
    import jax

    from benchmark.harness import train_cell
    from ray_tpu.models import transformer

    root, _ = copy_with_additions
    conf = spec.load_config(spec.load_benchmark(root), "toy_terms", root)
    arch = spec.load_architecture(conf, root)
    assert conf["objective"] == TOY_TERMS_OBJECTIVE
    fields = dict(spec.transformer_fields(conf, root), dtype="float32")
    cfg = spec.build_transformer_config(conf, root, max_seq_len=32,
                                        dtype="float32")
    params = transformer.init_params(jax.random.key(11), cfg)
    monkeypatch.setattr(transformer, "loss_fn", _toy_loss_fn(**program))
    got = train_cell.check_against_reference(
        params, cfg, fields, conf, arch, None, 11, 2, 32)
    obj = got["objective"]
    assert got["ok"] is correct and got["logits"]["ok"]
    assert sorted(obj["terms"]) == ["loss", "skip", "z"]
    assert obj["terms"]["loss"]["ok"]        # the cross entropy, not the
    assert obj["total"] > got["loss"] + 0.5  # total, is what is compared
    bad = [n for n, t in obj["terms"].items() if not t["ok"]] \
        + ([] if obj["weighted_sum"]["ok"] else ["weighted_sum"])
    assert bad == ([] if correct else
                   ["z", "weighted_sum"] if fault == "z" else [fault])
    if fault == "z":
        assert obj["terms"]["z"]["program"] is None
        assert obj["weighted_sum"]["unknown_terms"] == ["z"]
