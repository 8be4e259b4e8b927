"""`BENCHMARK.json` and the data files it names hold together: permitted
names and units, every `moves` an end-to-end metric that every reporting
cell reports, every metric a file and a reader, every cell its files."""

import json
import os
import re

import pytest

import bench_paths
from benchmark.harness import spec

BENCH = spec.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
LAYER = {m["name"]: m for m in BENCH["per_layer"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
ONE_LINE = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_are_exactly_the_contracts():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert BENCH["command"][1] == "benchmark/run.py"


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_names_units_and_keys(metric):
    assert NAME.match(metric["name"]), metric["name"]
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric["name"] in E2E:
        allowed |= {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        allowed |= {"layer", "moves"}
        assert ONE_LINE.match(metric["layer"])
    assert set(metric) <= allowed
    for cell in metric.get("workloads", []):
        assert cell in CELLS


def test_no_two_metrics_cells_or_configs_share_a_name():
    for group in (BENCH["end_to_end"] + BENCH["per_layer"],
                  BENCH["workloads"], BENCH["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]
    assert E2E["setup_s"]["bound"] <= 0.1


@pytest.mark.parametrize("name", sorted(LAYER))
def test_moves_names_an_end_to_end_metric_every_reporting_cell_has(name):
    metric = LAYER[name]
    moved = E2E[metric["moves"]]
    for cell in _cells_of(metric):
        assert cell in _cells_of(moved), (name, cell, moved["name"])


@pytest.mark.parametrize("name", sorted(LAYER))
def test_every_layer_metric_has_its_file_and_its_reader(name):
    entry = LAYER[name]
    m = spec.load_layer_metric(name)
    for key in ("unit", "layer", "moves", "better", "source"):
        assert m[key] == entry[key], (name, key)
    # which cells report a metric is said in BENCHMARK.json alone, so a
    # new cell joins a metric that is there by a list entry and no file
    assert "workloads" not in m
    assert callable(spec.load_reader(m))
    assert m["what"]


def test_kernel_roofline_metrics_are_named_and_unitised_as_such():
    for name, m in LAYER.items():
        if "roofline" in name or "mfu" in name:
            assert m["unit"] == "%"
        if "roofline" in name:
            assert name.endswith("_roofline")
            assert spec.load_layer_metric(name)["bound"] in ("compute",
                                                             "memory")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_every_cell_has_its_files_and_reports_enough(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and ONE_LINE.match(cell["why"])
    conf = spec.load_config(BENCH, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    assert traffic["kind"] in spec.TRAFFIC_KINDS and traffic["why"]
    if traffic["kind"] == "train":
        want = conf["deployment"]["chips"]
        assert cell["chips"] == want
    e2e = [m["name"] for m in spec.metrics_for(BENCH, cell["name"],
                                               "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics_for(BENCH, cell["name"], "per_layer")


def test_a_pair_of_config_and_traffic_appears_once_and_few_take_four_chips():
    pairs = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(c["chips"] == 4 for c in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert ONE_LINE.match(entry["source"]) and ONE_LINE.match(entry["why"])
    assert entry["source"].startswith("https://huggingface.co/")
    assert entry["file"].startswith("benchmark/configs/")
    assert any(c["config"] == entry["name"] for c in BENCH["workloads"])
    conf = spec.load_config(BENCH, entry["name"])
    assert conf["source"] == entry["source"]
    # `reduced` in BENCHMARK.json is exactly the file's list of cuts, each
    # with the number before and after; no width is ever among them
    assert sorted(entry["reduced"]) == sorted(conf["reduced"])
    for key, cut in conf["reduced"].items():
        assert conf[key] == cut["run"] != cut["published"] and cut["why"]
        assert not re.search(r"(size|_dim|_rank|head|width)", key)
    fields = spec.transformer_fields(conf)
    assert fields["d_model"] == conf["hidden_size"]
    assert fields["d_model"] // fields["n_heads"] == conf["head_dim"]
    assert conf["assumed"] and conf["deployment"]


@pytest.mark.parametrize("name,hidden,heads,kv,ff,vocab,layers", [
    ("internlm2-1.8b", 2048, 16, 8, 8192, 92544, 24),
    ("mistral-7b-v0.3", 4096, 32, 8, 14336, 32768, 32)])
def test_published_widths_are_never_cut(name, hidden, heads, kv, ff, vocab,
                                        layers):
    conf = spec.load_config(BENCH, name)
    assert (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["intermediate_size"],
            conf["vocab_size"]) == (hidden, heads, kv, ff, vocab)
    assert conf["rope_theta"] == 1e6 and conf["rms_norm_eps"] == 1e-5
    assert conf["tie_word_embeddings"] is False
    assert conf["reduced"]["num_hidden_layers"]["published"] == layers


def test_files_under_paths_are_named_from_permitted_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(bench_paths.REPO, top)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), bench_paths.REPO)
                assert ok.match(rel), rel


def test_peaks_table_names_its_source_and_the_v5e():
    peaks = spec.load_peaks()
    assert "cloud.google.com" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


# ---- an architecture is a file ---------------------------------------------

DENSE_CONFIGS = ("internlm2-1.8b", "mistral-7b-v0.3")


def test_every_config_names_an_architecture_file_with_the_interface():
    for entry in BENCH["configs"]:
        conf = spec.load_config(BENCH, entry["name"])
        if entry["name"] in DENSE_CONFIGS:
            # the two dense configurations name none: the dense block
            assert "architecture" not in conf
            want = "dense_gqa"
        else:   # any other names its own file
            want = conf["architecture"]
            assert want != "dense_gqa" and spec.NAME_RE.match(want)
        assert spec.architecture_name(conf) == want
        mod = spec.load_architecture(conf)
        assert os.path.basename(mod.__file__) == want + ".py"
        for fn in spec.ARCHITECTURE_INTERFACE:
            assert callable(getattr(mod, fn))
        assert mod is spec.load_architecture(conf)   # once per process


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_a_stated_objective_names_terms_the_reference_gives(entry):
    """`objective` ({term: weight}) is optional; where a config file has
    one, `loss` is in it and every other weighted term is one the
    architecture's `reference_terms` can be asked for."""
    conf = spec.load_config(BENCH, entry["name"])
    if "objective" not in conf:
        assert entry["name"] in DENSE_CONFIGS
        return
    weights = conf["objective"]
    assert weights["loss"] == 1.0
    assert all(isinstance(w, (int, float)) for w in weights.values())
    if set(weights) - {"loss"}:
        assert callable(spec.load_architecture(conf).reference_terms)


def test_an_architecture_file_without_the_interface_is_refused(tmp_path):
    d = tmp_path / "benchmark" / "architectures"
    d.mkdir(parents=True)
    (d / "half.py").write_text(
        "def reference_logits(params, tokens, fields, conf, last=0):\n"
        "    return None\n")
    with pytest.raises(spec.SpecError, match="forward_flops_per_token"):
        spec.load_architecture({"architecture": "half"}, str(tmp_path))


def test_loading_an_architecture_imports_no_jax():
    """The driver process loads the module for its counts and must stay
    off JAX (`run.py` refuses a driver that imported it)."""
    import subprocess
    import sys

    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark.harness import spec; "
            "b = spec.load_benchmark(); "
            "c = spec.load_config(b, 'internlm2-1.8b'); "
            "a = spec.load_architecture(c); "
            "f = spec.transformer_fields(c); "
            "print(a.forward_flops_per_token(f, c, 4096), "
            "a.num_params(f, c), 'jax' in sys.modules)"
            % bench_paths.REPO)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True).stdout.split()
    assert out[2] == "False" and float(out[0]) > 1e9 and int(out[1]) > 1e9


_HEADS = {"hidden_size": 64, "num_attention_heads": 4, "name": "t",
          "mapping": {"hidden_size": "d_model",
                      "num_attention_heads": "n_heads"}}


@pytest.mark.parametrize("head_dim,carried,ok", [
    (16, False, True),    # what the program derives: nothing to carry
    (32, True, True),     # not hidden / heads, but the mapping carries it
    (32, False, False),   # ... and nothing does: the program would differ
    (None, False, True)])
def test_a_head_dim_is_refused_only_where_nothing_carries_it(head_dim,
                                                             carried, ok):
    conf = dict(_HEADS, mapping=dict(_HEADS["mapping"]))
    if head_dim is not None:
        conf["head_dim"] = head_dim
    if carried:
        conf["mapping"]["head_dim"] = "head_dim"
    if not ok:
        with pytest.raises(spec.SpecError, match="head_dim 32"):
            spec.transformer_fields(conf)
        return
    fields = spec.transformer_fields(conf)
    assert fields["d_model"] == 64 and fields["n_heads"] == 4
    assert fields.get("head_dim") == (32 if carried else None)
