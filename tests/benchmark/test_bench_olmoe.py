"""The OLMoE configuration, its architecture file, its cell and its
per-layer metrics (PR 27; PR 31): the cell at toy size through the
harness's own functions on the CPU, the required work against hand counts
at the published widths, the grouped-matmul and scope readers on made-up
traces, the objective compared term by term (the load-balance loss with
and without a weight), and the metrics of the dense train cells that this
cell JOINED by a list entry in `BENCHMARK.json` (its six copies under
`*.olmoe` / `olmoe_*` names went with their files in PR 31)."""

import argparse
import json
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
# the metrics of the dense train cells that the cell joined (each replaced
# a copy: `<name>.olmoe`, `olmoe_flash_attention_roofline`) ...
JOINED = ["train_step_device_ms", "train_mfu", "peak_hbm_gb.train",
          "flash_attention_step_share", "flash_attention_roofline",
          "chip_worker_ready_s"]
# ... and those that are its own, in the order they were appended
OWN = ["moe_grouped_matmul_step_share.olmoe", "moe_grouped_matmul_roofline",
       "moe_dispatch_step_share", "moe_load_max_over_mean"]
NEW_METRICS = JOINED + OWN


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
    assert callable(ARCH.reference_terms)
    assert CONF["objective"] == {"loss": 1.0}   # cross entropy alone


def test_new_layer_metrics_are_appended_after_the_engines_thirteen():
    """PR 24's thirteen found where they stand (in their order), this
    cell's own metrics after them, last; the metrics it joined list it
    last among their cells and keep what they moved."""
    names = [m["name"] for m in BENCH["per_layer"]]
    n = len(OWN)
    assert names[-n:] == OWN
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
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"][-1] == CELL
        assert (m["workloads"] == [CELL]) == (name in OWN)
        assert m["moves"] == ("setup_s" if name.startswith("chip_worker")
                              else "train_tokens_per_s")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["train_tokens_per_s"]["workloads"][-1] == CELL
    assert e2e["train_tokens_per_s"]["bound"] == 0.01
    assert len(BENCH["per_layer"]) == 39


def test_no_metric_is_a_copy_of_another_under_a_cells_name():
    """One list of cells a metric: no two per-layer entries name the same
    reader with the same parameters and move the same end-to-end metric
    (what the `*.olmoe` copies were; `.chat` / `.batch` pairs move
    different ones, which the contract has split), and no metric file
    says which cells report it."""
    seen = {}
    for m in BENCH["per_layer"]:
        f = spec.load_layer_metric(m["name"])
        assert "workloads" not in f, m["name"]
        params = {k: v for k, v in f.items() if k not in (
            "name", "what", "moves_note", "better", "layer")}
        key = json.dumps(params, sort_keys=True)
        assert key not in seen, (m["name"], seen.get(key))
        seen[key] = m["name"]
    for gone in ("train_step_device_ms.olmoe", "train_mfu.olmoe",
                 "peak_hbm_gb.olmoe", "flash_attention_step_share.olmoe",
                 "olmoe_flash_attention_roofline",
                 "chip_worker_ready_s.olmoe"):
        assert gone not in seen.values()
        with pytest.raises(spec.SpecError, match="missing"):
            spec.load_layer_metric(gone)


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
    assert "workloads" not in metric and CELL in entry["workloads"]
    read = spec.load_reader(metric)
    # on a parent's evidence (no trace of such operations, no program
    # sizes, no step counters) every reader returns nothing and does not
    # raise
    assert read({"trace": {}, "out": {}, "fields": {}, "conf": {},
                 "traffic": {}, "cell": {"chips": 1}, "peaks": None},
                metric) is None
    if "op_pattern" not in metric:
        return
    rx = re.compile(metric["op_pattern"])
    attention = ["tpu_custom_call:closed_call.9",
                 "tpu_custom_call:checkpoint.20",
                 "tpu_custom_call:rematted_computation.10",
                 "tpu_custom_call:shard_map.385"]   # per shard, on a mesh
    grouped = ["tpu_custom_call:ragged-dot-none",
               "tpu_custom_call:ragged-dot-none.11",
               "tpu_custom_call:ragged-dot-metadata.2"]
    mine, other = (grouped, attention) if "grouped_matmul" in name \
        else (attention, grouped)
    assert all(rx.search(k) for k in mine)
    assert not any(rx.search(k) for k in other)
    assert not rx.search("fusion.1") and not rx.search("copy.3")


def test_the_attention_pattern_reads_the_dense_fixture_as_before():
    """`^tpu_custom_call:(?!ragged-dot)` against the pattern the dense
    cells had until PR 31 (`^tpu_custom_call:`), on the trace recorded on
    the chip: the same operations, the same seconds, to the digit."""
    from benchmark.harness import xplane

    red = xplane.reduce_planes(xplane.load_planes(os.path.join(
        bench_paths.REPO, "benchmark", "fixtures",
        "train_tiny_v5e.xplane.pb.gz")))
    for name in ("flash_attention_step_share", "flash_attention_roofline"):
        pattern = spec.load_layer_metric(name)["op_pattern"]
        assert pattern == "^tpu_custom_call:(?!ragged-dot)"
        new = xplane.op_seconds_matching(red, pattern)
        assert new == xplane.op_seconds_matching(red, "^tpu_custom_call:")
        assert new > 0
    # with grouped matmuls in the program the old pattern would have taken
    # them for attention; the new one does not
    red["op_seconds"]["tpu_custom_call:ragged-dot-none.3"] = 1.0
    assert xplane.op_seconds_matching(red, pattern) == new
    assert xplane.op_seconds_matching(red, "^tpu_custom_call:") == new + 1


def test_scope_share_reads_a_made_up_trace_and_scope_map():
    """Device time by `jax.named_scope`: the operations whose scope path
    the pattern is found in, wherever in the path (forward, under the
    backward's `transpose(jvp())`, under the remat's `checkpoint`)."""
    from benchmark.harness import xplane

    metric = spec.load_layer_metric("moe_dispatch_step_share")
    assert metric["scope_pattern"] == r"moe\.(dispatch|combine)"
    assert not re.search(r"\.\d", json.dumps(
        {k: v for k, v in metric.items() if k != "what"}))   # no numbers
    read = spec.load_reader(metric)
    body = "jit(step)/jvp()/while/body/closed_call/"
    back = "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
    hlo = "\n".join([
        'HloModule jit_step',
        '  %fusion.455 = bf16[8,4]{1,0} fusion(bf16[8,4]{1,0} %p), '
        'kind=kLoop, calls=%fc, metadata={op_name="' + body
        + 'moe.dispatch/gather" source_file="moe.py" source_line=98}',
        '  fusion.412.remat = bf16[8,4]{1,0} fusion(%a), kind=kLoop, '
        'metadata={op_name="' + back + 'moe.combine/gather"}',
        '  ROOT %sort.140 = s32[8]{0} sort(%k), dimensions={0}, '
        'metadata={op_name="' + back + 'moe.dispatch/sort"}',
        '  %fusion.7 = f32[8]{0} fusion(%b), kind=kLoop, '
        'metadata={op_name="' + body + 'moe.experts/mul"}',
        '  %ragged-dot-none.3 = bf16[8,4]{1,0} custom-call(%x, %w), '
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="' + body + 'moe.experts/ragged_dot"}',
        '  %copy.3 = f32[8]{0} copy(%c)'])
    scopes = xplane.op_scopes(hlo)
    assert scopes == {
        "fusion.455": body + "moe.dispatch/gather",
        "fusion.412.remat": back + "moe.combine/gather",
        "sort.140": back + "moe.dispatch/sort",
        "fusion.7": body + "moe.experts/mul",
        "tpu_custom_call:ragged-dot-none.3": body + "moe.experts/ragged_dot"}
    ops = {"fusion.455": 0.10, "fusion.412.remat": 0.20, "sort.140": 0.02,
           "fusion.7": 0.30, "tpu_custom_call:ragged-dot-none.3": 0.9,
           "copy.3": 0.4, "fusion.999": 0.05}     # .999: no scope known
    ev = {"trace": {"op_seconds": ops, "busy_s": 2.0},
          "out": {"op_scopes": scopes}}
    assert read(ev, metric) == pytest.approx(100 * 0.32 / 2.0)
    assert xplane.scope_seconds_matching(ev["trace"], scopes,
                                         r"moe\.experts") \
        == pytest.approx(1.2)
    # nothing traced under the scope, no scope map, no device time:
    # nothing to read
    assert read({"trace": {"op_seconds": {"copy.3": 1.0}, "busy_s": 2.0},
                 "out": {"op_scopes": scopes}}, metric) is None
    assert read({"trace": ev["trace"], "out": {}}, metric) is None
    assert read({"trace": {"op_seconds": ops}, "out": ev["out"]},
                metric) is None


def test_kernel_roofline_takes_the_head_widths_from_fields():
    """Heads that are not hidden / heads wide (20 heads of 256 on a hidden
    size of 2048: 102.4), and a value head narrower than the query/key
    head: the required work follows `fields`, and falls back to hidden /
    heads where `fields` gives no width."""
    from benchmark.harness import flops

    metric = spec.load_layer_metric("flash_attention_roofline")
    read = spec.load_reader(metric)
    ops = {"tpu_custom_call:checkpoint.10": 0.5,
           "tpu_custom_call:ragged-dot-none": 9.0}

    def least(hd, vd=None, heads=16):
        f = flops.flash_attention_cost(4, heads, 4096, 4096, hd,
                                       v_head_dim=vd)["flops"]
        b = flops.flash_attention_cost(4, heads, 4096, 4096, hd,
                                       backward=True, v_head_dim=vd)["flops"]
        return 3 * 4 * (f + b) / 197e12      # 3 layers, 4 steps

    assert read(_evidence(ops), metric) == pytest.approx(
        100 * least(128) / 0.5, rel=1e-12)
    wide = _evidence(ops, n_heads=20, head_dim=256)
    assert wide["fields"]["d_model"] / 20 == 102.4
    assert read(wide, metric) == pytest.approx(
        100 * least(256, heads=20) / 0.5, rel=1e-12)
    latent = _evidence(ops, n_heads=20, head_dim=256, v_head_dim=128)
    assert read(latent, metric) == pytest.approx(
        100 * least(256, 128, heads=20) / 0.5, rel=1e-12)
    # a value head half as wide: forward QK^T 1 + PV 1/2, backward three
    # products of the query/key width and two of the value width, 3 + 1,
    # against 2 + 5 where the widths are equal
    assert least(256, 128) == pytest.approx(least(256) * 5.5 / 7)


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
    # the objective, term by term: the cross entropy and the load-balance
    # loss are each held to the reference's, and the total (the cell
    # trains on cross entropy alone) to the weighted sum the file states
    terms = check["objective"]["terms"]
    assert sorted(terms) == ["loss", "moe_aux"]
    assert all(t["ok"] and t["abs_diff"] <= 1e-4 for t in terms.values())
    assert 1.0 < terms["moe_aux"]["reference"] < 4.0     # k = 2 at balance
    assert check["objective"]["weighted_sum"]["ok"]
    assert check["objective"]["total"] == terms["loss"]["program"]
    # ... the last line carries every compared number beside its limit,
    # as its last key
    assert list(line)[-1] == "compared"
    assert sorted(line["compared"]) == [
        "logits_rel_rms", "loss_abs_diff", "moe_aux_abs_diff",
        "total_minus_weighted_sum_rel"]
    assert all(v <= lim for v, lim in line["compared"].values())
    # the step's own counters, means over the window's steps
    counters = info["step_metrics"]
    assert {"loss", "moe_aux", "moe_load_max_over_mean",
            "grad_norm"} <= set(counters)
    assert counters["moe_load_max_over_mean"] >= 1.0


def test_a_wrong_routing_rule_is_not_correct(cpu_cluster):
    """The program renormalising the top-k weights where the published
    config says not to: the reference does not, and the check says so."""
    line, info = _run(trace=0, moe_norm_topk=True)
    assert line["correct"] is False
    assert info["check"]["reference"] == "olmoe"
    assert info["check"]["logits"]["rel_rms_error"] > 2e-4


def _check(conf, seed=31, **fields):
    """`train_cell.check_against_reference` in this process, at toy size,
    on weights from the program's initialiser."""
    import jax

    from benchmark.harness import train_cell
    from ray_tpu.models.transformer import init_params

    over = dict(TINY, **fields)
    cfg = spec.build_transformer_config(conf, max_seq_len=64, **over)
    params = init_params(jax.random.key(seed), cfg)
    return train_cell.check_against_reference(
        params, cfg, dict(spec.transformer_fields(conf), **over), conf,
        ARCH, None, seed, 1, 64)


def test_the_load_balance_term_is_compared_with_and_without_a_weight():
    """With the recipe's weight 0.01 in the objective (the config file
    stating it, the published forward adding it) the check passes: it
    compares the cross entropy and `moe_aux` each with the reference's,
    not the total with the cross entropy. The cell's file as it stands
    (cross entropy alone) refuses a program that adds the term anyway."""
    recipe = dict(CONF, output_router_logits=True,
                  objective={"loss": 1.0, "moe_aux": 0.01})
    assert ARCH.fields(recipe)["moe_aux_weight"] == 0.01
    got = _check(recipe)
    obj = got["objective"]
    assert got["ok"] and obj["weighted_sum"]["ok"]
    assert obj["total"] == pytest.approx(
        obj["terms"]["loss"]["program"]
        + 0.01 * obj["terms"]["moe_aux"]["program"], abs=1e-5)
    assert obj["total"] - got["loss"] > 0.01     # what PR 27's check saw
    assert got["loss_abs_diff"] < 1e-4
    # the program weighing the term twice as much as the file states
    twice = _check(recipe, moe_aux_weight=0.02)
    assert not twice["ok"] and twice["logits"]["ok"]
    assert all(t["ok"] for t in twice["objective"]["terms"].values())
    assert twice["objective"]["weighted_sum"]["rel_diff"] > 1e-3
    # the cell's own file: total = loss, and a program that adds the
    # term is not what the file states
    assert _check(CONF)["ok"]
    assert not _check(CONF, moe_aux_weight=0.01)["ok"]


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-4),
                                         ("bfloat16", 0.25)])
def test_a_load_balance_term_defined_otherwise_is_not_correct(monkeypatch,
                                                              dtype, limit):
    """What `moe_aux`'s limit is there to catch: the program counting an
    expert's share over assignments instead of over tokens (1/k of the
    published term). Its limit is the architecture file's own
    (`TERM_ABS_TOL`: the gap swings with the tokens that flip between two
    experts, and at the cell's size read over the cross entropy's), and
    the planted fault is far outside it in either dtype."""
    from ray_tpu.models import transformer

    assert ARCH.TERM_ABS_TOL == {"moe_aux": {"float32": 1e-4,
                                             "bfloat16": 0.25}}
    sound = _check(CONF, dtype=dtype)
    term = sound["objective"]["terms"]["moe_aux"]
    assert sound["ok"] and term["tolerance"] == limit
    assert sound["loss_tolerance"] == (1e-4 if dtype == "float32" else 1e-2)
    real = transformer.loss_fn

    def shares_over_assignments(params, batch, cfg, mesh=None):
        total, metrics = real(params, batch, cfg, mesh)
        return total, dict(metrics,
                           moe_aux=metrics["moe_aux"] / cfg.moe_top_k)

    monkeypatch.setattr(transformer, "loss_fn", shares_over_assignments)
    planted = _check(CONF, dtype=dtype)
    term = planted["objective"]["terms"]["moe_aux"]
    assert not planted["ok"] and not term["ok"] and planted["logits"]["ok"]
    assert term["abs_diff"] > 4 * 0.25       # k = 2: half of about 2.4


def test_term_limits_reads_program_and_control_at_toy_size():
    """`benchmark/term_limits.py`, which read the limits on the chip: one
    seed at toy widths in float32 on the CPU. The program agrees with the
    reference to rounding; the control (the reference on weights rounded
    to the precision below, bfloat16 here) is three orders off in the
    logits and fails their limit."""
    from benchmark import term_limits
    from benchmark.harness.reference import LOGIT_REL_RMS_TOL

    toy = dict(CONF, hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=4, head_dim=16,
               intermediate_size=32, vocab_size=512, num_experts=8,
               num_experts_per_tok=2)
    traffic = dict(spec.load_traffic("train-4k"), seq_len=64,
                   param_dtype="float32")
    arch = spec.load_architecture(toy)
    # the cell states bf16 compute; the toy states float32 through `fields`
    real = arch.fields
    arch.fields = lambda conf: dict(real(conf), dtype="float32")
    try:
        got = term_limits.read_seed(spec.find_cell(BENCH, CELL), toy,
                                    traffic, arch, 2 ** 31 + 31, spec.ROOT)
    finally:
        arch.fields = real
    assert got["program_ok"] and sorted(got["program"]) == [
        "logits_rel_rms", "loss_abs_diff", "moe_aux_abs_diff"]
    assert got["program"]["logits_rel_rms"] < 1e-5
    assert got["control"]["logits_rel_rms"] > LOGIT_REL_RMS_TOL["float32"] \
        > 10 * got["program"]["logits_rel_rms"]
    assert got["control"]["moe_aux_abs_diff"] > got["program"][
        "moe_aux_abs_diff"]


def test_the_check_pays_one_reference_pass_a_row(monkeypatch):
    """`reference_terms` is asked about the row `reference_logits` has
    just run and reads that pass's auxiliary loss; other tokens or other
    weights are another pass."""
    import jax
    import numpy as np

    from ray_tpu.models.transformer import init_params

    passes = []
    forward = ARCH._forward
    monkeypatch.setattr(ARCH, "_forward", lambda *a, **k: (
        passes.append(1), forward(*a, **k))[1])
    assert _check(CONF)["ok"] and len(passes) == 1
    assert _check(CONF, seed=32)["ok"] and len(passes) == 2
    f = dict(spec.transformer_fields(CONF), **TINY)
    cfg = spec.build_transformer_config(CONF, max_seq_len=64, **TINY)
    params = init_params(jax.random.key(1), cfg)
    row = np.arange(33) % 512
    ARCH.reference_logits(params, row[:-1], f, CONF)
    same = ARCH.reference_terms(params, row, f, CONF)
    assert len(passes) == 3
    other = dict(params, final_norm=params["final_norm"] * 2)
    assert ARCH.reference_terms(other, row, f, CONF) == same   # the aux
    assert len(passes) == 4               # ... of a pass of its own
    assert ARCH.reference_terms(params, row[::-1].copy(), f, CONF) != same
    assert len(passes) == 5


def test_traced_toy_run_reports_only_what_the_cpu_can(cpu_cluster):
    line, _ = _run(trace=1)
    # readers that need a device trace return nothing on the CPU
    assert set(line["metrics"]) == {"peak_hbm_gb.train",
                                    "chip_worker_ready_s",
                                    "moe_load_max_over_mean"}
    assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert line["correct"] is False and not line["device"]["busy_s"]
