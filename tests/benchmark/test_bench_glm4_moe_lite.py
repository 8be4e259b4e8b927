"""The GLM-4.7-Flash configuration, its architecture file, its cell and its
per-layer metrics (PR 32): the config file against the catalog row, the
required work against hand counts at the published widths, the cell at toy
size through the harness's own functions on the CPU (judged `correct`, and
NOT when the program computes one of the row's rules otherwise), and the
two new readers on made-up evidence.

Everything here is found BY NAME: no count of configurations, cells or
metrics, no position in a list, no last place is pinned, so that the next
cell's addition fails none of these."""

import argparse
import subprocess
import sys

import pytest

import bench_paths
import ray_tpu
from benchmark.harness import spec

BENCH = spec.load_benchmark()
RUN = bench_paths.load_run_module()
NAME = "glm-4.7-flash"
CELL = "glm-4.7-flash.train-4k-8rows"
CONF = spec.load_config(BENCH, NAME)
ARCH = spec.load_architecture(CONF)
# the catalog row's `config` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False,
    "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
CUT = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
TINY = dict(vocab_size=96, d_model=32, n_layers=3, n_heads=4, n_kv_heads=4,
            d_ff=24, head_dim=12, v_head_dim=16, rope_head_dim=4,
            q_lora_rank=10, kv_lora_rank=8, moe_experts=16,
            moe_held_experts=4, moe_first_expert=4, moe_top_k=2,
            moe_shared_d_ff=24, moe_dense_d_ff=40, dtype="float32")
JOINED = ["chip_worker_ready_s", "train_step_device_ms", "train_mfu",
          "peak_hbm_gb.train", "flash_attention_step_share",
          "moe_dispatch_step_share", "moe_load_max_over_mean",
          "moe_grouped_matmul_step_share.olmoe"]
OWN = ["mla_projection_step_share", "mtp_step_share",
       "moe_held_assignment_share", "mla_flash_attention_roofline",
       "moe_held_grouped_matmul_roofline"]


@pytest.fixture(scope="module")
def cpu_cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


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
        "zai-org/GLM-4.7-Flash/blob/main/config.json")
    assert CONF["architecture"] == "glm4_moe_lite"
    # the floors of the guide's section 4: a whole period and four expert
    # layers after the dense one, 8 routed experts, an eighth of the rows
    assert CONF["num_hidden_layers"] >= 1 + 4
    assert CONF["n_routed_experts"] == 8
    assert CONF["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    dep = CONF["deployment"]
    assert dep["chips_a_layer"] == 8 and dep["router_experts"] == 64
    assert dep["first_expert"] == 0 and dep["vocab_rows"] == [0, 19360]
    assert CONF["objective"] == {"loss": 1.0, "mtp_loss": 0.1}
    for key in ("mtp_weight", "router_bias", "rope_interleave", "weights",
                "initializer", "mtp_input"):
        assert CONF["assumed"][key], key


def test_fields_map_the_published_keys_onto_the_programs():
    f = spec.transformer_fields(CONF)
    assert (f["head_dim"], f["v_head_dim"], f["rope_head_dim"]) == (
        256, 256, 64)
    assert (f["q_lora_rank"], f["kv_lora_rank"], f["n_heads"]) == (
        768, 512, 20)
    assert (f["moe_experts"], f["moe_held_experts"], f["moe_top_k"]) == (
        64, 8, 4)
    assert (f["d_ff"], f["moe_shared_d_ff"], f["moe_dense_d_ff"]) == (
        1536, 1536, 10240)
    assert f["moe_scoring"] == "sigmoid" and f["moe_select_bias"]
    assert f["moe_norm_topk"] and f["moe_route_scale"] == 1.8
    assert f["moe_aux_weight"] == 0.0 and f["mtp_weight"] == 0.1
    assert f["n_layers"] == CONF["num_hidden_layers"]    # dense + expert
    assert f["moe_dense_layers"] == 1 and f["mtp_layers"] == 1
    assert f["vocab_size"] == 19360 and f["rope_theta"] == 1e6
    # plain Python values: `TransformerConfig(**fields)` is how the
    # harness builds it
    assert all(isinstance(v, (int, float, str, bool)) for v in f.values())
    cfg = spec.build_transformer_config(CONF)
    assert cfg.num_params == ARCH.num_params(f, CONF)
    with pytest.raises(ValueError, match="group-limited"):
        ARCH.fields(dict(CONF, n_group=8, topk_group=4))


def test_the_cell_and_the_metrics_it_reports_are_found_by_name():
    cell = spec.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "train-4k-8rows", 1)
    t, was = spec.load_traffic("train-4k-8rows"), \
        spec.load_traffic("train-4k")
    # train-4k with 8 rows; since PR 56 the Kimi cell's learning rate, at
    # which a seeded router stays at its balance, and the weights seed
    # whose held share stands at the balance the cell's `why` states
    differs = ("rows", "why", "name", "learning_rate", "weights_seed",
               "weights_seed_why")
    assert (t["rows"], was["rows"]) == (8, 4)
    assert (t["learning_rate"], was["learning_rate"]) == (1e-5, 3e-4)
    assert t["learning_rate"] == spec.load_traffic(
        "train-16k-2rows")["learning_rate"]
    assert "1e-5" in t["why"] and "0.125" in t["weights_seed_why"]
    assert {k: v for k, v in t.items() if k not in differs} \
        == {k: v for k, v in was.items() if k not in differs}
    e2e = {m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    reported = {m["name"]: m for m in spec.metrics_for(BENCH, CELL,
                                                       "per_layer")}
    assert set(reported) == set(JOINED + OWN)
    for name in OWN:
        entry, metric = reported[name], spec.load_layer_metric(name)
        assert entry["workloads"] == [CELL] and "workloads" not in metric
        assert entry["moves"] == metric["moves"] == "train_tokens_per_s"
        for key in ("unit", "better", "source", "layer"):
            assert entry[key] == metric[key], (name, key)
        assert callable(spec.load_reader(metric))
    assert spec.load_layer_metric("mla_projection_step_share")[
        "scope_pattern"] == "mla\\."
    assert spec.load_layer_metric("mtp_step_share")[
        "scope_pattern"] == "mtp\\."
    assert spec.load_layer_metric("moe_held_assignment_share")[
        "field"] == "step_metrics.moe_held_share"
    for name in ("mla_flash_attention_roofline",
                 "moe_held_grouped_matmul_roofline"):
        m = spec.load_layer_metric(name)
        assert m["unit"] == "%" and m["bound"] == "compute"


def test_loading_the_architecture_imports_no_jax_and_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark.harness import spec; "
            "b = spec.load_benchmark(); "
            "c = spec.load_config(b, %r); "
            "a = spec.load_architecture(c); "
            "f = spec.transformer_fields(c); "
            "print(a.forward_flops_per_token(f, c, 4096), "
            "a.num_params(f, c), 'jax' in sys.modules, "
            "any(m.startswith('ray_tpu') for m in sys.modules))"
            % (bench_paths.REPO, NAME))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True).stdout.split()
    assert out[2] == out[3] == "False"
    assert float(out[0]) > 9e8 and int(out[1]) > 6e8
    with open(ARCH.__file__) as f:
        assert "ray_tpu" not in f.read().replace("`ray_tpu/models/`", "")


# ---- required work, by hand --------------------------------------------------

def test_forward_flops_per_token_against_a_hand_count():
    f = spec.transformer_fields(CONF)
    n = f["n_layers"] - 1                               # expert layers
    proj = 2048 * 768 + 768 * 20 * 256 + 2048 * (512 + 64) \
        + 512 * 20 * (192 + 256) + 20 * 256 * 2048
    assert proj == 21_757_952                           # 21.76 M
    causal = 20 * 2 * (256 + 256) * (4096 + 1) / 2      # QK^T and PV, half
    routed = 4 * 3 * 2048 * 1536 * 8 / 64     # EXPECTED on this chip
    expert = 2 * (proj + 2048 * 64 + 3 * 2048 * 1536 + routed) + causal
    dense = 2 * (proj + 3 * 2048 * 10240) + causal
    head = 2 * 2048 * 19360
    assert expert == pytest.approx(114.0e6, rel=1e-3)
    assert dense == pytest.approx(211.3e6, rel=1e-3)
    assert head == pytest.approx(79.3e6, rel=1e-3)
    causal_mtp = 20 * 2 * 512 * 4096 / 2                # 4095 positions
    mtp = 4095 / 4096 * (2 * 2 * 2048 * 2048 + (expert - causal
                                                 + causal_mtp) + head)
    assert mtp == pytest.approx(210.0e6, rel=2e-3)
    got = ARCH.forward_flops_per_token(f, CONF, 4096)
    assert got == pytest.approx(dense + n * expert + head + mtp, rel=1e-12)
    # the issue's count at 1 + 6: 1.18 G, latent attention 58% of it
    six = ARCH.forward_flops_per_token(dict(f, n_layers=7), CONF, 4096)
    assert six == pytest.approx(1.18e9, rel=5e-3)
    attention = 8 * (2 * proj + causal)
    assert attention / six == pytest.approx(0.58, abs=0.01)
    # holding all 64 would be 8 times the routed work and nothing else
    whole = ARCH.layer_flops_per_token(dict(f, moe_held_experts=64), 4096,
                                       True)
    assert whole - expert == pytest.approx(2 * 7 * routed)


def test_num_params_is_what_this_chip_holds():
    f = spec.transformer_fields(CONF)
    n = f["n_layers"] - 1
    attn = 21_757_952 + 768 + 512 + 2 * 2048            # + 4 norm gains
    expert_layer = attn + 2048 * 64 + 64 + 9 * 3 * 2048 * 1536
    dense_layer = attn + 3 * 2048 * 10240
    assert expert_layer == pytest.approx(106.8e6, rel=1e-3)
    assert dense_layer == pytest.approx(84.7e6, rel=1e-3)
    slices = 2 * 19360 * 2048                           # embedding, head
    mtp = 2 * 2048 + 2 * 2048 * 2048 + expert_layer
    want = slices + dense_layer + n * expert_layer + 2048 + mtp
    assert ARCH.num_params(f, CONF) == want
    assert ARCH.num_params(dict(f, n_layers=7), CONF) == pytest.approx(
        920e6, rel=1e-3)                                # the issue's 1 + 6


# ---- the readers on made-up evidence -----------------------------------------

def _evidence(op_seconds, steps=4, held_share=0.125, **fields):
    f = dict(spec.transformer_fields(CONF), n_layers=7, **fields)
    return {"trace": {"op_seconds": op_seconds, "busy_s": 2.0,
                      "window_s": 2.0},
            "out": {"trace_steps": steps,
                    "step_metrics": {"moe_held_share": held_share}},
            "fields": f, "conf": CONF,
            "traffic": spec.load_traffic("train-4k-8rows"),
            "cell": spec.find_cell(BENCH, CELL),
            "peaks": spec.device_peaks("TPU v5 lite")}


def test_attention_roofline_counts_this_models_calls():
    from benchmark.harness import flops

    metric = spec.load_layer_metric("mla_flash_attention_roofline")
    read = spec.load_reader(metric)

    def call(seq):
        return sum(flops.flash_attention_cost(
            8, 20, seq, seq, 256, causal=True, backward=b,
            v_head_dim=256)["flops"] for b in (False, True))
    # 7 layers of the main stack at 4096, the module's once at 4095
    least = 4 * (7 * call(4096) + call(4095)) / 197e12
    ops = {"tpu_custom_call:checkpoint.10": 1.5,
           "tpu_custom_call:closed_call.3": 0.5,
           "tpu_custom_call:ragged-dot-none.3": 9.0,    # not attention
           "fusion.1": 9.0}
    assert read(_evidence(ops), metric) == pytest.approx(
        100 * least / 2.0, rel=1e-9)
    # forward 2 + backward 5 products of 2 x T x T x 256 / 2 a pair
    assert call(4096) == pytest.approx(
        7 * 2 * 4096 * 4096 * 256 / 2 * 160, rel=1e-12)
    # `kernel_roofline` would leave the module's call out
    plain = spec.load_reader({"reader": "kernel_roofline"})(
        _evidence(ops), metric)
    assert plain == pytest.approx(100 * 4 * 7 * call(4096) / 197e12 / 2.0)
    assert read(_evidence(ops, mtp_layers=0), metric) == pytest.approx(plain)
    assert read(_evidence({"fusion.1": 1.0}), metric) is None
    assert read(dict(_evidence(ops), peaks=None), metric) is None
    assert read(_evidence(ops, steps=0), metric) is None
    slow = dict(_evidence(ops), peaks={"bf16_flops_per_s": 197e12,
                                       "hbm_bytes_per_s": 1e9})
    with pytest.raises(ValueError, match="bound"):
        read(slow, metric)


def test_held_grouped_matmul_roofline_counts_the_rows_on_held_experts():
    metric = spec.load_layer_metric("moe_held_grouped_matmul_roofline")
    read = spec.load_reader(metric)
    m = 8 * 4096 * 4 * 0.125                            # 16,384 live rows
    flops = 3 * 3 * 2 * m * 2048 * 1536                 # a layer and step
    nbytes = 3 * 3 * 2 * (8 * 2048 * 1536 + m * 2048 + m * 1536)
    assert flops / nbytes == pytest.approx(614, rel=0.01)   # over the ridge
    layers = 6 + 1                  # expert layers + the module's
    least = layers * 4 * flops / 197e12
    ops = {"tpu_custom_call:ragged-dot-none": 0.2,
           "tpu_custom_call:ragged-dot-none.7": 0.3,
           "tpu_custom_call:checkpoint.9": 5.0,     # attention: not matched
           "fusion.412": 5.0}
    assert read(_evidence(ops), metric) == pytest.approx(
        100 * least / 0.5, rel=1e-9)
    # twice the share on held experts is twice the required rows; the
    # reader that counts every routed row against 64 experts' weights
    # would read eight times the work this chip was asked to do
    assert read(_evidence(ops, held_share=0.25), metric) == pytest.approx(
        2 * read(_evidence(ops), metric), rel=1e-9)
    other = spec.load_reader({"reader": "grouped_matmul_roofline"})
    assert other(_evidence(ops), metric) > 6 * read(_evidence(ops), metric)
    # nothing matched, no counter, no peaks, no steps: nothing to read
    assert read(_evidence({"fusion.1": 1.0}), metric) is None
    no_counter = _evidence(ops)
    no_counter["out"]["step_metrics"] = {}
    assert read(no_counter, metric) is None
    assert read(dict(_evidence(ops), peaks=None), metric) is None
    assert read(_evidence(ops, steps=0), metric) is None
    with pytest.raises(ValueError, match="bound"):
        read(_evidence(ops, held_share=0.01), metric)


def test_the_scope_and_counter_metrics_read_made_up_evidence():
    ev = _evidence({"fusion.1": 0.3, "fusion.2": 0.2, "fusion.3": 0.5,
                    "convolution.4": 0.4})
    ev["out"]["op_scopes"] = {
        "fusion.1": "jit(step)/jvp(mla.q)/dot_general",
        "fusion.2": "jit(step)/transpose(jvp(mtp.block))/checkpoint/"
                    "mla.kv/dot_general",
        "fusion.3": "jit(step)/mtp.head/reduce",
        "convolution.4": "jit(step)/moe.experts/ragged_dot"}

    def value(name):
        m = spec.load_layer_metric(name)
        return spec.load_reader(m)(ev, m)
    assert value("mla_projection_step_share") == pytest.approx(25.0)
    assert value("mtp_step_share") == pytest.approx(35.0)   # overlap: .2
    assert value("moe_held_assignment_share") == 0.125
    ev["out"]["op_scopes"] = {}
    assert value("mtp_step_share") is None


# ---- the cell at toy size ------------------------------------------------------

def _run(trace, seconds=2.0, seed=2 ** 31 + 32, **fields):
    cell = spec.find_cell(BENCH, CELL)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    return bench_paths.run_cell_with_info(
        RUN, BENCH, cell, args, platform="cpu",
        field_overrides=dict(TINY, **fields),
        traffic_overrides={"seq_len": 32, "rows": 2})


def test_cell_runs_end_to_end_at_toy_size_judged_by_its_reference(
        cpu_cluster):
    line, info = _run(trace=0)
    assert line["correct"] is True, line
    check = info["check"]
    assert check["reference"] == "glm4_moe_lite" and check["ok"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    terms = check["objective"]["terms"]
    assert sorted(terms) == ["loss", "mtp_loss"]
    assert all(t["ok"] and t["abs_diff"] <= 1e-4 for t in terms.values())
    assert check["objective"]["weighted_sum"]["ok"]
    assert check["objective"]["weighted_sum"]["weights"] == {
        "loss": 1.0, "mtp_loss": 0.1}
    assert sorted(line["compared"]) == [
        "logits_rel_rms", "loss_abs_diff", "mtp_loss_abs_diff",
        "total_minus_weighted_sum_rel"]
    assert all(v <= lim for v, lim in line["compared"].values())
    counters = info["step_metrics"]
    assert {"loss", "mtp_loss", "moe_held_share", "moe_load_max_over_mean",
            "grad_norm"} <= set(counters)
    assert 0.0 < counters["moe_held_share"] < 1.0


def test_a_wrong_rule_is_not_correct_through_the_whole_path(cpu_cluster):
    line, info = _run(trace=0, moe_scoring="softmax")
    assert line["correct"] is False
    assert info["check"]["reference"] == "glm4_moe_lite"
    assert info["check"]["logits"]["rel_rms_error"] > 2e-4


def test_traced_toy_run_reports_only_what_the_cpu_can(cpu_cluster):
    line, _ = _run(trace=1)
    assert {"chip_worker_ready_s", "moe_held_assignment_share",
            "moe_load_max_over_mean"} <= set(line["metrics"])
    for name in ("mla_flash_attention_roofline", "mtp_step_share",
                 "moe_held_grouped_matmul_roofline", "train_mfu"):
        assert name not in line["metrics"]       # no device trace here
    assert set(line["metrics"]) <= set(JOINED + OWN)
    assert line["correct"] is False              # no operation on a TPU


@pytest.mark.parametrize("rule,fields,shows_in", [
    ("softmax for sigmoid", {"moe_scoring": "softmax"}, "logits"),
    ("no renormalisation", {"moe_norm_topk": False}, "logits"),
    ("no scaling factor", {"moe_route_scale": 1.0}, "logits"),
    ("prediction weight wrong", {"mtp_weight": 0.3}, "total"),
])
def test_a_rule_computed_otherwise_is_not_correct(rule, fields, shows_in):
    """The harness's own comparison (`check_against_reference`, what a
    run's `correct` rests on) in this process, on a program configured to
    another rule than the published one."""
    check = _check(**fields)
    assert not check["ok"], rule
    if shows_in == "logits":
        assert check["logits"]["rel_rms_error"] > 2e-4
    else:
        assert check["logits"]["ok"]
        assert not check["objective"]["weighted_sum"]["ok"]


def test_rotary_columns_paired_by_halves_are_not_correct(monkeypatch):
    """Latent attention that turns the stored rotary columns by halves
    (what `_rope` does with what it is handed) and not as pairs (2i, 2i+1)
    is told by the logits."""
    import jax.numpy as jnp

    from ray_tpu.models import transformer

    real = transformer._rope

    def by_halves(x, positions, theta):    # undo `_latent_qkv`'s pairing
        n = x.shape[-1] // 2
        stored = jnp.stack([x[..., :n], x[..., n:]], axis=-1)
        return real(stored.reshape(x.shape), positions, theta)
    monkeypatch.setattr(transformer, "_rope", by_halves)
    bad = _check(seed=34)
    assert not bad["ok"] and bad["logits"]["rel_rms_error"] > 2e-4


def _check(params_edit=None, seed=31, **fields):
    """`train_cell.check_against_reference` in this process, at toy size,
    on weights from the program's initialiser (edited by ``params_edit``)."""
    import jax

    from benchmark.harness import train_cell
    from ray_tpu.models.transformer import init_params

    over = dict(TINY, **fields)
    cfg = spec.build_transformer_config(CONF, max_seq_len=32, **over)
    params = init_params(jax.random.key(seed), cfg)
    if params_edit:
        params = params_edit(params)
    return train_cell.check_against_reference(
        params, cfg, dict(spec.transformer_fields(CONF), **over), CONF,
        ARCH, None, seed, 1, 32)


def _with_bias(params):
    import jax

    def bias(stack, key):
        b = 0.5 * jax.random.normal(jax.random.key(key),
                                    stack["router_bias"].shape)
        return dict(stack, router_bias=b)
    return dict(params, layers=bias(params["layers"], 1),
                mtp=dict(params["mtp"],
                         layers=bias(params["mtp"]["layers"], 2)))


def test_the_bias_counted_into_the_weights_is_not_correct(monkeypatch):
    """With a bias that is not zero the check passes for the program as it
    is, and fails for a router that weighs the kept experts by score +
    bias (the selection alone may see the bias)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe

    good = _check(_with_bias)
    assert good["ok"] and good["logits"]["rel_rms_error"] < 2e-4

    def biased_weights(x, router, cfg, bias=None):
        probs, _, top_i = route(x, router, cfg, bias)
        scores = jax.nn.sigmoid(jnp.dot(
            x, router, precision=jax.lax.Precision.HIGHEST)) + bias
        top_p = jnp.take_along_axis(scores, top_i, axis=-1)
        top_p = top_p / top_p.sum(-1, keepdims=True) * cfg.moe_route_scale
        return probs, top_p, top_i
    route = moe.route
    monkeypatch.setattr(moe, "route", biased_weights)
    bad = _check(_with_bias, seed=32)
    assert not bad["ok"] and bad["logits"]["rel_rms_error"] > 2e-4


def test_a_prediction_module_defined_otherwise_is_not_correct(monkeypatch):
    """The module fed the next token's embedding where the token after
    next's target is compared is one thing; fed the CURRENT token's
    embedding it is another model, and `mtp_loss` says so while the logits
    and the cross entropy still agree."""
    from ray_tpu.models import transformer

    real = transformer._mtp_loss

    def shifted(params, x, targets, mask, cfg, mesh):
        import jax.numpy as jnp
        wrong = jnp.roll(targets, 1, axis=1)     # Emb(t_i), not t_{i+1}
        loss, stats = real(params, x, wrong, mask, cfg, mesh)
        return loss, stats
    assert _check()["ok"]
    monkeypatch.setattr(transformer, "_mtp_loss", shifted)
    bad = _check(seed=33)
    assert not bad["ok"] and bad["logits"]["ok"]
    terms = bad["objective"]["terms"]
    assert terms["loss"]["ok"] and not terms["mtp_loss"]["ok"]
