"""Every cell's whole path at toy size on the CPU, through the functions
`benchmark/run.py` runs on the chip: cluster, lease, weights from the seed,
reference check, warm-up, loaded window, reduction to the last line. The
numbers mean nothing here (a CPU is no device metric); the control flow,
the counts and `correct` do."""

import argparse

import pytest

import bench_paths
import ray_tpu
from benchmark.harness import spec

BENCH = spec.load_benchmark()
RUN = bench_paths.load_run_module()
TINY = dict(vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, dtype="float32")
TOY_DEPLOYMENT = {"slots": 4, "max_concurrency": 8, "max_prompt_len": 64,
                  "max_new_tokens": 16, "eos_id": -1, "greedy": True}
TOY_SERVE = {
    "deployment": dict(TOY_DEPLOYMENT, weights_seed=0),
    "prompt_len": {"median": 20, "sigma": 0.9, "min": 4, "max": 64},
    "output_len": {"median": 8, "sigma": 0.7, "min": 2, "max": 16},
    "rate_per_s": 6.0, "ramp_s": 1.5, "tail_s": 3.0, "clients": 8,
    "pool": 24, "check": {"prompt_lens": [40, 33, 50, 64]},
    "trace_at_s": 0.5, "trace_s": 1.0}
TOY = {
    "internlm2-1.8b.train-4k": {"seq_len": 64, "rows": 2},
    "mistral-7b-v0.3.train-fsdp2tp2": {"seq_len": 64, "rows": 4},
    "internlm2-1.8b.chat-steady": TOY_SERVE,
    "internlm2-1.8b.batch-closed": TOY_SERVE,
}


@pytest.fixture(scope="module")
def cpu_cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


def _run(cell_name, trace, seconds=2.0, seed=2 ** 31 + 11):
    """-> (last line, the information line) of one toy-size run."""
    # a CPU "chip" count of 1 keeps the lease check meaningful; the mesh
    # of the sharded cell is built over the CPU's virtual devices
    cell = dict(spec.find_cell(BENCH, cell_name), chips=1)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    return bench_paths.run_cell_with_info(
        RUN, BENCH, cell, args, platform="cpu", field_overrides=TINY,
        traffic_overrides=TOY[cell_name])


@pytest.mark.parametrize("cell", sorted(TOY))
def test_cell_runs_end_to_end_at_toy_size(cpu_cluster, cell):
    line, info = _run(cell, trace=0,
                      seconds=3.0 if "chat" in cell else 2.0)
    assert line["correct"] is True, line
    # the check names the architecture module that judged the cell
    assert info["check"]["reference"] == "dense_gqa" and info["check"]["ok"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    declared = {m["name"] for m in spec.metrics_for(BENCH, cell,
                                                    "end_to_end")}
    assert set(line["metrics"]) == declared
    for name, m in line["metrics"].items():
        assert m["value"] > 0 and m["unit"]
    # every number the check compared, beside its limit, is the last key
    assert list(line)[-1] == "compared"
    assert sorted(line["compared"]) == (
        ["logits_rel_rms", "loss_abs_diff"] if "train" in cell else
        ["decode_logits_rel_rms", "prefill_logits_rel_rms",
         "served_tokens_not_the_references"]
        + ["tokens_made_minus_tokens_received"] * ("closed" in cell))
    assert all(v <= limit for v, limit in line["compared"].values())
    if "closed" in cell:
        # the rate is the engine's count over the replica's clock; the
        # old count of whole requests stands beside it and decides nothing
        old = line["tokens_whole_requests"]
        assert old["per_s"] == old["tokens"] / old["window_s"] > 0
        run = info["whole_run"]
        assert run["engine_tokens_out"] == run["client_tokens"] > 0
        assert info["checks"]["tokens_made_are_tokens_received"] is True
    else:
        assert "tokens_whole_requests" not in line
    if "chat" in cell:   # a fixed request count: rate x window
        assert line["attempted"] == round(TOY_SERVE["rate_per_s"] * 3.0)
    if "train" not in cell:
        # every serve run, traced or not, prints the engine's heartbeat
        # and the sleeper that ran beside the window (10 ms a sleep)
        assert info["slow_events"] == []
        sleeper = info["sleeper"]
        assert sleeper["sleeps"] >= 50
        assert 0.010 <= sleeper["longest_gap_s"] < 2.0
        assert 0 <= sleeper["longest_gap_at_s"] <= 3.0
        assert sleeper["gaps_over_1s"] == 0


@pytest.mark.parametrize("cell", ["internlm2-1.8b.train-4k",
                                  "internlm2-1.8b.chat-steady"])
def test_traced_run_reports_layer_metrics_and_refuses_a_deviceless_trace(
        cpu_cluster, cell):
    line, _ = _run(cell, trace=1, seconds=3.0 if "chat" in cell else 2.0)
    # readers that need a device trace return nothing on the CPU and are
    # left out; those fed by counters, spans and the host clock report
    assert "chip_worker_ready_s" in line["metrics"]
    assert "train_step_device_ms" not in line["metrics"]
    assert "decode_substep_ms.chat" not in line["metrics"]
    if "chat" in cell:
        for name in ("ttft_p50_ms", "ttft_p95_ms", "generator_late_p99_ms",
                     "decode_occupancy.chat", "handle_rtt_p50_ms",
                     "fetch_wait_ms_per_fetch.chat", "peak_hbm_gb.chat"):
            assert name in line["metrics"], name
        assert 0 < line["metrics"]["decode_occupancy.chat"]["value"] <= 100
    declared = {m["name"] for m in spec.metrics_for(BENCH, cell,
                                                    "per_layer")}
    assert set(line["metrics"]) <= declared
    # no operation ran on a TPU: such a traced run is never `correct`
    assert line["correct"] is False and not line["device"]["busy_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("token,logits,rel_err,want", [
    (2, [0.0, 1.0, 5.0], 0.01, True),     # the argmax
    (1, [0.0, 1.0, 5.0], 0.01, False),    # not the argmax, clear margin
    (1, [0.0, 4.99, 5.0], 0.01, True),    # top two within the error
    (0, [0.0, 4.99, 5.0], 0.0, False),    # no error allowed: exact argmax
])
def test_served_tokens_are_held_to_the_reference_argmax(token, logits,
                                                        rel_err, want):
    from benchmark.harness import serve_cell

    assert serve_cell._is_argmax(token, logits,
                                 {"rel_rms_error": rel_err}) is want


def test_the_serve_check_draws_its_own_reference_weights():
    """The reference computes with the float32 tree the initialiser makes
    from the seed, not with the tree the engine holds: a fault in how the
    replica stores its weights (here: the final norm's gain 5% up) is a
    fault of the program alone, and the check sees it. Until PR 31 both
    sides read `engine.params`, and this passed."""
    from benchmark.harness import serve_cell

    conf = spec.load_config(BENCH, "internlm2-1.8b")
    dep = {k: v for k, v in TOY_DEPLOYMENT.items() if k != "max_concurrency"}
    rep = serve_cell.BenchReplica(conf, platform="cpu",
                                  field_overrides=TINY, seed=7, **dep)
    try:
        good = rep.bench_check(7, [40, 33])
        assert good["ok"] and good["reference"] == "dense_gqa"
        assert all(r["prefill"]["rel_rms_error"] < 2e-4
                   and r["decode"]["rel_rms_error"] < 2e-4
                   for r in good["rows"])
        held = rep.engine.params
        rep.engine.params = dict(held, final_norm=held["final_norm"] * 1.05)
        bad = rep.bench_check(7, [40, 33])
        assert not bad["ok"]
        assert all(0.04 < r["prefill"]["rel_rms_error"] < 0.06
                   for r in bad["rows"])
        rep.engine.params = held
        # `--seed` draws the check's prompts and nothing of the model ...
        assert rep.bench_check(8, [40])["ok"]
        # ... and a reference drawn from another number than the
        # replica's weights is another model
        rep._bench_weights_seed = 8
        assert not rep.bench_check(7, [40])["ok"]
    finally:
        rep.engine.shutdown()
