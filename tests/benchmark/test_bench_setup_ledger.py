"""The seven per-layer metrics under `setup_s` that read the program's own
ledger of a deploy (PR 58), all data: each reads one key of
`counters.engine_total` through `out_field`; on a program without the key
(the parent of that PR) the reader finds nothing and does not raise; they
stand last in `per_layer`, in the five serve cells; and a traced toy-size
chat cell reports every one, with no compile request inside its window."""

import argparse
import os

import pytest

import bench_paths
import ray_tpu
from benchmark.harness import spec
from test_bench_cells_cpu import TINY, TOY_SERVE

BENCH = spec.load_benchmark()
RUN = bench_paths.load_run_module()
CHAT = "internlm2-1.8b.chat-steady"
SERVE_CELLS = [CHAT, "internlm2-1.8b.batch-closed",
               "solar-open2-250b.batch-closed-128",
               "phi-4-mini-flash-reasoning.reason-closed-64",
               "granite-4.0-h-micro.reason-closed-64"]
# metric -> (its key of `engine.stats`, unit, source, layer), in the order
# `BENCHMARK.json` lists them
SEVEN = {
    "replica_weights_s": ("weights_s", "s", "program_span", "serve entry"),
    "engine_init_s": ("engine_init_s", "s", "program_span", "engine"),
    "engine_warmup_s": ("warmup_s", "s", "program_span", "engine"),
    "programs_compiled": ("programs_compiled", "programs",
                          "program_counter", "device"),
    "compile_wait_s": ("compile_wait_s", "s", "program_counter", "device"),
    "compile_cache_load_s": ("cache_load_s", "s", "program_counter",
                             "device"),
    "trace_lower_s": ("trace_lower_s", "s", "program_counter", "device"),
}
# `engine.stats` whole, as a run of PR 58's parent hands it to the readers
PARENT_ENGINE_TOTAL = {"prefills": 300, "tokens_out": 31000,
                       "fetch_wall_s": 40.1, "slow_s": 0.0, "entries": 300}


@pytest.mark.parametrize("name", list(SEVEN))
def test_the_metric_is_one_key_of_the_engines_totals_and_data_alone(name):
    key, unit, source, layer = SEVEN[name]
    metric = spec.load_layer_metric(name)
    assert metric["reader"] == "out_field"
    assert metric["field"] == f"counters.engine_total.{key}"
    assert metric["moves"] == "setup_s" and metric["better"] == "lower"
    assert (metric["unit"], metric["source"], metric["layer"]) == \
        (unit, source, layer)
    read = spec.load_reader(metric)
    with_key = {"out": {"counters": {"engine_total": dict(
        PARENT_ENGINE_TOTAL, **{key: 12.5})}}}
    assert read(with_key, metric) == 12.5
    # the parent's evidence: no such key, and no counters at all (a train
    # cell's `out`)
    parent = {"out": {"counters": {"engine_total": PARENT_ENGINE_TOTAL}}}
    assert read(parent, metric) is None
    assert read({"out": {"tokens": 1}}, metric) is None


def test_the_seven_stand_last_in_the_five_serve_cells():
    last = BENCH["per_layer"][-7:]
    assert [m["name"] for m in last] == list(SEVEN)
    for m in last:
        key, unit, source, layer = SEVEN[m["name"]]
        assert m["workloads"] == SERVE_CELLS
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["source"], m["layer"], m["moves"],
                m["better"]) == (unit, source, layer, "setup_s", "lower")
    kinds = {c["name"]: spec.load_traffic(c["traffic"])["kind"]
             for c in BENCH["workloads"]}
    assert SERVE_CELLS == [c for c, kind in kinds.items() if kind != "train"]
    # what timed set-up from outside before them stays as it was
    ready = BENCH["per_layer"][0]
    assert ready["name"] == "chip_worker_ready_s"
    assert len(ready["workloads"]) == len(BENCH["workloads"]) == 10


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(last line, information line) of one traced toy-size chat cell. Its
    profile goes under a root of this fixture's own (the benchmark's files
    behind a link): the checkout's `.bench_out/trace/<cell>` is one
    directory, emptied by every traced run of the cell, and the workers of
    one session run three modules that trace this cell."""
    root = tmp_path_factory.mktemp("bench_root")
    os.symlink(os.path.join(spec.ROOT, "benchmark"), root / "benchmark")
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        cell = dict(spec.find_cell(BENCH, CHAT), chips=1)
        args = argparse.Namespace(seed=2 ** 31 + 58, trace=1, seconds=3.0)
        yield bench_paths.run_cell_with_info(
            RUN, BENCH, cell, args, root=str(root), platform="cpu",
            field_overrides=TINY, traffic_overrides=TOY_SERVE)
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("name", list(SEVEN))
def test_the_traced_chat_cell_reports_the_metric(traced, name):
    line, _ = traced
    m = line["metrics"][name]
    assert m["unit"] == SEVEN[name][1] and m["value"] >= 0
    if name in ("engine_warmup_s", "engine_init_s", "compile_wait_s",
                "replica_weights_s", "trace_lower_s"):
        assert m["value"] > 0


def test_the_window_asked_the_backend_for_nothing(traced):
    line, info = traced
    # the program's own count and the harness's, over the same window
    assert info["engine"]["compile_requests"] == 0
    assert info["engine"]["programs_compiled"] == 0
    assert info["engine"]["programs_loaded"] == 0
    assert info["compilations_in_window"] == 0
    assert info["checks"]["no_compilation_in_window"] is True
    # set-up's phases lie inside set-up: the replica's three, one after
    # the other on one thread, in less than the run took to its window
    setup = line["end_to_end"]["setup_s"]
    phases = sum(line["metrics"][n]["value"] for n in (
        "replica_weights_s", "engine_init_s", "engine_warmup_s"))
    assert 0 < phases < setup
    waited = line["metrics"]["compile_wait_s"]["value"]
    assert line["metrics"]["compile_cache_load_s"]["value"] <= waited < setup
    # the warm-up's own count stands in the window's totals' difference
    # as nothing: it ran before the mark
    assert info["engine"]["warmup_programs"] == 0
    assert info["engine"]["warmup_s"] == 0
