"""The six `serve entry` metrics that read the engine's stream ledger, its
pickup stamps and the entry leg's stamp (PR 40), all data: a traced
toy-size chat cell reports every one; on a program without those counters
(the parent of that PR) the reader finds nothing and does not raise; and
the `serve.stream_wait` spans of thirty waiting request threads own no
idle gap of the device, which stay the `engine.*` spans'."""

import argparse

import pytest

import bench_paths
import ray_tpu
from benchmark.harness import spec, xplane
from test_bench_cells_cpu import TINY, TOY_SERVE

BENCH = spec.load_benchmark()
RUN = bench_paths.load_run_module()
CHAT = "internlm2-1.8b.chat-steady"
# metric -> (num, den, scale) of `engine.stats`
SIX = {
    "stream_held_ms_per_pull.chat": ("stream_held_s", "stream_pulls", 1e3),
    "stream_wait_share.chat": ("stream_wait_s", "stream_open_s", 100.0),
    "stream_ready_pull_share.chat": ("stream_ready_pulls", "stream_tokens",
                                     100.0),
    "token_pickup_lag_ms.chat": ("stream_pickup_lag_s", "stream_tokens",
                                 1e3),
    "first_token_pickup_ms.chat": ("first_pickup_s", "first_pickups", 1e3),
    "serve_entry_leg_ms.chat": ("entry_leg_s", "entries", 1e3),
}
# what `engine.stats` held at the parent of PR 40, differenced over a
# window, as a run of that program hands it to the readers
PARENT_ENGINE = {"prefills": 214, "prefill_dispatches": 190,
                 "decode_steps": 38000, "chunks_dispatched": 9500,
                 "chunks_delivered": 9500, "fetches": 4200,
                 "tokens_out": 22000, "requests_done": 214,
                 "first_token_s": 29.5, "first_tokens": 214,
                 "deliver_wall_s": 1.9, "slow_s": 0.0, "slow_count": 0}
MS = 1_000_000  # ns


@pytest.fixture(scope="module")
def traced():
    """(last line, information line) of one traced toy-size chat cell."""
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        cell = dict(spec.find_cell(BENCH, CHAT), chips=1)
        args = argparse.Namespace(seed=2 ** 31 + 40, trace=1, seconds=3.0)
        yield bench_paths.run_cell_with_info(
            RUN, BENCH, cell, args, platform="cpu", field_overrides=TINY,
            traffic_overrides=TOY_SERVE)
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("name", sorted(SIX))
def test_the_traced_chat_cell_reports_the_metric_from_its_counters(traced,
                                                                   name):
    line, info = traced
    eng = info["engine"]
    num, den, scale = SIX[name]
    assert eng[den] > 0
    assert line["metrics"][name]["value"] == pytest.approx(
        scale * eng[num] / eng[den])
    assert line["metrics"][name]["value"] >= 0
    assert line["metrics"][name]["unit"] == ("%" if scale == 100 else "ms")


def test_the_windows_streams_go_to_the_two_states_and_nowhere_else(traced):
    line, info = traced
    eng = info["engine"]
    # (on the CPU no traced run is `correct`: it has to see the device)
    assert all(info["checks"].values()) and line["failed"] == 0
    # every stream that ended in the window was pulled to its end, one
    # token a call (`stream_next` ships one item), through a real handle
    assert eng["streams_closed"] > 0 and eng["entries"] > 0
    assert eng["stream_pulls"] == eng["stream_tokens"] \
        + eng["streams_closed"] - eng["streams_abandoned"]
    assert eng["stream_wait_s"] + eng["stream_held_s"] == pytest.approx(
        eng["stream_open_s"], rel=1e-6)
    assert 0 < line["metrics"]["stream_wait_share.chat"]["value"] < 100
    assert 0 <= line["metrics"]["stream_ready_pull_share.chat"]["value"] \
        <= 100
    # the stamp crossed two processes of one machine on the wall clock,
    # and a first token is picked up after the engine has it
    assert 0 < line["metrics"]["serve_entry_leg_ms.chat"]["value"] < 60e3
    assert line["metrics"]["first_token_pickup_ms.chat"]["value"] > 0


@pytest.mark.parametrize("name", sorted(SIX))
def test_on_the_parents_counters_the_metric_reads_nothing(name):
    metric = spec.load_layer_metric(name)
    read = spec.load_reader(metric)
    assert metric["reader"] == "engine_ratio"
    assert (metric["num"], metric["den"], metric["scale"]) == SIX[name]
    for engine in (dict(PARENT_ENGINE), {}, None):
        assert read({"out": {"counters": {"engine": engine}}},
                    metric) is None
    assert read({"out": {}}, metric) is None
    # a window in which no stream ended (batch-closed) has nothing either
    zeros = dict(PARENT_ENGINE, **{k: 0 for pair in SIX.values()
                                   for k in pair[:2]})
    assert read({"out": {"counters": {"engine": zeros}}}, metric) is None


@pytest.mark.parametrize("name", sorted(SIX))
def test_the_entry_is_found_by_name_and_joins_the_chat_cell_alone(name):
    """By name: no count, position or last place is pinned (later PRs
    append after these)."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    entry, f = by_name[name], spec.load_layer_metric(name)
    assert entry["workloads"] == [CHAT] and entry["layer"] == "serve entry"
    assert entry["moves"] == "tpot_p50_ms"
    assert entry["source"] == ("program_counter" if "ready_pull" in name
                               else "program_span")
    assert entry["better"] == ("higher" if name == "stream_wait_share.chat"
                               else "lower")
    assert f["moves_note"] == spec.load_layer_metric(
        "handle_rtt_p50_ms")["moves_note"] and f["what"]
    # the six host-clock metrics of the layer stay beside them
    assert {"handle_rtt_p50_ms", "chat_ttft_mean_ms", "ttft_p50_ms",
            "ttft_p90_ms", "ttft_p95_ms", "generator_late_p99_ms"} \
        <= {m["name"] for m in BENCH["per_layer"]
            if m["layer"] == "serve entry"}


def test_waiting_streams_own_no_idle_gap_of_the_device():
    """Made-up planes: the device idle in three gaps, thirty request
    threads each under a `serve.stream_wait` span that covers every gap
    whole (and is the innermost span over it), the engine's own spans
    over two of them: the owners are the `engine.*` spans, and the gap
    the engine has no span over is nobody's."""
    lo, hi = 0, 1000 * MS
    gaps = [(100 * MS, 140 * MS), (400 * MS, 410 * MS), (700 * MS, 760 * MS)]
    busy = xplane.subtract([(lo, hi)], gaps)
    planes = {"/device:TPU:0": {xplane.OP_LINE: [
        (f"fusion.{i}", s, e - s) for i, (s, e) in enumerate(busy)]}}
    planes["/host:CPU"] = {
        "llm-engine": [("engine.park", 90 * MS, 60 * MS),
                       ("engine.decode_dispatch", 395 * MS, 20 * MS)],
        **{f"actor-thread-{t}": [("serve.stream_wait", s - MS, e - s + 2 * MS)
                                 for s, e in gaps] for t in range(30)}}
    red = xplane.reduce_planes(planes, min_gap_ns=1)
    assert dict(map(tuple, red["idle_gaps"])) == pytest.approx({
        "engine.park": 0.040, "engine.decode_dispatch": 0.010,
        xplane.UNATTRIBUTED: 0.060})
    assert set(red["host_spans"]) == {"engine.park",
                                      "engine.decode_dispatch"}
    # ... and under the `engine.` prefix the same spans would own all three
    stolen = {p: {ln: [(n.replace("serve.", "engine."), s, d)
                       for n, s, d in evs] for ln, evs in lines.items()}
              for p, lines in planes.items()}
    assert [n for n, _ in xplane.reduce_planes(
        stolen, min_gap_ns=1)["idle_gaps"]] == ["engine.stream_wait"]
