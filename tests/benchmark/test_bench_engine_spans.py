"""The per-layer metrics that read the engine's own spans, ledger and
request stamps (PR 24): a traced toy-size serve cell reports every one of
them, they agree with what the benchmark's wrapper counts from outside,
and on a program without those counters (the parent of that PR) their
readers find nothing and do not raise."""

import argparse
import json

import pytest

import bench_paths
import ray_tpu
from benchmark.harness import spec
from test_bench_cells_cpu import TINY, TOY_SERVE   # the toy sizes, once

BENCH = spec.load_benchmark()
RUN = bench_paths.load_run_module()
CHAT, BATCH = "internlm2-1.8b.chat-steady", "internlm2-1.8b.batch-closed"
NEW = {
    CHAT: ["engine_queue_wait_ms.chat", "engine_first_token_ms.chat",
           "chunks_ahead_at_admit.chat", "prefill_group_size.chat",
           "prefill_useful_share.chat", "fetch_lock_wait_ms_per_fetch.chat",
           "chunks_per_fetch.chat", "sched_dispatch_share.chat",
           "sched_park_cap_share.chat", "engine_stall_s.chat"],
    BATCH: ["prefill_useful_share.batch", "sched_dispatch_share.batch",
            "engine_stall_s.batch"],
}
# what `engine.stats` held before PR 24, as a traced run of that program
# hands it to the readers
PARENT_ENGINE = {"prefills": 14, "prefill_dispatches": 11,
                 "decode_steps": 144, "fetches": 10, "tokens_out": 900,
                 "requests_done": 12, "fetch_wall_s": 0.4,
                 "cap_stalls": 3, "dispatch_wall_s": 3.3}


@pytest.fixture(scope="module")
def cpu_cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def traced(cpu_cluster):
    """{cell: (last line, the information line's `trace` object)} of one
    traced toy-size run of each serve cell."""
    import contextlib
    import io

    out = {}
    for name in (CHAT, BATCH):
        cell = dict(spec.find_cell(BENCH, name), chips=1)
        args = argparse.Namespace(seed=2 ** 31 + 24, trace=1,
                                  seconds=3.0 if name == CHAT else 2.0)
        printed = io.StringIO()
        with contextlib.redirect_stdout(printed):
            line = RUN.run_cell(BENCH, cell, args, platform="cpu",
                                field_overrides=TINY,
                                traffic_overrides=TOY_SERVE)
        info = [json.loads(l) for l in printed.getvalue().splitlines()
                if l.startswith("{")]
        out[name] = (line, next(i for i in info if i.get("trace")))
    return out


@pytest.mark.parametrize("cell", [CHAT, BATCH])
def test_a_traced_serve_cell_reports_every_new_engine_metric(traced, cell):
    line, info = traced[cell]
    got = {n: line["metrics"][n]["value"] for n in NEW[cell]
           if n in line["metrics"]}
    assert sorted(got) == sorted(NEW[cell])
    kind = cell.rsplit("-", 1)[0].rsplit(".", 1)[1]   # chat | batch
    assert 0 < got[f"prefill_useful_share.{kind}"] <= 100
    assert 0 <= got[f"sched_dispatch_share.{kind}"] <= 100
    assert got[f"engine_stall_s.{kind}"] == 0
    if cell == CHAT:
        assert 1 <= got["prefill_group_size.chat"] <= 4
        assert got["chunks_per_fetch.chat"] >= 1
        assert 0 <= got["sched_park_cap_share.chat"] <= 100
        assert got["engine_first_token_ms.chat"] > 0
        assert line["metrics"]["chat_ttft_mean_ms"]["value"] > 0
        assert got["engine_queue_wait_ms.chat"] >= 0
        assert got["chunks_ahead_at_admit.chat"] >= 0
        assert got["fetch_lock_wait_ms_per_fetch.chat"] >= 0
    # the benchmark's wrapper and the program count the same padded
    # tokens over the traced seconds (both at `_admit_group`'s entry)
    tr = info["trace"]
    assert tr["padded_prefill_tokens"] \
        == tr["engine_in_trace"]["prefill_padded_tokens"] > 0
    assert abs(tr["prefill_dispatches"]
               - tr["engine_in_trace"]["prefill_dispatches"]) <= 1
    # the window's counters are plain numbers under dot-free keys
    eng = info["engine"]
    assert all(type(v) in (int, float) and "." not in k
               for k, v in eng.items())
    assert eng["chunks_dispatched"] > 0 and "cap_stalls" not in eng


@pytest.mark.parametrize("name", sorted(NEW[CHAT] + NEW[BATCH]))
def test_on_the_parents_counters_a_new_metric_reads_nothing(name):
    metric = spec.load_layer_metric(name)
    assert metric["layer"] == "engine" and name in [
        m["name"] for m in BENCH["per_layer"]]
    evidence = {"out": {"counters": {"engine": dict(PARENT_ENGINE)}}}
    value = spec.load_reader(metric)(evidence, metric)
    # only what the parent already counted can be read there
    keys = {metric.get("num"), metric.get("den")} - {None}
    if keys and keys <= set(PARENT_ENGINE):
        assert value == pytest.approx(14 / 11)   # prefill_group_size
    else:
        assert value is None
    assert spec.load_reader(metric)({"out": {}}, metric) is None


def test_the_thirteen_are_appended_and_change_nothing_that_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    new = NEW[CHAT] + NEW[BATCH]
    assert len(new) == 13 and set(names[-13:]) == set(new)
    for m in BENCH["per_layer"][-13:]:
        chat = m["name"].endswith(".chat")
        assert m["workloads"] == [CHAT if chat else BATCH]
        assert m["moves"] == ("tpot_p50_ms" if chat
                              else "serve_tokens_per_s")
        f = spec.load_layer_metric(m["name"])
        assert ("moves_note" in f) == chat
        assert f["reader"] in ("engine_ratio", "engine_ratio_present",
                               "out_field")
