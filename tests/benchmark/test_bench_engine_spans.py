"""The per-layer metrics that read the engine's own spans, ledger and
request stamps (PR 24): a traced toy-size serve cell reports every one of
them, and on a program without those counters (the parent of that PR)
their readers find nothing and do not raise. Since PR 26 a traced run
wraps no method of the engine: it reads the `engine.*` spans from the
trace and differences `engine.stats`, so an engine whose private methods
are renamed is traced all the same."""

import argparse
import inspect
import re
import textwrap
from concurrent.futures import ThreadPoolExecutor

import pytest

import bench_paths
import ray_tpu
from benchmark.harness import spec
from test_bench_cells_cpu import (TINY, TOY_DEPLOYMENT,   # the toy
                                  TOY_SERVE)              # sizes, once

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
    out = {}
    for name in (CHAT, BATCH):
        cell = dict(spec.find_cell(BENCH, name), chips=1)
        args = argparse.Namespace(seed=2 ** 31 + 24, trace=1,
                                  seconds=3.0 if name == CHAT else 2.0)
        out[name] = bench_paths.run_cell_with_info(
            RUN, BENCH, cell, args, platform="cpu", field_overrides=TINY,
            traffic_overrides=TOY_SERVE)
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
    # prefill_ms_per_ktok's inputs are the program's own counts,
    # differenced over the traced seconds
    tr = info["trace"]
    assert tr["padded_prefill_tokens"] \
        == tr["engine_in_trace"]["prefill_padded_tokens"] > 0
    assert tr["prefill_dispatches"] \
        == tr["engine_in_trace"]["prefill_dispatches"] >= 1
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
    """PR 24's thirteen, found by name: they stand together, in the order
    they were appended in, after everything that was there before them
    (later PRs append after them, as the contract has it)."""
    names = [m["name"] for m in BENCH["per_layer"]]
    new = NEW[CHAT] + NEW[BATCH]
    first = min(names.index(n) for n in new)
    assert len(new) == 13 and set(names[first:first + 13]) == set(new)
    assert names[first - 1] == "peak_hbm_gb.batch"   # PR 23's last
    for m in BENCH["per_layer"][first:first + 13]:
        chat = m["name"].endswith(".chat")
        assert m["workloads"] == [CHAT if chat else BATCH]
        assert m["moves"] == ("tpot_p50_ms" if chat
                              else "serve_tokens_per_s")
        f = spec.load_layer_metric(m["name"])
        assert ("moves_note" in f) == chat
        assert f["reader"] in ("engine_ratio", "out_field")


# ---- a traced run names no method of the engine ----------------------------

# the private methods a traced run wrapped until PR 26
FORMER_HOOKS = ["_admit_locked", "_dispatch_locked", "_fetch_chunks",
                "_deliver_locked", "_admit_group"]
MS = 1_000_000  # ns


@pytest.fixture(scope="module")
def renamed(tmp_path_factory):
    """One traced toy replica, in this process, whose engine is the
    program's own with FORMER_HOOKS under other names (its source,
    rewritten): what it was asked, the reduced trace, the trace's planes
    and the (K, P) metadata of its `engine.prefill_dispatch` spans."""
    from jax.profiler import ProfileData

    from benchmark.harness import serve_cell, xplane
    from ray_tpu.models import engine as engine_mod

    src = textwrap.dedent(inspect.getsource(engine_mod.InferenceEngine))
    for name in FORMER_HOOKS:
        src = re.sub(rf"\b{name}\b", name + "_renamed", src)
    scope = dict(vars(engine_mod))
    exec(compile(src, "<renamed engine>", "exec"), scope)
    trace_dir = str(tmp_path_factory.mktemp("renamed_trace"))
    conf = spec.load_config(BENCH, "internlm2-1.8b")
    dep = {k: v for k, v in TOY_DEPLOYMENT.items() if k != "max_concurrency"}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "InferenceEngine", scope["InferenceEngine"])
        rep = serve_cell.BenchReplica(conf, platform="cpu",
                                      field_overrides=TINY, seed=7, **dep)
    try:
        lengths = [5, 20, 33, 64, 9, 40, 17, 3]
        prompts = [[1 + (i + j) % 500 for j in range(n)]
                   for i, n in enumerate(lengths)]
        rep.bench_trace_start(trace_dir)
        with ThreadPoolExecutor(len(prompts)) as pool:   # idle before, and
            answers = list(pool.map(                     # idle again after
                lambda p: rep(p, max_new_tokens=4)["token_ids"], prompts))
        red = serve_cell.reduce_trace_outside(trace_dir,
                                              rep.bench_trace_stop())
        requests = rep.engine_requests()
    finally:
        rep.engine.shutdown()
    path = xplane.find_xplane(trace_dir)
    dispatches = [dict(ev.stats) for plane in ProfileData.from_file(
        path).planes for line in plane.lines for ev in line.events
        if ev.name == "engine.prefill_dispatch"]
    return {"engine": rep.engine, "answers": answers, "reduced": red,
            "requests": requests, "planes": xplane.load_planes(path),
            "dispatches": dispatches, "n": len(prompts)}


@pytest.mark.parametrize("hook", FORMER_HOOKS)
def test_a_traced_run_rests_on_no_private_method_of_the_engine(renamed,
                                                               hook):
    from benchmark.harness import serve_cell

    assert not hasattr(renamed["engine"], hook)   # renamed, and it ran:
    assert [len(a) for a in renamed["answers"]] == [4] * renamed["n"]
    source = inspect.getsource(serve_cell)
    assert hook not in source and "ENGINE_HOOKS" not in source


def test_padded_tokens_are_the_engines_own_count_of_what_it_dispatched(
        renamed):
    """`prefill_ms_per_ktok`'s inputs: `engine.stats` differenced over the
    traced seconds equals sum(bucket x group) of the dispatches driven,
    by the spans' own metadata and by the request stamps."""
    red, spans = renamed["reduced"], renamed["dispatches"]
    assert len(spans) >= 1
    assert red["prefill_dispatches"] == len(spans)
    assert red["padded_prefill_tokens"] \
        == sum(d["K"] * d["P"] for d in spans) \
        == sum(r["bucket"] for r in renamed["requests"]) > 0
    assert sum(d["K"] for d in spans) == renamed["n"]
    assert red["engine_in_trace"]["prefill_prompt_tokens"] \
        == sum(r["prompt_len"] for r in renamed["requests"])
    # ... and the reader turns them into the metric's unit of work
    metric = spec.load_layer_metric("prefill_ms_per_ktok")
    trace = dict(red, modules={"jit_prefill_slots": {
        "count": float(len(spans)), "seconds": 0.5}})
    got = spec.load_reader(metric)({"trace": trace, "out": {}}, metric)
    assert got == pytest.approx(
        1000.0 * 0.5 / (red["padded_prefill_tokens"] / 1000.0))


def test_idle_gaps_are_owned_by_the_engines_own_spans(renamed):
    """The device idle exactly while the engine dispatched a prefill: the
    reduction names `engine.prefill_dispatch` (the innermost of the
    program's spans over the gap) as the owner, for every second of it."""
    from benchmark.harness import xplane

    planes = dict(renamed["planes"])
    host = [ev for lines in planes.values() for evs in lines.values()
            for ev in evs]
    names = {n for n, _, _ in host}
    assert {"engine.admit", "engine.prefill_dispatch",
            "engine.decode_dispatch", "engine.fetch", "engine.deliver",
            "engine.park", "engine.lock_wait"} <= names
    assert not any(n.startswith("bench:") for n in names)
    gaps = sorted((s, s + d) for n, s, d in host
                  if n == "engine.prefill_dispatch")
    lo = min(s for _, s, _ in host) - MS
    hi = max(s + d for _, s, d in host) + MS
    busy = xplane.subtract([(lo, hi)], xplane.union(gaps))
    planes["/device:TPU:0"] = {xplane.OP_LINE: [
        (f"fusion.{i}", s, e - s) for i, (s, e) in enumerate(busy)]}
    red = xplane.reduce_planes(planes, min_gap_ns=1)
    assert [name for name, _ in red["idle_gaps"]] \
        == ["engine.prefill_dispatch"]
    assert red["idle_gaps"][0][1] == pytest.approx(
        xplane.total(xplane.union(gaps)) / 1e9)
    assert red["host_spans"]["engine.prefill_dispatch"]["count"] \
        == len(renamed["dispatches"])
