"""What PR 56 changed in the harness, on the CPU. Its rule: a run's number
depends on the program and on the traffic `--seed` draws, and on nothing
else the run draws; and a traced run ends.

- a traced serve run's profile is reduced where the driver side runs, in a
  child process, and gives the `trace` dictionary the replica's own
  reduction gave, key for key; a profile that is corrupt or missing fails
  the run; `BenchReplica` has no method that opens a profile;
- a closed loop's `serve_tokens_per_s` is the engine's `tokens_out` between
  the two reads that open and close the window over the seconds between
  them on the replica's clock, and a run whose whole-run count is off the
  clients' by one token is not `correct`;
- a cell's weights come from the traffic file's `weights_seed` (a serve
  mix's under `deployment`) and `--seed` draws the traffic: two runs with
  two `--seed`s hold bit-equal weights and other batches; a traffic file
  of either kind without the key is refused.
"""

import argparse
import gzip
import hashlib
import inspect
import json
import os

import numpy as np
import pytest

import bench_paths
import ray_tpu
from benchmark.harness import (serve_cell, spec, traffic as traffic_gen,
                               train_cell, xplane)
from test_bench_cells_cpu import TINY, TOY_SERVE

BENCH = spec.load_benchmark()
RUN = bench_paths.load_run_module()
BATCH = "internlm2-1.8b.batch-closed"
MIXES = sorted({c["traffic"] for c in BENCH["workloads"]})
# the three cells whose chip holds a SHARE of the experts, and the share a
# balanced router gives each
HELD_SHARE = {"batch-closed-128": "0.125", "train-4k-8rows": "0.125",
              "train-16k-2rows": "0.03125"}
STOPPED = {"wall_s": 4.5, "stop_s": 1.25,
           "stats": {"prefill_padded_tokens": 8192, "prefill_dispatches": 7,
                     "tokens_out": 12345, "decode_steps": 96}}


# ---- (1) the profile is reduced outside the replica ------------------------

def _as_the_replica_reduced_it(trace_dir, stopped):
    """`BenchReplica.bench_trace_reduce` as the parent commit had it."""
    red = xplane.reduce_trace(trace_dir)
    red.pop("op_count", None)
    red["trace_wall_s"] = stopped["wall_s"]
    red["padded_prefill_tokens"] = stopped["stats"].get(
        "prefill_padded_tokens", 0)
    red["prefill_dispatches"] = stopped["stats"].get("prefill_dispatches", 0)
    red["engine_in_trace"] = stopped["stats"]
    return red


def _profile_dir(root, content: bytes) -> str:
    """``content`` laid out as the profiler lays a run's profile out."""
    run = root / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(content)
    return str(root)


@pytest.fixture(scope="module")
def chip_trace_dir(tmp_path_factory):
    """The trace recorded on the chip (benchmark/fixtures)."""
    with gzip.open(os.path.join(
            bench_paths.REPO, "benchmark", "fixtures",
            "train_tiny_v5e.xplane.pb.gz"), "rb") as f:
        return _profile_dir(tmp_path_factory.mktemp("chip_trace"), f.read())


def test_the_driver_side_reduction_is_the_replicas_key_for_key(
        chip_trace_dir):
    got = serve_cell.reduce_trace_outside(chip_trace_dir, STOPPED)
    want = _as_the_replica_reduced_it(chip_trace_dir, STOPPED)
    assert list(got) == list(want)              # the keys, in their order
    assert got == json.loads(json.dumps(want))  # and every number
    assert got["devices"] == 1 and got["busy_s"] > 0
    assert got["op_seconds"] and got["device_ops"] and got["host_spans"]
    assert "op_count" not in got
    assert got["padded_prefill_tokens"] == 8192
    assert got["engine_in_trace"] == STOPPED["stats"]


@pytest.mark.parametrize("profile,says", [
    (b"\xffnot a profile" * 64, "could not be reduced"),
    (None, "no profile"),
])
def test_a_corrupt_or_missing_profile_fails_the_run_in_its_own_words(
        tmp_path, profile, says):
    """No retry, and no empty trace in its place."""
    trace_dir = str(tmp_path) if profile is None \
        else _profile_dir(tmp_path, profile)
    with pytest.raises(RuntimeError, match=says) as err:
        serve_cell.reduce_trace_outside(trace_dir, STOPPED)
    assert trace_dir in str(err.value)


def test_the_replica_has_no_method_that_opens_a_profile():
    names = [n for n, _ in inspect.getmembers(serve_cell.BenchReplica,
                                              inspect.isfunction)]
    assert "bench_trace_reduce" not in names
    assert [n for n in names if n.startswith("bench_trace")] == [
        "bench_trace_start", "bench_trace_stop"]
    for name in names:
        if name.startswith("bench_"):
            src = inspect.getsource(getattr(serve_cell.BenchReplica, name))
            for reader in ("xplane", "ProfileData", "reduce_trace("):
                assert reader not in src, (name, reader)
    # the one place that has a serve run's profile read starts a child
    outside = inspect.getsource(serve_cell.reduce_trace_outside)
    assert "subprocess.run" in outside and "xplane.py" in outside
    assert 'JAX_PLATFORMS="cpu"' in outside


# ---- (4) the rate counts the tokens made in the window ---------------------

def _closed_out(made=140_000, window_s=51.25, whole=(137_000, 51.0),
                engine_total=150_000, client_total=150_000):
    return {
        "mark": {"clock_s": 1000.5},
        "counters": {"engine": {"tokens_out": made},
                     "clock_s": 1000.5 + window_s, "compilations": 0},
        "client": {"tokens": whole[0], "window_s": whole[1], "failed": 0,
                   "attempted": 300},
        "whole_run": {"engine_tokens_out": engine_total,
                      "client_tokens": client_total},
        "check": {"ok": True, "rows": [
            {"prefill": {"rel_rms_error": 1e-3, "tolerance": 1e-2},
             "decode": {"rel_rms_error": 2e-3, "tolerance": 1e-2},
             "served_tokens_ok": True}]},
        "repeat": {"ok": True}}


@pytest.mark.parametrize("made,window_s,want", [
    (140_000, 51.25, 140_000 / 51.25),
    (2732 * 51, 51.0, 2732.0),
    (1, 50.875, 1 / 50.875)])
def test_the_rate_is_tokens_made_over_the_replicas_own_seconds(made,
                                                               window_s,
                                                               want):
    out = _closed_out(made=made, window_s=window_s)
    vals = RUN.end_to_end_values("closed_loop", out, setup_s=100.0)
    assert vals == {"setup_s": 100.0, "serve_tokens_per_s": want}
    # the count of whole requests no longer moves it
    out["client"]["tokens"] *= 2
    assert RUN.end_to_end_values("closed_loop", out, 100.0) == vals
    counts = RUN.closed_loop_counts(out)
    assert counts["tokens_whole_requests"] == {
        "tokens": 274_000, "window_s": 51.0, "per_s": 274_000 / 51.0}
    assert counts["tokens_made"] == {"tokens": made, "window_s": window_s,
                                     "per_s": want}


@pytest.mark.parametrize("off", [-1, 0, 1, 256])
def test_a_whole_run_count_off_by_a_token_is_not_correct(off):
    out = _closed_out(engine_total=150_000 + off)
    checks = serve_cell.judge(out)
    assert checks["tokens_made_are_tokens_received"] is (off == 0)
    assert all(checks.values()) is (off == 0)
    compared = RUN.compared_of_run(out)
    assert list(compared)[-1] == "tokens_made_minus_tokens_received"
    assert compared["tokens_made_minus_tokens_received"] == [abs(off), 0]


def test_an_open_loop_abandons_streams_and_holds_no_identity():
    out = _closed_out()
    del out["whole_run"]
    assert "tokens_made_are_tokens_received" not in serve_cell.judge(out)
    assert "tokens_made_minus_tokens_received" not in RUN.compared_of_run(out)


def test_the_replica_stamps_its_own_clock_beside_each_read():
    conf = spec.load_config(BENCH, "internlm2-1.8b")
    dep = {k: v for k, v in TOY_SERVE["deployment"].items()
           if k not in ("max_concurrency", "weights_seed")}
    rep = serve_cell.BenchReplica(conf, platform="cpu", field_overrides=TINY,
                                  seed=0, **dep)
    try:
        assert rep.bench_counters()["engine_total"]["tokens_out"] == 0
        # what the ramp makes before the mark is no part of the window
        assert len(rep([9, 8, 7], max_new_tokens=7)["token_ids"]) == 7
        mark = rep.bench_mark()
        assert len(rep([3, 4, 5], max_new_tokens=5)["token_ids"]) == 5
        close = rep.bench_counters()
        assert close["engine"]["tokens_out"] == 5
        assert close["engine_total"]["tokens_out"] == 12
        assert 0 < close["clock_s"] - mark["clock_s"] < 60
    finally:
        rep.engine.shutdown()


# ---- (2) weights from the cell's number, traffic from --seed ---------------

@pytest.mark.parametrize("mix", MIXES)
def test_every_mix_states_its_weights_seed_and_why(mix):
    t = spec.load_traffic(mix)
    held = t if t["kind"] == "train" else t["deployment"]
    assert spec.weights_seed(t) == held["weights_seed"]
    assert isinstance(held["weights_seed"], int)
    why = held["weights_seed_why"]
    assert "--seed draws" in why and len(why) > 100
    if mix in HELD_SHARE:
        # chosen once, by a rule on the held share and not by the rate it
        # gives: the file lists every reading taken
        assert held["weights_seed"] in range(8)
        assert HELD_SHARE[mix] in why and "first of 0..7" in why
        if t["kind"] == "train":
            # the mean share says nothing of one layer: a layer at twice
            # the balance leaves the sorted buffer's front by the rows
            # `--seed` draws, and the rate follows it
            assert "stays on the front" in why
    else:   # the chip holds every expert or none
        assert held["weights_seed"] == 0
    assert len(MIXES) == 8


def test_both_held_share_train_cells_run_at_one_learning_rate():
    """At 3e-4 a cold Adam collapses a sigmoid router before the window
    opens; at 1e-5 it stays at the seed's balance."""
    glm, kimi = (spec.load_traffic(m)
                 for m in ("train-4k-8rows", "train-16k-2rows"))
    assert glm["learning_rate"] == kimi["learning_rate"] == 1e-5
    assert "1e-5" in glm["why"] and "1e-5" in kimi["why"]


def _write_mix(root, t):
    os.makedirs(root / "benchmark" / "traffic", exist_ok=True)
    with open(root / "benchmark" / "traffic" / "mix.json", "w") as f:
        json.dump({k: v for k, v in t.items() if k != "name"}, f)


@pytest.mark.parametrize("mix", ["batch-closed", "chat-steady", "train-4k"])
@pytest.mark.parametrize("edit", ["no_key", "a_string", "negative", "a_bool"])
def test_a_mix_of_either_kind_without_a_weights_seed_is_refused(
        tmp_path, mix, edit):
    t = spec.load_traffic(mix)
    _write_mix(tmp_path, t)
    assert spec.load_traffic("mix", str(tmp_path))["kind"] == t["kind"]
    held = t if t["kind"] == "train" else t["deployment"]
    if edit == "no_key":
        del held["weights_seed"]
    else:
        held["weights_seed"] = {"a_string": "0", "negative": -1,
                                "a_bool": True}[edit]
    _write_mix(tmp_path, t)
    where = "weights_seed" if t["kind"] == "train" \
        else "deployment.weights_seed"
    with pytest.raises(spec.SpecError, match=f"`{where}`"):
        spec.load_traffic("mix", str(tmp_path))


def test_a_serve_mix_with_no_deployment_at_all_is_refused(tmp_path):
    t = spec.load_traffic("batch-closed")
    del t["deployment"]
    _write_mix(tmp_path, t)
    with pytest.raises(spec.SpecError, match="weights_seed"):
        spec.load_traffic("mix", str(tmp_path))
    # a train mix's key under `deployment` is not where a train cell reads
    t = spec.load_traffic("train-4k")
    t["deployment"] = {"weights_seed": t.pop("weights_seed")}
    _write_mix(tmp_path, t)
    with pytest.raises(spec.SpecError, match="weights_seed"):
        spec.load_traffic("mix", str(tmp_path))


def _digest(tree) -> str:
    """sha256 over every leaf's path and bytes."""
    import jax

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def cpu_cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


class _DigestReplica(serve_cell.BenchReplica):
    def bench_weights_digest(self):
        return _digest(self.engine.params)


def test_two_serve_deploys_two_seeds_one_weights_seed(cpu_cluster,
                                                      monkeypatch):
    """Bit-equal weights, other prompts; another `weights_seed` is another
    model under the same prompts."""
    from ray_tpu import serve

    conf = spec.load_config(BENCH, "internlm2-1.8b")
    monkeypatch.setattr(serve_cell, "BenchReplica", _DigestReplica)
    digests, firsts = {}, {}
    for name, weights_seed, seed in (("a", 0, 11), ("b", 0, 2 ** 31 + 12),
                                     ("c", 1, 11)):
        mix = dict(TOY_SERVE, kind="closed_loop", deployment=dict(
            TOY_SERVE["deployment"], weights_seed=weights_seed))
        try:
            handle, _ = serve_cell.deploy(conf, mix, platform="cpu",
                                          field_overrides=TINY)
            digests[name] = serve_cell.call(handle, "bench_weights_digest")
            # the check follows --seed for its prompts and the replica's
            # number for its reference: it agrees under any --seed
            assert serve_cell.call(handle, "bench_check", spec.seed32(seed),
                                   [40, 33])["ok"]
        finally:
            serve.shutdown()
        pool = traffic_gen.closed_loop_schedule(mix, seed)["pool"]
        firsts[name] = [(r["prompt_len"], r["output_len"], r["token_seed"])
                        for r in pool[:6]]
    assert digests["a"] == digests["b"] != digests["c"]
    assert firsts["a"] != firsts["b"] and firsts["a"] == firsts["c"]
    # the same multiset of work under either seed, in another order
    full = {n: sorted(r["prompt_len"] for r in
                      traffic_gen.closed_loop_schedule(TOY_SERVE, s)["pool"])
            for n, s in (("a", 11), ("b", 2 ** 31 + 12))}
    assert full["a"] == full["b"]


def _toy_train_run(seed: int, weights_seed: int) -> dict:
    """`train_cell.train_loop` in this process at toy size: the digest of
    the weights the train state starts from and of the weights the check
    ran on, every batch made, and what the loop reported."""
    from ray_tpu import train
    from ray_tpu.models import training

    seen = {"batches": [], "reported": None}
    real_init, real_batch = training.init_train_state, train_cell._make_batch
    real_check = train_cell.check_against_reference

    def init(key, cfg, tx, mesh):
        state = real_init(key, cfg, tx, mesh)
        seen["state_weights"] = _digest(state["params"])
        return state

    def check(params, *a, **kw):
        seen["check_weights"] = _digest(params)
        return real_check(params, *a, **kw)

    def batch(*a):
        made = real_batch(*a)
        seen["batches"].append(hashlib.sha256(
            made["tokens"].tobytes()).hexdigest())
        return made

    traffic = dict(spec.load_traffic("train-4k"), seq_len=64, rows=2,
                   weights_seed=weights_seed)
    with pytest.MonkeyPatch.context() as mp:   # undone before the next run
        mp.setattr(training, "init_train_state", init)
        mp.setattr(train_cell, "check_against_reference", check)
        mp.setattr(train_cell, "_make_batch", batch)
        mp.setattr(train, "report", lambda out: seen.update(reported=out))
        train_cell.train_loop(dict(
            conf=spec.load_config(BENCH, "internlm2-1.8b"), traffic=traffic,
            seed=seed, root=spec.ROOT, seconds=0.3, platform="cpu", chips=1,
            field_overrides=TINY, trace_dir=None))
    return seen


def test_two_toy_train_runs_two_seeds_one_weights_seed():
    """Bit-equal weights (the check's and the train state's, which are one
    draw), other batches from the check's rows on; another `weights_seed`
    is another model on the same batches."""
    a = _toy_train_run(11, 0)
    b = _toy_train_run(2 ** 31 + 12, 0)
    c = _toy_train_run(11, 1)
    for run in (a, b, c):
        assert run["state_weights"] == run["check_weights"]
        assert run["reported"]["check"]["ok"]
        assert len(run["batches"]) >= 3   # check rows, warm-up, window
    assert a["state_weights"] == b["state_weights"] != c["state_weights"]
    n = min(len(a["batches"]), len(b["batches"]), len(c["batches"]))
    assert not set(a["batches"][:n]) & set(b["batches"][:n])
    assert a["batches"][:n] == c["batches"][:n]
    # the first step's loss follows the weights AND the batch
    losses = [r["reported"]["losses"][0] for r in (a, b, c)]
    assert len(set(losses)) == 3


def test_a_traced_toy_serve_run_ends_with_the_trace_reduced_by_the_driver(
        cpu_cluster):
    cell = dict(spec.find_cell(BENCH, BATCH), chips=1)
    args = argparse.Namespace(seed=2 ** 31 + 55, seconds=2.0, trace=1)
    line, info = bench_paths.run_cell_with_info(
        RUN, BENCH, cell, args, platform="cpu", field_overrides=TINY,
        traffic_overrides=TOY_SERVE)
    assert sorted(info["trace"]) == [
        "devices", "engine_in_trace", "padded_prefill_tokens",
        "prefill_dispatches", "trace_wall_s", "xplane_bytes"]
    assert info["trace"]["engine_in_trace"]["tokens_out"] > 0
    assert info["trace_stop"]["stop_s"] >= 0 and info["trace_reduce_s"] > 0
    assert set(info["trace_stop"]) == {"wall_s", "stop_s", "stats"}
    assert info["checks"]["tokens_made_are_tokens_received"] is True
    # the rate a traced run states is the untraced run's definition
    c = info["engine"]["tokens_out"]
    assert line["end_to_end"]["serve_tokens_per_s"] > 0 and c > 0
    assert line["tokens_whole_requests"]["tokens"] > 0
    assert line["tokens_made"]["tokens"] == c
    # the window's count leaves out what the ramp and the tail made
    assert c < info["whole_run"]["engine_tokens_out"]
    assert 1.9 < line["tokens_made"]["window_s"] < 4.0
    assert line["end_to_end"]["serve_tokens_per_s"] == \
        line["tokens_made"]["per_s"]
    assert list(line)[-1] == "compared"
