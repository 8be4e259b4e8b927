"""The engine's own observability (toy model, CPU): the thread-time
ledger is complete, requests are stamped, the heartbeat tells a stall from
an idle engine, the spans are on the profiler's clock, and `engine.stats`
stays a flat dict of numbers (its readers difference every key)."""

import collections
import glob
import logging
import threading
import time

import jax
import pytest

from ray_tpu.models import engine as engine_mod
from ray_tpu.models.config import tiny_config
from ray_tpu.models.engine import InferenceEngine
from ray_tpu.models.transformer import init_params

SCHED_STATES = ("sched_lock_wait_s", "admit_wall_s", "dispatch_wall_s",
                "park_idle_s", "park_cap_s")
FETCHER_STATES = ("fetch_idle_s", "fetch_lock_wait_s", "fetch_wall_s",
                  "deliver_wall_s")
PROMPTS = [[(7 * i + j) % 19 + 1 for j in range(2 + (5 * i) % 13)]
           for i in range(12)]


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, init_params(jax.random.key(0), cfg)


def _engine(model, **kw):
    cfg, params = model
    kw = {"slots": 4, "max_prompt_len": 16, "max_new_tokens": 8,
          "decode_chunk": 2, **kw}
    return InferenceEngine(params, cfg, **kw).warmup()


def _drive(eng, reqs, steps=500):
    """Inline mode: step until every request is done."""
    for _ in range(steps):
        if all(r.done.is_set() for r in reqs):
            return
        eng.step()
    raise AssertionError("requests did not finish")


def _serve_staggered(eng, prompts=PROMPTS, gap_s=0.01):
    reqs = []
    for p in prompts:
        reqs.append(eng.submit(p))
        time.sleep(gap_s)
    for r in reqs:
        assert r.done.wait(120) and r.error is None
    return reqs


def test_every_second_of_both_threads_goes_to_one_state(model):
    eng = _engine(model, max_inflight=2).serve_forever()
    try:
        # gaps long enough that the toy model's microsecond regions (of
        # which the helper's own cost is a share) do not carry the sum
        _serve_staggered(eng, gap_s=0.05)
    finally:
        eng.shutdown()   # joined: no iteration is half counted
    st = eng.stats
    for wall, states in (("sched_wall_s", SCHED_STATES),
                         ("fetcher_wall_s", FETCHER_STATES)):
        assert st[wall] > 0
        assert sum(st[k] for k in states) == pytest.approx(st[wall],
                                                           rel=0.10)
    # the child's seconds are inside its parent's, not beside them
    assert 0 < st["prefill_dispatch_wall_s"] <= st["admit_wall_s"]
    assert st["chunks_dispatched"] == st["chunks_delivered"] > 0
    assert st["decode_steps"] == st["chunks_dispatched"] * eng.decode_chunk


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["inline", "pipelined"])
def test_request_stamps_and_their_counters(model, pipelined):
    eng = _engine(model)
    eng.request_log = collections.deque(maxlen=8)   # bounded: see below
    padded = []
    admit = eng._admit_group

    def counting(group):   # what the benchmark's wrapper counts
        P = max(eng._bucket(len(req.prompt)) for _, req in group)
        padded.append(P * len(group))
        return admit(group)
    eng._admit_group = counting
    if pipelined:
        eng.serve_forever()
        try:
            reqs = _serve_staggered(eng)
        finally:
            eng.shutdown()
    else:
        reqs = [eng.submit(p) for p in PROMPTS]
        _drive(eng, reqs)
    st = eng.stats
    assert st["first_tokens"] == st["prefills"] == st["requests_done"] == 12
    assert 0 <= st["queue_wait_s"] <= st["first_token_s"]
    assert st["prefill_prompt_tokens"] == sum(len(p) for p in PROMPTS)
    assert st["prefill_prompt_tokens"] <= st["prefill_padded_tokens"]
    assert st["prefill_padded_tokens"] == sum(padded)
    assert len(padded) == st["prefill_dispatches"]
    assert st["chunks_ahead_at_admit"] >= 0
    cfg, params = model
    assert InferenceEngine(params, cfg, slots=1).request_log.maxlen == 1024
    assert len(eng.request_log) == 8     # 12 finished, the last 8 kept
    by_rid = {r.rid: r for r in reqs}
    for rec in eng.request_log:
        req = by_rid[rec["rid"]]
        assert rec["t_submit"] <= rec["t_admit"] <= rec["t_first"] \
            <= rec["t_done"]
        assert rec["prompt_len"] == len(req.prompt) <= rec["bucket"]
        assert rec["group"] in (1, 2, 4) and rec["chunks_ahead"] >= 0
        assert rec["tokens_out"] == len(req.tokens) == 8


def _slow_states(eng):
    return [e["state"] for e in eng.slow_events]


def test_heartbeat_names_the_state_that_swallowed_the_seconds(
        model, monkeypatch, caplog):
    monkeypatch.setattr(engine_mod, "_SLOW_S", 0.05)
    real_get, calls = jax.device_get, []

    def first_get_sleeps(x):
        calls.append(1)
        if len(calls) == 1:
            time.sleep(0.2)
        return real_get(x)
    monkeypatch.setattr(jax, "device_get", first_get_sleeps)
    eng = _engine(model).serve_forever()
    with caplog.at_level(logging.WARNING, logger=engine_mod.__name__):
        try:
            _serve_staggered(eng, PROMPTS[:2])
        finally:
            eng.shutdown()
    assert _slow_states(eng).count("fetch") == 1
    ev = next(e for e in eng.slow_events if e["state"] == "fetch")
    assert ev["seconds"] >= 0.15 and ev["thread"] == "llm-engine-fetch"
    assert ev["undelivered_chunks"] >= 1 and ev["planned_slots"] >= 0
    assert abs(ev["t_wall"] - time.time()) < 60 and ev["t_perf"] > 0
    assert eng.stats["slow_s"] >= 0.15
    assert eng.stats["slow_count"] == len(eng.slow_events)
    assert sum(r.args[2] == "fetch" for r in caplog.records) == 1


def test_an_engine_without_traffic_is_idle_not_stalled(model, monkeypatch):
    monkeypatch.setattr(engine_mod, "_SLOW_S", 0.05)
    eng = _engine(model).serve_forever()
    try:
        time.sleep(0.3)
    finally:
        eng.shutdown()
    assert eng.stats["park_idle_s"] > 0.2 and eng.stats["fetch_idle_s"] > 0.2
    assert not eng.slow_events
    assert eng.stats["slow_s"] == 0 and eng.stats["slow_count"] == 0


def test_parked_at_the_cap_is_one_episode_not_one_per_wakeup(
        model, monkeypatch):
    monkeypatch.setattr(engine_mod, "_SLOW_S", 0.05)
    real_get, release = jax.device_get, threading.Event()

    def blocked_get(x):
        release.wait(10)
        return real_get(x)
    monkeypatch.setattr(jax, "device_get", blocked_get)
    eng = _engine(model, max_inflight=1, max_new_tokens=16).serve_forever()
    try:
        reqs = [eng.submit(p) for p in PROMPTS[:2]]
        time.sleep(0.4)   # the fetcher holds a chunk; the cap is 1
        cap = [dict(e) for e in eng.slow_events if e["state"] == "park_cap"]
        slow_s = eng.stats["slow_s"]
        release.set()
        for r in reqs:
            assert r.done.wait(120) and r.error is None
    finally:
        release.set()
        eng.shutdown()
    # several 50 ms wake-ups, one occurrence that kept growing
    assert len(cap) == 1
    assert cap[0]["seconds"] > 0.2 and slow_s >= cap[0]["seconds"] - 0.06
    assert cap[0]["thread"] == "llm-engine"
    assert cap[0]["undelivered_chunks"] >= 1


def _engine_events(trace_dir):
    """{line id: [(name, start_ns, end_ns)]} of the `engine.*` host
    events in the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path[-1]).planes:
        for i, line in enumerate(plane.lines):
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events if ev.name.startswith("engine.")]
            if evs:
                lines[(plane.name, i)] = evs
    return lines


def test_spans_are_on_the_profilers_clock(model, tmp_path):
    eng = _engine(model)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.serve_forever()
        _serve_staggered(eng, PROMPTS[:8])
        eng.shutdown()
    finally:
        jax.profiler.stop_trace()
        eng.shutdown()
    st, lines = eng.stats, _engine_events(str(tmp_path))
    assert len(lines) == 2     # the scheduler's line and the fetcher's
    flat = [ev for evs in lines.values() for ev in evs]

    def named(name):
        return [ev for ev in flat if ev[0] == name]

    def seconds(name):
        return sum(e - s for _, s, e in named(name)) / 1e9

    assert len(named("engine.decode_dispatch")) == st["chunks_dispatched"]
    assert len(named("engine.prefill_dispatch")) == st["prefill_dispatches"]
    assert len(named("engine.fetch")) == st["fetches"]
    for evs in lines.values():
        admits = [ev for ev in evs if ev[0] == "engine.admit"]
        for _, s, e in (ev for ev in evs
                        if ev[0] == "engine.prefill_dispatch"):
            assert any(a <= s and e <= b for _, a, b in admits)
    # the accumulators are the spans' own seconds (perf_counter around
    # the annotation: a few microseconds more per occurrence)
    for span, keys in (
            ("engine.decode_dispatch", ["dispatch_wall_s"]),
            ("engine.prefill_dispatch", ["prefill_dispatch_wall_s"]),
            ("engine.admit", ["admit_wall_s"]),
            ("engine.fetch", ["fetch_wall_s"]),
            ("engine.deliver", ["deliver_wall_s"]),
            ("engine.park", ["park_idle_s", "park_cap_s"]),
            ("engine.fetch_idle", ["fetch_idle_s"]),
            ("engine.lock_wait", ["sched_lock_wait_s",
                                  "fetch_lock_wait_s"])):
        assert seconds(span) == pytest.approx(
            sum(st[k] for k in keys), rel=0.10,
            abs=2e-5 * len(named(span))), span


def _flat_numbers(stats):
    assert all(type(v) in (int, float) for v in stats.values()), stats
    assert not any("." in k for k in stats)
    return set(stats)


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["inline", "pipelined"])
def test_stats_is_a_flat_dict_of_numbers_with_every_key_from_the_start(
        model, pipelined):
    eng = _engine(model)
    keys = _flat_numbers(eng.stats)
    assert all(v == 0 for v in eng.stats.values())
    assert "cap_stalls" not in keys and not hasattr(eng, "_at_cap")
    if pipelined:
        eng.serve_forever()
        try:
            _serve_staggered(eng, PROMPTS[:6])
        finally:
            eng.shutdown()
    else:
        _drive(eng, [eng.submit(p) for p in PROMPTS[:6]])
    assert _flat_numbers(eng.stats) == keys     # none created lazily
    st = eng.stats
    assert st["fetch_wall_s"] > 0 and st["deliver_wall_s"] > 0
    assert st["admit_wall_s"] > 0 and st["dispatch_wall_s"] > 0
    assert st["chunks_delivered"] == st["chunks_dispatched"] > 0
    fetcher_only = ("fetcher_wall_s", "fetch_idle_s", "fetch_lock_wait_s")
    if pipelined:
        assert all(st[k] > 0 for k in fetcher_only + ("sched_wall_s",))
    else:   # step() driven by the caller: no fetcher, no parked loop
        assert all(st[k] == 0 for k in fetcher_only + (
            "sched_wall_s", "park_idle_s", "park_cap_s"))
    assert st["slow_s"] == 0 and not eng.slow_events


def test_an_operators_way_in_through_the_replica(model, tmp_path):
    from ray_tpu.serve.llm import _ContinuousLLMReplica

    shape = dict(slots=2, max_prompt_len=16, max_new_tokens=4)
    _engine(model, decode_chunk=4, **shape)   # the replica's programs
    keep = jax.config.jax_persistent_cache_min_compile_time_secs
    try:   # the replica keeps every program in the compile cache
        rep = _ContinuousLLMReplica(model[0], **shape)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", keep)
    try:
        for p in PROMPTS[:3]:
            assert len(rep(p)["token_ids"]) == 4
        assert rep.engine_slow_events() == []
        recs = rep.engine_requests(last=2)
        assert [r["rid"] for r in recs] == [1, 2]
        assert recs[-1]["tokens_out"] == 4 and recs[-1]["t_done"] > 0
        assert rep.engine_stats()["requests_done"] == 3
        threading.Timer(0.05, lambda: rep(PROMPTS[3])).start()
        assert rep.trace(0.4, str(tmp_path)) == str(tmp_path)
    finally:
        rep.engine.shutdown()
    names = {ev[0] for evs in _engine_events(str(tmp_path)).values()
             for ev in evs}
    assert {"engine.park", "engine.fetch_idle", "engine.admit",
            "engine.decode_dispatch", "engine.deliver"} <= names


def test_a_lock_held_over_an_idle_engine_is_no_stall(model, monkeypatch):
    """A warm-up (or the benchmark's check) holds the engine's lock for
    seconds before traffic: the threads wait for it, nothing waits for
    them. The same wait with a request queued is a stall."""
    monkeypatch.setattr(engine_mod, "_SLOW_S", 0.05)
    eng = _engine(model).serve_forever()
    try:
        with eng._lock:
            time.sleep(0.2)
        time.sleep(0.1)
        assert not eng.slow_events and eng.stats["sched_lock_wait_s"] > 0.1
        with eng._lock:
            req = eng.submit(PROMPTS[0])
            time.sleep(0.2)
        assert req.done.wait(120)
    finally:
        eng.shutdown()
    waits = [e for e in eng.slow_events if e["state"] == "lock_wait"
             and e["thread"] == "llm-engine"]   # the fetcher may wait too
    assert len(waits) == 1
    assert waits[0]["queued"] == 1 and waits[0]["seconds"] >= 0.1
