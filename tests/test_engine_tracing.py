"""The engine's own observability (toy model, CPU): the thread-time
ledger is complete, requests are stamped, the heartbeat tells a stall from
an idle engine, the spans are on the profiler's clock, and `engine.stats`
stays a flat dict of numbers (its readers difference every key). Since
PR 40 a streamed request's ledger goes on from delivery to the pulls that
take its tokens: every second of an open stream is `wait` or `held`.
Since PR 58 the deploy is in the ledger too: the weights, the constructor
and the warm-up are spans with a key each, and the process's compile
ledger (`utils.compile_cache`) stands in every engine's `stats`."""

import collections
import glob
import logging
import sys
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


def _cold_engine(model, **kw):
    cfg, params = model
    kw = {"slots": 4, "max_prompt_len": 16, "max_new_tokens": 8,
          "decode_chunk": 2, **kw}
    return InferenceEngine(params, cfg, **kw)


def _engine(model, **kw):
    return _cold_engine(model, **kw).warmup()


def _drive(eng, reqs, steps=500):
    """Inline mode: step until every request is done."""
    for _ in range(steps):
        if all(r.done.is_set() for r in reqs):
            return
        eng.step()
    raise AssertionError("requests did not finish")


def _run_dry(eng, steps=500):
    """Inline mode: step until the engine has nothing left to do."""
    for _ in range(steps):
        if not eng.step():
            return
    raise AssertionError("the engine did not run dry")


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


def _engine_events(trace_dir, prefix="engine."):
    """{line id: [(name, start_ns, end_ns)]} of the `engine.*` host
    events in the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path[-1]).planes:
        for i, line in enumerate(plane.lines):
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events if ev.name.startswith(prefix)]
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


COMPILE_KEYS = ("compile_requests", "compile_wait_s", "programs_loaded",
                "programs_compiled", "cache_load_s", "trace_lower_s")


def _flat_numbers(stats):
    assert all(type(v) in (int, float) for v in stats.values()), stats
    assert not any("." in k for k in stats)
    return set(stats)


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["inline", "pipelined"])
def test_stats_is_a_flat_dict_of_numbers_with_every_key_from_the_start(
        model, pipelined):
    eng = _cold_engine(model)
    keys = _flat_numbers(eng.stats)
    # what construction sets: its own seconds, and the PROCESS's compile
    # ledger as it stands (this session compiled long before this engine).
    # `weights_s` is a replica's to write: 0 in an engine none built
    assert set(COMPILE_KEYS) < keys
    assert eng.stats["engine_init_s"] > 0
    assert eng.stats["compile_requests"] > 0
    assert all(v == 0 for k, v in eng.stats.items()
               if k not in COMPILE_KEYS + ("engine_init_s",))
    eng.warmup()    # sets its two keys and no other of the engine's own
    assert eng.stats["warmup_s"] > 0 and eng.stats["warmup_programs"] > 0
    assert all(v == 0 for k, v in eng.stats.items()
               if k not in COMPILE_KEYS + (
                   "engine_init_s", "warmup_s", "warmup_programs"))
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
        # the deploy, read the same way: the weights' seconds (the replica
        # drew them), and the programs this process asked the backend for
        st = rep.engine_stats()
        assert st["weights_s"] > 0 and st["engine_init_s"] > 0
        assert st["warmup_s"] == 0      # nobody warmed this replica up
        log_ = rep.compile_log()
        assert 0 < len(log_) <= 100 and len(rep.compile_log(last=3)) == 3
        assert set(log_[-1]) == {"fun_name", "wall_s", "loaded", "t_unix"}
        assert st["compile_requests"] >= len(log_)
        threading.Timer(0.05, lambda: rep(PROMPTS[3])).start()
        assert rep.trace(0.4, str(tmp_path)) == str(tmp_path)
    finally:
        rep.engine.shutdown()
    names = {ev[0] for evs in _engine_events(str(tmp_path)).values()
             for ev in evs}
    assert {"engine.park", "engine.fetch_idle", "engine.admit",
            "engine.decode_dispatch", "engine.deliver"} <= names


def test_warmup_is_a_phase_of_the_deploy_and_no_stall(model, monkeypatch):
    monkeypatch.setattr(engine_mod, "_SLOW_S", 0.0)
    eng = _cold_engine(model, max_prompt_len=64)
    assert eng._buckets == [16, 32, 64]
    before = dict(eng.stats)
    assert before["warmup_s"] == 0 and before["warmup_programs"] == 0
    eng.warmup()
    st = eng.stats
    assert st["warmup_s"] > 0
    # buckets x group sizes, and the decode chunk
    assert st["warmup_programs"] == 3 * len(eng._GROUP_SIZES) + 1
    assert st["engine_init_s"] == before["engine_init_s"] > 0
    assert not eng.slow_events and st["slow_count"] == 0
    # fewer group sizes where there are fewer slots than the largest
    two = _cold_engine(model, slots=2).warmup()
    assert two.stats["warmup_programs"] == 1 * 2 + 1


def test_the_deploys_spans_are_on_the_profilers_clock(model, tmp_path):
    from ray_tpu.serve.llm import _ContinuousLLMReplica

    keep = jax.config.jax_persistent_cache_min_compile_time_secs
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        rep = _ContinuousLLMReplica(model[0], slots=2, max_prompt_len=16,
                                    max_new_tokens=4)
        rep.engine.warmup()
    finally:
        jax.profiler.stop_trace()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", keep)
        rep.engine.shutdown()
    st = rep.engine_stats()
    spans = {}
    for prefix in ("engine.", "serve."):
        for evs in _engine_events(str(tmp_path), prefix).values():
            for name, s, e in evs:
                spans.setdefault(name, []).append((s, e))
    for span, key in (("serve.replica_weights", "weights_s"),
                      ("engine.init", "engine_init_s"),
                      ("engine.warmup", "warmup_s")):
        assert len(spans[span]) == 1, span
        (s, e), = spans[span]
        assert (e - s) / 1e9 == pytest.approx(st[key], rel=0.10, abs=1e-4)
    # one after the other, and a warm-up's programs inside it
    assert spans["serve.replica_weights"][0][1] <= spans["engine.init"][0][0]
    assert spans["engine.init"][0][1] <= spans["engine.warmup"][0][0]
    programs = spans["engine.warmup_program"]
    assert len(programs) == st["warmup_programs"] == 1 * 2 + 1
    (w0, w1), = spans["engine.warmup"]
    assert all(w0 <= s and e <= w1 for s, e in programs)


def test_a_stall_that_was_a_compile_says_so(model, monkeypatch):
    """No warm-up, and a shape no other test of this process runs: the
    first admission asks the backend for its prefill program while the
    request waits."""
    monkeypatch.setattr(engine_mod, "_SLOW_S", 0.0)
    eng = _cold_engine(model, slots=5, max_prompt_len=24, max_new_tokens=7)
    asked = eng.stats["compile_requests"]
    req = eng.submit(PROMPTS[1])
    _drive(eng, [req])
    admit = next(e for e in eng.slow_events if e["state"] == "admit")
    assert admit["compiles"] >= 1
    assert all(type(e["compiles"]) is int for e in eng.slow_events)
    assert sum(e["compiles"] for e in eng.slow_events) \
        <= eng.stats["compile_requests"] - asked
    # the same requests again compile nothing: a stall there was no compile
    eng.slow_events.clear()
    _drive(eng, [eng.submit(PROMPTS[1])])
    assert eng.slow_events
    assert all(e["compiles"] == 0 for e in eng.slow_events)


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


# ---- a stream's ledger: from the engine's delivery to the pull (PR 40) -----

STREAM_KEYS = ("stream_open_s", "stream_wait_s", "stream_held_s",
               "stream_pulls", "stream_ready_pulls", "stream_tokens",
               "stream_pickup_lag_s", "first_pickup_s", "first_pickups",
               "streams_closed", "streams_abandoned")
# a stream's record in `request_log` -> the key of `stats` it folds into
FOLDED = {"stream_open_s": "stream_open_s", "stream_wait_s": "stream_wait_s",
          "stream_held_s": "stream_held_s", "pulls": "stream_pulls",
          "ready_pulls": "stream_ready_pulls",
          "stream_tokens": "stream_tokens",
          "pickup_lag_s": "stream_pickup_lag_s"}


def _ended_streams(eng):
    """The records of the ended streams; every one of them, and their
    sum in `stats`, goes to the two states and nowhere else."""
    recs = [r for r in eng.request_log if "closed" in r]
    for r in recs:
        assert r["stream_open_s"] == pytest.approx(
            r["stream_wait_s"] + r["stream_held_s"], rel=1e-6, abs=1e-6)
        assert r["stream_wait_s"] >= 0 and r["stream_held_s"] >= 0
        assert 0 <= r["ready_pulls"] <= r["stream_tokens"] <= r["pulls"]
    st = eng.stats
    assert st["stream_open_s"] == pytest.approx(
        st["stream_wait_s"] + st["stream_held_s"], rel=1e-6, abs=1e-6)
    return recs


def _pull_all(stream, out):
    """Drain ``stream`` into ``out`` on a thread of its own -> the thread."""
    def pull():
        for tok in stream:
            out.append(tok)
    th = threading.Thread(target=pull, daemon=True)
    th.start()
    return th


def test_a_consumer_that_comes_late_finds_every_token_waiting(model):
    eng = _engine(model)
    stream = eng.submit_stream(PROMPTS[0])
    assert eng.stats["streams_closed"] == 0    # open: nothing folded yet
    _run_dry(eng)                              # the engine runs to the end
    assert "closed" not in eng.request_log[-1]  # done; its stream still open
    toks = list(stream)
    assert len(toks) == 8
    (rec,) = _ended_streams(eng)
    assert rec["closed"] == "done" and rec["stream_tokens"] == 8
    # 8 tokens and the end of the stream: nine pulls, none of them waited
    assert rec["pulls"] == 9 and rec["ready_pulls"] == 8
    assert rec["stream_wait_s"] == 0 and rec["stream_held_s"] > 0
    assert rec["pickup_lag_s"] > 0
    assert rec["t_first_pickup"] >= rec["t_done"] >= rec["t_first"]
    st = eng.stats
    assert st["first_pickups"] == 1
    assert st["first_pickup_s"] == pytest.approx(
        rec["t_first_pickup"] - rec["t_first"])
    assert st["streams_closed"] == 1 and st["streams_abandoned"] == 0
    assert st["entries"] == 0 and rec["t_sent"] is None   # no stamp came


def test_a_consumer_that_is_always_waiting_reads_the_reverse(model):
    eng = _engine(model, decode_chunk=1)
    got = []
    th = _pull_all(eng.submit_stream(PROMPTS[1]), got)
    for _ in range(500):    # one token a step, and a pause: the chip paces
        if not eng.step():
            break
        deadline = time.monotonic() + 60
        while len(got) < eng.stats["tokens_out"] \
                and time.monotonic() < deadline:
            time.sleep(0.001)
        time.sleep(0.02)    # ... while the consumer waits for the next one
    th.join(timeout=120)
    assert not th.is_alive() and len(got) == 8
    (rec,) = _ended_streams(eng)
    # only the first delivery brings two tokens (the prefill's and one); a
    # consumer the machine held up for a pause may find one more
    assert rec["ready_pulls"] <= 4 and rec["pulls"] == 9
    assert rec["stream_wait_s"] > rec["stream_held_s"]
    assert rec["stream_wait_s"] > rec["pickup_lag_s"]
    assert rec["stream_wait_s"] > 0.5 * rec["stream_open_s"]


def test_an_abandoned_stream_folds_once(model):
    eng = _engine(model)
    never_pulled, stream = (eng.submit_stream(p) for p in PROMPTS[:2])
    for _ in range(3):
        eng.step()
    first = next(stream)
    never_pulled.close()
    stream.close()
    stream.close()             # closed already: nothing folds again
    with pytest.raises(StopIteration):
        next(stream)
    st = eng.stats
    assert st["streams_closed"] == st["streams_abandoned"] == 2
    assert st["stream_tokens"] == st["first_pickups"] == 1
    assert st["stream_pulls"] == 1
    _run_dry(eng)              # the engine finishes both all the same
    recs = _ended_streams(eng)
    assert [r["closed"] for r in recs] == ["abandoned"] * 2
    assert sorted(r["stream_tokens"] for r in recs) == [0, 1]
    assert all(r["tokens_out"] == 8 for r in recs)
    assert st["streams_closed"] == 2 and isinstance(first, int)
    # a stream that ends after the engine has finished its request
    late = eng.submit_stream(PROMPTS[2])
    _run_dry(eng)
    next(late)
    late.close()
    assert [r["closed"] for r in _ended_streams(eng)] == ["abandoned"] * 3
    assert st["streams_abandoned"] == 3


def test_streams_that_end_at_once_fold_to_the_sum_of_their_records(model):
    """64 request threads end their streams in the same instant: `stats`
    holds the sum of the 64 records (a lost update of the unlocked
    ``stats[k] += x`` would not)."""
    n = 64
    eng = _engine(model).serve_forever()
    barrier = threading.Barrier(n)
    errors = []

    def consume(i):
        try:
            stream = eng.submit_stream(PROMPTS[i % len(PROMPTS)])
            for _ in range(8):
                next(stream)
            barrier.wait(timeout=120)
            assert next(stream, None) is None    # the end: folds here
        except BaseException as e:  # noqa: BLE001 — shown below
            errors.append(e)
            barrier.abort()

    keep = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=consume, args=(i,), daemon=True)
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not any(t.is_alive() for t in threads) and not errors
    finally:
        sys.setswitchinterval(keep)
        eng.shutdown()
    recs = _ended_streams(eng)
    st = eng.stats
    assert len(recs) == st["streams_closed"] == st["first_pickups"] == n
    for field, key in FOLDED.items():
        assert st[key] == pytest.approx(sum(r[field] for r in recs)), key
    assert st["stream_tokens"] == 8 * n and st["stream_pulls"] == 9 * n
    assert st["first_pickup_s"] == pytest.approx(
        sum(r["t_first_pickup"] - r["t_first"] for r in recs))
    assert st["streams_abandoned"] == 0


def test_a_whole_answer_touches_no_stream_key(model):
    eng = _engine(model)
    assert len(eng.generate(PROMPTS[0])) == 8
    sent = time.time() - 0.25
    assert len(eng.generate(PROMPTS[1], t_sent=sent)) == 8
    st = eng.stats
    assert all(st[k] == 0 for k in STREAM_KEYS)
    assert st["entries"] == 1 and 0.25 <= st["entry_leg_s"] < 60
    assert [r["t_sent"] for r in eng.request_log] == [None, sent]
    assert not any("closed" in r for r in eng.request_log)
    # ... and a stream fills them without making one lazily
    keys = _flat_numbers(eng.stats)
    assert keys >= set(STREAM_KEYS) | {"entry_leg_s", "entries"}
    stream = eng.submit_stream(PROMPTS[2], t_sent=time.time())
    _run_dry(eng)
    assert len(list(stream)) == 8
    assert _flat_numbers(eng.stats) == keys and st["entries"] == 2
    assert st["stream_tokens"] == 8 and st["stream_pulls"] == 9


def test_a_failed_stream_raises_and_folds_as_an_error(model, monkeypatch):
    eng = _engine(model)
    boom = RuntimeError("device lost")
    monkeypatch.setattr(engine_mod, "prefill_slots",
                        lambda *a, **k: (_ for _ in ()).throw(boom))
    stream = eng.submit_stream(PROMPTS[0])
    eng.serve_forever()
    try:
        with pytest.raises(RuntimeError, match="device lost"):
            next(stream)
    finally:
        eng.shutdown()
    st = eng.stats
    assert st["streams_closed"] == 1 and st["streams_abandoned"] == 0
    assert st["stream_tokens"] == 0 and st["stream_pulls"] == 1
    assert st["stream_open_s"] == pytest.approx(
        st["stream_wait_s"] + st["stream_held_s"], rel=1e-6, abs=1e-6)


def test_a_waited_pull_is_a_span_on_the_profilers_clock(model, tmp_path,
                                                        monkeypatch):
    """`serve.stream_wait` in a trace written by the replica's `trace()`:
    one span a pull that found nothing queued, its seconds the ledger's
    ``stream_wait_s``, and none of it under the `engine.` prefix."""
    from ray_tpu.serve.llm import _ContinuousLLMReplica

    shape = dict(slots=2, max_prompt_len=16, max_new_tokens=4)
    keep = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        rep = _ContinuousLLMReplica(model[0], **shape)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", keep)
    rep.engine.warmup()
    outs = [[] for _ in range(6)]

    def traffic(_seconds):   # what `trace()` sleeps through
        monkeypatch.undo()
        threads = [_pull_all(rep.stream(p), out)
                   for p, out in zip(PROMPTS, outs)]
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)

    monkeypatch.setattr(time, "sleep", traffic)
    try:
        rep.trace(1.0, str(tmp_path))
    finally:
        rep.engine.shutdown()
    assert [len(o) for o in outs] == [4] * 6
    recs = _ended_streams(rep.engine)
    assert len(recs) == 6 and all(r["closed"] == "done" for r in recs)
    spans = [ev for evs in _engine_events(
        str(tmp_path), "serve.stream_wait").values() for ev in evs]
    # a pull either found a token, waited, or found the END queued (which
    # no counter tells from a wait: at most one such pull a stream)
    not_ready = sum(r["pulls"] - r["ready_pulls"] for r in recs)
    assert 6 <= not_ready - len(recs) <= len(spans) <= not_ready
    assert sum(e - s for _, s, e in spans) / 1e9 == pytest.approx(
        rep.engine.stats["stream_wait_s"], rel=0.10, abs=2e-5 * len(spans))
    owners = {ev[0] for evs in _engine_events(str(tmp_path)).values()
              for ev in evs}
    assert owners and not any("stream" in name for name in owners)
