"""A serve cell: one continuous-batching replica behind `serve.run`, loaded
from this (driver) process through the deployment handle.

`BenchReplica` is the process that holds the chip. It is the program's
`_ContinuousLLMReplica`, bound exactly as `build_continuous_llm_deployment`
binds it, with READ-ONLY additions: probes, the logits check, warming the
engine's programs, and a profiler trace. Nothing about how a request is
served changes. The driver side (`run`) builds the requests from the
traffic file and `--seed`, paces them, and times every token at the client.

A traced run reads the program's own `engine.*` spans (they are in the
profiler's trace whenever one runs) and differences `engine.stats` over
the traced seconds; it wraps nothing and names no method of the engine.
The replica starts and stops the profiler and never opens the profile: the
driver side reduces it in a child process of its own, after the replica's
last call (`reduce_trace_outside`). The one tie to the engine's private
names that is left is `bench_check` (the served programs return no logits).

The replica's weights are drawn from the traffic file's
`deployment.weights_seed` (`spec.weights_seed`); `--seed` draws the traffic
and the check's prompts.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from ray_tpu.serve.llm import _ContinuousLLMReplica

from benchmark.harness import spec
from benchmark.harness import traffic as traffic_gen


class BenchReplica(_ContinuousLLMReplica):
    def __init__(self, conf: dict, *, platform: str, root: str = spec.ROOT,
                 field_overrides=None, **replica_kwargs):
        t0 = time.time()
        from benchmark.harness import probes

        self._bench_device = probes.device_description()
        self._bench_ready_unix = time.time()
        if self._bench_device["platform"] != platform:
            raise RuntimeError(
                f"expected platform {platform!r}, JAX found "
                f"{self._bench_device['platform']!r}")
        self._bench_conf = conf
        self._bench_arch = spec.load_architecture(conf, root)
        self._bench_fields = spec.transformer_fields(conf, root)
        self._bench_fields.update(field_overrides or {})
        cfg = spec.build_transformer_config(conf, root,
                                            **(field_overrides or {}))
        self._bench_compiles = probes.CompileCounter()
        # the number the weights are drawn from: `bench_check` draws its
        # reference from the same one
        self._bench_weights_seed = replica_kwargs.get("seed", 0)
        super().__init__(cfg, **replica_kwargs)
        self._bench_init_unix = t0
        self._bench_up_unix = time.time()
        self._bench_stats0 = None
        self._bench_trace = None
        self._bench_program_temp_bytes = 0   # known once `bench_warm` ran

    # ---- probes ---------------------------------------------------------

    def bench_info(self) -> dict:
        import jax

        weights = sum(x.nbytes for x in jax.tree.leaves(self.engine.params))
        leaves = jax.tree_util.tree_leaves_with_path(self.engine.cache)
        return {"device": self._bench_device,
                "init_unix": self._bench_init_unix,
                "ready_unix": self._bench_ready_unix,
                "up_unix": self._bench_up_unix,
                "weight_bytes": weights,
                "cache_bytes": sum(x.nbytes for _, x in leaves),
                # every leaf of the slot cache, whatever the engine keeps
                # there: keys and values, a recurrent state, a latent
                "cache_leaves": {
                    jax.tree_util.keystr(path, simple=True, separator="."):
                        {"shape": list(x.shape), "dtype": x.dtype.name}
                    for path, x in leaves},
                "decode_chunk": self.engine.decode_chunk,
                "max_inflight": self.engine.max_inflight}

    def bench_warm(self) -> dict:
        """Compile (or load) every program the serving loop can hit, and
        read `memory_analysis()` of the decode chunk, the largest."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.engine import decode_slots

        eng = self.engine
        t0 = time.perf_counter()
        self._bench_compiles.mark()
        # No request has been sent yet: the engine's threads park, and
        # without work they touch neither the cache nor the token chain,
        # so nothing steps the engine while this does.
        eng.warmup()
        mem = decode_slots.lower(
            eng.params, eng.cache, jnp.zeros(eng.slots, jnp.int32),
            jnp.ones(eng.slots, bool), jax.random.key(0), eng.cfg,
            eng.greedy, eng.temperature, eng.eos_id,
            steps=eng.decode_chunk).compile().memory_analysis()
        self._bench_program_temp_bytes = int(mem.temp_size_in_bytes)
        return {"warm_s": time.perf_counter() - t0,
                "programs": self._bench_compiles.since_mark(),
                "program_argument_bytes": int(mem.argument_size_in_bytes),
                "program_temp_bytes": int(mem.temp_size_in_bytes)}

    def bench_check(self, seed: int, lengths) -> dict:
        """`prefill_slots` then a decode step through the slot cache,
        against the full forward of the configuration's plain reference
        (`spec.load_architecture`): logits compared. The
        served programs return tokens only, so they are held to the
        reference through their tokens: the first token `prefill_slots`
        samples and the first one the `decode_slots` chunk program samples
        must be the argmax of the reference's logits (where its top two
        are further apart than the logits' own error). The reference
        computes with weights of its own: the float32 tree the program's
        initialiser makes from the number this replica's weights were
        drawn from, as the replica got it before it stored it its own way
        (`serving_params`), never the engine's tree, so a fault in how the
        replica holds its weights shows. ``seed`` (the run's `--seed`)
        draws the check's prompts and nothing of the model. That
        tree is on the chip where it fits beside what the replica holds
        and on the host where it does not (`reference.tree_fits_on_device`,
        from this replica's own numbers; `reference_weights` in the result
        says which), and the reference computes the same from either. Runs
        on the engine's own cache while it is idle, then resets the slot
        bookkeeping as `warmup()` does: `pos` and `start` to zero, EVERY
        other leaf of the cache kept as the programs left it, whatever its
        name, rank or dtype."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmark.harness import probes, reference
        from ray_tpu.models.engine import (_decode_one, decode_slots,
                                           prefill_slots)
        from ray_tpu.models.generate import _final_logits, _prefill_hidden
        from ray_tpu.models.transformer import init_params

        eng, cfg, fields = self.engine, self.engine.cfg, self._bench_fields
        arch, conf = self._bench_arch, self._bench_conf
        K = len(lengths)
        P = max(eng._bucket(n) for n in lengths)
        prompts = [traffic_gen.prompt_tokens(seed + i, n, cfg.vocab_size)
                   for i, n in enumerate(lengths)]
        toks = np.full((K, P), eng.pad_id, np.int32)
        starts = np.zeros(K, np.int32)
        for i, p in enumerate(prompts):
            toks[i, P - len(p):] = p
            starts[i] = P - len(p)
        slots = jnp.arange(K, dtype=jnp.int32)
        dtype = jnp.dtype(cfg.dtype).name

        prefill_logits = jax.jit(lambda p, t, s: _final_logits(
            p, _prefill_hidden(p, t, cfg, P, s)[0][:, -1:], cfg)[:, 0])
        decode_logits = jax.jit(
            lambda p, c, t: _decode_one(p, c, t, cfg)[1])
        with eng._lock:
            got_pre = prefill_logits(eng.params, jnp.asarray(toks),
                                     jnp.asarray(starts))
            eng.cache, first = prefill_slots(
                eng.params, eng.cache, jnp.asarray(toks), slots,
                jnp.asarray(starts), jax.random.key(0), cfg, True, 1.0)
            pending = jnp.zeros(eng.slots, jnp.int32).at[slots].set(first)
            got_dec = decode_logits(eng.params, eng.cache, pending)[:K]
            # the served chunk program, called as the scheduler calls it
            eng.cache, chunk = decode_slots(
                eng.params, eng.cache,
                pending.astype(eng._next_tok_dev.dtype),
                jnp.zeros(eng.slots, bool).at[slots].set(True),
                jax.random.key(0), cfg, eng.greedy, eng.temperature,
                eng.eos_id, steps=eng.decode_chunk)
            first, chunk = np.asarray(first), np.asarray(chunk)
            eng.cache = dict(eng.cache,
                             pos=jnp.zeros_like(eng.cache["pos"]),
                             start=jnp.zeros_like(eng.cache["start"]))
        # the replica's own weights came from this initialiser and this key
        # (`_replica_params`)
        info = self.bench_info()
        tree_bytes = 4 * arch.num_params(fields, conf)
        fits = reference.tree_fits_on_device(
            tree_bytes, probes.memory_limit_bytes(),
            info["weight_bytes"] + info["cache_bytes"]
            + self._bench_program_temp_bytes)
        t0 = time.perf_counter()
        ref_params = reference.draw_params(
            lambda k: init_params(k, cfg), self._bench_weights_seed,
            on_chip=fits)
        jax.block_until_ready(ref_params)
        t1 = time.perf_counter()
        rows, ok = [], True
        for i, p in enumerate(prompts):
            want = arch.reference_logits(
                ref_params, p + [int(first[i])], fields, conf, last=2)
            pre = reference.logits_agree(got_pre[i], want[0], dtype)
            dec = reference.logits_agree(got_dec[i], want[1], dtype)
            token_ok = _is_argmax(first[i], want[0], pre) \
                and int(chunk[i, 0]) == int(first[i]) \
                and _is_argmax(chunk[i, 1], want[1], dec)
            rows.append({"prompt_len": len(p), "prefill": pre,
                         "decode": dec, "served_tokens_ok": token_ok})
            ok = ok and pre["ok"] and dec["ok"] and token_ok
        return {"reference": spec.architecture_name(conf), "ok": bool(ok),
                "bucket": P, "rows": rows,
                "reference_weights": "device" if fits else "host",
                "reference_tree_bytes": tree_bytes,
                "draw_s": t1 - t0, "reference_s": time.perf_counter() - t1}

    def bench_mark(self) -> dict:
        self._bench_compiles.mark()
        self._bench_stats0 = dict(self.engine.stats)
        # this process's own clock beside the read, here and in
        # `bench_counters`: the seconds between the two reads hold no
        # round trip of a handle
        return {"unix": time.time(), "clock_s": time.perf_counter()}

    def bench_counters(self) -> dict:
        """Engine counters since the mark, compilations since the mark,
        and the runtime's memory peak."""
        from benchmark.harness import probes

        now, was = dict(self.engine.stats), self._bench_stats0 or {}
        return {"clock_s": time.perf_counter(),
                "engine": {k: now[k] - was.get(k, 0) for k in now},
                "engine_total": now, "slots": self.engine.slots,
                "compilations": self._bench_compiles.since_mark(),
                "memory_peak_bytes": probes.memory_peak_bytes()}

    # ---- tracing (a traced run only) ------------------------------------

    def bench_trace_start(self, trace_dir: str) -> dict:
        """Start the profiler; the engine's own `engine.*` spans land in
        its trace. The counters are read once it has started: what the
        engine does while it starts is not in the trace."""
        import jax

        from benchmark.harness import probes

        jax.profiler.start_trace(
            trace_dir, profiler_options=probes.trace_options())
        self._bench_trace = {"dir": trace_dir,
                             "stats0": dict(self.engine.stats),
                             "t0": time.perf_counter()}
        return {"unix": time.time()}

    def bench_trace_stop(self) -> dict:
        import jax

        tr, now = self._bench_trace, dict(self.engine.stats)
        # counters first: stopping the profiler takes seconds, and what
        # the engine does meanwhile is not in the trace
        tr["wall_s"] = time.perf_counter() - tr["t0"]
        tr["stats"] = {k: now[k] - tr["stats0"].get(k, 0) for k in now}
        jax.profiler.stop_trace()
        # what only this process knows; the profile stays where the
        # profiler wrote it and this process never opens it
        # (`reduce_trace_outside`)
        return {"wall_s": tr["wall_s"], "stats": tr["stats"],
                "stop_s": time.perf_counter() - tr["t0"] - tr["wall_s"]}


def _is_argmax(token, want_logits, agreement: dict) -> bool:
    """Is ``token`` the argmax of the reference's logits? Random weights:
    the top two logits can sit within rounding of each other, so a token
    is only held to the argmax when their margin is above the logits' own
    error: six times the measured RMS error, in logit units (the error of
    a difference of two logits has 1.4 times that RMS, so a right program
    fails this about once in 10^5 tokens)."""
    import numpy as np

    want = np.asarray(want_logits, np.float64)
    top2 = np.sort(want)[-2:]
    err = agreement["rel_rms_error"] * float(np.sqrt(np.mean(want ** 2)))
    return float(top2[1] - top2[0]) <= 6 * err \
        or int(token) == int(np.argmax(want))


# ---------------------------------------------------------------- driver

def deploy(conf: dict, traffic: dict, *, platform: str,
           root: str = spec.ROOT, field_overrides=None,
           timeout_s: float = 900.0):
    """`serve.run` of one BenchReplica; -> (handle, its `bench_info` with
    the seconds until it answered). The replica's weights are drawn from
    the traffic file's `deployment.weights_seed`, never from `--seed`."""
    from ray_tpu import serve
    from ray_tpu.serve.deployment import deployment
    from ray_tpu.serve.llm import _tpu_lease

    dep = traffic["deployment"]
    engine_kwargs = {k: dep[k] for k in (
        "slots", "max_prompt_len", "max_new_tokens", "eos_id", "greedy")}
    app = deployment(BenchReplica, name="bench_llm").options(
        num_replicas=1, max_concurrent_queries=dep["max_concurrency"],
        ray_actor_options=_tpu_lease(1)).bind(
            conf, platform=platform, root=root,
            field_overrides=field_overrides,
            seed=spec.seed32(spec.weights_seed(traffic)), **engine_kwargs)
    t0 = time.time()
    handle = serve.run(app, name="bench", route_prefix="/bench",
                       timeout_s=timeout_s)
    info = call(handle, "bench_info")
    info["deploy_s"] = time.time() - t0
    info["chip_worker_ready_s"] = info["ready_unix"] - t0
    return handle, info


def call(handle, method: str, *args, timeout_s: float = 900.0, **kwargs):
    return handle.options(method_name=method).remote(
        *args, **kwargs).result(timeout_s=timeout_s)


class _Client:
    """Sends requests through the handle and times them at this end."""

    def __init__(self, handle, vocab: int, threads: int):
        self.handle = handle
        self.vocab = vocab
        self.pool = ThreadPoolExecutor(max_workers=threads,
                                       thread_name_prefix="bench-client")
        self.stop = threading.Event()
        self.records = []
        self.counted_done = 0
        self._lock = threading.Lock()

    def _record(self, rec):
        with self._lock:
            self.records.append(rec)
            self.counted_done += bool(rec.get("counted"))

    def stream_request(self, req: dict, prompt, t_open: float):
        """One streamed request; every time is seconds from the window's
        opening, on this process's monotonic clock."""
        rec = dict(req, sent_s=time.perf_counter() - t_open, first_s=None,
                   last_s=None, n=0, bad_token=False, error=None,
                   gap_s=0.0, gap_at_s=None)
        gen = None
        try:
            gen = self.handle.options(
                method_name="stream", stream=True).remote(
                    prompt, max_new_tokens=req["output_len"])
            for chunk in gen:
                now = time.perf_counter() - t_open
                if rec["first_s"] is None:
                    rec["first_s"] = now
                elif now - rec["last_s"] > rec["gap_s"]:
                    # the stream's longest silence: tells a stalled engine
                    # (every live stream falls silent at once) from a
                    # stalled generator or handle (sent late, or a late
                    # first token alone)
                    rec["gap_s"], rec["gap_at_s"] = now - rec["last_s"], now
                rec["last_s"] = now
                rec["n"] += 1
                if not 0 <= chunk["token_id"] < self.vocab:
                    rec["bad_token"] = True
                if self.stop.is_set() and not req["counted"]:
                    break   # the run is over: abandon uncounted streams
        except Exception as e:  # noqa: BLE001 — a failed request is data
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        finally:
            if gen is not None:
                gen.close()
        self._record(rec)

    def whole_request(self, req: dict, prompt, t_open: float):
        rec = dict(req, sent_s=time.perf_counter() - t_open, done_s=None,
                   n=0, bad_token=False, error=None)
        try:
            ids = self.handle.remote(
                prompt, max_new_tokens=req["output_len"]).result(
                    timeout_s=600)["token_ids"]
            rec["done_s"] = time.perf_counter() - t_open
            rec["n"] = len(ids)
            rec["bad_token"] = not all(0 <= t < self.vocab for t in ids)
            rec["ids"] = ids if req.get("keep_ids") else None
        except Exception as e:  # noqa: BLE001
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        self._record(rec)
        return rec


def _sleep_until(t_target: float):
    while True:
        left = t_target - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.2) if left > 0.002 else 0)


def repeat_check(handle, vocab: int, seed: int) -> dict:
    """The same prompt twice at once (two slots, one prefill group): greedy
    decoding must give identical tokens, exactly as many as asked."""
    client = _Client(handle, vocab, 2)
    prompt = traffic_gen.prompt_tokens(seed ^ 0xA5A5, 48, vocab)
    req = {"output_len": 12, "counted": False, "keep_ids": True}
    futs = [client.pool.submit(client.whole_request, req, prompt,
                               time.perf_counter()) for _ in range(2)]
    recs = [f.result(timeout=600) for f in futs]
    client.pool.shutdown(wait=True)
    ok = all(r["error"] is None and r["n"] == 12 and not r["bad_token"]
             for r in recs) and recs[0]["ids"] == recs[1]["ids"]
    return {"ok": bool(ok), "tokens": [r.get("ids") for r in recs],
            "errors": [r["error"] for r in recs]}


def _tracer(handle, traffic: dict, trace_dir: str, t_open: float, out: dict):
    """Traced run only: a profiler trace of `trace_s` seconds starting
    `trace_at_s` into the window, and round trips of the replica's
    `device()` through the handle twice a second."""
    t_trace = t_open + traffic.get("trace_at_s", 5.0)

    def trace():
        try:
            _sleep_until(t_trace)
            call(handle, "bench_trace_start", trace_dir)
            _sleep_until(t_trace + traffic.get("trace_s", 4.0))
            out["trace_stop"] = call(handle, "bench_trace_stop")
        except Exception as e:  # noqa: BLE001 — raised again by _run
            out["trace_error"] = f"{type(e).__name__}: {e}"

    def rtt(stop):
        samples = []
        while not stop.is_set():
            t = time.perf_counter()
            call(handle, "device", timeout_s=60)
            samples.append((time.perf_counter() - t) * 1e3)
            stop.wait(0.5)
        out["handle_rtt_ms"] = samples

    stop = threading.Event()
    th = [threading.Thread(target=trace, daemon=True),
          threading.Thread(target=rtt, args=(stop,), daemon=True)]
    for t in th:
        t.start()
    return th, stop


class _Sleeper:
    """A thread of this (driver) process, which never touches JAX or the
    chip, that sleeps 10 ms at a time through the window and keeps its
    longest gap between two wake-ups and when it was. Information only:
    beside an engine stall it tells a machine that froze whole (the
    sleeper stopped too) from a device queue that stopped (it did not)."""

    STEP_S = 0.010

    def __init__(self, t_open: float, window_s: float):
        self.result = {"sleeps": 0, "longest_gap_s": 0.0,
                       "longest_gap_at_s": None, "gaps_over_100ms": 0,
                       "gaps_over_1s": 0}
        self.thread = threading.Thread(
            target=self._loop, args=(t_open, window_s), daemon=True,
            name="bench-sleeper")
        self.thread.start()

    def _loop(self, t_open: float, window_s: float):
        _sleep_until(t_open)
        r, last = self.result, time.perf_counter()
        while last < t_open + window_s:
            time.sleep(self.STEP_S)
            now = time.perf_counter()
            gap = now - last
            r["sleeps"] += 1
            r["gaps_over_100ms"] += gap > 0.1
            r["gaps_over_1s"] += gap > 1.0
            if gap > r["longest_gap_s"]:
                r["longest_gap_s"], r["longest_gap_at_s"] = \
                    gap, last - t_open
            last = now


class _Window:
    """What both kinds of loop do around the measured window: mark the
    replica's counters when it opens, read them when it closes, keep a
    sleeper beside it, and in a traced run trace a few seconds of it."""

    def __init__(self, handle, traffic: dict, args, trace_dir, t_open: float,
                 window_s: float):
        self.extra: dict = {}
        self._tracer = None
        self._sleeper = _Sleeper(t_open, window_s)

        def mark():
            _sleep_until(t_open)
            self.extra["mark"] = call(handle, "bench_mark")
            self.extra["window_open_unix"] = time.time()

        def close():
            _sleep_until(t_open + window_s)
            self.extra["counters"] = call(handle, "bench_counters")

        self._threads = [threading.Thread(target=mark, daemon=True),
                         threading.Thread(target=close, daemon=True)]
        for t in self._threads:
            t.start()
        if args.trace:
            self._tracer = _tracer(handle, traffic, trace_dir, t_open,
                                   self.extra)

    def join(self) -> dict:
        for t in self._threads + [self._sleeper.thread]:
            t.join(timeout=60)
        self.extra["sleeper"] = self._sleeper.result
        if self._tracer:
            threads, stop = self._tracer
            stop.set()
            for t in threads:
                t.join(timeout=120)
        return self.extra


def run_open_loop(handle, traffic: dict, vocab: int, args, trace_dir):
    sched = traffic_gen.open_loop_schedule(traffic, args.seed, args.seconds)
    reqs = sched["requests"]
    prompts = [traffic_gen.prompt_tokens(r["token_seed"], r["prompt_len"],
                                         vocab) for r in reqs]
    client = _Client(handle, vocab, traffic.get("client_threads", 160))
    t_open = time.perf_counter() + sched["ramp_s"] + 0.25
    window = _Window(handle, traffic, args, trace_dir, t_open,
                     sched["window_s"])
    counted_total = sched["n_counted"]
    futures = []
    for req, prompt in zip(reqs, prompts):
        _sleep_until(t_open + req["due_s"])
        if req["due_s"] >= sched["window_s"] \
                and client.counted_done >= counted_total:
            break   # every counted request has finished: stop the tail
        futures.append(client.pool.submit(client.stream_request, req,
                                          prompt, t_open))
    deadline = time.perf_counter() + 120
    while client.counted_done < counted_total \
            and time.perf_counter() < deadline:
        time.sleep(0.05)
    client.stop.set()
    extra = window.join()
    for f in futures:
        f.result(timeout=120)
    client.pool.shutdown(wait=True)
    return sched, client.records, extra


def run_closed_loop(handle, traffic: dict, vocab: int, args, trace_dir):
    sched = traffic_gen.closed_loop_schedule(traffic, args.seed)
    pool = sched["pool"]
    prompts = [traffic_gen.prompt_tokens(r["token_seed"], r["prompt_len"],
                                         vocab) for r in pool]
    n_clients = sched["clients"]
    client = _Client(handle, vocab, n_clients)
    nxt = {"i": 0}

    def made():   # the engine's own count of the tokens it has made
        return call(handle, "bench_counters")["engine_total"]["tokens_out"]

    made0 = made()   # before the ramp's first request
    t_open = time.perf_counter() + sched["ramp_s"]
    window_s = args.seconds
    window = _Window(handle, traffic, args, trace_dir, t_open, window_s)

    def loop():
        while time.perf_counter() < t_open + window_s:
            with client._lock:
                i = nxt["i"]
                nxt["i"] += 1
            req = dict(pool[i % len(pool)], counted=True)
            client.whole_request(req, prompts[i % len(pool)], t_open)

    futs = [client.pool.submit(loop) for _ in range(n_clients)]
    for f in futs:
        f.result(timeout=window_s + sched["ramp_s"] + 600)
    extra = window.join()
    client.pool.shutdown(wait=True)
    extra["whole_run"] = {   # the last client has its answer
        "engine_tokens_out": made() - made0,
        "client_tokens": sum(r["n"] for r in client.records)}
    sched["window_s"] = window_s
    return sched, client.records, extra


def reduce_open_loop(sched: dict, records: list) -> dict:
    """Client-side samples of the counted requests -> the cell's numbers."""
    counted = [r for r in records if r["counted"]]
    good = [r for r in counted if r["error"] is None and not r["bad_token"]
            and r["n"] == r["output_len"]]
    ttft = [(r["first_s"] - r["due_s"]) * 1e3 for r in good]
    tpot = [(r["last_s"] - r["first_s"]) / (r["n"] - 1) * 1e3
            for r in good if r["n"] > 1]
    late = [(r["sent_s"] - r["due_s"]) * 1e3 for r in counted]
    worst = max(good, key=lambda r: r["gap_s"], default=None)
    health = {"late_max_ms": max(late, default=None),
              "stream_gap_max_ms": worst and worst["gap_s"] * 1e3,
              "stream_gap_max_at_s": worst and worst["gap_at_s"],
              "streams_silent_over_1s": sum(r["gap_s"] > 1.0 for r in good),
              "ttft_max_ms": max(ttft, default=None)}
    return {"attempted": sched["n_counted"], "health": health,
            "failed": sched["n_counted"] - len(good),
            "ttft_ms": ttft, "tpot_ms": tpot, "late_ms": late,
            "errors": sorted({r["error"] for r in counted
                              if r["error"]})[:5],
            "last_done_s": max((r["last_s"] or 0.0) for r in counted)
            if counted else None}


def reduce_closed_loop(sched: dict, records: list) -> dict:
    W = sched["window_s"]
    in_window = [r for r in records
                 if r["error"] is not None or 0 <= (r["done_s"] or -1) < W]
    good = [r for r in in_window if r["error"] is None
            and not r["bad_token"] and r["n"] == r["output_len"]]
    done = sorted(r["done_s"] for r in good)
    quiet = max(((b - a, b) for a, b in zip([0.0] + done, done + [W])),
                default=(None, None))
    health = {"longest_quiet_s": quiet[0], "longest_quiet_until_s": quiet[1],
              "latency_max_ms": max(
                  ((r["done_s"] - r["sent_s"]) * 1e3 for r in good),
                  default=None)}
    return {"attempted": len(in_window), "health": health,
            "failed": len(in_window) - len(good),
            "tokens": sum(r["n"] for r in good), "window_s": W,
            "latency_ms": [(r["done_s"] - r["sent_s"]) * 1e3 for r in good],
            "errors": sorted({r["error"] for r in in_window
                              if r["error"]})[:5]}


def run(cell: dict, conf: dict, traffic: dict, args, *, root: str,
        platform="tpu", field_overrides=None, trace_dir=None) -> dict:
    """Driver side of a serve cell: deploy, warm, check, load, reduce."""
    from ray_tpu import serve

    try:
        return _run(conf, traffic, args, root, platform, field_overrides,
                    trace_dir)
    finally:
        serve.shutdown()


def _run(conf, traffic, args, root, platform, field_overrides, trace_dir):
    out: dict = {}
    handle, info = deploy(conf, traffic, platform=platform, root=root,
                          field_overrides=field_overrides)
    out["info"] = info
    out["chip_worker_ready_s"] = info["chip_worker_ready_s"]
    out["warm"] = call(handle, "bench_warm")
    chk = traffic["check"]
    out["check"] = call(handle, "bench_check", spec.seed32(args.seed),
                        chk["prompt_lens"])
    vocab = spec.transformer_fields(conf, root)["vocab_size"]
    if field_overrides and "vocab_size" in field_overrides:
        vocab = field_overrides["vocab_size"]
    out["repeat"] = repeat_check(handle, vocab, spec.seed32(args.seed))
    runner = run_open_loop if traffic["kind"] == "open_loop" \
        else run_closed_loop
    sched, records, extra = runner(handle, traffic, vocab, args, trace_dir)
    out.update(extra)
    reducer = reduce_open_loop if traffic["kind"] == "open_loop" \
        else reduce_closed_loop
    out["client"] = reducer(sched, records)
    out["n_requests_sent"] = len(records)
    if args.trace and "trace_error" in out:
        raise RuntimeError("the traced window failed: " + out["trace_error"])
    # the engine's heartbeat, every run: what stalled, on which thread,
    # for how long and when (`at_s`: seconds from the window's opening;
    # past the window it is the tail)
    out["slow_events"] = [
        dict(ev, at_s=ev["t_wall"] - out["window_open_unix"])
        for ev in call(handle, "engine_slow_events")]
    if args.trace:   # after the replica's last call
        t0 = time.perf_counter()
        out["trace"] = reduce_trace_outside(trace_dir, out["trace_stop"],
                                            root)
        out["trace_reduce_s"] = time.perf_counter() - t0
    return out


def reduce_trace_outside(trace_dir: str, stopped: dict,
                         root: str = spec.ROOT) -> dict:
    """The traced run's profile -> what the readers read, reduced in a
    child of THIS (driver) process and never in the replica: reading a
    profile and walking its events hold an interpreter's lock for tens of
    seconds, and a replica that does not answer `metrics()` for 10 s is
    taken for dead (`serve/controller.py`). A child, because the reader is
    JAX's and the driver process imports no JAX; held to the CPU, because
    the replica still has the chip. ``stopped`` is what `bench_trace_stop`
    returned: the traced seconds and the engine's own counters differenced
    over them. A profile that is missing or cannot be read fails the run:
    no retry, and no empty trace in its place."""
    reader = os.path.join(root, "benchmark", "harness", "xplane.py")
    child = subprocess.run(
        # -P keeps the file's directory off the child's path: a module of
        # the harness (`stats`, `traffic`) must not shadow one JAX imports
        [sys.executable, "-P", reader, trace_dir],
        text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=240)
    if child.returncode != 0:
        raise RuntimeError(
            f"the profile under {trace_dir} could not be reduced (the "
            f"child exited with code {child.returncode}): "
            + child.stderr.strip()[-600:])
    red = json.loads(child.stdout.splitlines()[-1])
    if "xplane_bytes" not in red:   # `reduce_trace` found no file
        raise RuntimeError(f"no profile (*.xplane.pb) under {trace_dir}: "
                           "the replica's profiler wrote none")
    red.pop("op_count", None)
    red["trace_wall_s"] = stopped["wall_s"]
    # the engine's own counts, differenced over the traced seconds
    red["padded_prefill_tokens"] = stopped["stats"].get(
        "prefill_padded_tokens", 0)
    red["prefill_dispatches"] = stopped["stats"].get("prefill_dispatches", 0)
    red["engine_in_trace"] = stopped["stats"]
    return red


def judge(out: dict) -> dict:
    c = out["client"]
    checks = {
        "reference_agrees": out["check"]["ok"],
        "repeat_identical": out["repeat"]["ok"],
        "no_request_failed": c["failed"] == 0 and c["attempted"] > 0,
        "no_compilation_in_window": out["counters"]["compilations"] == 0,
    }
    if "whole_run" in out:
        # a closed loop's rate rests on the engine's `tokens_out`: every
        # request of a closed loop returns, so over the whole run the
        # count EQUALS the tokens the clients received, to the token
        run = out["whole_run"]
        checks["tokens_made_are_tokens_received"] = \
            run["engine_tokens_out"] == run["client_tokens"]
    return checks
