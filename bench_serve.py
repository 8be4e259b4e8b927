"""Serving benchmarks: r14 serve-at-scale + the r5 LLM/proxy sections.

r14 phases (default; writes ``SERVE_BENCH_r14.json``) — the ROADMAP's
flagship serving workload on a multi-node cluster of REAL agent
processes with a paced object-plane uplink:

  coldstart  Broadcast-powered replica cold-start: deployment weights
             (64 MiB) travel BY REFERENCE through the object plane;
             scale-up 1->8 with pre-warm at decision time (OBJECT_WARM
             -> r13 prefetch -> r9 cooperative broadcast tree) vs the
             sequential-fetch baseline (one replica at a time — the
             "linear in concurrent scale-ups" shape the broadcast
             removes). Gates: coop wall <= 0.5x sequential; root egress
             <= 2xS for the concurrent scale-up. Also records the
             cold-start vs fleet-size curve (2/4/8) and a flat
             concurrent trial (broadcast_fanout=0) for the egress
             comparison.

  autoscale  Telemetry-driven autoscaling under sustained OPEN-LOOP
             traffic (fixed arrival rate, unbounded concurrency):
             a queue-depth surge must trigger a scale-up within one
             policy period, p50/p99 are recorded before/during/after
             each scale event, the steady surge phase must show ZERO
             direction reversals (asserted from serve_autoscale cluster
             events), and p99 during the scale-up must stay within 2x
             the steady-state p99 (no ingress stall while replicas
             warm). A separate SLO-burn section drives slow-but-sparse
             requests that only the p99 signal can see.

  ingress    Zero-copy ingress A/B: large (2 MiB) request tensors
             through the handle path with the by-ref conversion ON
             (``serve_request_by_ref_min_bytes``) vs OFF (inline
             pickle), interleaved seed/new pairs, median-of-pairwise
             ratios (MICROBENCH_r6 methodology).

Legacy phases (r5 artifact shape): ``proxy`` (HTTP ingress RPS on a
noop deployment), ``llm`` (continuous-batching vs cohort on the model
engine; needs an accelerator unless ``--model tiny`` is asked for).

Run: ``python bench_serve.py [--phases coldstart,autoscale,ingress]
[--out SERVE_BENCH_r14.json]``. Each phase embeds a ``loop_lag`` block
(head IO-loop health during the phase, bench_scale.py convention).
"""

import argparse
import json
import os
import sys
import threading
import time

# jax is imported only by the llm phase, which computes in this process:
# the r14 phases start agent processes, and a parent that touched JAX
# would hold the chip a child may need.
from bench_scale import _LoopLag  # loop_lag block convention

# ------------------------------------------------------- r14 constants

WEIGHTS_MIB = 64
# shared per-host uplink for the object plane during coldstart (the r9
# regime: pacing dominates, not 2-vCPU memcpy ceilings — at 40+ MiB/s
# the per-trial control overhead of the sequential baseline starts to
# rival its transfer time and the A/B stops isolating the data plane)
LINK_BPS = 20 * 1024 * 1024
FLEET = 8
AB_PAIRS = 3  # odd: the pairwise-ratio median is a real middle pair
INGRESS_PAYLOAD_MIB = 2
INGRESS_PAIRS = 3
INGRESS_CLIENTS = 8
INGRESS_HALF_S = 6.0


def _pct(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(p / 100 * len(sorted_vals)))]


def _lat_ms(lats):
    s = sorted(lats)
    return {"n": len(s),
            "p50_ms": round(_pct(s, 50) * 1000, 1),
            "p99_ms": round(_pct(s, 99) * 1000, 1)}


# ========================================================== r14: shared


def _boot_cluster(n_agents: int):
    """Embedded head with NO schedulable CPUs + real agent processes
    (1 CPU each): every serve replica requesting a CPU lands on an
    agent, so cold-start moves weights across host boundaries. Agents
    inherit the paced object-plane uplink via the env-overridable
    config knob."""
    os.environ["RAY_TPU_HOST_EGRESS_LIMIT_BPS"] = str(LINK_BPS)
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 0, "num_tpus": 0})
    handles = []
    for _ in range(n_agents):
        handles.append(cluster.add_remote_node(
            num_cpus=1, object_store_memory=192 << 20))
    return cluster, handles


def _head():
    from ray_tpu.core.api import _head as h

    return h


def _coldstart_model(version="w1"):
    import numpy as np

    from ray_tpu import serve

    @serve.deployment(version=version, health_check_timeout_s=180,
                      ray_actor_options={"num_cpus": 1})
    class Model:
        def __init__(self, w):
            self.total = float(np.asarray(w).sum())

        def __call__(self, x=None):
            return self.total

    return Model


# ====================================================== r14: coldstart


def _warm_worker_pool(n_agents: int):
    """Leave one warm idle interpreter on every agent: a task wave of
    num_cpus=1 tasks spreads one per single-CPU agent, and the workers
    drop back to the idle pool on return. Replica actors then REUSE
    those interpreters (the head's idle-worker lease path) instead of
    forking — on this 2-vCPU host 8 concurrent forks cost more wall
    than the 64 MiB transfer the trial measures, and production fleets
    keep warm pools anyway (the reference WorkerPool's prestart)."""
    import ray_tpu

    @ray_tpu.remote(num_cpus=1)
    def _touch():
        time.sleep(0.3)
        return 1

    ray_tpu.get([_touch.remote() for _ in range(n_agents)], timeout=300)


def _coldstart_trial(Model, weights, mode: str, fleet: int) -> dict:
    """One cold-start trial: deploy 1 replica (weights land on its
    node), then scale to ``fleet``. mode: "coop" (concurrent scale-up,
    cooperative broadcast), "flat" (concurrent, broadcast_fanout=0 —
    every puller stripes off the sealed holders), "seq" (one replica
    at a time — the baseline whose wall-clock is linear in fleet)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.config import get_config

    cfg = get_config()
    old_fanout = cfg.broadcast_fanout
    old_sources = cfg.pull_max_sources
    # seq is the naive serving baseline — every replica pulls the model
    # as ONE full stream (no striping, no relays: what a pod pulling
    # weights from a model store does), one replica at a time. flat (0)
    # keeps the concurrency but stripes off the sealed holder set (the
    # pre-r9 plan). coop is the r9/r14 path at the default fanout.
    if mode == "seq":
        cfg.broadcast_fanout = 0
        cfg.pull_max_sources = 1
    elif mode == "flat":
        cfg.broadcast_fanout = 0
    else:
        # narrow tree for the one-object weight broadcast: every hop is
        # a FULL-RATE single-source stream, so chunk pipelining holds at
        # the paced link (wider fanouts split each root's bucket into
        # half-rate striped streams — measured here, the relay chain
        # degrades toward store-and-forward and the leaf pays ~3x S/link)
        cfg.broadcast_fanout = 1
        cfg.pull_max_sources = 1
    head = _head()
    try:
        _warm_worker_pool(FLEET)
        wref = ray_tpu.put(weights)
        deadline = time.monotonic() + 30
        while wref.id not in head.objects and time.monotonic() < deadline:
            time.sleep(0.01)
        serve.run(Model.options(num_replicas=1).bind(wref),
                  name="cold", route_prefix=None, timeout_s=300)
        served0 = head._transfer_server.pull_requests
        egress0 = head._transfer_server.bytes_served
        t0 = time.monotonic()
        if mode == "seq":
            for k in range(2, fleet + 1):
                serve.run(Model.options(num_replicas=k).bind(wref),
                          name="cold", route_prefix=None, timeout_s=300)
        else:
            serve.run(Model.options(num_replicas=fleet).bind(wref),
                      name="cold", route_prefix=None, timeout_s=300)
        wall = time.monotonic() - t0
        st = serve.status()["applications"]["cold"]["deployments"]["Model"]
        auto = st["autoscaler"]
        expect = float(weights.sum())
        h = serve.get_app_handle("cold")
        vals = {h.remote().result(timeout_s=60) for _ in range(fleet * 2)}
        assert vals == {expect}, f"replica weights diverged: {vals}"
        out = {
            "mode": mode, "fleet": fleet,
            "wall_s": round(wall, 3),
            "root_egress_mib": round(
                (head._transfer_server.bytes_served - egress0) / 2**20, 1),
            "root_streams": head._transfer_server.pull_requests - served0,
            "cold_start": auto["cold_start"],
            "weights_by_ref": auto["weights_by_ref"],
        }
        serve.delete("cold")
        return out
    finally:
        cfg.broadcast_fanout = old_fanout
        cfg.pull_max_sources = old_sources


def bench_coldstart() -> dict:
    import numpy as np

    rng = np.random.default_rng(11)
    size = WEIGHTS_MIB * 2**20 // 8

    def fresh_weights():
        # fresh bytes per trial: every trial's object is cold on every
        # node (old trials' copies are GC'd when their refs die)
        return rng.random(size)

    Model = _coldstart_model()
    out = {"weights_mib": WEIGHTS_MIB,
           "link_mib_s": LINK_BPS // 2**20,
           "fleet": FLEET}

    # warmup: first trial pays every agent's worker-interpreter fork
    # plus jax/numpy imports; discard it
    _coldstart_trial(Model, fresh_weights(), "coop", FLEET)

    # headline A/B: interleaved (seq, coop) pairs, median of pairwise
    pairs = []
    for _ in range(AB_PAIRS):
        seq = _coldstart_trial(Model, fresh_weights(), "seq", FLEET)
        coop = _coldstart_trial(Model, fresh_weights(), "coop", FLEET)
        pairs.append({"seq": seq, "coop": coop,
                      "ratio": round(coop["wall_s"] / seq["wall_s"], 3)})
        print(json.dumps(pairs[-1]), file=sys.stderr, flush=True)
    ratios = sorted(p["ratio"] for p in pairs)
    out["ab_pairs"] = pairs
    out["coop_over_seq_wall_median"] = ratios[len(ratios) // 2]
    coop_egress = [p["coop"]["root_egress_mib"] for p in pairs]
    out["coop_root_egress_over_S_max"] = round(
        max(coop_egress) / WEIGHTS_MIB, 2)

    # flat concurrent (fanout=0): same concurrency, no broadcast tree —
    # isolates what the tree buys in root egress
    out["flat_concurrent"] = _coldstart_trial(
        Model, fresh_weights(), "flat", FLEET)

    # cold-start vs fleet-size curve (coop): near-constant, not linear
    out["curve"] = [
        _coldstart_trial(Model, fresh_weights(), "coop", n)
        for n in (2, 4, 8)]

    out["gates"] = {
        "coop_wall_le_half_seq":
            out["coop_over_seq_wall_median"] <= 0.5,
        # <= 2xS plus one transfer chunk of rounding slack
        "coop_root_egress_le_2S":
            out["coop_root_egress_over_S_max"] <= 2.0 + 8 / WEIGHTS_MIB,
    }
    return out


# ====================================================== r14: autoscale


def _open_loop(submit, rate_hz: float, duration_s: float, records: list,
               pool) -> None:
    """Fixed-arrival-rate driver: submissions never wait for earlier
    completions (open loop — queueing shows up as latency, closed-loop
    clients would throttle the surge instead)."""
    t_next = time.perf_counter()
    t_end = t_next + duration_s

    def one():
        t0 = time.perf_counter()
        try:
            submit()
            records.append((time.time(), time.perf_counter() - t0, True))
        except Exception:  # noqa: BLE001 — count, don't die
            records.append((time.time(), time.perf_counter() - t0, False))

    while t_next < t_end:
        now = time.perf_counter()
        if now < t_next:
            time.sleep(t_next - now)
        pool.submit(one)
        t_next += 1.0 / rate_hz


def _window(records, t0, t1):
    return _lat_ms([dt for ts, dt, ok in records if ok and t0 <= ts < t1])


def bench_autoscale() -> dict:
    from concurrent.futures import ThreadPoolExecutor

    import ray_tpu  # noqa: F401
    from ray_tpu import serve, state

    out = {}
    pool = ThreadPoolExecutor(max_workers=128)
    # scale-ups must reuse warm idle interpreters: a cold fork + numpy
    # import storm on this 2-vCPU host starves the RUNNING replicas'
    # serving path and pollutes the very p99-during-scale-up window the
    # gate measures (production fleets prestart workers anyway)
    _warm_worker_pool(FLEET)

    # ---- section 1: SLO burn. Sparse but SLOW requests: concurrency
    # stays under target (desired=1 by load), only the phase-histogram
    # p99 can see the degradation.
    @serve.deployment(
        version="s1", max_concurrent_queries=16,
        health_check_period_s=0.2,
        ray_actor_options={"num_cpus": 1},
        autoscaling_config=dict(
            min_replicas=1, max_replicas=3,
            target_num_ongoing_requests_per_replica=4.0,
            upscale_delay_s=0.5, downscale_delay_s=30.0,
            latency_slo_ms=300.0, slo_phase="e2e"))
    class SloModel:
        def __call__(self, ms):
            time.sleep(ms / 1000.0)
            return ms

    h = serve.run(SloModel.bind(), name="slo", route_prefix=None,
                  timeout_s=120)
    recs = []
    _open_loop(lambda: h.remote(40).result(timeout_s=60), 4.0, 8.0,
               recs, pool)          # fast steady: p99 ~ 45ms, desired 1
    slow_start = time.time()
    _open_loop(lambda: h.remote(600).result(timeout_s=60), 2.0, 16.0,
               recs, pool)          # slow: p99 blows the 300ms SLO
    time.sleep(2)
    evs = state.list_cluster_events(
        filters=[("type", "=", "serve_autoscale")])
    # only burns AFTER the slow traffic started count as reactions (the
    # per-func histograms are cumulative cluster-wide: an earlier
    # phase's slow samples can pre-arm the signal)
    slo_evs = [e for e in evs if e["extra"].get("app") == "slo"
               and "slo_burn" in e["extra"].get("reason", "")
               and e["ts"] >= slow_start - 0.25]
    out["slo_burn"] = {
        "fast_p99": _window(recs, 0, slow_start),
        "slow_p99": _window(recs, slow_start, time.time()),
        "upscale_events": len(slo_evs),
        "first_reason": slo_evs[0]["extra"]["reason"] if slo_evs else "",
        "reaction_s": round(slo_evs[0]["ts"] - slow_start, 2)
        if slo_evs else None,
    }
    serve.delete("slo")
    print(json.dumps({"slo_burn": out["slo_burn"]}), file=sys.stderr,
          flush=True)

    # ---- section 2: queue-depth surge under sustained open-loop load.
    UP_DELAY = 0.5
    @serve.deployment(
        version="a1", max_concurrent_queries=16,
        health_check_period_s=0.5,
        ray_actor_options={"num_cpus": 1},
        autoscaling_config=dict(
            min_replicas=1, max_replicas=4,
            target_num_ongoing_requests_per_replica=0.5,
            upscale_delay_s=UP_DELAY, downscale_delay_s=6.0,
            downscale_cooldown_s=8.0))
    class Sleeper:
        def __call__(self, ms):
            time.sleep(ms / 1000.0)
            return ms

    _warm_worker_pool(FLEET)  # slo replicas consumed/killed workers
    h = serve.run(Sleeper.bind(), name="surge", route_prefix=None,
                  timeout_s=120)
    recs = []
    t_low0 = time.time()
    _open_loop(lambda: h.remote(60).result(timeout_s=60), 6.0, 8.0,
               recs, pool)                     # steady low: fleet of 1
    t_surge = time.time()
    _open_loop(lambda: h.remote(60).result(timeout_s=60), 30.0, 22.0,
               recs, pool)                     # surge: fleet must grow
    t_after = time.time()
    _open_loop(lambda: h.remote(60).result(timeout_s=60), 6.0, 12.0,
               recs, pool)                     # back to low: shrink
    t_end = time.time()
    time.sleep(4)  # the averaged downscale window may land post-traffic

    evs = [e for e in state.list_cluster_events(
        filters=[("type", "=", "serve_autoscale")])
        if e["extra"].get("app") == "surge"]
    ups = [e for e in evs if e["extra"]["direction"] == "up"
           and e["ts"] >= t_surge - 0.5]
    downs = [e for e in evs if e["extra"]["direction"] == "down"]
    # steady surge phase: after the fleet stabilized, before the rate
    # drops — the no-flap window
    steady0, steady1 = t_surge + 8.0, t_after
    dirs = [e["extra"]["direction"] for e in evs
            if steady0 <= e["ts"] < steady1]
    reversals_steady = sum(1 for a, b in zip(dirs, dirs[1:]) if a != b) \
        + len(dirs)  # ANY decision inside the steady window counts
    during = _window(recs, t_surge, t_surge + 6.0)
    steady_high = _window(recs, steady0, steady1)
    st = serve.status()["applications"]["surge"]["deployments"]["Sleeper"]
    out["surge"] = {
        "rates_hz": {"low": 6, "surge": 30},
        "exec_ms": 60,
        "policy_period_s": UP_DELAY + 1.0,  # upscale window + signal poll
        "steady_low": _window(recs, t_low0 + 2, t_surge),
        "during_scale_up": during,
        "steady_surge": steady_high,
        "after_scale_down": _window(recs, t_after + 4, t_end),
        "reaction_s": round(ups[0]["ts"] - t_surge, 2) if ups else None,
        "up_events": [{"ts_rel": round(e["ts"] - t_surge, 2),
                       "from": e["extra"]["from"], "to": e["extra"]["to"],
                       "reason": e["extra"]["reason"]} for e in ups],
        "down_events": len(downs),
        "decisions_in_steady_window": len(dirs),
        "final": st["autoscaler"],
    }
    out["gates"] = {
        "reacted_within_policy_period":
            ups and out["surge"]["reaction_s"] is not None
            and out["surge"]["reaction_s"] <=
            out["surge"]["policy_period_s"] + 1.0,
        "zero_reversals_steady": reversals_steady == 0,
        "p99_during_le_2x_steady":
            during["p99_ms"] <= 2.0 * max(steady_high["p99_ms"], 1.0),
        "scaled_down_after": len(downs) >= 1,
    }
    serve.delete("surge")
    pool.shutdown(wait=False)
    return out


# ======================================================== r14: ingress


def bench_ingress() -> dict:
    """Seed/new A/B of the large-request ingress path through the
    handle: inline pickle (seed: by-ref conversion off) vs by-ref args
    through the object plane (new). Interleaved pairs, median of
    pairwise ratios. Replicas live on remote agent nodes, so the
    payload crosses a host boundary either way.

    Provenance: on THIS host (2 vCPUs, unpaced loopback) both paths are
    memcpy-bound and the inline path already rides the r8 zero-copy
    vectored wire over ONE socket hop, while by-ref pays an extra arena
    hop plus per-object control traffic (put/locate/pull/free) — so
    by-ref loses raw rps here, with the gap closing as payload size
    amortizes the fixed overhead (the ``size_sweep`` rows). The by-ref
    path's wins live where its mechanisms bite and are measured
    elsewhere in this artifact: shared-payload broadcast under a paced
    uplink (coldstart phase) and fetch/dispatch overlap (r13
    BENCH_device_path prefetch A/B, arg_fetch p95 −53%)."""
    import numpy as np

    import ray_tpu  # noqa: F401
    from ray_tpu import serve
    from ray_tpu.core.config import get_config

    @serve.deployment(version="i1", num_replicas=2,
                      max_concurrent_queries=16,
                      ray_actor_options={"num_cpus": 1})
    class SumModel:
        def __call__(self, x):
            return float(np.asarray(x).sum())

    h = serve.run(SumModel.bind(), name="ingress", route_prefix=None,
                  timeout_s=120)
    cfg = get_config()
    old = cfg.serve_request_by_ref_min_bytes

    def half(payload, expect, by_ref: bool) -> dict:
        cfg.serve_request_by_ref_min_bytes = 512 * 1024 if by_ref else 0
        lats, lock = [], threading.Lock()
        stop_at = time.perf_counter() + INGRESS_HALF_S

        def client():
            while time.perf_counter() < stop_at:
                t0 = time.perf_counter()
                assert h.remote(payload).result(timeout_s=120) == expect
                dt = time.perf_counter() - t0
                with lock:
                    lats.append(dt)

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(INGRESS_CLIENTS)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=INGRESS_HALF_S * 4 + 60)
        wall = time.perf_counter() - t0
        return {"rps": round(len(lats) / wall, 1), **_lat_ms(lats)}

    def one_pair(mib: int) -> dict:
        payload = np.random.default_rng(5).random(mib * 2**20 // 8)
        expect = float(payload.sum())
        seed = half(payload, expect, False)
        new = half(payload, expect, True)
        pair = {
            "payload_mib": mib,
            "seed_inline": seed, "new_by_ref": new,
            "rps_ratio": round(new["rps"] / max(seed["rps"], 1e-9), 3),
            "p99_ratio": round(new["p99_ms"] /
                               max(seed["p99_ms"], 1e-9), 3)}
        print(json.dumps(pair), file=sys.stderr, flush=True)
        return pair

    try:
        payload = np.random.default_rng(5).random(
            INGRESS_PAYLOAD_MIB * 2**20 // 8)
        expect = float(payload.sum())
        half(payload, expect, True)   # warm both paths before timing
        half(payload, expect, False)
        pairs = [one_pair(INGRESS_PAYLOAD_MIB)
                 for _ in range(INGRESS_PAIRS)]
        # fixed-overhead amortization: one interleaved pair per larger
        # payload size (per-object control cost stays flat, bytes grow)
        sweep = [one_pair(mib) for mib in (8, 16)]
    finally:
        cfg.serve_request_by_ref_min_bytes = old
    serve.delete("ingress")
    rps = sorted(p["rps_ratio"] for p in pairs)
    p99 = sorted(p["p99_ratio"] for p in pairs)
    return {
        "payload_mib": INGRESS_PAYLOAD_MIB,
        "clients": INGRESS_CLIENTS,
        "pairs": pairs,
        "by_ref_over_inline_rps_median": rps[len(rps) // 2],
        "by_ref_over_inline_p99_median": p99[len(p99) // 2],
        "size_sweep": sweep,
        "note": "unpaced 2-vCPU loopback: both paths memcpy-bound and "
                "inline already rides the r8 zero-copy wire one hop, so "
                "by-ref pays an extra arena hop + per-object control "
                "traffic and loses rps here, amortizing with payload "
                "size (see size_sweep); its wins are the paced-uplink "
                "broadcast cold-start (this artifact) and the r13 "
                "prefetch overlap (BENCH_device_path.json)",
    }


# ================================================ legacy (r5) sections


def _build(model_name: str):
    import jax

    from ray_tpu.models.config import get_config, tiny_config
    from ray_tpu.models.transformer import init_params
    import jax.numpy as jnp

    if jax.default_backend() == "cpu" and model_name != "tiny":
        sys.exit(f"the llm phase measures {model_name} on an accelerator; "
                 "JAX found only the CPU")
    if model_name == "tiny":
        cfg = tiny_config()
    else:
        cfg = get_config(model_name, param_dtype=jnp.bfloat16)
    params = init_params(jax.random.key(0), cfg)
    return model_name, cfg, params


def _workload(rng_seed: int, max_prompt: int, max_new: int):
    """Deterministic chat-shaped request stream: (prompt, max_new).

    80% short answers (U[max/16, max/4]) and 20% long generations
    (U[max/2, max]) — the high-variance mix continuous batching exists
    for: a cohort pays max_new for every member, so the short majority
    is held hostage by the long tail."""
    import random

    rng = random.Random(rng_seed)

    def next_request():
        plen = rng.randint(max(4, max_prompt // 8), max_prompt)
        if rng.random() < 0.8:
            want = rng.randint(max(2, max_new // 16), max(4, max_new // 4))
        else:
            want = rng.randint(max_new // 2, max_new)
        return [rng.randint(1, 200) for _ in range(plen)], want
    return next_request


def _closed_loop(submit, *, clients: int, duration_s: float, seed: int,
                 max_prompt: int, max_new: int):
    """`clients` threads each submit-wait-repeat for `duration_s`;
    returns (latencies, useful_tokens, n_done, wall)."""
    latencies, tokens, lock = [], [0], threading.Lock()
    stop = time.perf_counter() + duration_s

    def client(cid: int):
        nxt = _workload(seed + cid, max_prompt, max_new)
        while time.perf_counter() < stop:
            prompt, want = nxt()
            t0 = time.perf_counter()
            out = submit(prompt, want)
            dt = time.perf_counter() - t0
            with lock:
                latencies.append(dt)
                tokens[0] += len(out)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s * 4 + 120)
    wall = time.perf_counter() - t0
    return latencies, tokens[0], len(latencies), wall


def _percentiles(lat):
    lat = sorted(lat)

    def pct(p):
        if not lat:
            return 0.0
        return lat[min(len(lat) - 1, int(p / 100 * len(lat)))]
    return {"p50_s": round(pct(50), 4), "p95_s": round(pct(95), 4),
            "p99_s": round(pct(99), 4)}


def bench_continuous(cfg, params, *, slots, max_prompt, max_new,
                     clients, duration_s, decode_chunk=16,
                     fetch_every=4, max_inflight=6):
    from ray_tpu.models.engine import InferenceEngine

    eng = InferenceEngine(params, cfg, slots=slots,
                          max_prompt_len=max_prompt,
                          max_new_tokens=max_new,
                          decode_chunk=decode_chunk,
                          fetch_every=fetch_every,
                          max_inflight=max_inflight)
    # compile every (group, bucket) prefill + the decode chunk up front
    eng.warmup().serve_forever()
    try:
        def submit(prompt, want):
            return eng.generate(prompt, want, timeout=600)

        lat, toks, n, wall = _closed_loop(
            submit, clients=clients, duration_s=duration_s, seed=17,
            max_prompt=max_prompt, max_new=max_new)
        return {"engine": "continuous", "requests": n,
                "rps": round(n / wall, 2),
                "useful_tokens_per_s": round(toks / wall, 1),
                "decode_steps": eng.stats["decode_steps"],
                "prefills": eng.stats["prefills"],
                "prefill_dispatches": eng.stats["prefill_dispatches"],
                "fetches": eng.stats["fetches"],
                "fetch_wall_s": round(eng.stats["fetch_wall_s"], 2),
                "dispatch_wall_s": round(eng.stats["dispatch_wall_s"], 2),
                **_percentiles(lat)}
    finally:
        eng.shutdown()


def bench_cohort(cfg, params, *, slots, max_prompt, max_new,
                 clients, duration_s):
    """Round-3 cohort path: coalesce up to `slots` requests, run ONE
    generate() to max_new for all, trim per request — the policy
    continuous batching replaces."""
    import numpy as np

    import jax
    from ray_tpu.models.generate import generate
    from ray_tpu.serve.batching import _Batcher

    batcher = _Batcher(slots, 0.005)

    def run_batch(requests):
        prompts = [p for (p, _w) in requests]
        toks = np.zeros((slots, max_prompt), np.int32)
        start = np.zeros(slots, np.int32)
        for i, p in enumerate(prompts):
            toks[i, max_prompt - len(p):] = p
            start[i] = max_prompt - len(p)
        out = generate(params, toks, cfg, max_new_tokens=max_new,
                       greedy=True, rng=jax.random.key(0),
                       start=start)
        out = np.asarray(out)[:len(prompts), max_prompt:]
        return [out[i, :w].tolist() for i, (_p, w) in enumerate(requests)]

    # warm/compile the one batched program
    run_batch([([1, 2, 3], 2)])

    def submit(prompt, want):
        return batcher.submit(run_batch, (prompt, want))

    lat, toks, n, wall = _closed_loop(
        submit, clients=clients, duration_s=duration_s, seed=17,
        max_prompt=max_prompt, max_new=max_new)
    return {"engine": "cohort", "requests": n, "rps": round(n / wall, 2),
            "useful_tokens_per_s": round(toks / wall, 1),
            **_percentiles(lat)}


def bench_proxy(clients: int, duration_s: float) -> dict:
    """Proxy-level RPS/latency on a trivial deployment (measures the
    asyncio ingress + router + replica hop, NOT model compute; ref:
    the reference's serve microbenchmarks hit a noop deployment the
    same way). Keep-alive HTTP/1.1 connections, closed loop."""
    import http.client

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4, num_tpus=0, ignore_reinit_error=True)

    @serve.deployment(max_concurrent_queries=64)
    def noop(payload):
        return payload

    serve.run(noop.bind(), name="proxybench", route_prefix="/noop")
    port = serve.start()

    lat, lock = [], threading.Lock()
    stop_at = time.perf_counter() + duration_s

    def client():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        body = json.dumps({"k": 1})
        try:
            while time.perf_counter() < stop_at:
                t0 = time.perf_counter()
                conn.request("POST", "/noop", body=body)
                resp = conn.getresponse()
                resp.read()
                dt = time.perf_counter() - t0
                if resp.status == 200:
                    with lock:
                        lat.append(dt)
        finally:
            conn.close()

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s * 2 + 60)
    wall = time.perf_counter() - t0
    serve.shutdown()
    return {"deployment": "noop", "clients": clients,
            "requests": len(lat), "rps": round(len(lat) / wall, 1),
            **_percentiles(lat)}


def bench_llm(args) -> dict:
    import jax

    from ray_tpu.utils.compile_cache import enable_compile_cache

    # warmup compiles the full (bucket x group) program menu (~minutes);
    # cache it so repeat runs measure serving, not compilation
    enable_compile_cache()
    model_name, cfg, params = _build(args.model)
    if model_name == "tiny":
        args.duration = min(args.duration, 10.0)
    cont = bench_continuous(cfg, params, slots=args.slots,
                            max_prompt=args.max_prompt,
                            max_new=args.max_new, clients=args.clients,
                            duration_s=args.duration,
                            decode_chunk=args.decode_chunk,
                            fetch_every=args.fetch_every,
                            max_inflight=args.max_inflight)
    print(json.dumps(cont), file=sys.stderr)
    coh = bench_cohort(cfg, params, slots=args.slots,
                       max_prompt=args.max_prompt, max_new=args.max_new,
                       clients=args.clients, duration_s=args.duration)
    print(json.dumps(coh), file=sys.stderr)
    return {
        "model": model_name,
        "backend": jax.default_backend(),
        "slots": args.slots,
        "clients": args.clients,
        "continuous": cont,
        "cohort": coh,
        "continuous_over_cohort_tokens":
            round(cont["useful_tokens_per_s"] /
                  max(coh["useful_tokens_per_s"], 1e-9), 3),
        "continuous_over_cohort_p99":
            round(cont["p99_s"] / max(coh["p99_s"], 1e-9), 3),
    }


# ================================================================ main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--phases", default="coldstart,autoscale,ingress",
                    help="comma list: coldstart,autoscale,ingress,"
                         "proxy,llm")
    ap.add_argument("--out", default="SERVE_BENCH_r14.json")
    ap.add_argument("--agents", type=int, default=FLEET,
                    help="real agent processes for the r14 phases")
    # legacy llm/proxy knobs
    ap.add_argument("--model", default="llama3-1b")
    ap.add_argument("--duration", type=float, default=45.0)
    ap.add_argument("--clients", type=int, default=24)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-prompt", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--decode-chunk", type=int, default=16)
    ap.add_argument("--fetch-every", type=int, default=4)
    ap.add_argument("--max-inflight", type=int, default=6)
    ap.add_argument("--proxy-clients", type=int, default=16)
    ap.add_argument("--proxy-duration", type=float, default=15.0)
    args = ap.parse_args()
    phases = {p.strip() for p in args.phases.split(",") if p.strip()}

    result = {
        "benchmark": "serve_at_scale" if phases & {
            "coldstart", "autoscale", "ingress"}
        else "llm_serving_continuous_batching",
        "hardware": f"single host, {os.cpu_count()} cpu, "
                    f"{args.agents} real agent processes",
    }

    def flush():
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)

    lag = _LoopLag()
    r14 = phases & {"coldstart", "autoscale", "ingress"}
    cluster, handles = (None, [])
    try:
        if r14:
            print(f"# booting cluster ({args.agents} agents)",
                  file=sys.stderr, flush=True)
            cluster, handles = _boot_cluster(args.agents)
        if "coldstart" in phases:
            print("# coldstart", file=sys.stderr, flush=True)
            lag.snap()
            result["coldstart"] = bench_coldstart()
            result["coldstart"]["loop_lag"] = lag.delta()
            print(json.dumps(result["coldstart"]), file=sys.stderr)
            flush()
        if "autoscale" in phases or "ingress" in phases:
            # the r14 data-plane pacing exists for the coldstart
            # transfer regime; request/latency phases run unpaced
            _head()._transfer_server.egress_limit_bps = 0
        # autoscale runs BEFORE ingress: the SLO-burn signal reads the
        # cumulative per-func phase histograms, and the ingress A/B's
        # deliberately slow large-payload requests would pre-arm it
        if "autoscale" in phases:
            print("# autoscale", file=sys.stderr, flush=True)
            lag.snap()
            result["autoscale"] = bench_autoscale()
            result["autoscale"]["loop_lag"] = lag.delta()
            print(json.dumps(result["autoscale"]), file=sys.stderr)
            flush()
        if "ingress" in phases:
            print("# ingress A/B", file=sys.stderr, flush=True)
            lag.snap()
            result["ingress"] = bench_ingress()
            result["ingress"]["loop_lag"] = lag.delta()
            print(json.dumps(result["ingress"]), file=sys.stderr)
            flush()
        if "proxy" in phases:
            lag.snap()
            result["proxy"] = bench_proxy(args.proxy_clients,
                                          args.proxy_duration)
            result["proxy"]["loop_lag"] = lag.delta()
            print(json.dumps({"proxy": result["proxy"]}), file=sys.stderr)
            flush()
        if "llm" in phases:
            result.update(bench_llm(args))
            flush()
    finally:
        if r14 and cluster is not None:
            try:
                from ray_tpu import serve

                serve.shutdown()
            except Exception:  # noqa: BLE001
                pass
            for h in handles:
                h.terminate()
            cluster.shutdown()

    gates = {}
    for section in ("coldstart", "autoscale"):
        gates.update({f"{section}.{k}": v for k, v in
                      result.get(section, {}).get("gates", {}).items()})
    result["all_gates_pass"] = all(gates.values()) if gates else None
    flush()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
