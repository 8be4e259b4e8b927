"""LLM serving: a continuously batched autoregressive-generation deployment.

Ref analog: the reference's Serve LLM path (python/ray/serve, batched
Llama-3 inference served there via vLLM-on-GPU workers). TPU-first
re-design: a replica owns one `ray_tpu.models.engine.InferenceEngine` —
a fixed pool of KV-cache slots preallocated at a static ``max_len``, one
compiled decode program, prompts left-padded into a few fixed buckets
(static shapes; XLA never recompiles per request) — and every caller
blocked in ``__call__`` or ``stream`` is a request in that engine's queue.
"""

from __future__ import annotations

import functools
import time
from typing import List, Optional, Sequence

import numpy as np

from ray_tpu.serve.deployment import deployment
from ray_tpu.serve.multiplex import _request_sent_time


def _replica_params(cfg, checkpoint_dir: Optional[str], seed: int):
    """A replica's weights: the checkpoint's, or random ones from ``seed``
    as the engine holds them (`drawn_serving_params`). Called before the
    replica's first compile, so it also places the persistent compilation
    cache, and keeps every program in it: a replica's first requests run
    dozens of sub-second programs (prefill per bucket and group size,
    decode, glue), which JAX's default one-second threshold would compile
    again in every new replica."""
    import jax

    from ray_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if checkpoint_dir is None:
        return drawn_serving_params(cfg, seed)
    import pickle

    with open(checkpoint_dir, "rb") as f:
        return jax.tree.map(np.asarray, pickle.load(f))


def drawn_serving_params(cfg, seed: int):
    """``serving_params(init_params(key(seed), cfg), cfg)``, the same
    values, made a matrix at a time: the initialiser runs with its draws
    put off (keys, shapes and scales as it derives them), then each matrix
    is drawn and converted to the dtype the engine holds it in by ONE
    jitted program, shared by the matrices of one shape and scale. The
    float32 tree never stands whole on the device: the largest float32
    buffer alive is one leaf's. A replica of a model whose float32 copy (4
    bytes a parameter) is larger than the chip starts by this rule, and so
    does every other. The head's bf16 copy is made of the drawn leaf as
    `serving_params` makes it (`transformer.with_head_copy`)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import (init_params, read_in_float32,
                                            with_head_copy)

    in_float32 = read_in_float32(cfg)
    plan = init_params(jax.random.key(seed), cfg,
                       normal=lambda *draw: functools.partial(_drawn, *draw))

    def held(path, leaf):
        want = jnp.dtype(jnp.float32 if path[-1].key in in_float32
                         else cfg.dtype)
        if callable(leaf):
            return leaf(jnp.dtype(cfg.param_dtype), want)
        return leaf if leaf.dtype == want else leaf.astype(want)

    return with_head_copy(jax.tree_util.tree_map_with_path(held, plan), cfg)


@functools.lru_cache(maxsize=None)
def _drawn_program():
    """`moe.scaled_normal`'s arithmetic in ``made``, the dtype the
    initialiser makes a matrix in, then as a replica holds it: one compiled
    program a (shape, scale, dtypes). The barrier keeps the compiler from
    folding the scale into the normal's own constant, which the
    initialiser's separate steps cannot do either: the values are its to
    the bit (tests/test_replica_params.py), at the cost of the one float32
    leaf standing between the two halves. Made on first use: this module
    imports no JAX at the top (a benchmark's driver process imports it and
    must stay off the chip)."""
    import jax
    import jax.numpy as jnp

    def draw(key, shape, scale, made, held):
        x = jax.lax.optimization_barrier(
            jax.random.normal(key, shape, jnp.float32))
        return (x * scale).astype(made).astype(held)
    return jax.jit(draw, static_argnums=(1, 2, 3, 4))


def _drawn(key, shape, scale, made, held):
    return _drawn_program()(key, shape, scale, made, held)


def _tpu_lease(chips: int) -> dict:
    """``ray_actor_options`` of an LLM replica: it leases the chips it
    computes on, so that the replica — and no other process — is the one
    that may load the TPU library. A cluster without TPUs serves on the
    CPU and leases none."""
    import ray_tpu

    if ray_tpu.cluster_resources().get("TPU", 0) > 0:
        return {"num_tpus": chips}
    return {}


class _ContinuousLLMReplica:
    """Continuous-batching replica: slot-level admission/eviction.

    Ref analog: the reference's request-cohort `@serve.batch`
    (python/ray/serve/batching.py:337) holds a batch until every member
    finishes decoding; this replica instead owns an
    `ray_tpu.models.engine.InferenceEngine` whose decode loop refills a
    finished sequence's slot on the very next step — one long generation
    no longer stalls its batchmates (the vLLM-style redesign, TPU-first:
    static slot shapes, one compiled decode program, on-device sampling).
    ``model`` is a config name from ``ray_tpu.models.config.get_config``
    (e.g. "gpt2-small", "llama3-1b") or a TransformerConfig; weights are
    randomly initialized unless ``checkpoint_dir`` (a pickled tree saved
    by train) is given.
    The engine holds the weights as its programs read them: ``cfg.dtype``
    (a float32 checkpoint is rounded once at deploy, not in every
    program) but for the float32 vocabulary head (with its bf16 copy
    beside it, which a decode step multiplies by) and MoE router.

    ``tensor_parallel`` > 1 shards the model over that many local devices
    (a `num_tpus=N`-class replica): params/cache carry tensor-axis
    shardings and the SAME engine program runs TP via GSPMD propagation.
    """

    def __init__(self, model="tiny", *, slots: int = 8,
                 max_prompt_len: int = 64, max_new_tokens: int = 32,
                 checkpoint_dir: Optional[str] = None,
                 greedy: bool = True, temperature: float = 1.0,
                 pad_id: int = 0, eos_id: int = -1, seed: int = 0,
                 tensor_parallel: int = 1, decode_chunk: int = 4):
        import jax

        from ray_tpu.models.config import TransformerConfig, get_config
        from ray_tpu.models.engine import InferenceEngine

        cfg = (model if isinstance(model, TransformerConfig)
               else get_config(model))
        # the deploy's first phase, timed as the engine's `_timed` times
        # the two after it (`engine.init`, `engine.warmup`); the engine is
        # not there yet, so it is handed the seconds below
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(
                "serve.replica_weights",
                source="drawn" if checkpoint_dir is None else "checkpoint"):
            params = _replica_params(cfg, checkpoint_dir, seed)
        weights_s = time.perf_counter() - t0
        mesh = None
        if tensor_parallel > 1:
            from ray_tpu.parallel import MeshSpec

            devices = jax.devices()
            if len(devices) < tensor_parallel:
                raise ValueError(
                    f"tensor_parallel={tensor_parallel} but only "
                    f"{len(devices)} local devices")
            mesh = MeshSpec(data=1, fsdp=1, tensor=tensor_parallel) \
                .build(devices[:tensor_parallel])
        self.engine = InferenceEngine(
            params, cfg, slots=slots, max_prompt_len=max_prompt_len,
            max_new_tokens=max_new_tokens, greedy=greedy,
            temperature=temperature, eos_id=eos_id, pad_id=pad_id,
            mesh=mesh, seed=seed,
            decode_chunk=decode_chunk).serve_forever()
        self.engine.stats["weights_s"] = weights_s

    def __call__(self, prompt: Sequence[int],
                 max_new_tokens: Optional[int] = None) -> dict:
        toks = self.engine.generate(prompt, max_new_tokens,
                                    t_sent=_request_sent_time())
        return {"token_ids": toks}

    def stream(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None):
        # a generator: this body, and with it the engine's submit, runs in
        # the stream's first pull
        for tok in self.engine.submit_stream(prompt, max_new_tokens,
                                             t_sent=_request_sent_time()):
            yield {"token_id": tok}

    def engine_stats(self) -> dict:
        return dict(self.engine.stats)

    def engine_slow_events(self) -> List[dict]:
        """The engine's heartbeat: thread states that lasted over a
        second while work waited (newest last, at most 64)."""
        return [dict(ev) for ev in self.engine.slow_events]

    def engine_requests(self, last: int = 100) -> List[dict]:
        """Stamps of the ``last`` finished requests (of at most 1024):
        ``t_sent`` is the caller's wall clock at ``handle.remote()``, the
        ``t_*`` after it this process's ``perf_counter``. A streamed
        request's entry gains ``t_first_pickup``, ``stream_open_s`` =
        ``stream_wait_s`` + ``stream_held_s``, ``pickup_lag_s``, ``pulls``,
        ``ready_pulls``, ``stream_tokens`` and ``closed`` ("done",
        "abandoned" or "error") when its stream ends: without them it is
        a stream still open (or an answer that was not streamed)."""
        return list(self.engine.request_log)[-int(last):]

    def compile_log(self, last: int = 100) -> List[dict]:
        """The ``last`` programs (of at most 256) this PROCESS asked the
        backend for, oldest first: ``fun_name``, ``wall_s``, ``loaded``
        (from the persistent cache, not compiled) and ``t_unix``. Which
        program compiled again; the totals are in `engine_stats`
        (``compile_requests`` and the five keys beside it)."""
        from ray_tpu.utils.compile_cache import compile_log

        return compile_log(last)

    def trace(self, seconds: float, log_dir: str) -> str:
        """Profile this replica for ``seconds`` (only the process that
        holds the chip can trace it): device operations, the engine's
        ``engine.*`` spans and the streams' ``serve.stream_wait`` on one
        clock. -> ``log_dir``, which holds
        ``plugins/profile/<time>/*.xplane.pb``."""
        import jax

        jax.profiler.start_trace(log_dir)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        return log_dir

    def device(self) -> dict:
        """The device(s) this replica computes on, as JAX reports them."""
        import jax

        devices = jax.devices()
        return {"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices)}

    def __del__(self):
        eng = getattr(self, "engine", None)
        if eng is not None:
            eng.shutdown()


def build_continuous_llm_deployment(model="tiny", *, name: str = "llm",
                                    num_replicas: int = 1,
                                    max_concurrency: int = 32,
                                    **replica_kwargs):
    """-> an Application whose replicas continuously batch generations.
    Each replica holds its weights in the model's compute dtype
    (``cfg.dtype``; the vocabulary head and an MoE router in float32),
    whatever dtype the checkpoint was saved in.

    ``max_concurrency`` lifts the replica's query cap (and with it the
    actor's thread cap) so many callers can block in ``__call__`` while
    the engine thread interleaves them — admission happens per decode
    step, not per cohort.
    """
    dep = deployment(_ContinuousLLMReplica, name=name) \
        .options(num_replicas=num_replicas,
                 max_concurrent_queries=max_concurrency,
                 ray_actor_options=_tpu_lease(
                     replica_kwargs.get("tensor_parallel", 1)))
    return dep.bind(model, **replica_kwargs)
