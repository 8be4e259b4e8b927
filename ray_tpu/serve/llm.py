"""LLM serving: a batched autoregressive-generation deployment.

Ref analog: the reference's Serve LLM path (python/ray/serve + the
"Ray Serve: Llama-3 inference deployment (batched)" BASELINE.json
config, served there via vLLM-on-GPU workers). TPU-first re-design:
replicas hold jitted prefill/decode programs from
``ray_tpu.models.generate`` — the KV cache is preallocated at a static
``max_len`` so every batch shape compiles once — and ``@serve.batch``
coalesces concurrent single-prompt requests into one [B, P] generate
call that keeps the MXU busy. Prompts are right-aligned into a fixed
bucket (static shapes; XLA never recompiles per request).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from ray_tpu.serve.deployment import deployment


def _replica_params(cfg, checkpoint_dir: Optional[str], seed: int):
    """A replica's weights: the checkpoint's, or random ones from ``seed``.
    Called before the replica's first compile, so it also places the
    persistent compilation cache, and keeps every program in it: a
    replica's first requests run dozens of sub-second programs (prefill
    per bucket and group size, decode, glue), which JAX's default
    one-second threshold would compile again in every new replica."""
    import jax

    from ray_tpu.models.transformer import init_params
    from ray_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if checkpoint_dir is None:
        return init_params(jax.random.key(seed), cfg)
    import pickle

    with open(checkpoint_dir, "rb") as f:
        return jax.tree.map(np.asarray, pickle.load(f))


def _tpu_lease(chips: int) -> dict:
    """``ray_actor_options`` of an LLM replica: it leases the chips it
    computes on, so that the replica — and no other process — is the one
    that may load the TPU library. A cluster without TPUs serves on the
    CPU and leases none."""
    import ray_tpu

    if ray_tpu.cluster_resources().get("TPU", 0) > 0:
        return {"num_tpus": chips}
    return {}


class _LLMReplica:
    """Replica body: owns params + jitted generate for one model config.

    ``model`` is a config name from ``ray_tpu.models.config.get_config``
    (e.g. "gpt2-small", "llama3-1b") or a TransformerConfig; weights are
    randomly initialized unless ``checkpoint_dir`` (an orbax/pickle tree
    saved by train) is given — serving infrastructure is what's under
    test here, not weights.
    """

    def __init__(self, model="tiny", *, max_batch_size: int = 8,
                 max_prompt_len: int = 64, max_new_tokens: int = 32,
                 batch_wait_timeout_s: float = 0.02,
                 checkpoint_dir: Optional[str] = None,
                 greedy: bool = True, temperature: float = 1.0,
                 pad_id: int = 0, eos_id: int = -1, seed: int = 0):
        import jax

        from ray_tpu.models.config import TransformerConfig, get_config

        cfg = (model if isinstance(model, TransformerConfig)
               else get_config(model))
        self.cfg = cfg
        self.max_new_tokens = int(max_new_tokens)
        self.max_prompt_len = int(max_prompt_len)
        self.greedy = greedy
        self.temperature = float(temperature)
        self.pad_id = int(pad_id)
        # -1 (never sampled for non-negative vocabularies) disables the
        # eos freeze; when set, generate() stops extending finished rows
        # and stream() ends at the model's natural stop
        self.eos_id = int(eos_id)
        import threading

        # stream() runs on caller threads while _generate runs on the
        # batcher's drainer thread: key handout must be atomic or two
        # concurrent sampling requests split the same key
        self._rng_lock = threading.Lock()
        self._rng = jax.random.key(seed)
        self.params = _replica_params(cfg, checkpoint_dir, seed)
        self._max_bs = int(max_batch_size)
        # the batcher cap and the compiled batch shape MUST be the same
        # number, so the batcher is built per-instance from the
        # constructor arg (a class-level @serve.batch would freeze its
        # own cap). Held on self — not the module-global registry — so
        # replica teardown releases the params it closes over.
        from ray_tpu.serve.batching import _Batcher

        self._batcher = _Batcher(self._max_bs, batch_wait_timeout_s)

    def _pad_batch(self, prompts: Sequence[Sequence[int]]):
        """Left-pad to the bucket so the last prompt token sits at the
        cache's write position for every row; returns (tokens [B,P],
        start [B]) where start marks each row's first real token (pad
        positions are masked out of attention by generate)."""
        P = self.max_prompt_len
        out = np.full((len(prompts), P), self.pad_id, np.int32)
        start = np.zeros(len(prompts), np.int32)
        for i, p in enumerate(prompts):
            p = list(p)  # oversized prompts were rejected in __call__
            out[i, P - len(p):] = p
            start[i] = P - len(p)
        return out, start

    def _next_rng(self):
        import jax

        with self._rng_lock:
            self._rng, sub = jax.random.split(self._rng)
        return sub

    def _generate(self, prompts: List[Sequence[int]]) -> List[dict]:
        from ray_tpu.models.generate import generate

        toks, start = self._pad_batch(prompts)
        # pad the BATCH to the compiled size too: one XLA program total
        B = toks.shape[0]
        if B < self._max_bs:
            toks_full = np.resize(toks, (self._max_bs, toks.shape[1]))
            start_full = np.resize(start, (self._max_bs,))
        else:
            toks_full, start_full = toks, start
        out = generate(self.params, toks_full, self.cfg,
                       max_new_tokens=self.max_new_tokens,
                       greedy=self.greedy, temperature=self.temperature,
                       eos_id=self.eos_id, rng=self._next_rng(),
                       start=start_full)
        out = np.asarray(out)[:B, toks.shape[1]:]
        # trim each row at its first eos so the batched contract matches
        # stream(): output ends AT the natural stop, no eos-padded tail
        results = []
        for row in out:
            ids = row.tolist()
            if self.eos_id in ids:
                ids = ids[:ids.index(self.eos_id) + 1]
            results.append({"token_ids": ids})
        return results

    def stream(self, prompt: Sequence[int]):
        """Token-by-token generation: a generator the router streams back
        chunk-wise (``handle.options(method_name='stream', stream=True)``
        or chunked HTTP). Per-request B=1 decode via the stepwise
        prefill/decode_step API — streaming trades the batched program
        for first-token latency, the same trade the reference's streaming
        LLM responses make (serve/_private/replica.py generator path)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.generate import decode_step, prefill

        if len(prompt) > self.max_prompt_len:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds this deployment's "
                f"max_prompt_len={self.max_prompt_len}")
        # left-pad into the same fixed bucket as the batched path: ONE
        # compiled (prefill, decode) shape per deployment, not one per
        # distinct prompt length
        P = self.max_prompt_len
        toks = np.full((1, P), self.pad_id, np.int32)
        toks[0, P - len(prompt):] = list(prompt)
        start = jnp.asarray([P - len(prompt)], jnp.int32)
        toks = jnp.asarray(toks)
        max_len = P + self.max_new_tokens
        logits, cache = prefill(self.params, toks, self.cfg, max_len,
                                start)
        last = logits[:, -1]
        for i in range(self.max_new_tokens):
            if self.greedy:
                tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
            else:
                tok = jax.random.categorical(
                    self._next_rng(), last / max(self.temperature, 1e-6)
                ).astype(jnp.int32)
            yield {"token_id": int(tok[0])}
            if int(tok[0]) == self.eos_id:  # natural stop
                return
            if i + 1 < self.max_new_tokens:  # last step has no consumer
                last, cache = decode_step(self.params, cache, tok,
                                          self.cfg, start)

    def __call__(self, prompt: Sequence[int]) -> dict:
        if len(prompt) > self.max_prompt_len:
            # refuse rather than silently conditioning on a clipped
            # prompt; the per-request check keeps one oversized prompt
            # from failing a whole coalesced batch
            raise ValueError(
                f"prompt length {len(prompt)} exceeds this deployment's "
                f"max_prompt_len={self.max_prompt_len}")
        return self._batcher.submit(self._generate, prompt)


class _ContinuousLLMReplica:
    """Continuous-batching replica: slot-level admission/eviction.

    Ref analog: the reference's request-cohort `@serve.batch`
    (python/ray/serve/batching.py:337) holds a batch until every member
    finishes decoding; this replica instead owns an
    `ray_tpu.models.engine.InferenceEngine` whose decode loop refills a
    finished sequence's slot on the very next step — one long generation
    no longer stalls its batchmates (the vLLM-style redesign, TPU-first:
    static slot shapes, one compiled decode program, on-device sampling).
    The engine holds the weights as its programs read them: ``cfg.dtype``
    (a float32 checkpoint is rounded once at deploy, not in every
    program) but for the float32 vocabulary head and MoE router.

    ``tensor_parallel`` > 1 shards the model over that many local devices
    (a `num_tpus=N`-class replica): params/cache carry tensor-axis
    shardings and the SAME engine program runs TP via GSPMD propagation.
    """

    def __init__(self, model="tiny", *, slots: int = 8,
                 max_prompt_len: int = 64, max_new_tokens: int = 32,
                 checkpoint_dir: Optional[str] = None,
                 greedy: bool = True, temperature: float = 1.0,
                 pad_id: int = 0, eos_id: int = -1, seed: int = 0,
                 tensor_parallel: int = 1, decode_chunk: int = 4,
                 fetch_every: int = 1):
        import jax

        from ray_tpu.models.config import TransformerConfig, get_config
        from ray_tpu.models.engine import InferenceEngine

        cfg = (model if isinstance(model, TransformerConfig)
               else get_config(model))
        params = _replica_params(cfg, checkpoint_dir, seed)
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
            mesh=mesh, seed=seed, decode_chunk=decode_chunk,
            fetch_every=fetch_every).serve_forever()

    def __call__(self, prompt: Sequence[int],
                 max_new_tokens: Optional[int] = None) -> dict:
        toks = self.engine.generate(prompt, max_new_tokens)
        return {"token_ids": toks}

    def stream(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None):
        for tok in self.engine.submit_stream(prompt, max_new_tokens):
            yield {"token_id": tok}

    def engine_stats(self) -> dict:
        return dict(self.engine.stats)

    def engine_slow_events(self) -> List[dict]:
        """The engine's heartbeat: thread states that lasted over a
        second while work waited (newest last, at most 64)."""
        return [dict(ev) for ev in self.engine.slow_events]

    def engine_requests(self, last: int = 100) -> List[dict]:
        """Stamps of the ``last`` finished requests (of at most 1024)."""
        return list(self.engine.request_log)[-int(last):]

    def trace(self, seconds: float, log_dir: str) -> str:
        """Profile this replica for ``seconds`` (only the process that
        holds the chip can trace it): device operations and the engine's
        ``engine.*`` spans on one clock. -> ``log_dir``, which holds
        ``plugins/profile/<time>/*.xplane.pb``."""
        import time

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


def build_llm_deployment(model="tiny", *, name: str = "llm",
                         num_replicas: int = 1, **replica_kwargs):
    """-> an Application serving ``{prompt token ids} -> {token_ids}``.

    Usage::

        app = build_llm_deployment("gpt2-small", max_new_tokens=16)
        handle = serve.run(app, name="llm")
        out = handle.remote([1, 2, 3]).result()
    """
    dep = deployment(_LLMReplica, name=name) \
        .options(num_replicas=num_replicas,
                 ray_actor_options=_tpu_lease(1))
    return dep.bind(model, **replica_kwargs)
