"""Cached autoregressive generation: prefill/decode parity, padding,
EOS semantics, and the continuous-batching Serve LLM deployment.

Analog of the reference's serve LLM / batched-inference tests; parity is
checked against the training-path ``transformer.forward`` the same way
the reference checks vLLM outputs against HF generate.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu.models.config import tiny_config
from ray_tpu.models.engine import (InferenceEngine, decode_slots,
                                   init_slot_cache, prefill_slots)
from ray_tpu.models.generate import _final_logits, _prefill_hidden
from ray_tpu.models.transformer import forward, init_params


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(tiny_config(), dtype=jnp.float32,
                              param_dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


def _generate(params, cfg, prompt, n, *, start=None, eos_id=-1, rng=None,
              **sampling):
    """prompt [B, P] (row b's first real token at ``start[b]``) -> the
    ``n`` tokens each row generates: one `prefill_slots` into a fresh slot
    cache, then one `decode_slots` chunk of n - 1 steps."""
    B, P = prompt.shape
    if start is None:
        start = np.zeros(B, np.int32)
    if rng is None:
        rng = jax.random.key(0)
    cache, first = prefill_slots(
        params, init_slot_cache(cfg, B, P + n), jnp.asarray(prompt),
        jnp.arange(B, dtype=jnp.int32), jnp.asarray(start), rng, cfg,
        **sampling)
    _, toks = decode_slots(params, cache, first, jnp.ones(B, bool), rng,
                           cfg, eos_id=eos_id, steps=n - 1, **sampling)
    return np.asarray(toks)


class TestGenerate:
    def test_prefill_matches_forward(self, tiny):
        cfg, params = tiny
        prompt = jax.random.randint(jax.random.key(1), (2, 5), 0,
                                    cfg.vocab_size)
        lf = forward(params, prompt, cfg)
        hidden, cache = _prefill_hidden(params, prompt, cfg, 16,
                                        jnp.zeros(2, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(lf), np.asarray(_final_logits(params, hidden, cfg)),
            atol=1e-4)
        assert int(cache["pos"]) == 5
        assert cache["k"].shape == (cfg.n_layers, 2, 16, cfg.kv_heads,
                                    cfg.head_dim)

    def test_greedy_decode_parity_with_full_forward(self, tiny):
        """The cached decode must reproduce, token for token, what
        sequential argmax over the full (uncached) forward produces."""
        cfg, params = tiny
        B, P, N = 2, 5, 6
        prompt = np.asarray(jax.random.randint(jax.random.key(1), (B, P), 0,
                                               cfg.vocab_size))
        out = _generate(params, cfg, prompt, N)
        seq = prompt
        for _ in range(N):
            logits = forward(params, jnp.asarray(seq), cfg)
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            seq = np.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(seq[:, P:], out)

    def test_left_padded_batch_matches_unpadded_rows(self, tiny):
        """Variable-length prompts left-padded into one batch generate
        exactly what each prompt generates alone — pad masking + RoPE's
        relative-position property make the offset invisible."""
        cfg, params = tiny
        p1 = np.asarray(jax.random.randint(jax.random.key(2), (1, 3), 0,
                                           cfg.vocab_size))
        p2 = np.asarray(jax.random.randint(jax.random.key(3), (1, 6), 0,
                                           cfg.vocab_size))
        N, P = 5, 6
        solo1 = _generate(params, cfg, p1, N)[0]
        solo2 = _generate(params, cfg, p2, N)[0]
        batch = np.zeros((2, P), np.int32)
        batch[0, P - 3:] = p1[0]
        batch[1, :] = p2[0]
        out = _generate(params, cfg, batch, N,
                        start=np.asarray([P - 3, 0], np.int32))
        np.testing.assert_array_equal(out[0], solo1)
        np.testing.assert_array_equal(out[1], solo2)

    def test_eos_freezes_sequence(self, tiny):
        cfg, params = tiny
        prompt = np.asarray(jax.random.randint(jax.random.key(1), (1, 4), 0,
                                               cfg.vocab_size))
        free = _generate(params, cfg, prompt, 4)[0]
        eos = int(free[1])  # force EOS at the second generated token
        out = _generate(params, cfg, prompt, 4, eos_id=eos)[0]
        assert out[0] == free[0]
        assert out[1] == eos and out[2] == eos and out[3] == eos

    def test_moe_model_generates(self):
        cfg = dataclasses.replace(tiny_config(), dtype=jnp.float32,
                                  param_dtype=jnp.float32, moe_experts=4)
        params = init_params(jax.random.key(0), cfg)
        eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=8,
                              max_new_tokens=3)
        out = eng.generate([0, 0, 0])
        assert len(out) == 3
        assert all(0 <= t < cfg.vocab_size for t in out)

    def test_undersized_cache_rejected(self, tiny):
        """A prompt the cache cannot hold must error loudly — a
        dynamic_update_slice would otherwise clamp writes onto the last
        slot and corrupt attention silently."""
        cfg, params = tiny
        eng = InferenceEngine(params, cfg, slots=1, max_prompt_len=4,
                              max_new_tokens=2)
        with pytest.raises(ValueError, match="max_prompt_len"):
            eng.submit([0] * 6)
        with pytest.raises(ValueError, match="max_len"):
            _prefill_hidden(params, jnp.zeros((1, 6), jnp.int32), cfg, 4,
                            jnp.zeros(1, jnp.int32))

    def test_encoder_config_rejected(self, tiny):
        """Autoregressive decoding over a causal=False encoder would
        silently contradict its bidirectional training forward."""
        cfg, params = tiny
        enc = dataclasses.replace(cfg, causal=False)
        with pytest.raises(ValueError, match="causal"):
            _prefill_hidden(params, jnp.zeros((1, 4), jnp.int32), enc, 4,
                            jnp.zeros(1, jnp.int32))

    def test_sampled_generation_respects_temperature_rng(self, tiny):
        cfg, params = tiny
        prompt = np.asarray(jax.random.randint(jax.random.key(1), (2, 4), 0,
                                               cfg.vocab_size))
        a = _generate(params, cfg, prompt, 6, greedy=False,
                      rng=jax.random.key(5))
        b = _generate(params, cfg, prompt, 6, greedy=False,
                      rng=jax.random.key(5))
        c = _generate(params, cfg, prompt, 6, greedy=False,
                      rng=jax.random.key(6))
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)
        # a temperature near zero sharpens sampling into the argmax
        cold = _generate(params, cfg, prompt, 6, greedy=False,
                         temperature=1e-4, rng=jax.random.key(6))
        np.testing.assert_array_equal(cold, _generate(params, cfg, prompt, 6))


class TestServeLLM:
    @pytest.fixture
    def serve_rt(self):
        from ray_tpu import serve

        ray_tpu.init(num_cpus=4, num_tpus=0)
        yield serve
        serve.shutdown()
        ray_tpu.shutdown()

    def test_continuous_deployment_serves_concurrent_requests(self,
                                                              serve_rt):
        """Slot-level continuous batching behind serve: concurrent
        requests of different lengths all complete, short ones don't
        wait for long ones' cohort, and results are deterministic."""
        serve = serve_rt
        from ray_tpu.serve.llm import build_continuous_llm_deployment

        app = build_continuous_llm_deployment(
            "tiny", name="cllm", slots=4, max_prompt_len=8,
            max_new_tokens=8)
        handle = serve.run(app, name="cllm")
        futs = [handle.remote([1 + i, 2 + i], max_new_tokens=2 + i % 4)
                for i in range(8)]
        outs = [f.result(timeout_s=180) for f in futs]
        for i, o in enumerate(outs):
            assert len(o["token_ids"]) <= 2 + i % 4
        again = handle.remote([1, 2], max_new_tokens=2).result(timeout_s=120)
        assert again["token_ids"] == outs[0]["token_ids"]
        # every request got its own slot admission (no cohort batching)
        stats = handle.options(method_name="engine_stats") \
            .remote().result(timeout_s=60)
        assert stats["prefills"] == 9
        assert stats["requests_done"] == 9

    def test_continuous_streaming_matches_call(self, serve_rt):
        serve = serve_rt
        from ray_tpu.serve.llm import build_continuous_llm_deployment

        app = build_continuous_llm_deployment(
            "tiny", name="cllm_s", slots=2, max_prompt_len=8,
            max_new_tokens=4)
        handle = serve.run(app, name="cllm_s")
        whole = handle.remote([3, 1, 4]).result(timeout_s=120)
        gen = handle.options(method_name="stream",
                             stream=True).remote([3, 1, 4])
        streamed = [chunk["token_id"] for chunk in gen]
        assert streamed == whole["token_ids"]
