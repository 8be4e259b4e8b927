"""Continuous-batching engine tests.

Ref analog of what is being verified: the reference's serve batching
tests (python/ray/serve/tests/test_batching.py) plus the vLLM-style
slot-scheduler semantics the reference delegates to external engines —
here parity-checked against greedy argmax over the plain (cache-free)
`transformer.forward`.
"""

import threading
import time

import jax
import numpy as np
import pytest

from ray_tpu.models.config import tiny_config
from ray_tpu.models.engine import InferenceEngine
from ray_tpu.models.transformer import forward, init_params

_forward = jax.jit(forward, static_argnames=("cfg",))
_REF_LEN = 64  # one compiled reference program: sequences are right-padded


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


def _reference_tokens(params, cfg, prompt, max_new, eos_id=-1):
    """Greedy argmax over the full forward, one token at a time, for a
    single prompt (the forward is causal, so right-padding to one fixed
    length leaves the logits at the real positions as they are)."""
    seq, toks = list(prompt), []
    while len(toks) < max_new and (not toks or toks[-1] != eos_id):
        row = np.zeros((1, _REF_LEN), np.int32)
        row[0, :len(seq)] = seq
        logits = _forward(params, row, cfg)[0, len(seq) - 1]
        toks.append(int(np.argmax(np.asarray(logits))))
        seq.append(toks[-1])
    return toks


def test_single_request_matches_the_forwards_greedy_tokens(model):
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                          max_new_tokens=8)
    prompt = [3, 1, 4, 1, 5]
    got = eng.generate(prompt)
    want = _reference_tokens(params, cfg, prompt, 8)
    assert got == want


def test_staggered_arrivals_decode_together(model):
    """Requests admitted mid-flight must not perturb running slots, and
    every request must match its solo greedy generation."""
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=4, max_prompt_len=16,
                          max_new_tokens=10)
    prompts = [[3, 1, 4], [15, 9, 2, 6, 5], [8, 9], [7, 9, 3, 2],
               [1, 2, 3, 4, 5, 6, 7], [11, 13]]
    reqs = []
    # submit 2, run a few steps so they're mid-decode, then submit the rest
    for p in prompts[:2]:
        reqs.append(eng.submit(p))
    for _ in range(3):
        eng.step()
    for p in prompts[2:]:
        reqs.append(eng.submit(p))
    for _ in range(100):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    for p, r in zip(prompts, reqs):
        assert r.done.is_set()
        assert r.error is None
        assert list(r.tokens) == _reference_tokens(params, cfg, p, 10)


def test_slot_churn_more_requests_than_slots(model):
    """10 requests through 2 slots: finished slots must be refilled with
    queued work while other slots keep decoding (continuous batching)."""
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                          max_new_tokens=6)
    prompts = [[i + 1, (2 * i) % 19 + 1, (3 * i) % 7 + 1] for i in range(10)]
    reqs = [eng.submit(p) for p in prompts]
    for _ in range(300):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    for p, r in zip(prompts, reqs):
        assert list(r.tokens) == _reference_tokens(params, cfg, p, 6)
    # with 2 slots and 10 requests the engine must have reused slots
    assert eng.stats["prefills"] == 10
    assert eng.stats["requests_done"] == 10


def test_eos_frees_slot_early(model):
    cfg, params = model
    prompt = [5, 4, 3]
    # pick the first greedily generated token as "eos" so the request
    # finishes after exactly one token
    first = _reference_tokens(params, cfg, prompt, 1)[0]
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                          max_new_tokens=8, eos_id=first)
    req = eng.submit(prompt)
    while not req.done.is_set():
        eng.step()
    assert list(req.tokens) == [first]
    assert req.finish_reason == "eos"
    # the slot must be free again
    assert eng._slot_req == [None, None]


def test_per_request_max_new_tokens(model):
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                          max_new_tokens=8)
    req = eng.submit([2, 7, 1], max_new_tokens=3)
    while not req.done.is_set():
        eng.step()
    assert len(req.tokens) == 3
    assert req.finish_reason == "length"
    assert list(req.tokens) == \
        _reference_tokens(params, cfg, [2, 7, 1], 8)[:3]


def test_streaming_tokens_arrive_incrementally(model):
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                          max_new_tokens=5).serve_forever()
    try:
        it = eng.submit_stream([9, 8, 7])
        got = list(it)
        assert got == _reference_tokens(params, cfg, [9, 8, 7], 5)
    finally:
        eng.shutdown()


def test_background_thread_concurrent_submitters(model):
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=4, max_prompt_len=16,
                          max_new_tokens=6).serve_forever()
    try:
        prompts = [[i + 1, i + 2] for i in range(8)]
        results = {}

        def worker(i, p):
            results[i] = eng.generate(p, timeout=120)

        threads = [threading.Thread(target=worker, args=(i, p))
                   for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for i, p in enumerate(prompts):
            assert results[i] == _reference_tokens(params, cfg, p, 6)
    finally:
        eng.shutdown()


def test_chunked_decode_matches_single_step(model):
    """decode_chunk=1 and decode_chunk=5 must emit identical greedy
    tokens — multi-step scheduling changes dispatch, not math."""
    cfg, params = model
    prompts = [[3, 1, 4], [15, 9, 2, 6], [5, 3]]
    outs = {}
    for chunk in (1, 5):
        eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                              max_new_tokens=9, decode_chunk=chunk)
        reqs = [eng.submit(p) for p in prompts]
        for _ in range(200):
            if all(r.done.is_set() for r in reqs):
                break
            eng.step()
        outs[chunk] = [list(r.tokens) for r in reqs]
    assert outs[1] == outs[5]
    for p, toks in zip(prompts, outs[1]):
        assert toks == _reference_tokens(params, cfg, p, 9)


def test_chunked_eos_freezes_on_device(model):
    cfg, params = model
    prompt = [5, 4, 3]
    ref = _reference_tokens(params, cfg, prompt, 8)
    eos = ref[2]  # finish mid-chunk (chunk=4, eos at token 3 at latest)
    want = ref[:ref.index(eos) + 1]  # eos may repeat earlier in ref
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                          max_new_tokens=8, eos_id=eos, decode_chunk=4)
    req = eng.submit(prompt)
    while not req.done.is_set():
        eng.step()
    assert list(req.tokens) == want
    assert req.finish_reason == "eos"


def test_oversized_prompt_rejected(model):
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=8,
                          max_new_tokens=4)
    with pytest.raises(ValueError, match="max_prompt_len"):
        eng.submit(list(range(1, 20)))


def test_tensor_parallel_engine_parity(model):
    """The SAME engine code under a tensor mesh must produce the same
    greedy tokens — TP comes from sharding propagation, not new code.
    tensor=2 because tiny_config has 2 KV heads (the sharded axis)."""
    from ray_tpu.parallel import MeshSpec

    cfg, params = model
    mesh = MeshSpec(data=1, fsdp=1, tensor=2).build(jax.devices()[:2])
    eng_tp = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                             max_new_tokens=8, mesh=mesh)
    prompts = [[3, 1, 4, 1, 5], [2, 7]]
    reqs = [eng_tp.submit(p) for p in prompts]
    for _ in range(50):
        if all(r.done.is_set() for r in reqs):
            break
        eng_tp.step()
    for p, r in zip(prompts, reqs):
        assert list(r.tokens) == _reference_tokens(params, cfg, p, 8)


def test_long_generation_does_not_stall_batch(model):
    """The cohort-stall regression: a short request admitted next to a
    long one must finish and be replaced while the long one still runs."""
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                          max_new_tokens=32)
    long_req = eng.submit([1, 2, 3], max_new_tokens=32)
    short_req = eng.submit([4, 5, 6], max_new_tokens=2)
    third = None
    done_at = {}
    for i in range(200):
        eng.step()
        if short_req.done.is_set() and third is None:
            # the freed slot must pick this up while long still runs
            third = eng.submit([7, 8], max_new_tokens=2)
        for name, r in [("short", short_req), ("long", long_req)] + \
                ([("third", third)] if third is not None else []):
            if r.done.is_set() and name not in done_at:
                done_at[name] = i
        if len(done_at) == 3:
            break
    assert done_at["short"] < done_at["long"]
    # continuous batching: the third request entered the freed slot and
    # FINISHED before the long request did
    assert "third" in done_at and done_at["third"] < done_at["long"]
    assert list(third.tokens) == _reference_tokens(params, cfg, [7, 8], 2)


def test_step_loop_death_fails_all_waiters(model):
    """A fatal error escaping step() must error out every in-flight and
    queued request and make further submissions raise (a dead
    serve_forever thread used to leave waiters hanging silently)."""
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                          max_new_tokens=8)
    boom = RuntimeError("device lost")

    def exploding_step():
        raise boom
    # put a real undelivered chunk in flight so death handling must fail
    # in-flight snapshots too, not just the queue
    inflight_req = eng.submit([9, 9])
    eng._admit_locked()
    eng._dispatch_locked()  # one chunk dispatched and not fetched
    assert eng._inflight, "precondition: an undelivered chunk exists"
    eng.step = exploding_step
    req = eng.submit([1, 2, 3])  # queued before the loop ever runs
    eng.serve_forever()
    assert req.done.wait(10)
    assert req.error is boom and req.finish_reason == "error"
    assert inflight_req.done.wait(10)
    assert inflight_req.error is boom
    eng._thread.join(timeout=10)
    with pytest.raises(RuntimeError, match="dead"):
        eng.submit([4, 5])
    with pytest.raises(RuntimeError, match="dead"):
        eng.submit_stream([4, 5])


def test_batched_prefill_groups_match_serial(model):
    """6 simultaneous submissions into 6 free slots admit as 4+2 batched
    prefills (one dispatch each) and every request must still match its
    solo greedy generation — grouping changes dispatch count, not math."""
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=6, max_prompt_len=16,
                          max_new_tokens=6)
    prompts = [[i + 1, (3 * i) % 11 + 1] for i in range(6)]
    reqs = [eng.submit(p) for p in prompts]
    for _ in range(100):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    for p, r in zip(prompts, reqs):
        assert list(r.tokens) == _reference_tokens(params, cfg, p, 6)
    assert eng.stats["prefills"] == 6
    assert eng.stats["prefill_dispatches"] == 2  # groups of 4 + 2


def test_pipelined_fetcher_matches_inline(model):
    """serve_forever now fetches on a separate thread; tokens must be
    identical to the inline-step path and all waiters must complete."""
    cfg, params = model
    prompts = [[3, 1, 4], [15, 9, 2, 6], [5, 3], [8, 8, 8],
               [2, 7, 1, 8], [9, 9]]
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                          max_new_tokens=8, decode_chunk=3,
                          max_inflight=2).serve_forever()
    try:
        reqs = [eng.submit(p) for p in prompts]
        for r in reqs:
            assert r.done.wait(120)
            assert r.error is None
        for p, r in zip(prompts, reqs):
            assert list(r.tokens) == _reference_tokens(params, cfg, p, 8)
        assert eng.stats["fetches"] >= 1
    finally:
        eng.shutdown()


def test_warmup_compiles_and_resets(model):
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=4, max_prompt_len=16,
                          max_new_tokens=6)
    eng.warmup()
    # warmup must leave no residue: a fresh request still matches solo
    req = eng.submit([3, 1, 4, 1, 5])
    for _ in range(50):
        if req.done.is_set():
            break
        eng.step()
    assert list(req.tokens) == _reference_tokens(params, cfg,
                                                 [3, 1, 4, 1, 5], 6)
