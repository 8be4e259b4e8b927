"""A `decode_slots` chunk against the plain (cache-free) full forward.

Decode reads the slot cache and writes only the rows that change, in
place (one dynamic_update_slice per slot, after the layer scan). These are
the cases that write can get wrong, and, with the decode kernel forced on
(the interpreter; blocks of 4 positions), the cases its walk over
``[start, pos)`` can: `pos` advances on the device inside a chunk.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu.models.engine as engine
import ray_tpu.ops.decode_attention as decode_attention
from ray_tpu.models.config import tiny_config
from ray_tpu.models.engine import (InferenceEngine, _decode_one,
                                   decode_slots, init_slot_cache,
                                   prefill_slots)
from ray_tpu.models.transformer import forward, init_params

# A slot is (prompt length, solo decode steps taken before the chunk,
# active in the chunk); the prompt is left-padded to _P, so `start` =
# _P - length and `pos` = _P + steps before.

_P, _S = 8, 20  # prefill bucket; cache positions per slot

_CHUNK_CASES = {
    "pos_and_start_differ": dict(
        slots=[(8, 0, True), (3, 2, True), (5, 5, True), (1, 1, True)]),
    "inactive_between_active": dict(
        slots=[(4, 3, True), (6, 2, False), (7, 0, True), None,
               (2, 4, True)]),
    # slot 0 writes its last two positions, then runs past the end; slot 1
    # is parked past the end (a request that filled its row, then one
    # more chunk): their clamped writes must stay out of slot 2
    "runs_off_the_end_beside_a_parked_slot": dict(
        slots=[(8, _S - _P - 2, True), (5, 1, False), (6, 3, True)],
        parked_at={1: _S + 1}),
    "eos_freezes_mid_chunk": dict(
        slots=[(5, 1, True), (8, 3, True), (3, 0, True)], eos_after={0: 2}),
    "chunk_of_1_four_times": dict(
        slots=[(8, 0, True), (3, 2, True), (5, 5, False), (1, 1, True)],
        chunks=(1, 1, 1, 1)),
    "bfloat16": dict(
        slots=[(8, 0, True), (3, 2, True), (5, 5, True), (1, 1, True)],
        dtype="bfloat16"),
}


# the cases that run again through the kernel
_KERNEL_CASES = ("pos_and_start_differ", "inactive_between_active",
                 "runs_off_the_end_beside_a_parked_slot", "bfloat16")


@pytest.fixture
def kernel(monkeypatch):
    """Force the decode kernel where the CPU backend picks the masked
    contraction, walking blocks of ``block`` positions; the jitted chunk
    is traced anew on both sides of the test."""
    def force(block):
        monkeypatch.setattr(decode_attention, "_BLOCK", block)
        monkeypatch.setattr(engine, "_on_chip", lambda: True)
    decode_slots.clear_cache()
    yield force
    decode_slots.clear_cache()


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.mark.parametrize("case, attention", [
    (case, "xla") for case in _CHUNK_CASES] + [
    (case, "kernel") for case in _KERNEL_CASES])
def test_decode_chunk_equals_the_full_forward(case, attention, kernel):
    spec = _CHUNK_CASES[case]
    if attention == "kernel":
        kernel(4)  # _S = 20: five blocks a slot
    dtype = spec.get("dtype", "float32")
    # the same bounds the benchmark's reference check holds a run to
    bound = {"float32": 2e-4, "bfloat16": 0.08}[dtype]
    cfg = tiny_config(dtype=jnp.dtype(dtype))
    params = init_params(jax.random.key(0), cfg)
    B = len(spec["slots"])
    rng = jax.random.key(0)

    def want_logits(seq):
        return np.asarray(forward(params, jnp.asarray([seq], jnp.int32),
                                  cfg)[0, -1])

    def sure(logits, err):
        """Is the argmax of these reference logits beyond the error the
        cached path was just seen to have? (always, in float32)"""
        top = np.sort(logits)[-2:]
        return top[1] - top[0] > 6 * err * np.sqrt(np.mean(logits ** 2))

    # set-up: prefill every resident slot, then bring each to its own pos
    # by solo single steps; seq[b] = real tokens so far, the last pending
    cache = init_slot_cache(cfg, B, _S)
    seq = {}
    for b, slot in enumerate(spec["slots"]):
        if slot is None:
            continue
        n = slot[0]
        prompt = [int(t) for t in np.random.RandomState(b).randint(
            1, cfg.vocab_size, n)]
        toks = np.zeros((1, _P), np.int32)
        toks[0, _P - n:] = prompt
        cache, first = prefill_slots(
            params, cache, jnp.asarray(toks), jnp.asarray([b], jnp.int32),
            jnp.asarray([_P - n], jnp.int32), rng, cfg)
        seq[b] = prompt + [int(first[0])]
    pending = np.zeros(B, np.int32)
    for b in seq:
        pending[b] = seq[b][-1]
    before = {b: slot[1] for b, slot in enumerate(spec["slots"]) if slot}
    for r in range(max(before.values())):
        on = np.array([before.get(b, 0) > r for b in range(B)])
        cache, out = decode_slots(params, cache, jnp.asarray(pending),
                                  jnp.asarray(on), rng, cfg, steps=1)
        for b in np.flatnonzero(on):
            pending[b] = int(out[b, 1])
            seq[b].append(int(out[b, 1]))
    assert all(cache["pos"][b] == _P + n for b, n in before.items())
    for b, pos in spec.get("parked_at", {}).items():
        cache = dict(cache, pos=cache["pos"].at[b].set(pos))
    active = np.array([bool(slot and slot[2]) for slot in spec["slots"]])
    pos0 = np.asarray(cache["pos"])
    start0 = np.asarray(cache["start"])
    k0, v0 = np.asarray(cache["k"]), np.asarray(cache["v"])

    # one step's logits for every live slot, straight from the cache
    live = [b for b in seq if pos0[b] < _S]
    _, logits = _decode_one(params, cache, jnp.asarray(pending), cfg)
    err = {}
    for b in live:
        err[b] = _rel_rms(logits[b], want_logits(seq[b]))
        assert err[b] < bound, (b, err[b])

    # what the chunk must emit, from the full forward alone
    eos_id = -1
    for b, after in spec.get("eos_after", {}).items():
        s = list(seq[b])
        for _ in range(after):
            s.append(int(np.argmax(want_logits(s))))
        eos_id = s[-1]
    chunks = spec.get("chunks", (4,))
    steps = sum(chunks)
    got = [pending.copy()]
    for n in chunks:
        cache, out = decode_slots(params, cache, jnp.asarray(got[-1]),
                                  jnp.asarray(active), rng, cfg,
                                  eos_id=eos_id, steps=n)
        out = np.asarray(out)
        assert (out[:, 0] == got[-1]).all()  # column 0 echoes the input
        got.extend(out[:, 1:].T)
    got = np.stack(got[1:], axis=1)  # [B, steps]
    checked = owed = 0
    for b in np.flatnonzero(active):
        s, done = list(seq[b]), seq[b][-1] == eos_id
        for j in range(min(steps, _S - pos0[b])):  # past the row's end
            ref = want_logits(s)                   # is junk nobody is owed
            owed += 1
            if done or sure(ref, max(err.values())):
                assert got[b, j] == (eos_id if done else np.argmax(ref)), \
                    (b, j)
                checked += 1
            done = done or got[b, j] == eos_id
            s.append(int(got[b, j]))  # the chunk's own token: each step
            # is held to the reference on its own
    assert checked >= (owed // 2 if dtype == "bfloat16" else owed)
    if eos_id != -1:
        b = next(iter(spec["eos_after"]))
        assert list(got[b]).count(eos_id) >= steps - 1  # frozen at eos

    # only active rows advance, and nothing that was readable has moved:
    # every slot's [start, pos) is as it was (a parked slot: all but its
    # own last position, where its clamped junk write lands)
    pos1 = np.asarray(cache["pos"])
    assert (pos1 == np.where(active, pos0 + steps, pos0)).all()
    assert (np.asarray(cache["start"]) == start0).all()
    k1, v1 = np.asarray(cache["k"]), np.asarray(cache["v"])
    for b in range(B):
        keep = slice(start0[b], min(pos0[b], _S - 1))
        assert (k1[:, b, :, keep] == k0[:, b, :, keep]).all(), b
        assert (v1[:, b, :, keep] == v0[:, b, :, keep]).all(), b


@pytest.mark.parametrize("attention", ["xla", "kernel"])
def test_engine_counts_the_cache_rows_decode_reads(attention, kernel):
    """`decode_kv_rows_*` after two requests of known lengths, by hand.
    Prompts of 5 and 11 tokens are admitted as one group padded to 16, so
    start = 11 and 5 and pos = 16 for both; 24 positions a slot. Plans of
    6 and 3 tokens: chunk 1 (4 substeps, pos 16..19) runs both slots,
    chunk 2 (pos 20..23) the first alone."""
    if attention == "kernel":
        kernel(8)  # three blocks of 8 a slot
    cfg = tiny_config()
    params = init_params(jax.random.key(0), cfg)
    eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                          max_new_tokens=8, min_bucket=8, decode_chunk=4)
    assert eng._kv_block == (8 if attention == "kernel" else None)
    reqs = [eng.submit(list(range(1, 6)), 6),
            eng.submit(list(range(1, 12)), 3)]
    while not all(r.done.is_set() for r in reqs):
        assert eng.step()
    assert [len(r.tokens) for r in reqs] == [6, 3]
    assert eng.stats["decode_steps"] == 8
    assert eng.stats["decode_kv_rows_cache"] == 2 * 4 * 2 * 24
    valid = sum((16 + t - 11) + (16 + t - 5) for t in range(4)) \
        + sum(20 + t - 11 for t in range(4))
    assert eng.stats["decode_kv_rows_valid"] == valid == 118
    # blocks of 8: slot 0 owns [11, pos): block 1 at pos 16, blocks 1-2
    # from 17 on; slot 1 owns [5, pos): blocks 0-1 at 16, 0-2 from 17 on
    blocks = (1 + 2 + 2 + 2) + (2 + 3 + 3 + 3) + 4 * 2
    assert eng.stats["decode_kv_rows_read"] == (
        blocks * 8 if attention == "kernel" else 2 * 4 * 2 * 24)
    # and each request got the full forward's greedy tokens
    for r in reqs:
        seq = list(r.prompt)
        for tok in r.tokens:
            assert tok == int(jnp.argmax(forward(
                params, jnp.asarray([seq], jnp.int32), cfg)[0, -1]))
            seq.append(tok)


def test_kernel_runs_per_shard_under_a_tensor_mesh(kernel):
    """An engine on a `tensor=2` mesh (tiny_config has 2 KV heads, the
    sharded axis) with the kernel forced: one KV head a shard, the same
    greedy tokens as the unsharded masked contraction."""
    from ray_tpu.parallel import MeshSpec

    cfg = tiny_config()
    params = init_params(jax.random.key(0), cfg)
    prompts = [[3, 1, 4, 1, 5], [2, 7]]

    def tokens(mesh):
        eng = InferenceEngine(params, cfg, slots=2, max_prompt_len=16,
                              max_new_tokens=8, mesh=mesh)
        reqs = [eng.submit(p) for p in prompts]
        while not all(r.done.is_set() for r in reqs):
            assert eng.step()
        return [list(r.tokens) for r in reqs], eng._kv_block

    want, block = tokens(None)
    assert block is None
    kernel(8)
    mesh = MeshSpec(data=1, fsdp=1, tensor=2).build(jax.devices()[:2])
    assert tokens(mesh) == (want, 8)
