"""A `decode_slots` chunk against the plain (cache-free) full forward.

Decode reads the slot cache and writes only the rows that change, in
place (one dynamic_update_slice per slot, after the layer scan). These are
the cases that write can get wrong.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.config import tiny_config
from ray_tpu.models.engine import (_decode_one, decode_slots,
                                   init_slot_cache, prefill_slots)
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


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.mark.parametrize("case", list(_CHUNK_CASES))
def test_decode_chunk_equals_the_full_forward(case):
    spec = _CHUNK_CASES[case]
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
