"""Continuous-batching inference engine: per-step slot admission/eviction.

Ref analog: the reference serves LLMs through replica actors whose
batching is *request-cohort* shaped (`python/ray/serve/batching.py:337`
coalesces waiting calls; `python/ray/serve/_private/replica.py:237` runs
them) — a cohort must finish before its slots free, so one long
generation stalls the batch. This engine is the vLLM/Orca-style redesign
the reference delegates to external vLLM workers for, built TPU-first:

  - The KV cache is a fixed pool of B *slots* over one contiguous
    [L, B, KV, S, hd] array — static shapes, one compiled decode program
    for the life of the engine. A slot is a row; admission writes a new
    prompt's K/V into a freed row, eviction is just host bookkeeping.
  - The cache is a TREE of such leaves, each owned by one KIND of token
    mixer and stacked over the layers of that kind (`SlotCache` below).
    What a kind keeps a slot, how it lands there, its prefill and its
    one-token step are ONE table, `generate.MIXERS`: this module walks the
    layers as training does, looks each kind up and names none. Admission
    writes a slot's share of every leaf in the prefill program.
  - A decode substep reads the cache rows its requests own (on a chip a
    Pallas kernel, `ops/decode_attention.py`, fetches the blocks crossing
    ``[start, pos)``; else a masked contraction runs over every position)
    and writes only the rows that change, AFTER the scan (`_decode_one`).
  - Each decode step advances EVERY active slot by one token in a single
    batched program (per-row cache positions, per-row RoPE), then the
    host admits queued prompts into any slots that finished — finished
    sequences never block running ones.
  - Prefill is a separate program per (group size, prompt bucket) pair
    (both power-of-two, bounded compile count) whose K/V lands directly
    in the slot rows; queued prompts admit in groups of up to 4 as ONE
    batched program, the oldest with the oldest of its own bucket (a
    group pads to its largest member), and prefills interleave with
    decode chunks so time-to-first-token stays bounded under load.
  - Dispatch and fetch are pipelined across two threads: the scheduler
    thread admits + dispatches (cheap async calls), the fetcher thread
    does the device->host token transfers, which overlap with queued
    execution — worth it wherever a fetch round-trip costs far more
    than a dispatch; chip_smoke.py prints both on a local chip
    (ROADMAP D5 asks what the split is worth there).
  - Sampling happens on-device; the host sees B int32s per step — the
    decode loop's host<->device traffic is O(slots), not O(vocab).
  - Tensor parallelism comes from sharding, not new code: params carry
    their logical axes (kv_heads/heads/mlp/vocab -> "tensor") and the
    cache shards on its KV-head axis; XLA propagates the TP layout
    through the same jitted step and inserts the collectives.
  - Observability is always on: every second of the scheduler and the
    fetcher thread goes to one named state (``engine.stats`` seconds,
    ``jax.profiler.TraceAnnotation`` spans on the device trace's clock
    whenever someone traces the process), a state that lasts over a
    second while work waits is a ``slow_events`` entry, and requests are
    stamped at submit, admit, first token and finish. A streamed request
    keeps a ledger of its own from there to the pulls that take its
    tokens (`InferenceEngine._stream`).
"""

from __future__ import annotations

import collections
import itertools
import logging
import queue
import threading
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from functools import partial
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.generate import (MIXERS, _final_logits, _prefill_hidden,
                                     embed_tokens, join_period,
                                     join_segments, residual)
from ray_tpu.models.transformer import (Params, block_norm, ffn_block,
                                        layer_segments, param_logical_axes,
                                        refuse_unserved, serving_params,
                                        without_head_copy)
from ray_tpu.ops.decode_attention import pick_block, rows_read
from ray_tpu.utils.compile_cache import follow_compile_ledger

log = logging.getLogger(__name__)

SlotCache = Dict[str, jax.Array]
# A tree of leaves with a slots axis B. The engine's own: "pos" [B], slot
# b's next write position, "start" [B], its first real (non-pad) position,
# and, where layers have experts, "moe_counts" [3] float32: the held
# experts fetched, the assignments that fell on them and the layers whose
# grouped matmuls ran the short row buffer's kernel in the LAST decode
# chunk (the host adds them up as it fetches the chunk's tokens). Every
# other leaf belongs to the layers of ONE mixer kind (`generate.MIXERS`) and
# is written as its `Mixer.land` says; attention alone: k, v, pos, start.


def init_slot_cache(cfg: TransformerConfig, slots: int,
                    max_len: int) -> SlotCache:
    refuse_unserved(cfg)
    cache = {name: jnp.zeros(shape, dtype)
             for kind, mixer in MIXERS.items() if cfg.layers_of_kind(kind)
             for name, (shape, dtype) in mixer.leaves(cfg, slots,
                                                      max_len).items()}
    if cfg.moe_experts:
        cache["moe_counts"] = jnp.zeros((3,), jnp.float32)
    cache.update(pos=jnp.zeros((slots,), jnp.int32),
                 start=jnp.zeros((slots,), jnp.int32))
    return cache


def cache_logical_axes(cache=None) -> Dict[str, tuple]:
    """Logical axes of ``cache``'s leaves, or of the four an attention-only
    model holds (no slots axis: serving shards the model, not the batch)."""
    axes = {"pos": (None,), "start": (None,), "moe_counts": (None,)}
    for mixer in MIXERS.values():
        axes.update(mixer.axes)
    return {name: axes[name] for name in
            ((*MIXERS["attention"].axes, "pos", "start")
             if cache is None else cache)}


def _sample(logits, rng, greedy: bool, temperature):
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        rng, logits / jnp.maximum(temperature, 1e-6)).astype(jnp.int32)


def _put_rows(cache: SlotCache, new_k: jax.Array, new_v: jax.Array,
              slots: jax.Array, at: jax.Array, names=("k", "v")):
    """Write row i of ``new_k``/``new_v`` [L, n, KV, T, hd] into slot
    ``slots[i]`` at positions ``at[i]`` .. ``at[i]+T`` of the cache's two
    leaves ``names``; -> (k, v). One dynamic_update_slice per row (n is
    static: unrolled): on a donated cache it moves the rows, nothing else."""
    k, v = (cache[name] for name in names)
    zero = jnp.zeros((), jnp.int32)
    for i in range(new_k.shape[1]):
        start = (zero, slots[i], zero, at[i], zero)
        k = jax.lax.dynamic_update_slice(
            k, new_k[:, i:i + 1].astype(k.dtype), start)
        v = jax.lax.dynamic_update_slice(
            v, new_v[:, i:i + 1].astype(v.dtype), start)
    return k, v


def _put_slots(leaf: jax.Array, new: jax.Array, slots: jax.Array):
    """Row i of ``new`` [L, n, ...] over the whole of slot ``slots[i]`` of a
    leaf [L, B, ...]: a request finds there its own, never the last tenant's."""
    zero = jnp.zeros((), jnp.int32)
    for i in range(new.shape[1]):
        leaf = jax.lax.dynamic_update_slice(
            leaf, new[:, i:i + 1].astype(leaf.dtype),
            (zero, slots[i]) + (zero,) * (leaf.ndim - 2))
    return leaf


@partial(jax.jit, static_argnames=("cfg", "greedy"), donate_argnums=(1,))
def prefill_slots(params: Params, cache: SlotCache, tokens: jax.Array,
                  slots: jax.Array, starts: jax.Array, rng: jax.Array,
                  cfg: TransformerConfig, greedy: bool = True,
                  temperature: float = 1.0):
    """Batched prefill: ``tokens`` [K, P] (left-padded to one shared
    bucket, first real token of row i at ``starts[i]``) lands in cache
    rows ``slots`` [K]; -> (cache, first sampled tokens [K]). What a layer
    keeps a slot whole (a state, a tail, a ring) is REPLACED by the
    prompt's, computed from zero: admission is the reset. One compiled
    program per (K, P) pair; the scheduler keeps K to a few group sizes."""
    K, P = tokens.shape
    # the prompt pass reads the float32 head, not its held copy: XLA keeps
    # a group of ONE row's float32 product off the MXU, and there the copy
    # is another result (first-token logits apart by up to 0.009 on the
    # chip; groups of 2 and 4 equal to the bit: chip_head_copy.py)
    params = without_head_copy(params)
    x, cK = _prefill_hidden(params, tokens, cfg, P, starts)
    last = _final_logits(params, x[:, -1:], cfg)[:, 0]  # [K, V]
    toks = _sample(last, rng, greedy, temperature)      # [K]
    new = dict(cache, pos=cache["pos"].at[slots].set(P),
               start=cache["start"].at[slots].set(starts))
    for mixer in MIXERS.values():       # `Mixer.land`
        names = tuple(name for name in mixer.axes if name in cK)
        # keys, values [L, K, P, KV, hd] -> KV-major (small), each as it lands
        made = (cK[name] if mixer.land == "slot"
                else cK[name].transpose(0, 1, 3, 2, 4) for name in names)
        if names and mixer.land == "rows":      # row i into slot slots[i]
            new.update(zip(names, _put_rows(
                cache, *made, slots, jnp.zeros_like(slots), names)))
        else:
            new.update((name, _put_slots(cache[name], leaf, slots))
                       for name, leaf in zip(names, made))
    return new, toks


def _on_chip() -> bool:
    return jax.default_backend() != "cpu"


def _kv_block(cache: SlotCache) -> Optional[int]:
    """The position block the decode kernel walks the cache's keys and
    values by, or None where the masked contraction runs instead: on the
    CPU, for a shape the kernel does not take (`pick_block`: under
    differential attention the PAIRS the leaf holds) and for a model
    without attention layers: decided by what the code can observe."""
    if "k" not in cache or not _on_chip():
        return None
    k = cache["k"]
    return pick_block(k.shape[3], k.shape[4], k.dtype)


def _ring_mask(pos, start, window: int):
    """[B, window] bool: the places of a ring a decode step at ``pos`` reads.
    Place j holds the last position p < pos with p % window == j; the token
    sees it where p is within the ``window`` positions that end at the token
    (so never the place it is about to overwrite) and no left padding (nor
    a place never written: p < 0)."""
    j = jnp.arange(window)[None, :]
    last = pos[:, None] - 1
    held = last - (last - j) % window
    return (held >= start[:, None]) & (held > pos[:, None] - window) \
        & (held >= 0)


def _decode_one(params: Params, cache: SlotCache, tokens: jax.Array,
                cfg: TransformerConfig, active: Optional[jax.Array] = None,
                mesh=None):
    """One decode step for every slot: tokens [B] (each slot's pending
    token) -> (cache with pos advanced, logits [B, V]). ``active`` [B] bool
    left out reads every slot as active; a slot that is not attends to
    nothing cached and keeps its states and tails (its logits are junk).

    The layers are walked as `transformer._trunk` walks them (pos, RoPE
    and attention bounds all per-row): a segment of the pattern at a time
    (`cfg.segments`), each a scan over its repeats whose step is one period
    of mixer kinds, each kind taking its own step (`generate.MIXERS`).
    Leaves that land as rows or in a ring (`Mixer.land`) the scan only
    READS (the kernel indexes the whole stack, a masked contraction takes
    its layers' slabs); each layer's new row ([L,B,KV,hd], a megabyte)
    lands afterwards, one in-place dynamic_update_slice per slot: a write
    inside the scan makes it re-stack, and XLA copy, the whole cache every
    substep. A ``pos`` past the end clamps to the slot's own last position,
    which no request's plan reads (`InferenceEngine._max_len`). Leaves
    that land a slot whole ride the carry and are updated where they lie."""
    pos, start = cache["pos"], cache["start"]
    x = embed_tokens(params, tokens[:, None], cfg)  # [B, 1, d]
    positions = pos[:, None]  # [B, 1] per-row RoPE
    B = tokens.shape[0]
    mixers = {kind: mixer for kind, mixer in MIXERS.items()
              if cfg.layers_of_kind(kind)}
    # how the model's leaves land, and the places of one that lands so
    places = {mixer.land: cache[next(iter(mixer.axes))].shape[-2]
              for mixer in mixers.values() if mixer.land}
    kernel = _kv_block(cache) is not None
    # the served chunk names the active slots and donates its cache; a
    # caller that names none may keep the cache it gave (`Mixer.hands_back`)
    in_place = active is not None
    if active is None and (kernel or "slot" in places):
        active = jnp.ones_like(pos, bool)
    mask = ring = None      # the places of a leaf that a token reads
    if "rows" in places and not kernel:
        kpos = jnp.arange(places["rows"])[None, :]
        mask = (kpos >= start[:, None]) & (kpos < pos[:, None])  # [B, S]
    if "ring" in places:
        ring = _ring_mask(pos, start, places["ring"])
    shared = dict(cache=cache, positions=positions, active=active,
                  mask=mask, ring=ring, kernel=kernel, mesh=mesh,
                  in_place=in_place)

    def block(carry, scanned, kinds, first, seen, slab_names):
        # ``first``: the model's layers before the segment, ``seen``: of
        # each kind; ``slab_names``: the leaves scanned beside the weights
        lps, period, *slabs = scanned
        carry, slabs = dict(carry), dict(zip(slab_names, slabs))
        x, rows = carry["x"], {}
        for j, (kind, lp) in enumerate(zip(kinds, lps)):
            i, n = kinds[:j].count(kind), kinds.count(kind)
            # this layer among its kind's, the cache leaves' leading axis
            layer = period if n == 1 else period * n + i
            if seen[kind]:
                layer = layer + seen[kind]
            x, row = MIXERS[kind].step(x, lp, cfg, SimpleNamespace(
                **shared, slabs=slabs, carry=carry, seen=seen, layer=layer,
                i=i, index=lambda: first + period * len(kinds) + j))
            if row:
                rows.setdefault(kind, []).append(row)
            down, stats = ffn_block(
                block_norm(x, lp, "mlp_norm", cfg), lp, cfg)
            if "router" in lp and "moe_counts" in carry:
                assignments = x.shape[0] * x.shape[1] * cfg.moe_top_k
                carry["moe_counts"] = carry["moe_counts"] + jnp.stack(
                    [stats["fetched"], stats["held"] * assignments,
                     stats["rows_kernel"]])
            x = residual(x, down, cfg).astype(cfg.dtype)
        return dict(carry, x=x), {kind: tuple(zip(*pairs))
                                  for kind, pairs in rows.items()}

    carried = {name: cache[name] for mixer in mixers.values()
               if mixer.land == "slot"
               and (in_place or not mixer.hands_back) for name in mixer.axes}
    if "moe_counts" in cache:
        carried["moe_counts"] = cache["moe_counts"]
    carried["x"] = x
    for mixer in mixers.values():
        carried.update(mixer.carry(cfg, B, None, cache))
    first, seen, new_rows = 0, collections.Counter(), {}
    for (kinds, reps), stacks in zip(cfg.segments(),
                                     layer_segments(params["layers"])):
        count = collections.Counter(kinds)
        # a kernel indexes [L, ...] itself: the scan carries the period's
        # index; the masked contractions take their layers' slabs
        scanned, slab_names = [stacks, jnp.arange(reps)], []
        for kind, mixer in mixers.items():
            n = count[kind]
            if n and (mixer.land == "ring"
                      or mixer.land == "rows" and not kernel):
                for name in mixer.axes:
                    c = cache[name]
                    if n * reps != c.shape[0]:
                        c = c[seen[kind]:seen[kind] + n * reps]
                    scanned.append(c.reshape((reps, n) + c.shape[1:]))
                    slab_names.append(name)
        carried, rows = jax.lax.scan(
            partial(block, kinds=kinds, first=first,
                    seen=collections.Counter(seen), slab_names=slab_names),
            carried, tuple(scanned))
        for kind, (k_rows, v_rows) in rows.items():
            new_rows.setdefault(kind, []).append(
                (join_period(k_rows), join_period(v_rows)))
        first += len(kinds) * reps
        for kind, n in count.items():
            seen[kind] += n * reps
    x = carried["x"]
    if any(mixer.reads for mixer in mixers.values()):
        # the rows of a leaf that another kind's layers read are written once
        # its last reader is done: without the tie the write may be scheduled
        # before the last loop, and the whole leaf copied twice a substep
        x, new_rows = jax.lax.optimization_barrier((x, new_rows))
    logits = _final_logits(params, x, cfg)[:, 0]  # [B, V]
    # a slot that is not active stays where it is: what it writes lands on
    # the one place at its frozen position (of a ring: the place that has
    # just left the window), every substep of a chunk
    new = dict(cache, pos=pos + 1 if active is None
               else pos + active.astype(pos.dtype),
               **{name: carried[name] for name in carried if name in cache})
    every = jnp.arange(B, dtype=jnp.int32)
    for kind, mixer in mixers.items():
        if kind in new_rows:
            names = tuple(mixer.axes)
            k_rows, v_rows = (join_segments(parts)
                              for parts in zip(*new_rows[kind]))
            if mixer.land == "slot":    # handed back whole, a layer each
                new.update(zip(names, (k_rows, v_rows)))
                continue
            new.update(zip(names, _put_rows(
                cache, k_rows[:, :, :, None], v_rows[:, :, :, None], every,
                pos % places["ring"] if mixer.land == "ring" else pos,
                names)))
    return new, logits


@partial(jax.jit, static_argnames=("cfg", "greedy", "steps", "mesh"),
         donate_argnums=(1,))
def decode_slots(params: Params, cache: SlotCache, tokens: jax.Array,
                 active: jax.Array, rng: jax.Array,
                 cfg: TransformerConfig, greedy: bool = True,
                 temperature: float = 1.0, eos_id: int = -1,
                 steps: int = 1, mesh=None):
    """``steps`` decode substeps for every slot in ONE compiled program:
    tokens [B] (pending sampled-but-not-decoded tokens), active [B]
    bool; -> (cache, [B, steps+1]) where column 0 echoes the INPUT
    tokens and columns 1..steps are the new samples.

    Multi-step scheduling: the host pays one dispatch + one transfer per
    chunk instead of per token — admission granularity becomes ``steps``
    decode steps, host overhead drops by the same factor. The echoed
    input column lets the pipelined host loop learn prefill-sampled
    first tokens from the same fetch (the token chain itself never
    leaves the device). Rows whose input is ``eos_id`` or that hit it
    mid-chunk freeze on-device (keep emitting eos); inactive slots compute
    junk into a position the next real write or prefill overwrites, their
    positions don't advance, and the host ignores their samples. ``mesh``:
    the one the params and the cache are sharded over (the kernel needs it).
    """
    pos0 = cache["pos"]
    if "moe_counts" in cache:   # this chunk's alone
        cache = dict(cache, moe_counts=jnp.zeros_like(cache["moe_counts"]))

    def substep(carry, step_rng):
        cache, tok, done = carry
        cache, logits = _decode_one(params, cache, tok, cfg, active, mesh)
        nxt = _sample(logits, step_rng, greedy, temperature)
        nxt = jnp.where(done, jnp.asarray(eos_id, nxt.dtype), nxt)
        done = done | (nxt == eos_id)
        return (cache, nxt, done), nxt

    done0 = tokens == eos_id
    (cache, _, _), toks = jax.lax.scan(
        substep, (cache, tokens, done0), jax.random.split(rng, steps))
    # only active rows advance; inactive rows' junk substep writes are
    # overwritten by the next prefill/real decode at their frozen pos
    new_pos = jnp.where(active, cache["pos"], pos0).astype(jnp.int32)
    cache = dict(cache, pos=new_pos)
    return cache, jnp.concatenate([tokens[:, None], toks.T], axis=1)


# ---- host-side scheduler ----------------------------------------------------

_FINISH_EOS = "eos"
_FINISH_LENGTH = "length"
# one occurrence of a thread state (ordinary ones last at most ~0.1 s)
# beyond this many seconds is a slow event: the engine's heartbeat
_SLOW_S = 1.0


@dataclass
class _Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    tokens: List[int] = field(default_factory=list)
    done: threading.Event = field(default_factory=threading.Event)
    stream_q: Optional[queue.Queue] = None
    finish_reason: Optional[str] = None
    error: Optional[BaseException] = None
    # stamps (``time.perf_counter``): made, taken into a prefill group,
    # first token on the host, finished; and how it was admitted
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    bucket: int = 0        # P of the prefill program that admitted it
    group: int = 0         # K of that program
    chunks_ahead: int = 0  # undelivered decode chunks at its admission
    # ``time.time()`` where the caller made the request (a serve handle's
    # ``remote()``, in another process); None: the engine driven directly
    t_sent: Optional[float] = None
    # the stream's ledger (`InferenceEngine._stream`), written by the one
    # thread that pulls the stream and read once it has ended (``closed``,
    # set last): seconds blocked on ``stream_q`` and seconds suspended
    # between pulls, the tokens' seconds in ``stream_q``, resumptions,
    # those that found their token queued, tokens handed out
    t_first_pickup: float = 0.0
    stream_open_s: float = 0.0  # submit to the generator's end
    stream_wait_s: float = 0.0
    stream_held_s: float = 0.0
    pickup_lag_s: float = 0.0
    pulls: int = 0
    ready_pulls: int = 0
    stream_tokens: int = 0
    closed: Optional[str] = None  # "done" | "abandoned" | "error"
    log_entry: Optional[dict] = None  # its ``request_log`` entry, once done

    def emit(self, tok: int, t_delivered: float):
        self.tokens.append(tok)
        if self.stream_q is not None:
            self.stream_q.put((tok, t_delivered))

    def stream_record(self) -> dict:
        """What an ended stream adds to its ``request_log`` entry."""
        return {"t_first_pickup": self.t_first_pickup,
                "stream_open_s": self.stream_open_s,
                "stream_wait_s": self.stream_wait_s,
                "stream_held_s": self.stream_held_s,
                "pickup_lag_s": self.pickup_lag_s, "pulls": self.pulls,
                "ready_pulls": self.ready_pulls,
                "stream_tokens": self.stream_tokens, "closed": self.closed}

    def finish(self, reason: str):
        self.t_done = time.perf_counter()
        self.finish_reason = reason
        if self.stream_q is not None:
            self.stream_q.put(None)  # sentinel: stream closed
        self.done.set()


class InferenceEngine:
    """Slot scheduler over ``prefill_slots``/``decode_slots``.

    ``step()`` is one engine iteration: admit queued prompts into free
    slots (prefill), then advance every active slot one token (decode).
    ``serve_forever`` runs steps on a background thread; ``submit`` /
    ``submit_stream`` are thread-safe entry points. The weights are held
    in the dtype the programs read them in (`serving_params`: ``cfg.dtype``,
    the head and an MoE router float32, the head's bf16 copy beside it;
    converted once here), not in the dtype they were given in.
    """

    def __init__(self, params: Params, cfg: TransformerConfig, *,
                 slots: int = 8, max_prompt_len: int = 64,
                 max_new_tokens: int = 32, greedy: bool = True,
                 temperature: float = 1.0, eos_id: int = -1,
                 pad_id: int = 0, mesh=None, seed: int = 0,
                 min_bucket: int = 16, decode_chunk: int = 4,
                 max_inflight: int = 6):
        refuse_unserved(cfg)
        # running counters, always on. Plain numbers under dot-free keys,
        # every key here from the start (readers difference all of them);
        # each is written by one thread, slow_* by whichever was slow, the
        # stream_* / *pickup* / entr* keys by request threads under
        # `_fold_lock`.
        # The *_s keys are the thread-time ledger `_timed` fills: for the
        # scheduler, sched_wall_s = sched_lock_wait_s + admit_wall_s
        # (which holds prefill_dispatch_wall_s) + dispatch_wall_s +
        # park_idle_s + park_cap_s (+ fetch_wall_s + deliver_wall_s when
        # step() is driven inline); for the fetcher, fetcher_wall_s =
        # fetch_idle_s + fetch_lock_wait_s + fetch_wall_s + deliver_wall_s
        self.stats = {
            "prefills": 0, "prefill_dispatches": 0, "decode_steps": 0,
            "chunks_dispatched": 0, "chunks_delivered": 0, "fetches": 0,
            "tokens_out": 0, "requests_done": 0,
            "sched_wall_s": 0.0, "sched_lock_wait_s": 0.0,
            "admit_wall_s": 0.0, "prefill_dispatch_wall_s": 0.0,
            "dispatch_wall_s": 0.0, "park_idle_s": 0.0, "park_cap_s": 0.0,
            "fetcher_wall_s": 0.0, "fetch_idle_s": 0.0,
            "fetch_lock_wait_s": 0.0, "fetch_wall_s": 0.0,
            "deliver_wall_s": 0.0,
            # request stamps, summed where the work happens
            "queue_wait_s": 0.0, "first_token_s": 0.0, "first_tokens": 0,
            "chunks_ahead_at_admit": 0, "prefill_padded_tokens": 0,
            "prefill_prompt_tokens": 0,
            # (row position, layer) pairs the prefill programs computed,
            # and what every position through every layer would be
            "prefill_layer_tokens": 0, "prefill_padded_layer_tokens": 0,
            # cache rows (a position of a slot, every layer and head) of
            # the decode substeps dispatched: all there are, those decode
            # attention fetches, and those an active slot owns
            "decode_kv_rows_cache": 0, "decode_kv_rows_read": 0,
            "decode_kv_rows_valid": 0,
            # of the decode substeps dispatched: a KDA layer's states
            # updated (one unit: one active slot's state in EVERY KDA
            # layer, read and written once), the held experts offered (a
            # layer and substep: all it holds) and the (token, expert)
            # assignments routed; of the chunks delivered, counted on the
            # device and fetched with their tokens: the held experts that
            # got a row (whose weights a substep fetched), the
            # assignments that fell on held experts and the layers (a layer
            # and substep) whose grouped matmuls were `ops.grouped_matmul`'s
            # a mamba layer's and a mamba2 layer's likewise (one unit: one
            # active slot's states in EVERY layer of the kind); the places
            # of a window layer's ring
            # fetched (a place of a slot, every window layer and head:
            # the masked contraction reads all of them)
            "mamba_state_updates": 0, "mamba2_state_updates": 0,
            "window_kv_rows_read": 0,
            "kda_state_updates": 0, "moe_expert_calls": 0,
            "moe_assignments": 0, "moe_expert_fetches": 0,
            "moe_held_assignments": 0, "moe_rows_kernel_layers": 0,
            "slow_s": 0.0, "slow_count": 0,
            # the ended streams' ledgers (`_fold_stream`): stream_open_s =
            # stream_wait_s + stream_held_s; pickup lag over stream_tokens
            "stream_open_s": 0.0, "stream_wait_s": 0.0, "stream_held_s": 0.0,
            "stream_pulls": 0, "stream_ready_pulls": 0, "stream_tokens": 0,
            "stream_pickup_lag_s": 0.0, "first_pickup_s": 0.0,
            "first_pickups": 0, "streams_closed": 0, "streams_abandoned": 0,
            # caller's stamp to `_make_request`, of the requests that carry one
            "entry_leg_s": 0.0, "entries": 0,
            # the deploy by phase: what drawing or loading the weights took
            # (`serve.replica_weights`: the replica that then built this
            # engine writes it; 0 where no replica did), this constructor
            # (`engine.init`), `warmup()` (`engine.warmup`) and the
            # programs it ran (`engine.warmup_program` each)
            "weights_s": 0.0, "engine_init_s": 0.0, "warmup_s": 0.0,
            "warmup_programs": 0}
        # and the PROCESS's compile ledger, which its listeners keep
        # current in here: compile_requests = programs_compiled +
        # programs_loaded, compile_wait_s (of it cache_load_s),
        # trace_lower_s (`utils.compile_cache`)
        follow_compile_ledger(self, self.stats)
        # up to `max_concurrency` request threads end streams and make
        # requests at once and `stats[k] += x` is not atomic. NOT `_lock`:
        # the fetcher races the scheduler for that one (ROADMAP D5)
        self._fold_lock = threading.Lock()
        self.slow_events: collections.deque = collections.deque(maxlen=64)
        self.request_log: collections.deque = collections.deque(maxlen=1024)
        self._episodes: Dict[str, dict] = {}  # thread -> its open episode
        self._park = "idle"  # why _dispatch_locked last dispatched nothing
        with self._timed("engine_init_s", "engine.init", heartbeat=False):
            self.cfg = cfg
            # the layers a prompt's LAST position alone passes in prefill
            # (`cfg.tail_segment`; 0 for a pattern that ends in any other kind)
            self._last_row_layers = sum(
                len(kinds) * reps
                for kinds, reps in cfg.segments()[cfg.tail_segment():])
            self.slots = int(slots)
            self.max_prompt_len = int(max_prompt_len)
            self.max_new_tokens = int(max_new_tokens)
            self.greedy = bool(greedy)
            self.temperature = float(temperature)
            self.eos_id = int(eos_id)
            self.pad_id = int(pad_id)
            self.mesh = mesh
            # multi-step scheduling: decode_chunk substeps per dispatch (one
            # host round-trip per chunk); admission happens between chunks
            self.decode_chunk = max(1, int(decode_chunk))
            # pipelined mode: how many dispatched-but-unfetched decode chunks
            # may exist before the dispatch loop waits for the fetcher.
            # A device->host fetch OVERLAPS with queued execution, so the
            # win is dispatching ahead while a previous fetch is in flight;
            # the cap bounds result-delivery latency (~cap * chunk_time + one
            # fetch). What a fetch costs on a local chip: chip_smoke.py's
            # serve line (fetch_s_per_fetch).
            self.max_inflight = max(1, int(max_inflight))
            self._max_len = self.max_prompt_len + self.max_new_tokens
            self._buckets = []
            b = max(8, int(min_bucket))
            while b < self.max_prompt_len:
                self._buckets.append(b)
                b *= 2
            self._buckets.append(self.max_prompt_len)

            shardings = None
            self.cache = init_slot_cache(cfg, self.slots, self._max_len)
            if mesh is not None:
                from ray_tpu.parallel.sharding import (shard_array,
                                                       tree_shardings)

                if {"kda", "mamba", "mamba2"} & set(cfg.mixer_period) \
                        and mesh.size > 1:
                    raise NotImplementedError(
                        "a KDA, mamba or mamba2 layer's decode kernel is not "
                        "run per shard yet: serve such a model on one chip")

                shardings = tree_shardings(mesh, param_logical_axes(cfg))
                axes = cache_logical_axes(self.cache)
                self.cache = {k: shard_array(mesh, v, axes[k])
                              for k, v in self.cache.items()}
            self.params = serving_params(params, cfg, shardings)

            self._rng = jax.random.key(seed)
            self._step_i = itertools.count()
            self._rid = itertools.count()
            self._queue: "queue.Queue[_Request]" = queue.Queue()
            self._slot_req: List[Optional[_Request]] = [None] * self.slots
            # planned-occupancy scheduling: _slot_left[s] is how many tokens
            # the resident request is still OWED BY DISPATCH (not by fetch).
            # Residency is length-bounded and known at submit time, so
            # admission decisions never wait for a device->host fetch — the
            # fetch is pure result delivery. eos can only shorten a plan; it
            # is reclaimed when a fetch reveals it.
            self._slot_left: List[int] = [0] * self.slots
            # slots admitted but not yet decoded once: their next chunk's
            # echo column carries the prefill-sampled token (emit from col 0)
            self._slot_new: List[bool] = [False] * self.slots
            # the token chain lives ON DEVICE: chunk N+1's inputs are chunk
            # N's last samples (or a prefill's first sample, merged in with
            # .at[slot].set) — the host never syncs to keep the chain going
            self._next_tok_dev = jnp.zeros(self.slots, jnp.int32)
            # what the device's cache["start"] / cache["pos"] hold for a
            # resident slot, kept on the host for the decode_kv_rows_* counters
            self._slot_start = np.zeros(self.slots, np.int64)
            self._slot_pos = np.zeros(self.slots, np.int64)
            # None: the XLA contraction (or no attention layer at all)
            self._kv_block = _kv_block(self.cache)
            # what a decode substep costs by the model's shape, for the
            # counters: KDA and mamba layers, a window layer's ring, layers
            # with experts and what those offer
            self._kda_layers = cfg.layers_of_kind("kda")
            self._mamba_layers = cfg.layers_of_kind("mamba")
            self._mamba2_layers = cfg.layers_of_kind("mamba2")
            self._ring_rows = self.slots * cfg.sliding_window \
                if "win_k" in self.cache else 0
            moe_layers = cfg.n_layers if cfg.moe_experts else 0
            self._moe_calls = moe_layers * cfg.held_experts
            self._moe_assignments = moe_layers * self.slots * cfg.moe_top_k
            # dispatched-but-unfetched chunks: [(toks_dev [B, K+1],
            # [(slot, request, emit_from_col, take)], the chunk's moe_counts
            # or None)] — inline step() fetches
            # them in the step that dispatched them, the fetcher thread as the
            # device finishes them (one transfer for all that are ready)
            self._inflight: List[tuple] = []
            self._work = threading.Event()  # set when there may be work
            self._lock = threading.Lock()  # guards step() vs concurrent step()
            self._stop = threading.Event()
            self._thread: Optional[threading.Thread] = None
            # pipelined fetcher (serve_forever only): consumes _inflight so
            # the dispatch loop never blocks on a device->host transfer
            self._fetcher: Optional[threading.Thread] = None
            self._fetch_evt = threading.Event()   # work for the fetcher
            # set when the step loop died on an unrecoverable error (device /
            # XLA failure); submit() raises from then on instead of queueing
            # work that nothing will ever drain. _death_lock orders submit's
            # check+enqueue against _die's drain (NOT _lock — that is held for
            # the whole of a step(), and submissions must not block on it)
            self._fatal: Optional[BaseException] = None
            self._death_lock = threading.Lock()

    # -------------------------------------------------------- submission

    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None, *,
               t_sent: Optional[float] = None) -> _Request:
        """Enqueue a prompt; returns the request (``result()`` to wait).
        ``t_sent``: ``time.time()`` where the caller made the request."""
        req = self._make_request(prompt, max_new_tokens, stream=False,
                                 t_sent=t_sent)
        with self._death_lock:
            self._check_alive()
            self._queue.put(req)
        self._work.set()
        return req

    def submit_stream(self, prompt: Sequence[int],
                      max_new_tokens: Optional[int] = None, *,
                      t_sent: Optional[float] = None):
        """Enqueue a prompt; returns an iterator of token ids that ends
        when the sequence finishes (eos or length)."""
        req = self._make_request(prompt, max_new_tokens, stream=True,
                                 t_sent=t_sent)
        with self._death_lock:
            self._check_alive()
            self._queue.put(req)
        self._work.set()
        stream = self._stream(req)
        next(stream)  # to its first yield: from here on close() folds it
        return stream

    def _stream(self, req: _Request):
        """The generator behind `submit_stream`, and the stream's time
        ledger: from submit to its end every second goes to ``wait``
        (blocked on ``stream_q``: the engine has not delivered, the chip
        paces the stream; a `serve.stream_wait` span, whose prefix keeps it
        off the `engine.*` spans that are read as owners of device idle
        time) or to ``held`` (suspended at ``yield``: until the first
        pull, and from handing a token over until the next pull asks, so
        whoever pulls paces it). A token carries the time of the delivery
        that brought it; its pickup adds what it waited in ``stream_q``.
        All of it accumulates on the request, which this thread alone
        writes, and folds into ``stats`` once, however the stream ends."""
        clock, mark, closed = time.perf_counter, req.t_submit, "abandoned"
        try:
            yield  # `submit_stream` stops here
            while True:
                now = clock()  # a pull asks: ``held`` since `mark` ends
                req.stream_held_s += now - mark
                req.pulls += 1
                try:
                    item, ready, mark = req.stream_q.get_nowait(), True, now
                except queue.Empty:
                    ready = False
                    with jax.profiler.TraceAnnotation("serve.stream_wait",
                                                      rid=req.rid):
                        item = req.stream_q.get()
                    mark = clock()
                    req.stream_wait_s += mark - now
                if item is None:  # the engine's sentinel
                    if req.error is not None:
                        closed = "error"
                        raise req.error
                    closed = "done"
                    return
                tok, t_delivered = item
                if not req.stream_tokens:
                    req.t_first_pickup = mark
                req.stream_tokens += 1
                req.ready_pulls += ready
                req.pickup_lag_s += mark - t_delivered
                yield tok
        except GeneratorExit:  # closed between two pulls
            now = clock()
            req.stream_held_s += now - mark
            mark = now
            raise
        finally:
            self._fold_stream(req, closed, mark)

    def _fold_stream(self, req: _Request, closed: str, t_closed: float):
        """An ended stream's ledger into ``stats`` and into the request's
        ``request_log`` entry. The engine may finish the request (and make
        the entry) before or after: each side writes its own part first
        and then reads the other's, so the later of the two fills it."""
        req.stream_open_s, req.closed = t_closed - req.t_submit, closed
        with self._fold_lock:
            st = self.stats
            st["stream_open_s"] += req.stream_open_s
            st["stream_wait_s"] += req.stream_wait_s
            st["stream_held_s"] += req.stream_held_s
            st["stream_pulls"] += req.pulls
            st["stream_ready_pulls"] += req.ready_pulls
            st["stream_tokens"] += req.stream_tokens
            st["stream_pickup_lag_s"] += req.pickup_lag_s
            if req.stream_tokens:
                st["first_pickup_s"] += req.t_first_pickup - req.t_first
                st["first_pickups"] += 1
            st["streams_closed"] += 1
            st["streams_abandoned"] += closed == "abandoned"
        if req.log_entry is not None:
            req.log_entry.update(req.stream_record())

    def _make_request(self, prompt, max_new_tokens, stream: bool,
                      t_sent: Optional[float]):
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self.max_prompt_len:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds this engine's "
                f"max_prompt_len={self.max_prompt_len}")
        mnt = self.max_new_tokens if max_new_tokens is None \
            else min(int(max_new_tokens), self.max_new_tokens)
        if mnt <= 0:
            raise ValueError("max_new_tokens must be >= 1")
        if t_sent is not None:
            # wall clock against wall clock: the caller is another process
            with self._fold_lock:
                self.stats["entry_leg_s"] += time.time() - t_sent
                self.stats["entries"] += 1
        return _Request(rid=next(self._rid), prompt=prompt,
                        max_new_tokens=mnt,
                        stream_q=queue.Queue() if stream else None,
                        t_submit=time.perf_counter(), t_sent=t_sent)

    # ------------------------------------------------------ observability

    @contextmanager
    def _timed(self, key: str, span: Optional[str] = None, *,
               heartbeat: bool = True, episode_if=None, **meta):
        """THE timed region of the engine's threads: enters a
        ``jax.profiler.TraceAnnotation(span, **meta)`` (about a
        microsecond unless a profiler session is running; on the device
        trace's own clock when one is) and adds the elapsed seconds to
        ``stats[key]``. A spanned region is a thread state, and one
        occurrence over ``_SLOW_S`` while work waits is a slow event
        (``heartbeat=False``: a child whose seconds its parent's key
        already holds, or a phase of the deploy, which is no stall).
        ``episode_if`` marks a wait that wakes by its own
        timeout: back-to-back occurrences are ONE episode, counted for as
        long as the predicate says work is waiting for this thread."""
        t0, compiles0 = time.perf_counter(), self.stats["compile_requests"]
        try:
            with jax.profiler.TraceAnnotation(span, **meta) if span \
                    else nullcontext():
                yield
        finally:
            dt = time.perf_counter() - t0
            self.stats[key] += dt
            if span and heartbeat and (episode_if or dt > _SLOW_S):
                reason = meta.get("reason")
                self._beat(span[len("engine."):]
                           + (f"_{reason}" if reason else ""), t0, dt,
                           self.stats["compile_requests"] - compiles0,
                           episode_if)

    def _beat(self, state: str, t0: float, dt: float, compiles: int,
              episode_if):
        """The heartbeat behind `_timed`: ``slow_events`` gets one entry
        (and the log one warning) per occurrence or episode that passed
        ``_SLOW_S`` while work waited; ``slow_s`` its thread-seconds (a
        stall that blocks both threads counts on each); ``compiles`` the
        programs this process asked the backend for meanwhile, so a stall
        that was a compile says so (`compile_log()` names the program)."""
        thread = threading.current_thread().name
        if episode_if is None:
            if not self._work_waits():
                return  # e.g. the lock held by a warm-up: idle, not stalled
            ev = {"state": state, "t_perf": t0, "seconds": dt,
                  "compiles": compiles}
        elif not episode_if():
            self._episodes.pop(thread, None)  # idle, not stalled
            return
        else:
            ev = self._episodes.get(thread)
            if ev is None or ev["state"] != state:
                ev = self._episodes[thread] = {
                    "state": state, "t_perf": t0, "seconds": 0.0,
                    "compiles": 0}
            ev["seconds"] += dt
            ev["compiles"] += compiles
            if ev["seconds"] <= _SLOW_S:
                return
        if "thread" in ev:  # an episode already reported keeps growing
            self.stats["slow_s"] += dt
            return
        ev.update(thread=thread,
                  t_wall=time.time() - (time.perf_counter() - t0),
                  queued=self._queue.qsize(),
                  planned_slots=sum(n > 0 for n in self._slot_left),
                  undelivered_chunks=self._undelivered())
        self.slow_events.append(ev)
        self.stats["slow_count"] += 1
        self.stats["slow_s"] += ev["seconds"]
        log.warning("engine thread %s slow: %.2f s in state %s "
                    "(%d queued, %d planned slots, %d undelivered chunks, "
                    "%d compiles)",
                    thread, ev["seconds"], state, ev["queued"],
                    ev["planned_slots"], ev["undelivered_chunks"],
                    ev["compiles"])

    def _undelivered(self) -> int:
        """Decode chunks dispatched whose tokens have not reached their
        requests: queued or running on the device, or in the fetcher."""
        return self.stats["chunks_dispatched"] \
            - self.stats["chunks_delivered"]

    def _work_waits(self) -> bool:
        return self._undelivered() > 0 or not self._queue.empty() \
            or any(self._slot_left)

    @contextmanager
    def _locked(self, wait_key: str):
        """``with self._lock``, the wait for it timed as a state."""
        with self._timed(wait_key, "engine.lock_wait"):
            self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    # ------------------------------------------------------------- engine

    def _next_rng(self):
        return jax.random.fold_in(self._rng, next(self._step_i))

    def _bucket(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self.max_prompt_len

    def _admit_group(self, group: List[tuple]):
        """Dispatch ONE batched prefill for ``group`` = [(slot, req)]
        (ASYNC — the sampled first tokens join the device-side chain;
        their values reach the host in the next chunk's echo column).
        All rows pad to the largest member's bucket so the group shares
        one compiled (K, P) program."""
        K = len(group)
        P = max(self._bucket(len(req.prompt)) for _, req in group)
        # stamps and their counters where the scheduler takes the group
        # up, before a dispatch that may block on the device's queue
        now, ahead = time.perf_counter(), self._undelivered()
        for _, req in group:
            req.t_admit, req.bucket, req.group = now, P, K
            req.chunks_ahead = ahead
            self.stats["queue_wait_s"] += now - req.t_submit
            self.stats["prefill_prompt_tokens"] += len(req.prompt)
        self.stats["prefill_padded_tokens"] += K * P
        L, last = self.cfg.n_layers, self._last_row_layers
        self.stats["prefill_layer_tokens"] += K * P * (L - last) + K * last
        self.stats["prefill_padded_layer_tokens"] += K * P * L
        self.stats["chunks_ahead_at_admit"] += ahead
        with self._timed("prefill_dispatch_wall_s",
                         "engine.prefill_dispatch", heartbeat=False,
                         K=K, P=P,
                         rids=" ".join(str(req.rid) for _, req in group)):
            toks = np.full((K, P), self.pad_id, np.int32)
            slots = np.zeros(K, np.int32)
            starts = np.zeros(K, np.int32)
            for i, (slot, req) in enumerate(group):
                toks[i, P - len(req.prompt):] = req.prompt
                slots[i] = slot
                starts[i] = P - len(req.prompt)
            self.cache, first = prefill_slots(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(slots), jnp.asarray(starts),
                self._next_rng(), self.cfg, self.greedy, self.temperature)
            self._next_tok_dev = self._next_tok_dev.at[
                jnp.asarray(slots)].set(first)
        for slot, req in group:
            self._slot_req[slot] = req
            self._slot_start[slot] = P - len(req.prompt)
            self._slot_pos[slot] = P
        self.stats["prefills"] += K
        self.stats["prefill_dispatches"] += 1

    _GROUP_SIZES = (4, 2, 1)  # compiled-prefill batch sizes, largest first

    def _decode(self, active: np.ndarray):
        """Dispatch one decode chunk for the ``active`` slots (ASYNC) and
        chain its last samples; -> (the chunk's tokens [B, chunk + 1], its
        ``moe_counts`` or None: a copy, the next dispatch donates the
        cache's)."""
        # a mesh is named only where there is one, so that an unsharded
        # engine's program is the one a caller of `decode_slots` gets
        mesh = {} if self.mesh is None else {"mesh": self.mesh}
        self.cache, toks = decode_slots(
            self.params, self.cache, self._next_tok_dev,
            jnp.asarray(active), self._next_rng(), self.cfg, self.greedy,
            self.temperature, self.eos_id, steps=self.decode_chunk, **mesh)
        self._next_tok_dev = toks[:, -1]
        counts = self.cache.get("moe_counts")
        return toks, None if counts is None else jnp.copy(counts)

    def _count_kv_rows(self, active_slots: List[int]):
        """The decode_kv_rows_* counters for one chunk over these slots,
        from the bounds the kernel itself walks by (`rows_read`)."""
        steps = np.arange(self.decode_chunk)
        start = self._slot_start[active_slots, None]
        pos = self._slot_pos[active_slots, None] + steps
        self._slot_pos[active_slots] += self.decode_chunk
        whole = self.decode_chunk * self.slots * self._max_len
        self.stats["decode_kv_rows_cache"] += whole
        self.stats["decode_kv_rows_valid"] += int(np.sum(
            np.clip(pos, None, self._max_len) - start))
        self.stats["decode_kv_rows_read"] += whole \
            if self._kv_block is None else rows_read(
                start, pos, True, self._kv_block, self._max_len)

    def warmup(self):
        """Compile every program the serving loop can hit (per-bucket x
        per-group-size prefills, the decode chunk) so no compile lands
        mid-traffic. Resets slot state afterwards; call before serving.
        One `engine.warmup` span (``stats["warmup_s"]``) over an
        `engine.warmup_program` a program: the seconds until its dispatch
        returned, which is its compile or its load from the cache."""
        sizes = [s for s in self._GROUP_SIZES if s <= self.slots]
        with self._timed("warmup_s", "engine.warmup", heartbeat=False):
            for bucket in self._buckets:
                for K in sizes:
                    with self._warmup_program(K=K, P=bucket):
                        toks = np.full((K, bucket), self.pad_id, np.int32)
                        toks[:, -1] = 1
                        self.cache, first = prefill_slots(
                            self.params, self.cache, jnp.asarray(toks),
                            jnp.arange(K, dtype=jnp.int32),
                            jnp.full((K,), bucket - 1, jnp.int32),
                            self._next_rng(), self.cfg, self.greedy,
                            self.temperature)
                        # warm the chain-merge too (_admit_group runs it per
                        # group size; a mid-traffic compile stalls the loop)
                        self._next_tok_dev = self._next_tok_dev.at[
                            jnp.arange(K, dtype=jnp.int32)].set(first)
            with self._warmup_program(decode=self.decode_chunk):
                # and the last-column slice
                self._decode(np.ones(self.slots, bool))
            jax.block_until_ready(self._next_tok_dev)
            # reset bookkeeping: positions to zero, junk K/V is unreachable
            # (a state, a tail and a window's ring are replaced at admission)
            cache = self.cache
            self.cache = dict(cache, pos=jnp.zeros_like(cache["pos"]),
                              start=jnp.zeros_like(cache["start"]))
            self._next_tok_dev = jnp.zeros(self.slots, jnp.int32)
        return self

    def _warmup_program(self, **meta):
        self.stats["warmup_programs"] += 1
        return jax.profiler.TraceAnnotation("engine.warmup_program", **meta)

    def _emit_to(self, req: _Request, slot: int, tok: int,
                 t_delivered: float):
        """Record one generated token of the delivery that began at
        ``t_delivered``; on an eos finish, reclaim the slot's remaining
        planned occupancy (the plan is length-based and eos can only
        shorten it)."""
        if not req.tokens:  # the request's first token
            req.t_first = time.perf_counter()
            self.stats["first_token_s"] += req.t_first - req.t_submit
            self.stats["first_tokens"] += 1
        req.emit(tok, t_delivered)
        self.stats["tokens_out"] += 1
        reason = None
        if tok == self.eos_id:
            reason = _FINISH_EOS
        elif len(req.tokens) >= req.max_new_tokens:
            reason = _FINISH_LENGTH
        if reason is not None:
            if self._slot_req[slot] is req:
                self._slot_req[slot] = None
                self._slot_left[slot] = 0
            self.stats["requests_done"] += 1
            req.finish(reason)
            req.log_entry = {
                "rid": req.rid, "prompt_len": len(req.prompt),
                "bucket": req.bucket, "group": req.group,
                "chunks_ahead": req.chunks_ahead, "t_sent": req.t_sent,
                "t_submit": req.t_submit, "t_admit": req.t_admit,
                "t_first": req.t_first, "t_done": req.t_done,
                "tokens_out": len(req.tokens)}
            if req.closed is not None:  # abandoned before this: _fold_stream
                req.log_entry.update(req.stream_record())
            self.request_log.append(req.log_entry)

    def step(self) -> bool:
        """One engine iteration; returns True if any work was done."""
        with self._locked("sched_lock_wait_s"):
            return self._step_locked()

    def _step_locked(self) -> bool:
        # 1) admission: a slot whose planned occupancy ran out is free —
        #    no fetch needed to know it (delivery of its resident's
        #    tokens rides the already-recorded snapshots). Prefills are
        #    batched async dispatches chained on the device queue.
        admitted = self._admit_locked()
        # 2) dispatch one full-width decode chunk (async) when there is
        #    planned work and (pipelined mode) fetch headroom.
        dispatched = self._dispatch_locked()
        # 3) delivery. Inline mode fetches what is in flight here;
        #    pipelined mode hands the accumulated chunks to the fetcher
        #    thread instead, so the dispatch loop never blocks on a
        #    device->host round trip.
        processed = False
        if self._fetcher is None:
            if self._inflight:
                pending, self._inflight = self._inflight, []
                self._deliver_locked(self._fetch_chunks(pending), pending)
                processed = True
        elif self._inflight:
            self._fetch_evt.set()
        return bool(admitted or dispatched or processed)

    def _take_like(self, lead: "_Request", room: int) -> List["_Request"]:
        """Take out of the queue the requests that share a prefill with
        ``lead``: the oldest whose prompts fall in its bucket, from among
        the queue's first `slots` entries (those that could be admitted
        next anyway), as many as make a compiled group size with it
        within ``room`` slots."""
        bucket = self._bucket(len(lead.prompt))
        with self._queue.mutex:
            waiting = self._queue.queue
            like = [req for req in itertools.islice(waiting, self.slots)
                    if self._bucket(len(req.prompt)) == bucket]
            K = next(k for k in self._GROUP_SIZES
                     if k <= min(room, 1 + len(like)))
            del like[K - 1:]
            for req in like:
                waiting.remove(req)
        return like

    def _admit_locked(self) -> int:
        """Admit queued prompts into planned-free slots; dispatches one
        batched prefill a group. A group is the oldest queued request and
        the oldest after it of ITS bucket, up to a compiled group size: a
        group pads to its largest member's bucket, so a short prompt
        beside a long one would cost the long one's tokens (at 128 slots
        in first-come groups of four, twice the prompts' own). The oldest
        always leads, so none waits for ever. Returns #admitted."""
        with self._timed("admit_wall_s", "engine.admit"):
            free: List[int] = []
            for slot in range(self.slots):
                if self._slot_left[slot] > 0:
                    continue
                if self._slot_req[slot] is not None:
                    # planned release: dispatching for it is complete
                    self._slot_req[slot] = None
                free.append(slot)
            groups: List[List[tuple]] = []
            while free:
                try:
                    lead = self._queue.get_nowait()
                except queue.Empty:
                    break
                reqs = [lead] + self._take_like(lead, len(free))
                groups.append(list(zip(free, reqs)))
                del free[:len(reqs)]
            for i, group in enumerate(groups):
                try:
                    self._admit_group(group)
                except BaseException as e:
                    # a failed prefill dispatch poisons the whole engine
                    # (device/XLA error); fail this group's waiters AND every
                    # later dequeued-but-ungrouped request here — none of
                    # them are queued or slotted anymore, so _die cannot see
                    # them and they would otherwise hang forever
                    for later in groups[i:]:
                        for _slot, req in later:
                            req.error = e
                            req.finish("error")
                    raise
                for slot, req in group:
                    # the plan includes the prefill-sampled first token; it
                    # reaches the host in the next chunk's echo column
                    self._slot_left[slot] = req.max_new_tokens
                    self._slot_new[slot] = True
            return sum(len(group) for group in groups)

    def _dispatch_locked(self) -> bool:
        active_slots = [s for s in range(self.slots)
                        if self._slot_left[s] > 0]
        if not active_slots:
            self._park = "idle"  # no planned work
            return False
        if self._fetcher is not None and \
                len(self._inflight) >= self.max_inflight:
            self._park = "cap"  # dispatch-ahead cap: wait for the fetcher
            return False
        with self._timed("dispatch_wall_s", "engine.decode_dispatch",
                         active=len(active_slots)):
            width = self.decode_chunk
            snapshot = []
            for slot in active_slots:
                new = self._slot_new[slot]
                self._slot_new[slot] = False
                take = min(self._slot_left[slot],
                           width + (1 if new else 0))
                snapshot.append((slot, self._slot_req[slot],
                                 0 if new else 1, take))
                self._slot_left[slot] = max(
                    0,
                    self._slot_left[slot] - (width + 1 if new else width))
            active = np.zeros(self.slots, bool)
            active[active_slots] = True
            toks, counts = self._decode(active)
            self._count_kv_rows(active_slots)
            self.stats["decode_steps"] += width
            self.stats["chunks_dispatched"] += 1
            if self._kda_layers:
                self.stats["kda_state_updates"] += width * len(active_slots)
            if self._mamba_layers:
                self.stats["mamba_state_updates"] += \
                    width * len(active_slots)
            if self._mamba2_layers:
                self.stats["mamba2_state_updates"] += \
                    width * len(active_slots)
            self.stats["window_kv_rows_read"] += width * self._ring_rows
            self.stats["moe_expert_calls"] += width * self._moe_calls
            self.stats["moe_assignments"] += width * self._moe_assignments
        self._inflight.append((toks, snapshot, counts))
        return True

    def _fetch_chunks(self, pending) -> np.ndarray:
        """ONE batched host transfer for ``pending`` chunks (each
        [B, decode_chunk+1], and its expert counts where the model has
        experts: added to ``stats`` here), concatenated on the host. Device-side
        concat would compile a fresh program per distinct chunk count,
        and a mid-traffic compile stalls every slot. Called outside the
        lock by the fetcher; inline
        mode calls it under the lock."""
        with self._timed("fetch_wall_s", "engine.fetch",
                         chunks=len(pending)):
            parts, counts = jax.device_get(
                ([t for t, _, _ in pending],
                 [c for _, _, c in pending if c is not None]))
            big = parts[0] if len(parts) == 1 else np.concatenate(
                parts, axis=1)
            self.stats["fetches"] += 1
            for fetched, held, kernel in counts:
                self.stats["moe_expert_fetches"] += int(fetched)
                self.stats["moe_held_assignments"] += int(round(held))
                self.stats["moe_rows_kernel_layers"] += int(kernel)
        return big

    def _deliver_locked(self, big: np.ndarray, pending) -> None:
        W = self.decode_chunk + 1
        with self._timed("deliver_wall_s", "engine.deliver",
                         chunks=len(pending)):
            now = time.perf_counter()  # every token's time of delivery
            for i, (_toks_dev, snap, _counts) in enumerate(pending):
                seg = big[:, i * W:(i + 1) * W]
                for slot, req, from_col, take in snap:
                    if req.done.is_set():
                        continue  # finished in an earlier chunk
                    for t in range(from_col, from_col + take):
                        self._emit_to(req, slot, int(seg[slot, t]), now)
                        if req.done.is_set():
                            break  # rest of the row is frozen eos/junk
        self.stats["chunks_delivered"] += len(pending)

    # ---------------------------------------------------- background loop

    def serve_forever(self):
        """Run the engine on a daemon thread until ``shutdown()``, plus a
        fetcher thread that pipelines device->host transfers behind the
        dispatch loop (the transfer overlaps queued device execution, so
        its ~latency costs delivery time, never throughput)."""
        if self._thread is not None:
            return self
        self._stop.clear()

        def sched_cycle():
            try:
                busy = self.step()
            except BaseException as e:
                # an error escaping step() (device/XLA failure at
                # dispatch or fetch) kills the engine: error out every
                # in-flight and queued request so no waiter hangs, and
                # refuse new submissions (the loops end on _fatal)
                self._die(e)
                return
            if busy:
                self._episodes.pop(threading.current_thread().name, None)
                return
            # idle or at the dispatch-ahead cap: PARK until state can
            # change (submit(), fetcher taking chunks, or delivery all
            # set _work). A busy-spin here would eat the host core the
            # fetcher and request threads need — measured as ~half the
            # device sitting idle on a 1-core host.
            self._work.clear()
            with self._timed(f"park_{self._park}_s", "engine.park",
                             episode_if=self._work_waits,
                             reason=self._park):
                self._work.wait(timeout=0.05)

        def loop():
            while not self._stop.is_set() and self._fatal is None:
                with self._timed("sched_wall_s"):
                    sched_cycle()

        def fetch_cycle():
            with self._timed("fetch_idle_s", "engine.fetch_idle",
                             episode_if=lambda: bool(self._inflight)):
                self._fetch_evt.wait(timeout=0.05)
            with self._locked("fetch_lock_wait_s"):
                if not self._inflight:
                    self._fetch_evt.clear()
                    return
                # take the OLDEST chunk (delivery must advance) plus any
                # younger chunks the device has already finished — their
                # transfer piggybacks for free. Taking the whole backlog
                # instead would block this cycle on the newest,
                # just-dispatched chunk and stretch delivery latency to
                # the backlog depth.
                pending = [self._inflight.pop(0)]
                while self._inflight and self._inflight[0][0].is_ready():
                    pending.append(self._inflight.pop(0))
            self._episodes.pop(threading.current_thread().name, None)
            # taking the chunks made room under the dispatch cap — wake
            # the dispatch loop BEFORE the slow transfer so it overlaps
            # with queued execution
            self._work.set()
            try:
                big = self._fetch_chunks(pending)  # blocking transfer
                with self._locked("fetch_lock_wait_s"):
                    self._deliver_locked(big, pending)
            except BaseException as e:
                self._die(e)
                return
            # room under the cap + possibly eos-freed slots
            self._work.set()

        def fetch_loop():
            while self._fatal is None and not (
                    self._stop.is_set() and not self._inflight):
                with self._timed("fetcher_wall_s"):
                    fetch_cycle()

        self._thread = threading.Thread(target=loop, name="llm-engine",
                                        daemon=True)
        self._fetcher = threading.Thread(target=fetch_loop,
                                         name="llm-engine-fetch",
                                         daemon=True)
        self._thread.start()
        self._fetcher.start()
        return self

    def _check_alive(self):
        if self._fatal is not None:
            raise RuntimeError(
                "InferenceEngine is dead (step loop failed)") \
                from self._fatal

    def _die(self, exc: BaseException):
        """Mark the engine dead and fail every known request."""
        failed = [r for r in self._slot_req if r is not None]
        self._slot_req = [None] * self.slots
        self._slot_left = [0] * self.slots
        self._slot_new = [False] * self.slots
        with self._death_lock:
            # after this block no submit() can enqueue: _fatal is visible
            # to every subsequent check, and the queue is drained
            self._fatal = exc
            while True:
                try:
                    failed.append(self._queue.get_nowait())
                except queue.Empty:
                    break
        for _, snap, _ in self._inflight:
            failed.extend(req for _, req, _, _ in snap)
        self._inflight = []
        for req in failed:
            if not req.done.is_set():
                req.error = exc
                req.finish("error")

    def shutdown(self):
        self._stop.set()
        self._work.set()
        self._fetch_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        if self._fetcher is not None:
            self._fetcher.join(timeout=10)
            self._fetcher = None

    # ------------------------------------------------------- conveniences

    def generate(self, prompt: Sequence[int],
                 max_new_tokens: Optional[int] = None,
                 timeout: float = 300.0, *,
                 t_sent: Optional[float] = None) -> List[int]:
        """Blocking single-prompt helper (drives steps inline if no
        background thread is running)."""
        req = self.submit(prompt, max_new_tokens, t_sent=t_sent)
        if self._thread is None:
            while not req.done.is_set():
                if not self.step():
                    break
        if not req.done.wait(timeout):
            raise TimeoutError("generation timed out")
        if req.error is not None:
            raise req.error
        return list(req.tokens)
