"""Is the vocabulary head's held bf16 copy the operand the chip has always
multiplied by? On the chip, for each served configuration at its cell's own
slots and widths, the same programs on a replica's tree WITH the copy
(`transformer.with_head_copy`) and on that tree WITHOUT it (the float32 leaf
alone, as every replica held it before), every other leaf shared:

    python chip_head_copy.py [--workloads CELL ...] [--seed N]   # on a TPU

One JSON line a cell:

- ``decode_logits_equal``: `_decode_one`'s float32 logits [slots, vocab]
  after every slot was prefilled, `array_equal` between the two trees (and
  the largest difference, 0.0 where equal). The copy is a different result
  wherever this is false: the exit code says so.
- ``chunk_tokens_equal``: the served `decode_slots` chunk (4 substeps, the
  program in which XLA hoisted the conversion) from the same prefilled
  cache, its sampled tokens on either tree.
- ``prefill_logits_equal``: the first-token logits of a prompt group
  (`_final_logits` of `_prefill_hidden`'s last position) at groups of 1, 2
  and 4 rows, which decides whether the prompt pass may read the copy too
  (at one row XLA keeps a float32 product off the MXU: read false there
  in all four configurations and true at 2 and 4, so `prefill_slots` is
  given the tree without the copy: PERF.md section 6, PR 57).
- ``chunk_ms``: host clock around 20 chunks of either program dispatched
  back to back and waited for once, over 20: what the copy made again
  costs a chunk.

Every cell runs in a child process (a chip belongs to one process, and a
tree has to leave the device before the next one comes). Off the TPU it
refuses, unless ``--toy`` asks for toy widths on any platform: there a
float32 matmul IS one, so the two trees differ by the rounding and the
lines say by how much (the script's plumbing, not its verdict:
tests/test_head_copy.py).
"""

import argparse
import json
import subprocess
import sys
import time

TOY = dict(vocab_size=96, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
           head_dim=8, d_ff=24, attention_impl="xla", max_seq_len=64)
TOY_DEPLOYMENT = dict(slots=4, max_prompt_len=16, max_new_tokens=8)
GROUPS = (1, 2, 4)
CHUNK = 4


def served_cells(bench) -> list:
    """The first serve cell of each configuration the benchmark serves."""
    from benchmark.harness import spec

    cells = {}
    for cell in bench["workloads"]:
        if "slots" in spec.load_traffic(cell["traffic"]).get(
                "deployment", {}):
            cells.setdefault(cell["config"], cell["name"])
    return list(cells.values())


def compare(cfg, slots: int, max_len: int, bucket: int, seed: int,
            timed_chunks: int = 20) -> dict:
    """The line of one configuration (see the module's text)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import traffic
    from ray_tpu.models.engine import (_decode_one, decode_slots,
                                       init_slot_cache, prefill_slots)
    from ray_tpu.models.generate import _final_logits, _prefill_hidden
    from ray_tpu.models.transformer import HEAD_COPY, without_head_copy
    from ray_tpu.serve.llm import drawn_serving_params

    held = drawn_serving_params(cfg, seed)
    if HEAD_COPY not in held:
        return {"copy": False, "dtype": jnp.dtype(cfg.dtype).name}
    leaf = without_head_copy(held)
    K, P = max(GROUPS), bucket
    rows = slots - slots % K

    def group(g):       # K prompts of their own, left-padded to the bucket
        lengths = [P, P // 2 + 3, 5, P - 1][:K]
        toks, starts = np.zeros((K, P), np.int32), np.zeros(K, np.int32)
        for i, n in enumerate(lengths):
            toks[i, P - n:] = traffic.prompt_tokens(
                seed + 7 * g + i, n, cfg.vocab_size)
            starts[i] = P - n
        return jnp.asarray(toks), jnp.asarray(starts)

    def differ(a, b) -> dict:
        a, b = np.asarray(a), np.asarray(b)
        return {"equal": bool(np.array_equal(a, b)),
                "max_abs_diff": float(np.max(np.abs(
                    a.astype(np.float64) - b.astype(np.float64))))}

    prefill_logits = jax.jit(lambda p, t, s: _final_logits(
        p, _prefill_hidden(p, t, cfg, P, s)[0][:, -1:], cfg)[:, 0])
    toks, starts = group(0)
    prefill = {str(k): differ(prefill_logits(held, toks[:k], starts[:k]),
                              prefill_logits(leaf, toks[:k], starts[:k]))
               for k in GROUPS}

    def prefilled():    # every slot admitted, by the served program
        cache = init_slot_cache(cfg, slots, max_len)
        pending = jnp.zeros(slots, jnp.int32)
        for g in range(rows // K):
            toks, starts = group(g)
            at = jnp.arange(K, dtype=jnp.int32) + g * K
            cache, first = prefill_slots(held, cache, toks, at, starts,
                                         jax.random.key(0), cfg, True, 1.0)
            pending = pending.at[at].set(first)
        return cache, pending

    active = jnp.arange(slots) < rows

    def chunk(tree, cache, pending):
        return decode_slots(tree, cache, pending, active, jax.random.key(0),
                            cfg, True, 1.0, -1, steps=CHUNK)

    cache, pending = prefilled()
    step = jax.jit(lambda p, c, t: _decode_one(p, c, t, cfg)[1])
    decode = differ(step(held, cache, pending)[:rows],
                    step(leaf, cache, pending)[:rows])
    cache, toks_held = chunk(held, cache, pending)
    del cache
    cache, pending = prefilled()
    cache, toks_leaf = chunk(leaf, cache, pending)
    tokens = differ(toks_held, toks_leaf)

    chunk_ms = {}
    for name, tree in (("leaf", leaf), ("held", held)) * 2:
        nxt = toks_leaf[:, -1]
        jax.block_until_ready(cache)
        t0 = time.perf_counter()
        for _ in range(timed_chunks):
            cache, out = chunk(tree, cache, nxt)
            nxt = out[:, -1]
        jax.block_until_ready(out)
        chunk_ms.setdefault(name, []).append(
            (time.perf_counter() - t0) / timed_chunks * 1e3)
    head = held[HEAD_COPY]
    return {"copy": True, "copy_shape": list(head.shape),
            "copy_bytes": int(head.nbytes), "slots": slots, "rows": rows,
            "bucket": P, "decode_logits_equal": decode["equal"],
            "decode_logits_max_abs_diff": decode["max_abs_diff"],
            "chunk_tokens_equal": tokens["equal"],
            "prefill_logits_equal": {k: v["equal"]
                                     for k, v in prefill.items()},
            "prefill_logits_max_abs_diff": {
                k: v["max_abs_diff"] for k, v in prefill.items()},
            "chunk_ms": chunk_ms}


def one_cell(args) -> int:
    import jax

    from benchmark.harness import spec

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.toy:
        print(json.dumps({"ok": False, "error": "no TPU: " + dev.platform}))
        return 1
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.cell)
    conf = spec.load_config(bench, cell["config"])
    dep = dict(spec.load_traffic(cell["traffic"])["deployment"],
               **(TOY_DEPLOYMENT if args.toy else {}))
    cfg = spec.build_transformer_config(conf, **(TOY if args.toy else {}))
    line = compare(cfg, dep["slots"],
                   dep["max_prompt_len"] + dep["max_new_tokens"],
                   min(args.bucket, dep["max_prompt_len"]),
                   spec.seed32(args.seed),
                   timed_chunks=2 if args.toy else 20)
    print(json.dumps(dict(line, workload=args.cell, platform=dev.platform,
                          device_kind=dev.device_kind)), flush=True)
    return 0 if line.get("decode_logits_equal", True) or args.toy else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", nargs="+",
                    help="left out: the first serve cell of each "
                    "configuration the benchmark serves")
    ap.add_argument("--cell", help="run this one cell in this process")
    ap.add_argument("--seed", type=int, default=4200000501)
    ap.add_argument("--bucket", type=int, default=128,
                    help="prompt bucket of the groups (the head multiplies "
                    "a group's LAST positions: its shape does not depend "
                    "on the bucket)")
    ap.add_argument("--toy", action="store_true",
                    help="toy widths and slots, any platform")
    args = ap.parse_args(argv)
    if args.cell:
        return one_cell(args)
    from benchmark.harness import spec

    cells = args.workloads or served_cells(spec.load_benchmark())
    failed = []
    for cell in cells:
        cmd = [sys.executable, __file__, "--cell", cell, "--seed",
               str(args.seed), "--bucket", str(args.bucket)] \
            + (["--toy"] if args.toy else [])
        if subprocess.run(cmd).returncode:
            failed.append(cell)
    print(json.dumps({"ok": not failed, "cells": cells, "failed": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
