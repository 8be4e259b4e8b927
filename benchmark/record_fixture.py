"""Record the small device trace kept under benchmark/fixtures/ (run on the
chip, in one process, no cluster):

    python benchmark/record_fixture.py --out chiprun_out/fixture

A two-layer model at head_dim 128 with the Pallas attention kernel, three
traced train steps with the train loop's `bench:` host spans around them
and one deliberate 20 ms host sleep (span `bench:fixture.sleep`) between
the second and the third, so that busy/idle, per-operation time and gap
attribution all have something known to find. Writes the `.xplane.pb`,
what `describe` sees in it, and the facts the test asserts.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/fixture")
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import probes, xplane
    from ray_tpu.models.config import TransformerConfig
    from ray_tpu.models.training import (init_train_state, make_optimizer,
                                         make_train_step)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    cfg = TransformerConfig(
        vocab_size=2048, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=512, max_seq_len=512, rope_theta=1e6,
        param_dtype=jnp.bfloat16, attention_impl="auto")
    rows, seq = 2, 512
    tx = make_optimizer(3e-4, mu_dtype=jnp.bfloat16)
    state = init_train_state(jax.random.key(0), cfg, tx)
    rng = np.random.default_rng(0)

    def batch():
        return {"tokens": rng.integers(0, cfg.vocab_size, (rows, seq + 1),
                                       dtype=np.int32)}

    step = make_train_step(cfg, tx).lower(state, batch()).compile()
    state, m = step(state, batch())
    float(m["loss"])
    trace_dir = os.path.join(args.out, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    ann = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(
                trace_dir, profiler_options=probes.trace_options())
    t0 = time.perf_counter()
    for i in range(args.steps):
        if i == args.steps - 1:
            with ann("bench:fixture.sleep"):
                time.sleep(0.02)
        with ann("bench:train.dispatch"):
            state, m = step(state, batch())
        with ann("bench:train.wait_step"):
            float(m["loss"])
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = xplane.find_xplane(trace_dir)
    kept = os.path.join(args.out, "train_tiny_v5e.xplane.pb")
    shutil.copy(path, kept)
    red = xplane.reduce_planes(xplane.load_planes(kept))
    import re

    pattern = xplane.KERNEL_TAG
    calls = sum(c for k, c in red["op_count"].items()
                if re.search(pattern, k))
    facts = {"attention_op_pattern": pattern,
             "attention_kernel_calls": calls,
             "device_kind": dev.device_kind, "steps": args.steps,
             "rows": rows, "seq": seq, "layers": cfg.n_layers,
             "traced_wall_s": wall, "xplane_bytes": os.path.getsize(kept),
             "reduced": red}
    with open(os.path.join(args.out, "facts.json"), "w") as f:
        json.dump(facts, f, indent=1)
    with open(os.path.join(args.out, "describe.json"), "w") as f:
        json.dump(xplane.describe(kept, top=40), f, indent=1)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({k: facts[k] for k in facts if k != "reduced"}))
    print(json.dumps({k: red.get(k) for k in (
        "devices", "window_s", "busy_s", "device_ops", "idle_gaps",
        "modules")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
