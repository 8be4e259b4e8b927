"""Compile rehearsal: each cell's real programs, compiled for a DESCRIBED
v5e (no chip attached; `on-chip-measurement` guide, section 2.3).

    JAX_PLATFORMS=cpu python benchmark/rehearse.py [--workload NAME]
        [--layers N] [--rows R] [--slots S]

Prints one JSON line per program: per-device `memory_analysis()` bytes,
whether the Mosaic kernel (`tpu_custom_call`) and which collectives are in
the compiled text. Nothing runs, so nothing here is a speed or a result;
PERF.md quotes these lines for every `reduced` entry. `--layers/--rows/
--slots` explore a size the data files do not (yet) state.
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import spec  # noqa: E402


def _on(tree, sharding):
    import jax

    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)
    return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), tree, sharding)


def _report(cell, program, compiled, t0, **extra):
    m = compiled.memory_analysis()
    text = compiled.as_text()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    line = dict(
        cell=cell, program=program, compile_s=round(time.time() - t0, 1),
        argument_gb=m.argument_size_in_bytes / 1e9,
        temp_gb=m.temp_size_in_bytes / 1e9,
        output_gb=m.output_size_in_bytes / 1e9,
        alias_gb=m.alias_size_in_bytes / 1e9,
        # arguments + temporaries + outputs - aliased: an upper estimate
        # (donated state is counted in both); the compiler itself refuses
        # a program over the chip's 15.75 GiB, so a compile that returns
        # fits
        upper_estimate_gb=total / 1e9, fits=True,
        kernel_in_program="tpu_custom_call" in text,
        collectives=sorted(c for c in ("all-gather", "all-reduce",
                                       "reduce-scatter", "all-to-all",
                                       "collective-permute") if c in text),
        compiled_for="described v5e:2x2, nothing ran", **extra)
    print(json.dumps(line), flush=True)
    return line


def rehearse_train(cell, conf, traffic, topo, args):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models.training import (batch_sharding, make_init_fn,
                                         make_optimizer, make_train_step,
                                         state_shardings)

    rows = args.rows or traffic["rows"]
    seq = traffic["seq_len"]
    over = dict(max_seq_len=seq, param_dtype=traffic["param_dtype"],
                attention_impl="pallas")  # 'auto' picks xla off the chip
    if args.layers:
        over["n_layers"] = args.layers
    cfg = spec.build_transformer_config(conf, **over)
    tx = make_optimizer(traffic["learning_rate"],
                        mu_dtype=jnp.dtype(traffic["mu_dtype"]))
    shapes = jax.eval_shape(make_init_fn(cfg, tx), jax.random.key(0))
    mesh = None
    if traffic.get("mesh"):
        from ray_tpu.parallel import MeshSpec

        mesh = MeshSpec(**traffic["mesh"]).build(topo.devices)
        state = _on(shapes, state_shardings(cfg, tx, mesh))
        bsh = batch_sharding(mesh)
    else:
        bsh = SingleDeviceSharding(topo.devices[0])
        state = _on(shapes, bsh)
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq + 1), jnp.int32,
                                            sharding=bsh)}
    t0 = time.time()
    compiled = make_train_step(cfg, tx, mesh).lower(state, batch).compile()
    out = [_report(cell, "train_step", compiled, t0, layers=cfg.n_layers,
                   rows=rows, seq=seq, params=cfg.num_params)]
    # the program side of the correctness check: forward on the check rows
    import functools

    from ray_tpu.models.transformer import forward

    crow = traffic["check"]["rows"]
    toks = jax.ShapeDtypeStruct((crow, seq), jnp.int32, sharding=bsh)
    t0 = time.time()
    fwd = jax.jit(functools.partial(forward, cfg=cfg, mesh=mesh)).lower(
        state["params"], toks).compile()
    out.append(_report(cell, f"check_forward[{crow}x{seq}]", fwd, t0,
                       layers=cfg.n_layers))
    return out


def rehearse_serve(cell, conf, traffic, topo, args):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models.engine import (InferenceEngine, decode_slots,
                                       init_slot_cache, prefill_slots)
    from ray_tpu.models.transformer import init_params, serving_params

    dep = traffic["deployment"]
    slots = args.slots or dep["slots"]
    over = {"n_layers": args.layers} if args.layers else {}
    cfg = spec.build_transformer_config(conf, **over)
    one = SingleDeviceSharding(topo.devices[0])
    max_len = dep["max_prompt_len"] + dep["max_new_tokens"]
    # the tree as a replica holds it (bf16 but for the float32 head)
    params = _on(jax.eval_shape(
        lambda k: serving_params(init_params(k, cfg), cfg),
        jax.random.key(0)), one)
    cache = _on(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots, max_len)), one)
    rng = _on(jax.eval_shape(lambda: jax.random.key(0)), one)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    out = []
    t0 = time.time()
    K, P = 4, dep["max_prompt_len"]
    prefill = prefill_slots.lower(params, cache, i32(K, P), i32(K), i32(K),
                                  rng, cfg).compile()
    out.append(_report(cell, f"prefill_slots[{K}x{P}]", prefill, t0,
                       layers=cfg.n_layers, slots=slots))
    t0 = time.time()
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one)
    decode = decode_slots.lower(
        params, cache, i32(slots), active, rng, cfg,
        steps=inspect.signature(InferenceEngine).parameters[
            "decode_chunk"].default).compile()
    out.append(_report(cell, "decode_slots", decode, t0,
                       layers=cfg.n_layers, slots=slots))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--slots", type=int)
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described-device compile cannot be read back without a chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    fa._use_interpret = lambda: False  # lower the Mosaic kernel itself
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = spec.load_benchmark()
    names = args.workload or [c["name"] for c in bench["workloads"]]
    for name in names:
        cell = spec.find_cell(bench, name)
        conf = spec.load_config(bench, cell["config"])
        traffic = spec.load_traffic(cell["traffic"])
        fn = rehearse_train if traffic["kind"] == "train" else rehearse_serve
        try:
            fn(name, conf, traffic, topo, args)
        except jax.errors.JaxRuntimeError as e:
            # the chip's compiler refuses a program over its memory
            first = str(e).splitlines()[0]
            if "RESOURCE_EXHAUSTED" not in first:
                raise
            print(json.dumps(dict(
                cell=name, fits=False, refused=first[:300],
                layers=args.layers, rows=args.rows, slots=args.slots,
                compiled_for="described v5e:2x2, nothing ran")), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
