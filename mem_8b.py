"""AOT memory analysis of the v5p-64-sharded Llama-3-8B train step.

The north star (BASELINE.json) is an 8B pretrain on a v5p-64 slice at
>=40% MFU. No such slice is attached, but the memory story does not need
one: `jax.jit(...).lower(...).compile()` on a 64-device CPU mesh runs the
real GSPMD partitioner + buffer assignment for the per-device program, so
XLA's own accounting of per-chip argument/temp bytes is available ahead of
time (ref shape: the reference records per-run memory/assert artifacts for
its Alpa release tests, release/alpa_tests/train_opt_2_7b_minimum.py:315).

Writes `MEM_8B_r5.json`: for each candidate mesh, XLA-reported per-device
bytes (arguments = resident state shards, temp = activation/workspace
high-water mark) next to the analytic state-shard size, and whether the
layout fits a v5p chip's 95.7 GB HBM.

Like the dryrun, the parent NEVER touches the accelerator backend: it
re-execs itself onto a 64-device CPU mesh, which has to be asked for
before the child's interpreter starts JAX.
"""

import json
import os
import subprocess
import sys

_CHILD_ENV = "_RAY_TPU_MEM8B_CHILD"
_N_DEVICES = 64
_V5P_HBM = 95.7e9  # bytes per chip (public spec: 95 GiB HBM2e)

# Candidate v5p-64 layouts for the 8B north star. Global batch 64,
# seq 4096 => 256k tokens/step; remat everything (the MFU recipe trades
# recompute for activation memory).
MESHES = [
    {"name": "fsdp64", "spec": dict(fsdp=64)},
    {"name": "fsdp16_tensor4", "spec": dict(fsdp=16, tensor=4)},
    {"name": "data4_fsdp16", "spec": dict(data=4, fsdp=16)},
]
BATCH, SEQ = 64, 4096


def _child() -> None:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama3_8b_config, make_optimizer
    from ray_tpu.models.training import (
        batch_sharding,
        make_init_fn,
        make_train_step,
        state_shardings,
    )
    from ray_tpu.parallel import MeshSpec

    assert len(jax.devices()) == _N_DEVICES, jax.devices()
    cfg = llama3_8b_config(max_seq_len=SEQ, param_dtype=jnp.bfloat16,
                           remat=True, remat_policy="nothing")
    tx = make_optimizer(3e-4, mu_dtype=jnp.bfloat16)
    state_shapes = jax.eval_shape(make_init_fn(cfg, tx), jax.random.key(0))
    batch_shapes = {
        "inputs": jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32),
        "targets": jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32),
    }
    # analytic bytes of the full (unsharded) train state
    state_bytes = sum(s.size * s.dtype.itemsize
                     for s in jax.tree.leaves(state_shapes))

    out = {
        "benchmark": "llama3_8b_v5p64_memory_analysis",
        "model": "llama3-8b",
        "params_b": round(cfg.num_params / 1e9, 3),
        "n_devices": _N_DEVICES,
        "global_batch": BATCH,
        "seq_len": SEQ,
        "remat": "full",
        "state_dtypes": "bf16 params, bf16 adam mu, fp32 nu",
        "state_total_gb": round(state_bytes / 1e9, 2),
        "hbm_per_chip_gb": round(_V5P_HBM / 1e9, 1),
        "meshes": [],
    }
    for cand in MESHES:
        mesh = MeshSpec(**cand["spec"]).build(jax.devices())
        step = make_train_step(cfg, tx, mesh)
        shardings = state_shardings(cfg, tx, mesh)
        sharded_state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            state_shapes, shardings)
        bsh = batch_sharding(mesh)
        sharded_batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                                 sharding=bsh)
                         for k, v in batch_shapes.items()}
        compiled = step.lower(sharded_state, sharded_batch).compile()
        ma = compiled.memory_analysis()
        # per-device shard of the resident state (arguments alias outputs
        # via donation, so "arguments" is the steady-state residency)
        entry = {
            "mesh": cand["name"],
            "axes": {k: v for k, v in cand["spec"].items()},
        }
        if ma is not None:
            arg = getattr(ma, "argument_size_in_bytes", 0)
            tmp = getattr(ma, "temp_size_in_bytes", 0)
            outb = getattr(ma, "output_size_in_bytes", 0)
            alias = getattr(ma, "alias_size_in_bytes", 0)
            peak = arg + tmp + outb - alias
            entry.update({
                "xla_argument_gb": round(arg / 1e9, 2),
                "xla_temp_gb": round(tmp / 1e9, 2),
                "xla_output_gb": round(outb / 1e9, 2),
                "xla_aliased_gb": round(alias / 1e9, 2),
                "xla_peak_per_device_gb": round(peak / 1e9, 2),
                "fits_v5p_95gb": bool(peak < _V5P_HBM),
                "hbm_utilization": round(peak / _V5P_HBM, 3),
            })
        # analytic cross-check: state shard + token batch shard
        shard_bytes = 0
        for s, sh in zip(jax.tree.leaves(state_shapes),
                         jax.tree.leaves(shardings)):
            n = 1
            for d in sh.spec:
                if d is not None:
                    ax = (d,) if isinstance(d, str) else d
                    for a in ax:
                        n *= mesh.shape[a]
            shard_bytes += s.size * s.dtype.itemsize // max(n, 1)
        entry["analytic_state_shard_gb"] = round(shard_bytes / 1e9, 2)
        out["meshes"].append(entry)
        print(f"# {cand['name']}: {entry}", file=sys.stderr)
    json.dump(out, open("MEM_8B_r5.json", "w"), indent=1)
    print(json.dumps(out))


def main() -> None:
    if os.environ.get(_CHILD_ENV) == "1":
        _child()
        return
    env = dict(os.environ)
    env[_CHILD_ENV] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={_N_DEVICES}")
    env["XLA_FLAGS"] = " ".join(flags)
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, cwd=here, timeout=2400)
    if proc.returncode != 0:
        raise SystemExit(f"mem_8b child failed rc={proc.returncode}")


if __name__ == "__main__":
    main()
