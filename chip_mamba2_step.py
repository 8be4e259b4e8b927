"""Time `ops/mamba2.py:mamba2_decode_step` alone on one chip at the Granite
cell's sizes (64 slots x [128, 4096] float32 a layer; 12 layers of the
cell's 36, so that the stack, its `jax.numpy` twin and a copy fit beside one
another), against its memory roofline, for a list of channel blocks, and
hold the kernel to its `jax.numpy` form there.

    python chip_mamba2_step.py [--blocks 1024 2048 4096]   # on a TPU

One JSON line a block: microseconds a call (one layer, every slot), GB/s of
state moved (read + written), share of `benchmark/peaks.json`'s bandwidth."""
import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", type=int, nargs="+",
                    default=[1024, 2048, 4096])
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--slots", type=int, default=64)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.harness import spec
    from ray_tpu.ops import mamba2

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "no TPU: " + dev.platform}))
        return 1
    peak = spec.device_peaks(dev.device_kind)["hbm_bytes_per_s"]
    L, S, N, H, P = args.layers, args.slots, 128, 64, 64
    k = jax.random.split(jax.random.key(0), 6)
    state = jax.random.normal(k[0], (L, S, N, H * P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (S, H)) - 3.0)
    x = jax.random.normal(k[2], (S, H, P), jnp.bfloat16)
    Bm, Cm = (jax.random.normal(k[i], (S, N)) for i in (3, 4))
    A = -jnp.arange(1, H + 1, dtype=jnp.float32)
    active = jnp.ones((S,), bool).at[3].set(False)
    token = (dt, x, Bm, Cm, A, active)

    def every_layer(kernel):
        def run(state, *token):
            def layer(i, c):
                s, y = c
                s, o = mamba2.mamba2_decode_step(s, i, *token, kernel=kernel)
                return s, y + o
            return jax.lax.fori_loop(
                0, L, layer, (state, jnp.zeros((S, H, P), jnp.float32)))
        return jax.jit(run, donate_argnums=0)

    want_s, want_y = every_layer(False)(jnp.copy(state), *token)
    kept_want = want_s[:, 3]
    want_s = want_s[:, :3]      # (three slots of 64 are held to the twin)
    ok = True
    for cb in args.blocks:
        mamba2._CHANNEL_BLOCK = cb
        run = every_layer(True)
        s, y = run(jnp.copy(state), *token)
        err_s = float(jnp.max(jnp.abs(s[:, :3] - want_s)))
        # (an inactive slot's y is junk in either form)
        err_y = float(jnp.max(jnp.abs((y - want_y)[active])))
        kept = bool(jnp.all(s[:, 3] == state[:, 3])
                    & jnp.all(kept_want == state[:, 3]))
        jax.block_until_ready(s)
        reps, t0 = 5, time.perf_counter()
        for _ in range(reps):
            s, y = run(s, *token)
        jax.block_until_ready(s)
        call_s = (time.perf_counter() - t0) / reps / L
        moved = 2 * 4 * S * N * H * P
        good = err_s < 1e-4 and err_y < 1e-2 and kept
        ok = ok and good
        print(json.dumps({
            "channel_block": cb, "us_a_layer_call": 1e6 * call_s,
            "gb_per_s": moved / call_s / 1e9,
            "roofline_share": moved / call_s / peak, "max_err_state": err_s,
            "max_err_y": err_y, "inactive_slot_kept": kept, "ok": good,
            "device_kind": dev.device_kind}), flush=True)
    print(json.dumps({"ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
