"""OLMoE's expert layer alone, on the chip, at the published widths: the
program's ``models.moe.moe_layer`` in bf16 against the reference's loop
over all 64 experts (``benchmark/architectures/olmoe.py::moe_ffn_reference``,
float32 at the highest matmul precision) on the same bf16 input rows.

    python chip_olmoe_expert_layer.py [--seeds 1 2 3]   # on a TPU

Why it exists beside the benchmark's own check: at initialisation the
expert branch is a small part of the logits, so the cell's bound on logits
(0.08) would let a tenth of that branch be wrong. This holds the layer
itself, at 4 x 4096 rows of width 2048, 64 experts of width 1024, 8 a token.

Three checks a seed, and the exit code is 0 only when all hold for all:

- ``rel_rms_error`` < TOL = 2e-2. bf16 rounds to 2^-9 = 0.002 relative; the
  layer rounds its rows, gate, up, their product and the down projection, and
  sums 8 experts in float32: a few roundings, about 0.004 measured. 2e-2 is
  five times that and seven times under what the next precision down gives.
- ``rel_rms_error_inputs_rounded_to_fp8`` > TOL: the same layer with rows and
  expert weights rounded to float8_e4m3 on the way in (the nearest precision
  below the configuration's bf16) has to come out as NOT within the tolerance,
  or the tolerance holds nothing.
- ``tokens_whose_experts_differ`` == 0: the router is float32 at the highest
  precision on both sides, so a token's 8 experts differ only on a tie.

It is no benchmark: the seconds it prints are information only. Off the TPU
it refuses, unless ``--toy`` asks for a few rows at the published widths'
shapes cut down (what ``tests/test_olmoe_reference.py`` runs on the CPU).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

TOL = 2e-2
TOY = dict(d_model=64, d_ff=32, moe_experts=8, moe_top_k=2)


def compare(seed: int, conf: dict, shape=(4, 4096), **overrides) -> dict:
    """One seed's readings. ``overrides`` replace fields of the
    TransformerConfig built from ``conf`` (a toy size for the CPU)."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import spec
    from ray_tpu.models.moe import init_moe_params, moe_layer, route

    arch = spec.load_architecture(conf)
    fields = dict(spec.transformer_fields(conf), **overrides)
    cfg = spec.build_transformer_config(
        conf, param_dtype="bfloat16", max_seq_len=shape[1], n_layers=1,
        **overrides)
    d = cfg.d_model
    kp, kh, kg = jax.random.split(jax.random.key(spec.seed32(seed)), 3)
    lp = jax.tree.map(lambda a: a[0],
                      jax.jit(lambda k: init_moe_params(k, cfg))(kp))
    # rows as the layer meets them: unit RMS times a gain near one, in bf16
    gain = 1.0 + 0.1 * jax.random.normal(kg, (d,))
    h = (jax.random.normal(kh, shape + (d,)) * gain).astype(jnp.bfloat16)
    layer = jax.jit(lambda h, lp: moe_layer(h, lp, cfg))

    def rel_rms(got, want):
        return float(jnp.sqrt(jnp.mean((got - want) ** 2)
                              / jnp.mean(want ** 2)))

    t0 = time.perf_counter()
    got, stats = layer(h, lp)
    got = jax.block_until_ready(got).reshape(-1, d).astype(jnp.float32)
    _, _, top_i = jax.jit(lambda x, r: route(x, r, cfg))(
        h.reshape(-1, d), lp["router"])
    t1 = time.perf_counter()
    want, aux, keep = arch.moe_ffn_reference(
        h.reshape(-1, d).astype(jnp.float32), lp, fields, conf)
    want = jax.block_until_ready(want)
    t2 = time.perf_counter()
    mine = jnp.zeros_like(keep).at[
        jnp.arange(keep.shape[0])[:, None], top_i].set(True)
    per_token = jnp.sqrt(jnp.mean((got - want) ** 2, axis=-1)
                         / jnp.mean(want ** 2))

    def f8(a):
        return a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)

    lp8 = dict(lp, **{k: f8(lp[k]) for k in ("w_gate", "w_up", "w_down")})
    got8 = layer(f8(h), lp8)[0].reshape(-1, d).astype(jnp.float32)
    dev = jax.devices()[0]
    return {
        "seed": seed, "platform": dev.platform, "device_kind": dev.device_kind,
        "rows": int(got.shape[0]), "d_model": d, "d_ff": cfg.d_ff,
        "experts": cfg.moe_experts, "top_k": cfg.moe_top_k,
        "tolerance": TOL,
        "rel_rms_error": rel_rms(got, want),
        "rel_rms_error_inputs_rounded_to_fp8": rel_rms(got8, want),
        "worst_token_rel_error": float(per_token.max()),
        "tokens_whose_experts_differ": int(
            jnp.sum(jnp.any(mine != keep, axis=-1))),
        "aux_program": float(stats["aux"]), "aux_reference": float(aux),
        "load_max_over_mean": float(stats["load"]),
        "largest_group": int(keep.sum(0).max()),
        "smallest_group": int(keep.sum(0).min()),
        "program_s": t1 - t0, "reference_s": t2 - t1,
    }


def holds(r: dict) -> dict:
    """The three checks on one seed's readings."""
    return {
        "within_tolerance": r["rel_rms_error"] < TOL,
        "fp8_is_not_within_tolerance":
            r["rel_rms_error_inputs_rounded_to_fp8"] > TOL,
        "no_token_routed_differently": r["tokens_whose_experts_differ"] == 0,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[2700000001])
    ap.add_argument("--toy", action="store_true",
                    help="toy widths, 32 rows, any platform")
    args = ap.parse_args(argv)

    import jax

    from benchmark.harness import spec

    if jax.devices()[0].platform != "tpu" and not args.toy:
        print(json.dumps({"ok": False, "error": "no TPU: "
                          + jax.devices()[0].platform}))
        return 1
    conf = spec.load_config(spec.load_benchmark(), "olmoe-1b-7b")
    ok = True
    for seed in args.seeds:
        r = compare(seed, conf, **(dict(shape=(1, 32), **TOY)
                                   if args.toy else {}))
        r["checks"] = holds(r)
        ok = ok and all(r["checks"].values())
        print(json.dumps(r), flush=True)
    print(json.dumps({"ok": ok, "seeds": len(args.seeds)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
