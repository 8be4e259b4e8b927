"""An expert layer alone, on the chip, at the published widths, in bf16
against the configuration's plain reference in float32 at the highest
matmul precision, on the same bf16 input rows.

    python chip_expert_layer.py [--config NAME ...] [--seeds 1 2 3]  # on a TPU

Why it exists beside the benchmark's own check: at initialisation the
expert branch is a small part of the logits, so a cell's bound on logits
(0.08) would let a tenth of that branch be wrong; the check compares a
forward pass only; and random weights route near balance, so a cell never
sees a share's corner cases. Tolerance TOL = 2e-2 throughout: bf16 rounds to
2^-9 = 0.002 relative; the layer rounds its rows, gate, up, their product
and the down projection, and sums the kept experts in float32: a few
roundings, about 0.004 measured. 2e-2 is five times that and seven times
under what the next precision down gives.

``olmoe-1b-7b`` (`compare`, `holds`): the whole layer, `models.moe.moe_layer`
against the loop over all 64 experts
(`benchmark/architectures/olmoe.py::moe_ffn_reference`), 4 x 4096 rows of
width 2048, 64 experts of width 1024, 8 a token. Three checks a seed:

- ``rel_rms_error`` < TOL.
- ``rel_rms_error_inputs_rounded_to_fp8`` > TOL: the same layer with rows and
  expert weights rounded to float8_e4m3 on the way in (the nearest precision
  below the configuration's bf16) has to come out as NOT within the tolerance,
  or the tolerance holds nothing.
- ``tokens_whose_experts_differ`` == 0: the router is float32 at the highest
  precision on both sides, so a token's 8 experts differ only on a tie.

``glm-4.7-flash`` (`kernel`, `share`, `holds_share`): the two pieces that
configuration brought.

- ``kernel``: `ops.flash_attention` at [8, 4096, 20, 256] bf16 causal (160
  (row, head) pairs, q, k and v all 256 wide: 192 unrotated + 64 rotary,
  value heads 256), output and dq, dk, dv against `reference_attention` in
  float32 a row at a time, each under 1e-2 as `chip_smoke.kernel_phase`
  holds them; forward and forward + backward timed, with the share of the
  compute roofline `harness/flops.py` gives; a few named blocks beside the
  picked one, for information (`_pick_block` knows nothing of the width).
- ``share``: `moe_layer` holding experts 0-7 of 64 (router over all 64,
  sigmoid, top 4 by score + bias, shared expert) against the reference's
  loop over the held experts
  (`architectures/glm4_moe_lite.py::expert_ffn_reference`), output AND
  gradients (rows, held experts, shared expert, router; one cotangent), on
  1 x 4096 rows, with the selection bias steering the routing into the
  corners: ``natural`` (zeros), ``one_held`` (every token picks held expert
  0 and three absent ones: one group of N rows), ``none_held`` (four absent
  ones: no row in any group, the routed part and its gradients are exactly
  zero), ``all_held`` (four held ones: all N*k rows of the buffer live:
  the one case that overflows the sorted buffer's front, ``compact_path``
  0.0; ``one_held`` fills the one-row front exactly);
  `natural` with rows and expert weights rounded to float8_e4m3 has to come
  out NOT within the tolerance. Then forward + backward of the layer at the
  cell's 8 x 4096 rows is timed in each case: what `ragged_dot` charges for
  the rows past the sum of the group sizes is the difference between
  ``none_held`` and ``all_held``.

``--rows`` (`rows`, `holds_rows`): the grouped matmul of a row buffer one
row tile long (`ops.grouped_matmul`, what a decode substep's expert stage
runs) alone against `jax.lax.ragged_dot`, at the Solar cell's widths and
held count, 512 x 4096 over 40 experts of 4096 x 1280 and the way back, on
rows as decode has them (128 live over 31-32 touched groups) and as a small
prefill group has them (512 live, 13 a group): the largest difference (one
bf16 rounding of the largest output at most), milliseconds a call of each
over a stream of calls, and the GB/s and share of the memory roofline the
TOUCHED groups' weights cross at.

The exit code is 0 only when every check holds for every seed. It is no
benchmark: the seconds are information only. Off the TPU it refuses, unless
``--toy`` asks for toy widths and a few rows (what
tests/test_olmoe_reference.py and tests/test_glm4_moe_lite_reference.py run
on the CPU).
"""

from __future__ import annotations

import argparse
import json
import statistics
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
        "rel_rms_error": _rel_rms(got, want),
        "rel_rms_error_inputs_rounded_to_fp8": _rel_rms(got8, want),
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


KERNEL_TOL = 1e-2
SHARE_TOY = dict(d_model=64, d_ff=32, moe_shared_d_ff=32, moe_experts=16,
           moe_held_experts=4, moe_first_expert=0, moe_top_k=2)
CASES = ("natural", "one_held", "none_held", "all_held")


def _timed(fn, *args, repeats=5):
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _rel_rms(got, want):
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    scale = float(jnp.mean(want ** 2))
    if scale == 0.0:    # an exact zero has to be met exactly
        return float(jnp.max(jnp.abs(got)))
    return float(jnp.sqrt(jnp.mean((got - want) ** 2) / scale))


def kernel(seed: int, shape=(8, 4096, 20, 256), blocks=()) -> dict:
    import functools

    import jax
    import jax.numpy as jnp

    from benchmark.harness import flops, spec
    from ray_tpu.ops import flash_attention
    from ray_tpu.parallel import reference_attention

    B, T, H, D = shape
    keys = jax.random.split(jax.random.key(spec.seed32(seed)), 4)
    q, k, v, do = (jax.random.normal(key, shape, jnp.float32).astype(
        jnp.bfloat16) for key in keys)

    def with_gradients(attn):
        def run(q, k, v, do):
            o, vjp = jax.vjp(functools.partial(attn, causal=True), q, k, v)
            return (o,) + vjp(do)
        return jax.jit(run)

    reference = with_gradients(reference_attention)
    rows = [reference(*(a[b:b + 1].astype(jnp.float32)
                        for a in (q, k, v, do))) for b in range(B)]
    want = [jnp.concatenate(parts) for parts in zip(*rows)]
    got = with_gradients(flash_attention)(q, k, v, do)
    out = {"shape": list(shape), "errors": {
        name: _rel_rms(g, w) for name, g, w in zip(
            ("o", "dq", "dk", "dv"), got, want)}}
    peak = None
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        peak = spec.device_peaks(dev.device_kind)
    for label, kw in [("picked", {})] + [
            (f"{bq}x{bk}", dict(block_q=bq, block_k=bk))
            for bq, bk in blocks]:
        attn = functools.partial(flash_attention, **kw)
        fwd = jax.jit(functools.partial(attn, causal=True))
        t_f = _timed(fwd, q, k, v)
        t_fb = _timed(with_gradients(attn), q, k, v, do)
        line = {"forward_ms": 1e3 * t_f, "forward_backward_ms": 1e3 * t_fb}
        if peak:
            need = [flops.flash_attention_cost(B, H, T, T, D, causal=True,
                                               backward=b) for b in (0, 1)]
            line["forward_roofline_pct"] = 100 * flops.roofline_seconds(
                need[0]["flops"], need[0]["bytes"], peak)["seconds"] / t_f
            line["forward_backward_roofline_pct"] = \
                100 * flops.roofline_seconds(
                    need[0]["flops"] + need[1]["flops"],
                    need[0]["bytes"] + need[1]["bytes"],
                    peak)["seconds"] / t_fb
        out[label] = line
    return out


def _bias(case: str, cfg):
    """The selection bias that steers every token's top k: +10 (a sigmoid's
    score is under 1) on the experts the case names."""
    import jax.numpy as jnp

    k, first, held = cfg.moe_top_k, cfg.moe_first_expert, cfg.held_experts
    absent = [e for e in range(cfg.moe_experts)
              if not first <= e < first + held]
    pick = {"natural": [], "one_held": [first] + absent[:k - 1],
            "none_held": absent[:k],
            "all_held": list(range(first, first + k))}[case]
    return jnp.zeros((cfg.moe_experts,), jnp.float32).at[
        jnp.asarray(pick, jnp.int32)].set(10.0)


def share(seed: int, conf: dict, rows=(1, 4096), timed_rows=(8, 4096),
          **overrides) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark.harness import spec
    from ray_tpu.models.moe import init_moe_params, moe_layer

    arch = spec.load_architecture(conf)
    fields = dict(spec.transformer_fields(conf), **overrides)
    cfg = spec.build_transformer_config(
        conf, param_dtype="bfloat16", max_seq_len=rows[1], **overrides)
    d = cfg.d_model
    kp, kh, kg, kc = jax.random.split(jax.random.key(spec.seed32(seed)), 4)
    lp0 = jax.tree.map(lambda a: a[0], jax.jit(
        lambda k: init_moe_params(k, cfg, 1))(kp))
    gain = 1.0 + 0.1 * jax.random.normal(kg, (d,))

    def inputs(shape):
        return ((jax.random.normal(kh, shape + (d,)) * gain).astype(
            jnp.bfloat16), jax.random.normal(kc, shape + (d,)))

    def program(h, lp, cot):
        def f(h, lp):
            y, stats = moe_layer(h, lp, cfg)
            return jnp.sum(y.astype(jnp.float32) * cot), (y, stats)
        (_, (y, stats)), grads = jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True)(h, lp)
        return y, stats, grads

    def reference(h, lp, cot):
        def f(h, lp):
            y = arch.expert_ffn_reference(h.reshape(-1, d), lp, fields,
                                          conf)
            return jnp.sum(y * cot.reshape(-1, d)), y
        (_, y), grads = jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True)(h, lp)
        return y, grads

    program = jax.jit(program)
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    h, cot = inputs(rows)
    h_t, cot_t = inputs(timed_rows)
    out = {"rows": list(rows), "timed_rows": list(timed_rows), "cases": {}}
    for case in CASES:
        lp = dict(lp0, router_bias=_bias(case, cfg).astype(jnp.bfloat16))
        y, stats, (dh, dlp) = program(h, lp, cot)
        want_y, (want_dh, want_dlp) = reference(f32(h), f32(lp), cot)
        errors = {"y": _rel_rms(y.reshape(-1, d), want_y),
                  "d_rows": _rel_rms(dh, want_dh)}
        errors.update({f"d_{k}": _rel_rms(dlp[k], want_dlp[k])
                       for k in sorted(dlp) if k != "router_bias"})
        line = {"held_share": float(stats["held"]),
                "load_max_over_mean": float(stats["load"]),
                "compact_path": float(stats["compact"]),
                "errors": errors,
                "bias_gradient_is_zero": not bool(
                    jnp.any(dlp["router_bias"])),
                "layer_forward_backward_ms": 1e3 * _timed(
                    program, h_t, lp, cot_t)}
        if case == "natural":
            f8 = lambda a: a.astype(  # noqa: E731
                jnp.float8_e4m3fn).astype(jnp.bfloat16)
            lp8 = dict(lp, **{k: f8(lp[k]) for k in lp
                              if k.startswith("w")})
            line["y_error_inputs_rounded_to_fp8"] = _rel_rms(
                program(f8(h), lp8, cot)[0].reshape(-1, d), want_y)
        out["cases"][case] = line
    return out


def holds_share(kernel_out: dict, share_out: dict) -> dict:
    cases = share_out["cases"]
    checks = {f"kernel:{k}": v <= KERNEL_TOL
              for k, v in kernel_out["errors"].items()}
    for case, line in cases.items():
        checks[f"{case}:within_tolerance"] = all(
            v < TOL for v in line["errors"].values())
        checks[f"{case}:bias_takes_no_gradient"] = \
            line["bias_gradient_is_zero"]
    checks["fp8_is_not_within_tolerance"] = \
        cases["natural"]["y_error_inputs_rounded_to_fp8"] > TOL
    k = share_out["top_k"]
    checks["corners_are_what_they_say"] = (
        cases["none_held"]["held_share"] == 0.0
        and cases["all_held"]["held_share"] == 1.0
        and abs(cases["one_held"]["held_share"] - 1 / k) < 1e-6)
    return checks


ROWS_CASES = {"decode": (128, 32), "prefill64": (512, 40)}


def _group_sizes(rng, groups, live, touched):
    """``live`` rows over ``touched`` of ``groups`` groups, each touched
    one holding a row at least, the untouched ones anywhere between."""
    import numpy as np

    sizes = np.zeros(groups, np.int64)
    on = rng.choice(groups, size=touched, replace=False)
    sizes[on] = 1 + rng.multinomial(live - touched,
                                    np.full(touched, 1 / touched))
    return sizes


def _stream_ms(fn, *args, calls=50):
    """Milliseconds a call with ``calls`` of them in flight: the device
    runs them back to back, so the host's dispatch is not in the number."""
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(calls)]
        jax.block_until_ready(outs)
        times.append((time.perf_counter() - t0) / calls)
    return 1e3 * statistics.median(times)


def rows(seed: int, conf: dict, n_rows=512, cases=None,
         **overrides) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import spec
    from ray_tpu.ops.grouped_matmul import grouped_matmul_rows

    cfg = spec.build_transformer_config(conf, **overrides)
    d, f, G = cfg.d_model, cfg.d_ff, cfg.held_experts
    rng = np.random.default_rng(spec.seed32(seed))
    keys = jax.random.split(jax.random.key(spec.seed32(seed)), 4)
    dev = jax.devices()[0]
    peak = spec.device_peaks(dev.device_kind) if dev.platform == "tpu" \
        else None
    ragged = jax.jit(jax.lax.ragged_dot)
    kernel = jax.jit(grouped_matmul_rows)
    out = {"rows": n_rows, "groups": G, "cases": {}}
    for way, (d_in, d_out, kx, kw) in {"in": (d, f, *keys[:2]),
                                       "back": (f, d, *keys[2:])}.items():
        w = (jax.random.normal(kw, (G, d_in, d_out)) * d_in ** -0.5).astype(
            jnp.bfloat16)
        for case, (live, touched) in (cases or ROWS_CASES).items():
            sizes = _group_sizes(rng, G, live, touched)
            in_group = (jnp.arange(n_rows) < live)[:, None]
            xs = jnp.where(in_group, jax.random.normal(
                kx, (n_rows, d_in)), 0).astype(jnp.bfloat16)
            gs = jnp.asarray(sizes, jnp.int32)
            want = jnp.where(in_group, ragged(xs, w, gs), 0).astype(
                jnp.float32)
            got = kernel(xs, w, gs).astype(jnp.float32)
            line = {"live": live, "touched": touched,
                    "largest_group": int(sizes.max()),
                    "largest_difference": float(jnp.abs(got - want).max()),
                    "largest_output": float(jnp.abs(want).max()),
                    "rows_that_differ": int(jnp.sum(jnp.any(got != want,
                                                            axis=1)))}
            need = 2 * touched * d_in * d_out
            for name, fn in (("ragged_dot", ragged), ("kernel", kernel)):
                ms = _stream_ms(fn, xs, w, gs)
                line[name + "_ms"] = ms
                line[name + "_gb_per_s"] = need / ms / 1e6
                if peak:
                    line[name + "_roofline_pct"] = \
                        100 * need / peak["hbm_bytes_per_s"] / (ms / 1e3)
            out["cases"][f"{way}:{case}"] = line
    return out


def holds_rows(r: dict) -> dict:
    """Within one rounding of `ragged_dot`: bf16 keeps 8 bits, so two
    roundings of one float32 sum differ by 2^-8 of it at most."""
    return {f"{name}:one_rounding_apart":
            line["largest_difference"] <= 2 ** -8 * line["largest_output"]
            for name, line in r["cases"].items()}


def _rows_alone(seed, conf, toy):
    toy = dict(n_rows=32, cases={"decode": (8, 5), "prefill64": (32, 8)},
               d_model=128, d_ff=128, moe_experts=16, moe_held_experts=8,
               moe_first_expert=0, moe_top_k=2) if toy else {}
    r = rows(seed, conf, **toy)
    return r, holds_rows(r)


def _whole_layer(seed, conf, toy):
    r = compare(seed, conf, **(dict(shape=(1, 32), **TOY) if toy else {}))
    return r, holds(r)


def _share_and_kernel(seed, conf, toy):
    if toy:
        k = kernel(seed, shape=(2, 64, 2, 16))
        s = share(seed, conf, rows=(1, 32), timed_rows=(2, 32), **SHARE_TOY)
    else:
        k = kernel(seed, blocks=((256, 512), (512, 256), (1024, 512),
                                 (512, 1024), (256, 256)))
        s = share(seed, conf)
    s["top_k"] = SHARE_TOY["moe_top_k"] if toy \
        else conf["num_experts_per_tok"]
    return {"kernel": k, "share": s}, holds_share(k, s)


RUNS = {"olmoe-1b-7b": _whole_layer, "glm-4.7-flash": _share_and_kernel}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", nargs="+", choices=sorted(RUNS),
                    default=sorted(RUNS))
    ap.add_argument("--rows", action="store_true",
                    help="the short row buffer's grouped matmul alone, at "
                    "solar-open2-250b's widths, and nothing else")
    ap.add_argument("--seeds", type=int, nargs="+", default=[3200000001])
    ap.add_argument("--toy", action="store_true",
                    help="toy widths and rows, any platform")
    args = ap.parse_args(argv)

    import jax

    from benchmark.harness import spec

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.toy:
        print(json.dumps({"ok": False, "error": "no TPU: " + dev.platform}))
        return 1
    ok = True
    runs = {"solar-open2-250b": _rows_alone} if args.rows else RUNS
    names = list(runs) if args.rows else args.config
    for name in names:
        conf = spec.load_config(spec.load_benchmark(), name)
        for seed in args.seeds:
            r, checks = runs[name](seed, conf, args.toy)
            ok = ok and all(checks.values())
            print(json.dumps(dict(r, config=name, seed=seed,
                                  platform=dev.platform,
                                  device_kind=dev.device_kind,
                                  checks=checks)), flush=True)
    print(json.dumps({"ok": ok, "configs": names,
                      "seeds": len(args.seeds)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
