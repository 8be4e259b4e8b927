"""What a serve cell's `correct` can tell, on the chip, at the cell's own
sizes: the cell's replica as the benchmark binds it (`BenchReplica`, the
traffic file's slots and lengths), held to the configuration's plain
reference by the harness's own comparison (`bench_check`: prefill, one
decode step through the slot cache, the served chunk's tokens; limit
`reference.LOGIT_REL_RMS_TOL`), once as the program is and once under each
control: the program, or the weights it holds, changed to what the
configuration does NOT state.

    python chip_serve_controls.py [--workload CELL] [--seeds N ...]  # on a TPU

One JSON line a (seed, control): the worst prompt's prefill and decode
error beside the limit, `ok` as the cell's `correct` would have it, and
`expect`:

- ``program`` (expect agree): nothing changed.
- ``fp8_weights`` (expect differ): every bf16 matrix the replica holds
  rounded to float8_e4m3's bits under a scale a matrix, the nearest
  precision below the bf16 the configuration states (`chip_expert_layer.py`
  holds an expert layer's tolerance to the same control). If this agrees
  the limit holds nothing.
- ``write_strength_halved`` (expect differ): `kda_allow_neg_eigval` off in
  the served programs, b in (0, 1): a rule, not a precision.
- ``state_bf16`` (recorded): a slot's KDA state rounded to bfloat16 wherever
  the cache holds it, after prefill and after every decode step, as a bf16
  cache leaf would (the configuration's `assumed.state_dtype` says float32).
- ``init_depth_none`` (recorded; a replica of its own, drawn again): the
  residual outputs initialised by the depth that is run and not the
  published one (`TransformerConfig.init_depth`); the reference draws by
  the same rule, so this reads what the field is worth to the comparison.

and for a cell of differential attention behind windows
(``--workload phi-4-mini-flash-reasoning.reason-closed-64``), beside
``program`` and ``fp8_weights``:

- ``no_window`` (expect differ): a window layer attends to every earlier
  position of its row in prefill, and a decode step reads the ring's oldest
  place too.
- ``lam_fixed`` (expect differ): lam = lam0(layer), the four learned vectors
  left out.

and for a cell of Mamba-2 layers beside NoPE GQA layers under four fixed
multipliers (``--workload granite-4.0-h-micro.reason-closed-64``), beside
``program`` and ``fp8_weights``, five rules, each RECORDED (four attention
layers of forty behind a 0.22 may hide a scale: a wrong rule that agrees
is a finding about the check):

- ``attn_scale_sqrt``: the softmax scaled by 64 ** -0.5, not by the
  published `attention_multiplier` 1/64.
- ``residual_one``: `residual_multiplier` 1.0.
- ``no_decay``: A = 0, a state that forgets nothing.
- ``norm_before_gate``: RMSNorm(y) . silu(z) in place of RMSNorm(y . silu(z)).
- ``mamba2_state_bf16``: a slot's Mamba-2 state rounded to bfloat16 after
  prefill and after every decode step.

A recorded control's reading IS the result: `ok` true there says that this
comparison cannot tell that precision from the stated one (PERF.md
section 6, PR 42, has the readings and what follows from them). The exit
code is 0 when the program agrees and every control that must differ
does. Off the TPU it refuses, unless ``--toy`` asks for toy widths (what
`tests/test_solar_open2_reference.py` runs: the script's plumbing, not its
verdicts).
"""

import argparse
import contextlib
import dataclasses
import functools
import json
import sys

TOY = dict(vocab_size=96, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
           head_dim=8, d_ff=24, kda_heads=4, kda_head_dim=8, kda_gate_rank=6,
           moe_experts=16, moe_held_experts=4, moe_first_expert=4,
           moe_top_k=4, moe_shared_d_ff=24, attention_impl="xla",
           max_seq_len=256)
TOY_DEPLOYMENT = dict(slots=4, max_prompt_len=64, max_new_tokens=8)
TOY_LENGTHS = [20, 51, 7, 64]
EXPECT = {"program": "agree", "fp8_weights": "differ",
          "write_strength_halved": "differ", "state_bf16": None,
          "init_depth_none": None, "no_window": "differ",
          "lam_fixed": "differ", "attn_scale_sqrt": None,
          "residual_one": None, "no_decay": None, "norm_before_gate": None,
          "mamba2_state_bf16": None}
# the controls of an architecture, and its toy: all of the pattern's layers
# at toy widths, a window the toy prompts outgrow
CONTROLS = {
    "solar_open2": ["program", "fp8_weights", "write_strength_halved",
                    "state_bf16", "init_depth_none"],
    "phi4flash": ["program", "fp8_weights", "no_window", "lam_fixed"],
    "granitemoehybrid": ["program", "fp8_weights", "attn_scale_sqrt",
                         "residual_one", "no_decay", "norm_before_gate",
                         "mamba2_state_bf16"]}
TOYS = {"solar_open2": TOY,
        "phi4flash": dict(vocab_size=96, d_model=32, n_heads=4, n_kv_heads=2,
                          head_dim=8, d_ff=48, mamba_d_state=4,
                          mamba_dt_rank=3, sliding_window=8),
        "granitemoehybrid": dict(vocab_size=96, d_model=32, n_heads=4,
                                 n_kv_heads=2, head_dim=8, d_ff=48,
                                 mamba_heads=8, mamba_head_dim=8,
                                 mamba_d_state=16, mamba_chunk=8)}
# a control that is the configuration with ONE field changed
FIELD_CONTROLS = {"write_strength_halved": {"kda_allow_neg_eigval": False},
                  "attn_scale_sqrt": {"attn_scale": None},
                  "residual_one": {"residual_scale": 1.0}}


@contextlib.contextmanager
def _patched(*changes):
    """``changes``: (module, name, value). The served programs are traced
    anew inside and after."""
    import jax

    old = [(mod, name, getattr(mod, name)) for mod, name, _ in changes]
    for mod, name, value in changes:
        setattr(mod, name, value)
    jax.clear_caches()
    try:
        yield
    finally:
        for mod, name, value in old:
            setattr(mod, name, value)
        jax.clear_caches()


def _round(x, exponent_bits: int, mantissa_bits: int):
    """``x`` at fewer bits, by an operation of its own: a convert there and
    back is excess precision the compiler may take out (it does, on the
    TPU)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits, mantissa_bits)


def _fp8_in_place(eng):
    """Every bf16 matrix of the engine's weights through float8_e4m3 (4
    exponent bits, 3 of mantissa) under a scale of its own that puts its
    largest entry at 240, as an fp8 deployment holds a matrix: old buffer
    donated, one leaf live at a time (the tree does not fit beside a copy
    of itself)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0)
    def through(x):
        x32 = x.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x32)) / 240.0
        scale = jnp.where(scale > 0, scale, 1.0)    # a leaf of zeros
        return (_round(x32 / scale, 4, 3) * scale).astype(x.dtype)
    leaves, tree = jax.tree.flatten(eng.params)
    eng.params = None
    for i in range(len(leaves)):
        if leaves[i].ndim >= 2 and leaves[i].dtype == jnp.bfloat16:
            leaves[i] = through(leaves[i])
    eng.params = jax.tree.unflatten(tree, leaves)


def state_bf16():
    import jax

    from ray_tpu.models import generate
    from ray_tpu.ops import kda

    def rounded(x):
        return _round(x, 8, 7)
    scan, step = kda.kda_scan, generate.kda_decode_step

    def kda_scan(*args, final_state=False, **kw):
        if not final_state:
            return scan(*args, **kw)
        o, state = scan(*args, final_state=True, **kw)
        return o, rounded(state)

    def kda_decode_step(state, layer, *token, **kw):
        state, o = step(state, layer, *token, **kw)
        mine = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            state, rounded(mine), layer, 0), o
    return _patched((kda, "kda_scan", kda_scan),
                    (generate, "kda_decode_step", kda_decode_step))


def _mamba2_patched(scan_args=lambda args: args, state=lambda s: s):
    """The Mamba-2 recurrence's two forms with their arguments (dt, x, B, C,
    A) and the state they leave passed through ``scan_args`` / ``state``."""
    import jax

    from ray_tpu.models import generate
    from ray_tpu.ops import mamba2

    scan, step = mamba2.mamba2_scan, generate.mamba2_decode_step

    def mamba2_scan(*args, **kw):
        y, s = scan(*scan_args(args), **kw)
        return y, state(s)

    def mamba2_decode_step(stack, layer, *token, **kw):
        *token, active = token
        stack, y = step(stack, layer, *scan_args(tuple(token)), active, **kw)
        mine = jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            stack, state(mine), layer, 0), y
    return _patched((mamba2, "mamba2_scan", mamba2_scan),
                    (generate, "mamba2_decode_step", mamba2_decode_step))


def no_decay():
    return _mamba2_patched(scan_args=lambda a: a[:4] + (a[4] * 0.0,))


def mamba2_state_bf16():
    return _mamba2_patched(state=lambda s: _round(s, 8, 7))


def norm_before_gate():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer

    def mamba2_gated_norm(y, z, gain, eps):
        return transformer.rms_norm(y, gain, eps) \
            * jax.nn.silu(z.astype(jnp.float32))
    return _patched((transformer, "mamba2_gated_norm", mamba2_gated_norm))


def no_window():
    """A window layer without its window: prefill's mask widened to every
    valid earlier position (a position sees itself under either mask, so
    the diagonal says which keys are no padding), decode's ring read
    whole."""
    import jax.numpy as jnp

    from ray_tpu.models import engine, generate

    attend = generate._diff_attention

    def _diff_attention(q, k, v, mask):
        T = mask.shape[1]
        valid = jnp.diagonal(mask, axis1=1, axis2=2)
        return attend(q, k, v, jnp.tril(jnp.ones((T, T), bool))[None]
                      & valid[:, None, :])

    def _ring_mask(pos, start, window):
        j = jnp.arange(window)[None, :]
        held = pos[:, None] - 1 - (pos[:, None] - 1 - j) % window
        return (held >= start[:, None]) & (held >= 0)
    return _patched((generate, "_diff_attention", _diff_attention),
                    (engine, "_ring_mask", _ring_mask))


def lam_fixed():
    import jax.numpy as jnp

    from ray_tpu.models import generate, transformer

    def diff_out(o, lp, cfg, layer):
        return transformer.diff_out(o, dict(
            lp, diff_lambda=jnp.zeros_like(lp["diff_lambda"])), cfg, layer)
    return _patched((generate, "diff_out", diff_out))


def run_control(replica, name: str, seed: int, lengths) -> dict:
    """`bench_check` of ``replica`` under control ``name``; the replica is
    left as it came but for ``fp8_weights`` (run it last)."""
    eng = replica.engine
    ctx, cfg = contextlib.nullcontext(), eng.cfg
    if name in ("state_bf16", "no_window", "lam_fixed", "no_decay",
                "norm_before_gate", "mamba2_state_bf16"):
        ctx = globals()[name]()
    elif name in FIELD_CONTROLS:
        eng.cfg = dataclasses.replace(cfg, **FIELD_CONTROLS[name])
    if name == "fp8_weights":
        _fp8_in_place(eng)
    try:
        with ctx:
            return replica.bench_check(seed, lengths)
    finally:
        eng.cfg = cfg


def _line(name, seed, chk) -> dict:
    worst = {side: max(r[side]["rel_rms_error"] for r in chk["rows"])
             for side in ("prefill", "decode")}
    return {"control": name, "seed": seed, "expect": EXPECT[name],
            "ok": chk["ok"], "worst": worst,
            "limit": chk["rows"][0]["prefill"]["tolerance"],
            "rows": [[r["prompt_len"], r["prefill"]["rel_rms_error"],
                      r["decode"]["rel_rms_error"], r["served_tokens_ok"]]
                     for r in chk["rows"]],
            "draw_s": chk["draw_s"], "reference_s": chk["reference_s"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload",
                    default="solar-open2-250b.batch-closed-128")
    ap.add_argument("--seeds", type=int, nargs="+", default=[4200000501])
    ap.add_argument("--controls", nargs="+", choices=sorted(EXPECT),
                    help="left out: every control of the cell's "
                    "architecture")
    ap.add_argument("--lengths", type=int, nargs="+",
                    help="prompt lengths of the check's one group (left "
                    "out: the traffic file's `check.prompt_lens`)")
    ap.add_argument("--toy", action="store_true",
                    help="toy widths and slots, any platform")
    args = ap.parse_args(argv)

    import jax

    from benchmark.harness import spec
    from benchmark.harness.serve_cell import BenchReplica

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.toy:
        print(json.dumps({"ok": False, "error": "no TPU: " + dev.platform}))
        return 1
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    conf = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    dep = dict({k: traffic["deployment"][k] for k in (
        "slots", "max_prompt_len", "max_new_tokens", "eos_id", "greedy")},
        **(TOY_DEPLOYMENT if args.toy else {}))
    lengths = args.lengths or (
        TOY_LENGTHS if args.toy else traffic["check"]["prompt_lens"])
    arch = spec.architecture_name(conf)
    toy = TOYS[arch] if args.toy else {}
    args.controls = args.controls or CONTROLS[arch]
    # the weights stay good until `fp8_weights`; `init_depth_none` draws its own
    order = sorted(args.controls, key=lambda n: (
        n == "init_depth_none", n == "fp8_weights"))
    ok = True
    for seed in args.seeds:
        replica, depth = None, "published"
        for name in order:
            want = "none" if name == "init_depth_none" else "published"
            if replica is None or want != depth:
                if replica is not None:
                    replica.engine.shutdown()
                    del replica
                over = dict(toy, **({"init_depth": None} if want == "none"
                                    else {}))
                replica = BenchReplica(conf, platform=dev.platform,
                                       field_overrides=over,
                                       seed=spec.seed32(seed), **dep)
                depth = want
            line = _line(name, seed, run_control(
                replica, name, spec.seed32(seed), lengths))
            if line["expect"] is not None:
                ok = ok and line["ok"] == (line["expect"] == "agree")
            print(json.dumps(dict(line, workload=args.workload,
                                  platform=dev.platform,
                                  device_kind=dev.device_kind)), flush=True)
        replica.engine.shutdown()
        del replica
    print(json.dumps({"ok": ok, "workload": args.workload,
                      "seeds": len(args.seeds), "controls": order}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
