"""Smoke run of the two flagship paths on a real TPU chip.

    python chip_smoke.py            # one chip: kernel, train, then serve phase
    python chip_smoke.py --chips 4  # four-chip host: one chip vs fsdp=2 x tensor=2

Drives the entry points a user calls — ``ray_tpu.init()``, ``JaxTrainer``,
``serve.run(build_continuous_llm_deployment(...))`` — at the published
widths of the ``llama3-1b`` preset with random weights made from
``--seed`` (training holds bf16 parameters; the replica holds the preset
as `build_continuous_llm_deployment("llama3-1b")` gives it to any user:
float32 master weights, bf16 compute), and checks what comes back. It
proves the system starts and is right on the chip; it is no benchmark, and
the seconds it prints are information only.

One process per chip: this process never imports JAX. Each phase runs in a
worker that leases the chip and exits before the next phase starts, and
the device description on the last line comes from the worker that held
it. Finding no TPU is a failure, never a CPU run.

The kernel phase holds `ray_tpu.ops.flash_attention` at the train cells'
per-chip shape ([4, 4096, 16, 128] bf16, causal) to `reference_attention`
in float32 on the same inputs: output and the three gradients, as relative
RMS errors; then at an encoder's ([2, 197, 12, 64], no mask), a length
that is no multiple of a tile, which only the chip's compiler can refuse.
A builder who changes the kernel passes ``kernel_phase(kernel_files=...)``
the parent's module file: it is held to the reference beside the tree's,
in the same process and on the same inputs.

Output: one JSON object per line; every line that carries a number names
the ``platform``, ``device_kind`` and ``device_count`` it was taken on. The
last line is ``{"ok": ..., "device": {"platform", "kind", "count"}}`` and
the exit code is 0 only when every check of every phase passed.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
import threading
import time
import traceback
import urllib.request

MODEL = "llama3-1b"
VOCAB = 128_256  # llama3-1b's vocabulary (ray_tpu/models/config.py)


class SmokeFailure(RuntimeError):
    """A phase ran but a check on its output failed."""


def _emit(phase: str, device: dict | None = None, **fields):
    line = {"phase": phase}
    if device is not None:
        line.update(platform=device["platform"], device_kind=device["kind"],
                    device_count=device["count"])
    line.update(fields)
    print(json.dumps(line), flush=True)


def _require(checks: dict, phase: str):
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        raise SmokeFailure(f"{phase}: failed checks {failed}")


# ----------------------------------------------------------- kernel phase

def _device_of(config) -> tuple:
    """(first device, its description) in a worker; a platform other than
    the one asked for is an error, never a run."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != config["platform"]:
        raise RuntimeError(
            f"expected platform {config['platform']!r}, JAX found "
            f"{dev.platform!r} ({dev.device_kind})")
    return dev, {"platform": dev.platform, "kind": dev.device_kind,
                 "count": len(devices)}


def _kernel_loop(config):
    """Runs in the worker that leased the chip: `flash_attention` of the
    tree (and of every kernel module file named beside it) against
    `reference_attention` in float32, same inputs, output and gradients."""
    import functools
    import importlib
    import importlib.util

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.parallel import reference_attention

    _, out_device = _device_of(config)
    B, T, H, D = config["shape"]
    keys = jax.random.split(jax.random.key(config["seed"]), 4)
    q, k, v, do = (jax.random.normal(key, (B, T, H, D), jnp.float32).astype(
        jnp.dtype(config["dtype"])) for key in keys)

    def with_gradients(attn):
        def run(q, k, v, do):
            o, vjp = jax.vjp(functools.partial(
                attn, causal=config["causal"]), q, k, v)
            return (o,) + vjp(do)
        return jax.jit(run)

    # a row at a time: one row's float32 scores are H x T x T x 4 bytes
    reference = with_gradients(reference_attention)
    rows = [[np.asarray(x) for x in reference(*(
        a[b:b + 1].astype(jnp.float32) for a in (q, k, v, do)))]
        for b in range(B)]
    want = [np.concatenate(parts) for parts in zip(*rows)]

    modules = {"tree": importlib.import_module(
        "ray_tpu.ops.flash_attention")}
    for path in config["kernel_files"]:
        spec = importlib.util.spec_from_file_location(
            f"kernel_file_{len(modules)}", path)
        modules[path] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(modules[path])

    def rel_rms(got, ref):
        got = np.asarray(got, np.float32)
        return float(np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2)))

    errors = {}
    for label, module in modules.items():
        got = with_gradients(module.flash_attention)(q, k, v, do)
        errors[label] = {name: rel_rms(g, w) for name, g, w in
                         zip(("o", "dq", "dk", "dv"), got, want)}
    train.report({"device": out_device, "errors": errors})


def kernel_phase(shape, *, platform: str, seed: int, causal: bool = True,
                 dtype: str = "bfloat16", kernel_files=(),
                 bound: float = 1e-2) -> dict:
    """The attention kernel at ``shape`` ([B, T, H, D]) against the
    float32 reference: relative RMS error of the output and of dq, dk, dv,
    each under ``bound`` (bf16 rounding of the results alone is about
    2e-3). Returns the device description from the worker."""
    out = _run_leased(
        _kernel_loop, dict(shape=list(shape), causal=causal, dtype=dtype,
                           seed=seed, kernel_files=list(kernel_files)),
        platform=platform)
    dev = out["device"]
    _emit("kernel", dev, shape=list(shape), dtype=dtype, causal=causal,
          rel_rms_error_vs_float32_reference=out["errors"], bound=bound)
    checks = {"platform": dev["platform"] == platform}
    for label, errs in out["errors"].items():
        for name, err in errs.items():
            checks[f"{label}:{name}"] = math.isfinite(err) and err <= bound
    _require(checks, "kernel")
    return dev


# ------------------------------------------------------------ train phase

def _train_loop(config):
    """Runs in the trainer worker, the process that leased the chip(s)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models.config import get_config
    from ray_tpu.models.training import (init_train_state, make_eval_step,
                                         make_optimizer, make_train_step)

    devices = jax.devices()
    dev, described = _device_of(config)
    out = {"device": described,
           "tpu_visible_chips": os.environ.get("TPU_VISIBLE_CHIPS")}
    B, T = config["batch"], config["seq"]

    def cfg_with(attention_impl):
        return get_config(config["model"], max_seq_len=T,
                          param_dtype=jnp.dtype(config["param_dtype"]),
                          attention_impl=attention_impl)

    cfg = cfg_with("auto")
    tx = make_optimizer(3e-4, mu_dtype=jnp.bfloat16)
    mesh = None
    if config["mesh"]:
        from ray_tpu.parallel import MeshSpec

        n = math.prod(config["mesh"].values())
        mesh = MeshSpec(**config["mesh"]).build(devices[:n])
    rng = np.random.default_rng(config["seed"])

    def batch(rows=B):
        # host arrays: jit places them by the step's in_shardings
        return {"tokens": rng.integers(0, cfg.vocab_size, (rows, T + 1),
                                       dtype=np.int32)}

    fixed = batch(config["eval_batch"])
    state = init_train_state(jax.random.key(config["seed"]), cfg, tx, mesh)
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
    per_device = {}
    for leaf in jax.tree.leaves(state):
        for sh in leaf.addressable_shards:
            per_device[sh.device.id] = per_device.get(sh.device.id, 0) \
                + sh.data.nbytes
    out["state_bytes"] = state_bytes
    out["state_share_per_device"] = [
        per_device[d] / state_bytes for d in sorted(per_device)]

    evals = {impl: make_eval_step(cfg_with(impl), mesh)
             for impl in config["eval_impls"]}

    def eval_loss(impl, params):
        return float(evals[impl](params, fixed)["loss"])

    if evals:
        out["fixed_loss_before"] = eval_loss(config["eval_impls"][0],
                                             state["params"])

    first = batch()
    t0 = time.perf_counter()
    step = make_train_step(cfg, tx, mesh).lower(state, first).compile()
    out["compile_s"] = time.perf_counter() - t0
    mem = step.memory_analysis()
    out["program_argument_bytes"] = mem.argument_size_in_bytes
    out["program_temp_bytes"] = mem.temp_size_in_bytes
    text = step.as_text()
    out["kernel_in_program"] = "tpu_custom_call" in text
    out["collectives_in_program"] = sorted(
        c for c in ("all-gather", "reduce-scatter", "all-reduce")
        if c in text)

    losses, step_s = [], []
    for i in range(1 + config["steps"]):  # the first one is the warm-up
        b = first if i == 0 else batch()
        t0 = time.perf_counter()
        state, metrics = step(state, b)
        losses.append(float(metrics["loss"]))  # fetch ends the step
        step_s.append(time.perf_counter() - t0)
    out["losses"] = losses
    out["step_s"] = step_s
    out["step_counter"] = int(state["step"])
    for impl in evals:
        out[f"fixed_loss_after_{impl}"] = eval_loss(impl, state["params"])
    stats = dev.memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    out["bytes_limit"] = stats.get("bytes_limit")
    train.report(out)


def _run_leased(loop, config: dict, *, platform: str, chips: int = 1) -> dict:
    """One JaxTrainer run of ``loop`` in a worker that leases ``chips`` TPU
    chips (none when ``platform`` is "cpu"); returns what the loop
    reported, as plain Python values."""
    from ray_tpu.train import JaxTrainer, ScalingConfig

    on_tpu = platform == "tpu"
    result = JaxTrainer(
        loop, train_loop_config=dict(config, platform=platform),
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=on_tpu,
            tpus_per_worker=chips if on_tpu else None),
    ).fit()
    if result.error is not None:
        raise result.error
    return result.metrics


def run_train_loop(model: str, *, batch: int, seq: int, steps: int,
                   platform: str, seed: int, chips: int = 1, mesh=None,
                   eval_impls=("pallas", "xla"), eval_batch: int = 1,
                   param_dtype: str = "bfloat16") -> dict:
    """``_train_loop`` in a worker that leases ``chips`` TPU chips."""
    return _run_leased(
        _train_loop,
        dict(model=model, batch=batch, seq=seq, steps=steps, seed=seed,
             mesh=mesh, eval_impls=list(eval_impls), eval_batch=eval_batch,
             param_dtype=param_dtype),
        platform=platform, chips=chips)


def train_phase(model: str, *, batch: int, seq: int, steps: int,
                platform: str, seed: int, first_loss_range,
                param_dtype: str = "bfloat16") -> dict:
    """Train ``steps`` steps (after one warm-up) on fresh seeded batches
    and check the losses, the step counter, that the kernel is in the
    compiled program (TPU only), and the kernel against its XLA
    reference. Returns the device description from the worker."""
    out = run_train_loop(model, batch=batch, seq=seq, steps=steps,
                         platform=platform, seed=seed,
                         param_dtype=param_dtype)
    dev = out["device"]
    losses, step_s = out["losses"], sorted(out["step_s"][1:])
    median_step = step_s[len(step_s) // 2]
    kernel_vs_xla = abs(out["fixed_loss_after_pallas"]
                        - out["fixed_loss_after_xla"])
    _emit("train", dev, model=model, batch=batch, seq=seq,
          param_dtype=param_dtype, tpu_visible_chips=out["tpu_visible_chips"],
          compile_s=out["compile_s"], warmup_step_s=out["step_s"][0],
          step_s=out["step_s"][1:], median_step_s=median_step,
          tokens_per_s=batch * seq / median_step,
          peak_bytes_in_use=out["peak_bytes_in_use"],
          bytes_limit=out["bytes_limit"], state_bytes=out["state_bytes"],
          program_argument_bytes=out["program_argument_bytes"],
          program_temp_bytes=out["program_temp_bytes"],
          losses=losses, step_counter=out["step_counter"],
          kernel_in_program=out["kernel_in_program"],
          fixed_loss_before=out["fixed_loss_before"],
          fixed_loss_after_pallas=out["fixed_loss_after_pallas"],
          fixed_loss_after_xla=out["fixed_loss_after_xla"],
          kernel_vs_xla_abs_diff=kernel_vs_xla,
          note="information, not a benchmark result")
    lo, hi = first_loss_range
    _require({
        "platform": dev["platform"] == platform,
        "kernel_in_program": out["kernel_in_program"] == (platform == "tpu"),
        "losses_finite": all(math.isfinite(x) for x in losses),
        "first_loss_in_range": lo <= losses[0] <= hi,
        "step_counter": out["step_counter"] == 1 + steps,
        "fixed_loss_moved":
            out["fixed_loss_before"] != out["fixed_loss_after_pallas"],
        "kernel_matches_xla": kernel_vs_xla <= 1e-2,
    }, "train")
    return dev


# ------------------------------------------------------------ serve phase

def serve_phase(model: str, *, slots: int, max_prompt_len: int,
                max_new_tokens: int, vocab: int, platform: str,
                seed: int) -> dict:
    """Deploy one continuous-batching replica (it leases the chip on a
    TPU cluster) and answer ten requests: eight seeded prompts through
    the handle (four one after the other, one of them streamed, then four
    at once), the first prompt again, and one over HTTP. Nothing is
    warmed up, so first shapes compile under a request and the printed
    wall seconds show it. Returns the replica's device description."""
    import random

    from ray_tpu import serve
    from ray_tpu.serve.llm import build_continuous_llm_deployment

    rnd = random.Random(seed)
    spread = [16, 100, 300, 512, 32, 64, 200, 450]  # of a 512 bucket range
    lens = [max(1, n * max_prompt_len // 512) for n in spread]
    wants = [max_new_tokens, 8, 16, max_new_tokens, 8, 16, 32,
             max_new_tokens]
    wants = [min(w, max_new_tokens) for w in wants]
    prompts = [[rnd.randrange(1, vocab) for _ in range(n)] for n in lens]
    STREAMED = 1

    app = build_continuous_llm_deployment(
        model, name="llm", slots=slots, max_prompt_len=max_prompt_len,
        max_new_tokens=max_new_tokens, seed=seed)
    t0 = time.perf_counter()
    handle = serve.run(app, name="llm", route_prefix="/llm", timeout_s=900)
    deploy_s = time.perf_counter() - t0
    stats_handle = handle.options(method_name="engine_stats")
    dev = handle.options(method_name="device").remote().result(
        timeout_s=120)
    _emit("serve_deploy", dev, model=model, slots=slots, deploy_s=deploy_s,
          note="information, not a benchmark result")

    answers: dict = {}
    walls: dict = {}

    def call(key, prompt, want):
        t = time.perf_counter()
        answers[key] = handle.remote(prompt, max_new_tokens=want).result(
            timeout_s=900)["token_ids"]
        walls[key] = time.perf_counter() - t

    def stream(key, prompt, want):
        t = time.perf_counter()
        gen = handle.options(method_name="stream", stream=True).remote(
            prompt, max_new_tokens=want)
        answers[key] = [chunk["token_id"] for chunk in gen]
        walls[key] = time.perf_counter() - t

    for i in range(4):
        (stream if i == STREAMED else call)(i, prompts[i], wants[i])
    threads = [threading.Thread(target=call, args=(i, prompts[i], wants[i]))
               for i in range(4, 8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=1000)
    call("repeat", prompts[0], wants[0])

    port = serve.start()
    t = time.perf_counter()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/llm", data=json.dumps(prompts[0]).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        answers["http"] = json.loads(r.read())["token_ids"]
    walls["http"] = time.perf_counter() - t

    stats = stats_handle.remote().result(timeout_s=120)
    expected = {i: wants[i] for i in range(8)}
    expected.update(repeat=wants[0], http=max_new_tokens)
    sent = len(expected)
    _emit("serve", dev, model=model, slots=slots,
          prompt_lens=lens, asked=[expected[k] for k in expected],
          request_wall_s={str(k): walls.get(k) for k in expected},
          fetch_s_per_fetch=stats["fetch_wall_s"] / max(1, stats["fetches"]),
          dispatch_wall_s=stats["dispatch_wall_s"],
          engine_stats=stats,
          note="first shapes compile under a request; information, not a "
               "benchmark result")
    _require({
        "platform": dev["platform"] == platform,
        "all_answered": set(answers) == set(expected),
        "token_counts": all(len(answers.get(k, ())) == n
                            for k, n in expected.items()),
        "tokens_in_vocab": all(0 <= t < vocab
                               for a in answers.values() for t in a),
        "repeat_identical": answers.get("repeat") == answers.get(0),
        "prefills": stats["prefills"] == sent,
        "requests_done": stats["requests_done"] == sent,
    }, "serve")
    return dev


# ------------------------------------------------------- four-chip phase

def sharded_phase(model: str, *, batch: int, seq: int, steps: int,
                  platform: str, seed: int, mesh: dict,
                  param_dtype: str = "bfloat16") -> dict:
    """The same ``steps`` train steps, from the same seed and batches, on
    one chip and then on ``mesh`` over all of the host's chips; the
    sharded program must keep the kernel, use collectives, spread the
    state, and reproduce the one-chip losses."""
    chips = math.prod(mesh.values())
    common = dict(batch=batch, seq=seq, steps=steps - 1, platform=platform,
                  seed=seed, eval_impls=(), param_dtype=param_dtype)
    one = run_train_loop(model, chips=1, **common)
    _emit("one_chip", one["device"], model=model, losses=one["losses"],
          tpu_visible_chips=one["tpu_visible_chips"],
          compile_s=one["compile_s"], step_s=one["step_s"],
          note="information, not a benchmark result")
    _wait_chips_free(chips if platform == "tpu" else 0)
    many = run_train_loop(model, chips=chips, mesh=mesh, **common)
    dev = many["device"]
    diffs = [abs(a - b) for a, b in zip(one["losses"], many["losses"])]
    shares = many["state_share_per_device"]
    _emit("sharded", dev, model=model, mesh=mesh, losses=many["losses"],
          one_chip_losses=one["losses"], loss_abs_diff=diffs,
          kernel_in_program=many["kernel_in_program"],
          collectives_in_program=many["collectives_in_program"],
          state_share_per_device=shares, state_bytes=many["state_bytes"],
          peak_bytes_in_use=many["peak_bytes_in_use"],
          program_argument_bytes=many["program_argument_bytes"],
          program_temp_bytes=many["program_temp_bytes"],
          compile_s=many["compile_s"], step_s=many["step_s"],
          note="information, not a benchmark result")
    _require({
        "one_chip_saw_one_device": one["device"]["count"] == 1
        or platform != "tpu",
        "platform": dev["platform"] == platform,
        "device_count": dev["count"] >= chips,
        "losses_agree": len(diffs) == steps and max(diffs) <= 5e-2,
        "kernel_in_program":
            many["kernel_in_program"] == (platform == "tpu"),
        # XLA:CPU leaves the gradient reduction an all-reduce
        "collectives_in_program":
            ({"all-gather", "reduce-scatter"} if platform == "tpu"
             else {"all-gather"}) <= set(many["collectives_in_program"]),
        "state_spread": len(shares) == chips
        and all(0.15 <= s <= 0.40 for s in shares),
    }, "sharded")
    return dev


# ------------------------------------------------------------------ main

def _wait_chips_free(chips: int, timeout_s: float = 120.0):
    """The previous phase's worker is gone once its chips are free."""
    import ray_tpu

    deadline = time.monotonic() + timeout_s
    while ray_tpu.available_resources().get("TPU", 0) < chips:
        if time.monotonic() > deadline:
            raise SmokeFailure(
                f"{chips} TPU chip(s) not free {timeout_s}s after the "
                "previous phase ended")
        time.sleep(0.2)


def _run(args) -> dict:
    import ray_tpu
    from ray_tpu import serve

    _emit("host", tpu_env={k: v for k, v in sorted(os.environ.items())
                           if k.startswith(("TPU_", "JAX_"))},
          device_files=sorted(glob.glob("/dev/accel*")
                              + glob.glob("/dev/vfio/*")))
    ray_tpu.init()
    try:
        detected = ray_tpu.cluster_resources().get("TPU", 0)
        _emit("init", detected_tpu_chips=detected, wanted=args.chips)
        if detected < args.chips:
            raise SmokeFailure(
                f"this host offers {detected} TPU chip(s), "
                f"{args.chips} needed: no CPU run stands in for the chip")
        if args.chips == 4:
            return sharded_phase(
                MODEL, batch=4, seq=2048, steps=3, platform="tpu",
                seed=args.seed, mesh={"fsdp": 2, "tensor": 2})
        kernel_phase((4, 4096, 16, 128), platform="tpu", seed=args.seed)
        _wait_chips_free(1)
        kernel_phase((2, 197, 12, 64), causal=False, platform="tpu",
                     seed=args.seed)
        _wait_chips_free(1)
        train_phase(MODEL, batch=4, seq=2048, steps=5, platform="tpu",
                    seed=args.seed, first_loss_range=(11.5, 13.0))
        _wait_chips_free(1)
        try:
            return serve_phase(MODEL, slots=8, max_prompt_len=512,
                               max_new_tokens=64, vocab=VOCAB,
                               platform="tpu", seed=args.seed)
        finally:
            serve.shutdown()
    finally:
        ray_tpu.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-training comparison, on a "
                         "four-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device, ok = None, False
    try:
        device = _run(args)
        ok = True
    except Exception:  # noqa: BLE001 — reported as ok=false, exit 1
        traceback.print_exc()
    if "jax" in sys.modules:
        print("chip_smoke: the parent process imported jax",
              file=sys.stderr)
        ok = False
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
