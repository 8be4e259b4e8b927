"""A train cell: the benchmark's own train loop, run under `JaxTrainer` in
the worker that leases the chip(s), as a user's loop would be.

`train_loop` is the worker side (it alone touches JAX); `run` is the
driver side. The loop: weights by the program's initialiser from the
traffic file's `weights_seed` (`spec.weights_seed`: the checkpoint a job
starts from does not change from run to run), the logits/loss check against
the plain reference, one compiled step (its `memory_analysis()` kept), one
warm-up step, then the measured window of steps on fresh batches drawn from
`--seed` (as the check's rows are), each prepared while the step before
runs.
"""

from __future__ import annotations

import math
import time

from benchmark.harness import spec


def _make_batch(seed: int, step: int, rows: int, seq: int, vocab: int):
    import numpy as np

    from benchmark.harness.traffic import train_batch_seed

    rng = np.random.default_rng(train_batch_seed(seed, step))
    return {"tokens": rng.integers(0, vocab, (rows, seq + 1),
                                   dtype=np.int32)}


def check_against_reference(params, cfg, fields, conf, arch, mesh,
                            seed: int, rows: int, seq: int) -> dict:
    """The program's forward and objective on ``rows`` seeded rows of
    ``seq`` tokens against the plain float32 reference of the
    configuration's architecture (``arch``, from `spec.load_architecture`),
    row by row: the logits, the next-token cross entropy (the program's
    `metrics["loss"]`, not the total it differentiates) and every further
    term the architecture's optional `reference_terms` gives, each against
    the metric of that name; the total against the weighted sum the config
    file's `objective` states (`reference.objective_agrees`)."""
    import functools

    import jax
    import jax.numpy as jnp

    from benchmark.harness.reference import (logits_agree, objective_agrees,
                                             reference_loss)
    from ray_tpu.models.transformer import forward, loss_fn

    batch = _make_batch(seed ^ 0x5EED, 0, rows, seq, cfg.vocab_size)
    tokens = batch["tokens"]
    fwd = jax.jit(functools.partial(forward, cfg=cfg, mesh=mesh))
    loss = jax.jit(lambda p, b: loss_fn(p, b, cfg, mesh))
    got_logits = fwd(params, jnp.asarray(tokens[:, :-1]))
    total, metrics = loss(params, {"tokens": jnp.asarray(tokens)})
    total = float(total)
    metrics = {k: float(v) for k, v in metrics.items()}
    dtype = jnp.dtype(cfg.dtype).name
    more_terms = getattr(arch, "reference_terms", None)
    worst, ref_rows = None, []
    for r in range(rows):
        want = arch.reference_logits(params, tokens[r, :-1], fields, conf)
        res = logits_agree(got_logits[r], want, dtype)
        ref = {"loss": float(reference_loss(want, tokens[r, 1:]))}
        if worst is None or res["rel_rms_error"] > worst["rel_rms_error"]:
            worst = res
        del want
        if more_terms is not None:
            ref.update({k: float(v) for k, v in more_terms(
                params, tokens[r], fields, conf).items()})
        ref_rows.append(ref)
    del got_logits
    reference = {k: sum(ref[k] for ref in ref_rows) / rows
                 for k in ref_rows[0]}
    objective = objective_agrees(total, metrics, reference,
                                 conf.get("objective"), dtype,
                                 getattr(arch, "TERM_ABS_TOL", None))
    cross = objective["terms"]["loss"]
    return {"reference": spec.architecture_name(conf),
            "logits": worst, "loss": cross["program"],
            "reference_loss": cross["reference"],
            "loss_abs_diff": cross["abs_diff"],
            "loss_tolerance": cross["tolerance"], "rows": rows, "seq": seq,
            "objective": objective,
            "ok": bool(worst["ok"] and objective["ok"])}


def train_loop(config):
    """Runs in the trainer worker. Reports one dict through
    `train.report`."""
    t_loop = time.time()
    import jax
    import jax.numpy as jnp

    from benchmark.harness import probes, xplane
    from ray_tpu import train
    from ray_tpu.models.training import (init_train_state, make_optimizer,
                                         make_train_step, state_shardings)
    from ray_tpu.models.transformer import init_params

    out = {"loop_start_unix": t_loop}
    device = probes.device_description()
    out["device"] = device
    if device["platform"] != config["platform"]:
        raise RuntimeError(f"expected platform {config['platform']!r}, JAX "
                           f"found {device['platform']!r}")
    if device["count"] < config["chips"]:
        raise RuntimeError(f"cell needs {config['chips']} chip(s), JAX "
                           f"found {device['count']}")
    traffic, conf, seed = config["traffic"], config["conf"], config["seed"]
    rows, seq = traffic["rows"], traffic["seq_len"]
    root = config["root"]
    fields = spec.transformer_fields(conf, root)
    fields.update(config.get("field_overrides") or {})
    cfg = spec.build_transformer_config(
        conf, root, max_seq_len=seq, param_dtype=traffic["param_dtype"],
        attention_impl=traffic["attention_impl"],
        **(config.get("field_overrides") or {}))
    tx = make_optimizer(traffic["learning_rate"],
                        mu_dtype=jnp.dtype(traffic["mu_dtype"]))
    mesh = None
    if traffic.get("mesh"):
        from ray_tpu.parallel import MeshSpec

        n = math.prod(traffic["mesh"].values())
        mesh = MeshSpec(**traffic["mesh"]).build(jax.devices()[:n])
    compiles = probes.CompileCounter()
    # the weights of the check and of the train state: the cell's, not
    # the run's (`--seed` draws the batches and the check's rows)
    key = jax.random.key(spec.seed32(spec.weights_seed(traffic)))

    # -- correctness, outside the window, before the train state exists:
    # the same weights (same key, same initialiser) and nothing else
    t0 = time.perf_counter()
    check = traffic["check"]
    if mesh is None:
        params = jax.jit(lambda k: init_params(k, cfg))(key)
    else:
        params = jax.jit(lambda k: init_params(k, cfg), out_shardings=
                         state_shardings(cfg, tx, mesh)["params"])(key)
    out["check"] = check_against_reference(
        params, cfg, fields, conf, spec.load_architecture(conf, root), mesh,
        seed, check["rows"], check.get("seq_len", seq))
    del params
    out["check_s"] = time.perf_counter() - t0

    # -- state, the one step program, one warm-up step
    state = init_train_state(key, cfg, tx, mesh)
    out["state_bytes"] = sum(x.nbytes for x in jax.tree.leaves(state))
    first = _make_batch(seed, 0, rows, seq, cfg.vocab_size)
    t0 = time.perf_counter()
    step = make_train_step(cfg, tx, mesh).lower(state, first).compile()
    out["compile_s"] = time.perf_counter() - t0
    mem = step.memory_analysis()
    out["program_argument_bytes"] = int(mem.argument_size_in_bytes)
    out["program_temp_bytes"] = int(mem.temp_size_in_bytes)
    text = step.as_text()
    out["kernel_in_program"] = "tpu_custom_call" in text
    out["collectives_in_program"] = sorted(
        c for c in ("all-gather", "reduce-scatter", "all-reduce")
        if c in text)
    # device time by `jax.named_scope`: the trace names an operation by
    # its HLO instruction, the compiled text says which scope that
    # instruction was traced under
    scopes = xplane.op_scopes(text) if config.get("trace_dir") else {}
    del text
    state, metrics = step(state, first)
    losses = [float(metrics["loss"])]

    # -- the measured window
    trace_dir = config.get("trace_dir")
    trace_steps = traffic.get("trace_steps", 4) if trace_dir else 0
    trace_from = 2
    ann = jax.profiler.TraceAnnotation
    seconds = config["seconds"]
    compiles.mark()
    pending = []          # metrics of dispatched, unfinished steps
    in_window = []        # ... of the window's finished steps, on the host

    def wait_step():
        """The oldest dispatched step's scalars, once it has finished."""
        done = jax.device_get(pending.pop(0))
        in_window.append(done)
        losses.append(float(done["loss"]))

    i = 1                 # batch index; 0 was the warm-up
    tracing = False
    batch = _make_batch(seed, i, rows, seq, cfg.vocab_size)
    out["window_open_unix"] = time.time()
    t_open = time.perf_counter()
    done_steps = 0
    while True:
        if trace_steps and not tracing and done_steps >= trace_from:
            while pending:   # nothing in flight when the trace starts
                wait_step()
                done_steps += 1
            jax.profiler.start_trace(
                trace_dir, profiler_options=probes.trace_options())
            tracing, t_trace0, s_trace0 = True, time.perf_counter(), \
                done_steps
        with ann("bench:train.dispatch"):
            state, metrics = step(state, batch)
        pending.append(metrics)
        i += 1
        with ann("bench:train.make_batch"):
            batch = _make_batch(seed, i, rows, seq, cfg.vocab_size)
        if len(pending) > 1:   # one step always queued behind the running
            with ann("bench:train.wait_step"):
                wait_step()
            done_steps += 1
        if tracing and done_steps - s_trace0 >= trace_steps:
            with ann("bench:train.wait_step"):
                while pending:
                    wait_step()
                    done_steps += 1
            out["trace_wall_s"] = time.perf_counter() - t_trace0
            out["trace_steps"] = done_steps - s_trace0
            jax.profiler.stop_trace()
            tracing, trace_steps = False, 0
        if time.perf_counter() - t_open >= seconds and not tracing:
            break
    while pending:
        wait_step()
        done_steps += 1
    window_s = time.perf_counter() - t_open
    out["compilations_in_window"] = compiles.since_mark()
    out["window_s"] = window_s
    out["steps"] = done_steps
    out["tokens"] = done_steps * rows * seq
    out["losses"] = losses
    # the step's own counters (whatever `loss_fn` and the step report:
    # `loss`, `grad_norm`, an MoE block's `moe_load_max_over_mean`, ...),
    # each as its mean over the window's steps
    out["step_metrics"] = {
        k: sum(float(m[k]) for m in in_window) / len(in_window)
        for k in (in_window[0] if in_window else {})}
    out["step_counter"] = int(state["step"])
    out["memory_peak_bytes"] = probes.memory_peak_bytes()
    if trace_dir:
        red = xplane.reduce_trace(trace_dir)
        red.pop("op_count", None)
        out["trace"] = red
        out["op_scopes"] = {k: scopes[k] for k in red.get("op_seconds", {})
                            if k in scopes}
        # the steps the device ran inside the trace, from the trace itself
        out["trace_steps"] = xplane.module_executions(
            red, r"^jit_step")["count"]
    train.report(out)


def run(cell: dict, conf: dict, traffic: dict, args, *, root: str,
        platform="tpu", field_overrides=None, trace_dir=None) -> dict:
    """Driver side: one JaxTrainer run of `train_loop`; returns what the
    loop reported plus `chip_worker_ready_s`."""
    from ray_tpu.train import JaxTrainer, ScalingConfig

    on_tpu = platform == "tpu"
    t_fit = time.time()
    result = JaxTrainer(
        train_loop,
        train_loop_config=dict(
            conf=conf, traffic=traffic, seed=args.seed, root=root,
            seconds=args.seconds, platform=platform, chips=cell["chips"],
            field_overrides=field_overrides,
            trace_dir=trace_dir if args.trace else None),
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=on_tpu,
            tpus_per_worker=cell["chips"] if on_tpu else None),
    ).fit()
    if result.error is not None:
        raise result.error
    out = dict(result.metrics)
    out["chip_worker_ready_s"] = out["loop_start_unix"] - t_fit
    return out


def judge(out: dict, traffic: dict, platform: str) -> dict:
    """The checks that decide `correct` for a train cell."""
    losses = out["losses"]
    on_tpu = platform == "tpu"
    return {
        "reference_agrees": out["check"]["ok"],
        "losses_finite": all(math.isfinite(x) for x in losses),
        "step_counter": out["step_counter"] == out["steps"] + 1,
        "no_compilation_in_window": out["compilations_in_window"] == 0,
        "kernel_in_program": out["kernel_in_program"] == on_tpu,
        "collectives_in_program": (not traffic.get("mesh")) or
        {"all-gather"} <= set(out["collectives_in_program"]),
        "some_steps": out["steps"] >= 1,
    }
