"""RLlib learner-throughput benchmark (BASELINE.md north-star row 3:
"PPO + IMPALA, Atari-class, JAX learner on TPU + CPU rollout actors —
learner throughput (env-steps/s), match reference GPU learner").

Writes RLLIB_BENCH_r4.json with, per algorithm:
  - learner_env_steps_per_s: pure learner-update throughput — how many
    env steps of experience the jitted XLA update consumes per second
    (the row-3 metric; sampling excluded, batches prebuilt on host).
  - end_to_end_env_steps_per_s: algo.train() loop including rollout
    actors on this host's CPUs (bounded by host cores, reported for
    honesty, not the row-3 target).

Envs: Breakout-Mini (Atari-class, 400-dim observation) and CartPole.
Run: python bench_rllib.py [--duration 20]
"""

import argparse
import json
import os
import sys
import time

import numpy as np


def _fake_ppo_batch(obs_dim, num_actions, n, seed=0):
    from ray_tpu.rllib import SampleBatch
    from ray_tpu.rllib import sample_batch as SB

    rng = np.random.default_rng(seed)
    return SampleBatch({
        SB.OBS: rng.normal(size=(n, obs_dim)).astype(np.float32),
        SB.ACTIONS: rng.integers(0, num_actions, n),
        SB.REWARDS: rng.normal(size=n).astype(np.float32),
        SB.DONES: rng.random(n) < 0.05,
        SB.ACTION_LOGP: -np.abs(rng.normal(size=n)).astype(np.float32),
        SB.VF_PREDS: rng.normal(size=n).astype(np.float32),
        SB.ADVANTAGES: rng.normal(size=n).astype(np.float32),
        SB.VALUE_TARGETS: rng.normal(size=n).astype(np.float32),
    })


def _fake_impala_batch(obs_dim, num_actions, T, N, seed=0):
    from ray_tpu.rllib import SampleBatch
    from ray_tpu.rllib import sample_batch as SB

    rng = np.random.default_rng(seed)
    return SampleBatch({
        SB.OBS: rng.normal(size=(T, N, obs_dim)).astype(np.float32),
        SB.ACTIONS: rng.integers(0, num_actions, (T, N)),
        SB.REWARDS: rng.normal(size=(T, N)).astype(np.float32),
        SB.DONES: rng.random((T, N)) < 0.05,
        SB.ACTION_LOGP: -np.abs(rng.normal(size=(T, N))).astype(np.float32),
        "bootstrap_obs": rng.normal(size=(N, obs_dim)).astype(np.float32),
    })


def bench_learner(learner, batches, env_steps_per_update,
                  duration_s: float, update_kw=None) -> dict:
    """Spin learner.update for duration; -> env-steps/s consumed."""
    update_kw = update_kw or {}
    learner.update(batches[0], **update_kw)  # compile/warm
    n, i = 0, 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < duration_s:
        learner.update(batches[i % len(batches)], **update_kw)
        n += 1
        i += 1
    dt = time.perf_counter() - t0
    return {"updates": n,
            "updates_per_s": round(n / dt, 2),
            "learner_env_steps_per_s": round(
                n * env_steps_per_update / dt, 1)}


def bench_end_to_end(config_builder, duration_s: float) -> dict:
    algo = config_builder()
    algo.train()  # warm/compile
    steps0 = algo._num_env_steps
    t0 = time.perf_counter()
    iters = 0
    while time.perf_counter() - t0 < duration_s:
        algo.train()
        iters += 1
    dt = time.perf_counter() - t0
    steps = algo._num_env_steps - steps0
    algo.stop()
    return {"train_iters": iters,
            "end_to_end_env_steps_per_s": round(steps / dt, 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--out", default="RLLIB_BENCH_r4.json")
    ap.add_argument("--skip-end-to-end", action="store_true")
    args = ap.parse_args()

    import jax

    from ray_tpu.rllib import (APPOConfig, BreakoutMini, IMPALAConfig,
                               PPOConfig)
    from ray_tpu.rllib.appo import APPOLearner
    from ray_tpu.rllib.learner import ImpalaLearner, PPOLearner

    obs_dim = BreakoutMini.observation_dim  # 400: the Atari-class shape
    num_actions = BreakoutMini.num_actions
    result = {"benchmark": "rllib_learner_throughput",
              "backend": jax.default_backend(),
              "env": "Breakout-Mini-v0 (MinAtar-class, obs 400)",
              "model_hiddens": [256, 256]}

    def flush():
        # partial artifact survives a later phase dying / timing out
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)

    # ---- learner-only throughput (row-3 metric) ----
    ppo = PPOLearner(obs_dim, num_actions, hiddens=(256, 256))
    bs = 4096
    batches = [_fake_ppo_batch(obs_dim, num_actions, bs, seed=s)
               for s in range(4)]
    result["ppo"] = bench_learner(
        ppo, batches, bs * 4, args.duration,  # 4 epochs over the batch
        update_kw=dict(num_epochs=4, minibatch_size=1024))
    print(json.dumps({"ppo": result["ppo"]}), file=sys.stderr,
          flush=True)
    flush()

    T, N = 64, 64
    impala = ImpalaLearner(obs_dim, num_actions, hiddens=(256, 256))
    batches = [_fake_impala_batch(obs_dim, num_actions, T, N, seed=s)
               for s in range(4)]
    result["impala"] = bench_learner(impala, batches, T * N, args.duration)
    print(json.dumps({"impala": result["impala"]}), file=sys.stderr,
          flush=True)
    flush()

    appo = APPOLearner(obs_dim, num_actions, hiddens=(256, 256))
    result["appo"] = bench_learner(appo, batches, T * N, args.duration)
    print(json.dumps({"appo": result["appo"]}), file=sys.stderr,
          flush=True)
    flush()

    # DreamerV3 world-model + imagination update (replayed env steps
    # consumed per second; the heaviest per-step learner in the zoo)
    from ray_tpu.rllib.dreamer import DreamerLearner

    rng = np.random.default_rng(0)
    B, L = 16, 32
    dreamer = DreamerLearner(obs_dim, num_actions, deter=128, hidden=128)
    dbatches = [(rng.normal(size=(B, L, obs_dim)).astype(np.float32),
                 rng.integers(0, num_actions, (B, L)),
                 rng.normal(size=(B, L)).astype(np.float32),
                 np.ones((B, L), np.float32)) for _ in range(4)]

    class _DreamerShim:
        def update(self, batch):
            return dreamer.update(*batch)

    result["dreamerv3"] = bench_learner(
        _DreamerShim(), dbatches, B * L, args.duration)
    print(json.dumps({"dreamerv3": result["dreamerv3"]}),
          file=sys.stderr, flush=True)
    flush()

    # ---- end-to-end (host-CPU-bound rollouts; context, not the target)
    if not args.skip_end_to_end:
        os.environ.setdefault("TPU_CHIPS", "0")
        import ray_tpu

        ray_tpu.init(num_cpus=4, num_tpus=0, ignore_reinit_error=True)
        try:
            result["ppo_end_to_end"] = bench_end_to_end(
                lambda: PPOConfig().environment("Breakout-Mini-v0")
                .rollouts(num_rollout_workers=2, num_envs_per_worker=8,
                          rollout_fragment_length=64)
                .training(model_hiddens=(256, 256)).build(),
                args.duration)
            result["impala_end_to_end"] = bench_end_to_end(
                lambda: IMPALAConfig().environment("Breakout-Mini-v0")
                .rollouts(num_rollout_workers=2, num_envs_per_worker=8,
                          rollout_fragment_length=64)
                .training(model_hiddens=(256, 256)).build(),
                args.duration)
        finally:
            ray_tpu.shutdown()
        flush()

    result["reference_context"] = (
        "reference GPU learner throughput for PPO/IMPALA Atari is "
        "O(10k-50k) env-steps/s per GPU (release/rllib_tests); row-3 "
        "target is the learner_env_steps_per_s fields")
    flush()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
