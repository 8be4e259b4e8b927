"""Training backends: per-framework rendezvous hooks.

Ref analog: train/backend.py + train/torch/config.py:70 — where the
reference rendezvouses `torch.distributed` over NCCL, the JAX backend wires
`jax.distributed.initialize` so every worker (host) joins one global JAX
runtime and a Mesh can span the pod slice; ICI collectives then come from
XLA, not from a process-group library.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.utils.compile_cache import enable_compile_cache


@dataclasses.dataclass
class BackendConfig:
    @property
    def backend_cls(self):
        return Backend


class Backend:
    def on_start(self, worker_group: WorkerGroup, backend_config):
        pass

    def on_training_start(self, worker_group: WorkerGroup, backend_config):
        pass

    def on_shutdown(self, worker_group: WorkerGroup, backend_config):
        pass


@dataclasses.dataclass
class JaxConfig(BackendConfig):
    """JAX multi-host rendezvous config.

    distributed=None (auto): initialize `jax.distributed` only when the
    group has >1 worker — single-worker groups (including every unit test
    and the single-chip bench) run plain single-process JAX, where the mesh
    covers the locally visible devices.
    """

    distributed: Optional[bool] = None
    coordinator_port: int = 0  # 0 -> pick a free port on worker 0

    @property
    def backend_cls(self):
        return _JaxBackend


def _init_jax_distributed(coordinator_address: str, num_processes: int,
                          process_id: int):
    import jax

    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def _place_compile_cache():
    """On every worker before the train loop's first jit: a trainer
    compiles few programs, so all of them are kept, and a restarted or
    second run compiles nothing the first one compiled."""
    import jax

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _jax_shutdown():
    import jax

    try:
        jax.distributed.shutdown()
    except Exception:
        pass


class _JaxBackend(Backend):
    def on_start(self, worker_group: WorkerGroup, backend_config: JaxConfig):
        worker_group.execute(_place_compile_cache)
        n = worker_group.num_workers
        dist = backend_config.distributed
        if dist is None:
            dist = n > 1
        if not dist:
            return
        import ray_tpu

        w0 = worker_group.workers[0]
        addr = ray_tpu.get([w0.get_address.remote()])[0]
        port = backend_config.coordinator_port or ray_tpu.get(
            [w0.find_free_port.remote()])[0]
        coordinator = f"{addr}:{port}"
        self.coordinator_address = coordinator
        ray_tpu.get([
            w.execute.remote(_init_jax_distributed, coordinator, n, i)
            for i, w in enumerate(worker_group.workers)
        ])

    def on_shutdown(self, worker_group: WorkerGroup, backend_config):
        try:
            worker_group.execute(_jax_shutdown)
        except Exception:
            pass
