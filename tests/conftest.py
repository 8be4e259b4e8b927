"""Test configuration.

JAX runs on a virtual 8-device CPU mesh in all tests (TPU hardware is not
assumed), mirroring the reference's strategy of testing distributed
semantics in one process (SURVEY.md §4). The env vars must be set before any
JAX backend initializes.
"""

import os

# Tests never touch an accelerator: force the CPU backend whatever the
# session's environment says (the chip is reached through chip_smoke.py).
os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8").strip()
# Workers inherit this too; keep them off the TPU and quiet.
os.environ.setdefault("TPU_CHIPS", "0")

import jax  # noqa: E402

from ray_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

# Persistent compilation cache: model-heavy tests recompile identical
# programs on every run otherwise. First run pays the compiles and fills
# the cache; reruns hit it. XLA:CPU needs its sub-caches opted in, and
# the session's thousands of trivial programs stay out (0.5 s threshold).
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_enable_xla_caches", "all")

import pytest  # noqa: E402

# Module-level tier assignment: the RL, tune and breadth suites, which no
# cell of the benchmark runs (minutes of small-program compiles and
# rollouts). Everything else is the fast tier, the tests of models/, ops/,
# parallel/ and train/ among it: what runs on the chip is guarded by every
# PR. Keep in sync with pytest.ini's marker docs.
SLOW_MODULES = {
    "test_tune",
    "test_rllib", "test_rllib_breadth", "test_rllib_sac",
    "test_rllib_connectors", "test_rllib_continuous",
    "test_rllib_catalog",
    "test_serve_depth", "test_data_breadth",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = getattr(item.module, "__name__", "")
        if mod in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="session", autouse=True)
def _no_orphan_arenas():
    """Arena-hygiene invariant (memory observatory): the suite FAILS if
    it leaves orphaned ``/dev/shm/rtpu_*`` arenas behind — files no live
    process maps, each pinning its full arena size in shared memory
    until someone unlinks them (an r18 session leaked ~126 GB this
    way). r19 added unlink-on-exit; this fixture turns it from a doctor
    hint into an enforced CI invariant. Pre-existing orphans (other
    sessions on a shared host) are snapshotted and excluded — only
    arenas THIS suite leaked fail it."""
    from ray_tpu.dashboard import orphan_arena_files

    before = {p for p, _ in orphan_arena_files()}
    yield
    leaked = [x for x in orphan_arena_files() if x[0] not in before]
    if leaked:
        # agent/worker teardown is asynchronous: give late atexit
        # unlinkers one grace window before declaring the leak
        import time as _t

        _t.sleep(2.0)
        leaked = [x for x in orphan_arena_files() if x[0] not in before]
    if leaked:
        total_mb = sum(sz for _, sz in leaked) / (1024 * 1024)
        names = ", ".join(p for p, _ in leaked[:8])
        raise RuntimeError(
            f"test session leaked {len(leaked)} orphaned shm arena(s) "
            f"pinning {total_mb:.0f} MB: {names} — a store was created "
            "without being destroyed/unlinked on teardown")


@pytest.fixture
def ray_start():
    """Fresh single-node runtime per test (4 CPUs)."""
    import ray_tpu

    info = ray_tpu.init(num_cpus=4, num_tpus=0)
    yield info
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """A Cluster handle with a head node; tests add nodes as needed."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2, "num_tpus": 0})
    yield cluster
    cluster.shutdown()


@pytest.fixture(scope="module")
def shared_ray():
    """Module-scoped runtime for cheap API tests."""
    import ray_tpu

    info = ray_tpu.init(num_cpus=4, num_tpus=0, ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()
