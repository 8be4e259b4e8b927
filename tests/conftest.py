"""Test configuration.

JAX runs on a virtual 8-device CPU mesh in all tests (TPU hardware is not
assumed), mirroring the reference's strategy of testing distributed
semantics in one process (SURVEY.md §4). The env vars must be set before any
JAX backend initializes.
"""

import contextlib
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


def _orphan_arenas(mine, what: str) -> str:
    """Arena-hygiene invariant (memory observatory): the orphaned
    ``/dev/shm/rtpu_*`` arenas that ``mine(path)`` admits, as a message, ""
    where there are none: files no live process maps, each pinning its full
    arena size in shared memory until someone unlinks them (an r18 session
    leaked ~126 GB this way). Agent/worker teardown is asynchronous: late
    atexit unlinkers get one grace window before a leak is declared."""
    import time

    from ray_tpu.dashboard import orphan_arena_files

    for grace in (2.0, None):
        leaked = [x for x in orphan_arena_files() if mine(x[0])]
        if not leaked:
            return ""
        if grace:
            time.sleep(grace)
    total_mb = sum(sz for _, sz in leaked) / (1024 * 1024)
    names = ", ".join(p for p, _ in leaked[:8])
    return (f"{what} leaked {len(leaked)} orphaned shm arena(s) pinning "
            f"{total_mb:.0f} MB: {names} — a store was created without "
            "being destroyed/unlinked on teardown")


@contextlib.contextmanager
def _own_arenas_gone():
    """Around a runtime fixture's shutdown: an arena the test's own head
    registered and the shutdown left orphaned fails THAT test, once. It is
    unlinked here, so nothing else is charged for it."""
    from ray_tpu.core import api

    mine = {f"/dev/shm/{node.store_name}"
            for node in (api._head.nodes.values() if api._head else ())}
    yield
    mine = {path for path in mine if os.path.exists(path)}
    message = mine and _orphan_arenas(mine.__contains__,
                                      "this test's runtime")
    if message:
        for path in mine:
            with contextlib.suppress(OSError):
                os.unlink(path)
        raise RuntimeError(message)


# A leak no runtime fixture can see (a test that starts its own processes) is
# charged ONCE a run, by the process that owns the run (the xdist controller,
# or the only process there is): a per-worker check over all of /dev/shm
# charged one leaked arena to whatever test each of the six workers ran last.
def pytest_sessionstart(session):
    from ray_tpu.dashboard import orphan_arena_files

    if not hasattr(session.config, "workerinput"):
        # orphans of other sessions on a shared host are not this run's
        session.config._arenas_before = {p for p, _ in orphan_arena_files()}


def pytest_sessionfinish(session):
    before = getattr(session.config, "_arenas_before", None)
    message = before is not None and _orphan_arenas(
        lambda path: path not in before, "test session")
    if message:
        session.config.get_terminal_writer().line("\nERROR: " + message,
                                                  red=True)
        session.exitstatus = max(int(session.exitstatus),
                                 int(pytest.ExitCode.TESTS_FAILED))


@pytest.fixture
def ray_start():
    """Fresh single-node runtime per test (4 CPUs)."""
    import ray_tpu

    info = ray_tpu.init(num_cpus=4, num_tpus=0)
    yield info
    with _own_arenas_gone():
        ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """A Cluster handle with a head node; tests add nodes as needed."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2, "num_tpus": 0})
    yield cluster
    with _own_arenas_gone():
        cluster.shutdown()


@pytest.fixture(scope="module")
def shared_ray():
    """Module-scoped runtime for cheap API tests."""
    import ray_tpu

    info = ray_tpu.init(num_cpus=4, num_tpus=0, ignore_reinit_error=True)
    yield info
    with _own_arenas_gone():
        ray_tpu.shutdown()
