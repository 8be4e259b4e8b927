"""Host logic of the chip bring-up, on the CPU.

chip_smoke.py's phases at tiny size with expected platform "cpu" (the same
functions `main()` runs at llama3-1b widths on the chip), its refusal to
run without a TPU, chip detection, one-process-per-chip worker
environments, and where the compilation cache goes.
"""

import json
import os
import subprocess
import sys
import types

import pytest

import ray_tpu
from ray_tpu.core import resources as res
from ray_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # chip_smoke.py lives at the root of the checkout
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


# ------------------------------------------------ chip_smoke phases (CPU)

@pytest.fixture(scope="module")
def cpu_cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


def test_train_phase_tiny_on_cpu(cpu_cluster, capsys):
    dev = chip_smoke.train_phase(
        "tiny", batch=2, seq=64, steps=2, platform="cpu", seed=0,
        first_loss_range=(5.0, 7.0), param_dtype="float32")
    assert dev["platform"] == "cpu"
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "train" and line["platform"] == "cpu"
    assert line["step_counter"] == 3 and len(line["losses"]) == 3
    assert line["kernel_vs_xla_abs_diff"] <= 1e-2


def test_kernel_phase_tiny_on_cpu(cpu_cluster, capsys):
    """The kernel phase at a tiny float32 shape, with the tree's own
    kernel file named beside it: both are held to the reference."""
    import ray_tpu.ops

    beside = os.path.join(os.path.dirname(ray_tpu.ops.__file__),
                          "flash_attention.py")
    dev = chip_smoke.kernel_phase((1, 48, 2, 16), platform="cpu", seed=3,
                                  dtype="float32", kernel_files=[beside],
                                  bound=1e-4)
    assert dev["platform"] == "cpu"
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "kernel" and line["shape"] == [1, 48, 2, 16]
    errors = line["rel_rms_error_vs_float32_reference"]
    assert sorted(errors) == sorted(["tree", beside])
    assert errors["tree"] == errors[beside]
    assert set(errors["tree"]) == {"o", "dq", "dk", "dv"}
    assert all(0 < e <= 1e-4 for e in errors["tree"].values())


def test_kernel_phase_fails_over_its_bound(cpu_cluster):
    with pytest.raises(chip_smoke.SmokeFailure, match="kernel: failed"):
        chip_smoke.kernel_phase((1, 32, 1, 8), platform="cpu", seed=0,
                                causal=False,
                                dtype="bfloat16", bound=1e-6)


def test_train_phase_fails_on_wrong_platform(cpu_cluster):
    """Expecting a TPU and finding the CPU is a failure, not a CPU run."""
    with pytest.raises(Exception, match="expected platform 'tpu'"):
        chip_smoke._train_loop(dict(
            model="tiny", batch=2, seq=64, steps=1, platform="tpu", seed=0,
            mesh=None, eval_impls=[], eval_batch=1, param_dtype="float32"))


def test_serve_phase_tiny_on_cpu(cpu_cluster, capsys):
    from ray_tpu import serve

    try:
        dev = chip_smoke.serve_phase(
            "tiny", slots=4, max_prompt_len=32, max_new_tokens=8,
            vocab=256, platform="cpu", seed=0)
    finally:
        serve.shutdown()
    assert dev["platform"] == "cpu"
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["engine_stats"]["prefills"] == 10
    assert line["engine_stats"]["requests_done"] == 10
    assert len(line["request_wall_s"]) == 10


def test_sharded_phase_tiny_on_four_cpu_devices(cpu_cluster, capsys):
    dev = chip_smoke.sharded_phase(
        "tiny", batch=4, seq=64, steps=3, platform="cpu", seed=0,
        mesh={"fsdp": 2, "tensor": 2}, param_dtype="float32")
    assert dev["count"] >= 4
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "sharded"
    assert max(line["loss_abs_diff"]) <= 5e-2
    assert len(line["state_share_per_device"]) == 4


def test_chip_smoke_without_a_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_CHIPS="0")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": False, "device": None}


# ---------------------------------------------------------- chip detection

def _dev_tree(tmp_path, names):
    for name in names:
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.touch()
    return str(tmp_path)


@pytest.mark.parametrize("environ,files,chips", [
    ({"TPU_ACCELERATOR_TYPE": "v5litepod-1"}, [], 1),
    ({"TPU_ACCELERATOR_TYPE": "v5litepod-4"}, [], 4),
    ({}, [], 0),
    # a bare platform setting says nothing about chips
    ({"JAX_PLATFORMS": "tpu"}, [], 0),
    # 16 chips on 4 hosts; v5p counts TensorCores, two to a chip
    ({"TPU_ACCELERATOR_TYPE": "v5litepod-16",
      "TPU_WORKER_HOSTNAMES": "a,b,c,d"}, [], 4),
    ({"TPU_ACCELERATOR_TYPE": "v5p-8"}, [], 4),
    # the device files are what the host really has: the one-chip machine
    # of PR 21 said v5litepod-4 and held /dev/vfio/1 alone
    ({"TPU_ACCELERATOR_TYPE": "v5litepod-4"},
     ["vfio/1", "vfio/vfio"], 1),
    ({"TPU_TOPOLOGY": "2x2"},
     ["vfio/0", "vfio/1", "vfio/2", "vfio/3", "vfio/vfio"], 4),
    # a vfio group may as well be a NIC or a GPU bound to vfio-pci: it
    # counts only where the environment names a TPU
    ({}, ["vfio/0", "vfio/1", "vfio/vfio"], 0),
    ({"JAX_PLATFORMS": "tpu"}, ["vfio/0", "vfio/vfio"], 0),
    ({}, ["accel0", "accel1"], 2),
    ({"TPU_CHIPS": "0", "TPU_ACCELERATOR_TYPE": "v5litepod-4"},
     ["vfio/0"], 0),
])
def test_detect_tpu_chips(tmp_path, environ, files, chips):
    root = _dev_tree(tmp_path, files)
    assert res._detect_tpu_chips(environ, dev_root=root) == chips


def test_tpu_process_env():
    # one chip: its own 1x1x1 slice; the whole host: the host's settings
    assert res.tpu_process_env([2]) == {
        "TPU_VISIBLE_CHIPS": "2", "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1"}
    assert res.tpu_process_env([0, 1, 2, 3]) == {
        "TPU_VISIBLE_CHIPS": "0,1,2,3"}


# ------------------------------------------------- one process per chip

def test_worker_jax_platforms(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert res.worker_jax_platforms(leases_tpu=False) == "cpu"
    assert res.worker_jax_platforms(leases_tpu=True) == "tpu,cpu"


def test_worker_env_follows_its_lease_on_a_tpu_node(monkeypatch):
    """On a node that HAS chips, a worker whose class leases none starts
    with JAX_PLATFORMS=cpu; one whose class leases a chip inherits the
    driver's setting."""
    from ray_tpu.core import head as head_mod

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    seen = []
    monkeypatch.setattr(
        head_mod.subprocess, "Popen",
        lambda cmd, env, **kw: seen.append(env) or types.SimpleNamespace())
    ray_tpu.shutdown()  # own cluster: this node advertises four chips
    ray_tpu.init(num_cpus=1, num_tpus=4,
                 _system_config={"prestart_workers": False})
    try:
        from ray_tpu.core import api as _api

        head = _api._head
        node = next(iter(head.nodes.values()))
        cpu_w = head_mod.WorkerInfo(worker_id="c" * 32, node_idx=node.idx)
        tpu_w = head_mod.WorkerInfo(worker_id="t" * 32, node_idx=node.idx,
                                    tpu=True)
        head._popen_worker(node, cpu_w)
        head._popen_worker(node, tpu_w)
    finally:
        ray_tpu.shutdown()
    assert [e["JAX_PLATFORMS"] for e in seen] == ["cpu", "tpu,cpu"]


def _wait_gone(node, pid, timeout_s=30.0):
    """True once no live worker of ``node`` has ``pid`` (the driver gives
    a lease back a moment after its last task)."""
    import time

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if all(w.pid != pid for w in list(node.workers.values())):
            return True
        time.sleep(0.1)
    return False


@pytest.mark.parametrize("num_tpus", [1, 0.5])
def test_tpu_lease_spawns_a_tpu_worker_and_disposes_it(num_tpus):
    """A TPU task runs in a worker started for a TPU class, CPU tasks on
    the same node never do, and a worker that could see the TPU is
    disposed, not pooled, once its lease is returned: also one whose
    fractional lease was handed no chip ids."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=2)
    try:
        @ray_tpu.remote(num_tpus=num_tpus, num_cpus=0)
        def on_chip():
            # the chips are exported before anything could import jax
            assert "jax" not in sys.modules
            return os.getpid(), os.environ.get("TPU_VISIBLE_CHIPS")

        @ray_tpu.remote
        def on_cpu():
            return os.getpid(), os.environ["JAX_PLATFORMS"]

        tpu_pid, chips = ray_tpu.get(on_chip.remote(), timeout=120)
        cpu = ray_tpu.get([on_cpu.remote() for _ in range(4)], timeout=120)
        assert chips in (("0", "1") if num_tpus == 1 else (None,))
        assert all(plat == "cpu" and pid != tpu_pid for pid, plat in cpu)
        from ray_tpu.core import api as _api

        node = next(iter(_api._head.nodes.values()))
        assert _wait_gone(node, tpu_pid), "TPU worker still registered"
        idle = [wid for lst in node.idle_by_class.values() for wid in lst]
        assert all(not node.workers[wid].tpu for wid in idle
                   if wid in node.workers)
        assert not any(w.tpu for w in node.workers.values()
                       if w.pid in {pid for pid, _ in cpu})
    finally:
        ray_tpu.shutdown()


def test_driver_reads_device_arrays_on_the_cpu(tmp_path):
    """A driver leases nothing. Reading a `jax.Array` result imports JAX
    in it, and that must hold it to the CPU whatever the host's
    JAX_PLATFORMS says (the chip machine says "tpu,cpu"), never start the
    TPU backend: on a TPU host that would take a chip from the workers,
    and here it would hang looking for one."""
    script = tmp_path / "driver.py"
    script.write_text(f"""
import sys
sys.path.insert(0, {REPO!r})
import ray_tpu
ray_tpu.init(num_cpus=1, num_tpus=0)

@ray_tpu.remote
def f():
    import jax.numpy as jnp
    return jnp.arange(8)

assert "jax" not in sys.modules
x = ray_tpu.get(f.remote(), timeout=60)
import jax
print(type(x).__name__, jax.config.jax_platforms,
      sorted(d.platform for d in x.devices()), int(x.sum()))
ray_tpu.shutdown()
""")
    env = dict(os.environ, JAX_PLATFORMS="tpu,cpu", TPU_CHIPS="0")
    proc = subprocess.run([sys.executable, str(script)], cwd=str(tmp_path),
                          env=env, capture_output=True, text=True,
                          timeout=90)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == \
        "ArrayImpl cpu ['cpu'] 28"


# ------------------------------------------------------ compilation cache

def test_compile_cache_left_alone_when_placed_from_outside(monkeypatch):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
