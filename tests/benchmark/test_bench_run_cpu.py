"""`benchmark/run.py` refuses to measure without a TPU: non-zero exit, no
result line on stdout, `correct: false` with the reason on stderr."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_paths
from benchmark.harness import spec

BENCH = spec.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]


def _run(cwd, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_no_tpu_is_a_failure_never_a_cpu_run(cell, trace):
    p = _run(bench_paths.REPO, "--workload", cell, "--seed", "2147483659",
             "--seconds", "1", "--trace", trace)
    assert p.returncode != 0
    assert p.stdout.strip() == ""          # no result line
    last = json.loads(p.stderr.strip().splitlines()[-1])
    assert last["correct"] is False and last["metrics"] == {}
    assert "TPU" in last["error"]


def test_an_unknown_workload_is_a_failure():
    p = _run(bench_paths.REPO, "--workload", "no-such.cell", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no workload" in p.stderr


def test_benchmark_alone_in_a_directory_fails_without_a_result(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: no program to
    measure, so non-zero and nothing on stdout."""
    shutil.copy(os.path.join(bench_paths.REPO, "BENCHMARK.json"), tmp_path)
    for top in BENCH["paths"]:
        shutil.copytree(os.path.join(bench_paths.REPO, top),
                        tmp_path / top,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), "--workload", CELLS[0], "--seed", "1",
             "--seconds", "1", "--trace", "0",
             env_extra={"JAX_PLATFORMS": "", "PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_bench_run_environment_variable_is_ignored():
    p = _run(bench_paths.REPO, "--workload", CELLS[0], "--seed", "3",
             "--seconds", "1", "--trace", "0",
             env_extra={"BENCH_RUN": "change-3"})
    assert p.returncode != 0 and p.stdout.strip() == ""
