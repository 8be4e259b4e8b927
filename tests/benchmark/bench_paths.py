"""Shared by the benchmark's tests: the checkout root on `sys.path` (the
`benchmark` package lives there) and the loaded `BENCHMARK.json`."""

import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def load_run_module(root=REPO, name="benchmark_run_py"):
    """benchmark/run.py as a module (it is a script, not a package
    member)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
