"""Shared by the benchmark's tests: the checkout root on `sys.path` (the
`benchmark` package lives there) and the loaded `BENCHMARK.json`."""

import contextlib
import importlib.util
import io
import json
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


def run_cell_with_info(run, bench, cell, args, **kw):
    """`run.run_cell(...)` -> (its last line, the information line it
    printed for the cell)."""
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        line = run.run_cell(bench, cell, args, **kw)
    info = [json.loads(l) for l in printed.getvalue().splitlines()
            if l.startswith("{")]
    return line, next(i for i in info if i.get("cell") == cell["name"])
