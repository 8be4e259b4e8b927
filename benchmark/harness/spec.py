"""Where the benchmark's data files are and what they mean.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found BY NAME from `BENCHMARK.json`:

    benchmark/configs/<config>.json          sizes as run, source, cuts
    benchmark/traffic/<traffic>.json         kind + parameters of a mix
    benchmark/layer_metrics/<metric>.json    layer, moves, reader
    benchmark/readers/<reader>.py            one function: evidence -> number
    benchmark/architectures/<name>.py        plain reference + required work

so a later PR adds a cell or a metric by adding files and one entry, and
edits nothing here. Which cells report a metric is said in ONE place, the
`workloads` list of its entry in `BENCHMARK.json`: a metric's file holds
none, so a new cell joins a metric that is there by a list entry alone.
This module imports neither JAX nor the program: the driver process reads
it and must stay off the chip.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
TRAFFIC_KINDS = ("train", "open_loop", "closed_loop")
# the block a config file means when it names no `architecture`
DEFAULT_ARCHITECTURE = "dense_gqa"
ARCHITECTURE_INTERFACE = ("reference_logits", "forward_flops_per_token",
                          "num_params")


class SpecError(ValueError):
    """A data file is missing, malformed or names something unknown."""


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing benchmark file {path}") from e


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def bench_dir(root: str = ROOT) -> str:
    return os.path.join(root, "benchmark")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                    f"{[c['name'] for c in bench['workloads']]}")


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == name:
            conf = _read_json(os.path.join(root, entry["file"]))
            conf["name"] = name
            return conf
    raise SpecError(f"no config {name!r} in BENCHMARK.json")


def load_traffic(name: str, root: str = ROOT) -> dict:
    t = _read_json(os.path.join(bench_dir(root), "traffic", name + ".json"))
    if t.get("kind") not in TRAFFIC_KINDS:
        raise SpecError(f"traffic {name!r}: kind must be one of "
                        f"{TRAFFIC_KINDS}, got {t.get('kind')!r}")
    t["name"] = name
    weights_seed(t)   # refused here where the mix states none
    return t


def weights_seed(traffic: dict) -> int:
    """The whole number a cell's weights are drawn from: a train mix
    states it at the top of its file, a serve mix under `deployment`. A
    deployment holds ONE checkpoint and what a run varies is its data, so
    `--seed` draws the traffic (batches, lengths, arrivals, prompts, the
    check's rows) and nothing of the model. No default: a mix that left
    the key out would come to follow `--seed` again."""
    held = traffic if traffic.get("kind") == "train" \
        else traffic.get("deployment") or {}
    seed = held.get("weights_seed")
    if not isinstance(seed, int) or isinstance(seed, bool) or seed < 0:
        where = "weights_seed" if held is traffic \
            else "deployment.weights_seed"
        raise SpecError(
            f"traffic {traffic.get('name')!r}: a mix states the "
            f"seed of its weights as a whole number under `{where}`, got "
            f"{seed!r}")
    return seed


def load_peaks(root: str = ROOT) -> dict:
    return _read_json(os.path.join(bench_dir(root), "peaks.json"))


def device_peaks(kind: str, root: str = ROOT) -> dict:
    """Peaks of one chip of ``kind``; a device the table does not list is
    an error, never a default."""
    table = load_peaks(root)["devices"]
    if kind not in table:
        raise SpecError(f"device_kind {kind!r} is not in benchmark/"
                        f"peaks.json (have {sorted(table)})")
    return table[kind]


def metrics_for(bench: dict, cell_name: str, group: str) -> list:
    """Entries of ``group`` ('end_to_end' or 'per_layer') that the cell
    reports: those without a `workloads` key, or that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_layer_metric(name: str, root: str = ROOT) -> dict:
    m = _read_json(os.path.join(bench_dir(root), "layer_metrics",
                                name + ".json"))
    m["name"] = name
    return m


@functools.lru_cache(maxsize=None)
def _load_module(kind: str, name: str, root: str):
    """benchmark/<kind>/<name>.py as a module, loaded by path so that a
    new one is a new file and nothing else (once per process: a
    reference keeps its jitted block between calls)."""
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"bad name {name!r} for a file under "
                        f"benchmark/{kind}/")
    path = os.path.join(bench_dir(root), kind, name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"missing file {path}")
    modspec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(mod)
    return mod


def load_reader(metric: dict, root: str = ROOT):
    """The function `read(evidence, metric) -> float | None` in
    benchmark/readers/<reader>.py."""
    return _load_module("readers", metric["reader"], root).read


def architecture_name(conf: dict) -> str:
    return conf.get("architecture", DEFAULT_ARCHITECTURE)


def load_architecture(conf: dict, root: str = ROOT):
    """The module benchmark/architectures/<name>.py that ``conf`` names
    under `architecture` (absent: the dense GQA block): the plain
    reference that decides `correct` for the configuration and the work
    its forward pass requires (benchmark/README.md gives the
    interface). The driver process loads it for the counts, so the
    module imports JAX only inside the functions that compute."""
    name = architecture_name(conf)
    mod = _load_module("architectures", name, root)
    lacks = [f for f in ARCHITECTURE_INTERFACE
             if not callable(getattr(mod, f, None))]
    if lacks:
        raise SpecError(f"architecture {name!r} ({mod.__file__}) lacks "
                        f"{lacks}")
    return mod


def read_layer_metrics(bench: dict, cell_name: str, evidence: dict,
                       root: str = ROOT) -> dict:
    """{name: {"value", "unit"}} for every per-layer metric of the cell
    whose reader found something to read (None -> left out)."""
    out = {}
    for entry in metrics_for(bench, cell_name, "per_layer"):
        metric = load_layer_metric(entry["name"], root)
        value = load_reader(metric, root)(evidence, metric)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


# ---- configuration -> TransformerConfig ----------------------------------

def transformer_fields(conf: dict, root: str = ROOT) -> dict:
    """TransformerConfig fields (plain Python values) from a config file:
    `mapping` says which published key feeds which field, unless the
    configuration's architecture module brings a `fields(conf)` of its
    own (published keys that do not map one to one)."""
    own = getattr(load_architecture(conf, root), "fields", None)
    if own is not None:
        fields = dict(own(conf))
        carried = "head_dim" in fields
    else:
        fields = {}
        for published, field in conf["mapping"].items():
            if published not in conf:
                raise SpecError(
                    f"config {conf.get('name')}: mapping names "
                    f"{published!r}, which the file does not hold")
            fields[field] = conf[published]
        carried = "head_dim" in conf["mapping"]
    head_dim = conf.get("head_dim")
    if head_dim is not None and not carried and \
            head_dim * fields["n_heads"] != fields["d_model"]:
        raise SpecError(
            f"config {conf.get('name')}: head_dim {head_dim} is not "
            "d_model / n_heads and nothing carries it to a field, so the "
            "program would derive another")
    return fields


def build_transformer_config(conf: dict, root: str = ROOT, **overrides):
    """The program's TransformerConfig for ``conf`` (imports the program,
    and with it JAX: call it only in a process that may hold the chip or
    in tests). dtype names in ``overrides`` are given as strings."""
    import jax.numpy as jnp

    from ray_tpu.models.config import TransformerConfig

    fields = transformer_fields(conf, root)
    for key, val in overrides.items():
        if key in ("dtype", "param_dtype") and isinstance(val, str):
            val = jnp.dtype(val)
        fields[key] = val
    return TransformerConfig(**fields)


def seed32(seed: int) -> int:
    """A whole-number seed of any size folded into what a JAX key and the
    program's `seed` arguments take (below 2**31)."""
    return int(seed) % 2_147_483_629


def dig(obj, path: str):
    """`dig(out, "client.ttft_ms")`: follow a dotted path through nested
    dicts; None where a step is missing."""
    for key in path.split("."):
        if not isinstance(obj, dict) or key not in obj:
            return None
        obj = obj[key]
    return obj
