"""`counter_bytes_roofline` for a kernel that more than ONE of the served
programs runs, where the counter counts one program's units: the least
time for the bytes a counter of `engine.stats` says were moved, by the
peaks table, over the device time of the operations whose name matches
`metric["op_pattern"]` INSIDE the executions of the programs whose name
matches `metric["module_pattern"]`, both over the traced seconds. (A
decode substep's grouped matmuls over three rows an expert are bound by
the experts' weights; a prefill's over a hundred rows an expert are the
same kernels under the same names and are bound by the MXU: their time
has no place under the decode counter's bytes.)

The reduced trace (`harness/xplane.reduce_planes`) sums an operation's
time over every program that runs it, so this reader goes back to the
trace's file, `<root>/.bench_out/trace/<cell>/plugins/profile/*/
*.xplane.pb`, which the traced run leaves on this machine: the device
planes' `XLA Modules` line gives the programs' executions, the `XLA Ops`
line the operations, and an operation belongs to the execution it starts
in. The file is read with the protobuf schema that ships beside the
profiler (loaded by its path: the driver process imports neither JAX nor
TensorFlow). No file, no schema, no counter or no such kernel in such a
program: nothing to read.
"""
import gzip
import importlib.util
import os
import re

from benchmark.harness import flops, spec, xplane


def _schema():
    """`xplane_pb2`, found beside the installed profiler and loaded by its
    path (it needs `google.protobuf` alone), or None."""
    for package in ("tensorflow", "xprof", "tensorboard_plugin_profile"):
        try:
            found = importlib.util.find_spec(package)
        except (ImportError, ValueError):
            continue
        if found is None or not found.origin:
            continue
        for sub in ("tsl/profiler/protobuf", "protobuf"):
            path = os.path.join(os.path.dirname(found.origin), sub,
                                "xplane_pb2.py")
            if not os.path.exists(path):
                continue
            try:
                modspec = importlib.util.spec_from_file_location(
                    "benchmark_xplane_pb2", path)
                mod = importlib.util.module_from_spec(modspec)
                modspec.loader.exec_module(mod)
                return mod
            except Exception:  # noqa: BLE001 — another schema may load
                continue
    return None


def seconds_in_modules(path: str, op_pattern: str, module_pattern: str):
    """Device seconds (mean over the device planes) of the operations
    matching ``op_pattern`` (by `xplane.op_key`) that start inside an
    execution of a program matching ``module_pattern``; None where the
    file or the schema is missing."""
    schema = _schema()
    if schema is None or not path or not os.path.exists(path):
        return None
    space = schema.XSpace()
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        space.ParseFromString(f.read())
    op_rx, mod_rx = re.compile(op_pattern), re.compile(module_pattern)
    per_device = []
    for plane in space.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if xplane.OP_LINE not in lines or xplane.MODULE_LINE not in lines:
            continue
        names = plane.event_metadata

        def events(line):
            base = line.timestamp_ns * 1000
            return [(names[ev.metadata_id].name, base + ev.offset_ps,
                     ev.duration_ps) for ev in line.events]
        runs = sorted((s, s + d) for n, s, d in
                      events(lines[xplane.MODULE_LINE]) if mod_rx.search(n))
        keyed, ps, i = {}, 0, 0
        for name, s, d in sorted(events(lines[xplane.OP_LINE]),
                                 key=lambda ev: ev[1]):
            while i < len(runs) and runs[i][1] <= s:
                i += 1
            if i == len(runs):
                break
            if s < runs[i][0]:
                continue
            if name not in keyed:
                keyed[name] = bool(op_rx.search(xplane.op_key(name)))
            if keyed[name]:
                ps += d
        per_device.append(ps / 1e12)
    return sum(per_device) / len(per_device) if per_device else None


def read(evidence, metric):
    trace, peaks = evidence["trace"], evidence.get("peaks")
    units = (trace.get("engine_in_trace") or {}).get(metric["counter"])
    if not units or not peaks:
        return None
    path = xplane.find_xplane(os.path.join(
        evidence["root"], ".bench_out", "trace", evidence["cell"]["name"]))
    k = seconds_in_modules(path, metric["op_pattern"],
                           metric["module_pattern"])
    if not k:
        return None
    per_unit = spec._load_module("readers", "counter_bytes_roofline",
                                 evidence["root"]).per_unit
    f, conf = evidence["fields"], evidence["conf"]
    least = flops.roofline_seconds(
        units * per_unit(metric.get("flops_per_unit", [0]), f, conf),
        units * per_unit(metric["bytes_per_unit"], f, conf), peaks)
    if least["bound"] != metric["bound"]:
        raise ValueError(f"{metric.get('name')}: the metric file says the "
                         f"{metric['bound']} bound applies, the peaks "
                         f"table says {least['bound']}")
    return 100.0 * least["seconds"] / k
