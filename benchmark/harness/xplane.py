"""Reduction of a JAX profiler trace (`.xplane.pb`) to what the per-layer
metrics read: device busy and idle time, time per operation (self time, so
a `while` does not count its body twice), collective time no compute
covers, and the longest idle gaps named by the host span they fall in
(the benchmark's `bench:` spans around the train loop, the serving
engine's own `engine.*` spans).

Pure functions over (name, start_ns, duration_ns) tuples, plus one loader
that needs nothing but JAX (`jax.profiler.ProfileData`). A train cell's
worker runs `reduce_trace` on its own trace and ships the small result; a
serve cell's profile is reduced by this file run as a program, a child of
the driver process (`python benchmark/harness/xplane.py <trace_dir>` prints
`reduce_trace`'s result as one line of JSON;
`serve_cell.reduce_trace_outside`), never by the replica. Checked against
`benchmark/fixtures/*.xplane.pb`, recorded on the chip.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]           # [start_ns, end_ns)
Event = Tuple[str, int, int]         # (name, start_ns, duration_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
BENCH_SPAN = "bench:"                # the benchmark's own host spans
SPAN_PREFIXES = (BENCH_SPAN, "engine.")   # ... and the serving engine's
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
CONTROL_FLOW = re.compile(r"^(while|conditional|call)(\.|$)")
UNATTRIBUTED = "no_bench_span"


# ---- interval arithmetic --------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of union ``a`` not covered by union ``b`` (both sorted,
    disjoint)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(events: Sequence[Event]) -> List[Tuple[str, int]]:
    """(name, self_ns) per event of ONE line whose events nest properly:
    an event's self time is its duration minus its direct children's."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [ev[2] for ev in events]
    stack: List[int] = []
    for i in order:
        _, s, d = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= d
        stack.append(i)
    return [(events[i][0], max(0, self_ns[i])) for i in range(len(events))]


# ---- names ----------------------------------------------------------------

KERNEL_TAG = "tpu_custom_call"        # a Mosaic (Pallas) kernel's target


def op_key(name: str) -> str:
    """A short key for a device operation: the HLO instruction name without
    its leading '%', as far as the first ' = ' or '('. A Mosaic kernel is
    a `custom-call` whose instruction name comes from whatever JAX scope
    wrapped it (`closed_call.9`, `checkpoint.20`, ...), so its key is
    prefixed with its call target: `tpu_custom_call:closed_call.9`."""
    text = name.strip().lstrip("%")
    key = re.split(r" = |\(", text, maxsplit=1)[0].strip()[:96] or "unnamed"
    if f'custom_call_target="{KERNEL_TAG}"' in text:
        key = f"{KERNEL_TAG}:{key}"
    return key


_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="([^"]*)"')


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """{operation key: scope path} from a compiled program's text: the
    `op_name` of each instruction's metadata, which holds the
    `jax.named_scope`s the instruction was traced under
    (`jit(step)/transpose(jvp())/while/body/.../moe.dispatch/gather`; a
    fusion carries its root's). The trace names a device operation by its
    instruction (`op_key`), and instruction names change with any edit of
    the program or the compiler; scopes are names the program chose."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.search(line)
        if m and " = " in line:
            out[op_key(re.sub(r"^\s*ROOT\s+", "", line))] = m.group(1)
    return out


def scope_seconds_matching(reduced: dict, scopes: Dict[str, str],
                           pattern: str) -> float:
    """Self time of the operations whose scope path ``pattern`` is found
    in (searched anywhere: a backward or rematerialised operation carries
    its scope inside `transpose(jvp(...))` and `checkpoint` wrappers)."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced.get("op_seconds", {}).items()
               if rx.search(scopes.get(k, "")))


# ---- reduction ------------------------------------------------------------

def reduce_planes(planes: Dict[str, Dict[str, List[Event]]], *,
                  window: Optional[Interval] = None,
                  min_gap_ns: int = 20_000, top: int = 10) -> dict:
    """``planes``: {plane name: {line name: [(name, start_ns, dur_ns)]}}.

    Device planes are those named `/device:TPU:<n>`; their `XLA Ops` line
    holds one event per executed HLO instruction. Host planes contribute
    the benchmark's spans (`bench:<name>`, named without the prefix) and
    the program's (`engine.<state>`). ``window`` (ns) restricts
    everything to the traced steady window; default: from the first to the
    last device event.
    """
    dev = {p: lines for p, lines in planes.items()
           if DEVICE_PLANE.match(p) and lines.get(OP_LINE)}
    if not dev:
        return {"devices": 0}
    if window is None:
        # whole executions where the trace has them (a program's event
        # starts before its first and ends after its last operation)
        evs = [ev for l in dev.values()
               for ev in (l.get(MODULE_LINE) or l[OP_LINE])]
        window = (min(ev[1] for ev in evs), max(ev[1] + ev[2] for ev in evs))
    lo, hi = window
    spans = sorted((s, s + d, n.removeprefix(BENCH_SPAN))
                   for p, lines in planes.items() if p not in dev
                   for evs in lines.values() for n, s, d in evs
                   if n.startswith(SPAN_PREFIXES))
    host_spans: Dict[str, Dict[str, float]] = {}
    for s, e, name in spans:
        if e > lo and s < hi:
            h = host_spans.setdefault(name, {"count": 0, "seconds": 0.0})
            h["count"] += 1
            h["seconds"] += (min(e, hi) - max(s, lo)) / 1e9

    busy_s, exposed_s = [], []
    op_ns: Dict[str, int] = {}
    op_count: Dict[str, int] = {}
    gaps: Dict[str, int] = {}
    modules: Dict[str, List[int]] = {}
    for plane in sorted(dev):
        events = [ev for ev in dev[plane][OP_LINE]
                  if ev[1] + ev[2] > lo and ev[1] < hi]
        busy = clip(union((s, s + d) for _, s, d in events), lo, hi)
        busy_s.append(total(busy) / 1e9)
        coll = clip(union((s, s + d) for n, s, d in events
                          if COLLECTIVE.match(op_key(n))), lo, hi)
        compute = clip(union(
            (s, s + d) for n, s, d in events
            if not COLLECTIVE.match(op_key(n))
            and not CONTROL_FLOW.match(op_key(n))), lo, hi)
        exposed_s.append(total(subtract(coll, compute)) / 1e9)
        for name, ns in self_times(events):
            key = op_key(name)
            op_ns[key] = op_ns.get(key, 0) + ns
            op_count[key] = op_count.get(key, 0) + 1
        for s, e in subtract([(lo, hi)], busy):
            if e - s < min_gap_ns:
                continue
            owner = _owner(spans, s, e)
            gaps[owner] = gaps.get(owner, 0) + (e - s)
        for name, s, d in dev[plane].get(MODULE_LINE, ()):
            if lo <= s < hi:     # executions that START inside the window
                modules.setdefault(op_key(name), []).append(d)
    n = len(dev)
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / n,
        "busy_s_per_device": busy_s,
        "collective_exposed_s": sum(exposed_s) / n,
        # per-operation self time, summed over devices then averaged
        "op_seconds": {k: v / 1e9 / n for k, v in op_ns.items()},
        "op_count": {k: c / n for k, c in op_count.items()},
        "device_ops": [[k, v / 1e9 / n] for k, v in sorted(
            op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9 / n] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:top]],
        "modules": {k: {"count": len(v) / n, "seconds": sum(v) / 1e9 / n}
                    for k, v in modules.items()},
        # the host spans inside the window, by name
        "host_spans": host_spans,
    }


def _owner(spans, s: int, e: int) -> str:
    """Name of the innermost host span covering most of [s, e)."""
    best, best_cover, best_len = UNATTRIBUTED, 0, None
    for ss, se, name in spans:
        if ss >= e:
            break
        cover = min(e, se) - max(s, ss)
        if cover <= 0:
            continue
        if cover > best_cover or (cover == best_cover
                                  and se - ss < best_len):
            best, best_cover, best_len = name, cover, se - ss
    return best


def op_seconds_matching(reduced: dict, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(v for k, v in reduced.get("op_seconds", {}).items()
               if rx.search(k))


def module_executions(reduced: dict, pattern: str) -> dict:
    """{"count", "seconds"} of the programs whose name matches, per
    device: `jit_step(123...)` is keyed `jit_step`."""
    rx = re.compile(pattern)
    mods = [v for k, v in (reduced.get("modules") or {}).items()
            if rx.search(k)]
    return {"count": sum(v["count"] for v in mods),
            "seconds": sum(v["seconds"] for v in mods)}


# ---- loading --------------------------------------------------------------

def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_planes(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """Planes of an `.xplane.pb` (or `.xplane.pb.gz`) as plain tuples. Of
    host planes only the `bench:` and `engine.` spans are kept (a host
    plane holds every Python frame)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            if is_dev and line.name not in (OP_LINE, MODULE_LINE):
                continue
            evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                   for ev in line.events
                   if is_dev or ev.name.startswith(SPAN_PREFIXES)]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        if lines:
            planes[plane.name] = lines
    return planes


def describe(path: str, top: int = 12) -> dict:
    """What a trace holds, for a human looking at one for the first time:
    every plane, its lines, event counts and the commonest names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            names: Dict[str, int] = {}
            first = last = None
            n = 0
            for ev in line.events:
                n += 1
                names[ev.name[:120]] = names.get(ev.name[:120], 0) + 1
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                first = s if first is None else min(first, s)
                last = e if last is None else max(last, e)
            lines[line.name] = {
                "events": n, "first_ns": first, "last_ns": last,
                "top": sorted(names.items(), key=lambda kv: -kv[1])[:top]}
        out[plane.name] = lines
    return out


def reduce_trace(trace_dir: str, **kw) -> dict:
    path = find_xplane(trace_dir)
    if path is None:
        return {"devices": 0}
    red = reduce_planes(load_planes(path), **kw)
    red["xplane_bytes"] = os.path.getsize(path)
    return red


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(reduce_trace(sys.argv[1])))
