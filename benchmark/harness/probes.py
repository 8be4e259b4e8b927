"""Small probes shared by the processes that hold a chip: the device's
description, a count of compilations since a mark, memory readings.
Imports JAX: never import this module in the driver process."""

from __future__ import annotations

import threading

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def device_description() -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """`peak_bytes_in_use` on the fullest device; 0 where the backend
    reports none (the CPU)."""
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts programs compiled (or loaded from the persistent cache:
    both stall whoever waits) in this process, via `jax.monitoring`. One
    listener per process, registered once; `mark()` then `since_mark()`."""

    _lock = threading.Lock()
    _count = 0
    _installed = False

    @classmethod
    def install(cls):
        with cls._lock:
            if cls._installed:
                return
            cls._installed = True
        jax.monitoring.register_event_duration_secs_listener(cls._on_event)

    @classmethod
    def _on_event(cls, name, _duration, **_kw):
        if name == COMPILE_EVENT:
            with cls._lock:
                cls._count += 1

    def __init__(self):
        self.install()
        self._mark = self._count

    def mark(self):
        self._mark = self._count

    def since_mark(self) -> int:
        return self._count - self._mark


def trace_options():
    """Profiler options of every trace the benchmark takes: device events
    and `TraceAnnotation` host spans, no Python frames and no HLO dump
    (they make the file several times larger and the reduction reads
    neither)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts
