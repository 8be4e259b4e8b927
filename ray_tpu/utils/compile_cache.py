"""Where JAX's persistent compilation cache lives.

Every process that compiles (trainer workers, serve replicas, the test
session, the serving bench) calls `enable_compile_cache()` before its
first compile; nothing calls it at import. The directory is placed from
outside through ``JAX_COMPILATION_CACHE_DIR`` (workers inherit the
driver's environment, so it reaches them); only when that is unset does
the program pick one, and then a fixed path — the path is part of the
cache key, so a directory named from a pid, a time or a temporary name
would never hit.

The same call installs the process's compile ledger (`compile_ledger`,
`compile_log`): how many programs this process asked the backend for, how
many of them the persistent cache answered, and the seconds each part
took. It is what tells a deploy that compiled from one that loaded.
"""

from __future__ import annotations

import collections
import os
import threading
import time
import weakref
from typing import Dict, List, Optional

_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")

# One lock over the totals, the log and the followers.
# compile_requests: programs this process asked the backend for (`_BACKEND`
# fires for one that was compiled and for one the persistent cache held
# alike) = programs_loaded (the cache answered: `_HIT` inside the request)
# + programs_compiled (the rest: one the cache was never asked for counts
# here). compile_wait_s: the seconds callers waited for them, of which
# cache_load_s were reads of the cache. trace_lower_s: seconds tracing and
# lowering before the backend is asked, an outermost trace or lowering
# alone (a traced function that calls a jitted one traces it inside its
# own seconds).
_lock = threading.Lock()
_ledger = {"compile_requests": 0, "compile_wait_s": 0.0,
           "programs_loaded": 0, "programs_compiled": 0,
           "cache_load_s": 0.0, "trace_lower_s": 0.0}
_log: collections.deque = collections.deque(maxlen=256)
# owner -> the dict that mirrors the totals while the owner lives
_followers: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_installed = False
# the request / the trace this thread is inside: JAX states a region's
# start as a scalar and its end as a duration, both on the caller's thread
_thread = threading.local()


def _add(**amounts):
    """Under `_lock`: the totals and every follower's copy of them."""
    for key, amount in amounts.items():
        _ledger[key] += amount
    for stats in list(_followers.values()):
        for key in amounts:
            stats[key] = _ledger[key]


def _on_event(event, **_):
    if event == _HIT:
        _thread.hit = True


def _on_start(event, _value, **_):
    if event == _BACKEND:
        _thread.hit = False
    elif event in _TRACE_LOWER:
        _thread.depth = getattr(_thread, "depth", 0) + 1


def _on_duration(event, seconds, **meta):
    if event == _BACKEND:
        loaded = getattr(_thread, "hit", False)  # `_on_start` reset it
        with _lock:
            _add(compile_requests=1, compile_wait_s=seconds,
                 **{"programs_loaded" if loaded else "programs_compiled": 1})
            _log.append({"fun_name": str(meta.get("fun_name", "")),
                         "wall_s": seconds, "loaded": loaded,
                         "t_unix": time.time()})
    elif event == _LOAD:
        with _lock:
            _add(cache_load_s=seconds)
    elif event in _TRACE_LOWER:
        _thread.depth = depth = max(getattr(_thread, "depth", 1) - 1, 0)
        if not depth:
            with _lock:
                _add(trace_lower_s=seconds)


def install_compile_ledger() -> None:
    """One set of `jax.monitoring` listeners a process, however often this
    is called (`enable_compile_cache` does; so does whatever follows the
    ledger). Before the first compile, or the ledger misses what ran
    before it."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_start)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_ledger() -> Dict[str, float]:
    """A copy of this process's totals (the keys above `_ledger`)."""
    with _lock:
        return dict(_ledger)


def compile_log(last: int = 256) -> List[dict]:
    """The newest ``last`` (of at most 256) requests to the backend,
    oldest first: ``{"fun_name", "wall_s", "loaded", "t_unix"}``. Which
    program compiled again."""
    with _lock:
        return [dict(entry) for entry in list(_log)[-int(last):]]


def follow_compile_ledger(owner, stats: dict) -> None:
    """``stats`` gets the ledger's six keys, holding the PROCESS's totals
    (what compiled before this call is in them), and the listeners keep
    them current for as long as ``owner`` lives: ``stats`` is found through
    a weak reference to ``owner``, since a plain dict takes none."""
    install_compile_ledger()
    with _lock:
        stats.update(_ledger)
        _followers[owner] = stats


def enable_compile_cache() -> Optional[str]:
    """Point JAX at the persistent cache; returns the directory this call
    set, or None when ``JAX_COMPILATION_CACHE_DIR`` already says where
    (JAX reads that variable itself, so no directory is set here);
    otherwise ``<checkout>/.jax_cache``, which .gitignore lists. Installs
    the compile ledger on the way, wherever the cache is.

    Of the cache, only the place is decided here. Which programs are worth
    keeping (``jax_persistent_cache_min_compile_time_secs``, a second by
    default) is the caller's to say: a serve replica keeps everything, the
    test session keeps its thousands of trivial programs out."""
    install_compile_ledger()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    import ray_tpu

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(ray_tpu.__file__))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
