"""Where JAX's persistent compilation cache lives.

Every process that compiles (trainer workers, serve replicas, the test
session, the serving bench) calls `enable_compile_cache()` before its
first compile; nothing calls it at import. The directory is placed from
outside through ``JAX_COMPILATION_CACHE_DIR`` (workers inherit the
driver's environment, so it reaches them); only when that is unset does
the program pick one, and then a fixed path — the path is part of the
cache key, so a directory named from a pid, a time or a temporary name
would never hit.
"""

from __future__ import annotations

import os
from typing import Optional


def enable_compile_cache() -> Optional[str]:
    """Point JAX at the persistent cache; returns the directory this call
    set, or None when ``JAX_COMPILATION_CACHE_DIR`` already says where
    (JAX reads that variable itself, so no directory is set here);
    otherwise ``<checkout>/.jax_cache``, which .gitignore lists.

    Only the place is decided here. Which programs are worth keeping
    (``jax_persistent_cache_min_compile_time_secs``, a second by default)
    is the caller's to say: a serve replica keeps everything, the test
    session keeps its thousands of trivial programs out."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    import ray_tpu

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(ray_tpu.__file__))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
