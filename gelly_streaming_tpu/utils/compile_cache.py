"""Where this program keeps JAX's persistent compilation cache.

The forest step is jitted per pow2 ``(tcap, wcap, vcap)`` bucket and the
serving kernels per pow2 batch, so a process compiles a handful of
programs and every later process with the same shapes can load them
instead. The cache key includes the cache directory's path, so a
directory that moves between runs never hits: the location is either
the one the environment names or ONE fixed directory inside the
checkout — never a path built from ``tempfile``, a pid or the clock.

Every process entry point calls :func:`enable_compile_cache` before its
first jit (``chip_smoke.py``, ``bench.py``, the example CLIs, the
replica / router / chaos worker mains). A process pinned to the CPU gets
no cache: its programs compile in milliseconds, and XLA:CPU logs a
machine-feature mismatch for every cached executable it loads.
"""

from __future__ import annotations

import os
from typing import Optional

#: the fixed in-checkout location (listed in ``.gitignore``): the
#: directory that holds the ``gelly_streaming_tpu`` package
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> Optional[str]:
    """Place the persistent compilation cache and return its directory
    (None for a CPU-pinned process, which keeps none).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing. Where it is not, ``jax_compilation_cache_dir``
    points at :data:`DEFAULT_CACHE_DIR`, and every program is cached
    whatever its compile time (JAX's default skips programs that compile
    in under a second, which is most of the serving kernels), so a second
    process with the same shapes compiles nothing.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    if jax.config.jax_platforms == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_CACHE_DIR


def cache_entry_count(cache_dir: Optional[str]) -> int:
    """Number of compiled programs stored under ``cache_dir`` (0 when
    there is no cache or the directory does not exist yet)."""
    if cache_dir is None:
        return 0
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
