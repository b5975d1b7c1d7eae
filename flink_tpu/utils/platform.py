"""Process set-up shared by the executable entry points.

JAX reads ``JAX_PLATFORMS`` itself; the one thing every entry point
(``python -m flink_tpu``, ``bench.py``, ``chip_smoke.py``,
``__graft_entry__.py``, cluster workers via ``__main__``) still has to do
before its first backend use is place the persistent compilation cache:
every jitted step is static on its operator instance, so without a cache
each process recompiles each step from nothing.
"""

from __future__ import annotations

import os

#: the default cache lives inside the checkout at a FIXED path — the
#: directory is part of what a later run has to find again, so it is never
#: derived from tempfile, a pid or the clock
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and no
    directory is set in code; otherwise the fixed in-checkout default is.
    Takes effect only when called before the process's first compilation
    (JAX initialises the cache once)."""
    import jax

    # cache the sub-second compiles too: a warm run must compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
