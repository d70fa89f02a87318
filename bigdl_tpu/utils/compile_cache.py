"""Persistent XLA compile cache — the one place that says where it lives.

A ResNet-50 step or a 36-layer decode step compiles for tens of seconds;
a prior run of the same program turns that into a cache hit. The cache
directory is part of the cache key, so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it by itself and this
  module sets no directory in code — the cache is placed from outside.
* unset: ``<checkout>/.jax_cache``, fixed — never the cwd, a temp name,
  a pid or a time.

The key holds the HLO's metadata too (``op_name``: the path of
``jax.named_scope``s an operation was traced under, and its source
line). jax leaves it out by default, and an executable compiled before a
scope was renamed would then be handed back with the OLD names in it:
a profiler capture, and every reader of device time by scope
(``benchmark/program_scopes.py``), would see names the source no longer
has, or none. The price: an edit that only moves lines of traced code is
a new key too, and compiles once more.

Every entry point that compiles at real sizes (``bench.py``,
``chip_smoke.py``, the serving example, the perf CLI, ``tpu_sweep``,
``flash_matrix``) calls :func:`enable_persistent_cache` before its first
compile.
"""

from __future__ import annotations

import os

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_persistent_cache() -> str:
    """Turn the on-disk compile cache on and return its directory."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get(ENV_CACHE_DIR)
    if placed:
        return placed
    cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
