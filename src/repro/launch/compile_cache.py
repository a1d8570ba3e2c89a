"""Where the persistent JAX compilation cache lives.

Compilation is the largest one-time cost of the solver's engines, and a
persistent cache only hits when its directory stays put.  Every entry
point (``chip_smoke.py``, ``repro.launch.serve``, the benchmarks and the
examples) enables the cache through :func:`enable_compilation_cache`, so
one rule places it:

* ``$JAX_COMPILATION_CACHE_DIR`` when it is set — the deployment's choice,
  which no flag overrides;
* else ``<repo>/.jax_cache``, resolved from this file's location, so every
  working directory shares one cache.

A caller may still turn the cache off (``enabled=False``): a cold-compile
measurement needs that.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the fallback cache directory: ``.jax_cache`` at the repository root
REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def compilation_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set and non-empty, else
    :data:`REPO_CACHE_DIR`."""
    return os.environ.get(ENV_VAR) or REPO_CACHE_DIR


def enable_compilation_cache(enabled: bool = True) -> str | None:
    """Point JAX's persistent compilation cache at
    :func:`compilation_cache_dir` and return that directory, or turn the
    cache off and return None (``enabled=False``).  Call it before the
    first compile."""
    import jax

    if not enabled:
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    path = compilation_cache_dir()
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    # cache even fast compiles: a solver process builds many small programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
