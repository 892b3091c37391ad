"""Persistent XLA compilation cache for the program's entry points.

The entry points (``launch/truss.py``, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`enable_compile_cache` before their first
compile; importing the library never does.  JAX reads
``JAX_COMPILATION_CACHE_DIR`` itself, so when that variable is set it is
the cache and nothing is set in code.  Otherwise the cache lives at one
fixed directory inside the checkout (``.jax_cache``, git-ignored): the
path is part of what makes a later process find the entries, so it is
never built from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

#: the variable JAX itself reads for its cache directory
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the fixed in-checkout fallback (``<repo>/.jax_cache``)
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir(environ=None) -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR``, else the fixed one."""
    env = os.environ if environ is None else environ
    return env.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory.

    Every program is cached, not only those that took over a second to
    compile (JAX's default): a cold decomposition compiles a dozen small
    programs, and together they cost as much as the large ones.
    """
    import jax

    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
