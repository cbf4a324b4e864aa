"""JAX's persistent compilation cache, at a place that outlives the process.

The fused kernels take seconds to compile on a TPU (tens of seconds for a
wide vHGW ladder), and every process starts with no compiled programs. The
cache keys on its directory, so the directory must not move between runs:
it never comes from a temporary name, a pid or the time.

Entry points call :func:`enable_compile_cache` (``chip_smoke.py``, the
ingress worker's ``main``, ``benchmarks/common.py``); importing ``repro``
never does, so a library import changes no global JAX state.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (gitignored): this file is <checkout>/src/repro/
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other path; otherwise the cache goes to the fixed
    :data:`DEFAULT_CACHE_DIR` inside the checkout.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX skips programs that compiled in under a second; a served plan is
    # many such kernels per bucket and batch size, so keep them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
