"""Placement of JAX's persistent compilation cache.

Every entry point that compiles for the chip (``benchmark/run.py``,
``chip_smoke.py``, ``tools/tpu_smoke.py``, ``tools/chip_parity.py``)
calls :func:`enable_compile_cache` before its first jit. The cache directory is part of nothing the program decides at run time:
``JAX_COMPILATION_CACHE_DIR`` places it from outside (JAX reads the variable
itself, so nothing is set here); without it the cache lives at one fixed
path inside the checkout, because a directory whose name moves (temp name,
pid, timestamp) never hits.
"""

from __future__ import annotations

import os

import jax

#: the fixed in-checkout location (listed in .gitignore)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    The write thresholds drop to zero so the step programs are always
    stored (JAX's defaults skip entries that compiled in under a second)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
