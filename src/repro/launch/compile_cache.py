"""JAX's persistent compilation cache, placed once for every entry point.

``launch/serve.py``, ``launch/train.py`` and ``chip_smoke.py`` call
``enable_compile_cache()`` before they compile anything, so a rerun (or a
second process of the same command) loads its step programs instead of
compiling a full-depth model cold.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it into its own
``jax_compilation_cache_dir`` setting and this module sets no directory.
Otherwise the cache lives at ``<repo>/.jax_cache`` (git-ignored): a fixed
path, because the path is part of the cache key and a directory that moves
never hits.
"""
from __future__ import annotations

from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Every
    program is cached, however short its compile and however small its
    executable, so the decode and prefill steps always are."""
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
