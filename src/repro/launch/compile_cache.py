"""JAX's persistent compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other path. Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (listed in ``.gitignore``): never a name built from
a temp dir, a pid or the clock, because a directory that moves never hits.

Entry points (``launch/train.py``, ``scenario/smoke.py``, ``chip_smoke.py``)
call :func:`enable_compilation_cache` once before their first compile.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only those that took over a second to build:
    # a chip run compiles many small step and kernel programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
