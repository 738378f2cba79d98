"""JAX persistent compilation cache at one fixed path.

A compile on the TPU takes seconds to minutes, and the cache key includes
the cache directory, so the directory must not move between runs.
``enable_compile_cache`` is the one place that chooses it: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here; otherwise the cache lives in ``.jax_cache/`` at the root of the
checkout (gitignored). ``chip_smoke.py`` and the ``repro.launch`` mains
call it first thing; importing this module touches no device.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and return the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
