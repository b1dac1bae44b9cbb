"""JAX's persistent compilation cache for the entry points.

Called by ``chip_smoke.py``, ``repro.launch.train`` and
``repro.launch.serve`` before their first compile; never on ``import
repro``, so library users and the test suite keep JAX's own default.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — a fixed path: the directory is part of what a
# later process must find again, so it never carries a pid, time or temp
# name
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is set here; otherwise the cache goes to ``.jax_cache/`` under
    the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
