"""JAX's persistent compilation cache for the entry points.

Called by the programs a user runs (``chip_smoke.py``, ``launch.train``,
``launch.serve``, ``benchmarks.run``), never on import: a library that
turned the cache on would write it from every test and worker process.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets nothing.  Otherwise the cache lives at ``<repo>/.jax_cache``: a
fixed path, because the directory is part of what a later run must match
to find its programs there.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; → its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
