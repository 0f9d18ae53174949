"""JAX's persistent compilation cache, at one fixed place.

``enable()`` is called by the entry points that compile for a device
(``chip_smoke.py``, ``benchmarks/run.py``).  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is configured.  Otherwise the cache lives in ``<repo>/.jax_cache``,
anchored to this file, so the next run finds what this one wrote; a path
that moved from run to run (a temp directory, a pid) would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The repository's own cache directory (listed in .gitignore).
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
