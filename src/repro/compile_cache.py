"""Persistent XLA compilation cache at a place the caller can choose.

Call :func:`use_persistent_cache` once at the top of an entry point
(``chip_smoke.py``, the benchmark runners) — never at import time, and
never from the tests, which compile for described chips whose entries
cannot be read back.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no other directory.
* unset: the cache lives in ``.jax_cache/`` at the repository root, a fixed
  path (the path is part of the cache key, so a directory that moves
  between runs never hits).
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
