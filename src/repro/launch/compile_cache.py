"""Where JAX keeps its persistent compilation cache.

One helper for every entry point (``chip_smoke.py``, ``launch/train.py``,
``serving/cli.py``, ``tools/tensile_svc.py start``).  The cache's path is
part of its key, so it never moves between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no other
  directory is set in code;
* otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return the directory in use."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
