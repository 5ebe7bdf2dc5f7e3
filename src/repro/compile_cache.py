"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py``, ``python -m repro.sim``, ``python -m repro.serving`` and
``python -m repro.bench`` call :func:`enable_compile_cache` once at start-up
(never at import), so a rerun of the same programs skips XLA/Mosaic
compilation. Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and no other
  directory is set here;
* otherwise the fixed ``<checkout>/.jax_cache`` (git-ignored). The path is
  part of what makes an entry findable again, so it never depends on a
  temp directory, the pid or the clock.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
