"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call :func:`enable`
once at start-up; importing :mod:`repro` never touches the cache.  The rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing is
    set in code;
  * otherwise the cache goes to ``<checkout>/.jax_cache``, a fixed path, so
    later runs from the same checkout find what earlier runs compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[2]


def cache_dir() -> Path | None:
    """The directory :func:`enable` sets, or None when the env var rules."""
    if os.environ.get(ENV):
        return None
    return CHECKOUT / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache per the rule above; returns
    the directory in use."""
    path = cache_dir()
    if path is None:
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
