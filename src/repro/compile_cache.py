"""JAX's persistent compilation cache, kept at a fixed place.

A process that compiles for the device calls :func:`configure_compile_cache`
once, before its first compile: ``chip_smoke.py``, ``python -m
repro.serve`` and ``python -m benchmarks.run`` do.  Nothing calls it at
import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this sets no other directory.
* Otherwise the cache lives in ``.jax_cache`` at the root of the checkout
  (git ignores it).  The path never depends on a temp name, a pid or the
  time, so a later run from the same checkout finds its entries again.
  Outside a checkout (no ``pyproject.toml`` beside ``src/``) no cache is
  configured.

Either way every compile is cached, however short: a serving process
compiles many small programs, one per batch shape.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIRNAME = ".jax_cache"


def checkout_cache_dir() -> Path | None:
    """``<checkout>/.jax_cache``, or ``None`` outside a source checkout."""
    root = Path(__file__).resolve().parents[2]
    if not (root / "pyproject.toml").is_file():
        return None
    return root / CACHE_DIRNAME


def configure_compile_cache() -> str | None:
    """Point the persistent compilation cache at its directory and return
    that directory (``None``: no cache)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        where = checkout_cache_dir()
        if where is None:
            return None
        path = str(where)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
