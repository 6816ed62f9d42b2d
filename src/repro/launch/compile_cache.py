"""JAX's persistent compilation cache, placed for the whole checkout.

Entry points (``chip_smoke.py`` and every ``repro.launch`` ``main()``) call
:func:`enable` once, before their first compile; library modules never
touch the cache, on import or otherwise.

* ``$JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this helper
  sets no directory.
* unset: the cache lives at one fixed directory inside the checkout,
  ``<repo>/.jax_cache`` (listed in ``.gitignore``).  The path is part of the
  cache key, so it never comes from a temp name, a PID or the clock.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> None:
    """Turn the persistent cache on: at ``$JAX_COMPILATION_CACHE_DIR`` when
    it is set, else at :data:`DEFAULT_DIR`."""
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
