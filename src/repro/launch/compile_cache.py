"""A stable home for JAX's persistent compilation cache.

The cache key includes the directory, so a directory that moves between
runs never hits.  :func:`enable` is called first thing by the entry points
(``launch.serve``, ``launch.train``, ``chip_smoke.py``):

  * ``JAX_COMPILATION_CACHE_DIR`` set — that directory, and no other;
  * unset — one fixed directory inside the checkout, ``.jax_cache/``
    (listed in ``.gitignore``), never a name built from a temp dir, a pid
    or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout's fixed cache directory (src/repro/launch -> repo root)
CHECKOUT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at ``$ENV``, else at
    :data:`CHECKOUT_DIR`; returns the directory."""
    path = os.environ.get(ENV) or str(CHECKOUT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
