"""Placement of JAX's persistent compilation cache.

Entry points that compile at deployment size (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before their
first compile, so that the processes of one run on a machine share compiled
programs.  The cache is placed from outside the program:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
    sets nothing;
  * unset: the cache goes to ``<checkout>/.jax_cache``.  The path is fixed
    (no temp name, pid or time in it) because it is part of the cache key,
    so a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout default (``src/repro/`` -> checkout root)
DEFAULT_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def compile_cache_dir(environ: Mapping[str, str] = os.environ) -> Optional[str]:
    """The directory this module would set, or None when the environment
    already places the cache."""
    return None if environ.get(ENV_VAR) else DEFAULT_DIR


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    (leaving it alone when ``JAX_COMPILATION_CACHE_DIR`` is set); returns the
    directory set, or None."""
    path = compile_cache_dir()
    if path is not None:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
