"""Persistent JAX compilation cache for the entry points.

A cold TPU process recompiles every kernel and jitted program it runs;
the persistent cache lets a later process with the same programs load
them instead.  The cache key includes the directory, so the directory
must not move between runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when
that is set (JAX reads the variable itself and this module sets nothing
else), otherwise one fixed, git-ignored directory inside the checkout.

Entry points (``chip_smoke.py``, ``scripts/score.py``,
``scripts/ingest.py``) call :func:`enable_compile_cache` before their
first compile; importing the library never does.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the checkout-local default (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its one directory and
    return it: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    :data:`DEFAULT_DIR`."""
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    DEFAULT_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
