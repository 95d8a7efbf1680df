"""JAX's persistent compilation cache for the repository's entry points.

Scripts (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*``) call
:func:`enable` once, before their first compile. Library modules and
tests never do: a cache is a property of how a program is launched.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache — a fixed path, because the directory is part of
# what makes a later run find an entry again.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing; otherwise the cache lives in ``DEFAULT_DIR``."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
