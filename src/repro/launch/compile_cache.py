"""JAX's persistent compilation cache for the program's entry points.

Called by ``launch/serve.py:main``, ``benchmarks/run.py:main`` and
``chip_smoke.py`` — never on import and never in tests.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing.  Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
(git-ignored): the directory is part of the cache key, so a path built
from a temporary name, a pid or the time would never hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
