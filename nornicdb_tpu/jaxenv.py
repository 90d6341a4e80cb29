"""Process-edge JAX set-up: the compile cache and the children's backend.

One chip belongs to one process. The process that may own it (``open()``,
``benchmark/run.py``, ``chip_smoke.py``) calls :func:`ensure_compile_cache`
once; every child it spawns that must NOT own it (replica subprocesses,
wire-plane workers) is launched with :func:`cpu_child_env`.
"""

from __future__ import annotations

import os
from typing import Dict, Mapping, Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, never derived from a pid, a time or a temporary name: the path
# is part of the cache key, so a directory that moves never hits
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Make JAX's persistent compilation cache live somewhere stable and
    return the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself, so no
    directory is set in code; otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``. Idempotent."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    # every open() lands here: leave an already-placed cache untouched
    if jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def cpu_child_env(env: Optional[Mapping[str, str]] = None) -> Dict[str, str]:
    """Environment for a child process that must not own the chip: the
    parent's, with the JAX backend pinned to CPU. The pin is set by the
    spawner, before the child's interpreter starts, because JAX reads
    ``JAX_PLATFORMS`` once at import and a child's own module imports
    may already have pulled JAX in — an inherited ``JAX_PLATFORMS=tpu``
    would otherwise send the child after a chip its parent holds."""
    out = dict(os.environ if env is None else env)
    out["JAX_PLATFORMS"] = "cpu"
    return out
