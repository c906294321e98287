"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.train``/``serve``) call :func:`use_compile_cache` before
their first compile.  Importing this module sets nothing.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: Fixed (no temporary name, pid or time), so that one run's entries are
#: found by the next run from the same checkout.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    import jax

    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
