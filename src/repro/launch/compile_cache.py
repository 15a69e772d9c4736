"""JAX's persistent compilation cache, kept at one fixed path.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/``) calls :func:`enable_compile_cache`
before its first compile.  A cold 32-layer serving run otherwise
recompiles every prefill bucket and decode window in each new process.

The cache is keyed on its own path, so the path must not move between
runs: never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Returns the cache directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``DEFAULT_DIR``."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
