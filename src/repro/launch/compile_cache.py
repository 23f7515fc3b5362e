"""JAX's persistent compilation cache, set up in one place.

The entry points (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``) call :func:`enable_compile_cache` before their
first compile, so a second run of the same program on the same machine
loads its executables instead of compiling them again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache lives at
:data:`DEFAULT_CACHE_DIR`, one fixed directory inside the checkout: the
directory is part of what a cache hit depends on, so it must not move
between runs. Tests leave the cache off.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the cache uses: the environment's, else the default."""
    return os.environ.get(ENV_VAR) or DEFAULT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
