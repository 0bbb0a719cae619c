"""Where JAX keeps its persistent compilation cache — ONE definition.

The cache path is part of every entry's key, so it must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it, otherwise the fixed in-checkout ``<repo>/.jax_cache`` (gitignored).
``chip_smoke.py`` calls :func:`enable_compile_cache`;
no other code sets a cache path.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]


def compile_cache_dir() -> str:
    """The cache directory this process should use."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(REPO_ROOT / ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return the path."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
