"""Persistent XLA compilation cache for the program's entry points.

Only entry points call :func:`enable_compile_cache` (``chip_smoke.py``,
``python -m repro.launch.compressd``, the benchmark mains); importing the
library never touches the cache configuration.
"""
from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable itself
    and nothing is set here. Otherwise the cache goes to the fixed path
    ``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is part of
    what makes a later run hit, so it is never a temporary directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
