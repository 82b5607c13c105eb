"""Where compiled executables persist between processes.

A full-width engine warmup compiles about a dozen executables per model,
and every fresh process would compile them again.  JAX's persistent
compilation cache keeps them on disk; its key includes the cache path,
so the path must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there
    and nothing is changed.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
