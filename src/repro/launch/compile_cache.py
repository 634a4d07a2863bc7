"""JAX's persistent compilation cache, at one fixed place.

A whole-model round program takes tens of seconds to compile, so every
entry point turns the cache on before its first compile. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it and keeps the cache
there, and nothing else is set. Otherwise the cache lives in `.jax_cache`
at the root of the source checkout the package runs from (the directory
that holds `pyproject.toml` and `src/repro`), or in the working directory
when the package runs from outside a checkout. The path is fixed so that
a later run finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def cache_dir() -> Path:
    """`.jax_cache` in the checkout this module runs from, else in the
    working directory."""
    for d in Path(__file__).resolve().parents:
        if (d / "pyproject.toml").is_file() and (d / "src" / "repro").is_dir():
            return d / ".jax_cache"
    return Path.cwd() / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(cache_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
