"""JAX's persistent compilation cache for the command-line entry points.

Compiling the full-width step programs takes a large share of a cold run,
so every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, the benchmark scripts) calls ``enable_compile_cache``
first. Library modules never call it: importing them changes no global
JAX setting.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and this
sets nothing. Otherwise the cache lives at a FIXED directory inside the
checkout, ``<repo>/.jax_cache`` (git-ignored). A fixed path matters: the
directory is part of each entry's key, so a per-run temp name would never
hit."""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
