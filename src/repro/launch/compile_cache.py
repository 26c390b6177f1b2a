"""Persistent XLA compilation cache, placed from outside the program.

Entry points (`chip_smoke.py` and the `main()` of the serve, stream and
train launchers) call `enable_compile_cache()` before their first
compile; importing the package never does. When
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives at `<repo root>/.jax_cache`, a fixed
path, so each run of a checkout finds what the previous run compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
