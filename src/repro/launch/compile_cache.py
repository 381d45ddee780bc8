"""Persistent XLA compilation cache, placed by the entry points.

`use_compile_cache()` is called from `launch/train.py::main`,
`launch/serve.py::main` and `chip_smoke.py` — never at import time, so
tests and library users keep whatever cache configuration they set up.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
helper sets no other directory. Otherwise the cache lives at a fixed path
inside the checkout (`<repo>/.jax_cache`, listed in .gitignore). It is
built from no temp name, pid or time, so a second run of the same program
in the same checkout reads back what the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
