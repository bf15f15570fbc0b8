"""Where JAX keeps its persistent compilation cache.

A program that compiles for seconds to minutes keeps what it compiled, so
its next run with the same shapes starts warm. The directory comes from
outside when ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads that variable
itself, and nothing is set here); otherwise it is the fixed path
``<repo>/.jax_cache``, which does not move between runs, so the next run
finds what this one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory. Call it before
    the first compile, from a program's entry point."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
