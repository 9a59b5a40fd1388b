"""JAX's persistent compilation cache, at a directory that does not move.

Entry points (`chip_smoke.py`, `examples/*.py`, `benchmarks/*`,
`tools/repro_ctl.py`) call `enable_compile_cache()` once, before their
first compile, so a later run of any of them loads the compiled
programs instead of compiling again.  Importing this module changes
nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: src/repro/runtime/compile_cache.py -> parents[3]
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and no
    other directory is set here.  Otherwise the cache goes to
    `.jax_cache` at the root of the checkout: a fixed path, never a temp
    name, so the next process finds what this one wrote.  Must run
    before the process's first compile; JAX fixes the cache then.

    Every program is cached, not only those over JAX's default 1 s of
    compile time: the design service compiles ~220 programs, most of
    them in under a second, and together they are most of a cold start.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
