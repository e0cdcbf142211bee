"""Where the entry points keep JAX's persistent compilation cache.

A published-width step program takes tens of seconds to compile; the cache
lets a second process (or a second engine with the same program in one
process) load it instead.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins —
JAX reads that variable itself.  Otherwise the cache lives at a fixed path
inside the checkout, ``<checkout>/.jax_cache`` (gitignored): the directory
is part of the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory (see the
    module docstring) and return that directory.  Call at the top of an
    entry point's ``main``, before anything compiles."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
