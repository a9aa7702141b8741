"""Where JAX's persistent compilation cache lives for chip runs.

Entry points that run on the chip call :func:`use_compile_cache` once,
before their first compile. Importing :mod:`repro` sets nothing.

The cache key includes the directory, so the directory must not move from
one run to the next: it is either the one ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself, and nothing is set here) or the fixed
``.jax_cache`` directory at the root of the source checkout, derived from
this file's location.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
