"""Where JAX keeps its persistent compilation cache.

A full-width step compiles for tens of seconds, and a cold run on the
chip would spend much of its time compiling.  Entry points call
:func:`use_compile_cache` (never at import time) so that processes which
share a machine share their compilations.
"""
from __future__ import annotations

import os
import pathlib

import jax

# a fixed path inside the checkout (git-ignored): never built from a
# temporary name, a pid or the time, so a later run finds it again
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Keep the cache where ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads
    the variable itself), else at ``<repo>/.jax_cache``.  Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
