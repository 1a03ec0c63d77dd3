"""JAX's persistent compilation cache, switched on by programs.

A program that starts on a fresh machine compiles every kernel and step
again; with the cache on, a second run with the same shapes loads them.
``enable_compile_cache`` is called at the start of programs (the chip
smoke, ``repro.launch.serve``, ``benchmarks/run.py``) — never when a
library module is imported.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the fixed cache directory inside the checkout (listed in .gitignore)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``DEFAULT_DIR``, one
    fixed path, so that every run from this checkout finds the programs
    earlier runs wrote.  Returns the directory in use.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
