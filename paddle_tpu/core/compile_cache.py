"""Where the persistent XLA compile cache lives.

A cold start compiles every step program (a served engine's warm-up
ladder is dozens of them); JAX's persistent compilation cache turns the
next process's compiles into file reads. The cache directory is part of
every entry's key, so it has to be the SAME path run after run: placed
from outside through `JAX_COMPILATION_CACHE_DIR` (JAX reads the
variable itself — nothing is set in code then), or else one fixed
directory inside the checkout. Never a temporary, pid- or time-derived
path, which could not hit twice.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_compile_cache — listed in .gitignore
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on before the first compile.
    Returns the directory in use. Entry points (chip_smoke.py,
    benchmark/run.py, the examples) call this once; the library never
    does — importing the package configures nothing."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    return _CHECKOUT_CACHE_DIR
