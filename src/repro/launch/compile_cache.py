"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` places it from outside (JAX
reads the variable itself, and nothing here overrides it); otherwise it is
the fixed ``.jax_cache`` directory at the root of the checkout.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def init_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile.  With ``JAX_COMPILATION_CACHE_DIR`` set
    the config is left as JAX read it from the environment."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
