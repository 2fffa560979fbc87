"""Where JAX's persistent compilation cache lives, for the entry scripts.

Entry points (``chip_smoke.py``, ``examples/cnn_training.py``) call
``use_compile_cache()`` once, before their first compile.  Importing
``repro`` never touches the cache, so tests and described-topology
compiles stay silent.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# A fixed path: the cache key includes it, so a directory that moved
# between runs would never hit.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    A directory named by ``JAX_COMPILATION_CACHE_DIR`` is left to JAX,
    which reads that variable itself; otherwise the cache goes to the
    repository's ``.jax_cache``."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
