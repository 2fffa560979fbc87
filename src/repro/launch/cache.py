"""Where JAX's persistent compilation cache lives, for the entry scripts.

Entry points (``chip_smoke.py``, ``examples/cnn_training.py``) call
``use_compile_cache()`` once, before their first compile.  Importing
``repro`` never touches the cache, so tests and described-topology
compiles stay silent.
"""
from __future__ import annotations

import os
import re

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
# A fixed path: the cache key includes it, so a directory that moved
# between runs would never hit.
REPO_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    A directory named by ``JAX_COMPILATION_CACHE_DIR`` is left to JAX,
    which reads that variable itself; otherwise the cache goes to the
    repository's ``.jax_cache``.

    Either way the cache key covers the program's metadata.  JAX leaves it
    out by default, and then two programs that differ only in their scope
    names share one entry: the second loads the first's executable, whose
    ``op_name``s are what a profile of it reports.  Source paths in that
    metadata are taken relative to the repository, so a checkout that
    moved still finds its entries."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(REPO_ROOT + os.sep))
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
