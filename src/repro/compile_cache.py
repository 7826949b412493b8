"""Where entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so the directory must not move
between runs: a path made from a temporary name, a pid or the time never
hits.  ``enable_compile_cache`` is called by the run scripts
(``chip_smoke.py``, the bench CLI in ``benchmarks/common.py``); library
code and the tests never turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — listed in .gitignore
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it into
    ``jax_compilation_cache_dir`` and that directory is used as is.
    Otherwise the cache goes to the fixed ``DEFAULT_DIR`` in the checkout.
    """
    import jax

    if os.environ.get(ENV_VAR):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
