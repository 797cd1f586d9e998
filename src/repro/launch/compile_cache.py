"""JAX persistent compilation cache placement for the entry points.

Called by ``chip_smoke.py``, ``benchmarks/run.py`` and the examples' ``main``
(never at import of ``repro``): a cold chip run otherwise recompiles every
engine program, and each engine program takes 30–45 s to compile for a v5e.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# Fixed, git-ignored path inside the checkout: the cache directory is part
# of what a later run must find again, so it is never built from a temp
# name, a pid or the time.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and no other path is set.  Otherwise, on a TPU, the cache goes to
    ``<checkout>/.jax_cache``.  Off-TPU it stays off (returns None):
    XLA:CPU worker processes crashed (SIGSEGV / SIGABRT) while serializing
    engine executables into the cache under parallel test load."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
