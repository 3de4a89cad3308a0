"""Where JAX keeps its persistent compilation cache.

Entry points (`lda_train.main`, `serve.main`, `chip_smoke.py`) call
`use_compile_cache` before their first compile.  A cache directory given
from outside (``JAX_COMPILATION_CACHE_DIR``) is JAX's own business and is
left alone; otherwise, on an accelerator, the cache lives at one fixed
path inside the checkout (``<repo>/.jax_cache``, git-ignored) — the path
is part of the cache key, so it must not move between runs.  On the CPU
backend nothing is cached: XLA:CPU executables are tied to the host's
CPU features, and CPU runs (tests, rehearsals) compile in seconds.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache(backend=None):
    """Point JAX at the compile cache; returns the directory in use (None
    on the CPU backend).  ``backend`` defaults to JAX's default backend."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    import jax
    if (backend or jax.default_backend()) == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
