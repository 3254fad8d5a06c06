"""Where the command-line entry points keep JAX's persistent compile cache.

A cold start compiles every fused launch (tens of seconds for the MILC
normal operator), and the cache key includes the cache's path, so the
path has to be fixed to ever hit.  ``setup_compile_cache()`` is called at
the start of ``chip_smoke.py``, the examples, ``python -m
repro.launch.serve`` and the benchmark mains:

* with ``$JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it; the helper
  leaves it alone and sets no other cache;
* otherwise the cache lives at ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``) — never a temporary or per-process path.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "setup_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed place and
    return the directory in use."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    import jax  # noqa: PLC0415

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
