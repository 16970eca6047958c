"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``examples/serve_volume.py``,
``python -m repro.tuning.autotune``) calls
``enable_compile_cache()`` before its first compile, so that processes of
one run, and runs on one machine, share compiled programs.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  set here.
* not set: the cache lives at ``CACHE_DIR``, a fixed directory inside the
  checkout that git ignores.  The path is fixed on purpose: it is part of
  what a cache entry is found by, so a directory named after a temporary
  name, a pid or the time would never be hit again.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".cache" / "jax"
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; return it."""
    import jax

    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
