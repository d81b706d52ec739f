"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
``configure_compile_cache()`` once at start; importing the library never
touches the cache.
"""

from __future__ import annotations

import contextlib
import os
from pathlib import Path

__all__ = ["DEFAULT_CACHE_DIR", "configure_compile_cache", "persistent_cache_disabled"]

# A fixed path inside the checkout (git-ignored): the cache key includes the
# directory, so a path that moved between runs would never hit.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Enable the persistent compile cache; returns the directory in use.

    If ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is configured here. Otherwise the cache goes to
    ``DEFAULT_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


@contextlib.contextmanager
def persistent_cache_disabled():
    """Compile without reading or writing the persistent cache inside the
    block (cold-compile timings; compiles for a chip that is not attached,
    whose entries could not be read back)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
