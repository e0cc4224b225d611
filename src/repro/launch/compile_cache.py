"""JAX's persistent compilation cache, configured in one place.

Entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/e2e_cnn.py``) call ``configure_compile_cache`` before their
first compile, so a second run on the same machine reads its executables
back instead of recompiling.
"""
from __future__ import annotations

import os

#: The fixed in-checkout location used when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset.  The path is part of the cache key, so it never carries a temp
#: name, pid or time.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".cache", "jax_compile",
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is used as is (JAX reads it
    itself); otherwise ``<checkout>/.cache/jax_compile``.  Every executable
    is cached, however quick its compile: a Pallas kernel compiles in well
    under JAX's default one-second threshold.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path

