"""Small utilities (ref: python/mxnet/util.py)."""
from __future__ import annotations

import os


def makedirs(d):
    """Create directory recursively; no error if it exists
    (ref: util.py makedirs)."""
    os.makedirs(os.path.expanduser(d), exist_ok=True)


def enable_compile_cache():
    """Turn on jax's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and
    no directory is set in code. Otherwise the cache lives at the fixed
    ``<checkout>/.xla_cache``: the path is part of every entry's key, so
    a directory that moves between runs never hits. Call before the
    first compile."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".xla_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # eager per-op programs compile in milliseconds each but there are
    # hundreds of them: cache every entry, whatever its size or time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
