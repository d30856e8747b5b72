"""Where the persistent XLA compilation cache lives.

The repo targets one jax (``pyproject.toml``: ``jax>=0.9.0``) and calls its
API directly (``jax.shard_map``, ``jax.lax.axis_size``,
``jax.experimental.layout``). What every entry point still has to agree on
is the compile cache's directory: a later process only finds what an earlier
one compiled if both look in the same place, and whoever launches the
processes (a chip tool, CI) must be able to put that place on a disk that
survives. One resolver owns the choice:

1. ``JAX_COMPILATION_CACHE_DIR`` set in the environment — jax reads it
   itself at import; no code path here sets another directory.
2. otherwise the configured path (``TrainConfig.compile_cache_dir`` / CLI
   ``--compile-cache``);
3. otherwise no persistent cache.
"""

from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def resolve_compile_cache_dir(configured: str = "") -> str:
    """The directory this process's compile cache uses: the environment's
    ``JAX_COMPILATION_CACHE_DIR`` if set, else ``configured``, else ``""``
    (no persistent cache)."""
    return os.environ.get(CACHE_ENV) or configured or ""


def enable_compile_cache(configured: str = "") -> str:
    """Turn the persistent compilation cache on at the resolved directory
    (:func:`resolve_compile_cache_dir`) and return it; ``""`` = nothing to
    enable. Idempotent — every trainer / serving engine calls it.

    With the environment variable set, jax's own reading of it stands and
    no directory is set here. Either way the write thresholds are zeroed so
    fast-compiling programs (CPU tests, the small serving buckets) are
    cached too: a second run against the same directory then adds no
    entries."""
    path = resolve_compile_cache_dir(configured)
    if not path:
        return ""
    if not os.environ.get(CACHE_ENV):
        from jax.experimental.compilation_cache import compilation_cache as cc

        os.makedirs(path, exist_ok=True)
        if jax.config.jax_compilation_cache_dir != path:
            cc.set_cache_dir(path)
            # jax latches its cache-used decision on the FIRST compilation
            # of the process; enabling the cache mid-session (a trainer
            # constructed after other jax work) needs the latch cleared or
            # nothing is ever written
            cc.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


__all__ = ["CACHE_ENV", "enable_compile_cache", "resolve_compile_cache_dir"]
