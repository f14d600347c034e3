"""Persistent XLA compilation cache helper.

For XLA the compile IS the build step (the reference has no analogue —
CUDA kernels ship prebuilt), and the ResNet-50 train step alone costs the
better part of a minute of it, so every entry point — ``bench.py``,
``chip_smoke.py``, ``benchmarks/*`` and launcher-spawned workers — shares
one persistent cache, placed by one rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already points there; this
  module sets no directory in code.
- otherwise ``<checkout>/.jax_cache``, derived from the package path. The
  path is part of the cache key, so it never depends on the home
  directory, a temporary name, a pid or a time: a directory that moves
  never hits.

The directory in use is recorded (:func:`active_cache_dir`) so the
memledger's compile instrumentation (utils/memledger.py) can infer
persistent-cache hit/miss from the cache-dir entry delta across a
compile, and a failure to enable is visible three ways instead of being
a mystery recompile per process: a one-time warning with the reason,
the reason as the return value, and an ``hvd_compile_cache_enabled``
gauge (1/0).
"""

import logging
import os
from typing import Optional

LOG = logging.getLogger("horovod_tpu")

JAX_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_ACTIVE_DIR: Optional[str] = None
_WARNED = False


def active_cache_dir() -> Optional[str]:
    """The persistent-cache directory enabled in this process, or None —
    the memledger's hit/miss inference keys off this."""
    return _ACTIVE_DIR


def cache_entries() -> int:
    """Number of entries in the active cache directory, -1 when there is
    none or it cannot be read. A compile that leaves the count unchanged
    was a hit."""
    try:
        return len(os.listdir(_ACTIVE_DIR)) if _ACTIVE_DIR else -1
    except OSError:
        return -1


def enable_compilation_cache() -> Optional[str]:
    """Turn JAX's persistent compilation cache on, placed by the module's
    one rule. Call before the process's first compile.

    Returns None on success, else the failure reason (also warned once
    per process and published on the ``hvd_compile_cache_enabled``
    gauge). Never raises: the cache is an optimization — but a cache
    that is not there must be visible, not silent.
    """
    global _ACTIVE_DIR, _WARNED
    import jax

    try:
        cache_dir = os.environ.get(JAX_CACHE_DIR_ENV)
        if not cache_dir:
            cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
            os.makedirs(cache_dir, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        reason = None
    except Exception as e:
        reason = f"{type(e).__name__}: {e}"
    from . import metrics as metrics_mod

    metrics_mod.get_registry().gauge(
        "hvd_compile_cache_enabled",
        "1 when the persistent XLA compile cache is enabled, 0 when the "
        "last enable attempt failed").set(0 if reason else 1)
    if reason is None:
        _ACTIVE_DIR = cache_dir
        return None
    if not _WARNED:
        _WARNED = True
        LOG.warning("persistent compilation cache NOT enabled (%s): every "
                    "process pays every compile", reason)
    return reason
