"""Execution-environment honesty for every BENCH_*.json record.

Numbers from a CPU interpreter and numbers from a TPU are different
experiments; a bench record that omits the platform invites comparing
them.  Every bench merges :func:`bench_env` into its record so the
backend and device kind ride with the data.
"""

from __future__ import annotations

import os

#: the checkout root (this file lives in ``<root>/benchmarks/``)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: files the persistent cache held when :func:`use_compile_cache` ran
#: (None before it ran): 0 means this process compiled from scratch
_CACHE_ENTRIES_AT_START: int | None = None


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set.  Otherwise the cache lives in ``.jax_cache/``
    at the checkout root: a fixed path, so a later process in the same
    checkout finds what an earlier one compiled.  Entry points call
    this at start-up, before their first compile; importing the library
    never does.  How many entries the cache already held is kept for
    :func:`bench_env`: a compile time read from a warm cache is a
    cache load, not a compile.
    """
    import jax

    global _CACHE_ENTRIES_AT_START
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    _CACHE_ENTRIES_AT_START = (len(os.listdir(path))
                               if os.path.isdir(path) else 0)
    return path


def bench_env() -> dict:
    """Backend/platform facts for a bench record (cheap, no device
    work beyond enumerating what jax already initialised).

    ``compile_cache`` is the persistent cache directory in force (None
    when off) and ``compile_cache_entries_at_start`` what it held when
    the entry point turned it on (None: not turned on through
    :func:`use_compile_cache`); compile seconds in a record with a warm
    cache measure cache loads."""
    import jax

    devs = jax.devices()
    return {
        "backend": jax.default_backend(),
        "platform": devs[0].platform if devs else "none",
        "device_kind": devs[0].device_kind if devs else "none",
        "n_devices": len(devs),
        "jax_version": jax.__version__,
        "compile_cache": (jax.config.jax_compilation_cache_dir
                          if jax.config.jax_enable_compilation_cache
                          else None) or None,
        "compile_cache_entries_at_start": _CACHE_ENTRIES_AT_START,
    }
