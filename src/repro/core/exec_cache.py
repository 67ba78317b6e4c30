"""Instrumented LRU cache for compiled executables.

The sweep engine (``repro.core.experiments``) used to hide its jitted
executables behind a private ``functools.lru_cache`` — invisible to the
serving layer, which needs to *assert* "this 100-query replay compiled
exactly once" and to report hit rates and compile-time split as
first-class metrics.  ``ExecutableCache`` is that cache made explicit:

  * bounded LRU keyed by the caller's structural signature (static
    scan configuration — including the kernel tier and the megakernel's
    substep-block depth, since a mega sweep re-blocked at a different
    ``trace_every`` is a different program — plus the input pytree
    treedef and leaf shapes/dtypes, so a hit really means "this
    executable can run these arrays as-is");
  * hit / miss / eviction counters plus cumulative build (compile)
    seconds, snapshotable as :class:`CacheStats` — deltas subtract, so
    a serving engine can report per-window stats off a shared cache;
  * configurable capacity (``resize``), safe under concurrent readers
    (one lock; builders run under it so a key is only ever built once).

The module is dependency-free on purpose: the cache stores whatever the
builder returns (AOT-compiled ``jax.stages.Compiled`` executables for
the sweep engine, plain jitted callables for the mesh-sharded path).  A
miss's build runs under the ``repro.exec_cache.build`` span of
``obs``, which loads JAX only when a span opens.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Callable, Hashable

from . import obs


@dataclasses.dataclass(frozen=True)
class CacheStats:
    """Monotone counter snapshot; subtract two snapshots for a window."""

    hits: int = 0
    misses: int = 0
    evictions: int = 0
    build_s: float = 0.0          # cumulative seconds spent in builders

    @property
    def lookups(self) -> int:
        return self.hits + self.misses

    @property
    def hit_rate(self) -> float:
        """Hits per lookup (1.0 for the empty window: nothing missed)."""
        n = self.lookups
        return self.hits / n if n else 1.0

    def __sub__(self, other: "CacheStats") -> "CacheStats":
        return CacheStats(hits=self.hits - other.hits,
                          misses=self.misses - other.misses,
                          evictions=self.evictions - other.evictions,
                          build_s=self.build_s - other.build_s)

    def to_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "hit_rate": round(self.hit_rate, 4),
                "build_s": round(self.build_s, 3)}


def structural_signature(static: tuple, args) -> tuple:
    """The full structural cache key for a compiled program.

    ``static`` is the caller's static configuration tuple; ``args`` is
    the input pytree the executable will be called with.  The returned
    key appends the pytree's treedef and every leaf's
    (shape, dtype, weak_type) — exactly what determines the compiled
    program, so two calls with equal signatures can share one
    executable and run each other's arrays as-is.

    This is the sweep engine's key, exported so other layers (the fleet
    planner's structural buckets, the serving engine's compile-once
    assertion) can group work by "compiles to the same program" without
    re-deriving the rule.
    """
    import jax                     # lazy: the module itself stays free

    leaves, treedef = jax.tree.flatten(args)
    shapes = tuple((tuple(x.shape), x.dtype.name,
                    bool(getattr(x, "weak_type", False))) for x in leaves)
    return static + (treedef, shapes)


class ExecutableCache:
    """Bounded, instrumented LRU: key -> built executable.

    ``get_or_build(key, builder)`` returns the cached value for ``key``
    or runs ``builder()`` (counting its wall time as compile time) and
    inserts the result, evicting least-recently-used entries past
    ``capacity``.  Keys must be hashable; use a full structural
    signature — anything that changes the compiled program (static
    arguments, input shapes/dtypes/treedef) belongs in the key.
    """

    def __init__(self, capacity: int = 32, name: str = "exec"):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.name = name
        self._capacity = int(capacity)
        self._entries: "collections.OrderedDict[Hashable, Any]" = \
            collections.OrderedDict()
        self._lock = threading.RLock()
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        self._build_s = 0.0

    # -- core ---------------------------------------------------------------

    def get_or_build(self, key: Hashable,
                     builder: Callable[[], Any]) -> Any:
        with self._lock:
            if key in self._entries:
                self._hits += 1
                self._entries.move_to_end(key)
                return self._entries[key]
            # build under the lock: concurrent callers of one key must
            # not compile twice (compilation is the expensive part)
            self._misses += 1
            t0 = time.perf_counter()
            with obs.span("repro.exec_cache.build"):
                value = builder()
            self._build_s += time.perf_counter() - t0
            self._entries[key] = value
            while len(self._entries) > self._capacity:
                self._entries.popitem(last=False)
                self._evictions += 1
            return value

    # -- introspection ------------------------------------------------------

    @property
    def capacity(self) -> int:
        return self._capacity

    def resize(self, capacity: int) -> None:
        """Change capacity; shrinking evicts LRU entries immediately."""
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        with self._lock:
            self._capacity = int(capacity)
            while len(self._entries) > self._capacity:
                self._entries.popitem(last=False)
                self._evictions += 1

    def stats(self) -> CacheStats:
        with self._lock:
            return CacheStats(hits=self._hits, misses=self._misses,
                              evictions=self._evictions,
                              build_s=self._build_s)

    def reset_stats(self) -> None:
        """Zero the counters (entries stay — hit rates restart clean)."""
        with self._lock:
            self._hits = self._misses = self._evictions = 0
            self._build_s = 0.0

    def clear(self) -> None:
        """Drop every entry (not counted as evictions; stats persist)."""
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key: Hashable) -> bool:
        with self._lock:
            return key in self._entries

    def keys(self):
        with self._lock:
            return list(self._entries.keys())

    def values(self):
        with self._lock:
            return list(self._entries.values())

    def __repr__(self) -> str:
        s = self.stats()
        return (f"ExecutableCache({self.name!r}, {len(self)}/"
                f"{self._capacity} entries, hits={s.hits} "
                f"misses={s.misses} evictions={s.evictions})")
