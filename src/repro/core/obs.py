"""Program observability: host spans, counters and device scopes.

One process-wide registry holds, for each host span, how many times it
closed and the seconds it took, and for each counter its running total.
``stats()`` snapshots it; snapshots subtract (like
``exec_cache.CacheStats``), so a window is the difference of two.

  * ``span(name)`` — a host span: a ``jax.profiler.TraceAnnotation``, so
    a profiler trace shows it on the device trace's clock, whose
    ``time.perf_counter`` seconds also land in the registry.  The
    program's spans are named ``repro.*``.
  * ``count(name, n)`` — add ``n`` to a counter.
  * ``scope(name)`` — ``jax.named_scope``: names a phase of the step in
    the compiled program's op metadata (``op_name``) and adds no op.
    The step's scopes are named ``fluid.*``.
  * ``op_scopes(hlo_text)`` — instruction name -> innermost ``fluid.*``
    scope, read from an optimized HLO module's metadata, so a profiler
    trace's device ops can be put down to phases; ``sweep_op_scopes()``
    does so for every compiled executable of the sweep cache.

Nothing here runs on the launch path but the registry update: HLO is
parsed only when asked.  The module imports JAX lazily, so the
dependency-free ``exec_cache`` can use it.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import re
import threading
import time

SCOPE_PREFIX = "fluid."
UNSCOPED = "unscoped"


@dataclasses.dataclass(frozen=True)
class SpanStat:
    """How many times a span closed, their seconds, and the newest one's."""

    n: int = 0
    s: float = 0.0
    last_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class Stats:
    """Registry snapshot; subtract two snapshots for a window (a span's
    ``last_s`` is the newer snapshot's)."""

    spans: dict = dataclasses.field(default_factory=dict)    # name -> SpanStat
    counts: dict = dataclasses.field(default_factory=dict)   # name -> total

    def __sub__(self, other: "Stats") -> "Stats":
        zero = SpanStat()
        spans = {}
        for k, v in self.spans.items():
            o = other.spans.get(k, zero)
            if v.n > o.n:
                spans[k] = SpanStat(v.n - o.n, v.s - o.s, v.last_s)
        counts = {k: v - other.counts.get(k, 0) for k, v in self.counts.items()
                  if v != other.counts.get(k, 0)}
        return Stats(spans, counts)

    def span(self, name: str) -> SpanStat:
        return self.spans.get(name, SpanStat())


class Registry:
    """Span and counter totals of one process; safe across threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: dict = {}
        self._counts: dict = {}

    @contextlib.contextmanager
    def span(self, name: str):
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                n, s, _ = self._spans.get(name, (0, 0.0, 0.0))
                self._spans[name] = (n + 1, s + dt, dt)

    def count(self, name: str, n) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    def stats(self) -> Stats:
        with self._lock:
            return Stats({k: SpanStat(*v) for k, v in self._spans.items()},
                         dict(self._counts))


REGISTRY = Registry()
span = REGISTRY.span
count = REGISTRY.count
stats = REGISTRY.stats


def scope(name: str):
    """The only way the step names its phases (a test swaps it out)."""
    import jax

    return jax.named_scope(name)


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_SCOPE = re.compile(re.escape(SCOPE_PREFIX) + r"\w+")


def op_scopes(hlo_text: str) -> dict:
    """``{instruction name: innermost fluid.* scope}`` of an optimized
    HLO module.  A fusion carries the metadata of its root op, so it
    takes that op's scope; one the compiler left without metadata takes
    the scope most of its fused ops carry.  The rest, and instructions
    whose ``op_name`` holds no ``fluid.`` component, map to
    ``"unscoped"``."""
    own, calls, body, comp = {}, {}, collections.defaultdict(list), ""
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        found = _SCOPE.findall(op.group(1)) if op else []
        own[name] = found[-1] if found else UNSCOPED
        callee = _CALLS.search(line)
        if callee:
            calls[name] = callee.group(1)
        body[comp].append(name)

    def resolve(name, seen=()):
        if own[name] != UNSCOPED or name not in calls or name in seen:
            return own[name]
        votes = collections.Counter(resolve(n, seen + (name,)) for n in body[calls[name]])
        votes.pop(UNSCOPED, None)
        return votes.most_common(1)[0][0] if votes else UNSCOPED

    return {name: resolve(name) for name in own}


def module_name(hlo_text: str) -> str:
    """The ``HloModule`` name of an HLO module's text (``jit_scan_fn``);
    a profiler trace's ``XLA Modules`` events carry it."""
    head = hlo_text.split("\n", 1)[0]
    return head.split()[1].rstrip(",") if head.startswith("HloModule ") else ""


def sweep_op_scopes() -> dict:
    """``{module name: op_scopes(...)}`` of every AOT-compiled executable
    in the sweep cache (the mesh-sharded path caches a jitted callable,
    which has no compiled text and is left out).  Modules of one name
    merge."""
    from .experiments import SWEEP_EXEC_CACHE

    out: dict = {}
    for exe in SWEEP_EXEC_CACHE.values():
        as_text = getattr(exe, "as_text", None)
        if as_text is None:
            continue
        text = as_text()
        out.setdefault(module_name(text), {}).update(op_scopes(text))
    return out
