"""Trace fetch: bytes the program copies to the host per launch (the
decimated traces and the final state of every run), from its
``sweep.fetch_bytes`` counter over its ``repro.sweep.fetch`` spans
(``repro.core.obs``).  Nothing where the program keeps no counters."""


def read(ctx):
    try:
        from repro.core import obs
    except ImportError:
        return None
    s = obs.stats()
    n = s.span("repro.sweep.fetch").n
    return s.counts.get("sweep.fetch_bytes", 0) / n if n else None
