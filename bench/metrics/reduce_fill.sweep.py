"""Device step: the share of the dense link reduction's gathered slots
that hold a contributor, the program's ``sweep.reduce_rows`` over its
``sweep.reduce_slots`` counters (``repro.core.obs``) over the run's
launches.  Nothing where the program keeps no such counters."""


def read(ctx):
    try:
        from repro.core import obs
    except ImportError:
        return None
    counts = obs.stats().counts
    slots = counts.get("sweep.reduce_slots", 0)
    return counts.get("sweep.reduce_rows", 0) / slots if slots else None
