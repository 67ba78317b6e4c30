"""Scenario build (host): seconds of the program's ``repro.routes.build``
spans, the route-table and route-set builds (cache misses only:
``net/fabric.py``), all of which fall in set-up.  Read from the
program's span registry (``repro.core.obs``); nothing where the program
keeps no spans."""


def read(ctx):
    try:
        from repro.core import obs
    except ImportError:
        return None
    routes = obs.stats().span("repro.routes.build")
    return routes.s if routes.n else None
