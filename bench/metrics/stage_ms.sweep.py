"""Launch staging (host): milliseconds of the program's
``repro.sweep.stage`` span (``Sweep._prepare``: stack, pad and stage
every point) in the newest launch of the run, read from the program's
span registry (``repro.core.obs``).  The newest launch is the window's
last, so the warm-up's first staging, which also compiles its eager
ops, is left out.  Nothing where the program keeps no spans."""


def read(ctx):
    try:
        from repro.core import obs
    except ImportError:
        return None
    stage = obs.stats().span("repro.sweep.stage")
    return stage.last_s * 1e3 if stage.n else None
