"""Host scenario build: seconds on the host clock around the program's
scenario build in set-up (``Sweep(...)`` of the grid, or ``ScenarioSpec.build``
of each query fabric): route tables and the flows' routes."""


def read(ctx):
    return ctx.get("host_build_s")
