"""Device step, path selection: microseconds of device time a simulated
step under the program's ``fluid.select`` scope (UGAL's backlog pass and
candidate gathers, ``repro.core.fluid``), from the traced run's trace put
down to the program's scopes (``bench.scopes.reduce_scoped``).  Nothing
where the program names no such scope."""


def read(ctx):
    return ctx.get("select_us")
