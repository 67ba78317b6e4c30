"""Compile: seconds the program's executable cache spent building in
set-up (``SWEEP_EXEC_CACHE.stats().build_s``); a load from the persistent
compile cache counts here too."""


def read(ctx):
    return ctx.get("compile_s")
