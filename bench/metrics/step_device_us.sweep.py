"""Device step: microseconds of device busy time (the union of the
operations' intervals in the traced window) per simulated step of the
whole launch, all runs together."""


def read(ctx):
    t, steps = ctx.get("trace"), ctx.get("steps_simulated")
    if not t or not steps or t["busy_s"] <= 0:
        return None
    return t["busy_s"] / steps * 1e6
