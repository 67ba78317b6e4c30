"""Device step: share of the HBM-bandwidth roofline.  The least time of a
step is the bytes it must move (``bench.bytes_model.fluid_step_bytes`` at
the real shapes, summed over the launch's runs) over the chip's peak HBM
bandwidth; the step has no matrix product, so bandwidth binds.  That time
over the measured device time per step, in percent."""


def read(ctx):
    t, steps = ctx.get("trace"), ctx.get("steps_simulated")
    if not t or not steps or t["busy_s"] <= 0 or not ctx.get("step_bytes"):
        return None
    step_s = t["busy_s"] / steps
    return ctx["step_bytes"] / ctx["peaks"]["hbm_bytes_per_s"] / step_s * 100.0
