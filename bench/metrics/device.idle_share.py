"""Share of the profiled window in which no operation ran on the device."""


def read(ctx):
    prof = ctx.profile
    if prof is None or prof.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / prof.window_s)
