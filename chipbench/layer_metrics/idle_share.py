"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window. Where the idle
time went, by the host span it fell in, is the run's ``breakdown``."""


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - t.busy_s / t.window_s)
