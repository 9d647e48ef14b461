"""Share of the traced slice in which no operation ran on the device
(1 - busy / window, busy the union of operation intervals), in %."""


def read(ctx):
    if not ctx.trace.devices or not ctx.window_s:
        return None
    return 100.0 * (1.0 - ctx.trace.mean_busy_s() / ctx.window_s)
