"""Share of the traced jobs' wall time in which no operation ran on the
device, in percent: 1 - busy union / job span."""


def read(ctx):
    if not ctx.trace.ops or ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
