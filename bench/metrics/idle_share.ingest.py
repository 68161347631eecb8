"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    window = ctx.trace.window_s
    if window <= 0:
        return None
    return (1.0 - ctx.tr.busy_seconds(ctx.trace) / window) * 100
