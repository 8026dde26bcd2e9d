"""Device: share of the traced window in which no operation ran on the
chip (a fraction)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    busy = ctx.trace.busy_s
    if busy <= 0:
        return None
    return 1.0 - busy / ctx.trace.window_s
