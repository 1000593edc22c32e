"""Share of the traced window in which no operation ran on the device,
in percent."""


def read(ctx):
    lo, hi = ctx.span
    busy = ctx.trace.busy_seconds(lo, hi)
    return 100.0 * (1.0 - busy / ctx.window_s) if busy > 0 else None
