"""Device, closed-loop cells: share of the traced window in which no
operation ran on the chip (1 - the union of device op intervals over the
window)."""

NAME, UNIT = "device_idle_share.batch", "%"


def read(ctx):
    if ctx.loop != "closed" or ctx.summary is None:
        return None
    sm = ctx.summary
    return 100.0 * (1.0 - sm.busy_ns / sm.window_ns)
