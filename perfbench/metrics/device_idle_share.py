"""Share of the traced query's wall time in which no operation ran on
the device: one minus the union of device-operation intervals over the
``mc_query`` span."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
