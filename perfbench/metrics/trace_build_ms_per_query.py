"""Host time from the start of a query's ``mc_query`` span to the first
device operation after it: per-seed trace build and stacking in the
Monte-Carlo entry, before the first launch.  Read from the device trace."""


def read(ctx):
    t = ctx.trace
    if t is None or t.lead_s is None:
        return None
    return 1e3 * t.lead_s / t.queries
