"""Scan chunks the batched driver launched per query (``RunMetrics.chunks``),
averaged over the run's queries: the driver's count of host round trips."""


def read(ctx):
    return sum(ctx.chunks) / len(ctx.chunks) if ctx.chunks else None
