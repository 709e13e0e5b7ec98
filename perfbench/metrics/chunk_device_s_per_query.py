"""Device time of the chunk-scan program (``_chunk_jit``) per query: the
summed durations of its executions in the device trace."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.chunk_s:
        return None
    return t.chunk_s / t.queries
