"""Share of the batched driver's launches, after each query's first, at
which the chunk launched before had already finished on the device, in
%: 100 x the sum of ``RunMetrics.drained_launches`` over the sum of
``chunks - 1``, one value per query (every rollout of a query carries
its batch's counters).  Near 0 where the host keeps a chunk in flight;
``None`` where the records hold no such counter (a program without
it)."""


def read(ctx):
    recs = [q[0] for q in ctx.records
            if q and getattr(q[0], "drained_launches", None) is not None]
    launches = sum(r.chunks - 1 for r in recs)
    if not launches:
        return None
    return 100.0 * sum(r.drained_launches for r in recs) / launches
