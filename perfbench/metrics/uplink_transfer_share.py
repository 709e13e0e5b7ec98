"""Share of the transfer time spent over a rack uplink, in %: the
program's per-rollout ``RunMetrics.uplink_transfer_ticks`` over
``transfer_ticks`` (transfers in flight at each tick's end, summed over
ticks), summed over the run's rollouts.  ``None`` where the records hold
no such counter (a NIC-only fabric, or a program without it)."""


def read(ctx):
    recs = [r for q in ctx.records if q for r in q
            if getattr(r, "uplink_transfer_ticks", None) is not None]
    total = sum(r.transfer_ticks for r in recs)
    if not total:
        return None
    return 100.0 * sum(r.uplink_transfer_ticks for r in recs) / total
