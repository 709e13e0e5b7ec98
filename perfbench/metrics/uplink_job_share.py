"""Share of the jobs whose ring crossed a rack boundary, in %: the
program's per-rollout ``RunMetrics.uplink_jobs`` over ``n_jobs``, summed
over the run's rollouts.  Placement's outcome on a fabric with uplinks;
``None`` where the records hold no such counter (a NIC-only fabric, or a
program without it)."""


def read(ctx):
    recs = [r for q in ctx.records if q for r in q
            if getattr(r, "uplink_jobs", None) is not None]
    if not recs:
        return None
    return 100.0 * sum(r.uplink_jobs for r in recs) / sum(r.n_jobs for r in recs)
