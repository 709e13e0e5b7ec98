"""Per-run scheduling metrics, uniform across both simulator backends.

One :class:`RunMetrics` record per (scenario, seed, placement, comm policy,
backend) simulation: JCT statistics (avg/median/p95), makespan, GPU
utilization and contention-event counts, plus the wall-clock cost of the
simulation itself.  The sweep runner (``scenarios/sweep.py``) emits lists of
these; ``benchmarks/run.py`` prints them as CSV rows.

:class:`CellCI` aggregates the per-seed records of one scenario x policy x
placement cell into mean +/- std confidence intervals
(:func:`ci_from_runs`) — the output format of the Monte-Carlo sweeps
(``benchmarks/run.py --scenario ... --ci``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.cluster import left_sum
from repro.core.engine import SimResult, median, percentile

CSV_FIELDS = (
    "scenario",
    "backend",
    "placement",
    "comm",
    "sched",
    "seed",
    "n_jobs",
    "n_finished",
    "censored",
    "avg_jct",
    "median_jct",
    "p95_jct",
    "makespan",
    "gpu_util",
    "comm_contended",
    "comm_clean",
    "preemptions",
    "resizes",
    "faults",
    "cancelled",
    "work_lost",
    "p99_jct",
    "goodput",
    "wall_s",
    "peak_calendar",
    "stretch_frac",
    "gating_frac",
)


@dataclasses.dataclass(frozen=True)
class RunMetrics:
    scenario: str
    backend: str  # "event" | "fluid"
    placement: str
    comm: str
    seed: int
    n_jobs: int
    n_finished: int
    avg_jct: float
    median_jct: float
    p95_jct: float
    makespan: float
    gpu_util: float
    comm_contended: int = 0
    comm_clean: int = 0
    wall_s: float = 0.0
    #: job scheduling policy (engine/policy split; fluid is always static)
    sched: str = "static"
    #: jobs with no finish time (horizon cutoff, or never placeable) —
    #: excluded from the JCT stats above, surfaced so truncation is
    #: never silent
    censored: int = 0
    #: gang preemptions / elastic resizes performed during the run
    preemptions: int = 0
    resizes: int = 0
    #: fault-injection SLO metrics (core/chaos.py; zero on fault-free runs):
    #: fault events injected (server breakdowns + NIC degradation
    #: windows), jobs stochastically cancelled, samples
    #: of in-progress iterations lost to fault/preemption restarts, tail
    #: JCT, and goodput — delivered samples (finished + partial progress
    #: carried by preempted jobs) per second of makespan
    faults: int = 0
    cancelled: int = 0
    work_lost: int = 0
    p99_jct: float = math.nan
    goodput: float = 0.0
    #: event-calendar high-water mark (O(cluster) bound check; 0 = fluid
    #: backend or pre-obs record)
    peak_calendar: int = 0
    #: observability-layer JCT decomposition aggregates (repro.obs): mean
    #: fraction of a finished job's JCT lost to contention stretch /
    #: gating wait.  NaN when the run was not observed
    #: (``observe=None``) — absent data, not zero.
    stretch_frac: float = math.nan
    gating_frac: float = math.nan
    #: fluid backend: scan chunks the batched driver launched for the
    #: batch this run was part of, one speculative chunk after the last
    #: that finished a lane included (0 = event backend)
    chunks: int = 0
    #: fluid backend, for the same batch: launches after the first at
    #: which the chunk launched before had already finished on the device,
    #: so the chip ran dry while the host caught up (0 = event backend)
    drained_launches: int = 0
    #: fluid backend, for the same batch (0 = event backend): lanes at
    #: each chunk's launch summed over chunks, padding included
    #: (``lane_slots``) and of those the lanes not yet retired
    #: (``live_lane_slots``); re-gathers of the batch (``compactions``);
    #: distinct (lanes, jobs, buckets) shapes launched (``shapes``)
    lane_slots: int = 0
    live_lane_slots: int = 0
    compactions: int = 0
    shapes: int = 0
    #: fluid backend on a fabric with uplink domains, this rollout's own
    #: (``jaxsim.UPLINK_COUNTERS``): jobs whose ring crossed a rack
    #: boundary, and transfers in flight at each tick's end summed over
    #: ticks, those over an uplink and all of them.  ``None`` where the
    #: fabric has no uplink, and on the event backend.
    uplink_jobs: Optional[int] = None
    uplink_transfer_ticks: Optional[int] = None
    transfer_ticks: Optional[int] = None

    def as_csv_row(self) -> str:
        vals = []
        for f in CSV_FIELDS:
            v = getattr(self, f)
            if isinstance(v, float):
                # fractions are small (often < 0.01): two decimals would
                # round every cell to 0.00
                vals.append(f"{v:.4f}" if f.endswith("_frac") else f"{v:.2f}")
            else:
                vals.append(str(v))
        return ",".join(vals)

    @staticmethod
    def csv_header() -> str:
        return ",".join(CSV_FIELDS)


def from_jcts(
    jcts: Sequence[float],
    *,
    scenario: str,
    backend: str,
    placement: str,
    comm: str,
    seed: int,
    n_jobs: int,
    makespan: float,
    gpu_util: float = math.nan,
    comm_contended: int = 0,
    comm_clean: int = 0,
    wall_s: float = 0.0,
    sched: str = "static",
    censored: Optional[int] = None,
    preemptions: int = 0,
    resizes: int = 0,
    faults: int = 0,
    cancelled: int = 0,
    work_lost: int = 0,
    p99_jct: Optional[float] = None,
    goodput: float = 0.0,
    peak_calendar: int = 0,
    stretch_frac: float = math.nan,
    gating_frac: float = math.nan,
    chunks: int = 0,
    drained_launches: int = 0,
    lane_slots: int = 0,
    live_lane_slots: int = 0,
    compactions: int = 0,
    shapes: int = 0,
    uplink_jobs: Optional[int] = None,
    uplink_transfer_ticks: Optional[int] = None,
    transfer_ticks: Optional[int] = None,
) -> RunMetrics:
    jcts = [float(x) for x in jcts]
    n_fin = len(jcts)
    return RunMetrics(
        scenario=scenario,
        backend=backend,
        placement=placement,
        comm=comm,
        seed=seed,
        n_jobs=n_jobs,
        n_finished=n_fin,
        avg_jct=(left_sum(jcts) / n_fin) if n_fin else math.nan,
        median_jct=median(jcts),
        p95_jct=percentile(jcts, 0.95),
        makespan=float(makespan),
        gpu_util=float(gpu_util),
        comm_contended=comm_contended,
        comm_clean=comm_clean,
        wall_s=wall_s,
        sched=sched,
        censored=(n_jobs - n_fin) if censored is None else censored,
        preemptions=preemptions,
        resizes=resizes,
        faults=faults,
        cancelled=cancelled,
        work_lost=work_lost,
        p99_jct=percentile(jcts, 0.99) if p99_jct is None else float(p99_jct),
        goodput=goodput,
        peak_calendar=peak_calendar,
        stretch_frac=stretch_frac,
        gating_frac=gating_frac,
        chunks=chunks,
        drained_launches=drained_launches,
        lane_slots=lane_slots,
        live_lane_slots=live_lane_slots,
        compactions=compactions,
        shapes=shapes,
        uplink_jobs=uplink_jobs,
        uplink_transfer_ticks=uplink_transfer_ticks,
        transfer_ticks=transfer_ticks,
    )


def from_event_result(
    res: SimResult,
    *,
    scenario: str,
    seed: int,
    n_jobs: int,
    wall_s: float = 0.0,
) -> RunMetrics:
    return from_jcts(
        list(res.jct.values()),
        scenario=scenario,
        backend="event",
        placement=res.placement_name,
        comm=res.policy_name,
        seed=seed,
        n_jobs=n_jobs,
        makespan=res.makespan,
        gpu_util=res.gpu_util,
        comm_contended=res.comm_started_contended,
        comm_clean=res.comm_started_clean,
        wall_s=wall_s,
        sched=res.sched_name,
        censored=res.censored,
        preemptions=res.preemptions,
        resizes=res.resizes,
        faults=res.faults,
        cancelled=res.cancelled,
        work_lost=res.work_lost_samples,
        p99_jct=res.p99_jct(),
        goodput=res.goodput,
        peak_calendar=res.peak_calendar,
        stretch_frac=(
            res.obs.mean_stretch_frac() if res.obs is not None else math.nan
        ),
        gating_frac=(
            res.obs.mean_gating_frac() if res.obs is not None else math.nan
        ),
    )


def replay_summary(
    res: SimResult, window_s: float, warmup_frac: float = 0.1
) -> Dict[str, float]:
    """Flat windowed steady-state summary of one (typically streaming)
    replay run — the ``SimResult.steady_state`` sliding-horizon metrics
    (sustained goodput, sustained finish rate, p99 JCT, queueing delay)
    plus the run-level scale counters, in one JSON-ready dict.  This is
    what ``benchmarks/run.py --only engine`` records for the trace-replay
    cell."""
    out = dict(res.steady_state(window_s, warmup_frac=warmup_frac))
    out.update(
        makespan=res.makespan,
        n_finished=float(len(res.jct)),
        censored=float(res.censored),
        goodput=res.goodput,
        events=float(res.events_processed),
        peak_calendar=float(res.peak_calendar),
    )
    if res.phase_seconds:
        # profile_phases=True: where the simulator's own wall-clock went
        # (comm integration / event dispatch / gating / GPU scheduling)
        out.update({f"phase_{k}_s": float(v) for k, v in res.phase_seconds.items()})
    return out


CI_CSV_FIELDS = (
    "scenario",
    "backend",
    "placement",
    "comm",
    "n_seeds",
    "avg_jct_mean",
    "avg_jct_std",
    "p95_jct_mean",
    "makespan_mean",
    "makespan_std",
    "finished_frac",
    "wall_s",
)


@dataclasses.dataclass(frozen=True)
class CellCI:
    """Mean +/- std over seeds for one scenario x backend x placement x comm
    cell — the Monte-Carlo confidence-interval row."""

    scenario: str
    backend: str
    placement: str
    comm: str
    n_seeds: int
    avg_jct_mean: float
    avg_jct_std: float
    p95_jct_mean: float
    makespan_mean: float
    makespan_std: float
    finished_frac: float
    wall_s: float

    def as_csv_row(self) -> str:
        vals = []
        for f in CI_CSV_FIELDS:
            v = getattr(self, f)
            vals.append(f"{v:.2f}" if isinstance(v, float) else str(v))
        return ",".join(vals)

    @staticmethod
    def csv_header() -> str:
        return ",".join(CI_CSV_FIELDS)


def _mean_std(xs: Sequence[float]) -> Tuple[float, float]:
    if not xs:
        return math.nan, math.nan
    mu = left_sum(xs) / len(xs)
    var = left_sum((x - mu) ** 2 for x in xs) / len(xs)
    return mu, math.sqrt(var)


def ci_from_runs(records: Sequence[RunMetrics]) -> List[CellCI]:
    """Collapse per-seed :class:`RunMetrics` into one :class:`CellCI` per
    (scenario, backend, placement, comm) cell — population std over seeds."""
    groups: Dict[Tuple[str, str, str, str], List[RunMetrics]] = {}
    for r in records:
        groups.setdefault((r.scenario, r.backend, r.placement, r.comm), []).append(r)
    out: List[CellCI] = []
    for (scn, backend, placement, comm), rs in sorted(groups.items()):
        avg_mu, avg_sd = _mean_std([r.avg_jct for r in rs])
        p95_mu, _ = _mean_std([r.p95_jct for r in rs])
        mk_mu, mk_sd = _mean_std([r.makespan for r in rs])
        out.append(
            CellCI(
                scenario=scn,
                backend=backend,
                placement=placement,
                comm=comm,
                n_seeds=len(rs),
                avg_jct_mean=avg_mu,
                avg_jct_std=avg_sd,
                p95_jct_mean=p95_mu,
                makespan_mean=mk_mu,
                makespan_std=mk_sd,
                finished_frac=sum(r.n_finished for r in rs)
                / max(1, sum(r.n_jobs for r in rs)),
                wall_s=left_sum(r.wall_s for r in rs),
            )
        )
    return out


def summarize(records: Sequence[RunMetrics]) -> Dict[str, Dict[str, float]]:
    """Aggregate per (scenario, backend, placement, comm): mean avg-JCT,
    mean makespan, mean utilization and total finished over seeds."""
    groups: Dict[str, List[RunMetrics]] = {}
    for r in records:
        groups.setdefault(
            f"{r.scenario}/{r.backend}/{r.placement}/{r.comm}", []
        ).append(r)
    out: Dict[str, Dict[str, float]] = {}
    for key, rs in sorted(groups.items()):
        out[key] = {
            "avg_jct": left_sum(r.avg_jct for r in rs) / len(rs),
            "p95_jct": left_sum(r.p95_jct for r in rs) / len(rs),
            "makespan": left_sum(r.makespan for r in rs) / len(rs),
            "gpu_util": left_sum(r.gpu_util for r in rs) / len(rs),
            "finished_frac": sum(r.n_finished for r in rs)
            / max(1, sum(r.n_jobs for r in rs)),
            "n_runs": float(len(rs)),
        }
    return out
