"""Event engine for multi-job DDL cluster simulation (the mechanism half
of the engine/policy split; paper Algorithm 3 and Section V, exact
continuous-time variant).

This module owns everything *mechanical*: the event calendar, cluster/GPU
occupancy, the communication streams (Eq. 5 contention with exact
piecewise-constant-rate integration, WFBP bucket pipelines, topology
domain sets), trace recording, and result collection.  Every job-level
*decision* — admit, place, preempt, resize — is delegated to a
:class:`~repro.core.schedpolicy.SchedPolicy` through its
``on_arrival`` / ``on_job_finish`` / ``on_quantum`` hooks; the engine
exposes a small decision API for them:

* :meth:`EventEngine.place_job`       — commit a gang placement (rebuilds
  the WFBP fusion plan and topology domain sets for the placed world);
* :meth:`EventEngine.preempt_job`     — atomically tear a running gang
  down: cancel its in-flight compute and communication, release memory,
  carry its *completed* iterations, requeue it (the in-progress iteration
  is lost; the next placement pays the checkpoint/restore penalty
  :func:`repro.core.netmodel.preemption_cost`);
* :meth:`EventEngine.request_resize`  — schedule an elastic world-size
  change, applied by the engine at the job's next iteration boundary
  (where no in-iteration work exists to lose).

The default :class:`~repro.core.schedpolicy.StaticGangPolicy` reproduces
the pre-split monolithic ``ClusterSimulator`` bit-for-bit (no quantum
events, no preemption, no elasticity — the event stream is untouched);
``core/simulator.py`` remains the compatibility entry point.

Semantics preserved from the paper (see the original module docstring,
now in ``core/simulator.py``): online arrivals, SRSF priority everywhere,
memory admission with GPU time-sharing, pluggable communication gating
(AdaDUAL / SRSF(n) / k-way) and placement, and the beyond-paper WFBP
tensor-fusion subsystem.

Fault injection (beyond-paper, ``core/chaos.py``): a :class:`ChaosSpec`
arms seed-deterministic server breakdown/repair processes (a breakdown
force-preempts every gang touching the dead server and marks its GPUs
unplaceable until repair), transient per-server NIC degradation windows
(per-server bandwidth multipliers, integrated exactly), per-iteration
straggler jitter, and stochastic job cancellation.  Policies observe
faults through the ``on_fault`` / ``on_recovery`` hooks.  An absent or
inactive spec leaves the event stream bit-exact with the unfaulted
engine.

Progress accounting is in *samples* (per-GPU batches): a job's total work
is ``iterations x nominal GPUs`` and each completed iteration contributes
the current world size, so rigid jobs count exactly their ``iterations``
while elastic resizes conserve total work.  Jobs still running (or still
queued) when ``run(max_time=...)``'s horizon ends are reported as the
explicit ``SimResult.censored`` count instead of silently vanishing from
the JCT statistics.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import math
import os
import time
from bisect import insort
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple, Union

from repro.core import netmodel
from repro.core.chaos import (
    ChaosSpec,
    cancel_time,
    jitter_factor,
    nic_degradation_stream,
    server_failure_stream,
)
from repro.core.cluster import Cluster, GpuId, JobSpec, left_sum
from repro.core.clusterindex import ClusterIndex
from repro.core.contention import ContentionParams
from repro.core.trace import TraceSource
from repro.obs.recorder import ObsRecorder
from repro.core.placement import PlacementPolicy
from repro.core.schedpolicy import (
    AdaDual,
    CommPolicy,
    SchedPolicy,
    StaticGangPolicy,
    sched_policy_from_name,
)
from repro.core.topology import RingEdgeTopology, Topology, nic_topology

_EPS = 1e-9


# ---------------------------------------------------------------------------
# Runtime state
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CommTask:
    job_id: int
    servers: Set[int]
    remaining_bytes: float
    latency_left: float  # the fixed 'a' consumed in wall time before draining
    #: contention domains this task loads: topology domain indices (the
    #: fabric cuts its ring crosses — NICs, rack uplinks, ...; see
    #: core/topology.py) or, under the legacy "link" reading
    #: (``RingEdgeTopology``), the directed ring edges themselves (the
    #: paper's "each link between two nodes" wording)
    domains: frozenset = frozenset()
    #: WFBP bucket index this transfer carries (-1 = the monolithic
    #: iteration-level all-reduce)
    bucket: int = -1
    #: start order (monotonic per engine): ``_active_comm`` is insertion-
    #: ordered, so sorting any task subset by ``seq`` reproduces that
    #: dict's iteration order — the per-domain task index relies on this
    #: to rebuild a waiter's ``olds`` list in the exact legacy order
    seq: int = -1


@dataclasses.dataclass
class JobRun:
    spec: JobSpec
    gpus: List[GpuId]
    servers: Set[int]
    placed_at: float
    #: contention domains this placement's ring loads — a pure function of
    #: (topology, servers), so computed once per placement instead of per
    #: gating evaluation (``EventEngine.place_job`` fills it in)
    domains: frozenset = frozenset()
    iter_done: int = 0
    # Per-worker progress within the current iteration:
    f_done: Set[int] = dataclasses.field(default_factory=set)
    b_done: Set[int] = dataclasses.field(default_factory=set)
    comm_ready_at: Optional[float] = None  # all-reduce ready, not yet started
    comm_active: bool = False
    #: chunks of the current iteration's all-reduce still to send (beyond-
    #: paper: tensor-fusion-style chunked, hence preemptible, communication)
    comm_chunks_left: int = 0
    #: WFBP fusion plan ``(bucket_bytes, bucket_t_b)`` from
    #: ``netmodel.fusion_plan`` — None = the monolithic legacy path (the
    #: paper's iteration-level all-reduce, bit-for-bit).
    plan: Optional[Tuple[Tuple[float, ...], Tuple[float, ...]]] = None
    #: WFBP per-worker backward progress: completed segments (len n_world).
    b_prog: List[int] = dataclasses.field(default_factory=list)
    #: WFBP comm pipeline: next bucket to hand to the (FIFO) comm stream
    #: and buckets whose transfer already completed this iteration.
    next_bucket: int = 0
    buckets_done: int = 0
    finished_at: Optional[float] = None
    #: Progress in samples (per-GPU batches): total work carried by the
    #: job (conserved across preemptions and elastic resizes) and the part
    #: already done.  Each completed iteration contributes ``n_world``.
    samples_total: int = 0
    samples_done: int = 0
    #: Iterations this incarnation will have completed when the remaining
    #: samples drain at the current world size (None = the rigid
    #: ``spec.iterations`` — direct-constructed runs in tests).
    target_iters: Optional[int] = None
    #: Workers that still owe the checkpoint-restore penalty (charged on
    #: each worker's first compute task after a preemption/resize).
    restore_need: Set[int] = dataclasses.field(default_factory=set)
    restore_cost: float = 0.0
    #: Elastic world size requested for the next iteration boundary.
    pending_resize: Optional[int] = None
    #: memo for the nominal (non-bandwidth-aware) per-iteration service
    #: time — the SRSF keys recompute it on every comparison, but for one
    #: incarnation it only changes with the fusion plan / gang span (a
    #: re-placement builds a fresh JobRun, so staleness is impossible)
    _svc_cache: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    #: memo for the full SRSF priority key — valid while ``iter_done`` and
    #: the engine's service epoch (bumped when NIC chaos rewrites the
    #: bandwidth map) are unchanged; see ``EventEngine.srsf_key_running``
    _key_cache: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    #: lazily-built gpu -> worker-rank map (``gpus.index`` memo; ``gpus``
    #: is never reassigned after construction — a resize or requeue builds
    #: a fresh JobRun)
    _worker_of: Optional[Dict[GpuId, int]] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def __post_init__(self) -> None:
        if self.samples_total == 0:
            self.samples_total = self.spec.total_samples

    @property
    def n_world(self) -> int:
        """Current world size (== ``spec.n_gpus`` for rigid jobs)."""
        return len(self.gpus)

    @property
    def has_comm(self) -> bool:
        return len(self.servers) > 1

    @property
    def n_buckets(self) -> int:
        return len(self.plan[0]) if self.plan is not None else 1

    @property
    def _target(self) -> int:
        return (
            self.target_iters if self.target_iters is not None
            else self.spec.iterations
        )

    def per_iter_service(
        self, params: ContentionParams, bandwidth_aware: bool = False
    ) -> float:
        """Per-iteration service time: compute + contention-free comm (the
        per-message latency ``a`` is paid once per WFBP bucket).

        ``bandwidth_aware`` (beyond-paper, ROADMAP item) divides the
        per-byte term by the slowest member server's NIC multiplier, so a
        job placed on degraded links is recognized as having more service
        left.  Default False = the paper-faithful nominal estimate.
        """
        if not bandwidth_aware:
            # nominal estimate: pure function of (gang span, bucket count,
            # a, b) for this incarnation — memoized, recomputed only when
            # the fusion plan or span changes (bandwidth-aware estimates
            # read the mutable degradation state and are never cached)
            key = (len(self.servers) > 1, self.n_buckets, params.a, params.b)
            cached = self._svc_cache
            if cached is not None and cached[0] == key:
                return cached[1]
        t = self.spec.model.t_iter_compute
        if self.has_comm:
            scale = params.bandwidth_scale(self.servers) if bandwidth_aware else 1.0
            t += self.n_buckets * params.a + params.b * self.spec.model.size_bytes / scale
        if not bandwidth_aware:
            self._svc_cache = (key, t)
        return t

    def remaining_service(
        self, params: ContentionParams, bandwidth_aware: bool = False
    ) -> float:
        """SRSF key: remaining time x allocated GPUs (Tiresias-style)."""
        rem_iters = self._target - self.iter_done
        return rem_iters * self.per_iter_service(params, bandwidth_aware) * self.n_world


@dataclasses.dataclass(frozen=True)
class _Carry:
    """Progress of a preempted/resized job between placements."""

    iter_done: int
    samples_done: int
    samples_total: int
    restore_cost: float


def median(xs: Sequence[float]) -> float:
    """Median (mean of the middle two for even-length lists)."""
    if not xs:
        return math.nan
    ys = sorted(xs)
    n = len(ys)
    return ys[n // 2] if n % 2 else 0.5 * (ys[n // 2 - 1] + ys[n // 2])


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile, q in [0, 1] (the convention all JCT
    reporting in this repo shares)."""
    if not xs:
        return math.nan
    ys = sorted(xs)
    idx = min(len(ys) - 1, int(math.ceil(q * len(ys))) - 1)
    return ys[max(0, idx)]


@dataclasses.dataclass
class SimResult:
    policy_name: str
    placement_name: str
    jct: Dict[int, float]  # job_id -> completion - arrival
    finish: Dict[int, float]
    makespan: float
    gpu_busy: Dict[GpuId, float]
    gpu_util: float  # mean busy fraction over makespan
    queueing_delay: Dict[int, float]
    events_processed: int
    comm_started_contended: int
    comm_started_clean: int
    #: high-water mark of the event calendar (heap length) over the run —
    #: the engine's memory footprint driver.  With a materialized job list
    #: every arrival is pushed up front, so this is >= n_jobs; with a
    #: streaming :class:`~repro.core.trace.TraceSource` feed at most one
    #: future arrival is in the calendar at a time, so the high-water mark
    #: is O(cluster), independent of trace length.
    peak_calendar: int = 0
    #: name of the job scheduling policy (engine/policy split)
    sched_name: str = "static"
    #: jobs with no finish time: cut off by the simulation horizon
    #: (``run``'s ``max_time``), or stranded because they could never be
    #: placed (more GPUs/memory than the cluster has).  Excluded from the
    #: JCT statistics — this count makes the truncation explicit instead
    #: of silent.  0 whenever every job ran to completion.
    censored: int = 0
    #: gang preemptions (checkpoint + requeue) performed by the policy
    preemptions: int = 0
    #: elastic world-size changes applied at iteration boundaries
    resizes: int = 0
    #: fault injection (``core/chaos.py``): server breakdowns + NIC
    #: degradation windows suffered, stochastic job cancellations, and the
    #: samples of in-progress work thrown away by involuntary restarts
    #: (every teardown loses the in-flight iteration; the carry keeps only
    #: completed ones)
    faults: int = 0
    cancelled: int = 0
    work_lost_samples: int = 0
    #: delivered training throughput: samples completed by finished or
    #: still-live jobs per second of makespan.  Cancelled jobs contribute
    #: nothing — their partial progress was never delivered to anyone.
    goodput: float = 0.0
    task_trace: Optional[List[Tuple]] = None  # (job, iter, kind, worker, t0, t1)
    #: per-job delivered samples at finish time — the basis of the windowed
    #: goodput view (long replays care about *sustained* throughput, not the
    #: single makespan-frame average)
    job_samples: Dict[int, int] = dataclasses.field(default_factory=dict)
    #: opt-in (``profile_phases=True``) wall seconds per engine phase over
    #: the whole run: comm_advance / dispatch / gating / gpu_schedule.
    #: None when profiling was off (the default — zero overhead).
    phase_seconds: Optional[Dict[str, float]] = None
    #: opt-in (``observe=ObsConfig(...)``) observability report
    #: (``repro.obs.ObsReport``): exact per-job JCT decomposition,
    #: per-domain contention timelines, the gating audit log, and the
    #: Perfetto span records.  None when observability was off (the
    #: default — zero overhead, bit-exact event stream either way).
    obs: Optional[object] = None

    def avg_jct(self) -> float:
        return left_sum(self.jct.values()) / len(self.jct)

    def median_jct(self) -> float:
        return median(list(self.jct.values()))

    def p95_jct(self) -> float:
        return percentile(list(self.jct.values()), 0.95)

    def p99_jct(self) -> float:
        """Tail JCT — the SLO statistic the chaos scenarios report (fault
        restarts hit the tail far harder than the mean)."""
        return percentile(list(self.jct.values()), 0.99)

    # -- windowed steady-state view (trace-replay scale) ----------------------
    def windowed(self, window_s: float) -> List[Dict[str, float]]:
        """Bucket finished jobs into ``[i*w, (i+1)*w)`` windows over the run
        and report per-window completion stats.

        The finite-makespan frame (one average over the whole run) is the
        wrong lens for a 100k-arrival replay: it mixes the empty ramp-up,
        the steady state, and the final drain.  Each window row carries::

            t0, t1              window bounds (seconds)
            n_finished          jobs completing in the window
            goodput             delivered samples / window_s
            jobs_per_sec        completion rate
            p99_jct             nearest-rank p99 JCT of the window's jobs
            queueing_delay_mean mean queueing delay of the window's jobs

        Jobs are attributed to the window containing their *finish* time
        (the only instant at which JCT exists).
        """
        if window_s <= 0:
            raise ValueError(f"window_s must be positive, got {window_s}")
        if not self.finish:
            return []
        rows = sorted(
            (
                f,
                self.jct[j],
                self.queueing_delay.get(j, 0.0),
                self.job_samples.get(j, 0),
            )
            for j, f in self.finish.items()
        )
        n_win = int(self.makespan // window_s) + 1
        out: List[Dict[str, float]] = []
        i = 0
        for w in range(n_win):
            t0, t1 = w * window_s, (w + 1) * window_s
            jcts: List[float] = []
            qds: List[float] = []
            samples = 0
            while i < len(rows) and rows[i][0] < t1:
                _, jct, qd, s = rows[i]
                jcts.append(jct)
                qds.append(qd)
                samples += s
                i += 1
            out.append(
                {
                    "t0": t0,
                    "t1": t1,
                    "n_finished": float(len(jcts)),
                    "goodput": samples / window_s,
                    "jobs_per_sec": len(jcts) / window_s,
                    "p99_jct": percentile(jcts, 0.99),
                    "queueing_delay_mean": (
                        left_sum(qds) / len(qds) if qds else math.nan
                    ),
                }
            )
        return out

    def steady_state(
        self, window_s: float, warmup_frac: float = 0.1
    ) -> Dict[str, float]:
        """Sliding-horizon summary for long replays: drop the warmup prefix
        (first ``warmup_frac`` of the makespan) and the trailing partial
        window (the drain), then summarize the surviving body windows.

        ``sustained_goodput`` / ``sustained_jobs_per_sec`` are *medians* over
        the body windows (robust to a single empty or bursty window); the
        JCT/queueing-delay tails are nearest-rank percentiles over every job
        finishing inside the body interval.  Falls back to all windows when
        the run is too short for a warmup cut to leave anything."""
        wins = self.windowed(window_s)
        if not wins:
            return {}
        warmup_t = warmup_frac * self.makespan
        body = [w for w in wins[:-1] if w["t0"] >= warmup_t] or wins
        t_lo, t_hi = body[0]["t0"], body[-1]["t1"]
        jcts = [self.jct[j] for j, f in self.finish.items() if t_lo <= f < t_hi]
        qds = [
            self.queueing_delay.get(j, 0.0)
            for j, f in self.finish.items()
            if t_lo <= f < t_hi
        ]
        return {
            "window_s": window_s,
            "t_lo": t_lo,
            "t_hi": t_hi,
            "n_windows": float(len(body)),
            "n_jobs": float(len(jcts)),
            "sustained_goodput": median([w["goodput"] for w in body]),
            "sustained_jobs_per_sec": median([w["jobs_per_sec"] for w in body]),
            "p99_jct": percentile(jcts, 0.99),
            "queueing_delay_mean": left_sum(qds) / len(qds) if qds else math.nan,
            "queueing_delay_p99": percentile(qds, 0.99),
        }


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class EventEngine:
    """Exact event-driven simulation of Algorithm 3's dynamics, with all
    job-level decisions delegated to a pluggable
    :class:`~repro.core.schedpolicy.SchedPolicy`."""

    def __init__(
        self,
        jobs: Union[Sequence[JobSpec], TraceSource],
        cluster: Optional[Cluster] = None,
        placement: Optional[PlacementPolicy] = None,
        comm_policy: Optional[CommPolicy] = None,
        params: Optional[ContentionParams] = None,
        fuse_fb: bool = True,
        record_trace: bool = False,
        comm_chunks: int = 1,
        contention_domain: str = "server",  # server (NIC) | link (ring edges)
        exclusive_gpus: bool = False,  # paper assumption 3 reading
        bandwidth_aware_srsf: bool = False,  # hetero-aware remaining-service
        topology: Optional[Topology] = None,  # fabric contention domains
        fusion: object = "all",  # WFBP tensor fusion: 'all' | 'none' | bytes
        sched: Union[SchedPolicy, str, None] = None,  # job scheduling policy
        preemption_quantum: Optional[float] = None,  # tick for named scheds
        checkpoint_cost: Optional[float] = None,  # None = netmodel model
        chaos: Optional[ChaosSpec] = None,  # fault injection (core/chaos.py)
        gating: Optional[str] = None,  # incremental (default) | rescan
        workloads: Optional[str] = None,  # incremental (default) | rescan
        profile_phases: bool = False,  # per-phase wall-clock counters
        observe: Optional[object] = None,  # repro.obs.ObsConfig | None
    ) -> None:
        # Streaming arrival feed (trace-replay scale): a TraceSource yields
        # arrivals lazily, so the calendar holds at most ONE future arrival
        # instead of the whole trace — O(cluster) memory at 100k+ jobs.
        # A materialized job list keeps the legacy all-up-front behaviour
        # bit-for-bit.
        if isinstance(jobs, TraceSource):
            self._source: Optional[TraceSource] = jobs
            self.jobs: Dict[int, JobSpec] = {}
        else:
            self._source = None
            self.jobs = {j.job_id: j for j in jobs}
        self.cluster = cluster or Cluster()
        self.placement = placement or PlacementPolicy("lwf", kappa=1)
        self.comm_policy = comm_policy or AdaDual()
        self.params = params or ContentionParams()
        # Fusing f+b into one GPU occupancy halves event count; a newly
        # placed higher-priority job can then preempt only at (f+b)
        # boundaries instead of f|b boundaries (distortion <= t_b ~ 50 ms).
        # Fidelity tests set fuse_fb=False.
        self.fuse_fb = fuse_fb and not record_trace
        self.record_trace = record_trace
        # Beyond-paper (future-work #3 adjacent): split each all-reduce into
        # N chunks scheduled independently — a long transfer can lose the
        # link to a shorter job's message at every chunk boundary, making
        # communication effectively preemptible.  The per-message latency
        # `a` is charged per chunk (that is the real cost of chunking).
        self.comm_chunks = max(1, comm_chunks)
        # WFBP tensor fusion (layer-granular communication subsystem):
        # 'all' = one monolithic all-reduce per iteration (the paper's model
        # and the legacy behaviour bit-for-bit); 'none' / a byte threshold =
        # per-bucket transfers (netmodel.fusion_plan) that overlap the
        # remaining backward pass, gated per bucket.  Only jobs whose
        # ModelProfile carries layer data (repro.workloads) are affected;
        # Table III profiles always run monolithic.
        self._fusion_threshold = netmodel.fusion_threshold(fusion)
        self.fusion = fusion
        if self._fusion_threshold != math.inf and self.comm_chunks > 1:
            raise ValueError(
                "comm_chunks and WFBP fusion are mutually exclusive — the "
                "fusion plan already chunks the all-reduce"
            )
        self._plan_cache: Dict[int, Optional[tuple]] = {}
        # "server": the server's NIC is the shared resource (conservative —
        # all flows through one 10GbE port contend).  "link": the paper's
        # wording — contention only between tasks sharing a ring edge
        # (server pair), allowing disjoint transfers to proceed in parallel.
        if contention_domain not in ("server", "link"):
            raise ValueError(f"unknown contention domain {contention_domain!r}")
        self.contention_domain = contention_domain
        # An explicit fabric topology (core/topology.py) supersedes the
        # contention_domain string; the default NIC-only topology is the
        # identical computation as "server" (one domain per server, all
        # oversub 1.0), so behaviour is bit-for-bit unchanged.  The legacy
        # ring-edge "link" reading is the dynamic RingEdgeTopology: the same
        # per-task domains the old inline code produced (regression-locked
        # in tests/test_chunked_comm.py), expressed as topology domains.
        if topology is not None and topology.n_servers != self.cluster.n_servers:
            raise ValueError(
                f"topology covers {topology.n_servers} servers, cluster has "
                f"{self.cluster.n_servers}"
            )
        if topology is None:
            topology = (
                nic_topology(self.cluster.n_servers)
                if contention_domain == "server"
                else RingEdgeTopology(self.cluster.n_servers)
            )
        self.topology = topology
        #: every contention domain at unit oversubscription (NIC-only
        #: fabrics, ring edges) ⇒ k_eff is just the max integer domain
        #: load — lets the comm drain skip the per-domain float multiply
        #: (RingEdgeTopology's dynamic edge domains are always 1.0, and
        #: its static ``domains`` tuple is empty, so all() holds)
        self._uniform_oversub = all(d.oversub == 1.0 for d in topology.domains)
        self.cluster.exclusive = exclusive_gpus
        # SRSF priority estimate under server_bandwidth heterogeneity: the
        # paper's nominal homogeneous comm time (False, default) or scaled
        # by the slowest member NIC (True) — see JobRun.per_iter_service.
        self.bandwidth_aware_srsf = bandwidth_aware_srsf
        # Job scheduling strategy (engine/policy split).  The static
        # default schedules no quantum events and never preempts/resizes,
        # so the event stream matches the pre-split simulator exactly.
        if sched is None:
            sched = StaticGangPolicy()
        elif isinstance(sched, str):
            sched = sched_policy_from_name(sched, quantum=preemption_quantum)
        self.sched = sched
        self.checkpoint_cost = checkpoint_cost
        # Communication gating strategy: "incremental" re-evaluates only
        # waiters whose contention domains were touched since their last
        # evaluation (bit-exact with the full rescan — see
        # _try_start_comms_incremental); "rescan" is the legacy
        # every-waiter-every-event reference the differential tests lock
        # against.  REPRO_GATING overrides the default for A/B runs.
        if gating is None:
            gating = os.environ.get("REPRO_GATING", "incremental")
        if gating not in ("incremental", "rescan"):
            raise ValueError(
                f"unknown gating mode {gating!r} (expected 'incremental' or "
                "'rescan')"
            )
        self.gating = gating
        # Workload-aggregate strategy: "incremental" maintains the
        # cluster-state indices of core/clusterindex.py (per-GPU resident
        # lists, per-server/rack load sums, feasibility aggregates) updated
        # in O(gang) per state change, and refresh_workloads re-folds only
        # dirty GPUs; "rescan" is the legacy zero-and-rebuild walk over all
        # live runs, kept as the differential oracle.  Bit-exact either way
        # (tests/test_workloads_incremental.py); REPRO_WORKLOADS overrides.
        if workloads is None:
            workloads = os.environ.get("REPRO_WORKLOADS", "incremental")
        if workloads not in ("incremental", "rescan"):
            raise ValueError(
                f"unknown workloads mode {workloads!r} (expected "
                "'incremental' or 'rescan')"
            )
        self.workloads = workloads
        self._windex: Optional[ClusterIndex] = (
            ClusterIndex(self.cluster) if workloads == "incremental" else None
        )
        # the placement policy reads the maintained aggregates when bound;
        # rebinding here keeps a reused policy object consistent with THIS
        # engine's mode
        self.placement.index = self._windex
        self.profile_phases = profile_phases
        self._phase_seconds: Optional[Dict[str, float]] = (
            {"comm_advance": 0.0, "dispatch": 0.0, "gating": 0.0,
             "gpu_schedule": 0.0}
            if profile_phases
            else None
        )

        self._heap: List[Tuple[float, int, str, tuple]] = []
        self._peak_heap = 0
        self._seq = itertools.count()
        self._queue: List[int] = []  # unplaced job ids
        #: queued job ids grouped by resource profile ``(n_gpus, mem_mb)``,
        #: each bucket in ``srsf_key_queued`` order (the queue's own order).
        #: Maintained by ``_enqueue``/``remove_queued`` — placement
        #: feasibility is a pure function of the profile, so the admission
        #: scan can walk bucket heads instead of every queued job.
        self._queue_buckets: Dict[tuple, List[int]] = {}
        self._queue_profile_of: Dict[int, tuple] = {}
        self._runs: Dict[int, JobRun] = {}
        #: placed-and-unfinished job ids in the same (insertion) order their
        #: runs sit in ``_runs`` — the workload refresh walks this instead
        #: of all of ``_runs`` (which keeps every finished run for result
        #: collection and so grows with the whole trace); identical float
        #: accumulation order, O(live) instead of O(total jobs) per refresh
        self._live: Dict[int, None] = {}
        self._active_comm: Dict[int, CommTask] = {}
        #: In-flight transfers per contention domain, maintained
        #: incrementally on every comm start/finish/abort — the same
        #: integers the old per-event scans over ``_active_comm``
        #: produced (bit-exact), without the O(active^2) rescans.
        self._domain_load: Dict[object, int] = {}
        #: In-flight transfers per contention domain (same keys as
        #: ``_domain_load``), each list in start (``seq``) order — the
        #: gating path rebuilds a waiter's overlapping-transfer list from
        #: these instead of scanning all of ``_active_comm`` per attempt.
        self._domain_tasks: Dict[object, List[CommTask]] = {}
        #: monotonic comm-start counter feeding ``CommTask.seq``
        self._comm_seq = 0
        #: memo for ``params.rate(k)`` — valid for the whole run: ``b`` and
        #: ``eta`` are fixed at construction (NIC chaos replaces only
        #: ``server_bandwidth``), and the cached value IS the float the
        #: expression produced once, so reuse is bit-identical
        self._rate_cache: Dict[float, float] = {}
        #: bumped whenever NIC chaos rewrites the bandwidth map — part of
        #: the ``JobRun._key_cache`` validity key for SRSF priorities
        self._svc_epoch = 0
        self._waiting_comm: List[int] = []  # job ids with gated all-reduce
        self._waiting_set: Set[int] = set()  # same ids, O(1) membership
        #: incremental gating indexes: waiters per contention domain, and
        #: the set of waiters whose gating decision may have changed since
        #: their last evaluation (new waiters + waiters on domains touched
        #: by a comm start/end/abort) — see _try_start_comms_incremental
        self._domain_waiters: Dict[object, Set[int]] = {}
        self._gate_candidates: Set[int] = set()
        self._comm_epoch = 0
        self._last_comm_update = 0.0
        self._dirty_gpus: Set[GpuId] = set()
        self._events = 0
        self._comm_contended = 0
        self._comm_clean = 0
        self._trace: List[Tuple] = []
        self._unfinished = set(self.jobs)
        # Streaming-feed state: the lazy arrival iterator, how many arrival
        # events are in the calendar but not yet processed (at most 1), the
        # monotonicity check on source order, how many jobs have *entered*
        # the system (== len(jobs) in list mode), and runs awaiting
        # end-of-event retirement (streaming keeps memory O(live jobs)).
        self._stream: Optional[Iterator[JobSpec]] = None
        self._arrivals_pending = 0
        self._last_arrival = -math.inf
        self._n_seen = len(self.jobs)
        self._retire_buf: List[int] = []
        # Per-job results recorded at finish time (the streaming feed
        # retires finished runs, so results cannot be collected from _runs
        # at the end the way list mode does).
        self._jct_at_finish: Dict[int, float] = {}
        self._finish_at: Dict[int, float] = {}
        self._qdelay_at_finish: Dict[int, float] = {}
        self._job_samples: Dict[int, int] = {}
        # Preemption/elasticity mechanism state:
        self._carry: Dict[int, _Carry] = {}  # progress of requeued jobs
        self._epoch_of: Dict[int, int] = {}  # run incarnation (tombstones)
        self._first_placed: Dict[int, float] = {}
        self._preemptions = 0
        self._resizes = 0
        self._comm_dirty = False  # active comm set mutated outside gating
        # Fault injection (core/chaos.py).  An absent or inactive spec keeps
        # every chaos code path cold: no chaos events are ever pushed, so the
        # event stream is bit-exact with the unfaulted engine (the zero-rate
        # no-op, regression-locked in tests/test_chaos.py).
        self._chaos = chaos if (chaos is not None and chaos.active) else None
        # Observability (repro.obs).  Same pattern as chaos: an absent or
        # inactive config keeps every obs hook cold — the recorder never
        # mutates engine state, so the event stream is bit-exact with
        # observability on OR off (locked in tests/test_obs.py).
        self._obs = (
            ObsRecorder(observe)
            if (observe is not None and observe.active)
            else None
        )
        if self._obs is not None:
            # the deferred replay needs the Eq. 5 constants and the gating
            # policy (for audit `explain` terms) — both fixed for the run
            self._obs.bind(self.params, self.comm_policy)
        # Hot-stream caches: the highest-frequency obs hooks (comm windows,
        # compute spans, gating audits, gating queue enter/leave, transfer
        # ends) are plain flat-list extends inlined at the call sites below
        # — a None cache means that record family is off and costs one
        # is-check.  The recorder's flush clears the log in place, so these
        # references never go stale.
        o = self._obs
        self._obs_win = o.log if (o is not None and o.decompose_on) else None
        self._obs_comm = o.log if (o is not None and o.log_comm) else None
        self._obs_gate = o.log if (o is not None and o.log_gate) else None
        self._obs_rc = o.raw_compute if (o is not None and o.spans_on) else None
        # raw_compute is flat at stride 6, so the element cap is 6x
        self._obs_rc_cap = o.config.span_cap * 6 if o is not None else 0
        self._obs_audit = o.audit_raw if (o is not None and o.audit_on) else None
        self._obs_audit_left = o.config.audit_cap if o is not None else 0
        self._faults = 0
        self._cancelled = 0
        self._work_lost_samples = 0
        self._down_servers: Set[int] = set()
        self._fail_streams: Dict[int, Iterator[Tuple[float, float]]] = {}
        self._nic_streams: Dict[int, Iterator[Tuple[float, float]]] = {}
        self._nic_degraded: Set[int] = set()
        self._base_server_bw: Tuple[float, ...] = ()
        self.sched.bind(self)

    # -- policy-facing state views -------------------------------------------
    @property
    def queue(self) -> List[int]:
        """Unplaced job ids, mutated in place by the scheduling policy."""
        return self._queue

    @property
    def queue_buckets(self) -> Dict[tuple, List[int]]:
        """Queued jobs grouped by ``(n_gpus, mem_mb)`` resource profile,
        each bucket in SRSF-queued order (read-only for policies)."""
        if len(self._queue_profile_of) != len(self._queue):
            self._sync_queue_buckets()
        return self._queue_buckets

    @property
    def queue_profile_of(self) -> Dict[int, tuple]:
        """Resource profile of every queued job (read-only for policies)."""
        if len(self._queue_profile_of) != len(self._queue):
            self._sync_queue_buckets()
        return self._queue_profile_of

    def _sync_queue_buckets(self) -> None:
        """Rebuild the profile indices from the queue list.  Only needed
        when outside code mutates ``queue`` directly (legacy tests append
        to the exposed list) instead of going through ``_enqueue`` /
        ``remove_queued``, which maintain the indices incrementally."""
        self._queue_profile_of = prof = {}
        self._queue_buckets = buckets = {}
        jobs = self.jobs
        for jid in sorted(self._queue, key=self.srsf_key_queued):
            spec = jobs[jid]
            profile = (spec.n_gpus, spec.model.mem_mb)
            prof[jid] = profile
            bucket = buckets.get(profile)
            if bucket is None:
                buckets[profile] = [jid]
            else:
                bucket.append(jid)

    @property
    def runs(self) -> Dict[int, JobRun]:
        """Live job runs (read-only for policies; mutate via the API)."""
        return self._runs

    # -- queue maintenance -----------------------------------------------------
    def _enqueue(self, job_id: int) -> None:
        """Insert a job into the wait queue and its profile bucket, both in
        ``srsf_key_queued`` order (the key is static while the job waits)."""
        insort(self._queue, job_id, key=self.srsf_key_queued)
        spec = self.jobs[job_id]
        profile = (spec.n_gpus, spec.model.mem_mb)
        self._queue_profile_of[job_id] = profile
        bucket = self._queue_buckets.get(profile)
        if bucket is None:
            self._queue_buckets[profile] = [job_id]
        else:
            insort(bucket, job_id, key=self.srsf_key_queued)

    def remove_queued(self, job_ids) -> None:
        """Drop placed/cancelled jobs from the wait queue and its profile
        buckets — the policies' queue-surgery API (one order-preserving
        rebuild instead of an O(queue) ``list.remove`` per job).  Ids not
        currently queued are ignored."""
        drop = set(job_ids)
        if not drop:
            return
        buckets = self._queue_buckets
        if len(drop) == 1:
            # the common case (one admission per scan): a C-level
            # list.remove beats rebuilding the whole queue
            (jid,) = drop
            profile = self._queue_profile_of.pop(jid, None)
            if profile is None:
                return
            self._queue.remove(jid)
            bucket = buckets[profile]
            bucket.remove(jid)
            if not bucket:
                del buckets[profile]
            return
        self._queue[:] = [j for j in self._queue if j not in drop]
        for jid in drop:
            profile = self._queue_profile_of.pop(jid, None)
            if profile is None:
                continue
            bucket = buckets[profile]
            bucket.remove(jid)
            if not bucket:
                del buckets[profile]

    # -- event helpers -------------------------------------------------------
    def _push(self, t: float, kind: str, data: tuple) -> None:
        heapq.heappush(self._heap, (t, next(self._seq), kind, data))
        if len(self._heap) > self._peak_heap:
            self._peak_heap = len(self._heap)

    # -- SRSF priority ---------------------------------------------------------
    def srsf_key_queued(self, job_id: int):
        """SRSF key of a queued job.  Fresh jobs use the paper's
        convention (E_J = 0 before placement, Section IV-A); requeued
        preempted jobs use their carried remaining work in samples."""
        spec = self.jobs[job_id]
        carry = self._carry.get(job_id)
        if carry is None:
            rem = spec.compute_time * spec.n_gpus
        else:
            rem_samples = carry.samples_total - carry.samples_done
            rem = spec.model.t_iter_compute * rem_samples
        return (rem, spec.arrival, job_id)

    def srsf_key_running(self, job_id: int):
        # The key is a pure function of (iter_done, service epoch) for one
        # incarnation: target/plan/world are fixed at placement, params.a/b
        # never change, and the only mutable input — the NIC bandwidth map
        # read under bandwidth_aware_srsf — bumps _svc_epoch when chaos
        # rewrites it.  Priority sorts call this O(waiters log waiters)
        # per dispatch, so the memo is load-bearing at 10k+ jobs.
        run = self._runs[job_id]
        cached = run._key_cache
        if (
            cached is not None
            and cached[0] == run.iter_done
            and cached[1] == self._svc_epoch
        ):
            return cached[2]
        rem = run.remaining_service(self.params, self.bandwidth_aware_srsf)
        key = (rem, run.spec.arrival, job_id)
        run._key_cache = (run.iter_done, self._svc_epoch, key)
        return key

    # backwards-compatible private aliases (pre-split internal names)
    _srsf_key_queued = srsf_key_queued
    _srsf_key_running = srsf_key_running

    # -- communication bookkeeping --------------------------------------------
    def _domains_of(self, servers: Set[int]) -> frozenset:
        """Contention domains a comm task over ``servers`` loads: the
        topology cuts its ring crosses (domain indices), or — under the
        legacy "link" reading, now ``RingEdgeTopology`` — the directed ring
        edges themselves."""
        return self.topology.loaded_domains(servers)

    def _comm_started(self, task: CommTask) -> None:
        for d in task.domains:
            self._domain_load[d] = self._domain_load.get(d, 0) + 1
            self._domain_tasks.setdefault(d, []).append(task)
        self._mark_domains_dirty(task.domains)

    def _comm_ended(self, task: CommTask) -> None:
        for d in task.domains:
            left = self._domain_load[d] - 1
            if left:
                self._domain_load[d] = left
            else:
                del self._domain_load[d]
            lst = self._domain_tasks[d]
            lst.remove(task)
            if not lst:
                del self._domain_tasks[d]
        self._mark_domains_dirty(task.domains)

    # -- incremental gating indexes -------------------------------------------
    def _waiter_add(self, jid: int, run: JobRun) -> None:
        """Enqueue a gated all-reduce: the waiter list (SRSF evaluation
        order lives there), the per-domain index, and the candidate set —
        a fresh waiter always gets its first evaluation."""
        self._waiting_comm.append(jid)
        self._waiting_set.add(jid)
        for d in run.domains:
            self._domain_waiters.setdefault(d, set()).add(jid)
        self._gate_candidates.add(jid)
        lg = self._obs_gate
        if lg is not None:
            # _advance_comm unconditionally stamps _last_comm_update with
            # the current event time before any dispatch reaches here
            lg.extend((4, self._last_comm_update, jid))

    def _waiter_drop(self, jid: int, domains: frozenset) -> None:
        """Remove a waiter from every gating index (started / preempted /
        cancelled).  ``domains`` is passed explicitly because teardown
        paths pop the run from ``_runs`` before cleaning the indexes."""
        self._waiting_comm.remove(jid)
        self._waiting_set.discard(jid)
        for d in domains:
            ws = self._domain_waiters.get(d)
            if ws is not None:
                ws.discard(jid)
                if not ws:
                    del self._domain_waiters[d]
        self._gate_candidates.discard(jid)
        lg = self._obs_gate
        if lg is not None:
            lg.extend((5, self._last_comm_update, jid))

    def _mark_domains_dirty(self, domains: frozenset) -> None:
        """A comm start/end/abort touched these domains: every waiter
        sharing one must be re-evaluated (its ``olds`` set or ``max_conc``
        input just changed)."""
        for d in domains:
            ws = self._domain_waiters.get(d)
            if ws:
                self._gate_candidates.update(ws)

    def _comm_k_eff(self, task: CommTask) -> float:
        """Effective contention for the Eq. (5) *rate*: per-domain count
        scaled by that domain's oversubscription factor (an uplink with
        oversub f delivers 1/f of nominal bandwidth, so k tasks crossing it
        drain like k*f tasks on a NIC).  All-1.0 oversub (the NIC-only
        topology, and the legacy ring-link reading) reduces to the raw k.

        ``_domain_load`` carries exactly the counts the old scans over
        ``_active_comm`` computed, so the result is bit-identical."""
        if self._uniform_oversub:
            # all-1.0 oversub: k = max(1, integer domain load) exactly
            # (load * 1.0 == float(load)) — skips the oversub_of calls
            load = self._domain_load
            k = 1
            for d in task.domains:
                v = load.get(d, 0)
                if v > k:
                    k = v
            return float(k)
        k = 1.0
        load = self._domain_load
        oversub = self.topology.oversub_of
        for d in task.domains:
            v = load.get(d, 0) * oversub(d)
            if v > k:
                k = v
        return k

    def _advance_comm(self, now: float) -> List[int]:
        """Drain all in-flight comm tasks from the last update to ``now``.
        Returns job ids whose all-reduce completed in this window."""
        dt = now - self._last_comm_update
        self._last_comm_update = now
        finished: List[int] = []
        if dt <= 0 or not self._active_comm:
            return finished
        # Rates are piecewise constant between events because the active set
        # only changes at events (domain loads are a pure function of the
        # active set); use the rate as of the window start — this stays an
        # exact piecewise-rate integration under any topology.
        lg = self._obs_win
        ks: Optional[Dict[int, float]] = None
        if lg is not None:
            ks = {jid: self._comm_k_eff(t) for jid, t in self._active_comm.items()}
            # decomposition record: the window's per-task rates, logged
            # before the drain loop consumes latency_left (the deferred
            # replay re-consumes the latency slice identically).  Flat
            # layout — 0, dt, n, jid*n, k*n — so only scalars are
            # retained (a retained tuple per window is real GC pressure)
            lg.extend((0, dt, len(ks)))
            lg.extend(ks)
            lg.extend(ks.values())
            if len(lg) >= self._obs.flush_at:
                self._obs._flush()
        params = self.params
        bw_uniform = not params.server_bandwidth
        rcache = self._rate_cache
        # no keys are added/removed inside the loop (_comm_ended runs after
        # it), so iterating the dict directly is safe — no list() copy
        for jid, task in self._active_comm.items():
            ll = task.latency_left
            if ll < dt:
                # legacy arithmetic: lat = min(ll, dt) = ll, latency -> 0.0
                # exactly, drain_t = dt - ll
                task.latency_left = 0.0
                # domain loads are constant inside this loop, so computing
                # k here instead of in the up-front dict is the same
                # number — the dict is only materialized when the obs
                # window log needs all of them
                k = ks[jid] if ks is not None else self._comm_k_eff(task)
                rate = rcache.get(k)
                if rate is None:
                    rate = params.rate(k)
                    rcache[k] = rate
                if not bw_uniform:
                    # rate * 1.0 == rate exactly, so the uniform case may
                    # skip the multiply the legacy expression performed
                    rate *= params.bandwidth_scale(task.servers)
                task.remaining_bytes -= (dt - ll) * rate
                if task.remaining_bytes <= 1.0:
                    # tolerance: 1 byte ~ 1e-9 s — absorbs float drift in
                    # the piecewise integration (latency_left is 0 here)
                    finished.append(jid)
            else:
                # pure-latency window: lat = dt, no drain (drain_t = 0)
                task.latency_left = ll - dt
                if task.latency_left <= _EPS and task.remaining_bytes <= 1.0:
                    finished.append(jid)
        lg = self._obs_comm
        for jid in finished:
            self._comm_ended(self._active_comm[jid])
            del self._active_comm[jid]
            if lg is not None:
                lg.extend((2, now, jid))
        return finished

    def _next_comm_finish(self) -> Optional[float]:
        if not self._active_comm:
            return None
        t_min = math.inf
        params = self.params
        bw_uniform = not params.server_bandwidth
        rcache = self._rate_cache
        base = self._last_comm_update
        for task in self._active_comm.values():
            k = self._comm_k_eff(task)
            rate = rcache.get(k)
            if rate is None:
                rate = params.rate(k)
                rcache[k] = rate
            if not bw_uniform:
                rate *= params.bandwidth_scale(task.servers)
            t = base + task.latency_left + task.remaining_bytes / rate
            if t < t_min:
                t_min = t
        return t_min

    def _reschedule_comm_check(self) -> None:
        self._comm_epoch += 1
        t = self._next_comm_finish()
        if t is not None:
            self._push(t, "comm_check", (self._comm_epoch,))

    def _abort_comm(self, job_id: int) -> None:
        """Abort ``job_id``'s in-flight all-reduce (preemption, breakdown,
        cancellation).  Beyond dropping the task and its domain loads, this
        flags ``_comm_dirty`` so the main loop both re-predicts the finish
        times of the survivors (their rates just improved) *and* re-runs the
        gating pass — a waiter that was gated against the aborted transfer
        must get its lookahead re-evaluated against the freed domains in the
        same event, not at the next unrelated comm event.  Locked by
        ``tests/test_chaos.py::TestAbortedCommGating``."""
        task = self._active_comm.pop(job_id)
        self._comm_ended(task)
        self._comm_dirty = True
        if self._obs is not None:
            # the aborted transfer's accrued comm time delivered nothing:
            # reattribute it to preemption/fault overhead
            self._obs.comm_abort(job_id, self._last_comm_update)

    # -- WFBP fusion plans -------------------------------------------------------
    def _assign_plan(self, run: JobRun) -> None:
        """Attach the WFBP fusion plan to a freshly-placed run: per-bucket
        (bytes, backward-segment seconds) when fusion is finite, the model
        carries layer data, and the placement actually spans servers —
        otherwise the monolithic legacy path (plan None)."""
        if self._fusion_threshold == math.inf or not run.has_comm:
            return
        model = run.spec.model
        if not getattr(model, "has_layers", False):
            return
        key = id(model)
        if key not in self._plan_cache:
            self._plan_cache[key] = netmodel.fusion_plan(
                model.layer_grad_bytes, model.layer_t_b, self._fusion_threshold
            )
        run.plan = self._plan_cache[key]
        run.b_prog = [0] * run.n_world

    def _maybe_enqueue_bucket(self, run: JobRun) -> None:
        """Hand the next WFBP bucket to the gating queue once (a) all
        workers have finished its backward segment and (b) the job's comm
        stream is free (buckets serialize FIFO, the PyTorch-DDP model)."""
        jid = run.spec.job_id
        if run.comm_active or jid in self._waiting_set:
            return
        if run.next_bucket >= run.n_buckets:
            return
        if run.next_bucket < min(run.b_prog):
            self._waiter_add(jid, run)

    # -- the decision API (called by SchedPolicy hooks) ------------------------
    def refresh_workloads(self) -> None:
        """Alg. 3 line 3: recompute every GPU's remaining workload L_g as the
        sum of its resident jobs' remaining service (shared per GPU).

        Incremental mode delegates to the ClusterIndex, which re-folds only
        GPUs touched by a release or an iteration-boundary decay since the
        last refresh — same values in the same accumulation order as the
        full rescan below (see core/clusterindex.py)."""
        if self._windex is not None:
            self._windex.refresh(self._runs, self.params, self.bandwidth_aware_srsf)
            return
        for g in self.cluster.gpus.values():
            g.workload = 0.0
        for jid in self._live:
            run = self._runs[jid]
            share = run.remaining_service(self.params, self.bandwidth_aware_srsf)
            for gid in run.gpus:
                self.cluster.gpus[gid].workload += share

    _refresh_workloads = refresh_workloads  # pre-split internal name

    def place_job(self, job_id: int, gpu_ids: Sequence[GpuId], now: float) -> JobRun:
        """Commit a gang placement chosen by the scheduling policy.

        Rebuilds everything placement-derived — the member-server set (and
        hence topology domain sets), the WFBP fusion plan for the placed
        world size, and the SRSF workload share — and restores carried
        progress (plus the restore penalty) for requeued jobs."""
        spec = self.jobs[job_id]
        servers = self.cluster.servers_of(gpu_ids)
        run = JobRun(
            spec=spec,
            gpus=list(gpu_ids),
            servers=servers,
            placed_at=now,
            domains=self._domains_of(servers),
        )
        carry = self._carry.pop(job_id, None)
        if carry is not None:
            run.iter_done = carry.iter_done
            run.samples_done = carry.samples_done
            run.samples_total = carry.samples_total
            run.restore_cost = carry.restore_cost
            run.restore_need = set(range(run.n_world))
        rem_samples = run.samples_total - run.samples_done
        run.target_iters = run.iter_done + max(0, -(-rem_samples // run.n_world))
        self._assign_plan(run)
        workload = run.remaining_service(self.params, self.bandwidth_aware_srsf)
        self.cluster.place(spec, gpu_ids, workload)
        self._runs[job_id] = run
        self._live[job_id] = None
        if self._windex is not None:
            self._windex.on_place(job_id, run.gpus, workload)
        self._dirty_gpus.update(gpu_ids)
        self._first_placed.setdefault(job_id, now)
        if self._obs is not None:
            self._obs.placed(job_id, run, now)
        return run

    def _checkpoint_cost_of(self, run: JobRun) -> float:
        if self.checkpoint_cost is not None:
            return self.checkpoint_cost
        return netmodel.preemption_cost(run.spec.model.size_bytes)

    def preempt_job(self, job_id: int, now: float) -> None:
        """Atomically tear a running gang down and requeue the job.

        The whole gang stops together: every in-flight compute task is
        cancelled (pending ``gpu_done`` events are tombstoned by epoch),
        any in-flight or waiting all-reduce is aborted, memory is
        released.  Progress is carried at the last *completed* iteration —
        the in-progress iteration is lost, exactly a checkpoint-restart —
        and the next placement pays the checkpoint/restore penalty."""
        run = self._runs.pop(job_id)
        self._live.pop(job_id, None)
        if run.finished_at is not None:
            raise ValueError(f"cannot preempt finished job {job_id}")
        lost = self._lost_in_progress(run)
        self._work_lost_samples += lost
        self._epoch_of[job_id] = self._epoch_of.get(job_id, 0) + 1
        for gid in run.gpus:
            g = self.cluster.gpus[gid]
            if g.busy_job == job_id:
                if g.busy_until is not None and g.busy_until > now:
                    g.busy_accum -= g.busy_until - now  # un-accrue lost work
                g.busy_until = None
                g.busy_job = None
            self._dirty_gpus.add(gid)
        self.cluster.release(run.spec, run.gpus)
        if self._windex is not None:
            self._windex.on_release(job_id, run.gpus)
        if job_id in self._waiting_set:
            self._waiter_drop(job_id, run.domains)
        if job_id in self._active_comm:
            self._abort_comm(job_id)
        self._carry[job_id] = _Carry(
            iter_done=run.iter_done,
            samples_done=run.samples_done,
            samples_total=run.samples_total,
            restore_cost=self._checkpoint_cost_of(run),
        )
        # the queue is kept sorted by srsf_key_queued (the carry above is
        # what the key reads, so it must be set before this enqueue)
        self._enqueue(job_id)
        self._preemptions += 1
        if self._obs is not None:
            # after the waiter-drop/abort hooks above, so the aborted
            # transfer's reattribution already landed in the ledger
            self._obs.preempted(job_id, now, lost)
        if self.record_trace:
            # drop the aborted in-progress iteration's records (they will
            # be re-executed after resume) and mark the preemption point
            self._trace = [
                r
                for r in self._trace
                if r[2] in ("preempt", "resize")
                or not (r[0] == job_id and r[1] >= run.iter_done)
            ]
            self._trace.append((job_id, run.iter_done, "preempt", -1, now, now))

    def request_resize(self, job_id: int, n_new: int) -> None:
        """Ask for an elastic world-size change, applied at the job's next
        iteration boundary (clamped to the job's declared bounds)."""
        run = self._runs[job_id]
        lo, hi = run.spec.gpu_bounds
        n_new = max(lo, min(hi, int(n_new)))
        run.pending_resize = None if n_new == run.n_world else n_new

    def _apply_resize(self, run: JobRun, now: float) -> None:
        """Apply a pending resize at an iteration boundary: tear the gang
        down (nothing in-iteration exists to lose here), re-place at the
        new size through the normal placement path — rebuilding the WFBP
        fusion plan and topology domain sets — and charge the
        checkpoint/restore penalty for the state redistribution."""
        job_id = run.spec.job_id
        n_new = run.pending_resize
        run.pending_resize = None
        self._epoch_of[job_id] = self._epoch_of.get(job_id, 0) + 1
        self.cluster.release(run.spec, run.gpus)
        if self._windex is not None:
            self._windex.on_release(job_id, run.gpus)
        self._dirty_gpus.update(run.gpus)
        del self._runs[job_id]
        self._live.pop(job_id, None)
        # re-rank with this gang's workload gone (cluster.release keeps the
        # per-GPU L_g; the freed GPUs must look free to the placement)
        self.refresh_workloads()
        spec = run.spec
        trial = spec if n_new == spec.n_gpus else dataclasses.replace(spec, n_gpus=n_new)
        gpu_ids = self.placement(self.cluster, trial)
        applied = gpu_ids is not None
        if not applied:
            # a failed grow is a *cancelled* resize: keep EXACTLY the old
            # GPUs (just freed, so they fit) — no migration, no
            # checkpoint/restore penalty, no resize counted
            gpu_ids = list(run.gpus)
        self._carry[job_id] = _Carry(
            iter_done=run.iter_done,
            samples_done=run.samples_done,
            samples_total=run.samples_total,
            restore_cost=self._checkpoint_cost_of(run) if applied else 0.0,
        )
        self.place_job(job_id, gpu_ids, now)
        if applied:
            self._resizes += 1
            if self._obs is not None:
                self._obs.resized(job_id, now)
            if self.record_trace:
                self._trace.append((job_id, run.iter_done, "resize", -1, now, now))
        self.sched.on_resize(now, job_id)

    # -- fault injection (core/chaos.py) ------------------------------------------
    def _lost_in_progress(self, run: JobRun) -> int:
        """Samples of in-iteration work a teardown throws away: the whole
        gang's current iteration counts as lost if *any* worker made
        progress in it (the carry keeps only completed iterations).  Must
        be called before the per-GPU busy state is cleaned up."""
        in_prog = bool(
            run.f_done
            or run.b_done
            or run.comm_active
            or run.comm_ready_at is not None
            or (
                run.plan is not None
                and (run.next_bucket or run.buckets_done or any(run.b_prog))
            )
        )
        if not in_prog:
            # nothing recorded done yet, but a worker may be mid-task
            in_prog = any(
                self.cluster.gpus[gid].busy_job == run.spec.job_id
                for gid in run.gpus
            )
        return run.n_world if in_prog else 0

    def _seed_chaos_events(self) -> None:
        """Arm the fault processes at run start: one outstanding breakdown /
        NIC window per server (advanced lazily, so the infinite stochastic
        streams never flood the calendar) plus every job's cancellation
        instant."""
        spec = self._chaos
        self._base_server_bw = tuple(self.params.server_bandwidth)
        for s in range(self.cluster.n_servers):
            self._fail_streams[s] = server_failure_stream(spec, s)
            self._advance_failure(s)
            self._nic_streams[s] = nic_degradation_stream(spec, s)
            self._advance_nic(s)
        for job in self.jobs.values():
            t_c = cancel_time(spec, job.job_id, job.arrival)
            if t_c is not None:
                # the arrival event was pushed first, so a same-instant
                # cancellation still finds the job in the queue
                self._push(max(t_c, job.arrival), "cancel", (job.job_id,))

    def _advance_failure(self, server: int) -> None:
        win = next(self._fail_streams[server], None)
        if win is not None:
            self._push(win[0], "breakdown", (server, win[1]))

    def _advance_nic(self, server: int) -> None:
        win = next(self._nic_streams[server], None)
        if win is not None:
            self._push(win[0], "nic_down", (server, win[1]))

    def _on_breakdown(self, server: int, repair_t: float, now: float) -> None:
        """A server died: force-preempt every gang touching it (atomic
        teardown through the normal preempt machinery — epoch tombstones,
        carry at the last completed iteration, restore penalty on resume)
        and mark its GPUs unplaceable until repair."""
        self._faults += 1
        self._down_servers.add(server)
        for g in self.cluster.gpus_of_server(server):
            g.down = True
        if self._windex is not None:
            self._windex.on_server_capacity(server)
        victims = sorted(
            jid
            for jid, run in self._runs.items()
            if run.finished_at is None and server in run.servers
        )
        for jid in victims:
            self.preempt_job(jid, now)
        self._push(repair_t, "repair", (server,))
        if self._obs is not None:
            self._obs.fault("breakdown", server, now)
        self.sched.on_fault(now, server, victims)

    def _on_repair(self, server: int, now: float) -> None:
        self._down_servers.discard(server)
        for g in self.cluster.gpus_of_server(server):
            g.down = False
        if self._windex is not None:
            self._windex.on_server_capacity(server)
        self.cluster.capacity_epoch += 1  # placeable capacity grew
        self._advance_failure(server)
        if self._obs is not None:
            self._obs.fault("repair", server, now)
        self.sched.on_recovery(now, server)

    def _apply_nic_bandwidth(self) -> None:
        """Rebuild ``params.server_bandwidth`` from the base multipliers and
        the currently-degraded set.  The main loop integrated all in-flight
        transfers up to ``now`` *before* dispatching this event, so the
        piecewise-constant-rate integration stays exact across the change;
        ``_comm_dirty`` forces the finish-time re-prediction."""
        scale = self._chaos.nic_degraded_scale
        base = self._base_server_bw
        self.params = dataclasses.replace(
            self.params,
            server_bandwidth=tuple(
                (base[s] if s < len(base) else 1.0)
                * (scale if s in self._nic_degraded else 1.0)
                for s in range(self.cluster.n_servers)
            ),
        )
        self._comm_dirty = True
        # bandwidth_aware_srsf keys read the map just replaced: invalidate
        # every memoized SRSF key (no-op for the nominal estimate, which
        # never depends on server_bandwidth)
        self._svc_epoch += 1

    def _on_nic_down(self, server: int, end_t: float, now: float) -> None:
        self._faults += 1
        self._nic_degraded.add(server)
        self._apply_nic_bandwidth()
        self._push(end_t, "nic_up", (server,))
        if self._obs is not None:
            self._obs.fault("nic_down", server, now)

    def _on_nic_up(self, server: int, now: float) -> None:
        self._nic_degraded.discard(server)
        self._apply_nic_bandwidth()
        self._advance_nic(server)
        if self._obs is not None:
            self._obs.fault("nic_up", server, now)

    def _on_cancel(self, job_id: int, now: float) -> None:
        """Stochastic cancellation: the job leaves the system — running
        gangs are torn down atomically (same mechanics as a preemption,
        without the requeue), queued jobs just leave the queue.  Cancelled
        jobs are counted separately from ``censored`` (they are not silent
        truncation) and contribute nothing to JCT stats or goodput."""
        if job_id not in self._unfinished:
            return  # finished before the axe fell
        run = self._runs.get(job_id)
        lost = 0.0
        if run is not None:
            self._epoch_of[job_id] = self._epoch_of.get(job_id, 0) + 1
            lost = self._lost_in_progress(run)
            self._work_lost_samples += lost
            del self._runs[job_id]
            self._live.pop(job_id, None)
            for gid in run.gpus:
                g = self.cluster.gpus[gid]
                if g.busy_job == job_id:
                    if g.busy_until is not None and g.busy_until > now:
                        g.busy_accum -= g.busy_until - now
                    g.busy_until = None
                    g.busy_job = None
                self._dirty_gpus.add(gid)
            self.cluster.release(run.spec, run.gpus)
            if self._windex is not None:
                self._windex.on_release(job_id, run.gpus)
            if job_id in self._waiting_set:
                self._waiter_drop(job_id, run.domains)
            if job_id in self._active_comm:
                self._abort_comm(job_id)
            if self.record_trace:
                self._trace.append((job_id, run.iter_done, "cancel", -1, now, now))
        elif job_id in self._queue:
            self.remove_queued((job_id,))
            self._carry.pop(job_id, None)
        self._cancelled += 1
        self._unfinished.discard(job_id)
        if self._obs is not None:
            self._obs.cancelled(job_id, now, lost)
        # freed memory/GPUs (or a shorter queue) may admit other jobs
        self.sched.on_job_finish(now, job_id)

    # -- communication gating -----------------------------------------------------
    def _gate_try_one(
        self, jid: int, run: JobRun, now: float, qpos: int = -1
    ) -> bool:
        """Evaluate the gating policy for one waiter and commit the start
        when it accepts.  Returns True iff a transfer started.  This body
        is shared verbatim by the rescan and incremental paths, so the two
        modes can only differ in *which* waiters they evaluate.  ``qpos``
        is the waiter's rank in the pass's SRSF evaluation order — audit
        metadata only, never a decision input."""
        servers = run.servers
        domains = run.domains
        # WFBP: the gating decision and the transfer carry the
        # current *bucket's* bytes, not the whole message.
        if run.plan is not None:
            bucket = run.next_bucket
            new_bytes = run.plan[0][bucket]
        else:
            bucket = -1
            new_bytes = run.spec.model.size_bytes
        dload = self._domain_load
        max_conc = 0
        for d in domains:
            v = dload.get(d, 0)
            if v > max_conc:
                max_conc = v
        policy = self.comm_policy
        obs = self._obs
        lg = self._obs_audit
        dtasks = self._domain_tasks
        if lg is None and policy.scalar_gate:
            # Fast path: AdaDUAL/SRSF(n) depend on the overlapping set only
            # through min(remaining_bytes) (and the count, already in
            # max_conc), so skip materializing the olds list.  min over the
            # per-domain task lists visits each overlapping transfer at
            # least once — same minimum as the legacy left-to-right
            # min(old_rem), hence the same decision.  Only taken with the
            # audit log off (the audit record needs the full list).
            min_old = math.inf
            for d in domains:
                lst = dtasks.get(d)
                if lst:
                    for t in lst:
                        if t.remaining_bytes < min_old:
                            min_old = t.remaining_bytes
            ok = policy.should_start_min(new_bytes, min_old, max_conc, self.params)
            old_rem: List[float] = []  # audit-only below; lg is None here
        else:
            # Full olds list, rebuilt from the per-domain task lists in
            # ``_active_comm`` insertion (== start ``seq``) order: a single
            # loaded domain's list is already seq-ordered; overlaps across
            # several domains dedupe through the seq-keyed dict.  Same
            # floats in the same order as the legacy full scan — k-way
            # lookahead sums over this list, so order is load-bearing.
            lists = [dtasks[d] for d in domains if d in dtasks]
            if not lists:
                old_rem = []
            elif len(lists) == 1:
                old_rem = [t.remaining_bytes for t in lists[0]]
            else:
                seen: Dict[int, float] = {}
                for lst in lists:
                    for t in lst:
                        seen[t.seq] = t.remaining_bytes
                old_rem = [seen[s] for s in sorted(seen)]
            ok = policy.should_start(
                new_bytes,
                old_rem,
                max_conc,
                self.params,
            )
        if lg is not None:
            # audit record, inlined — the densest hook on contended cells
            # (one per gate evaluation); dedicated flat stream, engine-
            # side budget countdown
            n = self._obs_audit_left
            if n > 0:
                self._obs_audit_left = n - 1
                lg.extend(
                    (
                        now,
                        jid,
                        bucket,
                        new_bytes,
                        max_conc,
                        ok,
                        qpos,
                        len(self._waiting_comm),
                        len(old_rem),
                    )
                )
                lg.extend(old_rem)
            else:
                obs.audit_dropped += 1
        if not ok:
            return False
        self._waiter_drop(jid, domains)
        task = CommTask(
            job_id=jid,
            servers=set(servers),
            remaining_bytes=(
                new_bytes
                if run.plan is not None
                else run.spec.model.size_bytes / self.comm_chunks
            ),
            latency_left=self.params.a,
            domains=domains,
            bucket=bucket,
            seq=self._comm_seq,
        )
        self._comm_seq += 1
        self._active_comm[jid] = task
        self._comm_started(task)
        if run.plan is not None:
            run.next_bucket += 1
        else:
            run.comm_chunks_left -= 1
        run.comm_active = True
        if max_conc > 0:
            self._comm_contended += 1
        else:
            self._comm_clean += 1
        if obs is not None:
            obs.comm_start(jid, bucket, now, task)
        if self.record_trace:
            kind = "c" if bucket < 0 else f"c{bucket}"
            self._trace.append((jid, run.iter_done, kind, -1, now, None))
        return True

    def _try_start_comms(self, now: float) -> bool:
        if not self._waiting_comm:
            return False
        if self.gating == "rescan":
            return self._try_start_comms_rescan(now)
        return self._try_start_comms_incremental(now)

    def _try_start_comms_rescan(self, now: float) -> bool:
        """Legacy reference gating: evaluate EVERY waiter in SRSF order on
        every call, restarting from the top after each start.  O(waiters x
        evaluations) per event — kept as the differential-test oracle for
        the incremental path (REPRO_GATING=rescan)."""
        any_started = False
        # Alg. 3 line 16: consider ready communication tasks in SRSF order.
        self._waiting_comm.sort(key=self.srsf_key_running)
        started_any = True
        while started_any:
            started_any = False
            for qpos, jid in enumerate(list(self._waiting_comm)):
                run = self._runs[jid]
                if run.comm_active or jid in self._active_comm:
                    self._waiter_drop(jid, run.domains)
                    continue
                if self._gate_try_one(jid, run, now, qpos):
                    started_any = True
                    any_started = True
                    break  # re-evaluate contention state after each start
        return any_started

    def _try_start_comms_incremental(self, now: float) -> bool:
        """Dirty-domain gating: evaluate only waiters whose decision inputs
        may have changed — fresh waiters, plus waiters sharing a contention
        domain with any comm start/end/abort since their last evaluation
        (``_gate_candidates``, maintained by ``_comm_started`` /
        ``_comm_ended`` / ``_waiter_add``).

        Bit-exactness with the rescan rests on three facts:

        1. Within one pass, candidates are evaluated in the same SRSF order
           the rescan sorts the full waiter list into (identical keys), and
           a start restarts evaluation with the fresh contention state —
           waiters woken by the start (its domains just got dirtied) merge
           into the candidate set, exactly the waiters whose inputs the
           start changed.  A waiter NOT sharing a domain with the start has
           an unchanged ``olds`` list (``_active_comm`` is insertion-
           ordered and only appended to here) and unchanged ``max_conc``,
           so re-evaluating it (as the rescan does) provably returns the
           same False as its last evaluation this pass.
        2. Between events under a *fixed* active set, in-flight transfers
           only drain.  For the drain-monotone policies (AdaDUAL: start iff
           ``new < min(olds) * threshold`` with a ``max_conc`` cap — drain
           shrinks ``min(olds)``; SRSF(n): depends on ``max_conc`` only) a
           False decision stays False until a start/end/abort touches the
           waiter's domains, which is precisely when it re-enters the
           candidate set.  Skipping the re-evaluation is unobservable.
        3. Policies that are NOT drain-monotone (the k-way exact lookahead
           integrates the actual remaining bytes, so mere drain can flip
           its decision) declare ``drain_monotone = False`` and are
           re-evaluated in full every event — the rescan itself, through
           the shared ``_gate_try_one`` body.

        Chaos paths that mutate comm state outside this function
        (``_abort_comm``, NIC bandwidth changes replacing ``params``) set
        ``_comm_dirty``, which forces a full-waiter pass for that event.

        Locked by tests/test_gating_incremental.py across the fusion x
        policy x chaos x sched grid."""
        if self._comm_dirty or not self.comm_policy.drain_monotone:
            cand = set(self._waiting_comm)
            self._gate_candidates.clear()
        else:
            if not self._gate_candidates:
                return False
            cand = self._gate_candidates
            self._gate_candidates = set()
        any_started = False
        if self._obs_audit is None:
            # Heap variant of the loop below: SRSF keys are stable within
            # a pass (no iteration completes during gating), so popping a
            # lazily-deduped heap yields exactly the sorted(cand) order
            # the restart loop re-derives after every start — without the
            # O(cand log cand) re-sort.  Only taken with the audit log
            # off: the audit record carries ``qpos``, the waiter's rank in
            # the restart-local sorted order, which the heap does not
            # reproduce.
            key = self.srsf_key_running
            heap = [(key(jid), jid) for jid in cand]
            heapq.heapify(heap)
            while heap:
                _, jid = heapq.heappop(heap)
                if jid not in cand:
                    continue  # re-pushed duplicate already handled
                cand.discard(jid)
                run = self._runs[jid]
                if run.comm_active or jid in self._active_comm:
                    # defensive mirror of the rescan's cleanup path
                    self._waiter_drop(jid, run.domains)
                    continue
                if self._gate_try_one(jid, run, now, -1):
                    any_started = True
                    # the start dirtied its domains: merge the woken
                    # waiters; contention state is already fresh for the
                    # next pop (the restart below re-sorts for the same
                    # effect)
                    woken = self._gate_candidates
                    if woken:
                        for w in woken:
                            if w not in cand:
                                cand.add(w)
                                heapq.heappush(heap, (key(w), w))
                        woken.clear()
            return any_started
        while cand:
            restart = False
            for qpos, jid in enumerate(sorted(cand, key=self.srsf_key_running)):
                run = self._runs[jid]
                if run.comm_active or jid in self._active_comm:
                    # defensive mirror of the rescan's cleanup path
                    self._waiter_drop(jid, run.domains)
                    cand.discard(jid)
                    restart = True
                    break
                if self._gate_try_one(jid, run, now, qpos):
                    any_started = True
                    cand.discard(jid)
                    # the start dirtied its domains: merge the woken
                    # waiters and restart with fresh contention state
                    cand |= self._gate_candidates
                    self._gate_candidates.clear()
                    restart = True
                    break
                cand.discard(jid)
            if not restart:
                break  # every candidate evaluated False — pass complete
        return any_started

    # -- iteration/worker state machine ---------------------------------------------
    def _begin_iteration(self, run: JobRun, now: float) -> None:
        run.f_done.clear()
        run.b_done.clear()
        run.comm_ready_at = None
        run.comm_active = False
        if run.plan is not None:
            run.b_prog = [0] * run.n_world
            run.next_bucket = 0
            run.buckets_done = 0
        self._dirty_gpus.update(run.gpus)

    def _complete_iteration(self, run: JobRun, now: float) -> None:
        run.iter_done += 1
        run.samples_done += run.n_world
        if self._windex is not None:
            # remaining-service decay: the share is stale until the next
            # refresh (harmlessly redundant on the finish/resize branches
            # below — their releases drop the job from the index)
            self._windex.on_decay(run.spec.job_id)
        if run.samples_done >= run.samples_total:
            self._finish_job(run, now)
        elif run.pending_resize is not None:
            self._apply_resize(run, now)
        else:
            self._begin_iteration(run, now)

    def _finish_job(self, run: JobRun, now: float) -> None:
        run.finished_at = now
        jid = run.spec.job_id
        self.cluster.release(run.spec, run.gpus)
        if self._windex is not None:
            self._windex.on_release(jid, run.gpus)
        self._dirty_gpus.update(run.gpus)
        self._unfinished.discard(jid)
        self._live.pop(jid, None)
        # Results are recorded at finish time (list mode re-derives them
        # from _runs at collection for the legacy float-order guarantees;
        # streaming mode retires the run below, so this is the only copy).
        self._finish_at[jid] = now
        self._jct_at_finish[jid] = now - run.spec.arrival
        self._qdelay_at_finish[jid] = (
            self._first_placed.get(jid, run.placed_at) - run.spec.arrival
        )
        self._job_samples[jid] = run.samples_done
        if self._obs is not None:
            self._obs.finished(jid, run, now)
        if self._source is not None:
            # streaming feed: drop the finished run's state at the end of
            # this event so memory stays O(live jobs) over a 100k+ replay
            # (not immediately — the current event's handlers may still
            # hold references, e.g. the finished-comms loop)
            self._retire_buf.append(jid)

    def _on_backward_done(self, run: JobRun, now: float) -> None:
        if len(run.b_done) < run.n_world:
            return
        # Barrier reached (Fig. 3: all-reduce waits for all backprops).
        if run.has_comm:
            jid = run.spec.job_id
            assert jid not in self._waiting_set and not run.comm_active, (
                f"duplicate barrier for job {jid}"
            )
            run.comm_ready_at = now
            run.comm_chunks_left = self.comm_chunks
            self._waiter_add(jid, run)
        else:
            self._complete_iteration(run, now)

    # -- GPU scheduling (Alg. 3 lines 22-30) -------------------------------------
    def _restore_extra(self, run: JobRun, w: int) -> float:
        """Checkpoint-restore penalty owed by worker ``w``: charged on its
        first compute task after a preemption/resize (state reload delays
        the forward pass)."""
        return run.restore_cost if w in run.restore_need else 0.0

    def _ready_compute_tasks(self, gid: GpuId):
        """Yield (job_id, worker, kind, duration, segment) ready on this
        GPU; segment is the WFBP backward-segment index (-1 = monolithic)."""
        g = self.cluster.gpus[gid]
        for jid in g.resident_jobs:
            run = self._runs.get(jid)
            if run is None or run.finished_at is not None:
                continue
            wmap = run._worker_of
            if wmap is None:
                wmap = {g2: i for i, g2 in enumerate(run.gpus)}
                run._worker_of = wmap
            w = wmap.get(gid)
            if w is None:
                continue
            # Straggler jitter (core/chaos.py): per-(job, iteration) compute
            # stretch, identical for every worker and segment of the
            # iteration.  The restore penalty is a state reload, not
            # compute — never jittered.
            jit = (
                jitter_factor(self._chaos, jid, run.iter_done)
                if self._chaos is not None
                else 1.0
            )
            if run.plan is not None:
                # WFBP: backward runs in per-bucket segments that overlap
                # in-flight transfers — comm never blocks compute within
                # the iteration (only the iteration boundary barriers).
                if w not in run.f_done:
                    yield (jid, w, "f", run.spec.model.t_f * jit + self._restore_extra(run, w), -1)
                elif run.b_prog[w] < run.n_buckets:
                    s = run.b_prog[w]
                    yield (jid, w, "b", run.plan[1][s] * jit, s)
                continue
            if run.comm_ready_at is not None or run.comm_active:
                continue  # between barrier and next iteration
            if w not in run.f_done:
                if self.fuse_fb:
                    yield (jid, w, "fb", run.spec.model.t_iter_compute * jit + self._restore_extra(run, w), -1)
                else:
                    yield (jid, w, "f", run.spec.model.t_f * jit + self._restore_extra(run, w), -1)
            elif w not in run.b_done:
                yield (jid, w, "b", run.spec.model.t_b * jit, -1)

    def _schedule_gpus(self, now: float) -> None:
        for gid in list(self._dirty_gpus):
            self._dirty_gpus.discard(gid)
            g = self.cluster.gpus[gid]
            if g.down:
                continue  # broken server: nothing runs until repair
            # busy_job is cleared only by this GPU's own gpu_done event, so a
            # task ending exactly at `now` (event still in the heap) cannot be
            # double-scheduled by another same-timestamp event.
            if g.busy_job is not None:
                continue
            candidates = list(self._ready_compute_tasks(gid))
            if not candidates:
                g.busy_until = None
                g.busy_job = None
                continue
            # SRSF among resident jobs' ready tasks (a single candidate
            # needs no key evaluation at all — the common case on
            # lightly-shared GPUs).
            if len(candidates) > 1:
                candidates.sort(key=lambda c: self.srsf_key_running(c[0]))
            jid, w, kind, dur, seg = candidates[0]
            run = self._runs[jid]
            if kind in ("f", "fb") and w in run.restore_need:
                run.restore_need.discard(w)  # penalty committed with this task
            g.busy_until = now + dur
            g.busy_job = jid
            g.busy_accum += dur
            self._push(
                now + dur,
                "gpu_done",
                (gid, jid, w, kind, seg, self._epoch_of.get(jid, 0)),
            )
            rc = self._obs_rc
            if rc is not None:
                if len(rc) < self._obs_rc_cap:
                    rc.extend((jid, w, kind, seg, now, now + dur))
                else:
                    self._obs.span_dropped += 1
            if self.record_trace:
                if kind == "fb":
                    self._trace.append((jid, run.iter_done, "f", w, now, now + run.spec.model.t_f))
                    self._trace.append((jid, run.iter_done, "b", w, now + run.spec.model.t_f, now + dur))
                else:
                    tkind = kind if seg < 0 else f"{kind}{seg}"
                    self._trace.append((jid, run.iter_done, tkind, w, now, now + dur))

    # -- streaming arrival feed (TraceSource) -------------------------------------
    def _push_next_arrival(self) -> None:
        """Pull ONE arrival ahead from the streaming source into the
        calendar.  Exactly one future arrival is outstanding at a time, so
        the calendar stays O(cluster) regardless of trace length."""
        spec = next(self._stream, None)
        if spec is None:
            self._stream = None
            return
        if spec.arrival < self._last_arrival:
            raise ValueError(
                f"TraceSource must yield arrivals in nondecreasing order: "
                f"job {spec.job_id} arrives at {spec.arrival} after "
                f"{self._last_arrival}"
            )
        if spec.job_id in self.jobs:
            raise ValueError(f"TraceSource repeated job_id {spec.job_id}")
        self._last_arrival = spec.arrival
        self._push(spec.arrival, "arrival", (spec,))
        self._arrivals_pending += 1

    def _register_arrival(self, spec: JobSpec, now: float) -> None:
        """A streamed arrival event fired: the job enters the system now
        (list mode registers everything in __init__ instead)."""
        jid = spec.job_id
        self.jobs[jid] = spec
        self._unfinished.add(jid)
        self._n_seen += 1
        self._arrivals_pending -= 1
        if self._chaos is not None:
            # per-arrival twin of _seed_chaos_events' cancellation seeding
            t_c = cancel_time(self._chaos, jid, spec.arrival)
            if t_c is not None:
                self._push(max(t_c, spec.arrival), "cancel", (jid,))
        self._push_next_arrival()

    def _retire_finished(self) -> None:
        """Streaming-only end-of-event cleanup: drop finished runs' state so
        a 100k-job replay holds O(live jobs) memory.  Results were already
        recorded at finish time; gpu_done tombstones survive via the
        ``_runs.get`` guard in the main loop (a stale event of a retired
        job simply finds no run)."""
        for jid in self._retire_buf:
            self._runs.pop(jid, None)
            self.jobs.pop(jid, None)
            self._first_placed.pop(jid, None)
            self._epoch_of.pop(jid, None)
        self._retire_buf.clear()

    # -- main loop ----------------------------------------------------------------
    def run(self, max_time: float = math.inf) -> SimResult:
        if self._source is not None:
            self._stream = iter(self._source.arrivals())
            self._push_next_arrival()
        else:
            for spec in self.jobs.values():
                self._push(spec.arrival, "arrival", (spec.job_id,))
        if self.sched.quantum is not None:
            if self.jobs:
                first = min(s.arrival for s in self.jobs.values())
            elif self._heap:
                first = self._heap[0][0]  # streaming: the one-ahead arrival
            else:
                first = None
            if first is not None:
                self._push(first + self.sched.quantum, "quantum", ())
        if self._chaos is not None:
            self._seed_chaos_events()
        prof = self._phase_seconds
        perf = time.perf_counter
        streaming = self._source is not None
        now = 0.0
        while self._heap and (self._unfinished or self._arrivals_pending):
            t, _, kind, data = heapq.heappop(self._heap)
            if kind == "comm_check" and data[0] != self._comm_epoch:
                continue
            if t > max_time:
                break
            now = t
            self._events += 1
            self._comm_dirty = False
            if prof is not None:
                t0 = perf()

            finished_comms = self._advance_comm(now)
            for jid in finished_comms:
                run = self._runs[jid]
                run.comm_active = False
                if self.record_trace:
                    # patch the open comm record ("c" or a WFBP "c<bucket>")
                    for i in range(len(self._trace) - 1, -1, -1):
                        r = self._trace[i]
                        if r[0] == jid and r[2].startswith("c") and r[5] is None:
                            self._trace[i] = (r[0], r[1], r[2], r[3], r[4], now)
                            break
                if run.plan is not None:
                    # WFBP: bucket done; the iteration completes with the
                    # LAST bucket's transfer (earlier ones only overlapped
                    # the remaining backward), else hand the next ready
                    # bucket to the FIFO comm stream.
                    run.buckets_done += 1
                    if run.buckets_done >= run.n_buckets:
                        self._complete_iteration(run, now)
                    else:
                        self._maybe_enqueue_bucket(run)
                elif run.comm_chunks_left > 0:
                    # chunked comm: re-queue the next chunk (it competes for
                    # the link like a fresh task — preemption point)
                    self._waiter_add(jid, run)
                else:
                    self._complete_iteration(run, now)
            if prof is not None:
                t1 = perf()
                prof["comm_advance"] += t1 - t0

            if kind == "arrival":
                if streaming:
                    spec = data[0]
                    jid = spec.job_id
                    self._register_arrival(spec, now)
                else:
                    jid = data[0]
                # the queue is kept in srsf_key_queued order (the key is
                # static while a job waits, so one insort here replaces the
                # pre-split full sort on every placement scan)
                self._enqueue(jid)
                self.sched.on_arrival(now, jid)
            elif kind == "gpu_done":
                gid, jid, w, tkind, seg, epoch = data
                run = self._runs.get(jid)
                if run is not None and epoch == self._epoch_of.get(jid, 0):
                    g = self.cluster.gpus[gid]
                    g.busy_until = None
                    g.busy_job = None
                    self._dirty_gpus.add(gid)
                    if run.plan is not None:
                        if tkind == "f":
                            run.f_done.add(w)
                        else:  # backward segment `seg` of worker w
                            run.b_prog[w] += 1
                            self._maybe_enqueue_bucket(run)
                    elif tkind == "fb":
                        run.f_done.add(w)
                        run.b_done.add(w)
                        self._on_backward_done(run, now)
                    elif tkind == "f":
                        run.f_done.add(w)
                    elif tkind == "b":
                        run.b_done.add(w)
                        self._on_backward_done(run, now)
                    if run.finished_at is not None:
                        # memory freed -> queued jobs may fit now
                        self.sched.on_job_finish(now, jid)
                # else: stale event of a preempted/resized incarnation — the
                # GPU was already freed (and possibly rebooked) at teardown
            elif kind == "quantum":
                self.sched.on_quantum(now)
                # keep ticking only while progress is possible — a live run
                # or a pending event; otherwise the tick would spin forever
                # on a stuck (never-placeable) queue the way the pre-split
                # simulator's drained heap never could
                if (self._unfinished or self._arrivals_pending) and (
                    self._heap
                    or any(r.finished_at is None for r in self._runs.values())
                ):
                    self._push(now + self.sched.quantum, "quantum", ())
            elif kind == "comm_check":
                pass  # generic comm processing above already handled it
            elif kind == "breakdown":
                self._on_breakdown(data[0], data[1], now)
            elif kind == "repair":
                self._on_repair(data[0], now)
            elif kind == "nic_down":
                self._on_nic_down(data[0], data[1], now)
            elif kind == "nic_up":
                self._on_nic_up(data[0], now)
            elif kind == "cancel":
                self._on_cancel(data[0], now)

            if finished_comms:
                # job finishing via comm also frees memory
                for j in finished_comms:
                    run = self._runs.get(j)
                    if run is not None and run.finished_at is not None:
                        self.sched.on_job_finish(now, j)
                        break  # one re-evaluation per event (pre-split shape)
            if prof is not None:
                t2 = perf()
                prof["dispatch"] += t2 - t1

            # Gating re-evaluated whenever comm state may have changed or new
            # barriers were reached this event.
            started = self._try_start_comms(now)
            if prof is not None:
                t3 = perf()
                prof["gating"] += t3 - t2
            self._schedule_gpus(now)
            if prof is not None:
                prof["gpu_schedule"] += perf() - t3
            # Rates only change when the active comm set changes, so the
            # pending finish prediction stays valid otherwise.  A comm_check
            # that finished nothing (float drift) must still reschedule, or
            # the in-flight task would stall forever.  Policy actions that
            # abort an active transfer (preemption) also change the rates.
            if started or finished_comms or kind == "comm_check" or self._comm_dirty:
                if prof is not None:
                    # The finish-time re-prediction belongs to gating when it
                    # was forced by a gating/abort action this event (a new
                    # transfer started or the rate set was invalidated), and
                    # to comm integration when it merely tracks transfers
                    # draining on a stable rate set.
                    t4 = perf()
                    self._reschedule_comm_check()
                    phase = (
                        "gating" if (self._comm_dirty or started) else "comm_advance"
                    )
                    prof[phase] += perf() - t4
                else:
                    self._reschedule_comm_check()
            if self._retire_buf:
                self._retire_finished()

        return self._collect(now)

    # -- results ------------------------------------------------------------------
    def _collect(self, now: float) -> SimResult:
        if self._source is None:
            # List mode: re-derive results from the (never-retired) runs in
            # their _runs insertion order — the pre-split float accumulation
            # order, kept bit-exact for the captured-baseline locks.
            jct, finish, qdelay = {}, {}, {}
            for jid, run in self._runs.items():
                if run.finished_at is not None:
                    finish[jid] = run.finished_at
                    jct[jid] = run.finished_at - run.spec.arrival
                    qdelay[jid] = (
                        self._first_placed.get(jid, run.placed_at)
                        - run.spec.arrival
                    )
            # Delivered throughput: samples completed by finished or still-
            # live jobs (runs + requeued carries).  Cancelled jobs left the
            # system with their partial progress — not delivered, not
            # counted.
            delivered = left_sum(
                r.samples_done for r in self._runs.values()
            ) + left_sum(c.samples_done for c in self._carry.values())
        else:
            # Streaming mode: finished runs were retired as the replay went,
            # so the finish-time records are the only copy (finish order).
            jct = self._jct_at_finish
            finish = self._finish_at
            qdelay = self._qdelay_at_finish
            delivered = (
                left_sum(self._job_samples.values())
                + left_sum(r.samples_done for r in self._runs.values())
                + left_sum(c.samples_done for c in self._carry.values())
            )
        makespan = max(finish.values()) if finish else now
        busy = {gid: g.busy_accum for gid, g in self.cluster.gpus.items()}
        util = (
            left_sum(busy.values()) / (len(busy) * makespan) if makespan > 0 else 0.0
        )
        obs_report = None
        if self._obs is not None:
            obs_report = self._obs.build_report(
                topology=self.topology,
                params=self.params,
                makespan=makespan,
                horizon=now,
            )
        return SimResult(
            policy_name=self.comm_policy.name,
            placement_name=repr(self.placement),
            jct=jct,
            finish=finish,
            makespan=makespan,
            gpu_busy=busy,
            gpu_util=util,
            queueing_delay=qdelay,
            events_processed=self._events,
            comm_started_contended=self._comm_contended,
            comm_started_clean=self._comm_clean,
            peak_calendar=self._peak_heap,
            sched_name=self.sched.name,
            # cancelled jobs are an explicit outcome, not silent truncation:
            # censored counts only jobs cut off by the horizon or stranded
            # unplaced (a breakdown-preempted job still queued at max_time
            # lands here — it must not vanish from the aggregates).
            # _n_seen is the number of jobs that ENTERED the system: all of
            # them in list mode, only processed arrivals in streaming mode
            # (an un-yielded arrival past the horizon was never censored —
            # it never existed).
            censored=self._n_seen - len(finish) - self._cancelled,
            preemptions=self._preemptions,
            resizes=self._resizes,
            faults=self._faults,
            cancelled=self._cancelled,
            work_lost_samples=self._work_lost_samples,
            goodput=(delivered / makespan) if makespan > 0 else 0.0,
            task_trace=self._trace if self.record_trace else None,
            job_samples=dict(self._job_samples),
            phase_seconds=(
                dict(self._phase_seconds) if self._phase_seconds else None
            ),
            obs=obs_report,
        )
