"""Vectorized JAX cluster simulator: Monte-Carlo over traces in one jit.

A fixed-timestep, batched ("fluid") approximation of the Ada-SRSF
dynamics in ``jax.lax`` control flow, ``vmap``-ed over seeded traces, so
JCT confidence intervals over many sampled workloads cost one compiled
program per trace shape.

The policy/network math (Eq. 5 rate model, per-server bandwidth, gating
predicates, placement ranking) lives in ``core/netmodel.py`` and is shared
with the exact event simulator; this module supplies the fluid state
machine around it:

* every gating policy: AdaDUAL, SRSF(n), and k-way AdaDUAL (``kway2``/
  ``kway3``/...), which runs the exact per-bucket lookahead
  (``netmodel.kway_exact_start``);
* per-server heterogeneous NIC bandwidth (slowest-member drain rate);
* fabric contention domains (``core/topology.py``) via a static
  ``[domains, servers]`` incidence matrix;
* gang placement: ``consolidate`` / ``first_fit`` / ``least_loaded`` /
  ``random`` / ``rack_pack``.

One step, one driver
--------------------

* **The step** (:func:`_make_lane_step`) executes one tick: admission and
  placement, one evaluation of the contention/rate core
  (:func:`fluid_step_core`), the compute drain, gating, the comm drain and
  the iteration bookkeeping.  Monolithic traces start at most one gated
  all-reduce per tick (smallest remaining service first); bucketed WFBP
  traces start the greedy gating closure of
  :func:`netmodel.gating_fixed_point` in one masked pass.

* **Next-event skip** (``cfg.skip``): after the executed tick the step
  computes, per lane, how many following ticks are eventless (pure linear
  drains: no admission, no phase transition, no gating decision that could
  flip) and advances the drains in bulk.  Skipping gating re-evaluations is
  safe because the threshold predicate is antitone in the active set and
  non-increasing in time while the set is fixed (see
  :func:`netmodel.gating_fixed_point`); exact k-way is a cost comparison,
  not a monotone threshold, so the skip is off while any transfer waits
  under it.  Bulk advancement computes ``rem - n*dt`` instead of n
  subtractions, so a skip run may drift from a tick-by-tick run by ulps
  (at most one tick per phase segment); runs with the *same* config are
  bit-exact across batching, padding and compaction.

* **The driver** (:func:`_drive_batched`, entered through
  :func:`simulate_traces_batched`) runs lanes through
  ``cfg.chunk_steps``-tick ``lax.scan`` segments, one jitted launch each;
  finished lanes freeze via a per-lane ``live`` guard.  Between segments
  the host exits once every lane is done and (``cfg.compact``) retires
  finished lanes, shrinks the lane axis to the next power of two, and
  trims trailing all-invalid job columns (multiples of 8) and dead bucket
  columns.  Compaction is bit-exact: lanes are independent, and padded
  jobs are inert in every reduction.

``skip=False`` and ``compact=False`` are the tick-by-tick and
uncompacted references the tests hold the fast settings to.

Remaining approximations against the event simulator, each documented and
tested for qualitative agreement:

* gang placement: a job occupies whole GPUs exclusively;
* time advances in fixed dt steps; compute/comm remainders drain linearly
  (the Eq. 5 rate is exact within a step while the active comm set is
  unchanged, so dt only quantizes transition times);
* at most one queued job is admitted per step;
* WFBP tensor-fusion buckets drain as a FIFO stream over a static
  ``[jobs, buckets]`` size matrix; overlap of transfers with remaining
  backward compute is not modeled (documented pessimism);
* the fixed all-reduce latency ``a`` is folded into the bandwidth term
  (under WFBP it is charged once per bucket).

State is a struct-of-arrays over jobs plus per-server occupancy; policies
are branchless masks parameterized by the shared layer.  Traces carry a
boolean ``valid`` mask so ragged per-seed traces pad to one rectangular
batch (:func:`stack_traces`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import netmodel
from repro.core.contention import ContentionParams
from repro.core.topology import Topology, nic_topology

# job phases
QUEUED, COMPUTE, COMM, DONE = 0, 1, 2, 3

#: Safety margin (in ticks) for float tick-count conversions:
#: ``floor(x/dt - margin) + 1`` never *overestimates* ``ceil(x/dt)``
#: (proof: ``floor(y - m) + 1 <= ceil(y)`` for all ``y > 0, 0 < m < 1``),
#: and the margin absorbs f32 division error for counts up to ~1e5 ticks.
#: Underestimating only delays an event detection by <= 1 executed tick
#: (the safe direction — the event fires on the ``rem <= 0`` test).
_TICK_MARGIN = 1e-2

#: "No event" sentinel for per-job tick caps (far above any max_steps).
_BIG_TICKS = 1 << 30

#: per-lane counters of a fabric with uplink domains
#: (:meth:`Topology.uplink_mask`), kept in the lane state and returned by
#: :func:`simulate_traces_batched`: admissions whose ring crosses a rack
#: boundary (``uplink_jobs``), and transfers in flight at the end of each
#: tick, summed over ticks: those loading an uplink
#: (``uplink_transfer_ticks``) and all of them (``transfer_ticks``).  A
#: NIC-only fabric has no uplink and its lanes carry none of them.
UPLINK_COUNTERS = ("uplink_jobs", "uplink_transfer_ticks", "transfer_ticks")


@dataclasses.dataclass(frozen=True)
class JaxSimConfig:
    n_servers: int = 16
    gpus_per_server: int = 4
    dt: float = 0.05          # [s]
    #: dt * max_steps = simulated horizon cap (100,000 s at the default
    #: dt, several times the paper trace's longest makespan).  A lane
    #: still running at the cap returns unfinished jobs; the scenario
    #: Monte-Carlo entry point raises on it.
    max_steps: int = 2_000_000
    policy: str = "ada"       # ada | srsfN | kwayK (netmodel.parse_policy)
    #: consolidate | first_fit | least_loaded | random | rack_pack
    placement: str = "consolidate"
    a: float = ContentionParams().a
    b: float = ContentionParams().b
    eta: float = ContentionParams().eta
    dual_threshold: float = ContentionParams().dual_threshold
    #: per-server relative NIC bandwidth multipliers (1.0 = nominal);
    #: servers beyond the tuple are nominal, () = homogeneous network.
    server_bandwidth: Tuple[float, ...] = ()
    #: fabric contention domains (core/topology.py); None = the paper's
    #: NIC-only model (one NIC domain per server).  Topology
    #: is frozen/hashable, so it rides along as part of this jit-static
    #: config and lowers to *static* incidence/oversub matrices.
    topology: Optional[Topology] = None
    #: PRNG seed for the ``random`` gang placement mode (fold_in per step).
    placement_seed: int = 0
    # ---- fast-path knobs (all jit-static; see module docstring) --------
    #: ticks per jitted scan segment between host early-exit/compaction
    #: checks.
    chunk_steps: int = 256
    #: bulk-advance eventless ticks (next-event skip).
    skip: bool = True
    #: retire finished lanes / trim padding between chunks.
    compact: bool = True

    def __post_init__(self) -> None:
        if self.chunk_steps < 1:
            raise ValueError(f"chunk_steps must be >= 1, got {self.chunk_steps}")


def _place(free: jnp.ndarray, n_gpus: jnp.ndarray,
           rank_key: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Gang placement: fill servers in ascending ``rank_key`` order (the
    shared :func:`netmodel.placement_rank` key; stable order, server-index
    ties).  Returns (per-server takes, feasible flag).

    Sort-free formulation: ``cum[s]`` (GPUs available on servers at or
    before s in rank order) is a masked sum over the lexicographic
    comparison matrix instead of a cumsum over ``argsort`` output — pure
    elementwise + one (S,S) reduction, so XLA fuses it into the
    surrounding step instead of emitting sort/scatter thunks (the hot-loop
    profile was dominated by exactly those).  Bit-identical to the sorted
    version: free counts are small integers, exact in f32 under any
    summation order."""
    # before[s, u]: server u precedes-or-equals s in (rank_key, index) order
    key_u = rank_key[None, :]
    key_s = rank_key[:, None]
    idx = jnp.arange(free.shape[0])
    before = (key_u < key_s) | ((key_u == key_s) & (idx[None, :] <= idx[:, None]))
    cum = (before * free[None, :]).sum(axis=1)
    want = n_gpus.astype(free.dtype)
    take = jnp.clip(want - (cum - free), 0, free)
    feasible = free.sum() >= want
    return jnp.where(feasible, take, 0), feasible


#: Sentinel for the policy field of the jit-static config key: the gating
#: policy rides along as *runtime* scalars (max_ways, threshold_gated), so
#: every policy shares one compiled graph per trace shape (see
#: :func:`_policy_args`); the inner simulator must never read cfg.policy.
_DYNAMIC_POLICY = "<dynamic>"

#: Sentinel for exact-lookahead (``kwayK``) policies: the per-candidate
#: overlap mask and pairwise-min matmuls of ``netmodel.kway_exact_start``
#: are a materially different graph, so exact k-way compiles separately
#: while ada/srsf keep sharing the cheap threshold graph above.
_EXACT_KWAY_POLICY = "<exact-kway>"


def _policy_args(cfg: JaxSimConfig):
    """(max_ways, threshold_gated) as arrays + the policy-stripped static
    config key; threshold policies (ada/srsfN) all share one compiled
    graph, exact-lookahead ``kwayK`` policies share another."""
    spec = netmodel.parse_policy(cfg.policy)
    sentinel = _EXACT_KWAY_POLICY if spec.exact_lookahead else _DYNAMIC_POLICY
    return (
        jnp.asarray(spec.max_ways, jnp.float32),
        jnp.asarray(spec.threshold_gated, bool),
        dataclasses.replace(cfg, policy=sentinel),
    )


def _ticks_to_zero(x, inv_dt):
    """Safe underestimate of ``ceil(x / dt)`` (see :data:`_TICK_MARGIN`)."""
    return jnp.floor(x * inv_dt - _TICK_MARGIN).astype(jnp.int32) + 1


def _fabric(cfg: JaxSimConfig) -> Topology:
    """The configured fabric; none is one NIC domain per server."""
    if cfg.topology is not None:
        return cfg.topology
    return nic_topology(cfg.n_servers)


def _is_wfbp(trace) -> bool:
    """Whether a trace (or batch) carries multi-bucket WFBP planes: a
    compile-time flag, since ``(jobs, 1)`` planes and none at all run the
    monolithic step."""
    bucket_bytes = trace.get("bucket_bytes")
    return bucket_bytes is not None and int(bucket_bytes.shape[-1]) > 1


def _init_lane_state(trace: Dict[str, jnp.ndarray], cfg: JaxSimConfig):
    """Initial per-lane state, with the tick counter ``i`` and the
    :data:`UPLINK_COUNTERS` where the fabric has an uplink."""
    n_jobs = trace["arrival"].shape[0]
    ns = cfg.n_servers
    topo = _fabric(cfg)
    n_domains = np.asarray(topo.incidence()).shape[0]
    state = {
        "phase": jnp.where(trace["valid"], QUEUED, DONE).astype(jnp.int32),
        # domain-load mask, maintained incrementally (membership only
        # changes at admission / completion) so the hot loop never
        # re-derives it via incidence matmuls
        "loads": jnp.zeros((n_jobs, n_domains), bool),
        "iters_left": trace["iters"],
        "rem": jnp.zeros((n_jobs,), jnp.float32),
        "servers": jnp.zeros((n_jobs, ns), jnp.int32),
        "finish": jnp.full((n_jobs,), jnp.inf, jnp.float32),
        "free": jnp.full((ns,), float(cfg.gpus_per_server), jnp.float32),
        "t": jnp.asarray(0.0, jnp.float32),
        "n_done": jnp.asarray(0, jnp.int32),
        "i": jnp.asarray(0, jnp.int32),
        "started": jnp.zeros((n_jobs,), bool),
    }
    if _is_wfbp(trace):
        state["bucket"] = jnp.zeros((n_jobs,), jnp.int32)
    if topo.uplink_mask().any():
        state.update(dict.fromkeys(UPLINK_COUNTERS, jnp.asarray(0, jnp.int32)))
    return state


def fluid_step_core(loads, member, active, rem, bw, oversub, *,
                    b: float, eta: float, need_overlap: bool = False):
    """One tick's contention/rate evaluation.

    ``loads`` is carried in the lane state rather than derived here: it
    changes only at admission and completion.  ``min_old_rem`` is a min of
    per-domain minima rather than a masked min over the ``(J, J)`` overlap
    matrix; f32 ``min`` is exact, so both forms give the same bits, and
    this one needs no ``J x J`` intermediate.

    Args:
      loads: ``(J, D)`` bool, the contention domains each job's ring
        crosses (``netmodel.domain_loads``).
      member: ``(J, S)`` float {0,1}, the servers each job holds GPUs on.
      active: ``(J,)`` bool, transfers in flight (started, ``rem > 0``).
      rem: ``(J,)`` float, remaining cost of each job's current phase.
      bw: ``(S,)`` float, per-server relative NIC bandwidth.
      oversub: ``(D,)`` float, per-domain oversubscription.
      b / eta: Eq. 5 per-byte cost and contention penalty (static).
      need_overlap: build the ``(J, J)`` overlap matrix (WFBP and exact
        k-way gating need it; the monolithic threshold path does not).

    Returns a dict with ``counts`` (D, int32), ``k_eff`` (J, float),
    ``ratio`` (J, float: the slowest-member-scaled Eq. 5 rate fraction),
    ``k_would`` (J, int32), ``min_old_rem`` (J, float, ``inf`` where no
    overlapping transfer is in flight) and ``overlap`` (``(J, J)`` bool,
    or None unless ``need_overlap``).
    """
    counts = netmodel.domain_counts(loads, active)  # (D,)
    k_eff = netmodel.domain_k(loads, counts.astype(jnp.float32) * oversub)
    scale = netmodel.slowest_member_scale(bw, member > 0)
    ratio = scale * netmodel.rate_ratio(k_eff, b, eta)
    k_would = netmodel.domain_k(loads, counts, extra=1)
    # per-domain minimum in-flight remainder, then min over loaded domains
    dmin = jnp.where(loads & active[:, None], rem[:, None], jnp.inf).min(axis=0)
    min_old_rem = jnp.where(loads, dmin[None, :], jnp.inf).min(axis=1)
    return {
        "counts": counts,
        "k_eff": k_eff,
        "ratio": ratio,
        "k_would": k_would,
        "min_old_rem": min_old_rem,
        "overlap": job_overlap(loads) if need_overlap else None,
    }


def job_overlap(loads):
    """``(J, J)`` bool: which jobs load a common domain (an exact product
    of {0,1} values compared with 0)."""
    loads_f = loads.astype(jnp.float32)
    return (loads_f @ loads_f.T) > 0


def _make_lane_step(trace: Dict[str, jnp.ndarray], cfg: JaxSimConfig,
                    max_ways, gated):
    """Build the per-lane step function: one executed tick followed
    (``cfg.skip``) by the bulk advancement of eventless ticks."""
    n_jobs = trace["arrival"].shape[0]
    ns = cfg.n_servers
    assert cfg.policy in (_DYNAMIC_POLICY, _EXACT_KWAY_POLICY), (
        "callers go through _policy_args"
    )
    exact_kway = cfg.policy == _EXACT_KWAY_POLICY
    placement = netmodel.canonical_placement(cfg.placement)
    bw = jnp.asarray(
        netmodel.server_bandwidth_array(cfg.server_bandwidth, ns), jnp.float32
    )
    # Fabric topology as STATIC matrices (cfg is jit-static, so these are
    # compile-time constants): domain incidence (n_domains, n_servers),
    # per-domain oversubscription, and each server's rack for rack_pack.
    topo = _fabric(cfg)
    if topo.n_servers != ns:
        raise ValueError(
            f"topology covers {topo.n_servers} servers, config has {ns}"
        )
    incidence = jnp.asarray(topo.incidence(), jnp.float32)
    inc_t = incidence.T  # (S, D) for the incremental loads-row update
    oversub = jnp.asarray(topo.oversub_array(), jnp.float32)
    # static: a fabric without uplinks keeps no counters, and its step
    # graph is the one it had before they existed
    uplink = topo.uplink_mask()
    counted = bool(uplink.any())
    server_rack = jnp.asarray(topo.server_rack(), jnp.int32)
    n_racks = len(topo.rack_groups())
    place_key = jax.random.PRNGKey(cfg.placement_seed)
    server_index = jnp.arange(ns, dtype=jnp.float32)
    inv_dt = np.float32(1.0 / cfg.dt)

    # WFBP tensor-fusion buckets (layer-granular comm subsystem): a static
    # ``(jobs, B)`` size matrix plus a per-job bucket count.  ``wfbp`` is a
    # compile-time flag: without multi-bucket planes (fusion="all", or
    # (jobs, 1) planes) the step is the monolithic one (locked in
    # tests/test_wfbp.py).
    wfbp = _is_wfbp(trace)
    if wfbp:
        bucket_bytes = trace["bucket_bytes"]
        b_max = int(bucket_bytes.shape[-1])
        n_buckets = trace["n_buckets"].astype(jnp.int32)
        # per-bucket contention-free seconds; the latency `a` is paid per
        # bucket (the real cost of finer granularity), folded into the drain
        bucket_t = cfg.a + cfg.b * bucket_bytes  # (jobs, B)
        bucket_live = jnp.arange(b_max) < n_buckets[:, None]
        comm_total = jnp.where(bucket_live, bucket_t, 0.0).sum(axis=-1)
    else:
        comm_total = cfg.a + cfg.b * trace["msg_bytes"]  # contention-free s
    # Ticks per full-iteration compute segment (loop-invariant, hoisted
    # out of the scan by XLA) — the bulk fast-forward quantum for
    # non-spanning jobs, whose iteration boundaries are externally
    # invisible (their rings cross no cut => zero domain loads).
    k_iter = jnp.maximum(_ticks_to_zero(trace["t_iter"], inv_dt), 1)

    def step(st):
        step_i = st["i"]
        # Derive t from the integer tick counter instead of accumulating
        # `t += dt`: one f32 multiply has no cumulative rounding, so the
        # clock is bit-identical whether ticks execute one-by-one or jump
        # in bulk (next-event skip) — accumulated drift vs exact arrival
        # times (which sit on dt multiples) would otherwise shift
        # admissions by a tick and butterfly through placement.
        t = (step_i + 1).astype(jnp.float32) * cfg.dt
        phase, rem = st["phase"], st["rem"]

        spans0 = (st["servers"] > 0).sum(axis=1) > 1
        # Running-job SRSF key mirrors the event backend's remaining_service:
        # remaining iters x (compute + contention-free comm) x GPUs.
        rem_service = (
            st["iters_left"]
            * (trace["t_iter"] + jnp.where(spans0, comm_total, 0.0))
            * trace["n_gpus"]
        )
        # Per-server remaining workload (Alg. 3 line 3's L_S in gang form).
        load = (rem_service[:, None] * st["servers"]).sum(0)

        # ---- admission: smallest-SRSF arrived job that FITS (no head-of-
        # line blocking: infeasible jobs don't stall smaller ones) ---------
        fits = trace["n_gpus"].astype(jnp.float32) <= st["free"].sum()
        # Strict '<': a job arriving exactly on a tick boundary is seen at
        # the *next* tick.  The accumulated-f32 clock of the original loop
        # summed to slightly below k*dt, so its `<=` behaved exactly like
        # this on lattice arrivals; with the drift-free derived clock the
        # strictness must be explicit to keep admission timing (and the
        # placement decisions racing against same-tick completions) stable.
        arrived = (phase == QUEUED) & (trace["arrival"] < t) & fits
        # E_J = 0 before placement (paper Section IV-A): queued-job priority
        # is compute-only, matching the event backend's _srsf_key_queued.
        queued_key = jnp.where(
            phase == QUEUED,
            st["iters_left"] * trace["t_iter"] * trace["n_gpus"],
            jnp.inf,
        )
        pick = jnp.argmin(jnp.where(arrived, queued_key, jnp.inf))
        can_pick = arrived[pick]
        if placement == "random":
            # fresh uniform server order per step: the gang analogue of the
            # event backend's per-GPU RAND placement
            rank_extra = jax.random.uniform(
                jax.random.fold_in(place_key, step_i), (ns,)
            )
        elif placement == "rack_pack":
            rank_extra = netmodel.rack_pack_rank(
                st["free"], server_rack, n_racks, cfg.gpus_per_server
            )
        else:
            rank_extra = None
        rank_key = netmodel.placement_rank(
            placement, st["free"], load, server_index, rank_extra
        )
        take, feasible = _place(st["free"], trace["n_gpus"][pick], rank_key)
        admit = can_pick & feasible
        # one-hot select instead of .at[pick].set scatters: selects fuse
        # into the elementwise step graph, scatters are standalone thunks
        # that dominated the per-tick profile on CPU
        hot = (jnp.arange(n_jobs) == pick) & admit
        free = st["free"] - jnp.where(admit, take, 0)
        servers = jnp.where(
            hot[:, None], take.astype(jnp.int32)[None, :], st["servers"]
        )
        phase = jnp.where(hot, COMPUTE, phase)
        rem = jnp.where(hot, trace["t_iter"], rem)
        # incremental domain-load update: only the admitted job's row
        # changes (one S-vector against the static incidence — the full
        # (J,S)x(S,D) matmuls per tick dominated the CPU profile).
        # Bit-exact vs recomputing from scratch: pure boolean algebra on
        # exact {0,1} sums.
        row_member = (take > 0).astype(jnp.float32)
        row_in = row_member @ inc_t
        row_out = row_member @ (1.0 - inc_t)
        row_loads = (row_in > 0) & (row_out > 0)
        loads = jnp.where(hot[:, None], row_loads[None, :], st["loads"])

        spans = (servers > 0).sum(axis=1) > 1

        # ---- communication contention state --------------------------------
        started = st["started"]
        in_comm = phase == COMM
        # Only *started* transfers occupy links: a job that reached its
        # barrier but is still gated must not count toward contention (it
        # would otherwise see itself and deadlock under ada/srsf1).
        active = in_comm & started & (rem > 0)
        member = (servers > 0).astype(jnp.float32)  # (jobs, ns)
        # One evaluation of the contention/rate core: in-flight counts over
        # the carried domain-load mask, oversub-weighted effective k, Eq. 5
        # drain ratio, and the gating-side k_would / min_old_rem (+ the
        # overlap matrix where gating needs it).  Evaluated before the
        # compute drain: min_old_rem/k_would only read COMM rows, whose
        # ``rem`` the compute drain below cannot touch.
        core = fluid_step_core(
            loads, member, active, rem, bw, oversub,
            b=cfg.b, eta=cfg.eta,
            need_overlap=(wfbp or exact_kway),
        )
        counts = core["counts"]
        k_eff, overlap = core["k_eff"], core["overlap"]

        # ---- drain compute ---------------------------------------------------
        is_comp = phase == COMPUTE
        rem = jnp.where(is_comp, rem - cfg.dt, rem)
        comp_done = is_comp & (rem <= 0)
        # -> job with comm enters COMM (waiting: rem = full message time);
        #    single-server job completes the iteration directly.
        to_comm = comp_done & spans
        iter_done_direct = comp_done & ~spans

        # ---- comm gating (on jobs in COMM with rem == full, i.e. waiting) ---
        # Candidate cost is proportional to M_new — the gates are unit-free.
        # For a waiting WFBP job ``rem`` is the current *bucket's* size
        # (equal to comm_total while a monolithic job waits), so gating
        # decides per bucket like the event backend.
        new_cost = rem if wfbp else comm_total
        waiting = in_comm & ~started

        def may_start_vs(k_would, min_old_rem, olds_mask):
            if exact_kway:
                # Exact per-bucket k-way lookahead (closed-form option-A/
                # option-B comparison); costs are comm *seconds* (the folded
                # latency ``a`` rides along per bucket) — scale-invariant,
                # so the unit mismatch vs the event backend's raw bytes only
                # perturbs borderline calls by the a-fold.
                return netmodel.may_start_dynamic(
                    k_would, new_cost, min_old_rem, max_ways, gated,
                    cfg.dual_threshold, exact_kway_olds=olds_mask, rem=rem,
                    eta_over_b=cfg.eta / cfg.b,
                )
            return netmodel.may_start_dynamic(
                k_would, new_cost, min_old_rem, max_ways, gated,
                cfg.dual_threshold,
            )

        # candidates passing against the base active set
        olds0 = overlap & active[None, :] if overlap is not None else None
        start_ok = waiting & may_start_vs(
            core["k_would"], core["min_old_rem"], olds0
        )
        if wfbp:
            # the greedy gating closure in one masked pass (see
            # netmodel.gating_fixed_point for the antitone-predicate
            # argument)
            accept = netmodel.gating_fixed_point(
                start_ok, rem_service, loads, counts, overlap, active, rem,
                new_cost, max_ways, gated, cfg.dual_threshold,
                exact_kway=exact_kway, eta_over_b=cfg.eta / cfg.b,
            )
            started = started | accept
            leftover = start_ok & ~accept
        else:
            # one start a tick, smallest remaining service first: the event
            # sim's sorted re-evaluate-after-each-start loop (starts are
            # rare relative to dt for monolithic traces, so the cap rarely
            # binds)
            pick_c = jnp.argmin(jnp.where(start_ok, rem_service, jnp.inf))
            start_now = (jnp.arange(n_jobs) == pick_c) & start_ok
            started = started | start_now
            leftover = start_ok & ~start_now

        # ---- drain comm (started only), at the Eq. 5 rate evaluated at the
        # effective (oversub-weighted) contention and scaled by the slowest
        # member server's NIC (per-server heterogeneity) ----------------------
        ratio = core["ratio"]
        draining = in_comm & started
        rem = jnp.where(draining, rem - cfg.dt * ratio, rem)
        comm_done = draining & (rem <= 0)

        # ---- iteration bookkeeping ------------------------------------------
        # WFBP bucket stream: a finished bucket with buckets left hands the
        # next one to gating afresh (started resets — the FIFO comm stream
        # competes for the fabric per bucket, like the event backend);
        # only the LAST bucket's completion ends the iteration.
        if wfbp:
            next_b = st["bucket"] + 1
            more_buckets = comm_done & (next_b < n_buckets)
            iter_done = iter_done_direct | (comm_done & ~more_buckets)
        else:
            more_buckets = jnp.zeros_like(comm_done)
            iter_done = iter_done_direct | comm_done
        iters_left = st["iters_left"] - iter_done.astype(jnp.float32)
        job_done = iter_done & (iters_left <= 0)
        next_compute = iter_done & ~job_done

        phase = jnp.where(to_comm, COMM, phase)
        rem = jnp.where(to_comm, bucket_t[:, 0] if wfbp else comm_total, rem)
        if wfbp:
            bucket = jnp.where(to_comm, 0, st["bucket"])
            next_t = jnp.take_along_axis(
                bucket_t, jnp.clip(next_b, 0, b_max - 1)[:, None], axis=-1
            )[:, 0]
            rem = jnp.where(more_buckets, next_t, rem)
            bucket = jnp.where(more_buckets, next_b, bucket)
            started = started & ~(to_comm | iter_done | more_buckets)
        else:
            started = started & ~(to_comm | iter_done)
        phase = jnp.where(next_compute, COMPUTE, phase)
        rem = jnp.where(next_compute, trace["t_iter"], rem)
        phase = jnp.where(job_done, DONE, phase)
        finish = jnp.where(job_done, t, st["finish"])
        free = free + (servers * job_done[:, None].astype(jnp.int32)).sum(0)
        servers = jnp.where(job_done[:, None], 0, servers)
        loads = loads & ~job_done[:, None]

        new_state = {
            "phase": phase,
            "loads": loads,
            "iters_left": iters_left,
            "rem": rem,
            "servers": servers,
            "finish": finish,
            "free": free,
            "t": t,
            "n_done": (phase == DONE).sum().astype(jnp.int32),
            "i": step_i + 1,
            "started": started,
        }
        if wfbp:
            new_state["bucket"] = bucket
        if counted:
            new_state["uplink_jobs"] = st["uplink_jobs"] + (
                admit & jnp.any(row_loads & uplink)).astype(jnp.int32)
            flying = (phase == COMM) & started & (rem > 0)
            n_flying = flying.sum(dtype=jnp.int32)
            n_on_uplink = (flying & jnp.any(loads & uplink, axis=1)).sum(
                dtype=jnp.int32)

        def count_transfers(state, ticks):
            """Add the transfers in flight at the end of each of
            ``ticks`` ticks: no transfer starts or ends inside a skip, so
            each skipped tick ends with the executed tick's."""
            if counted:
                state["transfer_ticks"] = st["transfer_ticks"] + ticks * n_flying
                state["uplink_transfer_ticks"] = (
                    st["uplink_transfer_ticks"] + ticks * n_on_uplink)
            return state

        if not cfg.skip:
            return count_transfers(new_state, 1)

        # ---- next-event skip: bulk-advance eventless ticks ------------------
        # The executed tick is the one above; ``extra`` is a
        # per-lane lower bound on the number of *following* ticks at which
        # provably nothing discrete happens — no admission, no compute/comm
        # completion, no gating decision that could flip (the threshold
        # predicate is antitone in the active set and non-increasing in
        # time while the set is fixed: min_old_rem only drains).  Those
        # ticks reduce to linear drains, applied in closed form.
        rem2, phase2, iters2 = new_state["rem"], new_state["phase"], iters_left
        in_comm2 = phase2 == COMM
        is_comp2 = phase2 == COMPUTE
        started2 = new_state["started"]
        active2 = in_comm2 & started2 & (rem2 > 0)
        waiting2 = jnp.any(in_comm2 & ~started2)
        # Post-tick drain ratio: the active set may have changed this tick
        # (starts / completions), the member rows of draining jobs cannot
        # have (only job_done zeroes servers) — so loads and the slowest-
        # member scale are reusable and only counts/k_eff need refreshing.
        counts2 = netmodel.domain_counts(loads, active2)
        k_eff2 = netmodel.domain_k(loads, counts2.astype(jnp.float32) * oversub)
        ratio2 = (ratio / netmodel.rate_ratio(k_eff, cfg.b, cfg.eta)
                  ) * netmodel.rate_ratio(k_eff2, cfg.b, cfg.eta)
        # Gating must re-run next tick when: a passing candidate was not
        # started (one-start cap / closure pessimism), a completion freed
        # capacity while transfers wait (antitone: shrinking the active
        # set can flip a predicate True), a barrier or fresh bucket just
        # arrived, or the policy is an exact k-way cost comparison (not
        # monotone in time — never skip while anything waits).
        gate_block = (
            jnp.any(leftover)
            | (jnp.any(comm_done) & waiting2)
            | jnp.any(to_comm)
            | jnp.any(more_buckets)
            | (jnp.asarray(exact_kway) & waiting2)
        )
        # Per-job caps: ticks strictly before the next arrival of a job
        # that fits (free GPUs are constant during a skip), the next
        # compute completion (non-spanning jobs fast-forward whole
        # invisible iterations), and the next comm completion.
        t2 = t
        queued2 = phase2 == QUEUED
        fits2 = trace["n_gpus"].astype(jnp.float32) <= new_state["free"].sum()
        cap_arr = jnp.where(
            queued2 & fits2,
            _ticks_to_zero(trace["arrival"] - t2, inv_dt) - 1,
            _BIG_TICKS,
        )
        k_cur = _ticks_to_zero(rem2, inv_dt)
        iters_i = iters2.astype(jnp.int32)
        spans2 = (new_state["servers"] > 0).sum(axis=1) > 1
        ns_comp = is_comp2 & ~spans2
        cap_comp = jnp.where(
            is_comp2 & spans2,
            k_cur - 1,
            jnp.where(
                ns_comp, k_cur - 1 + k_iter * (iters_i - 1), _BIG_TICKS
            ),
        )
        cap_comm = jnp.where(
            active2 & (ratio2 > 0),
            _ticks_to_zero(rem2 / jnp.where(ratio2 > 0, ratio2, 1.0), inv_dt) - 1,
            _BIG_TICKS,
        )
        caps = jnp.minimum(jnp.minimum(cap_arr, cap_comp), cap_comm).min()
        extra = jnp.clip(
            jnp.minimum(caps, cfg.max_steps - new_state["i"]), 0, _BIG_TICKS
        )
        extra = jnp.where(gate_block, 0, extra)
        nf = extra.astype(jnp.float32)
        # Bulk advance: linear drains, plus whole-iteration jumps for
        # non-spanning compute jobs crossing >= 1 invisible boundary.
        cross = ns_comp & (extra >= k_cur) & (extra > 0)
        m = jnp.maximum(extra - k_cur, 0)
        aq = m // k_iter
        rq = m - aq * k_iter
        rem3 = jnp.where(
            cross,
            trace["t_iter"] - rq.astype(jnp.float32) * cfg.dt,
            jnp.where(
                is_comp2,
                rem2 - nf * cfg.dt,
                jnp.where(active2, rem2 - nf * cfg.dt * ratio2, rem2),
            ),
        )
        new_state["rem"] = rem3
        new_state["iters_left"] = jnp.where(
            cross, iters2 - (1 + aq).astype(jnp.float32), iters2
        )
        new_state["i"] = new_state["i"] + extra
        new_state["t"] = new_state["i"].astype(jnp.float32) * cfg.dt
        return count_transfers(new_state, 1 + extra)

    return step


def _lane_chunk(trace, st, cfg: JaxSimConfig, max_ways, gated):
    """One ``cfg.chunk_steps``-tick scan segment of a single lane; frozen
    (via the per-leaf ``live`` select) once the lane finishes or hits the
    step cap, so a vmapped batch can run past early finishers."""
    n_jobs = trace["arrival"].shape[0]
    step = _make_lane_step(trace, cfg, max_ways, gated)

    def body(st, _):
        live = (st["n_done"] < n_jobs) & (st["i"] < cfg.max_steps)
        st2 = step(st)
        st2 = {k: jnp.where(live, v, st[k]) for k, v in st2.items()}
        return st2, None

    st, _ = jax.lax.scan(body, st, None, length=cfg.chunk_steps)
    return st


@functools.partial(jax.jit, static_argnames=("cfg",))
def _init_jit(traces, cfg: JaxSimConfig):
    return jax.vmap(lambda tr: _init_lane_state(tr, cfg))(traces)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _chunk_jit(traces, state, cfg: JaxSimConfig, max_ways, gated):
    return jax.vmap(
        lambda tr, st: _lane_chunk(tr, st, cfg, max_ways, gated)
    )(traces, state)


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


#: query-wide counters of :func:`_drive_batched`, returned beside its
#: results and kept in each fluid ``RunMetrics``
DRIVER_COUNTERS = ("chunks", "lane_slots", "live_lane_slots", "compactions",
                   "shapes", "drained_launches")


def _drive_batched(traces: Dict[str, jnp.ndarray], cfg: JaxSimConfig,
                   max_ways, gated) -> Dict[str, np.ndarray]:
    """Host driver: chunked scan segments with early exit and (optional)
    lane/job/bucket compaction.  ``cfg`` is the policy-stripped static
    key from :func:`_policy_args`.  Returns numpy result planes shaped
    like the input batch, plus the :data:`DRIVER_COUNTERS`: ``chunks``
    (scan segments launched, the speculative last one included),
    ``lane_slots`` and ``live_lane_slots`` (lanes at each launch, summed
    over launches: all of them, padding included, and those not yet
    retired), ``compactions`` (re-gathers of the batch), ``shapes``
    (distinct ``(lanes, jobs, buckets)`` shapes launched) and
    ``drained_launches`` (launches after the first whose input chunk had
    already finished on the device: the host did not keep ahead).  On a
    fabric with uplink domains it also returns, per lane, the
    :data:`UPLINK_COUNTERS`, pulled as the lane retires.

    One chunk is kept in flight: chunk ``k + 1`` is launched on chunk
    ``k``'s output before chunk ``k``'s done flags are read, so the host
    syncs, retires and decides on compaction while the device runs.  A
    finished lane is frozen inside the scan, so the chunk a lane runs
    past its end changes no bit.  Lanes are retired from chunk ``k``'s
    state, which ``orig`` indexes; a compaction decided on chunk ``k``'s
    flags gathers from chunk ``k + 1``'s, at the same shapes as a
    synchronous loop would, and a lane that finished in chunk ``k + 1``
    is retired at the next sync.  Once every lane is done the chunk
    launched last is not read, only waited for, so that no device work
    of this call outlasts it: one chunk more than the synchronous loop.

    Each host phase sits in a profiler span (``jax.profiler.
    TraceAnnotation``, a no-op of about a microsecond when no trace is
    recorded): ``fluid.init``; per chunk, with the stat ``chunk``,
    ``fluid.launch`` (with ``uplinks``, the fabric's uplink domains),
    ``fluid.sync``, ``fluid.retire`` when lanes finish, and
    ``fluid.compact`` when finished lanes are considered for
    compaction.  Every launch has its sync, the speculative last chunk's
    included."""
    span = jax.profiler.TraceAnnotation
    with span("fluid.init"):
        arrival0 = np.asarray(traces["arrival"], np.float32)
        n_lanes0, n_jobs0 = arrival0.shape
        if "valid" not in traces:
            traces = dict(traces)
            traces["valid"] = jnp.ones((n_lanes0, n_jobs0), bool)
        state = _init_jit(traces, cfg)
    wfbp = _is_wfbp(traces)
    lane_counters = [k for k in UPLINK_COUNTERS if k in state]
    n_uplinks = int(_fabric(cfg).uplink_mask().sum())
    results = {
        "jct": np.full((n_lanes0, n_jobs0), np.inf, np.float32),
        "finished": np.zeros((n_lanes0, n_jobs0), bool),
        "makespan": np.zeros((n_lanes0,), np.float32),
        **dict.fromkeys(DRIVER_COUNTERS, 0),
        **{k: np.zeros((n_lanes0,), np.int64) for k in lane_counters},
    }
    shapes = set()
    orig = np.arange(n_lanes0)  # current lane -> original row (-1 = retired)

    while True:
        launched = results["chunks"]
        n_lanes_cur, n_jobs_cur = (int(d) for d in traces["arrival"].shape)
        shapes.add((n_lanes_cur, n_jobs_cur,
                    int(traces["bucket_bytes"].shape[-1]) if wfbp else 1))
        results["lane_slots"] += n_lanes_cur
        results["live_lane_slots"] += int((orig >= 0).sum())
        if launched and state["n_done"].is_ready():
            results["drained_launches"] += 1
        with span("fluid.launch", chunk=launched, lanes=n_lanes_cur,
                  jobs=n_jobs_cur, uplinks=n_uplinks):
            ahead = _chunk_jit(traces, state, cfg, max_ways, gated)
        results["chunks"] += 1
        if not launched:
            state = ahead
            continue
        chunk = launched - 1  # the chunk that produced ``state``
        with span("fluid.sync", chunk=chunk):
            n_done, tick = jax.device_get((state["n_done"], state["i"]))
            done = (n_done >= n_jobs_cur) | (tick >= cfg.max_steps)
            newly = [l for l in np.nonzero(done)[0] if orig[l] >= 0]
        if newly:
            with span("fluid.retire", chunk=chunk):
                # one device_get, so that the pulls overlap rather than
                # each waiting for the one before
                pulled = jax.device_get({
                    **{k: state[k] for k in ("phase", "finish", "t",
                                             *lane_counters)},
                    "valid": traces["valid"], "arrival": traces["arrival"],
                })
                phase, finish, t_now, valid, arr = (pulled[k] for k in (
                    "phase", "finish", "t", "valid", "arrival"))
                counts = {k: pulled[k] for k in lane_counters}
                for l in newly:
                    row = orig[l]
                    fin = (phase[l] == DONE) & valid[l]
                    results["jct"][row, :n_jobs_cur] = finish[l] - arr[l]
                    results["finished"][row, :n_jobs_cur] = fin
                    results["makespan"][row] = (
                        finish[l][fin].max() if fin.any() else t_now[l]
                    )
                    for k, v in counts.items():
                        results[k][row] = v[l]
                    orig[l] = -1
        if done.all():
            with span("fluid.sync", chunk=launched):
                ahead["n_done"].block_until_ready()
            break
        state = ahead
        if not (cfg.compact and done.any()):
            continue

        # ---- compaction: retire finished lanes, shrink the batch --------
        # Shapes are bucketed (pow2 lanes, jobs in multiples of 8, >= 2
        # buckets) to bound recompiles; dropped lanes are finished (their
        # results are already finalized, and they stay frozen through the
        # chunk in flight) and dropped job columns are all-invalid across
        # the surviving lanes, so results are unchanged bit-for-bit
        # (padded jobs are inert in every reduction of the step).
        with span("fluid.compact", chunk=chunk):
            live = np.nonzero(~done)[0]
            n_live = len(live)
            lanes_new = _next_pow2(n_live)
            valid = np.asarray(traces["valid"])
            pad_lane = int(np.nonzero(done)[0][0])
            sel = np.concatenate(
                [live, np.full(lanes_new - n_live, pad_lane, live.dtype)]
            )
            col_used = valid[live].any(axis=0)
            jobs_need = (
                int(np.nonzero(col_used)[0][-1]) + 1 if col_used.any() else 1
            )
            jobs_new = min(n_jobs_cur, max(8, -(-jobs_need // 8) * 8))
            if lanes_new >= len(done) and jobs_new > 3 * n_jobs_cur // 4:
                continue
            sel_dev = jnp.asarray(sel)
            traces = {
                k: jnp.take(v, sel_dev, axis=0)[:, :jobs_new]
                for k, v in traces.items()
            }
            state = {
                k: (
                    jnp.take(v, sel_dev, axis=0)[:, :jobs_new]
                    if v.ndim >= 2 and v.shape[1] == n_jobs_cur
                    else jnp.take(v, sel_dev, axis=0)
                )
                for k, v in state.items()
            }
            state["n_done"] = (
                (state["phase"] == DONE).sum(axis=1).astype(jnp.int32))
            if wfbp:
                b_cur = int(traces["bucket_bytes"].shape[-1])
                # keep >= 2 bucket columns: collapsing to one would flip the
                # static wfbp flag (a different gating cadence, not just a
                # smaller graph)
                b_need = max(2, int(np.asarray(traces["n_buckets"]).max()))
                if b_need < b_cur:
                    traces["bucket_bytes"] = (
                        traces["bucket_bytes"][:, :, :b_need])
            orig = np.concatenate(
                [orig[live], np.full(lanes_new - n_live, -1, orig.dtype)]
            )
            results["compactions"] += 1
    results["shapes"] = len(shapes)
    return results


def simulate_traces_batched(traces: Dict[str, jnp.ndarray], cfg: JaxSimConfig):
    """Chunked-scan launches over a stacked batch of traces (leading axis
    = seed; see :func:`stack_traces`), as device arrays or host numpy
    planes (moved to the device here, plane by plane: hand a host batch
    to ``jax.device_put`` first for one overlapped transfer, as
    ``scenarios.sweep.monte_carlo_fluid`` does).  Returns per-lane jct/finished
    arrays, a per-lane makespan vector, the driver's query-wide
    counters (:data:`DRIVER_COUNTERS`: scan chunks launched, lane slots,
    compactions, shapes, drained launches) and, on a fabric with uplinks,
    the per-lane :data:`UPLINK_COUNTERS`.  The gating policy enters the
    jitted graph as runtime scalars (:func:`_policy_args`), so sweeping
    policies over one trace shape reuses one compilation; finished lanes
    retire between chunks (``cfg.compact``) so stragglers don't pay full
    batch width."""
    max_ways, gated, cfg_key = _policy_args(cfg)
    out = _drive_batched(
        {k: jnp.asarray(v) for k, v in traces.items()}, cfg_key, max_ways, gated
    )
    return {
        "jct": jnp.asarray(out["jct"]),
        "finished": jnp.asarray(out["finished"]),
        "makespan": jnp.asarray(out["makespan"]),
        **{k: out[k] for k in DRIVER_COUNTERS + UPLINK_COUNTERS if k in out},
    }


def trace_from_jobs(jobs, fusion: object = "all") -> Dict[str, np.ndarray]:
    """Convert ``JobSpec`` lists (trace generator / scenario engine output)
    into the struct-of-arrays layout the fluid simulator consumes: host
    numpy planes (``float32`` ``arrival``/``iters``/``t_iter``/
    ``msg_bytes``, ``int32`` ``n_gpus``), with no device transfer.

    ``fusion`` ('all' | 'none' | a byte threshold) adds the WFBP bucket
    planes: a static ``(jobs, B)`` ``bucket_bytes`` matrix (zero-padded)
    plus per-job ``int32`` ``n_buckets``, from ``netmodel.fusion_plan``
    over each model's layer data.  Models without layer data (the paper's
    Table III profiles) stay one monolithic bucket; ``fusion="all"`` omits
    the planes entirely, the monolithic trace."""
    tr = {
        "arrival": np.array([j.arrival for j in jobs], np.float32),
        "iters": np.array([j.iterations for j in jobs], np.float32),
        "t_iter": np.array([j.model.t_iter_compute for j in jobs], np.float32),
        "msg_bytes": np.array([j.model.size_bytes for j in jobs], np.float32),
        "n_gpus": np.array([j.n_gpus for j in jobs], np.int32),
    }
    thr = netmodel.fusion_threshold(fusion)
    if thr == float("inf"):
        return tr
    plans = []
    for j in jobs:
        m = j.model
        if getattr(m, "has_layers", False):
            plans.append(netmodel.fusion_plan(m.layer_grad_bytes, m.layer_t_b, thr)[0])
        else:
            plans.append((m.size_bytes,))
    b_max = max(len(p) for p in plans)
    bb = np.zeros((len(plans), b_max), np.float32)
    for i, p in enumerate(plans):
        bb[i, : len(p)] = p
    tr["bucket_bytes"] = bb
    tr["n_buckets"] = np.array([len(p) for p in plans], np.int32)
    return tr


#: each batch plane's dtype and the value that pads it with inert jobs
_PLANES = {
    "arrival": (np.float32, 0.0),
    "iters": (np.float32, 1.0),
    "t_iter": (np.float32, 1.0),
    "msg_bytes": (np.float32, 0.0),
    "n_gpus": (np.int32, 1),
    "valid": (np.bool_, False),
    "bucket_bytes": (np.float32, 0.0),
    "n_buckets": (np.int32, 1),
}


def stack_traces(traces: Sequence[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """Stack per-seed traces into one rectangular batch for
    :func:`simulate_traces_batched`, padding ragged job counts with inert
    jobs masked out by a boolean ``valid`` plane (padded lanes start DONE
    and are excluded from ``finished``).  WFBP bucket planes
    (``bucket_bytes``/``n_buckets``, see :func:`trace_from_jobs`) are
    padded along both the job and the bucket axis; lanes missing the
    planes get monolithic ones when any lane carries them.

    Each plane is allocated once on the host, filled with its pad, and
    each lane's rows are copied in; lanes may be numpy or jax arrays.
    Returns numpy planes and runs no device program: the caller makes
    the one transfer (``jax.device_put`` of the whole dict)."""
    if not traces:
        raise ValueError("need at least one trace to stack")
    lanes = [{k: np.asarray(v) for k, v in tr.items()} for tr in traces]
    n_max = max(lane["arrival"].shape[0] for lane in lanes)
    has_buckets = any("bucket_bytes" in lane for lane in lanes)
    b_max = max(
        (lane["bucket_bytes"].shape[-1] for lane in lanes if "bucket_bytes" in lane),
        default=1,
    )
    out = {}
    for k in sorted(set().union(*lanes, ["valid"])):
        dtype, fill = _PLANES[k]
        shape = (len(lanes), n_max) + ((b_max,) if k == "bucket_bytes" else ())
        out[k] = np.full(shape, fill, dtype)
    for i, lane in enumerate(lanes):
        n = lane["arrival"].shape[0]
        lane.setdefault("valid", np.ones((n,), bool))
        if has_buckets and "bucket_bytes" not in lane:
            lane["bucket_bytes"] = lane["msg_bytes"][:, None]
            lane["n_buckets"] = np.ones((n,), np.int32)
        for k, v in lane.items():
            out[k][(i,) + tuple(slice(d) for d in v.shape)] = v
    return out


def simulate_jobs(jobs, cfg: JaxSimConfig, fusion: object = "all") -> Dict[str, np.ndarray]:
    """One fluid simulation of a fixed job list (a one-lane batch);
    numpy outputs."""
    out = simulate_traces_batched(
        stack_traces([trace_from_jobs(jobs, fusion=fusion)]), cfg)
    return {
        "jct": np.asarray(out["jct"][0]),
        "finished": np.asarray(out["finished"][0]),
        "makespan": float(out["makespan"][0]),
    }
