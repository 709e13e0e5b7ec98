"""Sweep runner: scenario x policy x seed matrices over both backends.

* :func:`run_scenario_event` — one exact event-driven simulation of a
  scenario (the reference backend; supports every placement/comm policy and
  heterogeneous per-server bandwidth).
* :func:`run_scenario_fluid` — one vectorized fluid (JAX) simulation of the
  same scenario through the ``core/jaxsim.py`` fixed-trace entry point.
  Feature parity via the shared ``core/netmodel.py`` layer: every gating
  policy (AdaDUAL, SRSF(n), exact closed-form k-way), per-server
  heterogeneous bandwidth, and three gang placement modes.  Remaining
  approximations: gang-exclusive placement, fixed dt.  Fault injection
  (``Scenario.chaos``) is event-only — :func:`fluid_config` raises.
* :func:`sweep` — the full matrix, optionally fanned out over a
  ``multiprocessing`` pool (event backend only: jax jits don't fork well),
  returning one :class:`~repro.scenarios.metrics.RunMetrics` per cell.
* :func:`monte_carlo_fluid` / :func:`sweep_ci` — seeds batched into ONE
  vmapped device launch per fluid cell (padded via
  ``jaxsim.stack_traces``), aggregated to mean +/- std
  :class:`~repro.scenarios.metrics.CellCI` rows.

Policy strings accept the simulator's names ('ada', 'srsf1', 'kway3', ...)
plus the paper aliases 'adadual'/'ada-srsf'.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core import netmodel
from repro.core.placement import PlacementPolicy
from repro.core.simulator import ClusterSimulator, SimResult, comm_policy_from_name
from repro.scenarios import metrics as metrics_mod
from repro.scenarios.registry import Scenario, get_scenario

COMM_ALIASES = {
    "adadual": "ada",
    "ada-srsf": "ada",
    "ada_srsf": "ada",
}

#: Gating policies the fluid backend supports (branchless masks from the
#: shared layer): AdaDUAL, SRSF(n), and threshold-gated k-way AdaDUAL.
FLUID_POLICIES = ("ada", "srsf1", "srsf2", "srsf3", "kway2", "kway3")

#: Fluid-vs-event agreement bound on aggregate metrics (avg JCT, makespan):
#: each backend's value within this factor of the other's.  Gang placement
#: makes the fluid backend pessimistic on shared-GPU scenarios.
FLUID_EVENT_RATIO = 2.0


def canonical_comm(comm: str) -> str:
    return COMM_ALIASES.get(comm.lower(), comm.lower())


# ---------------------------------------------------------------------------
# Single runs
# ---------------------------------------------------------------------------


def run_scenario_event(
    scenario: Scenario,
    placement: str = "lwf",
    kappa: int = 1,
    comm: str = "ada",
    **sim_kw,
) -> SimResult:
    """Exact event-driven simulation of one scenario instance.  The
    scenario's scheduling knobs (``sched``, ``preemption_quantum``,
    ``checkpoint_cost``, ``exclusive_gpus``) are defaults; any ``sim_kw``
    override wins — that is how the regression tests compare
    preemptive-vs-static on the same workload.

    A scenario carrying a streaming ``source`` (trace-replay scale) feeds
    the engine the lazy arrival stream instead of a materialized list, so
    the event calendar stays O(live jobs + cluster) at 100k+-job scale —
    results are identical either way (the engine's streaming mode is
    regression-locked against list mode in tests/test_tracesource.py)."""
    cluster = scenario.make_cluster()
    params = scenario.params
    jobs = scenario.source if scenario.source is not None else scenario.job_list()
    sim_kw.setdefault("fusion", scenario.fusion)
    sim_kw.setdefault("sched", scenario.sched)
    sim_kw.setdefault("preemption_quantum", scenario.preemption_quantum)
    sim_kw.setdefault("checkpoint_cost", scenario.checkpoint_cost)
    sim_kw.setdefault("exclusive_gpus", scenario.exclusive_gpus)
    sim_kw.setdefault("chaos", scenario.chaos)
    max_time = sim_kw.pop("max_time", math.inf)  # run() arg, not ctor
    sim = ClusterSimulator(
        jobs,
        cluster=cluster,
        placement=PlacementPolicy(
            placement, kappa=kappa, seed=scenario.seed, topology=scenario.topology
        ),
        comm_policy=comm_policy_from_name(canonical_comm(comm)),
        params=params,
        topology=scenario.topology,
        **sim_kw,
    )
    return sim.run(max_time=max_time)


def fluid_config(
    scenario: Scenario,
    comm: str = "ada",
    placement: str = "lwf",
    dt: float = 0.05,
    max_steps: Optional[int] = None,
    **fast_kw,
):
    """JaxSimConfig for a scenario: per-server bandwidth and the fabric
    topology pass through verbatim (the fluid backend drains each transfer
    at its slowest member server and at the oversub-weighted per-domain
    contention); event placement names map to their gang analogues
    (lwf->consolidate, ff->first_fit, ls->least_loaded, rand->random,
    lwf_rack->rack_pack).  ``fast_kw`` forwards the fast-path knobs
    (``skip``, ``compact``, ``chunk_steps``), which is how the
    equivalence tests pin the references ``skip=False`` and
    ``compact=False``.  ``max_steps=None`` keeps the config's horizon
    cap."""
    from repro.core.jaxsim import JaxSimConfig

    comm = canonical_comm(comm)
    if comm not in FLUID_POLICIES:
        raise ValueError(
            f"fluid backend supports {FLUID_POLICIES}, got {comm!r}"
        )
    if scenario.chaos is not None and scenario.chaos.active:
        raise ValueError(
            f"scenario {scenario.name!r} arms fault injection (chaos=), "
            "which is event-backend only: the fluid backend's static "
            "traces cannot express mid-run gang teardown/repair"
        )
    if scenario.source is not None and not scenario.jobs:
        raise ValueError(
            f"scenario {scenario.name!r} is an unmaterialized streaming "
            "trace replay (source= without jobs), which is event-backend "
            "only: the fluid backend needs the whole trace as one static "
            "tensor, defeating the O(live jobs) replay memory bound"
        )
    if max_steps is not None:
        fast_kw["max_steps"] = max_steps
    p = scenario.params
    gang_mode = netmodel.canonical_placement(placement)
    return JaxSimConfig(
        n_servers=scenario.n_servers,
        gpus_per_server=scenario.gpus_per_server,
        dt=dt,
        policy=comm,
        placement=gang_mode,
        a=p.a,
        b=p.b,
        eta=p.eta,
        dual_threshold=p.dual_threshold,
        server_bandwidth=tuple(p.server_bandwidth),
        topology=scenario.topology,
        # the seed is jit-static config: keep it constant unless the
        # placement actually consumes it, so seed sweeps share one compile
        placement_seed=scenario.seed if gang_mode == "random" else 0,
        **fast_kw,
    )


def run_scenario_fluid(
    scenario: Scenario,
    comm: str = "ada",
    placement: str = "lwf",
    dt: float = 0.05,
    max_steps: Optional[int] = None,
    **fast_kw,
) -> Dict[str, object]:
    """Fluid (vectorized JAX) simulation of one scenario instance (the
    scenario's WFBP ``fusion`` spec shapes the bucket planes of the
    trace — ``"all"`` leaves it monolithic)."""
    from repro.core.jaxsim import simulate_jobs

    cfg = fluid_config(
        scenario, comm=comm, placement=placement, dt=dt,
        max_steps=max_steps, **fast_kw,
    )
    return simulate_jobs(scenario.job_list(), cfg, fusion=scenario.fusion)


def _dedupe_fluid_placements(placements: Sequence[str]) -> Tuple[str, ...]:
    """Map event placement names to their gang analogues up front (so
    'rand' fails fast) and dedupe aliases that collapse to one mode."""
    seen: Dict[str, str] = {}
    for pl in placements:
        seen.setdefault(netmodel.canonical_placement(pl), pl)
    return tuple(seen.values())


# ---------------------------------------------------------------------------
# The matrix
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SweepCell:
    """One picklable cell of the sweep matrix (workers rebuild the scenario
    from (name, seed, overrides) so nothing heavyweight crosses processes).

    ``sim_kw`` carries extra event-simulator keyword overrides (e.g.
    ``sched="preemptive_srsf"`` or ``bandwidth_aware_srsf=True``) — the
    event backend only; a fluid cell with ``sim_kw`` raises rather than
    silently ignoring the knobs."""

    scenario: str
    seed: int
    placement: str
    kappa: int
    comm: str
    backend: str  # "event" | "fluid"
    overrides: Tuple[Tuple[str, object], ...] = ()
    dt: float = 0.05
    sim_kw: Tuple[Tuple[str, object], ...] = ()


def run_cell(cell: SweepCell) -> metrics_mod.RunMetrics:
    if cell.sim_kw and cell.backend != "event":
        raise ValueError(
            f"sim_kw {dict(cell.sim_kw)} is event-backend only "
            f"(got backend {cell.backend!r})"
        )
    if cell.backend == "event":
        scn = get_scenario(cell.scenario, seed=cell.seed, **dict(cell.overrides))
        t0 = time.time()
        res = run_scenario_event(
            scn,
            placement=cell.placement,
            kappa=cell.kappa,
            comm=cell.comm,
            **dict(cell.sim_kw),
        )
        return metrics_mod.from_event_result(
            res,
            scenario=cell.scenario,
            seed=cell.seed,
            n_jobs=scn.n_jobs,
            wall_s=time.time() - t0,
        )
    if cell.backend == "fluid":
        (rec,) = monte_carlo_fluid(
            cell.scenario, [cell.seed], comm=cell.comm,
            placement=cell.placement, overrides=dict(cell.overrides),
            dt=cell.dt,
        )
        return rec
    raise ValueError(f"unknown backend {cell.backend!r}")


def sweep(
    scenarios: Sequence[str],
    comms: Sequence[str] = ("ada", "srsf1", "srsf2"),
    placements: Sequence[str] = ("lwf",),
    kappa: int = 1,
    seeds: Sequence[int] = (0,),
    backend: str = "event",
    overrides: Optional[Dict[str, object]] = None,
    per_scenario_overrides: Optional[Dict[str, Dict[str, object]]] = None,
    processes: Optional[int] = None,
    dt: float = 0.05,
    sim_kw: Optional[Dict[str, object]] = None,
) -> List[metrics_mod.RunMetrics]:
    """Run the full scenario x placement x comm x seed matrix.

    ``overrides`` applies to every scenario; ``per_scenario_overrides``
    (keyed by scenario name, e.g. ``QUICK_OVERRIDES``) layers on top, so
    one call — and hence one worker pool — can span scenarios that need
    different sizing.  ``sim_kw`` forwards event-simulator keyword
    overrides to every cell (e.g. ``sched=`` or ``bandwidth_aware_srsf=``
    — how the nightly grid runs the same cells under different scheduling
    modes).  ``processes > 1`` fans cells out over a multiprocessing pool
    (event backend only — jitted jax functions don't survive fork well)."""
    if backend == "fluid":
        placements = _dedupe_fluid_placements(placements)

    def cell_overrides(name: str) -> Tuple[Tuple[str, object], ...]:
        d = dict(overrides or {})
        d.update((per_scenario_overrides or {}).get(name, {}))
        return tuple(sorted(d.items()))

    cells = [
        SweepCell(
            scenario=s,
            seed=seed,
            placement=pl,
            kappa=kappa,
            comm=c,
            backend=backend,
            overrides=cell_overrides(s),
            dt=dt,
            sim_kw=tuple(sorted((sim_kw or {}).items())),
        )
        for s in scenarios
        for pl in placements
        for c in comms
        for seed in seeds
    ]
    if processes and processes > 1 and backend == "event" and len(cells) > 1:
        import multiprocessing as mp

        # spawn, not fork: the caller may hold jitted jax state and worker
        # imports are cheap (the event backend is jax-free)
        with mp.get_context("spawn").Pool(processes) as pool:
            return pool.map(run_cell, cells)
    return [run_cell(c) for c in cells]


# ---------------------------------------------------------------------------
# Batched Monte-Carlo (confidence intervals per cell)
# ---------------------------------------------------------------------------


def monte_carlo_fluid(
    scenario: str,
    seeds: Sequence[int],
    comm: str = "ada",
    placement: str = "lwf",
    overrides: Optional[Dict[str, object]] = None,
    dt: float = 0.05,
    max_steps: Optional[int] = None,
    **fast_kw,
) -> List[metrics_mod.RunMetrics]:
    """All seeds of one scenario x policy x placement cell in ONE vmapped
    fluid launch: per-seed traces are encoded and padded/stacked on the
    host as numpy planes (``jaxsim.trace_from_jobs``,
    ``jaxsim.stack_traces``), moved to the device in one
    ``jax.device_put`` of the whole batch, and swept by
    ``simulate_traces_batched`` — one XLA compilation, one
    :class:`RunMetrics` per seed.  The ``fluid.build.stack`` span carries
    what the transfer moved: ``lanes``, ``jobs`` (the padded width) and
    ``bytes``.  The contention model/cluster shape must not vary with the
    seed (true for every registered scenario); the seed only resamples
    jobs.

    Stacking pads every seed's trace to the batch-max job count, but the
    padding does NOT persist for the whole run: the chunked driver re-pads
    per chunk, retiring finished lanes and trimming the job axis down to
    the widest *live* lane after each compaction, so one long-tailed seed
    no longer drags the whole batch at max width (the old driver ran every
    lane at the global max shape for every step).

    Raises ``RuntimeError`` if any lane reaches the horizon cap
    (``max_steps`` ticks) with jobs unfinished: its JCT statistics would
    cover only the jobs that finished and read as a fast run."""
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation as span

    from repro.core.jaxsim import (
        DRIVER_COUNTERS,
        UPLINK_COUNTERS,
        simulate_traces_batched,
        stack_traces,
        trace_from_jobs,
    )

    seeds = list(seeds)
    with span("fluid.query", lanes=len(seeds)):
        with span("fluid.build"):
            with span("fluid.build.scenario"):
                scns = [get_scenario(scenario, seed=s, **(overrides or {}))
                        for s in seeds]
                cfg = fluid_config(
                    scns[0], comm=comm, placement=placement, dt=dt,
                    max_steps=max_steps, **fast_kw,
                )
            t0 = time.time()
            with span("fluid.build.encode"):
                traces = [trace_from_jobs(s.job_list(), fusion=s.fusion)
                          for s in scns]
            with span("fluid.build.stack") as stack:
                host = stack_traces(traces)
                stack.set_metadata(
                    lanes=len(seeds), jobs=host["arrival"].shape[1],
                    bytes=sum(v.nbytes for v in host.values()))
                batch = jax.device_put(host)
        out = simulate_traces_batched(batch, cfg)
        with span("fluid.collect"):
            jct = np.asarray(out["jct"])
            fin = np.asarray(out["finished"])
            mks = np.asarray(out["makespan"])
            wall = (time.time() - t0) / len(seeds)
            stranded = [
                (seed, scn.n_jobs - int(fin[i].sum()))
                for i, (seed, scn) in enumerate(zip(seeds, scns))
                if fin[i].sum() != scn.n_jobs
            ]
            if stranded:
                raise RuntimeError(
                    f"fluid {scenario}/{cfg.policy}: {len(stranded)} lanes "
                    f"reached the horizon cap of {cfg.max_steps} ticks "
                    f"({cfg.max_steps * cfg.dt:g} s at dt {cfg.dt:g}) with "
                    f"jobs unfinished ((seed, jobs) {stranded[:8]}); pass a "
                    "larger max_steps"
                )
            counters = {k: out[k] for k in DRIVER_COUNTERS}
            per_lane = {k: out[k] for k in UPLINK_COUNTERS if k in out}
            return [
                metrics_mod.from_jcts(
                    jct[i][fin[i]].tolist(),
                    scenario=scenario,
                    backend="fluid",
                    placement=f"gang-{cfg.placement}",
                    comm=cfg.policy,
                    seed=seed,
                    n_jobs=scn.n_jobs,
                    makespan=float(mks[i]),
                    wall_s=wall,
                    **counters,
                    **{k: int(v[i]) for k, v in per_lane.items()},
                )
                for i, (seed, scn) in enumerate(zip(seeds, scns))
            ]


def sweep_ci(
    scenarios: Sequence[str],
    comms: Sequence[str] = ("ada", "srsf1", "srsf2"),
    placements: Sequence[str] = ("lwf",),
    kappa: int = 1,
    seeds: Sequence[int] = (0, 1, 2, 3, 4),
    backend: str = "fluid",
    overrides: Optional[Dict[str, object]] = None,
    per_scenario_overrides: Optional[Dict[str, Dict[str, object]]] = None,
    processes: Optional[int] = None,
    dt: float = 0.05,
    sim_kw: Optional[Dict[str, object]] = None,
) -> List[metrics_mod.CellCI]:
    """Mean +/- std avg-JCT per scenario x placement x comm cell over
    ``seeds``.  Fluid backend: one vmapped batch per cell
    (:func:`monte_carlo_fluid`); event backend: the exact per-seed sweep
    (optionally multiprocessed), aggregated the same way.  ``sim_kw`` is
    event-only (see :func:`sweep`)."""
    if backend == "fluid":
        if sim_kw:
            raise ValueError(f"sim_kw {sim_kw} is event-backend only")
        placements = _dedupe_fluid_placements(placements)
        records: List[metrics_mod.RunMetrics] = []
        for s in scenarios:
            cell_over = dict(overrides or {})
            cell_over.update((per_scenario_overrides or {}).get(s, {}))
            for pl in placements:
                for c in comms:
                    records.extend(
                        monte_carlo_fluid(
                            s, seeds, comm=c, placement=pl,
                            overrides=cell_over, dt=dt,
                        )
                    )
    else:
        records = sweep(
            scenarios,
            comms=comms,
            placements=placements,
            kappa=kappa,
            seeds=seeds,
            backend=backend,
            overrides=overrides,
            per_scenario_overrides=per_scenario_overrides,
            processes=processes,
            dt=dt,
            sim_kw=sim_kw,
        )
    return metrics_mod.ci_from_runs(records)
