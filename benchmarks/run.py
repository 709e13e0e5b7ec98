"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Default scale is reduced so
``python -m benchmarks.run`` completes in minutes on one CPU; pass
``--full`` for the paper-scale 160-job/64-GPU configuration used in
EXPERIMENTS.md (the headline numbers there come from --full runs).

    PYTHONPATH=src python -m benchmarks.run [--full] [--only table5 ...]

Scenario-engine sweeps (``--scenario``) print one RunMetrics CSV row per
scenario x placement x comm x seed cell, on either backend:

    # event backend, one scenario x policy matrix
    PYTHONPATH=src python -m benchmarks.run --scenario philly_heavy_tail \
        --policy adadual srsf1 srsf2
    # fluid backend incl. k-way AdaDUAL and placement modes
    PYTHONPATH=src python -m benchmarks.run --scenario hetero_bandwidth \
        --backend fluid --policy ada kway3 --placement lwf ff
    # mean +/- std confidence intervals per cell; fluid batches every seed
    # into ONE vmapped device launch (CellCI CSV rows)
    PYTHONPATH=src python -m benchmarks.run --scenario all --ci \
        --seeds 0 1 2 3 4 --backend fluid
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Dict, List, Tuple

from repro.compile_cache import use_compile_cache
from repro.core import (
    ContentionParams,
    PAPER_A,
    PAPER_B,
    allreduce_cost_terms,
    fit_linear_cost,
    paper_trace,
    simulate,
)
from repro.core.contention import fit_contention_penalty, simulate_contention_sweep

ROWS: List[str] = []


def emit(name: str, us_per_call: float, derived: str) -> None:
    row = f"{name},{us_per_call:.2f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def provenance() -> Dict[str, object]:
    """Run provenance stamped into every ``BENCH_*.json``: git sha (with a
    ``-dirty`` suffix when the tree has local edits), UTC timestamp and
    host identity.  ``benchmarks/compare.py`` prints these when flagging a
    regression so a nightly alert is attributable to a commit + machine."""
    import datetime
    import platform
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    sha = "unknown"
    try:
        p = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, cwd=repo, timeout=10,
        )
        if p.returncode == 0:
            sha = p.stdout.strip()
            q = subprocess.run(
                ["git", "status", "--porcelain"],
                capture_output=True, text=True, cwd=repo, timeout=10,
            )
            if q.returncode == 0 and q.stdout.strip():
                sha += "-dirty"
    except (OSError, subprocess.SubprocessError):
        pass
    try:
        from importlib.metadata import version

        jax_version = version("jax")
    except Exception:  # jax absent: the event-only benches still stamp
        jax_version = "unknown"
    return {
        "git_sha": sha,
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc
        ).isoformat(timespec="seconds"),
        "host": platform.node(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "cpu_count": os.cpu_count(),
        "jax_version": jax_version,
    }


def trace_for(full: bool, seed: int = 0):
    if full:
        return paper_trace(seed=seed)
    return paper_trace(seed=seed, n_jobs=64, min_iters=200, max_iters=1200)


# ---------------------------------------------------------------------------
# Table I — All-Reduce algorithm costs
# ---------------------------------------------------------------------------


def bench_table1(full: bool) -> None:
    alpha, beta, gamma = 5e-5, 8e-10, 1e-10  # 10GbE-flavoured
    m = 100e6
    for alg in ("binary_tree", "recursive_doubling", "recursive_halving_doubling", "ring"):
        a, b = allreduce_cost_terms(alg, 16, alpha, beta, gamma)
        t = (a + b * m) * 1e6
        emit(f"table1/{alg}", t, f"a={a:.3e};b={b:.3e}")


# ---------------------------------------------------------------------------
# Fig. 2(a) — single All-Reduce cost model fit
# ---------------------------------------------------------------------------


def bench_fig2a(full: bool) -> None:
    import numpy as np

    rng = np.random.default_rng(0)
    ms = np.linspace(1e6, 500e6, 60)
    ts = PAPER_A + PAPER_B * ms
    ts = ts * (1 + rng.normal(0, 0.02, ts.shape))  # 2% measurement noise
    t0 = time.time()
    a, b = fit_linear_cost(ms, ts)
    dt = (time.time() - t0) * 1e6
    emit(
        "fig2a/fit",
        dt,
        f"a={a:.3e}(paper {PAPER_A:.3e});b={b:.3e}(paper {PAPER_B:.3e})",
    )


# ---------------------------------------------------------------------------
# Fig. 2(b) — k-way contention sweep
# ---------------------------------------------------------------------------


def bench_fig2b(full: bool) -> None:
    p = ContentionParams()
    m = 100e6
    times = simulate_contention_sweep(p, m, 8)
    ideal_share = [(p.a + k * p.b * m) for k in range(1, 9)]
    for k, (t, ideal) in enumerate(zip(times, ideal_share), start=1):
        eff = ideal / t
        emit(f"fig2b/k={k}", t * 1e6, f"bandwidth_efficiency={eff:.3f}")
    import numpy as np

    eta = fit_contention_penalty(np.arange(1, 9), times, m, p.a, p.b)
    emit("fig2b/eta_refit", 0.0, f"eta={eta:.3e}(truth {p.eta:.3e})")


# ---------------------------------------------------------------------------
# Table IV / Fig. 4 — placement comparison under Ada-SRSF
# ---------------------------------------------------------------------------


def bench_table4(full: bool) -> None:
    jobs = trace_for(full)
    for placement in ("rand", "ff", "ls", "lwf"):
        t0 = time.time()
        res = simulate(jobs, placement=placement, kappa=1, comm="ada")
        dt = (time.time() - t0) * 1e6
        emit(
            f"table4/{placement}",
            dt,
            f"avg_jct={res.avg_jct():.1f};median={res.median_jct():.1f};"
            f"p95={res.p95_jct():.1f};util={res.gpu_util:.4f};finished={len(res.jct)}",
        )


# ---------------------------------------------------------------------------
# Fig. 5 — kappa sweep for LWF
# ---------------------------------------------------------------------------


def bench_fig5(full: bool) -> None:
    jobs = trace_for(full)
    for kappa in (1, 2, 4, 8):
        t0 = time.time()
        res = simulate(jobs, placement="lwf", kappa=kappa, comm="ada")
        dt = (time.time() - t0) * 1e6
        emit(
            f"fig5/kappa={kappa}",
            dt,
            f"avg_jct={res.avg_jct():.1f};util={res.gpu_util:.4f}",
        )


# ---------------------------------------------------------------------------
# Table V / Fig. 6 — communication scheduling comparison under LWF-1
# ---------------------------------------------------------------------------


def bench_table5(full: bool) -> None:
    jobs = trace_for(full)
    for comm in ("srsf1", "srsf2", "srsf3", "ada", "kway3"):
        t0 = time.time()
        res = simulate(jobs, placement="lwf", kappa=1, comm=comm)
        dt = (time.time() - t0) * 1e6
        tag = "table5" if comm != "kway3" else "beyond/kway"
        emit(
            f"{tag}/{comm}",
            dt,
            f"avg_jct={res.avg_jct():.1f};median={res.median_jct():.1f};"
            f"p95={res.p95_jct():.1f};util={res.gpu_util:.4f};"
            f"contended={res.comm_started_contended};finished={len(res.jct)}",
        )


# ---------------------------------------------------------------------------
# Beyond-paper: chunked / preemptible communication (future-work #3 adjacent)
# ---------------------------------------------------------------------------


def bench_chunked(full: bool) -> None:
    """Contention-heavy scenario: many multi-server jobs share few servers;
    chunking lets short messages preempt long in-flight transfers."""
    from repro.core.cluster import TABLE_III, JobSpec

    jobs = []
    jid = 0
    for wave in range(6 if full else 3):
        for model, iters in (("vgg16", 400), ("resnet50", 400), ("resnet50", 400)):
            jobs.append(JobSpec(jid, wave * 5.0, 8, iters, TABLE_III[model]))
            jid += 1
    for chunks in (1, 4, 8):
        for comm in ("srsf1", "ada"):
            t0 = time.time()
            res = simulate(jobs, placement="lwf", comm=comm, comm_chunks=chunks,
                           n_servers=4, gpus_per_server=4)
            dt = (time.time() - t0) * 1e6
            emit(
                f"beyond/chunked{chunks}/{comm}",
                dt,
                f"avg_jct={res.avg_jct():.1f};p95={res.p95_jct():.1f};"
                f"util={res.gpu_util:.4f};finished={len(res.jct)}",
            )


# ---------------------------------------------------------------------------
# Scenario engine sweep (src/repro/scenarios)
# ---------------------------------------------------------------------------

def _scenario_sweep(
    names, policies, placements, seeds, backend, processes, full, ci=False,
    kappas=(1,), sched=None, bw_aware_srsf=False, obs=False,
) -> None:
    from repro.scenarios import QUICK_OVERRIDES, metrics as metrics_mod
    from repro.scenarios import scenario_names, sweep, sweep_ci

    if names == ["all"]:
        names = scenario_names()
        if backend == "fluid":
            # fault injection and streaming trace replay are event-only
            # (run_scenario_fluid raises on an armed chaos spec or an
            # unmaterialized source): 'all' means 'all supported' here,
            # while naming such a scenario explicitly still fails loudly
            names = [
                n for n in names
                if not n.startswith(("chaos_", "trace_replay_"))
            ]
    sim_kw = {}
    if sched is not None:
        sim_kw["sched"] = sched
    if bw_aware_srsf:
        sim_kw["bandwidth_aware_srsf"] = True
    if obs:
        # arm the JCT decomposition so the stretch_frac / gating_frac CSV
        # columns carry data (event backend only — the fluid sweep rejects
        # engine sim_kw)
        if backend == "fluid":
            raise SystemExit("--obs requires the event backend")
        from repro.obs import ObsConfig

        sim_kw["observe"] = ObsConfig(decompose=True)
    header_done = False
    for kappa in kappas:
        kw = dict(
            comms=policies,
            placements=placements,
            kappa=kappa,
            seeds=seeds,
            backend=backend,
            per_scenario_overrides={} if full else QUICK_OVERRIDES,
            processes=processes,
            sim_kw=sim_kw or None,
        )
        if ci:
            if not header_done:
                print(metrics_mod.CellCI.csv_header(), flush=True)
                header_done = True
            for r in sweep_ci(names, **kw):
                print(r.as_csv_row(), flush=True)
            continue
        if not header_done:
            print(metrics_mod.RunMetrics.csv_header(), flush=True)
            header_done = True
        for r in sweep(names, **kw):
            print(r.as_csv_row(), flush=True)


def bench_scenarios(full: bool) -> None:
    """Default-path smoke of the scenario sweep: two cheap scenarios."""
    from repro.scenarios import QUICK_OVERRIDES, sweep

    for name in ("smoke", "adversarial_allbig"):
        t0 = time.time()
        records = sweep(
            [name],
            comms=("ada", "srsf1", "srsf2"),
            seeds=(0,),
            per_scenario_overrides={} if full else QUICK_OVERRIDES,
        )
        dt = (time.time() - t0) * 1e6 / max(1, len(records))
        for r in records:
            emit(
                f"scenarios/{name}/{r.comm}",
                dt,
                f"avg_jct={r.avg_jct:.1f};p95={r.p95_jct:.1f};"
                f"makespan={r.makespan:.1f};util={r.gpu_util:.4f};"
                f"finished={r.n_finished}",
            )


# ---------------------------------------------------------------------------
# Topology-aware scheduling (core/topology.py) + fluid batched throughput
# ---------------------------------------------------------------------------


def bench_topology(full: bool) -> None:
    """oversub_fabric on both backends, the rack-aware placement payoff on
    rack_locality, and the fluid backend's batched Monte-Carlo throughput
    (traces/sec through one vmapped launch), persisted to
    ``BENCH_topology.json`` (path override: ``REPRO_BENCH_TOPOLOGY_JSON``)
    so the nightly workflow can track the trend."""
    import numpy as np

    from repro.core.jaxsim import (
        simulate_traces_batched,
        stack_traces,
        trace_from_jobs,
    )
    from repro.scenarios import QUICK_OVERRIDES, get_scenario
    from repro.scenarios.sweep import fluid_config, run_scenario_event

    overrides = {} if full else QUICK_OVERRIDES["oversub_fabric"]
    seeds = list(range(8))
    scns = [get_scenario("oversub_fabric", seed=s, **overrides) for s in seeds]
    cfg = fluid_config(scns[0], comm="ada", placement="lwf")
    batch = stack_traces([trace_from_jobs(s.job_list()) for s in scns])

    # compile once, then time steady-state launches (numpy conversion syncs)
    np.asarray(simulate_traces_batched(batch, cfg)["makespan"])
    n_rep = 3
    t0 = time.time()
    for _ in range(n_rep):
        out = simulate_traces_batched(batch, cfg)
        np.asarray(out["makespan"])
    wall = (time.time() - t0) / n_rep
    traces_per_sec = len(seeds) / wall
    jct = np.asarray(out["jct"])
    fin = np.asarray(out["finished"])
    fluid_avg = float(np.mean([jct[i][fin[i]].mean() for i in range(len(seeds))]))

    t0 = time.time()
    ev = run_scenario_event(scns[0], comm="ada")
    ev_wall = time.time() - t0

    rack = get_scenario("rack_locality", seed=1)
    plain = run_scenario_event(rack, comm="ada", placement="lwf")
    aware = run_scenario_event(rack, comm="ada", placement="lwf_rack")
    speedup = plain.makespan / aware.makespan

    emit(
        "topology/fluid_batched",
        wall * 1e6,
        f"traces_per_sec={traces_per_sec:.2f};avg_jct={fluid_avg:.1f};"
        f"n_seeds={len(seeds)}",
    )
    emit(
        "topology/event_oversub",
        ev_wall * 1e6,
        f"avg_jct={ev.avg_jct():.1f};finished={len(ev.jct)}",
    )
    emit("topology/rack_aware_speedup", 0.0, f"makespan_ratio={speedup:.2f}")

    path = os.environ.get("REPRO_BENCH_TOPOLOGY_JSON", "BENCH_topology.json")
    with open(path, "w") as f:
        json.dump(
            {
                "provenance": provenance(),
                "scenario": "oversub_fabric",
                "full": full,
                "n_seeds": len(seeds),
                "n_jobs": scns[0].n_jobs,
                "fluid_traces_per_sec": traces_per_sec,
                "fluid_wall_s_per_batch": wall,
                "fluid_avg_jct": fluid_avg,
                "event_avg_jct": ev.avg_jct(),
                "event_wall_s": ev_wall,
                "rack_aware_makespan_speedup": speedup,
            },
            f,
            indent=2,
        )
        f.write("\n")


# ---------------------------------------------------------------------------
# WFBP layer-granular communication subsystem (repro.workloads + fusion)
# ---------------------------------------------------------------------------


def bench_wfbp(full: bool) -> None:
    """The fusion threshold x policy grid on the event backend (the
    acceptance cell: finite fusion vs 'all' vs 'none' under Ada-SRSF), the
    model_zoo cell on both backends, and the fluid batched throughput over
    bucketed traces; key numbers persist to ``BENCH_wfbp.json`` (path
    override: ``REPRO_BENCH_WFBP_JSON``) for nightly trend tracking."""
    import dataclasses as _dc

    import numpy as np

    from repro.core.jaxsim import (
        simulate_traces_batched,
        stack_traces,
        trace_from_jobs,
    )
    from repro.scenarios import QUICK_OVERRIDES, get_scenario
    from repro.scenarios.sweep import fluid_config, run_scenario_event

    # fusion threshold x policy grid on the regression cell
    base = get_scenario("fusion_sweep", seed=1,
                        base_iters=80 if full else 40)
    grid: Dict[str, Dict[str, float]] = {}
    for fusion in ("all", "none", 16e6, 32e6, 128e6):
        tag = fusion if isinstance(fusion, str) else f"{int(fusion/1e6)}MB"
        scn = _dc.replace(base, fusion=fusion)
        grid[tag] = {}
        for comm in ("ada", "srsf1", "srsf2"):
            t0 = time.time()
            res = run_scenario_event(scn, comm=comm)
            dt = (time.time() - t0) * 1e6
            grid[tag][comm] = res.avg_jct()
            emit(
                f"wfbp/fusion={tag}/{comm}",
                dt,
                f"avg_jct={res.avg_jct():.2f};makespan={res.makespan:.2f};"
                f"contended={res.comm_started_contended};finished={len(res.jct)}",
            )
    finite_vs_all = grid["all"]["ada"] / grid["32MB"]["ada"]
    finite_vs_none = grid["none"]["ada"] / grid["32MB"]["ada"]
    emit("wfbp/finite_vs_all", 0.0, f"speedup={finite_vs_all:.3f}")
    emit("wfbp/finite_vs_none", 0.0, f"speedup={finite_vs_none:.3f}")

    # model_zoo on the event backend + fluid batched throughput
    overrides = {} if full else QUICK_OVERRIDES["model_zoo"]
    seeds = list(range(4))
    scns = [get_scenario("model_zoo", seed=s, **overrides) for s in seeds]
    t0 = time.time()
    ev = run_scenario_event(scns[0], comm="ada")
    ev_wall = time.time() - t0
    emit(
        "wfbp/event_model_zoo",
        ev_wall * 1e6,
        f"avg_jct={ev.avg_jct():.1f};finished={len(ev.jct)}",
    )
    cfg = fluid_config(scns[0], comm="ada", dt=0.01)
    batch = stack_traces(
        [trace_from_jobs(s.job_list(), fusion=s.fusion) for s in scns]
    )
    np.asarray(simulate_traces_batched(batch, cfg)["makespan"])  # compile
    n_rep = 3
    t0 = time.time()
    for _ in range(n_rep):
        out = simulate_traces_batched(batch, cfg)
        np.asarray(out["makespan"])
    wall = (time.time() - t0) / n_rep
    traces_per_sec = len(seeds) / wall
    jct = np.asarray(out["jct"])
    fin = np.asarray(out["finished"])
    fluid_avg = float(np.mean([jct[i][fin[i]].mean() for i in range(len(seeds))]))
    emit(
        "wfbp/fluid_batched",
        wall * 1e6,
        f"traces_per_sec={traces_per_sec:.2f};avg_jct={fluid_avg:.1f};"
        f"n_seeds={len(seeds)};buckets={int(batch['bucket_bytes'].shape[-1])}",
    )

    path = os.environ.get("REPRO_BENCH_WFBP_JSON", "BENCH_wfbp.json")
    with open(path, "w") as f:
        json.dump(
            {
                "provenance": provenance(),
                "full": full,
                "fusion_grid_avg_jct": grid,
                "finite_vs_all_speedup": finite_vs_all,
                "finite_vs_none_speedup": finite_vs_none,
                "model_zoo_event_avg_jct": ev.avg_jct(),
                "model_zoo_event_wall_s": ev_wall,
                "model_zoo_fluid_avg_jct": fluid_avg,
                "fluid_traces_per_sec": traces_per_sec,
                "n_seeds": len(seeds),
                "n_jobs": scns[0].n_jobs,
            },
            f,
            indent=2,
        )
        f.write("\n")


# ---------------------------------------------------------------------------
# Engine/policy split: events/sec + preemptive-vs-static avg JCT
# ---------------------------------------------------------------------------

#: Events/sec of the pre-refactor monolithic ClusterSimulator, measured at
#: the last pre-split commit (PR 4 HEAD) on the quick paper cell (seed 0,
#: n_jobs=40, iters 100-600, comm=ada, lwf, fuse_fb on, single CPU) — the
#: same cell bench_engine times below.  Absolute events/sec is
#: machine-dependent; the nightly artifact tracks the *trend* of the
#: refactored engine and this constant anchors the refactor-time ratio
#: (also recorded in tests/data/engine_regression_baseline.json).
PRE_REFACTOR_EVENTS_PER_SEC = 41984.0


def stream_trace(n_jobs: int, seed: int = 0, mean_gap: float = 0.05,
                 min_iters: int = 3, max_iters: int = 8):
    """Streaming-arrival stress workload: ``n_jobs`` small mixed-size jobs
    with exponential inter-arrival gaps, sized so a 16x4 cluster stays
    moderately loaded and the calendar drains as it fills (rather than the
    paper trace's burst of long jobs).  Shared by the ``--only engine``
    stress cell and the tier-1 linearity smoke test."""
    import numpy as np

    from repro.core.cluster import TABLE_III, JobSpec

    rng = np.random.default_rng(seed)
    models = ("resnet50", "vgg16", "inception_v3", "lstm_ptb")
    arrivals = np.cumsum(rng.exponential(mean_gap, n_jobs))
    return [
        JobSpec(
            j,
            float(arrivals[j]),
            int(rng.choice((1, 1, 2, 4))),
            int(rng.integers(min_iters, max_iters + 1)),
            TABLE_III[models[int(rng.integers(len(models)))]],
        )
        for j in range(n_jobs)
    ]


def bench_engine(
    full: bool, n_jobs: int = None, trace_source: str = "synth"
) -> None:
    """Throughput of the refactored event engine (events/sec on the quick
    paper cell, vs the recorded pre-refactor baseline), the 10k-job
    streaming-arrival stress cell (events/sec + peak calendar size + the
    per-event phase breakdown), the streaming TraceSource replay cell
    (``n_jobs`` lazy arrivals — 100k nightly — with windowed steady-state
    metrics), plus the preemptive-vs-static and elastic-vs-static avg-JCT
    cells on their regression seeds; persists ``BENCH_engine.json`` (path
    override: ``REPRO_BENCH_ENGINE_JSON``) for nightly trend tracking.

    ``n_jobs`` sizes the replay cell (CLI ``--n-jobs``; default 20k quick /
    100k with ``--full``); ``trace_source`` picks its arrival feed (CLI
    ``--trace-source``: 'synth', 'philly', 'alibaba', or
    'csv:<dialect>:<path>').  ``REPRO_REPLAY_WINDOWS_CSV=<path>`` also
    dumps the replay's full 60 s-window timeline as CSV (the weekly 1M
    replay's CI artifact)."""
    from repro.scenarios import (
        QUICK_OVERRIDES,
        get_scenario,
        trace_source_from_spec,
    )
    from repro.scenarios import metrics as metrics_mod
    from repro.scenarios.sweep import run_scenario_event

    overrides = {} if full else QUICK_OVERRIDES["paper"]
    scn = get_scenario("paper", seed=0, **overrides)
    run_scenario_event(scn, comm="ada")  # warm caches
    n_rep = 3
    t0 = time.time()
    for _ in range(n_rep):
        res = run_scenario_event(scn, comm="ada")
    wall = (time.time() - t0) / n_rep
    eps = res.events_processed / wall
    emit(
        "engine/events_per_sec",
        wall * 1e6,
        f"events_per_sec={eps:.0f};events={res.events_processed};"
        f"vs_pre_refactor={eps / PRE_REFACTOR_EVENTS_PER_SEC:.3f}",
    )

    # 10k-job streaming-arrival stress cell: online arrivals at ~20 jobs/s
    # against a 16x2 cluster, list mode — the calendar holds every future
    # arrival up front, so peak size ~ n_jobs + O(cluster); events/sec is
    # the engine-scalability headline the nightly run trends.  Profiling is
    # on: 4 perf_counter reads per ~100us event are noise, and the phase
    # split (gating / dispatch / comm-advance / gpu-schedule) is what makes
    # a throughput regression attributable.
    stress_n = 10_000
    jobs = stream_trace(stress_n, seed=0)
    t0 = time.time()
    stress = simulate(jobs, placement="lwf", comm="ada",
                      n_servers=16, gpus_per_server=2, profile_phases=True)
    stress_wall = time.time() - t0
    stress_eps = stress.events_processed / stress_wall
    phases = stress.phase_seconds or {}
    emit(
        "engine/stress_10k_stream",
        stress_wall * 1e6,
        f"events_per_sec={stress_eps:.0f};events={stress.events_processed};"
        f"peak_calendar={stress.peak_calendar};finished={len(stress.jct)};"
        + ";".join(f"phase_{k}={v:.2f}" for k, v in sorted(phases.items())),
    )

    # Streaming TraceSource replay cell: the same engine consuming a lazy
    # arrival feed — the calendar stays O(live jobs + cluster) however long
    # the trace is, and the windowed steady-state metrics (sustained
    # goodput, p99 JCT, queueing delay over a sliding horizon) replace
    # whole-run averages that a 100k-job stream would wash out.
    replay_n = n_jobs if n_jobs is not None else (100_000 if full else 20_000)
    replay_src = trace_source_from_spec(trace_source, n_jobs=replay_n, seed=0)
    t0 = time.time()
    replay = simulate(replay_src, placement="lwf", comm="ada",
                      n_servers=16, gpus_per_server=2)
    replay_wall = time.time() - t0
    replay_eps = replay.events_processed / replay_wall
    replay_ss = metrics_mod.replay_summary(replay, window_s=60.0)
    windows_csv = os.environ.get("REPRO_REPLAY_WINDOWS_CSV")
    if windows_csv:
        # full per-window timeline (the summary above collapses it to one
        # steady-state row) — the weekly 1M replay uploads this as a CI
        # artifact so a queueing/goodput drift is inspectable over time
        import csv as _csv

        rows = replay.windowed(60.0)
        with open(windows_csv, "w", newline="") as wf:
            writer = _csv.DictWriter(
                wf, fieldnames=list(rows[0]) if rows else ["t0", "t1"]
            )
            writer.writeheader()
            writer.writerows(rows)
    emit(
        f"engine/trace_replay_{trace_source}",
        replay_wall * 1e6,
        f"events_per_sec={replay_eps:.0f};n_jobs={replay_n};"
        f"events={replay.events_processed};"
        f"peak_calendar={replay.peak_calendar};finished={len(replay.jct)};"
        f"sustained_goodput={replay_ss['sustained_goodput']:.1f};"
        f"p99_jct={replay_ss['p99_jct']:.2f}",
    )

    pre_scn = get_scenario("preemption_gain", seed=2)
    t0 = time.time()
    static = run_scenario_event(pre_scn, comm="ada")
    pre = run_scenario_event(pre_scn, comm="ada", sched="preemptive_srsf")
    pre_wall = time.time() - t0
    emit(
        "engine/preemptive_vs_static",
        pre_wall * 1e6,
        f"static_avg_jct={static.avg_jct():.2f};"
        f"preemptive_avg_jct={pre.avg_jct():.2f};"
        f"speedup={static.avg_jct() / pre.avg_jct():.3f};"
        f"preemptions={pre.preemptions}",
    )

    el_scn = get_scenario("elastic_surge", seed=1)
    el_static = run_scenario_event(el_scn, comm="ada")
    el = run_scenario_event(el_scn, comm="ada", sched="elastic")
    emit(
        "engine/elastic_vs_static",
        0.0,
        f"static_avg_jct={el_static.avg_jct():.2f};"
        f"elastic_avg_jct={el.avg_jct():.2f};"
        f"speedup={el_static.avg_jct() / el.avg_jct():.3f};resizes={el.resizes}",
    )

    path = os.environ.get("REPRO_BENCH_ENGINE_JSON", "BENCH_engine.json")
    with open(path, "w") as f:
        json.dump(
            {
                "provenance": provenance(),
                "full": full,
                "events_per_sec": eps,
                "events_processed": res.events_processed,
                "pre_refactor_events_per_sec": PRE_REFACTOR_EVENTS_PER_SEC,
                "vs_pre_refactor": eps / PRE_REFACTOR_EVENTS_PER_SEC,
                "stress_n_jobs": stress_n,
                "stress_events_per_sec": stress_eps,
                "stress_events_processed": stress.events_processed,
                "stress_peak_calendar": stress.peak_calendar,
                "stress_finished": len(stress.jct),
                "stress_phase_seconds": phases,
                "replay_trace_source": trace_source,
                "replay_n_jobs": replay_n,
                "replay_events_per_sec": replay_eps,
                "replay_events_processed": replay.events_processed,
                "replay_peak_calendar": replay.peak_calendar,
                "replay_finished": len(replay.jct),
                "replay_wall_s": replay_wall,
                "replay_steady_state": replay_ss,
                "preemption_gain_seed": 2,
                "static_avg_jct": static.avg_jct(),
                "preemptive_avg_jct": pre.avg_jct(),
                "preemptive_speedup": static.avg_jct() / pre.avg_jct(),
                "preemptions": pre.preemptions,
                "elastic_surge_seed": 1,
                "elastic_static_avg_jct": el_static.avg_jct(),
                "elastic_avg_jct": el.avg_jct(),
                "elastic_speedup": el_static.avg_jct() / el.avg_jct(),
                "resizes": el.resizes,
            },
            f,
            indent=2,
        )
        f.write("\n")


def obs_overhead_paired(
    run_off, run_on, rounds: int = 4
) -> Tuple[float, float, float]:
    """Fractional slowdown of ``run_on`` over ``run_off`` from
    order-alternated paired CPU-time rounds, as a ratio of total times —
    the estimator the slow-marked guard test shares.  Wall-clock
    min-of-N is hopeless for a <3 % signal on a noisy shared host:
    ``process_time`` excludes scheduler preemption and summing over
    alternated pairs cancels drift.  Returns (overhead_frac, t_off,
    t_on)."""
    t_off = t_on = 0.0
    for i in range(rounds):
        pair = (run_off, run_on) if i % 2 == 0 else (run_on, run_off)
        for fn in pair:
            t0 = time.process_time()
            fn()
            dt = time.process_time() - t0
            if fn is run_off:
                t_off += dt
            else:
                t_on += dt
    return (t_on / t_off) - 1.0, t_off, t_on


def bench_obs(full: bool) -> None:
    """Observability overhead cells: ``observe=None`` vs
    ``ObsConfig.full()`` (all four channels armed).

    Two cells, deliberately opposite regimes:

    * ``paper`` quick — the events/sec microbenchmark (~10 us/event, ~2
      obs records per event).  Upper bound: every record's cost is
      visible against the tiny per-event baseline.
    * preemptive streaming replay — the engine's feature-complete mode
      (preemptive SRSF + gating + WFBP over streaming arrivals), where
      scheduling work dominates the event loop.  This is the <3 %
      guard cell (mirrored by the slow-marked test in
      ``tests/test_obs.py``).

    The off-path must be free (the hooks are never entered).  Persists
    ``BENCH_obs.json`` (path override: ``REPRO_BENCH_OBS_JSON``)."""
    from repro.obs import ObsConfig
    from repro.scenarios import QUICK_OVERRIDES, get_scenario
    from repro.scenarios.sweep import run_scenario_event

    overrides = {} if full else QUICK_OVERRIDES["paper"]
    scn = get_scenario("paper", seed=0, **overrides)
    cfg = ObsConfig.full()
    run_scenario_event(scn, comm="ada")  # warm caches

    res_off = run_scenario_event(scn, comm="ada")
    res_on = run_scenario_event(scn, comm="ada", observe=cfg)
    assert res_on.jct == res_off.jct, "observability changed the simulation"
    paper_ov, t_off, _ = obs_overhead_paired(
        lambda: run_scenario_event(scn, comm="ada"),
        lambda: run_scenario_event(scn, comm="ada", observe=cfg),
    )
    eps_off = res_off.events_processed * 4 / t_off
    obs = res_on.obs
    emit(
        "obs/overhead_paper",
        0.0,
        f"events_per_sec_off={eps_off:.0f};overhead_frac={paper_ov:.4f};"
        f"decomposed={len(obs.decomp)};audit={len(obs.audit)};"
        f"spans={len(obs.spans)}",
    )

    guard_n = 800 if full else 400
    jobs = stream_trace(guard_n, seed=0)
    guard_kw = dict(
        placement="lwf", comm="ada", n_servers=16, gpus_per_server=2,
        sched="preemptive_srsf",
    )
    g_off = simulate(jobs, **guard_kw)
    g_on = simulate(jobs, **guard_kw, observe=cfg)
    assert g_on.jct == g_off.jct, "observability changed the guard cell"
    guard_ov, g_t_off, _ = obs_overhead_paired(
        lambda: simulate(jobs, **guard_kw),
        lambda: simulate(jobs, **guard_kw, observe=cfg),
    )
    emit(
        "obs/overhead_guard",
        0.0,
        f"n_jobs={guard_n};events={g_off.events_processed};"
        f"overhead_frac={guard_ov:.4f};budget=0.03",
    )
    path = os.environ.get("REPRO_BENCH_OBS_JSON", "BENCH_obs.json")
    with open(path, "w") as f:
        json.dump(
            {
                "provenance": provenance(),
                "full": full,
                "scenario": "paper",
                "obs_off_events_per_sec": eps_off,
                "obs_overhead_frac": paper_ov,
                "obs_guard_overhead_frac": guard_ov,
                "obs_guard_n_jobs": guard_n,
                "n_jobs_decomposed": len(obs.decomp),
                "mean_stretch_frac": obs.mean_stretch_frac(),
                "mean_gating_frac": obs.mean_gating_frac(),
                "audit_entries": len(obs.audit),
                "span_entries": len(obs.spans),
                "timeline_points": len(obs.timeline),
            },
            f,
            indent=2,
        )
        f.write("\n")


def export_traces(
    out_dir: str,
    names,
    comm: str = "ada",
    seed: int = 2,
    full: bool = False,
    sched: str = None,
) -> List[str]:
    """``--trace-out``: one fully-observed run per scenario, written as a
    Perfetto-loadable Chrome trace JSON plus the per-job JCT-decomposition
    CSV.  Returns the written paths."""
    from repro.obs import ObsConfig
    from repro.scenarios import QUICK_OVERRIDES, get_scenario
    from repro.scenarios.sweep import run_scenario_event

    os.makedirs(out_dir, exist_ok=True)
    written: List[str] = []
    for name in names:
        overrides = {} if full else QUICK_OVERRIDES.get(name, {})
        scn = get_scenario(name, seed=seed, **overrides)
        kw = {} if sched is None else {"sched": sched}
        res = run_scenario_event(
            scn, comm=comm, observe=ObsConfig.full(), **kw
        )
        tag = "" if sched is None else f"_{sched}"
        stem = os.path.join(out_dir, f"{name}_seed{seed}_{comm}{tag}")
        trace_path = stem + ".perfetto.json"
        res.obs.to_chrome_trace(trace_path)
        csv_path = stem + ".decomp.csv"
        with open(csv_path, "w") as f:
            f.write(res.obs.decomposition_csv())
        written += [trace_path, csv_path]
        print(
            f"trace-out,{name},seed={seed},comm={comm},"
            f"jobs={len(res.obs.decomp)},spans={len(res.obs.spans)},"
            f"files={trace_path};{csv_path}",
            flush=True,
        )
    return written


def bench_chaos(full: bool) -> None:
    """Fault-injection SLO grid: every ``chaos_*`` scenario under the
    static ada/srsf1/srsf2 schedulers plus ada under ``preemptive_srsf``,
    over multiple seeds.  Prints the full RunMetrics CSV (including the
    goodput / work_lost / p99_jct fault columns) and persists the
    per-cell means plus the per-seed recovery-storm ada/srsf2 ratios to
    ``BENCH_chaos.json`` (path override: ``REPRO_BENCH_CHAOS_JSON``).

    Every run is observed (``ObsConfig(decompose=True)`` — bit-exact with
    unobserved, locked in tests/test_obs.py) so the CSV's
    stretch_frac/gating_frac columns carry data, and each run asserts the
    conservation law: the engine's ``work_lost_samples`` fault counter
    must equal the decomposition's total lost samples."""
    from repro.obs import ObsConfig
    from repro.scenarios import get_scenario
    from repro.scenarios import metrics as metrics_mod
    from repro.scenarios.sweep import run_scenario_event

    scenarios = ("chaos_steady", "chaos_recovery_storm", "chaos_stragglers")
    seeds = (0, 1, 2, 3, 4) if full else (1, 3)
    grid = (
        ("ada", "static"),
        ("srsf1", "static"),
        ("srsf2", "static"),
        ("ada", "preemptive_srsf"),
    )
    records: List[metrics_mod.RunMetrics] = []
    by_cell: Dict[tuple, List[metrics_mod.RunMetrics]] = {}
    storm_ratio: Dict[int, float] = {}
    print(metrics_mod.RunMetrics.csv_header())
    for name in scenarios:
        for seed in seeds:
            scn = get_scenario(name, seed=seed)
            per_comm = {}
            for comm, sched in grid:
                t0 = time.time()
                res = run_scenario_event(
                    scn, comm=comm, sched=sched,
                    observe=ObsConfig(decompose=True),
                )
                assert res.obs.work_lost_total == res.work_lost_samples, (
                    f"{name}/{comm}/{sched} seed={seed}: decomposition lost "
                    f"{res.obs.work_lost_total} samples but the engine "
                    f"counted {res.work_lost_samples}"
                )
                m = metrics_mod.from_event_result(
                    res,
                    scenario=name,
                    seed=seed,
                    n_jobs=scn.n_jobs,
                    wall_s=time.time() - t0,
                )
                print(m.as_csv_row(), flush=True)
                records.append(m)
                by_cell.setdefault((name, comm, sched), []).append(m)
                if sched == "static":
                    per_comm[comm] = res.avg_jct()
            if name == "chaos_recovery_storm":
                storm_ratio[seed] = per_comm["ada"] / per_comm["srsf2"]
    for (name, comm, sched), ms in sorted(by_cell.items()):
        emit(
            f"chaos/{name}/{comm}/{sched}",
            sum(m.wall_s for m in ms) / len(ms) * 1e6,
            f"goodput={sum(m.goodput for m in ms) / len(ms):.1f};"
            f"work_lost={sum(m.work_lost for m in ms) / len(ms):.1f};"
            f"p99_jct={sum(m.p99_jct for m in ms) / len(ms):.2f};"
            f"faults={sum(m.faults for m in ms) / len(ms):.1f}",
        )
    mean_storm = sum(storm_ratio.values()) / len(storm_ratio)
    emit(
        "chaos/recovery_storm/ada_vs_srsf2",
        0.0,
        f"mean_ratio={mean_storm:.3f};"
        + ";".join(f"seed{s}={r:.3f}" for s, r in sorted(storm_ratio.items())),
    )
    path = os.environ.get("REPRO_BENCH_CHAOS_JSON", "BENCH_chaos.json")
    with open(path, "w") as f:
        json.dump(
            {
                "provenance": provenance(),
                "full": full,
                "seeds": list(seeds),
                "cells": {
                    f"{name}/{comm}/{sched}": {
                        "goodput_mean": sum(m.goodput for m in ms) / len(ms),
                        "work_lost_mean": sum(m.work_lost for m in ms) / len(ms),
                        "p99_jct_mean": sum(m.p99_jct for m in ms) / len(ms),
                        "avg_jct_mean": sum(m.avg_jct for m in ms) / len(ms),
                        "faults_mean": sum(m.faults for m in ms) / len(ms),
                        "cancelled": sum(m.cancelled for m in ms),
                        "censored": sum(m.censored for m in ms),
                    }
                    for (name, comm, sched), ms in sorted(by_cell.items())
                },
                "recovery_storm_ada_over_srsf2": {
                    str(s): r for s, r in sorted(storm_ratio.items())
                },
                "recovery_storm_ratio_mean": mean_storm,
            },
            f,
            indent=2,
        )
        f.write("\n")


# ---------------------------------------------------------------------------
# Roofline table (from the dry-run artifact)
# ---------------------------------------------------------------------------


def bench_roofline(full: bool) -> None:
    path = os.environ.get("REPRO_DRYRUN_JSON", "results/dryrun.json")
    if not os.path.exists(path):
        emit("roofline/missing", 0.0, f"run repro.launch.dryrun first ({path})")
        return
    with open(path) as f:
        data = json.load(f)
    for key, res in sorted(data.items()):
        if res.get("status") != "ok" or "|single|" not in key:
            continue
        arch, shape, _, _ = key.split("|")
        r = res["roofline"]
        dom_t = r[f"{r['dominant']}_s"]
        emit(
            f"roofline/{arch}/{shape}",
            dom_t * 1e6,
            f"dominant={r['dominant']};compute={r['compute_s']:.4f};"
            f"memory={r['memory_s']:.4f};collective={r['collective_s']:.4f};"
            f"useful_ratio={r['useful_flops_ratio']:.3f};hbm_frac={r['hbm_peak_frac']:.2f}",
        )


BENCHES: Dict[str, Callable[[bool], None]] = {
    "table1": bench_table1,
    "fig2a": bench_fig2a,
    "fig2b": bench_fig2b,
    "table4": bench_table4,
    "fig5": bench_fig5,
    "table5": bench_table5,
    "chunked": bench_chunked,
    "scenarios": bench_scenarios,
    "topology": bench_topology,
    "wfbp": bench_wfbp,
    "engine": bench_engine,
    "chaos": bench_chaos,
    "obs": bench_obs,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale 160-job trace")
    ap.add_argument("--only", nargs="+", choices=list(BENCHES), default=None)
    ap.add_argument(
        "--scenario",
        nargs="+",
        default=None,
        metavar="NAME",
        help="run the scenario sweep instead of the table benches "
        "('all' or names from repro.scenarios)",
    )
    ap.add_argument(
        "--policy",
        nargs="+",
        default=["ada", "srsf1", "srsf2"],
        help="comm policies for --scenario (ada/adadual, srsfN, kwayK — "
        "the fluid backend supports ada, srsf1-3, kway2/kway3)",
    )
    ap.add_argument(
        "--placement",
        nargs="+",
        default=["lwf"],
        choices=["rand", "ff", "ls", "lwf", "lwf_rack"],
        help="placement policies for --scenario (fluid maps lwf->consolidate,"
        " ff->first_fit, ls->least_loaded, rand->random, lwf_rack->rack_pack"
        " gang modes)",
    )
    ap.add_argument(
        "--backend",
        default="event",
        choices=["event", "fluid"],
        help="simulator backend for --scenario",
    )
    ap.add_argument("--seeds", nargs="+", type=int, default=[0])
    ap.add_argument(
        "--kappa",
        nargs="+",
        type=int,
        default=[1],
        help="LWF consolidation thresholds for --scenario; several values "
        "run the whole matrix once per kappa (the placement column carries "
        "the kappa, e.g. LWF_RACK-4)",
    )
    ap.add_argument(
        "--sched",
        default=None,
        choices=["static", "preemptive_srsf", "elastic"],
        help="job scheduling policy override for --scenario (event backend "
        "only; default: each scenario's own sched field, normally static)",
    )
    ap.add_argument(
        "--bw-aware-srsf",
        action="store_true",
        help="enable the bandwidth-aware SRSF remaining-service estimate "
        "for --scenario (event backend only; default: paper-faithful "
        "nominal estimate)",
    )
    ap.add_argument(
        "--ci",
        action="store_true",
        help="with --scenario: aggregate seeds into mean +/- std CellCI rows"
        " (fluid backend runs all seeds of a cell in one vmapped launch)",
    )
    ap.add_argument(
        "--processes",
        type=int,
        default=None,
        help="multiprocessing fan-out for --scenario (event backend)",
    )
    ap.add_argument(
        "--n-jobs",
        type=int,
        default=None,
        help="job count of the --only engine streaming replay cell "
        "(default: 20000, or 100000 with --full)",
    )
    ap.add_argument(
        "--trace-source",
        default="synth",
        help="arrival feed of the --only engine replay cell: 'synth', "
        "'philly', 'alibaba' (bundled samples), or 'csv:<dialect>:<path>'",
    )
    ap.add_argument(
        "--obs",
        action="store_true",
        help="with --scenario (event backend): arm the JCT decomposition "
        "so the stretch_frac/gating_frac CSV columns carry data",
    )
    ap.add_argument(
        "--trace-out",
        default=None,
        metavar="DIR",
        help="export one fully-observed run per scenario (--scenario names, "
        "default: paper chaos_recovery_storm fusion_sweep) as Perfetto "
        "trace JSON + JCT-decomposition CSV into DIR, then exit; "
        "--policy/--seeds pick the (single) comm policy and seed",
    )
    args = ap.parse_args()
    use_compile_cache()
    if args.trace_out:
        export_traces(
            args.trace_out,
            args.scenario or ["paper", "chaos_recovery_storm", "fusion_sweep"],
            comm=args.policy[0] if args.policy else "ada",
            seed=args.seeds[0],
            full=args.full,
            sched=args.sched,
        )
        return
    if args.scenario:
        _scenario_sweep(
            args.scenario,
            args.policy,
            args.placement,
            args.seeds,
            args.backend,
            args.processes,
            args.full,
            ci=args.ci,
            kappas=args.kappa,
            sched=args.sched,
            bw_aware_srsf=args.bw_aware_srsf,
            obs=args.obs,
        )
        return
    print("name,us_per_call,derived")
    names = args.only or list(BENCHES)
    for name in names:
        if name == "engine":
            bench_engine(
                args.full, n_jobs=args.n_jobs, trace_source=args.trace_source
            )
        else:
            BENCHES[name](args.full)


if __name__ == "__main__":
    main()
