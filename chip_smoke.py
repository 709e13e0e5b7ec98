#!/usr/bin/env python3
"""Smoke run of the fluid Monte-Carlo path on one TPU chip.

The paper's cluster (16 servers x 4 GPUs, 160 jobs per lane, Ada-SRSF
gating, LWF placement) is simulated for ``LANES`` seeded lanes through
``repro.scenarios.monte_carlo_fluid`` on the chip, at the published
trace's iterations per job.  Then:

(a) every job of every lane finished inside the horizon;
(b) ``CPU_LANES`` lanes rerun on the host's CPU device finish the same
    jobs as the chip's, and each lane's average JCT agrees within
    ``JCT_REL_TOL``;
(c) the event engine on ``EVENT_SEEDS`` seeds agrees with the fluid
    average JCT within ``FLUID_EVENT_RATIO``.

Timings, rollouts per second and chunk counts are printed for
information; they are not benchmark metrics.  Any failure exits non-zero.
Without a TPU the script exits non-zero before running anything.  The
last line of a passing run is one JSON object naming the device.

Usage (from the root of the checkout):
    python3 chip_smoke.py [--lanes N] [--iters MIN MAX]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SCENARIO, COMM, PLACEMENT = "paper", "ada", "lwf"
LANES = 1024
#: the published trace's iterations per job (paper Section V-A)
PUBLISHED_ITERS = (1000, 6000)
DT = 0.05
#: bound on |avg JCT difference| / avg JCT per lane between two float32
#: evaluations of the same rollout (chip vs host CPU)
JCT_REL_TOL = 0.02
CPU_LANES = 4
EVENT_SEEDS = 2


def fail(msg: str) -> None:
    sys.exit(f"FAIL: {msg}")


def check_all_finished(label: str, recs, n_jobs: int) -> None:
    """Check (a): every job of every lane finished before the horizon
    cap (``monte_carlo_fluid`` already raises on a capped lane)."""
    from repro.core.jaxsim import JaxSimConfig

    horizon = JaxSimConfig.max_steps * DT
    bad = [r.seed for r in recs
           if r.n_finished != n_jobs or not r.makespan < horizon]
    if bad:
        fail(f"{label}: {len(bad)} lanes left jobs unfinished or reached "
             f"the {horizon:.0f} s horizon (seeds {bad[:8]})")
    print(f"check (a) {label}: all {n_jobs} jobs finished in each of "
          f"{len(recs)} lanes; max makespan "
          f"{max(r.makespan for r in recs)!r} s < horizon {horizon!r} s")


def check_parity(label: str, got, want) -> None:
    """Check (b): same finished jobs (all of them, after (a)) and
    per-lane average JCT within JCT_REL_TOL."""
    worst, worst_seed = 0.0, None
    for g, w in zip(got, want, strict=True):
        if g.seed != w.seed or g.n_finished != w.n_finished:
            fail(f"{label}: seed {g.seed}/{w.seed} finished "
                 f"{g.n_finished} vs {w.n_finished} jobs")
        rel = abs(g.avg_jct - w.avg_jct) / w.avg_jct
        if rel >= worst:
            worst, worst_seed = rel, g.seed
    n_same = sum(g.avg_jct == w.avg_jct for g, w in zip(got, want))
    print(f"check {label}: {len(got)} lanes, finished masks identical; "
          f"avg JCT bit-identical in {n_same} lanes; max relative "
          f"difference {worst!r} (seed {worst_seed}), bound {JCT_REL_TOL}")
    if worst > JCT_REL_TOL:
        fail(f"{label}: avg JCT differs by {worst!r} > {JCT_REL_TOL}")


def run_fluid(label: str, seeds, overrides, *, warm: bool):
    """Run the Monte-Carlo entry point (twice when ``warm``); print the
    informational times.  Returns the records of the last call."""
    from repro.scenarios import monte_carlo_fluid

    def call():
        t0 = time.perf_counter()
        recs = monte_carlo_fluid(
            SCENARIO, seeds, comm=COMM, placement=PLACEMENT,
            overrides=overrides, dt=DT,
        )
        return recs, time.perf_counter() - t0

    recs, first = call()
    line = (f"{label}: {len(seeds)} lanes, first call {first!r} s "
            f"(compile included), chunks {recs[0].chunks}")
    if warm:
        recs, again = call()
        line += (f"; warm call {again!r} s, "
                 f"{len(seeds) / again!r} rollouts/s")
    print(line, flush=True)
    return recs


def main() -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lanes", type=int, default=LANES)
    ap.add_argument("--iters", type=int, nargs=2, default=PUBLISHED_ITERS,
                    metavar=("MIN", "MAX"))
    args = ap.parse_args()

    # check (b) needs the host's CPU device beside the chip
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("no TPU: this smoke run needs one", file=sys.stderr)
        return 1

    from repro.compile_cache import use_compile_cache
    from repro.core.jaxsim import JaxSimConfig
    from repro.scenarios import get_scenario, run_scenario_event
    from repro.scenarios.sweep import FLUID_EVENT_RATIO

    use_compile_cache()
    seeds = list(range(args.lanes))
    iters = tuple(args.iters)
    overrides = dict(min_iters=iters[0], max_iters=iters[1])
    n_jobs = get_scenario(SCENARIO, seed=0, **overrides).n_jobs
    cut = ("published range, no cut" if iters == PUBLISHED_ITERS else
           f"CUT from the published {PUBLISHED_ITERS[0]}-{PUBLISHED_ITERS[1]}")
    print(f"workload: {SCENARIO}/{COMM}/{PLACEMENT}, {n_jobs} jobs per lane, "
          f"seeds 0-{args.lanes - 1}, iterations {iters[0]}-{iters[1]} "
          f"({cut}), dt {DT} s, horizon {JaxSimConfig.max_steps * DT:.0f} s",
          flush=True)

    chip = run_fluid("chip", seeds, overrides, warm=True)
    stats = dev.memory_stats() or {}
    print(f"memory: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"of bytes_limit {stats.get('bytes_limit')}", flush=True)
    check_all_finished("chip", chip, n_jobs)

    cpu_seeds = seeds[:CPU_LANES]
    with jax.default_device(jax.devices("cpu")[0]):
        cpu = run_fluid("host cpu", cpu_seeds, overrides, warm=False)
    check_all_finished("host cpu", cpu, n_jobs)
    check_parity("(b) host cpu vs chip", cpu, chip[:CPU_LANES])

    for seed in range(EVENT_SEEDS):
        t0 = time.perf_counter()
        ev = run_scenario_event(get_scenario(SCENARIO, seed=seed, **overrides),
                                placement=PLACEMENT, comm=COMM)
        ev_avg, fl_avg = ev.avg_jct(), chip[seed].avg_jct
        print(f"event engine seed {seed}: {time.perf_counter() - t0!r} s, "
              f"{len(ev.jct)} jobs finished, avg JCT {ev_avg!r} s; "
              f"fluid (chip) {fl_avg!r} s, ratio {fl_avg / ev_avg!r}",
              flush=True)
        if len(ev.jct) != n_jobs:
            fail(f"event engine seed {seed} finished {len(ev.jct)} jobs")
        if not ev_avg / FLUID_EVENT_RATIO <= fl_avg <= ev_avg * FLUID_EVENT_RATIO:
            fail(f"(c) seed {seed}: fluid {fl_avg} vs event {ev_avg} outside "
                 f"x{FLUID_EVENT_RATIO}")
    print(f"check (c) fluid vs event: {EVENT_SEEDS} seeds within "
          f"x{FLUID_EVENT_RATIO}")
    print(f"total wall {time.perf_counter() - t_start!r} s")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
