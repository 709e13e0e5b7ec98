#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 perfbench/calibrate.py --workload paper.mc256 --seeds 1 2 3

For each seed, after the cell's warm-up: one query of the window through
the program, and the rollouts that a run of that seed would sample, run
by the configuration's plain reference (``workload.reference_of``) and by
two controls put in the program's place:

* ``bf16``: the reference with its continuous quantities (remaining phase
  time, phase lengths, drain fraction) in bfloat16, the precision below
  the configuration's float32;
* ``dt2``: the reference at twice the configuration's time step, the
  coarser resolution that would make a rollout about twice as cheap.

Prints one JSON line per seed with, for the program and for each
control, the numbers compared (``lanes_unfinished``, ``lanes_off``: the
rollouts whose finished jobs, average JCT or makespan differ from the
reference) and ``correct`` as the harness's own limits judge them, and
the seconds each took.  A control has to come out not correct on every
seed; the program correct.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from perfbench.lib import cell, check, workload  # noqa: E402


def controls(cfg: dict) -> dict:
    """Name -> (configuration, float type) of each control."""
    import jax.numpy as jnp

    return {
        "bf16": (cfg, jnp.bfloat16),
        "dt2": ({**cfg, "dt": 2 * cfg["dt"]}, jnp.float32),
    }


def judged(prog: list, ref: list, n_jobs: int) -> dict:
    """The numbers compared for ``prog`` (``(n_finished, avg_jct,
    makespan)`` per sampled rollout) and the harness's verdict on them."""
    values = {"lanes_unfinished": sum(p[0] != n_jobs for p in prog),
              "lanes_off": check.lanes_off(prog, ref)}
    checks, ok = check.judge(values)
    return {**values, "correct": ok}


def readings(program, cfg: dict, traffic: dict, seed: int,
             with_controls: bool = True) -> dict:
    """One seed's readings of the program and of each control."""
    seeds = cell.query_seeds(seed, 0, traffic["lanes"])
    t0 = time.perf_counter()
    recs = program.query(seeds)
    out = {"seed": seed, "query_s": time.perf_counter() - t0}
    query = cell.SimpleNamespace(seeds=seeds, recs=recs, seconds=0.0)
    picked = cell.sample([query], traffic["sample_lanes"], seed)
    lanes = [workload.job_arrays(workload.generate(cfg, s)) for s, _ in picked]
    prog = [(r.n_finished, r.avg_jct, r.makespan) for _, r in picked]
    simulate = workload.reference_of(cfg)
    t0 = time.perf_counter()
    ref = simulate(lanes, cfg)
    out["reference_s"] = time.perf_counter() - t0
    out["program"] = judged(prog, ref, cfg["n_jobs"])
    for name, (ccfg, ftype) in controls(cfg).items():
        if not with_controls:
            break
        t0 = time.perf_counter()
        ctl = simulate(lanes, ccfg, ftype=ftype)
        as_prog = [(*check.summarize(c["jct"], c["finished"]),
                    float(c["makespan"])) for c in ctl]
        out[name] = {**judged(as_prog, ref, cfg["n_jobs"]),
                     "seconds": time.perf_counter() - t0}
    out["lanes"] = len(lanes)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3,
                    help="run the controls on the first N seeds only")
    args = ap.parse_args()

    import jax

    spec = workload.load_json(CHECKOUT / "BENCHMARK.json")
    entry = next(w for w in spec["workloads"] if w["name"] == args.workload)
    cfg = workload.load_config(entry["config"])
    traffic = workload.load_traffic(entry["traffic"])
    if cell.accelerator(jax, entry["chips"], True) is None:
        return 2
    cell.set_up_jax(jax)
    program = cell.Program(cfg)
    counter = cell.CompileCounter(jax.monitoring)
    cell.warm_up(program, cfg, traffic, counter)
    counter.close()
    for k, seed in enumerate(args.seeds):
        print(json.dumps(readings(program, cfg, traffic, seed,
                                  with_controls=k < args.controls)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
