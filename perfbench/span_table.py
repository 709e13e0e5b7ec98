#!/usr/bin/env python3
"""Where a traced query's host time and device idle time go, span by span.

    python3 perfbench/span_table.py --workload paper.mc256 --seed 7 --seed 8

Sets a cell up as ``run.py`` does (device check, compilation cache,
workload guard, warm-up), then traces the window's first query once for
each ``--seed``: the query that ``run.py --trace 1`` traces under that
seed.  For each query it prints one JSON line on standard output: the
query's length on the host clock and as the ``mc_query`` span, the
device's idle time in it, the driver's counters from the query's
records, the programs loaded while it ran, and per ``fluid.*`` program
span its count, total and self time and the idle time under it
(``lib/spans.py``).  The span table goes to standard error.  Without an
accelerator it exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from perfbench.lib import cell, spans, trace, workload  # noqa: E402

#: ``RunMetrics`` counters of the batched driver; a program without one
#: leaves it out of the line
COUNTERS = ("chunks", "lane_slots", "live_lane_slots", "compactions",
            "shapes")


def traced(program, jax, seeds: list, counter) -> tuple:
    """One query under the profiler: (line, span reduction)."""
    tdir = tempfile.mkdtemp(prefix="perfbench-spans-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        before = counter.count
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(cell.QUERY_SPAN):
                t0 = time.perf_counter()
                recs = program.query(seeds)
                took = time.perf_counter() - t0
        finally:
            jax.profiler.stop_trace()
        from jax.profiler import ProfileData

        (path,) = Path(tdir).rglob("*.xplane.pb")
        pd = ProfileData.from_file(str(path))
        red = spans.reduce(pd, cell.QUERY_SPAN)
        (a, b), = trace.query_thread(pd, cell.QUERY_SPAN)[0]
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    line = {
        "query_s": took, "span_s": (b - a) / 1e9, "idle_s": red.idle_s,
        "unattributed_idle_s": red.unattributed_idle_s,
        "programs_loaded": counter.count - before,
        "counters": {k: getattr(recs[0], k) for k in COUNTERS
                     if hasattr(recs[0], k)},
        "spans": {n: dataclasses.asdict(st) for n, st in red.table.items()},
    }
    return line, red


def trace_queries(entry: dict, cfg: dict, traffic: dict, seeds: list,
                  require_accelerator: bool = True):
    """The lines of the traced queries, or ``None`` where the machine
    cannot run the cell."""
    import jax

    devices = cell.accelerator(jax, entry["chips"], require_accelerator)
    if devices is None:
        return None
    cell.set_up_jax(jax)
    program = cell.Program(cfg)
    workload.check_program_jobs(
        cfg, cell.lane_seeds(seeds[0], cell.GUARD, 0, 3), program.get_scenario
    )
    counter = cell.CompileCounter(jax.monitoring)
    lines = []
    try:
        cell.warm_up(program, cfg, traffic, counter)
        for seed in seeds:
            line, red = traced(
                program, jax, cell.query_seeds(seed, 0, traffic["lanes"]),
                counter)
            lines.append({"seed": seed, **line})
            print(f"seed {seed}: query {line['query_s']:.3f} s, device idle "
                  f"{red.idle_s} s\n{spans.format_table(red)}",
                  file=sys.stderr)
    finally:
        counter.close()
    return lines


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args()

    spec = workload.load_json(CHECKOUT / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == args.workload),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    lines = trace_queries(entry, workload.load_config(entry["config"]),
                          workload.load_traffic(entry["traffic"]), args.seed)
    if lines is None:
        return 2
    for line in lines:
        print(json.dumps({"workload": args.workload, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
