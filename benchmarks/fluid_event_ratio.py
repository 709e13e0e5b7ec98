"""Fluid-vs-event average-JCT ratio of one scenario, per seed and
iteration range.

For each iteration range and seed it prints the event engine's average
JCT and the fluid backend's at three resolutions: the default fast path
(``dt``, next-event skip on), skip off, and a five times finer ``dt``.
The two witnesses change only how finely the fluid model is integrated,
so a ratio they share is the fluid model's, not its integrator's.  The
default fluid batch runs twice and must give identical results.  Each
range ends with the seeds whose ratio falls outside
``sweep.FLUID_EVENT_RATIO``.

Usage (from the root of the checkout; any backend, CPU is enough):
    JAX_PLATFORMS=cpu python3 benchmarks/fluid_event_ratio.py \\
        --iters 83 500 --iters 100 600 --seeds 0 1 2 3 4 5 6 7
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scenario", default="paper")
    ap.add_argument("--comm", default="ada")
    ap.add_argument("--placement", default="lwf")
    ap.add_argument("--iters", type=int, nargs=2, action="append",
                    metavar=("MIN", "MAX"), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=list(range(8)))
    ap.add_argument("--dt", type=float, default=0.05)
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    from repro.scenarios import get_scenario, monte_carlo_fluid, run_scenario_event
    from repro.scenarios.sweep import FLUID_EVENT_RATIO

    use_compile_cache()
    kw = dict(comm=args.comm, placement=args.placement)
    for lo, hi in args.iters:
        over = dict(min_iters=lo, max_iters=hi)

        def fluid(dt=args.dt, **fast):
            recs = monte_carlo_fluid(args.scenario, args.seeds, overrides=over,
                                     dt=dt, **kw, **fast)
            return [r.avg_jct for r in recs]

        base = fluid()
        if fluid() != base:
            raise SystemExit(f"iterations {lo}-{hi}: repeated fluid run differs")
        no_skip = fluid(skip=False)
        fine = fluid(dt=args.dt / 5)
        print(f"{args.scenario}/{args.comm}/{args.placement} iterations "
              f"{lo}-{hi}: seed, event avg JCT, fluid avg JCT and ratio at "
              f"dt {args.dt:g} (repeat identical), skip off, dt {args.dt / 5:g}",
              flush=True)
        outside = []
        for seed, fl, ns, fi in zip(args.seeds, base, no_skip, fine):
            ev = run_scenario_event(
                get_scenario(args.scenario, seed=seed, **over),
                placement=args.placement, comm=args.comm).avg_jct()
            ratios = [x / ev for x in (fl, ns, fi)]
            if not 1 / FLUID_EVENT_RATIO <= ratios[0] <= FLUID_EVENT_RATIO:
                outside.append(seed)
            print(f"  seed {seed}: event {ev!r}; fluid {fl!r} ({ratios[0]!r}), "
                  f"{ns!r} ({ratios[1]!r}), {fi!r} ({ratios[2]!r})", flush=True)
        print(f"  outside x{FLUID_EVENT_RATIO}: {len(outside)} of "
              f"{len(args.seeds)} seeds {outside}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
