"""Time of one fluid scan chunk (``jaxsim._chunk_jit``, ``chunk_steps``
ticks) per lane count.

The ``paper`` scenario at full size (160 jobs per lane) is stacked into
a batch; each line is the first call (compile included) and the warm
mean over ``--reps`` further chunks, timed on the host clock around
``block_until_ready``.  ``--cpu-lanes`` adds a row on the host's CPU
device.  These are per-layer numbers for the chunk-scan layer, not
end-to-end metrics.

Usage (from the root of the checkout):
    python3 benchmarks/chunk_time.py --lanes 8 128 1024
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scenario", default="paper")
    ap.add_argument("--lanes", type=int, nargs="+", default=[8, 128, 1024])
    ap.add_argument("--cpu-lanes", type=int, default=4,
                    help="lanes of the host-CPU row (0: none)")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()

    plats = os.environ.get("JAX_PLATFORMS", "")
    if args.cpu_lanes and plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax

    from repro.compile_cache import use_compile_cache
    from repro.core.jaxsim import (
        _chunk_jit, _init_jit, _policy_args, stack_traces, trace_from_jobs,
    )
    from repro.scenarios import get_scenario
    from repro.scenarios.sweep import fluid_config

    use_compile_cache()
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind}", flush=True)
    n = max(args.lanes + [args.cpu_lanes])
    scns = [get_scenario(args.scenario, seed=s) for s in range(n)]
    batch = stack_traces([trace_from_jobs(s.job_list(), fusion=s.fusion)
                          for s in scns])
    base = fluid_config(scns[0])

    def per_chunk(lanes: int, device) -> None:
        max_ways, gated, key = _policy_args(base)
        tr, max_ways, gated = jax.device_put(
            ({k: v[:lanes] for k, v in batch.items()}, max_ways, gated), device)
        with jax.default_device(device):
            st = _init_jit(tr, key)
            t0 = time.perf_counter()
            st = jax.block_until_ready(_chunk_jit(tr, st, key, max_ways, gated))
            first = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(args.reps):
                st = _chunk_jit(tr, st, key, max_ways, gated)
            jax.block_until_ready(st)
        warm = (time.perf_counter() - t0) / args.reps
        print(f"{device.platform} lanes {lanes}: first call "
              f"{first!r} s, warm {warm * 1e3!r} ms per {base.chunk_steps}-tick "
              "chunk", flush=True)

    for lanes in args.lanes:
        per_chunk(lanes, dev)
    if args.cpu_lanes:
        per_chunk(args.cpu_lanes, jax.devices("cpu")[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
