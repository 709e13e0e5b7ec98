#!/usr/bin/env python3
"""Benchmark of the fluid simulator's Monte-Carlo path on the accelerator.

    python3 perfbench/run.py --workload paper.mc256 --seed 7 --seconds 30 --trace 0

Runs one cell of ``BENCHMARK.json`` (a configuration under a traffic mix)
on the machine it is started on and prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last the numbers compared for ``correct`` beside their limits.  Without an
accelerator, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from perfbench.lib import cell, workload  # noqa: E402


def cell_spec(spec: dict, name: str) -> tuple:
    """The workload entry and, per kind, the ``(name, unit)`` of each
    metric it reports."""
    try:
        entry = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json") from None
    metrics = {
        kind: [(m["name"], m["unit"]) for m in spec[kind]]
        for kind in ("end_to_end", "per_layer")
    }
    return entry, metrics


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    spec = workload.load_json(CHECKOUT / "BENCHMARK.json")
    entry, metrics = cell_spec(spec, args.workload)
    result = cell.measure(
        entry, workload.load_config(entry["config"]),
        workload.load_traffic(entry["traffic"]), metrics,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START,
    )
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
