"""The comparison that decides ``correct``.

Each rollout the window finished is summarised as (jobs finished, average
JCT, makespan), the program's way: JCTs are float32 ``finish - arrival``,
averaged by a left fold in job order.  The plain reference
(:mod:`perfbench.lib.reference`) reproduces the program bit for bit, so a
sampled rollout either matches it exactly or is off.  The limits are in
``perfbench/limits.json``; the readings they were set from are in PERF.md.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from perfbench.lib.workload import load_json

LIMITS = Path(__file__).resolve().parents[1] / "limits.json"


def summarize(jct, finished) -> tuple:
    """(jobs finished, average JCT) of one reference rollout."""
    done = np.asarray(jct)[np.asarray(finished)].tolist()
    total = 0.0
    for x in done:
        total += x
    return len(done), total / len(done) if done else float("nan")


def lanes_off(program: list, reference: list) -> int:
    """Rollouts whose jobs finished, average JCT or makespan differ from
    the reference.  ``program`` holds ``(n_finished, avg_jct, makespan)``,
    ``reference`` the reference's result dicts, lane for lane."""
    off = 0
    for (n_fin, avg, mks), ref in zip(program, reference, strict=True):
        r_fin, r_avg = summarize(ref["jct"], ref["finished"])
        if (n_fin, avg, mks) != (r_fin, r_avg, float(ref["makespan"])):
            off += 1
    return off


def judge(values: dict) -> tuple:
    """``({name: {"value", "limit"}}, correct)``: a number passes when it
    is at most its limit."""
    limits = load_json(LIMITS)
    checks = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return checks, ok
