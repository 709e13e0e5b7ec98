"""The controls of ``correct`` come out as not correct, and the program
as correct, at a size a CPU test run holds: the reference in bfloat16
(the precision below the configuration's float32) and at twice the
configuration's time step.  The chip readings at the cells' own sizes,
which the limits were set from, are in PERF.md
(``perfbench/calibrate.py``)."""

import pytest

from perfbench import calibrate
from perfbench.lib import cell, check, workload

SMALL = dict(n_jobs=48, min_iters=100, max_iters=300, horizon_s=60.0)
TRAFFIC = {"lanes": 8, "sample_lanes": 8}


@pytest.fixture(scope="module")
def readings():
    cfg = workload.load_config("paper")
    cfg.update(SMALL)
    cfg["scenario_overrides"] = dict(SMALL)
    program = cell.Program(cfg)
    return [calibrate.readings(program, cfg, TRAFFIC, seed)
            for seed in (11, 2**31 + 1, 4_000_000_000)]


def test_program_is_correct(readings):
    assert all(r["program"]["correct"] for r in readings), readings


@pytest.mark.parametrize("control", ["bf16", "dt2"])
def test_control_is_not_correct(readings, control):
    limit = workload.load_json(check.LIMITS)["lanes_off"]
    assert all(not r[control]["correct"] for r in readings), readings
    assert all(r[control]["lanes_off"] > limit for r in readings), readings
