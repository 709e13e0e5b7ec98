"""A run whose timed path is broken underneath has to come out with
``correct`` false.  The run is driven whole (guard, warm-up, window,
sample, reference, limits) at a small size on the CPU, without the
harness's look for an accelerator; each fault is planted once the warm-up
is done, where the timed path would produce it.

The faults a cell of this benchmark can have: a step that leaves the
state as it was (only the clock moves), half of the batch left out with
its results taken from the other half, and an answer altered where it is
produced.  An exchange between chips does not exist on one chip.
"""

import time

import numpy as np
import pytest

from perfbench.lib import cell, workload
from repro.core import jaxsim

SMALL = dict(n_jobs=24, min_iters=10, max_iters=40, horizon_s=120.0)
TRAFFIC = {"lanes": 8, "sample_lanes": 8}
METRICS = {"end_to_end": [("rollouts_per_s", "rollouts/s"), ("setup_s", "s")],
           "per_layer": []}


def small_config():
    cfg = workload.load_config("paper")
    cfg.update(SMALL)
    cfg["scenario_overrides"] = dict(SMALL)
    return cfg


def frozen_step(chunk):
    def run(traces, state, cfg, max_ways, gated):
        return {**state, "i": state["i"] + cfg.chunk_steps}
    return run


def half_batch(simulate):
    def run(traces, cfg):
        n = traces["arrival"].shape[0]
        half = max(1, n // 2)
        out = simulate({k: v[:half] for k, v in traces.items()}, cfg)
        fill = np.arange(n) % half
        return {**out, **{k: np.asarray(out[k])[fill]
                          for k in ("jct", "finished", "makespan")}}
    return run


def altered_answer(drive):
    def run(traces, cfg, max_ways, gated):
        out = drive(traces, cfg, max_ways, gated)
        out["jct"][:, 0] += cfg.dt
        return out
    return run


FAULTS = {
    "frozen_step": ("_chunk_jit", frozen_step),
    "half_batch": ("simulate_traces_batched", half_batch),
    "altered_answer": ("_drive_batched", altered_answer),
}


def run_small(monkeypatch, fault=None):
    warm_up = cell.warm_up

    def warm_then_break(*args, **kw):
        loaded = warm_up(*args, **kw)
        if fault is not None:
            name, make = FAULTS[fault]
            monkeypatch.setattr(jaxsim, name, make(getattr(jaxsim, name)))
        return loaded

    monkeypatch.setattr(cell, "warm_up", warm_then_break)
    return cell.measure(
        {"chips": 1}, small_config(), TRAFFIC, METRICS, seed=2**31 + 3,
        seconds=0.5, trace=False, t_start=time.perf_counter(),
        require_accelerator=False,
    )


def test_sound_run_is_correct(monkeypatch):
    res = run_small(monkeypatch)
    assert res["correct"], res["checks"]
    assert res["checks"]["lanes_off"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(monkeypatch, fault):
    res = run_small(monkeypatch, fault)
    assert not res["correct"], res["checks"]
