"""The workload guard: the program's generator has to draw the jobs that
the benchmark's own copy draws, and its scenario has to build the fabric
that the configuration states, or set-up stops."""

import dataclasses

import pytest

from perfbench.lib import workload
from repro.scenarios import get_scenario


def test_program_matches_own_generator():
    cfg = workload.load_config("paper")
    workload.check_program_jobs(cfg, [0, 7, 2**31 + 11], get_scenario)


def _shift_first_arrival(name, seed=0, **kw):
    scn = get_scenario(name, seed=seed, **kw)
    jobs = list(scn.jobs)
    jobs[0] = dataclasses.replace(jobs[0], arrival=jobs[0].arrival + 1.0)
    return dataclasses.replace(scn, jobs=tuple(jobs))


def _more_iterations(name, seed=0, **kw):
    kw["max_iters"] += 1
    return get_scenario(name, seed=seed, **kw)


def _other_bandwidth(name, seed=0, **kw):
    from repro.core.contention import ContentionParams

    scn = get_scenario(name, seed=seed, **kw)
    return dataclasses.replace(scn, params=ContentionParams(b=2 * scn.params.b))


@pytest.mark.parametrize(
    "changed", [_shift_first_arrival, _more_iterations, _other_bandwidth]
)
def test_changed_generator_fails(changed):
    cfg = workload.load_config("paper")
    with pytest.raises(RuntimeError, match="workload guard"):
        workload.check_program_jobs(cfg, [0, 1, 2], changed)


def fabric_config():
    return workload.load_json(workload.ROOT / "tests" / "data" / "fabric.json")


def test_program_builds_the_stated_fabric():
    workload.check_program_jobs(fabric_config(), [0, 2**31 + 11], get_scenario)


def _other_oversub(cfg):
    cfg["topology"] = {**cfg["topology"], "oversub": 2}


def _other_rack_size(cfg):
    cfg["topology"] = {**cfg["topology"], "servers_per_rack": 4}


def _nic_stated(cfg):
    cfg["topology"] = "nic"


@pytest.mark.parametrize("change", [_other_oversub, _other_rack_size, _nic_stated])
def test_fabric_the_program_does_not_build_fails(change):
    cfg = fabric_config()
    change(cfg)
    with pytest.raises(RuntimeError, match="workload guard"):
        workload.check_program_jobs(cfg, [0], get_scenario)
