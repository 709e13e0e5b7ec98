"""The workload guard on the benchmark's ``oversub`` configuration: the
program's ``oversub_fabric`` scenario builds the fabric that
``configs/oversub.json`` states, and a change to the stated rack size,
oversubscription or fabric kind stops set-up."""

import pytest

from perfbench.lib import workload
from repro.scenarios import get_scenario


def test_program_builds_the_oversub_fabric():
    cfg = workload.load_config("oversub")
    workload.check_program_jobs(cfg, [0, 7, 2**31 + 11], get_scenario)


def _other_oversub(cfg):
    cfg["topology"] = {**cfg["topology"], "oversub": 2}


def _other_rack_size(cfg):
    per_rack = cfg["topology"]["servers_per_rack"]
    cfg["topology"] = {**cfg["topology"], "servers_per_rack": 2 * per_rack}


def _nic_stated(cfg):
    cfg["topology"] = "nic"


@pytest.mark.parametrize("change", [_other_oversub, _other_rack_size, _nic_stated])
def test_oversub_fabric_the_program_does_not_build_fails(change):
    cfg = workload.load_config("oversub")
    change(cfg)
    with pytest.raises(RuntimeError, match="workload guard"):
        workload.check_program_jobs(cfg, [0], get_scenario)
