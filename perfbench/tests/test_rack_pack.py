"""Rack-aware placement (``lwf_rack``) on a two-tier fabric
(``data/fabric.json``: 8 servers x 4 GPUs, racks of 2 servers behind 3x
oversubscribed uplinks): the program agrees bit for bit with
``references/rack_pack.py``, in its answers and in its uplink counters, a
reference with the rack-blind LWF rank in its place does not, and the
per-layer readers of the ``oversub`` cell read those counters, and nothing
on a NIC-only fabric."""

import types

import pytest

from perfbench.lib import cell, check, reference, workload
from perfbench.metrics import uplink_job_share, uplink_transfer_share
from perfbench.references import rack_pack

FABRIC = workload.ROOT / "tests" / "data" / "fabric.json"
SEEDS = [3, 17, 2**31 + 9, 4_000_000_001]
PAPER_SMALL = dict(n_jobs=48, min_iters=100, max_iters=300, horizon_s=60.0)


def fabric_config(**changes):
    return {**workload.load_json(FABRIC), **changes}


def lanes_of(cfg, seeds):
    return [workload.job_arrays(workload.generate(cfg, s)) for s in seeds]


def rows(recs):
    return [(r.n_finished, r.avg_jct, r.makespan) for r in recs]


def counts(recs):
    return [tuple(getattr(r, k) for k in rack_pack.COUNTERS) for r in recs]


@pytest.fixture(scope="module")
def rack_runs():
    """policy -> (the program's records under ``lwf_rack``, the rack_pack
    reference's results with its counters, the lanes, the configuration)."""
    out = {}
    for policy in ("ada", "srsf2"):
        cfg = fabric_config(policy=policy, placement="lwf_rack",
                            reference="rack_pack")
        lanes = lanes_of(cfg, SEEDS)
        out[policy] = (cell.Program(cfg).query(SEEDS),
                       rack_pack.simulate(lanes, cfg, counters=True), lanes, cfg)
    return out


@pytest.mark.parametrize("policy", ["ada", "srsf2"])
def test_rack_pack_matches_program(rack_runs, policy):
    recs, ref, _, cfg = rack_runs[policy]
    assert check.lanes_off(rows(recs), ref) == 0
    assert workload.reference_of(cfg) is rack_pack.simulate
    assert counts(recs) == [tuple(r[k] for k in rack_pack.COUNTERS) for r in ref]
    assert all(jobs > 0 and 0 < up <= ticks for jobs, up, ticks in counts(recs))


@pytest.mark.parametrize("policy", ["ada", "srsf2"])
def test_lwf_rank_in_rack_packs_place_is_off(rack_runs, policy):
    """The base reference's LWF rank, which ignores racks, in its place."""
    recs, _, lanes, cfg = rack_runs[policy]
    lwf = reference.simulate(lanes, {**cfg, "placement": "lwf"})
    assert check.lanes_off(rows(recs), lwf) >= 1


def test_uplink_readers_read_the_counters(rack_runs):
    recs, ref, _, _ = rack_runs["ada"]
    ctx = types.SimpleNamespace(records=[recs, None])  # a query that raised
    jobs = sum(r["uplink_jobs"] for r in ref)
    up = sum(r["uplink_transfer_ticks"] for r in ref)
    ticks = sum(r["transfer_ticks"] for r in ref)
    assert uplink_job_share.read(ctx) == 100.0 * jobs / (24 * len(SEEDS))
    assert uplink_transfer_share.read(ctx) == 100.0 * up / ticks


def test_uplink_readers_find_nothing_on_nics():
    """``paper``'s NIC-only fabric keeps no counter, and a program from
    before them has no such field: nothing to read in either."""
    cfg = {**workload.load_config("paper"), **PAPER_SMALL,
           "scenario_overrides": PAPER_SMALL}
    for records in ([cell.Program(cfg).query([5, 6])],
                    [[types.SimpleNamespace(n_jobs=48)]]):
        ctx = types.SimpleNamespace(records=records)
        assert uplink_job_share.read(ctx) is None
        assert uplink_transfer_share.read(ctx) is None
