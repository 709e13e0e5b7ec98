"""Cross-backend differential harness: the exact event simulator
(``core/simulator.py``) vs the vectorized fluid simulator
(``core/jaxsim.py``).

The fluid backend is a documented approximation (gang-exclusive placement,
fixed dt, single admission per step), so agreement is *qualitative*:
completeness, bounded JCT/makespan ratios,
determinism, matching policy/placement orderings, and the no-contention
limit where both backends are exact.

Coverage (per the shared ``core/netmodel.py`` layer):

* every fluid-supported gating policy (``FLUID_POLICIES``: ada, srsf1-3,
  kway2/kway3) on the deterministic ``smoke`` scenario, the
  policy-differentiating ``contended_residue`` scenario, a downsized
  ``hetero_bandwidth`` cell with true per-server (not cluster-mean)
  bandwidth, and a downsized multi-tier ``oversub_fabric`` cell
  (``core/topology.py`` contention domains on both backends);
* the gang placement modes vs their event analogues (LWF-1 <= FF on a
  fragmentation-sensitive workload, RAND on smoke, and rack-aware
  lwf_rack/rack_pack <= plain LWF on ``rack_locality``, on both backends);
* the WFBP layer-granular cells: config-derived ``model_zoo`` profiles
  with finite tensor fusion and the ``fusion_sweep`` regression cell
  (per-bucket gating on the event side vs the static [jobs, buckets]
  chunked drain on the fluid side).

This harness is what caught the fluid gating self-deadlock (a waiting
all-reduce counted itself as an active transfer and never started under
ada/srsf1) — keep it green."""

import numpy as np
import pytest

from repro.core.cluster import TABLE_III, JobSpec
from repro.scenarios import (
    FLUID_POLICIES,
    get_scenario,
    run_scenario_event,
    run_scenario_fluid,
)
from repro.scenarios.registry import Scenario
from repro.scenarios.sweep import FLUID_EVENT_RATIO
from repro.core.contention import ContentionParams

DT = 0.02
#: fluid-vs-event tolerance on aggregate metrics (gang placement makes the
#: fluid backend pessimistic on shared-GPU scenarios)
RATIO = FLUID_EVENT_RATIO

#: Tightened tolerance for the WFBP fusion cells: with k-way gating now
#: *exact* on both backends (netmodel.kway_exact_start — the same closed
#: form the event integrator computes), the remaining gap is only the
#: fluid backend's non-overlap of bucket streams with backward compute
#: plus dt quantization.  Measured worst case across the fusion cells
#: (ada/srsf2/kway2/kway3 on fusion_sweep + model_zoo): 1.21.
FUSION_RATIO = 1.35

#: Downsized hetero_bandwidth cell: small enough for tier-1, large enough
#: that half the servers being 0.4x slow actually shapes the schedule.
#: (Re-smoke-sized in PR 5 from 16 jobs / 60-300 iters using the
#: --durations data: the 6-policy fluid matrices were the slowest
#: differential cells; the qualitative bounds hold unchanged.)
HETERO_KW = dict(seed=1, n_jobs=12, min_iters=50, max_iters=200)

#: Downsized oversub_fabric cell (same sizing): 16-server two-tier fabric,
#: racks of 4 behind 3x-oversubscribed uplinks.
OVERSUB_KW = dict(seed=1, n_jobs=12, min_iters=50, max_iters=200)


@pytest.fixture(scope="module")
def smoke():
    return get_scenario("smoke")


@pytest.fixture(scope="module")
def hetero():
    return get_scenario("hetero_bandwidth", **HETERO_KW)


@pytest.fixture(scope="module")
def contended():
    return get_scenario("contended_residue", seed=1)


@pytest.fixture(scope="module")
def event_res(smoke):
    return run_scenario_event(smoke, comm="ada")


@pytest.fixture(scope="module")
def fluid_res(smoke):
    return run_scenario_fluid(smoke, comm="ada", dt=DT)


def fluid_avg(out):
    return float(out["jct"][out["finished"]].mean())


class TestSmokeAgreement:
    def test_both_backends_finish_everything(self, smoke, event_res, fluid_res):
        assert len(event_res.jct) == smoke.n_jobs
        assert int(fluid_res["finished"].sum()) == smoke.n_jobs

    def test_avg_jct_within_ratio(self, event_res, fluid_res):
        ev = event_res.avg_jct()
        fl = fluid_avg(fluid_res)
        assert ev / RATIO <= fl <= ev * RATIO, (ev, fl)

    def test_makespan_within_ratio(self, event_res, fluid_res):
        ev = event_res.makespan
        fl = float(fluid_res["makespan"])
        assert ev / RATIO <= fl <= ev * RATIO, (ev, fl)

    @pytest.mark.parametrize("comm", FLUID_POLICIES)
    def test_no_policy_strands_jobs(self, smoke, comm):
        """Regression for the fluid gating self-deadlock: every policy must
        complete the smoke scenario's multi-server jobs."""
        out = run_scenario_fluid(smoke, comm=comm, dt=DT)
        assert int(out["finished"].sum()) == smoke.n_jobs, comm

    def test_fluid_deterministic(self, smoke, fluid_res):
        again = run_scenario_fluid(smoke, comm="ada", dt=DT)
        np.testing.assert_array_equal(fluid_res["jct"], again["jct"])


class TestEveryPolicyEveryBackend:
    """Each fluid-supported gating policy, event-vs-fluid, on the scenario
    built so gang placements must share servers (all-reduces collide even
    under exclusive placement — the cell where the masks actually bite)."""

    @pytest.mark.parametrize("comm", FLUID_POLICIES)
    def test_contended_cell_agrees(self, contended, comm):
        ev = run_scenario_event(contended, comm=comm)
        fl = run_scenario_fluid(contended, comm=comm, dt=DT)
        assert len(ev.jct) == contended.n_jobs
        assert int(fl["finished"].sum()) == contended.n_jobs
        assert ev.avg_jct() / RATIO <= fluid_avg(fl) <= ev.avg_jct() * RATIO

    def test_gating_differentiates_like_event(self, contended):
        """AdaDUAL refuses the always-colliding equal-size transfers (all
        messages are identical, so Theorem 2's ratio test fails) while
        SRSF(2) blindly accepts 2-way contention — on BOTH backends the
        blind policy must be no better."""
        fl_ada = fluid_avg(run_scenario_fluid(contended, comm="ada", dt=DT))
        fl_s2 = fluid_avg(run_scenario_fluid(contended, comm="srsf2", dt=DT))
        ev_ada = run_scenario_event(contended, comm="ada").avg_jct()
        ev_s2 = run_scenario_event(contended, comm="srsf2").avg_jct()
        assert fl_ada < fl_s2, (fl_ada, fl_s2)
        assert ev_ada < ev_s2, (ev_ada, ev_s2)


class TestHeteroBandwidth:
    """Per-server bandwidth on the fluid backend (the cell that previously
    could not be differentially tested: heterogeneity used to collapse to
    the cluster mean)."""

    @pytest.mark.parametrize("comm", FLUID_POLICIES)
    def test_agrees_with_event(self, hetero, comm):
        ev = run_scenario_event(hetero, comm=comm)
        fl = run_scenario_fluid(hetero, comm=comm, dt=0.05)
        assert len(ev.jct) == hetero.n_jobs
        assert int(fl["finished"].sum()) == hetero.n_jobs
        assert ev.avg_jct() / RATIO <= fluid_avg(fl) <= ev.avg_jct() * RATIO

    def test_slow_servers_slow_the_fluid_backend(self, hetero):
        """Same workload, homogeneous network: the degraded cluster must
        not finish sooner — proves per-server rates reach the drain loop
        (the old mean-collapse fluid backend got this wrong by design)."""
        import dataclasses

        homog = dataclasses.replace(hetero, params=ContentionParams())
        slow = run_scenario_fluid(hetero, comm="ada", dt=0.05)
        fast = run_scenario_fluid(homog, comm="ada", dt=0.05)
        assert fluid_avg(slow) > fluid_avg(fast)


class TestOversubFabric:
    """Every fluid-supported gating policy on a multi-tier topology: the
    per-domain contention state (NIC + oversubscribed rack uplinks) must
    keep the two backends in qualitative agreement."""

    @pytest.fixture(scope="class")
    def oversub(self):
        return get_scenario("oversub_fabric", **OVERSUB_KW)

    @pytest.mark.parametrize("comm", FLUID_POLICIES)
    def test_agrees_with_event(self, oversub, comm):
        ev = run_scenario_event(oversub, comm=comm)
        fl = run_scenario_fluid(oversub, comm=comm, dt=0.05)
        assert len(ev.jct) == oversub.n_jobs
        assert int(fl["finished"].sum()) == oversub.n_jobs
        assert ev.avg_jct() / RATIO <= fluid_avg(fl) <= ev.avg_jct() * RATIO

    def test_oversub_slows_both_backends(self, oversub):
        """Same workload without the fabric (NIC-only): the oversubscribed
        uplinks must not make anything faster — proves the topology reaches
        the drain loop of each backend, not just the config."""
        import dataclasses

        flat = dataclasses.replace(oversub, topology=None)
        assert run_scenario_event(oversub, comm="ada").avg_jct() >= (
            run_scenario_event(flat, comm="ada").avg_jct() * (1 - 1e-9)
        )
        assert fluid_avg(run_scenario_fluid(oversub, comm="ada", dt=0.05)) >= (
            fluid_avg(run_scenario_fluid(flat, comm="ada", dt=0.05)) * (1 - 1e-9)
        )


class TestRandPlacement:
    """RAND on the fluid backend (gang-random server order vs the event
    backend's per-GPU uniform sample) — closes the parity-matrix gap."""

    def test_agrees_with_event_on_smoke(self, smoke):
        ev = run_scenario_event(smoke, comm="ada", placement="rand")
        fl = run_scenario_fluid(smoke, comm="ada", placement="rand", dt=DT)
        assert len(ev.jct) == smoke.n_jobs
        assert int(fl["finished"].sum()) == smoke.n_jobs
        assert ev.avg_jct() / RATIO <= fluid_avg(fl) <= ev.avg_jct() * RATIO

    def test_deterministic_given_seed(self, smoke):
        a = run_scenario_fluid(smoke, comm="ada", placement="rand", dt=DT)
        b = run_scenario_fluid(smoke, comm="ada", placement="rand", dt=DT)
        np.testing.assert_array_equal(a["jct"], b["jct"])

    def test_every_policy_completes_under_rand(self, smoke):
        for comm in FLUID_POLICIES:
            out = run_scenario_fluid(smoke, comm=comm, placement="rand", dt=DT)
            assert int(out["finished"].sum()) == smoke.n_jobs, comm


class TestRackAwarePlacement:
    """rack_locality: rack-sized jobs behind 6x-oversubscribed uplinks.
    Rack-aware placement (event lwf_rack / fluid rack_pack) must beat the
    topology-blind LWF on both backends — the placement-side payoff of the
    fabric layer."""

    @pytest.fixture(scope="class")
    def rack(self):
        return get_scenario("rack_locality", seed=1)

    def test_rack_aware_beats_plain_lwf_event(self, rack):
        plain = run_scenario_event(rack, comm="ada", placement="lwf")
        aware = run_scenario_event(rack, comm="ada", placement="lwf_rack")
        assert len(aware.jct) == rack.n_jobs
        assert aware.makespan <= plain.makespan * 1.005
        assert aware.avg_jct() <= plain.avg_jct() * 1.005

    def test_rack_aware_beats_plain_lwf_fluid(self, rack):
        # dt=0.1: this cell is step-bound (makespans of hundreds of sim
        # seconds); both runs quantize identically so the ordering holds
        plain = run_scenario_fluid(rack, comm="ada", placement="lwf", dt=0.1)
        aware = run_scenario_fluid(rack, comm="ada", placement="lwf_rack", dt=0.1)
        assert int(aware["finished"].sum()) == rack.n_jobs
        assert float(aware["makespan"]) <= float(plain["makespan"]) * 1.005
        assert fluid_avg(aware) <= fluid_avg(plain) * 1.005


class TestPlacementModes:
    """Fluid gang placement modes vs their event analogues on a workload
    where first-fit fragments multi-server jobs across partially-occupied
    servers (comm + contention) while consolidation gives whole servers."""

    def _scenario(self):
        jobs = []
        jid = 0
        for wave in range(3):
            t = float(wave * 2)
            jobs.append(JobSpec(jid, t, 1, 80, TABLE_III["resnet50"]))
            jid += 1
            jobs.append(JobSpec(jid, t, 4, 40, TABLE_III["vgg16"]))
            jid += 1
        return Scenario(
            name="frag",
            seed=0,
            n_servers=4,
            gpus_per_server=4,
            jobs=tuple(jobs),
            params=ContentionParams(),
        )

    @pytest.mark.parametrize("placement", ["lwf", "ff"])
    def test_each_mode_completes_and_agrees(self, placement):
        scn = self._scenario()
        ev = run_scenario_event(scn, comm="ada", placement=placement)
        fl = run_scenario_fluid(scn, comm="ada", placement=placement, dt=DT)
        assert len(ev.jct) == scn.n_jobs
        assert int(fl["finished"].sum()) == scn.n_jobs
        assert ev.makespan / RATIO <= float(fl["makespan"]) <= ev.makespan * RATIO

    def test_least_loaded_completes_and_consolidates(self):
        """Gang `least_loaded` fills whole servers in L_S order, so its
        event anchor is LWF-kappa — per-GPU list scheduling (LS) instead
        *deliberately* fragments jobs across servers, a shape gang
        placement cannot express (documented parity gap)."""
        scn = self._scenario()
        fl = run_scenario_fluid(scn, comm="ada", placement="ls", dt=DT)
        ev_lwf = run_scenario_event(scn, comm="ada", placement="lwf")
        assert int(fl["finished"].sum()) == scn.n_jobs
        assert (
            ev_lwf.makespan / RATIO
            <= float(fl["makespan"])
            <= ev_lwf.makespan * RATIO
        )

    def test_lwf_beats_ff_on_both_backends(self):
        scn = self._scenario()
        fl_lwf = float(run_scenario_fluid(scn, comm="ada", placement="lwf", dt=DT)["makespan"])
        fl_ff = float(run_scenario_fluid(scn, comm="ada", placement="ff", dt=DT)["makespan"])
        ev_lwf = run_scenario_event(scn, comm="ada", placement="lwf").makespan
        ev_ff = run_scenario_event(scn, comm="ada", placement="ff").makespan
        assert fl_lwf < fl_ff, (fl_lwf, fl_ff)
        assert ev_lwf < ev_ff, (ev_lwf, ev_ff)


class TestModelZoo:
    """The config-derived model zoo (repro.workloads) with WFBP tensor
    fusion, event-vs-fluid: layer-granular profiles, per-bucket gating and
    the static [jobs, buckets] fluid drain must keep the backends in
    qualitative agreement (smoke-sized for tier-1 budget)."""

    ZOO_KW = dict(seed=1, n_jobs=8, min_iters=10, max_iters=40, horizon_s=300.0)

    @pytest.fixture(scope="class")
    def zoo(self):
        return get_scenario("model_zoo", **self.ZOO_KW)

    @pytest.mark.parametrize("comm", ["ada", "srsf2", "kway2", "kway3"])
    def test_agrees_with_event(self, zoo, comm):
        ev = run_scenario_event(zoo, comm=comm)
        fl = run_scenario_fluid(zoo, comm=comm, dt=0.02)
        assert len(ev.jct) == zoo.n_jobs
        assert int(fl["finished"].sum()) == zoo.n_jobs
        assert (
            ev.avg_jct() / FUSION_RATIO
            <= fluid_avg(fl)
            <= ev.avg_jct() * FUSION_RATIO
        )

    @pytest.mark.parametrize("comm", ["ada", "kway3"])
    def test_fusion_sweep_cell_agrees(self, comm):
        from repro.scenarios import QUICK_OVERRIDES

        # dt=0.01 shares the compiled graph with
        # test_fluid_deterministic_with_buckets below (same config)
        scn = get_scenario("fusion_sweep", seed=1, **QUICK_OVERRIDES["fusion_sweep"])
        ev = run_scenario_event(scn, comm=comm)
        fl = run_scenario_fluid(scn, comm=comm, dt=0.01)
        assert len(ev.jct) == scn.n_jobs
        assert int(fl["finished"].sum()) == scn.n_jobs
        assert (
            ev.avg_jct() / FUSION_RATIO
            <= fluid_avg(fl)
            <= ev.avg_jct() * FUSION_RATIO
        )

    def test_fluid_deterministic_with_buckets(self):
        from repro.scenarios import QUICK_OVERRIDES

        scn = get_scenario("fusion_sweep", seed=1, **QUICK_OVERRIDES["fusion_sweep"])
        a = run_scenario_fluid(scn, comm="ada", dt=0.01)
        b = run_scenario_fluid(scn, comm="ada", dt=0.01)
        np.testing.assert_array_equal(a["jct"], b["jct"])


class TestSchedScenarios:
    """The preemptive/elastic workloads under their *static* defaults,
    event-vs-fluid.  Preemption and elasticity themselves are event-only
    (the fluid backend's static traces cannot express mid-run gang
    teardown — see the parity matrix), so the differential cell pins the
    shared static baseline both regression locks are measured against."""

    @pytest.mark.parametrize(
        "name,seed", [("preemption_gain", 2), ("elastic_surge", 1)]
    )
    def test_static_mode_agrees(self, name, seed):
        scn = get_scenario(name, seed=seed)
        assert scn.sched == "static"
        ev = run_scenario_event(scn, comm="ada")
        fl = run_scenario_fluid(scn, comm="ada", dt=0.1)
        assert len(ev.jct) == scn.n_jobs
        assert ev.censored == 0
        assert int(fl["finished"].sum()) == scn.n_jobs
        assert ev.avg_jct() / RATIO <= fluid_avg(fl) <= ev.avg_jct() * RATIO


class TestNoCommLimit:
    """Single-server jobs have no communication: both backends reduce to
    pure compute and must agree to within the fluid dt quantization."""

    def _scenario(self):
        jobs = (
            JobSpec(0, 0.0, 1, 40, TABLE_III["resnet50"]),
            JobSpec(1, 0.0, 1, 25, TABLE_III["vgg16"]),
        )
        return Scenario(
            name="nocomm",
            seed=0,
            n_servers=2,
            gpus_per_server=2,
            jobs=jobs,
            params=ContentionParams(),
        )

    def test_exact_agreement_modulo_dt(self):
        scn = self._scenario()
        dt = 0.01
        ev = run_scenario_event(scn, comm="ada")
        fl = run_scenario_fluid(scn, comm="ada", dt=dt)
        assert int(fl["finished"].sum()) == 2
        for job in scn.jobs:
            expect = ev.jct[job.job_id]
            got = float(fl["jct"][job.job_id])
            # fixed-dt integration rounds every iteration up to a multiple
            # of dt, and admission lags up to a couple of steps
            assert got == pytest.approx(expect, abs=dt * (job.iterations + 5))
