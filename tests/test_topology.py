"""Network-fabric topology layer (``core/topology.py``) tests.

Three layers of coverage:

* the :class:`Topology` object itself — construction validation, the one
  load rule (a task loads a domain iff its ring crosses the domain's cut),
  incidence-matrix structure, and the numpy/jax ``netmodel.domain_loads``
  lowering agreeing with the set-based ``loaded_domains``;
* the **NIC-only parity regression** both acceptance criteria hinge on:
  an explicit ``nic_topology`` must reproduce the default (no-topology)
  event- and fluid-backend results bit for bit, and so must the two
  degenerate fabrics that reduce to it (a two-tier fabric with a single
  rack, and racks-of-one with oversub 1.0);
* behavioural checks: oversubscribed uplinks slow cross-rack traffic on
  both backends, intra-rack traffic is unaffected, and the rack-aware
  LWF placement keeps rack-sized jobs off the uplinks;
* the fluid backend's uplink counters: kept only where the fabric has an
  uplink domain, the same with and without the next-event skip, and
  exact on a job whose transfers can be counted by hand.
"""

import dataclasses
import math

import numpy as np
import pytest

from repro.core import netmodel
from repro.core.cluster import TABLE_III, Cluster, JobSpec
from repro.core.contention import ContentionParams
from repro.core.placement import PlacementPolicy, place_lwf_rack
from repro.core.topology import Domain, Topology, nic_topology, two_tier, uplink_only
from repro.scenarios import (
    get_scenario,
    monte_carlo_fluid,
    run_scenario_event,
    run_scenario_fluid,
)
from repro.scenarios.registry import Scenario
from repro.scenarios.sweep import fluid_config


class TestConstruction:
    def test_nic_topology_shape(self):
        t = nic_topology(4)
        assert t.n_domains == 4
        assert all(d.oversub == 1.0 for d in t.domains)
        np.testing.assert_array_equal(t.incidence(), np.eye(4, dtype=np.float32))

    def test_two_tier_shape(self):
        t = two_tier(8, 4, oversub=3.0)
        assert t.n_domains == 8 + 2  # NICs + 2 rack uplinks
        assert t.racks == ((0, 1, 2, 3), (4, 5, 6, 7))
        assert t.oversub_array()[-1] == pytest.approx(3.0)
        np.testing.assert_array_equal(t.server_rack(), [0, 0, 0, 0, 1, 1, 1, 1])

    def test_ragged_last_rack(self):
        t = two_tier(5, 2)
        assert t.racks == ((0, 1), (2, 3), (4,))

    def test_validation(self):
        with pytest.raises(ValueError, match="oversub"):
            Domain("d", (0,), oversub=0.0)
        with pytest.raises(ValueError, match="no servers"):
            Domain("d", ())
        with pytest.raises(ValueError, match="references servers outside"):
            Topology("t", 2, (Domain("d", (5,)),))
        with pytest.raises(ValueError, match="references servers outside"):
            # negative indices would silently wrap in incidence()
            Topology("t", 4, (Domain("d", (-1, 0)),))
        with pytest.raises(ValueError, match="two racks"):
            Topology("t", 2, (), racks=((0,), (0, 1)))

    def test_hashable_and_picklable(self):
        """Topology rides inside a jit-static JaxSimConfig and crosses the
        sweep runner's multiprocessing boundary."""
        import pickle

        t = two_tier(8, 4)
        assert hash(t) == hash(two_tier(8, 4))
        assert pickle.loads(pickle.dumps(t)) == t


class TestLoadRule:
    def test_single_server_task_loads_nothing(self):
        """A single-server job's traffic never leaves the server: no cut is
        crossed, no shared domain is loaded — in any topology."""
        for topo in (nic_topology(4), two_tier(4, 2, 3.0), uplink_only(4, 2)):
            assert topo.loaded_domains({2}) == frozenset()

    def test_nic_domains_are_the_member_servers(self):
        t = nic_topology(4)
        assert t.loaded_domains({0, 2}) == {0, 2}

    def test_intra_rack_task_skips_uplinks(self):
        t = two_tier(8, 4, oversub=3.0)
        # servers 0,1 are both in rack 0: NIC cuts crossed, uplink not
        assert t.loaded_domains({0, 1}) == {0, 1}

    def test_cross_rack_task_loads_both_uplinks(self):
        t = two_tier(8, 4, oversub=3.0)
        assert t.loaded_domains({0, 4}) == {0, 4, 8, 9}

    def test_non_contiguous_gang_placement(self):
        """A fragmented gang across non-adjacent servers in three racks
        loads each touched NIC and each touched rack's uplink."""
        t = two_tier(12, 4, oversub=2.0)  # racks {0-3},{4-7},{8-11}
        loaded = t.loaded_domains({1, 6, 11})
        assert loaded == {1, 6, 11, 12 + 0, 12 + 1, 12 + 2}

    def test_domain_covering_everything_never_loads(self):
        t = Topology("all", 4, (Domain("world", (0, 1, 2, 3)),))
        assert t.loaded_domains({0, 3}) == frozenset()


class TestIncidenceLowering:
    """netmodel.domain_loads (the fluid backend's branchless form) must
    agree with Topology.loaded_domains (the event backend's set form) for
    every member set — including non-contiguous gang placements."""

    @pytest.mark.parametrize(
        "topo",
        [nic_topology(6), two_tier(6, 2, 3.0), two_tier(6, 4, 2.0), uplink_only(6, 3)],
        ids=lambda t: t.name,
    )
    def test_matches_set_form(self, topo):
        inc = topo.incidence()
        rng = np.random.default_rng(0)
        member_sets = [
            {0},
            {0, 1},
            {0, 5},
            {1, 3, 5},
            {0, 1, 2, 3, 4, 5},
        ] + [set(rng.choice(6, size=rng.integers(1, 6), replace=False).tolist())
             for _ in range(20)]
        for s in member_sets:
            mask = np.zeros((6,), dtype=np.float32)
            mask[list(s)] = 1.0
            loads = netmodel.domain_loads(mask, inc)
            assert set(np.nonzero(loads)[0]) == set(topo.loaded_domains(s)), s

    def test_batched_member_masks(self):
        topo = two_tier(6, 2, 3.0)
        inc = topo.incidence()
        masks = np.asarray(
            [[1, 1, 0, 0, 0, 0], [1, 0, 0, 0, 0, 1], [0, 0, 1, 0, 0, 0]],
            dtype=np.float32,
        )
        loads = netmodel.domain_loads(masks, inc)
        assert loads.shape == (3, topo.n_domains)
        assert set(np.nonzero(loads[0])[0]) == {0, 1}          # intra-rack
        assert set(np.nonzero(loads[1])[0]) == {0, 5, 6, 8}    # cross-rack
        assert not loads[2].any()                              # single server

    def test_domain_k_counts_and_oversub(self):
        loads = np.asarray([[True, False, True], [True, True, False]])
        counts = netmodel.domain_counts(loads, np.asarray([True, True]))
        np.testing.assert_array_equal(counts, [2, 1, 1])
        k = netmodel.domain_k(loads, counts)
        np.testing.assert_array_equal(k, [2, 2])
        k_eff = netmodel.domain_k(loads, counts * np.asarray([1.0, 1.0, 4.0]))
        np.testing.assert_array_equal(k_eff, [4.0, 2.0])
        # a task loading no domain is uncontended
        k_none = netmodel.domain_k(np.zeros((1, 3), bool), counts)
        np.testing.assert_array_equal(k_none, [1])


@pytest.fixture(scope="module")
def smoke():
    return get_scenario("smoke")


@pytest.fixture(scope="module")
def contended():
    return get_scenario("contended_residue", seed=1)


class TestNicParityRegression:
    """The acceptance-criteria lock: NIC-only topology must reproduce the
    pre-topology numbers exactly on both backends."""

    @pytest.mark.parametrize("name", ["smoke", "contended_residue"])
    @pytest.mark.parametrize("comm", ["ada", "srsf1", "kway3"])
    def test_event_backend_bit_exact(self, name, comm):
        scn = get_scenario(name, seed=1)
        nic = dataclasses.replace(scn, topology=nic_topology(scn.n_servers))
        a = run_scenario_event(scn, comm=comm)
        b = run_scenario_event(nic, comm=comm)
        assert a.jct == b.jct
        assert a.makespan == b.makespan
        assert a.events_processed == b.events_processed
        assert a.comm_started_contended == b.comm_started_contended

    @pytest.mark.parametrize("comm", ["ada", "srsf2", "kway3"])
    def test_fluid_backend_bit_exact(self, contended, comm):
        nic = dataclasses.replace(contended, topology=nic_topology(contended.n_servers))
        a = run_scenario_fluid(contended, comm=comm, dt=0.02)
        b = run_scenario_fluid(nic, comm=comm, dt=0.02)
        np.testing.assert_array_equal(np.asarray(a["jct"]), np.asarray(b["jct"]))
        assert float(a["makespan"]) == float(b["makespan"])

    def test_single_rack_two_tier_degenerates_to_nic(self, smoke):
        """One rack covering every server: the uplink cut is never crossed,
        so the fabric is exactly the NIC-only model."""
        degen = dataclasses.replace(
            smoke, topology=two_tier(smoke.n_servers, smoke.n_servers, oversub=9.0)
        )
        a = run_scenario_event(smoke, comm="ada")
        b = run_scenario_event(degen, comm="ada")
        assert a.jct == b.jct
        fa = run_scenario_fluid(smoke, comm="ada", dt=0.02)
        fb = run_scenario_fluid(degen, comm="ada", dt=0.02)
        np.testing.assert_array_equal(np.asarray(fa["jct"]), np.asarray(fb["jct"]))

    def test_racks_of_one_unit_oversub_degenerates_to_nic(self, contended):
        """Racks of a single server with oversub 1.0 duplicate the NIC cuts
        at unit capacity: per-domain counts and maxima are unchanged."""
        degen = dataclasses.replace(
            contended, topology=two_tier(contended.n_servers, 1, oversub=1.0)
        )
        a = run_scenario_event(contended, comm="srsf2")
        b = run_scenario_event(degen, comm="srsf2")
        assert a.jct == b.jct
        fa = run_scenario_fluid(contended, comm="srsf2", dt=0.02)
        fb = run_scenario_fluid(degen, comm="srsf2", dt=0.02)
        np.testing.assert_array_equal(np.asarray(fa["jct"]), np.asarray(fb["jct"]))


class TestOversubBehaviour:
    def test_oversub_uplinks_slow_crossing_traffic_event_and_fluid(self, smoke):
        """Racks of one: every spanning job crosses an oversubscribed
        uplink, so the whole schedule must stretch on both backends."""
        slow = dataclasses.replace(smoke, topology=two_tier(smoke.n_servers, 1, oversub=4.0))
        ev_nic = run_scenario_event(smoke, comm="ada")
        ev_slow = run_scenario_event(slow, comm="ada")
        assert ev_slow.makespan > ev_nic.makespan
        assert len(ev_slow.jct) == smoke.n_jobs
        fl_nic = run_scenario_fluid(smoke, comm="ada", dt=0.02)
        fl_slow = run_scenario_fluid(slow, comm="ada", dt=0.02)
        assert float(fl_slow["makespan"]) > float(fl_nic["makespan"])
        assert int(fl_slow["finished"].sum()) == smoke.n_jobs

    def test_uncontended_crossing_rate_matches_oversub(self):
        """One 2-server job on a 2-rack oversub fabric: its only transfer is
        uncontended (k=1) but crosses the uplink, so it drains at the
        Eq. (5) rate of k_eff = oversub — the event backend integrates this
        exactly."""
        p = ContentionParams()
        oversub = 4.0
        jobs = [JobSpec(0, 0.0, 2, 10, TABLE_III["vgg16"])]

        def run(topology):
            scn = Scenario(
                name="one",
                seed=0,
                n_servers=2,
                gpus_per_server=1,
                jobs=tuple(jobs),
                params=p,
                topology=topology,
            )
            return run_scenario_event(scn, comm="ada")

        base = run(None)
        crossed = run(two_tier(2, 1, oversub=oversub))
        m = TABLE_III["vgg16"].size_bytes
        extra_per_iter = m * (p.seconds_per_byte(oversub) - p.seconds_per_byte(1))
        expect = base.makespan + 10 * extra_per_iter
        assert crossed.makespan == pytest.approx(expect, rel=1e-9)

    def test_uplink_only_relieves_nic_contention(self, contended):
        """Without NIC domains, intra-rack all-reduces never contend: the
        uplink_only fabric (single rack) can only be faster."""
        free = dataclasses.replace(
            contended,
            topology=uplink_only(contended.n_servers, contended.n_servers),
        )
        a = run_scenario_event(contended, comm="srsf3")
        b = run_scenario_event(free, comm="srsf3")
        assert b.avg_jct() <= a.avg_jct() * (1 + 1e-9)


class TestRackAwarePlacement:
    def _pinned_cluster(self):
        """2 racks x 2 servers x 4 GPUs with servers 1 and 2 partially
        occupied: plain LWF picks the two idle servers 0 and 3 (different
        racks) for a 6-GPU job; rack-aware placement stays inside rack 0."""
        topo = two_tier(4, 2, oversub=8.0)
        cluster = Cluster(n_servers=4, gpus_per_server=4)
        pin = JobSpec(99, 0.0, 1, 100, TABLE_III["resnet50"])
        for s in (1, 2):
            cluster.place(pin, [(s, 0)], workload_share=50.0)
        return topo, cluster

    def test_plain_lwf_crosses_racks(self):
        topo, cluster = self._pinned_cluster()
        job = JobSpec(0, 0.0, 6, 10, TABLE_III["resnet50"])
        gpus = PlacementPolicy("lwf")(cluster, job)
        assert {s for s, _ in gpus} == {0, 3}  # idle servers, racks 0 and 1
        assert len(topo.loaded_domains({s for s, _ in gpus}) - {0, 3}) > 0

    def test_rack_aware_stays_inside_one_rack(self):
        topo, cluster = self._pinned_cluster()
        job = JobSpec(0, 0.0, 6, 10, TABLE_III["resnet50"])
        gpus = place_lwf_rack(cluster, job, topo.rack_groups())
        servers = {s for s, _ in gpus}
        assert servers == {0, 1}  # all of rack 0
        # only NIC cuts crossed — no uplink domain loaded
        assert all(topo.domains[d].oversub == 1.0 for d in topo.loaded_domains(servers))

    def test_without_topology_degenerates_to_lwf(self):
        cluster = Cluster(n_servers=4, gpus_per_server=4)
        job = JobSpec(0, 0.0, 6, 10, TABLE_III["resnet50"])
        a = PlacementPolicy("lwf")(cluster, job)
        b = PlacementPolicy("lwf_rack")(cluster, job)
        assert a == b

    def test_rack_pack_rank_prefers_emptiest_rack(self):
        free = np.asarray([1.0, 1.0, 4.0, 3.0])
        server_rack = np.asarray([0, 0, 1, 1])
        rank = netmodel.rack_pack_rank(free, server_rack, 2, gpus_per_server=4)
        order = np.argsort(rank, kind="stable")
        assert order.tolist() == [2, 3, 0, 1]  # rack 1 (7 free) first, fuller-first inside


class TestUplinkCounters:
    """``jaxsim.UPLINK_COUNTERS``: admissions whose ring crosses a rack
    boundary, and transfers in flight at each tick's end, over an uplink
    and in all."""

    SMALL = dict(n_jobs=24, min_iters=20, max_iters=80, horizon_s=30.0,
                 n_servers=8, servers_per_rack=2)

    @staticmethod
    def counts(recs):
        return [(r.uplink_jobs, r.uplink_transfer_ticks, r.transfer_ticks)
                for r in recs]

    @pytest.mark.parametrize("placement", ["lwf", "lwf_rack"])
    def test_same_with_and_without_skip(self, placement):
        on, off = (
            self.counts(monte_carlo_fluid(
                "oversub_fabric", [1, 2, 3, 4], placement=placement,
                overrides=self.SMALL, skip=skip))
            for skip in (True, False))
        assert on == off
        for jobs, uplink_ticks, ticks in on:
            assert 1 <= jobs <= self.SMALL["n_jobs"]
            assert 0 < uplink_ticks <= ticks

    @pytest.mark.parametrize("n_gpus, crosses", [(3, True), (2, False)])
    def test_one_job_counted_by_hand(self, n_gpus, crosses):
        """Two racks of two one-GPU servers: a 3-GPU ring must cross the
        racks and drains at k_eff = oversub 3; a 2-GPU one fills rack 0
        under rack-aware placement and loads only NICs."""
        from repro.core.jaxsim import simulate_traces_batched, stack_traces, trace_from_jobs

        p, model, iters = ContentionParams(), TABLE_III["vgg16"], 10
        scn = Scenario(
            name="one", seed=0, n_servers=4, gpus_per_server=1,
            jobs=(JobSpec(0, 0.0, n_gpus, iters, model),), params=p,
            topology=two_tier(4, 2, oversub=3.0),
        )
        cfg = fluid_config(scn, placement="lwf_rack")
        out = simulate_traces_batched(
            stack_traces([trace_from_jobs(scn.job_list())]), cfg)
        # a transfer's first tick drains dt at the contention it found
        # (none: it is alone), each later one dt at the Eq. 5 fraction of
        # k_eff; in flight at the end of every tick but its last
        k = 3.0 if crosses else 1.0
        comm = p.a + p.b * model.size_bytes
        ticks = (comm - cfg.dt) / (cfg.dt * p.b / (k * p.b + (k - 1) * p.eta))
        assert abs(ticks - round(ticks)) > 1e-3  # no end on a tick boundary
        in_flight = iters * math.ceil(ticks)
        assert out["uplink_jobs"].tolist() == [int(crosses)]
        assert out["transfer_ticks"].tolist() == [in_flight]
        assert out["uplink_transfer_ticks"].tolist() == [in_flight * crosses]

    def test_nic_fabric_keeps_none(self):
        """No uplink domain: no counter in the lane state (so the chunk
        program is the one it was before they existed), and ``None`` in
        every record."""
        import jax

        from repro.core.jaxsim import UPLINK_COUNTERS, _init_jit, stack_traces, trace_from_jobs

        small = dict(n_jobs=8, min_iters=5, max_iters=20, horizon_s=10.0)
        scn = get_scenario("paper", seed=0, **small)
        batch = stack_traces([trace_from_jobs(scn.job_list())])
        state = jax.eval_shape(lambda tr: _init_jit(tr, fluid_config(scn)), batch)
        assert not set(UPLINK_COUNTERS) & set(state)
        recs = monte_carlo_fluid("paper", [1, 2], overrides=small)
        assert self.counts(recs) == [(None, None, None)] * 2
