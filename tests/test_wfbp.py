"""Tests for the layer-granular WFBP communication subsystem.

Covers the whole vertical slice:

* ``netmodel.fusion_plan`` / ``fusion_threshold`` (the shared tensor-fusion
  planner);
* ``repro.workloads`` config-derived layer profiles (sum invariants, zoo
  well-formedness);
* the event backend's per-bucket overlapped execution — including the
  acceptance-criteria locks: ``fusion="all"`` is bit-exact against
  layer-stripped monolithic profiles on BOTH backends, a finite fusion
  threshold measurably beats both ``"all"`` and fully-unfused under
  Ada-SRSF on the ``fusion_sweep`` regression cell, and every simulated
  trace is a valid linear extension of the layer-granular formal DAG
  (deterministic + Hypothesis property test, overlap edges included);
* the fluid backend's static ``[jobs, buckets]`` chunked drain;
* the legacy ring-edge "link" reading expressed as dynamic topology
  domains (``RingEdgeTopology``), locked against the old inline formula.
"""

import dataclasses
import math

import pytest

from repro.core import simulate
from repro.core import netmodel
from repro.core.cluster import TABLE_III, JobSpec
from repro.core.contention import ContentionParams
from repro.core.dag import TaskKind, TaskRef, build_job_dag, validate_schedule
from repro.core.topology import RingEdgeTopology
from repro.scenarios import get_scenario, run_scenario_event, run_scenario_fluid
from repro.workloads import (
    GRAD_BYTES_PER_PARAM,
    TOKENS_PER_GPU,
    ZOO_ARCHS,
    ZOO_GPU_MEM_MB,
    derive_layer_profiles,
    model_profile_from_config,
    zoo_profiles,
)

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

P = ContentionParams()


def strip_layers(model):
    """The monolithic (pre-WFBP) reading of a layer-granular profile."""
    return dataclasses.replace(model, layer_grad_bytes=(), layer_t_b=())


def strip_scenario(scn):
    jobs = tuple(
        dataclasses.replace(j, model=strip_layers(j.model)) for j in scn.jobs
    )
    return dataclasses.replace(scn, jobs=jobs, fusion="all")


# ---------------------------------------------------------------------------
# Fusion planner (netmodel)
# ---------------------------------------------------------------------------


class TestFusionPlan:
    LB = (10.0, 20.0, 5.0, 40.0, 5.0)
    TB = (1.0, 2.0, 0.5, 4.0, 0.5)

    def test_threshold_normalization(self):
        assert netmodel.fusion_threshold("all") == math.inf
        assert netmodel.fusion_threshold("none") == 0.0
        assert netmodel.fusion_threshold(25e6) == 25e6
        with pytest.raises(ValueError):
            netmodel.fusion_threshold("sometimes")
        with pytest.raises(ValueError):
            netmodel.fusion_threshold(-1.0)

    def test_all_is_one_bucket(self):
        sizes, times = netmodel.fusion_plan(self.LB, self.TB, math.inf)
        assert sizes == (sum(self.LB),)
        assert times == (sum(self.TB),)

    def test_none_is_per_layer(self):
        sizes, times = netmodel.fusion_plan(self.LB, self.TB, 0.0)
        assert sizes == self.LB
        assert times == self.TB

    def test_finite_threshold_buckets_greedily(self):
        # threshold 25: [10+20]=30 seals, [5+40]=45 seals, [5] trails
        sizes, times = netmodel.fusion_plan(self.LB, self.TB, 25.0)
        assert sizes == (30.0, 45.0, 5.0)
        assert times == (3.0, 4.5, 0.5)

    def test_sums_preserved_exactly(self):
        for thr in (0.0, 7.0, 25.0, 60.0, math.inf):
            sizes, times = netmodel.fusion_plan(self.LB, self.TB, thr)
            assert sum(sizes) == pytest.approx(sum(self.LB), rel=1e-12)
            assert sum(times) == pytest.approx(sum(self.TB), rel=1e-12)
            assert all(s > 0 for s in sizes)

    def test_threshold_above_total_is_single_bucket(self):
        sizes, _ = netmodel.fusion_plan(self.LB, self.TB, 1e9)
        assert len(sizes) == 1

    def test_validation(self):
        with pytest.raises(ValueError, match="align"):
            netmodel.fusion_plan((1.0,), (1.0, 2.0), 0.0)
        with pytest.raises(ValueError, match="at least one layer"):
            netmodel.fusion_plan((), (), 0.0)

    def test_plan_for_model(self):
        zoo = zoo_profiles()
        m = zoo["mamba2_130m"]
        assert netmodel.plan_for_model(m, "all") is None
        assert netmodel.plan_for_model(TABLE_III["vgg16"], "none") is None
        sizes, times = netmodel.plan_for_model(m, "none")
        assert len(sizes) == len(m.layer_grad_bytes)
        assert sum(sizes) == pytest.approx(m.size_bytes)
        assert sum(times) == pytest.approx(m.t_b)


# ---------------------------------------------------------------------------
# Config-derived workload profiles
# ---------------------------------------------------------------------------


class TestWorkloadProfiles:
    def test_zoo_covers_the_announced_archs(self):
        zoo = zoo_profiles()
        assert set(zoo) == set(ZOO_ARCHS)
        for arch in ("mamba2_130m", "llama32_1b", "phi4_mini_3_8b", "gemma_7b"):
            assert arch in zoo

    @pytest.mark.parametrize("arch", ZOO_ARCHS)
    def test_profile_invariants(self, arch):
        m = zoo_profiles()[arch]
        assert m.has_layers
        assert len(m.layer_grad_bytes) == len(m.layer_t_b) >= 3
        assert sum(m.layer_grad_bytes) == pytest.approx(m.size_bytes, rel=1e-9)
        assert sum(m.layer_t_b) == pytest.approx(m.t_b, rel=1e-9)
        assert all(b > 0 for b in m.layer_grad_bytes)
        assert all(t > 0 for t in m.layer_t_b)
        assert m.t_b == pytest.approx(2.0 * m.t_f, rel=0.05)  # bwd ~ 2x fwd
        assert m.mem_mb < ZOO_GPU_MEM_MB  # admissible on the zoo cluster

    def test_grad_bytes_match_param_count(self):
        from repro.configs import get_config

        cfg = get_config("llama32_1b")
        m = model_profile_from_config(cfg)
        # the analytic param model and the layer sum agree to ~1%
        assert m.size_bytes == pytest.approx(
            GRAD_BYTES_PER_PARAM * cfg.param_count(), rel=0.01
        )

    def test_backward_ready_order_starts_at_the_output(self):
        from repro.configs import get_config

        layers = derive_layer_profiles(get_config("llama32_1b"), TOKENS_PER_GPU)
        assert layers[0].name == "embed"
        assert layers[1].name.startswith("layer")
        # decoder layers come out in reverse order (output side first)
        idx = [int(l.name[5:]) for l in layers[1:]]
        assert idx == sorted(idx, reverse=True)

    def test_layer_mismatch_rejected_by_model_profile(self):
        with pytest.raises(ValueError, match="align"):
            dataclasses.replace(
                TABLE_III["vgg16"], layer_grad_bytes=(1.0,), layer_t_b=()
            )

    def test_zoo_derivation_is_jax_free(self):
        """The event-simulator path stays jax-free: deriving the zoo
        profiles (configs -> models.config -> workloads) must not import
        jax — that is why the multiprocessing sweep workers start cheap
        (checked in a fresh interpreter)."""
        import subprocess
        import sys

        code = (
            "import sys\n"
            "from repro.workloads import zoo_profiles\n"
            "zoo_profiles()\n"
            "assert 'jax' not in sys.modules, 'zoo derivation imported jax'\n"
        )
        subprocess.run([sys.executable, "-c", code], check=True)


# ---------------------------------------------------------------------------
# Event backend: WFBP execution
# ---------------------------------------------------------------------------


ZOO_KW = dict(n_servers=4, gpus_per_server=4, gpu_mem_mb=ZOO_GPU_MEM_MB)


def zoo_jobs(arch="mamba2_130m", n=1, iters=30, n_gpus=8):
    m = zoo_profiles()[arch]
    return [JobSpec(i, float(i), n_gpus, iters, m) for i in range(n)]


class TestEventWfbp:
    def test_fusion_all_equals_layer_stripped_monolithic(self):
        """The acceptance-criteria lock (event side): fusion='all' on
        layer-granular profiles is bit-exact against the same workload
        with the layer data stripped — the subsystem is a strict
        generalization of the iteration-level model."""
        jobs = zoo_jobs(n=4, iters=20)
        mono = [dataclasses.replace(j, model=strip_layers(j.model)) for j in jobs]
        a = simulate(jobs, fusion="all", **ZOO_KW)
        b = simulate(mono, **ZOO_KW)
        assert a.jct == b.jct
        assert a.makespan == b.makespan
        assert a.events_processed == b.events_processed

    def test_single_bucket_plan_equals_monolithic(self):
        """A finite threshold above the total message size yields one
        bucket; the WFBP machinery must then reproduce the monolithic
        unfused (fuse_fb=False) execution exactly."""
        jobs = zoo_jobs(n=2, iters=15)
        a = simulate(jobs, fusion=1e12, fuse_fb=False, **ZOO_KW)
        b = simulate(jobs, fusion="all", fuse_fb=False, **ZOO_KW)
        assert a.jct == b.jct
        assert a.makespan == b.makespan

    def test_overlap_shortens_a_single_job(self):
        """One spanning job: per-layer WFBP overlaps all-reduce with the
        remaining backward, so the unfused JCT must undercut the
        monolithic one by roughly the overlappable backward time, while
        never beating the comm+forward lower bound."""
        m = zoo_profiles()["mamba2_130m"]
        iters = 30
        jobs = zoo_jobs(n=1, iters=iters)
        mono = simulate(jobs, fusion="all", **ZOO_KW).jct[0]
        unfused = simulate(jobs, fusion="none", **ZOO_KW).jct[0]
        assert unfused < mono
        # lower bound: forward + every bucket's latency+bytes, no compute
        # overlap can hide the serialized comm stream itself
        n_l = len(m.layer_grad_bytes)
        lb = iters * (m.t_f + n_l * P.a + P.b * m.size_bytes)
        assert unfused > lb * 0.999
        # the win is bounded by the overlappable backward compute
        assert mono - unfused <= iters * m.t_b * 1.001

    def test_finite_fusion_beats_both_extremes_on_fusion_sweep(self):
        """THE acceptance criterion: on the fusion_sweep regression cell a
        finite fusion threshold measurably beats fusion='all' (overlap)
        AND fully-unfused (per-bucket latency + gating overhead) under
        Ada-SRSF."""
        from repro.scenarios import QUICK_OVERRIDES

        for seed in (0, 1):
            base = get_scenario(  # fusion=32e6
                "fusion_sweep", seed=seed, **QUICK_OVERRIDES["fusion_sweep"]
            )
            allf = dataclasses.replace(base, fusion="all")
            none = dataclasses.replace(base, fusion="none")
            r_fin = run_scenario_event(base, comm="ada")
            r_all = run_scenario_event(allf, comm="ada")
            r_non = run_scenario_event(none, comm="ada")
            assert len(r_fin.jct) == base.n_jobs
            # measurable: >= 1% over unfused, >= 10% over monolithic
            assert r_fin.avg_jct() * 1.01 <= r_non.avg_jct(), seed
            assert r_fin.avg_jct() * 1.10 <= r_all.avg_jct(), seed

    @pytest.mark.parametrize("comm", ["ada", "srsf1", "srsf2", "kway3"])
    def test_every_policy_completes_with_fusion(self, comm):
        jobs = zoo_jobs(n=6, iters=10)
        res = simulate(jobs, comm=comm, fusion=32e6, **ZOO_KW)
        assert len(res.jct) == 6, comm

    def test_fusion_with_chunks_rejected(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            simulate(zoo_jobs(), fusion="none", comm_chunks=4, **ZOO_KW)

    def test_gating_counts_bucket_bytes(self):
        """Under SRSF(1) every bucket start is exclusive: contended starts
        must be zero even with many buckets in flight across jobs."""
        jobs = zoo_jobs(n=4, iters=8)
        res = simulate(jobs, comm="srsf1", fusion="none", **ZOO_KW)
        assert res.comm_started_contended == 0
        assert res.comm_started_clean > 0


# ---------------------------------------------------------------------------
# DAG validity of WFBP traces (satellite: Hypothesis property test)
# ---------------------------------------------------------------------------


def intervals_by_job(trace):
    """Parse the simulator's task trace into per-job TaskRef interval maps
    (legacy kinds 'f'/'b'/'c'; WFBP kinds 'b<seg>'/'c<seg>')."""
    per_job = {}
    for (jid, it, kind, w, t0, t1) in trace:
        if kind in ("f", "b", "c"):
            ref = TaskRef(jid, it, TaskKind(kind), w if kind != "c" else -1)
        else:
            seg = int(kind[1:])
            k = TaskKind(kind[0])
            ref = TaskRef(jid, it, k, w if k is not TaskKind.ALLREDUCE else -1, seg)
        per_job.setdefault(jid, {})[ref] = (t0, t1)
    return per_job


def normalize_single_bucket(intervals):
    """A one-bucket WFBP plan emits segment 0; the formal monolithic DAG
    uses segment -1 — remap when exactly one segment exists."""
    segs = {r.segment for r in intervals if r.kind is TaskKind.ALLREDUCE}
    if segs == {0}:
        return {
            dataclasses.replace(r, segment=-1): iv for r, iv in intervals.items()
        }
    return intervals


def validate_run(jobs, fusion, comm="ada", **kw):
    res = simulate(
        jobs, fusion=fusion, record_trace=True, fuse_fb=False, comm=comm, **kw
    )
    assert len(res.jct) == len(jobs)
    per_job = intervals_by_job(res.task_trace)
    specs = {j.job_id: j for j in jobs}
    for jid, intervals in per_job.items():
        spec = specs[jid]
        comm_refs = [r for r in intervals if r.kind is TaskKind.ALLREDUCE]
        has_comm = bool(comm_refs)
        n_buckets = max((r.segment for r in comm_refs), default=-1) + 1
        if n_buckets <= 1:
            intervals = normalize_single_bucket(intervals)
            n_buckets = 1
        dag = build_job_dag(jid, spec.n_gpus, spec.iterations, has_comm, n_buckets)
        ok, msg = validate_schedule(dag, intervals)
        assert ok, f"job {jid} (fusion={fusion}): {msg}"
    return res


class TestDagValidity:
    @pytest.mark.parametrize("fusion", ["all", "none", 32e6])
    def test_trace_is_valid_linear_extension(self, fusion):
        jobs = zoo_jobs(n=3, iters=4) + [
            JobSpec(3, 0.0, 1, 6, zoo_profiles()["llama32_1b"]),  # no comm
        ]
        validate_run(jobs, fusion, **ZOO_KW)

    def test_comm_overlaps_backward(self):
        """The point of WFBP: some bucket transfer must run concurrently
        with a backward segment of the same job and iteration."""
        res = simulate(
            zoo_jobs(n=1, iters=5), fusion="none", record_trace=True,
            fuse_fb=False, **ZOO_KW
        )
        per_job = intervals_by_job(res.task_trace)
        overlapped = False
        for intervals in per_job.values():
            comms = [(r, iv) for r, iv in intervals.items()
                     if r.kind is TaskKind.ALLREDUCE]
            bwds = [(r, iv) for r, iv in intervals.items()
                    if r.kind is TaskKind.BACKWARD]
            for cr, (c0, c1) in comms:
                for br, (b0, b1) in bwds:
                    if br.iteration == cr.iteration and br.segment > cr.segment:
                        if min(c1, b1) - max(c0, b0) > 1e-6:
                            overlapped = True
        assert overlapped

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    @settings(max_examples=20, deadline=None)
    @given(
        n_jobs=st.integers(1, 3),
        iters=st.integers(1, 4),
        n_gpus=st.sampled_from([4, 8]),
        fusion=st.sampled_from(["all", "none", 16e6, 64e6, 1e12]),
        comm=st.sampled_from(["ada", "srsf1", "srsf2"]),
        arch=st.sampled_from(["mamba2_130m", "llama32_1b"]),
    )
    def test_property_every_trace_is_valid(
        self, n_jobs, iters, n_gpus, fusion, comm, arch
    ):
        """Hypothesis sweep: every event-sim trace — with and without
        fusion, any gating policy — is a valid linear extension of the
        layer-granular DAG (overlap edges included)."""
        m = zoo_profiles()[arch]
        jobs = [
            JobSpec(i, float(i % 2), n_gpus, iters, m) for i in range(n_jobs)
        ]
        validate_run(jobs, fusion, comm=comm, n_servers=2, gpus_per_server=4,
                     gpu_mem_mb=ZOO_GPU_MEM_MB)


# ---------------------------------------------------------------------------
# Fluid backend: chunked drain over the static [jobs, buckets] matrix
# ---------------------------------------------------------------------------


class TestFluidWfbp:
    def test_fusion_all_bit_exact_vs_layer_stripped(self):
        """Acceptance-criteria lock (fluid side): fusion='all' on
        layer-granular profiles is bit-identical to the layer-stripped
        monolithic workload."""
        import numpy as np

        scn = get_scenario("model_zoo", seed=1, n_jobs=8, min_iters=10,
                           max_iters=30, horizon_s=200.0, fusion="all")
        mono = strip_scenario(scn)
        a = run_scenario_fluid(scn, comm="ada", dt=0.05)
        b = run_scenario_fluid(mono, comm="ada", dt=0.05)
        np.testing.assert_array_equal(np.asarray(a["jct"]), np.asarray(b["jct"]))
        assert float(a["makespan"]) == float(b["makespan"])

    def test_explicit_monolithic_planes_bit_exact(self):
        """A (jobs, 1) bucket matrix is the same trajectory as no bucket
        planes at all — the generalized state machine collapses exactly."""
        import numpy as np

        from repro.core.jaxsim import (
            simulate_traces_batched, stack_traces, trace_from_jobs,
        )
        from repro.scenarios.sweep import fluid_config

        scn = get_scenario("smoke")
        cfg = fluid_config(scn, comm="ada")
        plain = trace_from_jobs(scn.job_list())
        planes = dict(plain)
        planes["bucket_bytes"] = plain["msg_bytes"][:, None]
        planes["n_buckets"] = np.ones((scn.n_jobs,), np.int32)
        a = simulate_traces_batched(stack_traces([plain]), cfg)
        b = simulate_traces_batched(stack_traces([planes]), cfg)
        np.testing.assert_array_equal(np.asarray(a["jct"]), np.asarray(b["jct"]))

    @pytest.mark.parametrize("fusion", ["none", 32e6])
    def test_bucketed_fluid_completes_and_orders_like_event(self, fusion):
        from repro.scenarios import QUICK_OVERRIDES

        scn = dataclasses.replace(
            get_scenario("fusion_sweep", seed=1, **QUICK_OVERRIDES["fusion_sweep"]),
            fusion=fusion,
        )
        fl = run_scenario_fluid(scn, comm="ada", dt=0.01)
        ev = run_scenario_event(scn, comm="ada")
        assert int(fl["finished"].sum()) == scn.n_jobs
        fl_avg = float(fl["jct"][fl["finished"]].mean())
        assert ev.avg_jct() / 2.0 <= fl_avg <= ev.avg_jct() * 2.0

    def test_stack_traces_pads_bucket_planes(self):
        import numpy as np

        from repro.core.jaxsim import stack_traces, trace_from_jobs

        zoo = zoo_profiles()
        j1 = [JobSpec(0, 0.0, 8, 5, zoo["mamba2_130m"])]
        j2 = [JobSpec(0, 0.0, 8, 5, zoo["llama32_1b"]),
              JobSpec(1, 0.0, 4, 5, TABLE_III["vgg16"])]
        batch = stack_traces([
            trace_from_jobs(j1, fusion="none"),
            trace_from_jobs(j2, fusion="none"),
        ])
        bb = np.asarray(batch["bucket_bytes"])
        nb = np.asarray(batch["n_buckets"])
        assert bb.shape[0] == 2 and bb.shape[1] == 2  # lanes x padded jobs
        assert bb.shape[2] == 25  # mamba2: 24 layers + embed
        assert nb[0, 0] == 25 and nb[1, 0] == 17 and nb[1, 1] == 1
        # padded lane-0 job is inert
        assert not bool(np.asarray(batch["valid"])[0, 1])

    def test_mixed_lanes_without_planes_get_monolithic_ones(self):
        import numpy as np

        from repro.core.jaxsim import stack_traces, trace_from_jobs

        j = [JobSpec(0, 0.0, 4, 5, TABLE_III["vgg16"])]
        batch = stack_traces([
            trace_from_jobs(j, fusion="none"),
            trace_from_jobs(j),  # no planes
        ])
        nb = np.asarray(batch["n_buckets"])
        assert nb.shape == (2, 1) and nb[1, 0] == 1


# ---------------------------------------------------------------------------
# Legacy ring-edge "link" reading as dynamic topology domains (satellite)
# ---------------------------------------------------------------------------


class TestRingEdgeTopology:
    def legacy_edges(self, servers):
        """The exact inline formula the simulator used before PR 4."""
        ring = sorted(servers)
        return frozenset(
            (ring[i], ring[(i + 1) % len(ring)]) for i in range(len(ring))
        )

    @pytest.mark.parametrize(
        "servers",
        [(0, 1), (1, 3), (0, 1, 2), (0, 2, 5), (1, 4, 6, 7), (0, 3, 4, 5, 7)],
    )
    def test_matches_legacy_inline_formula(self, servers):
        topo = RingEdgeTopology(8)
        got = {(u, v) for (_, u, v) in topo.loaded_domains(set(servers))}
        assert got == self.legacy_edges(set(servers))

    def test_single_server_loads_nothing(self):
        assert RingEdgeTopology(4).loaded_domains({2}) == frozenset()

    def test_unit_oversub_and_no_incidence(self):
        topo = RingEdgeTopology(4)
        assert topo.oversub_of(("edge", 0, 1)) == 1.0
        with pytest.raises(NotImplementedError):
            topo.incidence()
        with pytest.raises(ValueError):
            topo.loaded_domains({0, 9})

    def test_two_server_pair_equivalent_to_nic_reading(self):
        """Every comm task spanning the same server pair: ring edges and
        NIC cuts count identical contenders, so the two readings must be
        bit-exact — the equivalence lock for the migration."""
        jobs = [
            JobSpec(0, 0.0, 8, 60, TABLE_III["vgg16"]),
            JobSpec(1, 0.5, 8, 60, TABLE_III["resnet50"]),
        ]
        kw = dict(n_servers=2, gpus_per_server=4)
        for comm in ("ada", "srsf1", "srsf2"):
            a = simulate(jobs, comm=comm, contention_domain="server", **kw)
            b = simulate(jobs, comm=comm, contention_domain="link", **kw)
            assert a.jct == b.jct, comm
            assert a.events_processed == b.events_processed

    def test_disjoint_rings_on_shared_server_still_overlap(self):
        """The behavioral point of the link reading (kept from the PR 3
        suite): rings sharing a server but no edge do not contend."""
        topo = RingEdgeTopology(3)
        a = topo.loaded_domains({0, 1})
        b = topo.loaded_domains({1, 2})
        assert not (a & b)
