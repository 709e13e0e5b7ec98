"""Scenario-engine tests: registry sanity, per-scenario invariants, and the
paper's policy-ordering regressions (Ada-SRSF vs SRSF(1)/SRSF(2) avg JCT,
LWF-kappa vs first-fit makespan) locked on fixed-seed downsized scenarios."""

import dataclasses
import functools

import pytest

from repro.core.contention import ContentionParams
from repro.scenarios import (
    QUICK_OVERRIDES,
    get_scenario,
    run_scenario_event,
    scenario_names,
    summarize,
    sweep,
)

#: Fixed seeds for the regression tests, paired with the shared downsized
#: QUICK_OVERRIDES sizing.  Each (seed, overrides) cell was verified to
#: (a) finish every job and (b) satisfy the paper orderings; any scheduler
#: change that breaks one of them is a regression (or a finding worth an
#: EXPERIMENTS.md entry).
REGRESSION_SEEDS = {
    "paper": 0,
    "philly_heavy_tail": 1,
    "bursty_diurnal": 1,
    "hetero_bandwidth": 1,
    "large_job_dominated": 1,
    "adversarial_allbig": 1,
    "contended_residue": 1,
    "oversub_fabric": 1,
    "rack_locality": 1,
    "model_zoo": 1,
    "fusion_sweep": 1,
    # the preemptive/elastic cells run their *static* defaults here (the
    # generic ordering locks); the sched-policy gains are regression-locked
    # separately in tests/test_engine.py
    "preemption_gain": 2,
    "elastic_surge": 1,
    "smoke": 0,
    # chaos cells run their registered fault specs (event-only); seeds
    # verified to keep every ordering AND inject faults (faults > 0).
    # The recovery-storm gating finding is locked separately in
    # tests/test_chaos.py::TestRecoveryStormFinding on its own seeds.
    "chaos_steady": 1,
    "chaos_recovery_storm": 3,
    "chaos_stragglers": 1,
    # trace-replay cells run through the streaming TraceSource path of
    # run_scenario_event (bit-identical to list mode; the streaming engine
    # is locked separately in tests/test_tracesource.py)
    "trace_replay_synth": 0,
    "trace_replay_philly": 0,
    "trace_replay_alibaba": 0,
}

#: Scenarios whose workload does not derive from ``seed``: the fully
#: deterministic smoke cell and the CSV trace replays (a replayed file is
#: the same file at every seed).
SEED_INDEPENDENT = {"smoke", "trace_replay_philly", "trace_replay_alibaba"}
REGRESSION_CELLS = {
    name: (seed, QUICK_OVERRIDES[name]) for name, seed in REGRESSION_SEEDS.items()
}

RTOL = 5e-3  # numerical slack on the <= orderings


def small(name):
    seed, overrides = REGRESSION_CELLS[name]
    return get_scenario(name, seed=seed, **overrides)


@functools.lru_cache(maxsize=None)
def sim(name, comm="ada", placement="lwf"):
    """Memoized event-sim run of a regression cell (results are reused
    across the ordering tests; simulations are deterministic)."""
    return run_scenario_event(small(name), comm=comm, placement=placement)


class TestRegistry:
    def test_at_least_six_scenarios(self):
        assert len(scenario_names()) >= 6
        assert set(REGRESSION_CELLS) == set(scenario_names())

    def test_unknown_scenario_raises(self):
        with pytest.raises(KeyError, match="unknown scenario"):
            get_scenario("nope")

    def test_duplicate_registration_raises(self):
        from repro.scenarios import register

        with pytest.raises(ValueError, match="already registered"):
            register("smoke")(lambda seed=0: None)

    @pytest.mark.parametrize("name", sorted(REGRESSION_CELLS))
    def test_seed_determinism(self, name):
        a, b = small(name), small(name)
        assert a.jobs == b.jobs
        assert a.params == b.params

    @pytest.mark.parametrize(
        "name", [n for n in sorted(REGRESSION_CELLS) if n not in SEED_INDEPENDENT]
    )
    def test_different_seeds_differ(self, name):
        _, overrides = REGRESSION_CELLS[name]
        a = get_scenario(name, seed=100, **overrides)
        b = get_scenario(name, seed=101, **overrides)
        assert a.jobs != b.jobs


class TestScenarioInvariants:
    @pytest.mark.parametrize("name", sorted(REGRESSION_CELLS))
    def test_well_formed(self, name):
        scn = small(name)
        jobs = scn.job_list()
        assert len(jobs) > 0
        assert len({j.job_id for j in jobs}) == len(jobs)
        assert all(j.arrival >= 0 for j in jobs)
        assert all(jobs[i].arrival <= jobs[i + 1].arrival for i in range(len(jobs) - 1))
        assert all(0 < j.n_gpus <= scn.total_gpus for j in jobs)
        assert all(j.iterations >= 1 for j in jobs)
        cluster, jlist, params = scn.build()
        assert cluster.n_servers == scn.n_servers
        assert len(jlist) == scn.n_jobs
        assert isinstance(params, ContentionParams)

    def test_fresh_cluster_per_build(self):
        scn = small("smoke")
        c1, c2 = scn.make_cluster(), scn.make_cluster()
        assert c1 is not c2
        c1.gpus[(0, 0)].mem_used_mb = 999.0
        assert c2.gpus[(0, 0)].mem_used_mb == 0.0

    def test_smoke_is_fully_deterministic(self):
        assert get_scenario("smoke", seed=0).jobs == get_scenario("smoke", seed=7).jobs

    def test_hetero_bandwidth_has_slow_servers(self):
        scn = small("hetero_bandwidth")
        bw = scn.params.server_bandwidth
        assert len(bw) == scn.n_servers
        assert min(bw) < 1.0 < max(bw) + 1e-9

    def test_hetero_bandwidth_slows_jobs_down(self):
        """Same workload on a degraded network must not finish sooner."""
        scn = small("hetero_bandwidth")
        homog = dataclasses.replace(scn, params=ContentionParams())
        slow = run_scenario_event(scn, comm="ada")
        fast = run_scenario_event(homog, comm="ada")
        assert slow.avg_jct() >= fast.avg_jct() * (1 - RTOL)
        assert slow.makespan >= fast.makespan * (1 - RTOL)

    @pytest.mark.parametrize("name", sorted(REGRESSION_CELLS))
    def test_no_horizon_censoring(self, name):
        """Every regression cell must drain completely: the explicit
        ``SimResult.censored`` count (jobs cut off by a ``max_time``
        horizon, which used to vanish silently from the JCT stats) is
        asserted zero so truncation can never corrupt a locked ordering.
        This includes every chaos cell: a breakdown-preempted job still
        queued when the run drains would show up here, not vanish."""
        res = sim(name, comm="ada")
        assert res.censored == 0
        assert len(res.jct) == small(name).n_jobs

    @pytest.mark.parametrize(
        "name", [n for n in sorted(REGRESSION_CELLS) if n.startswith("chaos_")]
    )
    def test_chaos_cells_actually_inject(self, name):
        """A chaos regression cell whose spec never fires would silently
        degenerate to its fault-free baseline — require the injector to
        land at least one fault event at the locked seed."""
        scn = small(name)
        assert scn.chaos is not None and scn.chaos.active
        res = sim(name, comm="ada")
        assert res.faults > 0
        assert res.goodput > 0.0

    def test_topology_scenarios_carry_a_fabric(self):
        for name in ("oversub_fabric", "rack_locality"):
            scn = small(name)
            assert scn.topology is not None
            assert scn.topology.n_servers == scn.n_servers
            assert max(d.oversub for d in scn.topology.domains) > 1.0
            assert len(scn.topology.racks) >= 2


class TestPhillyCalibration:
    """philly_heavy_tail is calibrated against the published Philly-trace
    statistics (Jeon et al., ATC'19): the scale-free duration-quantile
    ratios and the single-GPU-dominated request mix.  Fixed seed so any
    change to the generator's shape parameters trips this lock."""

    def _durations_and_gpus(self, seed):
        import numpy as np

        scn = get_scenario("philly_heavy_tail", seed=seed, n_jobs=4000)
        dur = np.asarray([j.iterations * j.model.t_iter_compute for j in scn.jobs])
        gpus = np.asarray([j.n_gpus for j in scn.jobs])
        return dur, gpus

    def test_duration_tail_ratios_match_published(self):
        import numpy as np

        from repro.scenarios.library import (
            PHILLY_DURATION_P90_OVER_P50,
            PHILLY_DURATION_P95_OVER_P50,
        )

        dur, _ = self._durations_and_gpus(seed=7)
        p50, p90, p95 = np.percentile(dur, [50, 90, 95])
        assert p90 / p50 == pytest.approx(PHILLY_DURATION_P90_OVER_P50, rel=0.25)
        assert p95 / p50 == pytest.approx(PHILLY_DURATION_P95_OVER_P50, rel=0.30)

    def test_gpu_request_mix_matches_published(self):
        import numpy as np

        from repro.scenarios.library import PHILLY_GPU_WEIGHTS

        _, gpus = self._durations_and_gpus(seed=7)
        weights = dict(PHILLY_GPU_WEIGHTS)
        assert float(np.mean(gpus == 1)) == pytest.approx(weights[1], abs=0.03)
        assert float(np.mean(gpus >= 8)) == pytest.approx(
            weights[8] + weights[16] + weights[32], abs=0.02
        )

    def test_alpha_solves_the_p90_identity(self):
        import math

        from repro.scenarios.library import (
            PHILLY_DURATION_P90_OVER_P50,
            PHILLY_PARETO_ALPHA,
        )

        # p90/p50 of a Pareto(alpha) is 5**(1/alpha)
        assert 5.0 ** (1.0 / PHILLY_PARETO_ALPHA) == pytest.approx(
            PHILLY_DURATION_P90_OVER_P50, rel=1e-12
        )


class TestBurstyIntensityCalibration:
    """The bursty_diurnal arrival intensity is a calibrated knob
    (peak-to-mean arrival-rate ratio), not a hand-picked burst fraction
    (ROADMAP item from PR 3).  Fixed seed: any change to the
    burst_fraction identity or the generator shape trips these locks."""

    def _peak_to_mean(self, peak_to_mean, seed=11, n_jobs=4000):
        import numpy as np

        scn = get_scenario(
            "bursty_diurnal", seed=seed, n_jobs=n_jobs, peak_to_mean=peak_to_mean
        )
        arr = np.asarray([j.arrival for j in scn.jobs])
        # arrival-rate histogram at the burst width (sigma = H/60 = 20 s)
        counts, _ = np.histogram(arr, bins=60, range=(0.0, 1200.0))
        return counts.max() / counts.mean()

    def test_default_reproduces_legacy_burst_fraction(self):
        """peak_to_mean=4 at the default shape solves to the previous
        hand-picked burst_frac=0.6 (the identity's calibration anchor)."""
        import math

        from repro.scenarios.library import BURSTY_PEAK_TO_MEAN, burst_fraction

        frac = burst_fraction(BURSTY_PEAK_TO_MEAN, 1200.0, 4, 1200.0 / 60.0)
        assert frac == pytest.approx(0.6, abs=0.01)
        assert math.isclose(burst_fraction(1.0, 1200.0, 4, 20.0), 0.0)

    def test_realized_intensity_tracks_the_knob(self):
        """The realized peak-to-mean arrival-rate ratio follows the knob:
        monotone in it, and at the fixed seed the default knob's realized
        value is locked (a quantile lock like the Philly calibration).
        The realized max-bin ratio sits above the designed per-burst
        center intensity — bursts can overlap and the max over 60 bins is
        an extreme-value statistic — so the lock is on the measured value,
        not on knob == realized."""
        lo = self._peak_to_mean(1.5)
        mid = self._peak_to_mean(4.0)
        hi = self._peak_to_mean(5.5)
        assert lo < mid < hi
        assert mid == pytest.approx(7.05, rel=0.1)
        assert 1.0 * 4.0 <= mid <= 2.5 * 4.0

    def test_fixed_seed_lock(self):
        """Concrete-value lock on the default-knob workload (seed 1): any
        change to the burst_fraction identity, the RNG draw order, or the
        arrival formula shifts these pinned numbers."""
        a = get_scenario("bursty_diurnal", seed=1, n_jobs=32)
        assert [j.arrival for j in a.jobs[:6]] == [
            151.0, 203.0, 217.0, 221.0, 232.0, 235.0,
        ]
        assert [(j.n_gpus, j.iterations) for j in a.jobs[:3]] == [
            (1, 789), (1, 1317), (2, 3986),
        ]
        assert sum(j.arrival for j in a.jobs) == 17439.0

    def test_knob_validation(self):
        with pytest.raises(ValueError, match="peak_to_mean"):
            get_scenario("bursty_diurnal", seed=0, n_jobs=4, peak_to_mean=0.5)


class TestPaperOrderings:
    """The paper's headline orderings, locked per scenario on fixed seeds."""

    #: WFBP regime shift (documented finding, not a bug): with fine-grained
    #: bucketed transfers (fusion_sweep), AdaDUAL's pairwise-overlap
    #: acceptance buys little — per-bucket overlap windows are short — while
    #: the eta penalty still accrues, so Ada-SRSF lands within ~2% of, but
    #: not strictly below, the exclusive-link SRSF(1) baseline.  The paper's
    #: strict ordering is a claim about monolithic iteration-level comm.
    SRSF1_SLACK = {"fusion_sweep": 2e-2}

    @pytest.mark.parametrize("name", sorted(REGRESSION_CELLS))
    def test_ada_beats_srsf_baselines(self, name):
        scn = small(name)
        ada = sim(name, comm="ada")
        srsf1 = sim(name, comm="srsf1")
        srsf2 = sim(name, comm="srsf2")
        assert len(ada.jct) == scn.n_jobs, "Ada-SRSF stranded jobs"
        assert len(srsf1.jct) == scn.n_jobs
        assert len(srsf2.jct) == scn.n_jobs
        slack = self.SRSF1_SLACK.get(name, RTOL)
        assert ada.avg_jct() <= srsf1.avg_jct() * (1 + slack), (
            f"{name}: Ada-SRSF {ada.avg_jct():.1f} vs SRSF(1) {srsf1.avg_jct():.1f}"
        )
        assert ada.avg_jct() <= srsf2.avg_jct() * (1 + RTOL), (
            f"{name}: Ada-SRSF {ada.avg_jct():.1f} vs SRSF(2) {srsf2.avg_jct():.1f}"
        )

    @pytest.mark.parametrize("name", sorted(REGRESSION_CELLS))
    def test_lwf_beats_first_fit_makespan(self, name):
        lwf = sim(name, comm="ada", placement="lwf")
        ff = sim(name, comm="ada", placement="ff")
        assert lwf.makespan <= ff.makespan * (1 + RTOL), (
            f"{name}: LWF-1 {lwf.makespan:.1f} vs FF {ff.makespan:.1f}"
        )


def _run_cell_report_backend(cell):
    """Spawn-pool worker body: the sweep's cell runner, then whether this
    process initialized a JAX backend."""
    from jax._src import xla_bridge

    from repro.scenarios.sweep import run_cell

    rec = run_cell(cell)
    return rec.avg_jct, xla_bridge.backends_are_initialized()


class TestSweepRunner:
    def test_matrix_shape_and_summary(self):
        records = sweep(
            ["smoke"], comms=("ada", "srsf2"), placements=("lwf", "ff"), seeds=(0, 1)
        )
        assert len(records) == 1 * 2 * 2 * 2
        agg = summarize(records)
        assert len(agg) == 4  # seeds collapse into the group key
        for v in agg.values():
            assert v["n_runs"] == 2.0
            assert v["finished_frac"] == 1.0

    def test_multiprocessing_matches_serial(self):
        kw = dict(comms=("ada",), seeds=(0, 1), overrides={})
        serial = sweep(["smoke"], processes=None, **kw)
        fanned = sweep(["smoke"], processes=2, **kw)
        assert [r.avg_jct for r in serial] == [r.avg_jct for r in fanned]
        assert [r.makespan for r in serial] == [r.makespan for r in fanned]

    def test_pool_workers_start_no_jax_backend(self):
        # the pool runs event cells only; a worker that brought up a JAX
        # backend would contend with its parent for the chip on a TPU host
        import multiprocessing as mp

        from repro.scenarios.sweep import SweepCell

        cells = [SweepCell("smoke", seed, "lwf", 1, "ada", "event")
                 for seed in (0, 1)]
        with mp.get_context("spawn").Pool(2) as pool:
            out = pool.map(_run_cell_report_backend, cells)
        serial = sweep(["smoke"], comms=("ada",), seeds=(0, 1))
        assert [avg for avg, _ in out] == [r.avg_jct for r in serial]
        assert not any(up for _, up in out)

    def test_policy_aliases(self):
        from repro.scenarios import canonical_comm

        assert canonical_comm("adadual") == "ada"
        assert canonical_comm("Ada-SRSF") == "ada"
        assert canonical_comm("srsf2") == "srsf2"


class TestMonteCarloCI:
    """The vmap-batched Monte-Carlo path: one device launch per cell,
    per-seed records identical to serial fluid runs, CellCI aggregation."""

    def test_batched_matches_serial_fluid(self):
        from repro.scenarios import monte_carlo_fluid, run_scenario_fluid

        seeds = (0, 1)
        recs = monte_carlo_fluid("contended_residue", seeds, comm="ada", dt=0.05)
        assert [r.seed for r in recs] == list(seeds)
        for r, seed in zip(recs, seeds):
            scn = get_scenario("contended_residue", seed=seed)
            out = run_scenario_fluid(scn, comm="ada", dt=0.05)
            serial = [float(j) for j, f in zip(out["jct"], out["finished"]) if f]
            assert r.n_finished == len(serial) == scn.n_jobs
            assert r.avg_jct == pytest.approx(sum(serial) / len(serial))
            assert r.makespan == pytest.approx(float(out["makespan"]))

    def test_capped_lane_raises(self):
        # a lane still running at the horizon cap would average only the
        # jobs that finished and read as a fast run
        from repro.scenarios import monte_carlo_fluid

        with pytest.raises(RuntimeError, match=r"horizon cap of 256 ticks"):
            monte_carlo_fluid("smoke", (0, 1), comm="ada", dt=0.05,
                              max_steps=256)

    def test_fluid_cell_is_one_lane_batch(self):
        from repro.scenarios import monte_carlo_fluid
        from repro.scenarios.sweep import SweepCell, run_cell

        rec = run_cell(SweepCell("smoke", 1, "lwf", 1, "srsf2", "fluid"))
        (want,) = monte_carlo_fluid("smoke", [1], comm="srsf2", dt=0.05)
        assert rec.n_finished == rec.n_jobs
        assert (rec.avg_jct, rec.makespan, rec.placement, rec.comm) == (
            want.avg_jct, want.makespan, want.placement, want.comm)

    def test_fluid_ci_preserves_paper_ordering(self):
        from repro.scenarios import sweep_ci

        cis = sweep_ci(
            ["contended_residue"],
            comms=("ada", "srsf2"),
            placements=("lwf",),
            seeds=(0, 1, 2),
            backend="fluid",
            dt=0.05,
        )
        by = {c.comm: c for c in cis}
        assert set(by) == {"ada", "srsf2"}
        for c in cis:
            assert c.n_seeds == 3
            assert c.finished_frac == 1.0
            assert c.avg_jct_std >= 0.0
            assert c.backend == "fluid"
        assert by["ada"].avg_jct_mean <= by["srsf2"].avg_jct_mean

    def test_ci_from_runs_math(self):
        from repro.scenarios import ci_from_runs, from_jcts

        recs = [
            from_jcts(
                [10.0 + off], scenario="s", backend="event", placement="p",
                comm="c", seed=i, n_jobs=1, makespan=20.0 + off,
            )
            for i, off in enumerate((-2.0, 0.0, 2.0))
        ]
        (ci,) = ci_from_runs(recs)
        assert ci.n_seeds == 3
        assert ci.avg_jct_mean == pytest.approx(10.0)
        assert ci.avg_jct_std == pytest.approx((8.0 / 3) ** 0.5)
        assert ci.makespan_mean == pytest.approx(20.0)
        assert ci.finished_frac == 1.0

    def test_means_are_left_folds(self):
        # builtin sum() compensates rounding from Python 3.12 on (it gives
        # 1.0 here); metrics fold left so results match on any interpreter
        from repro.scenarios import ci_from_runs, from_jcts

        jcts = [1e16, 1.0, -1e16]
        rec = from_jcts(jcts, scenario="s", backend="event", placement="p",
                        comm="c", seed=0, n_jobs=3, makespan=1.0)
        assert rec.avg_jct == 0.0
        recs = [dataclasses.replace(rec, seed=i, avg_jct=x)
                for i, x in enumerate(jcts)]
        assert ci_from_runs(recs)[0].avg_jct_mean == 0.0
