"""Fast-path equivalence locks for the chunked fluid simulator.

The fluid step (``core/jaxsim.py``) gates bucketed traces with a one-shot
closure (``netmodel.gating_fixed_point``) and runs with periodic lane/job
compaction (``compact``) and next-event skipping (``skip``).  This module
pins the equivalences these promise:

* closure vs the sequential 4-round re-gating loop it replaced
  (:func:`_legacy_rounds`, patched in for a second run): bit-exact
  metrics on the fusion x policy grid (both sides run ``skip=False``:
  the skipper reads which candidates were left unstarted, so skip is held
  off for a bit-exact comparison);
* compaction on vs off: bit-exact metrics on two registry cells (lane
  retirement + job-axis trimming are pure re-indexing);
* the driver's one chunk in flight vs a synchronous launch-read-retire
  loop (:func:`_synchronous_driver`): bit-exact results and uplink
  counters, one chunk more;
* recompile guard: the whole 6-policy gating matrix shares at most two
  compiled chunk graphs per trace shape (threshold policies ride the
  dynamic-policy sentinel, exact k-way the second graph);
* the streaming-arrival engine stress cell scales linearly in events and
  keeps the calendar bounded by arrivals + O(cluster).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.core import jaxsim, netmodel
from repro.core.jaxsim import (
    simulate_traces_batched,
    stack_traces,
    trace_from_jobs,
)
from repro.scenarios import QUICK_OVERRIDES, get_scenario
from repro.scenarios.sweep import FLUID_POLICIES, fluid_config


def _cell(scn_name, seeds, comm="ada", placement="lwf", fusion=None,
          dt=0.05, **fast_kw):
    """A registry cell's stacked batch and fluid config."""
    over = QUICK_OVERRIDES.get(scn_name, {})
    scns = [get_scenario(scn_name, seed=s, **over) for s in seeds]
    cfg = fluid_config(scns[0], comm=comm, placement=placement, dt=dt,
                       **fast_kw)
    fus = scns[0].fusion if fusion is None else fusion
    batch = stack_traces(
        [trace_from_jobs(s.job_list(), fusion=fus) for s in scns]
    )
    return batch, cfg


def _run_cell(scn_name, seeds, **kw):
    """One batched fluid run of a registry cell, as plain numpy arrays."""
    out = simulate_traces_batched(*_cell(scn_name, seeds, **kw))
    return {k: np.asarray(v) for k, v in out.items()}


def _assert_identical(a, b):
    np.testing.assert_array_equal(a["finished"], b["finished"])
    np.testing.assert_array_equal(a["jct"], b["jct"])
    np.testing.assert_array_equal(a["makespan"], b["makespan"])


def _legacy_rounds(r1, priority, loads, counts, overlap, active, rem,
                   new_cost, max_ways, threshold_gated, dual_threshold, *,
                   exact_kway=False, eta_over_b=None):
    """The sequential re-gating loop, with the signature of
    ``netmodel.gating_fixed_point``: from the candidates ``r1``, each of
    4 rounds starts the smallest-``priority`` one that passes against the
    active set as it stands, this tick's earlier starts included."""
    idx = jnp.arange(r1.shape[-1])
    started = r1 & (idx == jnp.argmin(jnp.where(r1, priority, jnp.inf)))
    for _ in range(3):
        active_now = active | started
        k_would = netmodel.domain_k(
            loads, netmodel.domain_counts(loads, active_now), extra=1)
        olds = overlap & active_now[None, :]
        min_old_rem = jnp.where(olds, rem[None, :], jnp.inf).min(axis=1)
        exact = (dict(exact_kway_olds=olds, rem=rem, eta_over_b=eta_over_b)
                 if exact_kway else {})
        ok = r1 & ~started & netmodel.may_start_dynamic(
            k_would, new_cost, min_old_rem, max_ways, threshold_gated,
            dual_threshold, **exact)
        started = started | (ok & (idx == jnp.argmin(
            jnp.where(ok, priority, jnp.inf))))
    return started


def _shipped_and_rounds(monkeypatch, **kw):
    """One ``model_zoo`` cell as shipped and with :func:`_legacy_rounds`
    in place of the closure; the jit caches are cleared around the
    patched run so that neither side reuses the other's program."""
    shipped = _run_cell("model_zoo", **kw)
    jax.clear_caches()
    with monkeypatch.context() as m:
        m.setattr(netmodel, "gating_fixed_point", _legacy_rounds)
        rounds = _run_cell("model_zoo", **kw)
    jax.clear_caches()
    return shipped, rounds


class TestGatingFixedPoint:
    """One-shot gating closure == the sequential 4-round re-gating loop."""

    @pytest.mark.parametrize("fusion", ["none", 16e6])
    @pytest.mark.parametrize("comm", ["ada", "srsf2", "kway2"])
    def test_bit_exact_on_fusion_policy_grid(self, fusion, comm, monkeypatch):
        fp, rounds = _shipped_and_rounds(
            monkeypatch, seeds=(0, 1), comm=comm, fusion=fusion, skip=False)
        _assert_identical(fp, rounds)
        assert fp["finished"].any()  # the cell actually exercises gating

    def test_monolithic_trace_unaffected_by_gating_knob(self, monkeypatch):
        # fusion="all" has one bucket: the closure never runs, so swapping
        # it must leave the monolithic path's results as they are
        fp, rounds = _shipped_and_rounds(
            monkeypatch, seeds=(0,), comm="ada", fusion="all", skip=False)
        _assert_identical(fp, rounds)


class TestCompaction:
    """Lane retirement / job-axis trimming is pure re-indexing: metrics on
    the registry cells are bit-identical with compaction disabled."""

    def test_oversub_fabric_cell(self):
        kw = dict(seeds=(0, 1, 2, 3), comm="ada")
        on = _run_cell("oversub_fabric", compact=True, **kw)
        off = _run_cell("oversub_fabric", compact=False, **kw)
        _assert_identical(on, off)

    def test_model_zoo_wfbp_cell(self):
        kw = dict(seeds=(0, 1), comm="srsf2", fusion=16e6)
        on = _run_cell("model_zoo", compact=True, **kw)
        off = _run_cell("model_zoo", compact=False, **kw)
        _assert_identical(on, off)


def _synchronous_driver(batch, cfg):
    """The batched driver as a synchronous loop: launch a chunk, read the
    whole state back, retire the lanes that finished in it; no
    compaction.  Returns the result planes, the per-lane uplink
    counters where the fabric has them, and the chunks launched."""
    max_ways, gated, key = jaxsim._policy_args(cfg)
    traces = {k: jnp.asarray(v) for k, v in batch.items()}
    state = jaxsim._init_jit(traces, key)
    n_lanes, n_jobs = batch["arrival"].shape
    counters = [k for k in jaxsim.UPLINK_COUNTERS if k in state]
    out = {"jct": np.full((n_lanes, n_jobs), np.inf, np.float32),
           "finished": np.zeros((n_lanes, n_jobs), bool),
           "makespan": np.zeros((n_lanes,), np.float32),
           **{k: np.zeros((n_lanes,), np.int64) for k in counters}}
    retired = np.zeros((n_lanes,), bool)
    chunks = 0
    while not retired.all():
        state = jaxsim._chunk_jit(traces, state, key, max_ways, gated)
        chunks += 1
        st = jax.device_get(state)
        done = (st["n_done"] >= n_jobs) | (st["i"] >= key.max_steps)
        for lane in np.nonzero(done & ~retired)[0]:
            fin = (st["phase"][lane] == jaxsim.DONE) & batch["valid"][lane]
            out["jct"][lane] = st["finish"][lane] - batch["arrival"][lane]
            out["finished"][lane] = fin
            out["makespan"][lane] = (st["finish"][lane][fin].max()
                                     if fin.any() else st["t"][lane])
            for k in counters:
                out[k][lane] = st[k][lane]
        retired |= done
    return out, chunks


#: (cell, seeds, keywords): the paper's NICs, a two-tier fabric with
#: uplink counters, and WFBP bucketed traces (bucket trim on compaction)
DRIVER_CELLS = {
    "paper": ("paper", (0, 1, 2), {}),
    "oversub_fabric": ("oversub_fabric", (0, 1, 2), {}),
    "model_zoo_wfbp": ("model_zoo", (0, 1, 2),
                       dict(comm="srsf2", fusion=16e6)),
}


class TestChunkInFlight:
    """The driver launches chunk k+1 before it reads chunk k's flags: the
    results are the synchronous loop's to the bit, with one chunk more."""

    @pytest.mark.parametrize("compact", [True, False])
    @pytest.mark.parametrize("cell", sorted(DRIVER_CELLS))
    def test_bit_exact_against_synchronous_loop(self, cell, compact):
        name, seeds, kw = DRIVER_CELLS[cell]
        batch, cfg = _cell(name, seeds, compact=compact, **kw)
        want, chunks = _synchronous_driver(batch, cfg)
        got = simulate_traces_batched(batch, cfg)
        uplinks = set(want) - {"jct", "finished", "makespan"}
        assert uplinks == (set(jaxsim.UPLINK_COUNTERS)
                           if name == "oversub_fabric" else set())
        assert all(want[k].sum() > 0 for k in uplinks)
        for k, v in want.items():
            np.testing.assert_array_equal(np.asarray(got[k]), v, err_msg=k)
        assert want["finished"].any()
        assert got["chunks"] == chunks + 1
        assert 0 <= got["drained_launches"] <= got["chunks"] - 1
        assert (got["compactions"] >= 1) == compact

    def test_drained_launches_count_a_chunk_waited_for(self, monkeypatch):
        """A chunk program that returns only when its chunk has finished
        drains the device before every launch after the first."""
        chunk_jit = jaxsim._chunk_jit

        def waited_for(*args, **kw):
            return jax.block_until_ready(chunk_jit(*args, **kw))

        monkeypatch.setattr(jaxsim, "_chunk_jit", waited_for)
        name, seeds, kw = DRIVER_CELLS["paper"]
        out = simulate_traces_batched(*_cell(name, seeds, compact=False, **kw))
        assert out["chunks"] >= 2
        assert out["drained_launches"] == out["chunks"] - 1


class TestRecompileGuard:
    def test_policy_matrix_shares_compiled_graphs(self):
        """All six gating policies at one trace shape compile at most two
        chunk graphs: every threshold policy (ada / srsf1-3) traces through
        the dynamic-policy sentinel with thresholds as runtime arrays, and
        the exact k-way policies share the lookahead graph.  ``compact``
        is off so the whole run stays at one (lane, job, bucket) shape."""
        over = QUICK_OVERRIDES["oversub_fabric"]
        scn = get_scenario("oversub_fabric", seed=0, **over)
        batch = stack_traces([trace_from_jobs(scn.job_list())])
        before = jaxsim._chunk_jit._cache_size()
        for comm in FLUID_POLICIES:
            cfg = fluid_config(scn, comm=comm, compact=False)
            out = simulate_traces_batched(batch, cfg)
            assert np.asarray(out["finished"]).any()
        grown = jaxsim._chunk_jit._cache_size() - before
        assert grown <= 2, (
            f"6-policy matrix compiled {grown} new chunk graphs (expected "
            "<= 2: one dynamic-threshold, one exact k-way)"
        )


class TestEngineStreamStress:
    """Smoke-sized twin of the ``--only engine`` 10k-job stress cell."""

    def _run(self, n_jobs):
        from benchmarks.run import stream_trace

        from repro.core import simulate

        jobs = stream_trace(n_jobs, seed=0)
        return simulate(jobs, placement="lwf", comm="ada",
                        n_servers=16, gpus_per_server=2)

    def test_events_linear_and_calendar_bounded(self):
        small = self._run(250)
        big = self._run(500)
        assert len(small.jct) == 250 and len(big.jct) == 500
        # iteration counts are iid across jobs: events scale ~linearly
        ratio = big.events_processed / small.events_processed
        assert 1.7 < ratio < 2.3, ratio
        # the calendar holds every future arrival (pushed up front) plus a
        # bounded set of live simulation events — O(cluster), not O(jobs)
        n_gpus = 16 * 2
        assert small.peak_calendar <= 250 + 2 * n_gpus
        assert big.peak_calendar <= 500 + 2 * n_gpus
        assert big.peak_calendar >= 500  # arrivals alone reach n_jobs

    def test_streaming_feed_keeps_calendar_o_cluster(self):
        """The same workload through a TraceSource: identical results, but
        the calendar peak is bounded by live jobs + O(cluster) instead of
        growing with the trace length — the invariant that lets the nightly
        100k-job replay run in bounded memory."""
        from benchmarks.run import stream_trace

        from repro.core import simulate
        from repro.core.trace import ListTraceSource

        n_gpus = 16 * 2
        peaks = {}
        for n_jobs in (250, 500):
            jobs = stream_trace(n_jobs, seed=0)
            kw = dict(placement="lwf", comm="ada",
                      n_servers=16, gpus_per_server=2)
            lst = simulate(jobs, **kw)
            stream = simulate(ListTraceSource(jobs), **kw)
            assert stream.jct == lst.jct
            assert stream.finish == lst.finish
            assert stream.events_processed == lst.events_processed
            peaks[n_jobs] = stream.peak_calendar
            # one-ahead arrival + per-run events: O(live + cluster)
            assert stream.peak_calendar <= 4 * n_gpus, stream.peak_calendar
        # doubling the trace must NOT grow the streaming calendar —
        # footprint tracks concurrency, not trace length
        assert peaks[500] <= peaks[250] + n_gpus // 2, peaks
