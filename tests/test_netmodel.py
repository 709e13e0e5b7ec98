"""Unit tests for the shared policy/network layer (``core/netmodel.py``):
the same predicates must give identical answers on Python scalars (event
backend path) and numpy arrays (the shape the fluid backend traces), and
must agree with the event-side wrappers in ``core/adadual.py``."""

import math

import numpy as np
import pytest

from repro.core import netmodel
from repro.core.adadual import adadual_should_start, srsf_n_should_start
from repro.core.contention import ContentionParams

P = ContentionParams()


class TestRateModel:
    def test_ratio_is_one_uncontended(self):
        assert netmodel.rate_ratio(1, P.b, P.eta) == pytest.approx(1.0)

    def test_ratio_matches_params_rate(self):
        for k in (1, 2, 3, 5):
            assert netmodel.rate(k, P.b, P.eta) == pytest.approx(P.rate(k))
            assert netmodel.rate_ratio(k, P.b, P.eta) == pytest.approx(
                P.rate(k) / P.rate(1)
            )

    def test_ratio_vectorizes(self):
        ks = np.array([1, 2, 4])
        out = netmodel.rate_ratio(ks, P.b, P.eta)
        assert out.shape == (3,)
        assert out[0] == pytest.approx(1.0)
        assert np.all(np.diff(out) < 0)  # more contention, smaller share


class TestServerBandwidth:
    def test_empty_is_homogeneous(self):
        assert np.all(netmodel.server_bandwidth_array((), 4) == 1.0)

    def test_pad_and_truncate(self):
        bw = netmodel.server_bandwidth_array((0.5, 2.0), 4)
        np.testing.assert_allclose(bw, [0.5, 2.0, 1.0, 1.0])
        bw = netmodel.server_bandwidth_array((0.5, 2.0, 3.0), 2)
        np.testing.assert_allclose(bw, [0.5, 2.0])

    def test_zero_servers(self):
        assert netmodel.server_bandwidth_array((0.5,), 0).shape == (0,)

    def test_slowest_member_matches_params(self):
        params = ContentionParams(server_bandwidth=(0.4, 1.0, 0.7))
        bw = netmodel.server_bandwidth_array(params.server_bandwidth, 4)
        for servers in ({0}, {1}, {0, 2}, {2, 3}, {1, 3}):
            mask = np.zeros(4, dtype=bool)
            mask[list(servers)] = True
            assert netmodel.slowest_member_scale(bw, mask) == pytest.approx(
                params.bandwidth_scale(servers)
            ), servers

    def test_slowest_member_no_members_is_nominal(self):
        bw = netmodel.server_bandwidth_array((0.4,), 3)
        assert netmodel.slowest_member_scale(bw, np.zeros(3, bool)) == 1.0

    def test_slowest_member_batched(self):
        bw = np.array([0.4, 1.0, 0.7])
        masks = np.array([[1, 0, 1], [0, 1, 0], [0, 0, 0]], dtype=bool)
        out = netmodel.slowest_member_scale(bw, masks)
        np.testing.assert_allclose(out, [0.4, 1.0, 1.0])


class TestParsePolicy:
    def test_known(self):
        assert netmodel.parse_policy("ada") == netmodel.PolicySpec("ada", 2, True)
        assert netmodel.parse_policy("srsf1") == netmodel.PolicySpec("srsf1", 1, False)
        assert netmodel.parse_policy("srsf3") == netmodel.PolicySpec("srsf3", 3, False)
        assert netmodel.parse_policy("kway3") == netmodel.PolicySpec(
            "kway3", 3, True, exact_lookahead=True
        )

    @pytest.mark.parametrize("bad", ["", "srsf0", "kway1", "lwf", "adadual"])
    def test_unknown_raises(self, bad):
        with pytest.raises(ValueError, match="unknown comm policy"):
            netmodel.parse_policy(bad)


class TestMayStart:
    def test_matches_adadual_wrapper(self):
        """The shared predicate and the event backend's Algorithm 2 wrapper
        must be the same function."""
        cases = [
            (0.0, []),            # uncontended
            (50e6, [200e6]),      # small vs one big old -> start
            (150e6, [200e6]),     # ratio test fails -> wait
            (50e6, [200e6, 60e6]),  # binding old is the small one
            (50e6, [0.0]),        # exhausted old -> refuse (event parity)
        ]
        for new_bytes, olds in cases:
            for max_conc in (0, 1, 2, 3):
                expect = adadual_should_start(new_bytes, olds, max_conc, P)
                got = netmodel.may_start(
                    max_conc + 1,
                    new_bytes,
                    min(olds, default=math.inf),
                    max_ways=2,
                    threshold_gated=True,
                    dual_threshold=P.dual_threshold,
                )
                assert bool(got) == expect, (new_bytes, olds, max_conc)

    def test_matches_srsf_n(self):
        for n in (1, 2, 3):
            for max_conc in (0, 1, 2, 3, 4):
                expect = srsf_n_should_start(max_conc, n)
                got = netmodel.may_start(
                    max_conc + 1, 0.0, math.inf,
                    max_ways=n, threshold_gated=False, dual_threshold=0.0,
                )
                assert bool(got) == expect, (n, max_conc)

    def test_vectorized_mask(self):
        k_would = np.array([1, 2, 2, 3])
        new_cost = np.array([1.0, 1.0, 1.0, 1.0])
        min_old = np.array([np.inf, 10.0, 1.0, 10.0])
        out = netmodel.may_start(
            k_would, new_cost, min_old,
            max_ways=2, threshold_gated=True, dual_threshold=0.4,
        )
        # lane0 uncontended; lane1 passes ratio (1 < 4); lane2 fails
        # (1 !< 0.4); lane3 over the cap
        np.testing.assert_array_equal(out, [True, True, False, False])

    def test_dynamic_variant_is_boolean_identical(self):
        """may_start_dynamic (runtime policy params — how the fluid backend
        shares one compiled graph across every gating policy) must agree
        with the static-parameter predicate everywhere."""
        rng = np.random.default_rng(0)
        k_would = rng.integers(1, 5, 200)
        new_cost = rng.uniform(0.0, 300e6, 200)
        min_old = np.where(rng.random(200) < 0.2, np.inf, rng.uniform(0, 300e6, 200))
        for max_ways in (1, 2, 3):
            for gated in (False, True):
                ref = netmodel.may_start(
                    k_would, new_cost, min_old,
                    max_ways=max_ways, threshold_gated=gated,
                    dual_threshold=P.dual_threshold,
                )
                dyn = netmodel.may_start_dynamic(
                    k_would, new_cost, min_old,
                    np.float32(max_ways), np.asarray(gated),
                    P.dual_threshold,
                )
                np.testing.assert_array_equal(ref, dyn, err_msg=f"{max_ways}/{gated}")


class TestKwayExactStart:
    """The closed-form exact k-way gate must agree decision-for-decision
    with the event backend's integrator-based reference
    (``adadual.kway_adadual_should_start``)."""

    E = P.eta / P.b

    def _closed(self, new_bytes, olds, max_ways):
        k = len(olds)
        rem = np.array([new_bytes] + list(olds), dtype=np.float64)
        new_cost = np.array([new_bytes] + [0.0] * k)
        mask = np.zeros((k + 1, k + 1), dtype=bool)
        mask[0, 1:] = True
        return bool(
            netmodel.kway_exact_start(new_cost, rem, mask, float(max_ways), self.E)[0]
        )

    def test_uncontended_always_starts(self):
        assert self._closed(123e6, [], 4)

    def test_max_ways_cap(self):
        olds = [100e6, 200e6, 300e6]
        assert not self._closed(1e6, olds, 3)  # k+1 = 4 > 3

    def test_matches_integrator_reference(self):
        from repro.core.adadual import kway_adadual_should_start

        rng = np.random.default_rng(7)
        for _ in range(300):
            k = int(rng.integers(0, 5))
            olds = list(rng.uniform(1e6, 8e8, k))
            new = float(rng.uniform(1e6, 8e8))
            max_ways = int(rng.integers(2, 6))
            ref = kway_adadual_should_start(new, olds, P, max_ways=max_ways)
            assert self._closed(new, olds, max_ways) == ref, (new, olds, max_ways)

    def test_matches_integrator_on_exact_ties(self):
        from repro.core.adadual import kway_adadual_should_start

        for k in (1, 2, 3):
            for ratio in (0.01, 0.4, P.dual_threshold, 1.0, 2.0):
                s = 3e8
                olds = [s] * k
                new = ratio * s
                ref = kway_adadual_should_start(new, olds, P, max_ways=4)
                assert self._closed(new, olds, 4) == ref, (k, ratio)

    def test_batched_rows_independent(self):
        """A batch of candidate rows must reproduce the per-row answers."""
        rem = np.array([50e6, 200e6, 150e6, 400e6])
        new_cost = np.array([50e6, 0.0, 150e6, 0.0])
        mask = np.zeros((4, 4), dtype=bool)
        mask[0, [1, 3]] = True   # candidate 0 vs olds {1, 3}
        mask[2, 1] = True        # candidate 2 vs old {1}
        out = netmodel.kway_exact_start(new_cost, rem, mask, 4.0, self.E)
        assert bool(out[0]) == self._closed(50e6, [200e6, 400e6], 4)
        assert bool(out[2]) == self._closed(150e6, [200e6], 4)

    def test_finish_times_match_integrator(self):
        """The closed form T_x = (1+e)*sum_y min(s_x, s_y) - e*s_x that the
        gate is built on must match the exact piecewise integrator."""
        from repro.core.adadual import simulate_task_set

        rng = np.random.default_rng(3)
        for _ in range(50):
            k = int(rng.integers(1, 6))
            sizes = rng.uniform(1e6, 8e8, k)
            ref = simulate_task_set([0.0] * k, list(sizes), P)
            m = np.minimum(sizes[:, None], sizes[None, :])
            closed = (P.b + P.eta) * m.sum(axis=1) - P.eta * sizes
            np.testing.assert_allclose(closed, ref, rtol=1e-9)


class TestGatingFixedPoint:
    """Random states against the claim of ``gating_fixed_point``: its
    start set never violates a cap or threshold that the sequential loop
    enforces, under the threshold policies (``max_ways`` 1-3, gated or
    not)."""

    def _state(self, rng, n_jobs=12, n_domains=8):
        """A random gating state: domain loads, an in-flight set, the
        waiting transfers apart from it, remainders and priorities."""
        loads = rng.random((n_jobs, n_domains)) < 0.3
        active = rng.random(n_jobs) < 0.35
        waiting = ~active & (rng.random(n_jobs) < 0.6)
        rem = rng.uniform(0.05, 10.0, n_jobs).astype(np.float32)
        new_cost = rng.uniform(0.05, 10.0, n_jobs).astype(np.float32)
        priority = rng.uniform(1.0, 1e4, n_jobs).astype(np.float32)
        loads_f = loads.astype(np.float32)
        overlap = (loads_f @ loads_f.T) > 0
        return loads, active, waiting, rem, new_cost, priority, overlap

    @staticmethod
    def _passes(i, in_flight, loads, overlap, rem, new_cost, policy):
        """Candidate ``i``'s predicate against the in-flight set."""
        counts = netmodel.domain_counts(loads, in_flight)
        k_would = netmodel.domain_k(loads, counts, extra=1)[i]
        olds = overlap[i] & in_flight
        min_old = rem[olds].min() if olds.any() else np.inf
        return bool(netmodel.may_start_dynamic(
            k_would, new_cost[i], min_old, *policy))

    @pytest.mark.parametrize("gated", [False, True])
    @pytest.mark.parametrize("max_ways", [1, 2, 3])
    def test_start_set_is_sound_for_the_sequential_loop(self, max_ways, gated):
        rng = np.random.default_rng(100 * max_ways + gated)
        policy = (np.float32(max_ways), np.asarray(gated), P.dual_threshold)
        n_nonempty = 0
        for _ in range(300):
            loads, active, waiting, rem, new_cost, priority, overlap = (
                self._state(rng))
            n = len(active)
            r1 = waiting & np.array([
                self._passes(i, active, loads, overlap, rem, new_cost, policy)
                for i in range(n)])
            if not r1.any():
                continue
            n_nonempty += 1
            start = netmodel.gating_fixed_point(
                r1, priority, loads, netmodel.domain_counts(loads, active),
                overlap, active, rem, new_cost, *policy)
            assert not (start & ~r1).any()
            head = int(np.argmin(np.where(r1, priority, np.inf)))
            assert start[head]
            # the head first, then the rest in priority order: each start
            # passes against the in-flight set as it stands
            in_flight = active.copy()
            rest = [i for i in np.argsort(priority, kind="stable")
                    if start[i] and i != head]
            for i in [head] + rest:
                assert self._passes(i, in_flight, loads, overlap, rem,
                                    new_cost, policy), (i, start)
                in_flight[i] = True
        assert n_nonempty >= 100


class TestPlacementRank:
    FREE = np.array([1.0, 4.0, 0.0, 2.0])
    LOAD = np.array([9.0, 0.0, 5.0, 2.0])
    IDX = np.arange(4, dtype=float)

    def order(self, mode):
        return list(np.argsort(
            netmodel.placement_rank(mode, self.FREE, self.LOAD, self.IDX),
            kind="stable",
        ))

    def test_modes(self):
        assert self.order("consolidate") == [1, 3, 0, 2]   # most free first
        assert self.order("first_fit") == [0, 1, 2, 3]     # index order
        assert self.order("least_loaded") == [1, 3, 2, 0]  # smallest L_S first

    def test_unknown_mode_raises(self):
        with pytest.raises(ValueError, match="unknown placement mode"):
            netmodel.placement_rank("nope", self.FREE, self.LOAD, self.IDX)

    def test_extra_key_modes_require_rank_extra(self):
        for mode in ("random", "rack_pack"):
            with pytest.raises(ValueError, match="rank_extra"):
                netmodel.placement_rank(mode, self.FREE, self.LOAD, self.IDX)
        key = np.array([3.0, 0.0, 2.0, 1.0])
        out = netmodel.placement_rank("random", self.FREE, self.LOAD, self.IDX, key)
        np.testing.assert_array_equal(out, key)

    def test_canonical_placement(self):
        assert netmodel.canonical_placement("lwf") == "consolidate"
        assert netmodel.canonical_placement("FF") == "first_fit"
        assert netmodel.canonical_placement("ls") == "least_loaded"
        assert netmodel.canonical_placement("consolidate") == "consolidate"
        assert netmodel.canonical_placement("rand") == "random"
        assert netmodel.canonical_placement("lwf_rack") == "rack_pack"
        with pytest.raises(ValueError, match="fluid backend supports"):
            netmodel.canonical_placement("nope")
