"""The fluid step core (``jaxsim.fluid_step_core``) against a plain
NumPy evaluation of the same definitions, and under ``vmap``.

The plain evaluation walks jobs and domains one by one, straight from
the definitions: in-flight counts per domain, the oversub-weighted
effective k and the gating-side k of a new start (max over the domains a
job loads, at least 1), the slowest member server's bandwidth, the Eq. 5
rate fraction, Theorem 2's M_old (the least remainder of an in-flight job
sharing a domain, ``inf`` where none does) and the job overlap matrix.
Integer planes, masks and M_old must match exactly; the float rates to
float32 round-off.

Under the Monte-Carlo driver the core runs inside ``vmap`` (and inside
the chunk's ``lax.scan``); batched it must give the bits it gives lane by
lane.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.jaxsim import fluid_step_core

B, ETA = 7e-10, 3e-10


def _rand_inputs(seed, n_jobs=12, n_servers=6, n_domains=9):
    rng = np.random.default_rng(seed)
    loads = rng.random((n_jobs, n_domains)) < 0.35
    # a comm-capable job loads >= 1 domain; some rows left empty on purpose
    member = rng.random((n_jobs, n_servers)) < 0.4
    active = rng.random(n_jobs) < 0.5
    rem = rng.uniform(0.05, 80.0, n_jobs)
    bw = rng.uniform(0.4, 2.5, n_servers)
    oversub = rng.uniform(1.0, 4.0, n_domains)
    return (
        jnp.asarray(loads),
        jnp.asarray(member, dtype=jnp.float32),
        jnp.asarray(active),
        jnp.asarray(rem, dtype=jnp.float32),
        jnp.asarray(bw, dtype=jnp.float32),
        jnp.asarray(oversub, dtype=jnp.float32),
    )


def _plain(loads, member, active, rem, bw, oversub, b=B, eta=ETA):
    """The step core's outputs, job by job, in NumPy float32."""
    loads, member, active = (np.asarray(x) for x in (loads, member, active))
    rem, bw, oversub = (np.asarray(x, np.float32) for x in (rem, bw, oversub))
    n_jobs, n_domains = loads.shape
    f32 = np.float32
    counts = np.array([sum(bool(loads[j, d] and active[j])
                           for j in range(n_jobs))
                       for d in range(n_domains)], np.int32)
    k_eff = np.ones(n_jobs, f32)
    k_would = np.ones(n_jobs, np.int32)
    ratio = np.empty(n_jobs, f32)
    min_old = np.full(n_jobs, np.inf, f32)
    overlap = np.zeros((n_jobs, n_jobs), bool)
    for i in range(n_jobs):
        doms = [d for d in range(n_domains) if loads[i, d]]
        for d in doms:
            k_eff[i] = max(k_eff[i], f32(counts[d]) * oversub[d])
            k_would[i] = max(k_would[i], counts[d] + 1)
        servers = [s for s in range(member.shape[1]) if member[i, s] > 0]
        scale = min(bw[s] for s in servers) if servers else f32(1.0)
        k = k_eff[i]
        ratio[i] = scale * (f32(b) / (k * f32(b) + (k - f32(1)) * f32(eta)))
        for j in range(n_jobs):
            overlap[i, j] = any(loads[j, d] for d in doms)
            if overlap[i, j] and active[j]:
                min_old[i] = min(min_old[i], rem[j])
    return {"counts": counts, "k_eff": k_eff, "ratio": ratio,
            "k_would": k_would, "min_old_rem": min_old, "overlap": overlap}


def _assert_plain(out, want):
    for key in ("counts", "k_would", "k_eff", "min_old_rem", "overlap"):
        np.testing.assert_array_equal(np.asarray(out[key]), want[key],
                                      err_msg=key)
    np.testing.assert_allclose(np.asarray(out["ratio"]), want["ratio"],
                               rtol=1e-6)


class TestPlainParity:
    @pytest.mark.parametrize("widths", [(12, 6, 9), (40, 8, 20), (160, 16, 16)])
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_random_states_match(self, seed, widths):
        n_jobs, n_servers, n_domains = widths
        args = _rand_inputs(seed, n_jobs, n_servers, n_domains)
        out = fluid_step_core(*args, b=B, eta=ETA, need_overlap=True)
        _assert_plain(out, _plain(*args))

    def test_output_dtypes(self):
        out = fluid_step_core(*_rand_inputs(0), b=B, eta=ETA,
                              need_overlap=True)
        want = {"counts": np.int32, "k_would": np.int32,
                "k_eff": np.float32, "ratio": np.float32,
                "min_old_rem": np.float32, "overlap": np.bool_}
        assert {k: np.asarray(v).dtype for k, v in out.items()} == want

    def test_no_active_transfers(self):
        loads, member, _, rem, bw, oversub = _rand_inputs(5)
        active = jnp.zeros(loads.shape[0], dtype=bool)
        out = fluid_step_core(loads, member, active, rem, bw, oversub,
                              b=B, eta=ETA, need_overlap=True)
        assert int(np.asarray(out["counts"]).sum()) == 0
        # nothing in flight: k is 1, the full rate, M_old the +inf sentinel
        assert (np.asarray(out["k_eff"]) == 1.0).all()
        assert np.isinf(np.asarray(out["min_old_rem"])).all()
        _assert_plain(out, _plain(loads, member, active, rem, bw, oversub))

    def test_empty_loads_rows(self):
        loads, member, active, rem, bw, oversub = _rand_inputs(6)
        loads = loads.at[0].set(False)  # comm-less job
        out = fluid_step_core(loads, member, active, rem, bw, oversub,
                              b=B, eta=ETA, need_overlap=True)
        # a loadless row contends with nothing: k floors at 1, M_old = inf
        assert float(np.asarray(out["k_eff"])[0]) == 1.0
        assert int(np.asarray(out["k_would"])[0]) == 1
        assert np.isinf(np.asarray(out["min_old_rem"])[0])
        assert not np.asarray(out["overlap"])[0].any()
        _assert_plain(out, _plain(loads, member, active, rem, bw, oversub))

    def test_ref_skips_overlap_unless_needed(self):
        out = fluid_step_core(*_rand_inputs(1), b=B, eta=ETA,
                              need_overlap=False)
        assert out["overlap"] is None


def _lane_batch(seed, lanes, n_jobs, n_servers, n_domains):
    """``lanes`` random states of one cluster: the per-lane planes
    stacked lanes first; ``bw`` and ``oversub`` shared, as in the
    simulator."""
    per_lane = [_rand_inputs(seed + i, n_jobs, n_servers, n_domains)
                for i in range(lanes)]
    stacked = [jnp.stack(planes) for planes in zip(*per_lane)]
    return (*stacked[:4], per_lane[0][4], per_lane[0][5])


def _assert_same(got, want):
    assert got.keys() == want.keys()
    for key in got:
        if want[key] is None:
            assert got[key] is None, key
            continue
        g, w = np.asarray(got[key]), np.asarray(want[key])
        assert g.dtype == w.dtype, key
        np.testing.assert_array_equal(g, w, err_msg=key)


class TestLaneBatched:
    """The core under ``vmap``, as the chunk scan runs it: bit-identical
    to the same core run lane by lane."""

    @pytest.mark.parametrize("need_overlap", [False, True])
    @pytest.mark.parametrize("n_domains", [16, 20])
    @pytest.mark.parametrize("lanes", [1, 3, 37, 130])
    def test_vmap_matches_lane_by_lane(self, lanes, n_domains, need_overlap):
        # paper-wide jobs and servers
        loads, member, active, rem, bw, oversub = _lane_batch(
            lanes, lanes, n_jobs=160, n_servers=16, n_domains=n_domains)

        def lane(l, m, a, r):
            return fluid_step_core(l, m, a, r, bw, oversub, b=B, eta=ETA,
                                   need_overlap=need_overlap)

        batched = jax.jit(jax.vmap(lane))(loads, member, active, rem)
        one = jax.jit(lane)
        per_lane = [one(loads[i], member[i], active[i], rem[i])
                    for i in range(lanes)]
        want = {k: None if per_lane[0][k] is None else
                jnp.stack([p[k] for p in per_lane]) for k in per_lane[0]}
        _assert_same(batched, want)

    def test_vmap_inside_scan(self):
        """The per-lane core inside ``lax.scan`` inside ``vmap``,
        remainders drained by each tick's rates so that transfers end
        mid-scan, against the same scan run lane by lane."""
        loads, member, active, rem, bw, oversub = _lane_batch(
            11, 5, n_jobs=24, n_servers=6, n_domains=9)

        def lane_chunk(loads, member, active, rem):
            def tick(rem, _):
                out = fluid_step_core(
                    loads, member, active & (rem > 0), rem, bw, oversub,
                    b=B, eta=ETA)
                return jnp.maximum(rem - 3.0 * out["ratio"], 0.0), out
            return jax.lax.scan(tick, rem, None, length=6)

        rem_b, out_b = jax.jit(jax.vmap(lane_chunk))(loads, member, active,
                                                      rem)
        one = jax.jit(lane_chunk)
        for i in range(loads.shape[0]):
            rem_i, out_i = one(loads[i], member[i], active[i], rem[i])
            np.testing.assert_array_equal(np.asarray(rem_b[i]),
                                          np.asarray(rem_i))
            _assert_same({k: None if v is None else v[i]
                          for k, v in out_b.items()}, out_i)
        # transfers really ended inside the scan
        assert (np.asarray(rem_b) == 0).any()

