"""Parity of the fused Pallas fluid-step core against the lax reference.

The reference path (``kernels/fluidstep/ref.py``) is the physics anchor —
it is what CPU CI and every differential test run; on a TPU the compiled
kernel is the default (``tests/test_tpu_compile.py`` compiles it).  The
Pallas kernel (``kernel.py``) must be indistinguishable through the
``ops.py`` dispatch: same dtypes, same values (integer planes exact, float
planes to f32 round-off), same ``inf`` sentinel for jobs with no
overlapping in-flight transfer.  Interpreter mode runs the kernel body on CPU, so this guards
the kernel math everywhere, not just on TPU runners.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.fluidstep import fluid_step_core, ops
from repro.kernels.fluidstep.ops import default_impl, resolve_impl


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


def _both(seed, **kw):
    loads, member, active, rem, bw, oversub = _rand_inputs(seed, **kw)
    args = dict(b=7e-10, eta=3e-10, need_overlap=True)
    ref = fluid_step_core(loads, member, active, rem, bw, oversub,
                          impl="ref", **args)
    pal = fluid_step_core(loads, member, active, rem, bw, oversub,
                          impl="interpret", **args)
    return ref, pal


class TestPallasParity:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_random_states_match(self, seed):
        ref, pal = _both(seed)
        np.testing.assert_array_equal(
            np.asarray(ref["counts"]), np.asarray(pal["counts"])
        )
        np.testing.assert_array_equal(
            np.asarray(ref["k_would"]), np.asarray(pal["k_would"])
        )
        np.testing.assert_array_equal(
            np.asarray(ref["overlap"]), np.asarray(pal["overlap"])
        )
        np.testing.assert_allclose(
            np.asarray(ref["k_eff"]), np.asarray(pal["k_eff"]), rtol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(ref["ratio"]), np.asarray(pal["ratio"]), rtol=1e-6
        )
        r_min = np.asarray(ref["min_old_rem"])
        p_min = np.asarray(pal["min_old_rem"])
        np.testing.assert_array_equal(np.isinf(r_min), np.isinf(p_min))
        finite = ~np.isinf(r_min)
        np.testing.assert_allclose(r_min[finite], p_min[finite], rtol=1e-6)

    def test_dtypes_identical_across_impls(self):
        ref, pal = _both(0)
        for key in ("counts", "k_eff", "ratio", "k_would", "min_old_rem",
                    "overlap"):
            assert np.asarray(ref[key]).dtype == np.asarray(pal[key]).dtype, key

    def test_no_active_transfers(self):
        loads, member, _, rem, bw, oversub = _rand_inputs(5)
        active = jnp.zeros(loads.shape[0], dtype=bool)
        args = dict(b=7e-10, eta=3e-10, need_overlap=True)
        ref = fluid_step_core(loads, member, active, rem, bw, oversub,
                              impl="ref", **args)
        pal = fluid_step_core(loads, member, active, rem, bw, oversub,
                              impl="interpret", **args)
        assert int(np.asarray(ref["counts"]).sum()) == 0
        np.testing.assert_array_equal(
            np.asarray(ref["counts"]), np.asarray(pal["counts"])
        )
        # nothing in flight -> every job's M_old is the +inf sentinel
        assert np.isinf(np.asarray(pal["min_old_rem"])).all()

    def test_empty_loads_rows(self):
        loads, member, active, rem, bw, oversub = _rand_inputs(6)
        loads = loads.at[0].set(False)  # comm-less job
        args = dict(b=7e-10, eta=3e-10, need_overlap=True)
        ref = fluid_step_core(loads, member, active, rem, bw, oversub,
                              impl="ref", **args)
        pal = fluid_step_core(loads, member, active, rem, bw, oversub,
                              impl="interpret", **args)
        # a loadless row contends with nothing: k floors at 1, M_old = inf
        assert float(np.asarray(ref["k_eff"])[0]) == 1.0
        assert float(np.asarray(pal["k_eff"])[0]) == 1.0
        assert np.isinf(np.asarray(pal["min_old_rem"])[0])
        np.testing.assert_array_equal(
            np.asarray(ref["overlap"]), np.asarray(pal["overlap"])
        )


class TestDispatch:
    def test_unknown_impl_raises(self):
        loads, member, active, rem, bw, oversub = _rand_inputs(0)
        with pytest.raises(ValueError, match="unknown fluid step impl"):
            fluid_step_core(loads, member, active, rem, bw, oversub,
                            b=7e-10, eta=3e-10, impl="cuda")

    def test_default_is_ref_off_tpu(self):
        # the suite runs on the CPU backend: the lax reference is its own
        assert jax.default_backend() != "tpu"
        assert default_impl() == "ref"
        assert resolve_impl("") == "ref"
        with jax.default_device(jax.devices("cpu")[0]):
            assert default_impl() == "ref"

    def test_default_is_compiled_kernel_on_tpu(self, monkeypatch):
        monkeypatch.setattr(ops, "backend_platform", lambda: "tpu")
        assert default_impl() == "tpu"
        assert resolve_impl("") == "tpu"
        # a caller naming an impl always gets that impl
        assert resolve_impl("ref") == "ref"
        assert resolve_impl("interpret") == "interpret"

    def test_tpu_impl_raises_off_tpu(self):
        loads, member, active, rem, bw, oversub = _rand_inputs(0)
        with pytest.raises(ValueError, match="needs a TPU backend"):
            fluid_step_core(loads, member, active, rem, bw, oversub,
                            b=7e-10, eta=3e-10, impl="tpu")

    def test_simulator_config_resolves_kernel(self):
        from repro.core.jaxsim import JaxSimConfig, _policy_args

        assert _policy_args(JaxSimConfig())[2].kernel == "ref"
        named = JaxSimConfig(kernel="interpret")
        assert _policy_args(named)[2].kernel == "interpret"
        with pytest.raises(ValueError, match="needs a TPU backend"):
            _policy_args(JaxSimConfig(kernel="tpu"))

    def test_ref_skips_overlap_unless_needed(self):
        loads, member, active, rem, bw, oversub = _rand_inputs(1)
        out = fluid_step_core(loads, member, active, rem, bw, oversub,
                              b=7e-10, eta=3e-10, need_overlap=False,
                              impl="ref")
        assert out["overlap"] is None


class TestSimulatorPath:
    @pytest.mark.parametrize("scenario", ["paper", "oversub_fabric"])
    def test_kernel_in_simulator_matches_ref(self, scenario):
        """The kernel on the simulator's main path (interpreter mode here)
        finishes the same jobs at the same times as the reference."""
        from repro.scenarios import monte_carlo_fluid

        kw = dict(overrides=dict(n_jobs=10, min_iters=20, max_iters=60))
        ref = monte_carlo_fluid(scenario, [0, 1], kernel="ref", **kw)
        pal = monte_carlo_fluid(scenario, [0, 1], kernel="interpret", **kw)
        for r, p in zip(ref, pal):
            assert r.n_finished == p.n_finished == r.n_jobs
            assert p.avg_jct == pytest.approx(r.avg_jct, rel=1e-5)
            assert p.makespan == pytest.approx(r.makespan, rel=1e-5)
