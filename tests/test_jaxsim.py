"""Tests for the vectorized (fluid) JAX simulator's trace build and
batched entry: the struct-of-arrays trace layout, the padded batch, and
vmapped lanes reproducing one-lane runs bit-for-bit.  Its Monte-Carlo
behaviour (determinism, the paper's policy ordering) is tested through
the scenario entry points (``test_differential.py``,
``test_scenarios.py``).
"""

import numpy as np
import pytest

from repro.core.jaxsim import (
    JaxSimConfig,
    simulate_traces_batched,
    stack_traces,
    trace_from_jobs,
)
from repro.scenarios import get_scenario

CFG = JaxSimConfig(n_servers=4, gpus_per_server=2, dt=0.02)


class TestTraceFromJobs:
    def test_round_trips_scenario_jobs(self):
        jobs = get_scenario("smoke").job_list()
        tr = trace_from_jobs(jobs)
        assert set(tr) == {"arrival", "iters", "t_iter", "msg_bytes", "n_gpus"}
        for key in tr:
            assert tr[key].shape == (len(jobs),), key
        assert tr["n_gpus"].dtype == np.int32
        for key in ("arrival", "iters", "t_iter", "msg_bytes"):
            assert tr[key].dtype == np.float32, key
        for i, j in enumerate(jobs):
            assert float(tr["arrival"][i]) == j.arrival
            assert int(tr["iters"][i]) == j.iterations
            assert int(tr["n_gpus"][i]) == j.n_gpus
            assert float(tr["t_iter"][i]) == pytest.approx(
                j.model.t_iter_compute, rel=1e-6
            )
            assert float(tr["msg_bytes"][i]) == pytest.approx(
                j.model.size_bytes, rel=1e-6
            )

    def test_empty_job_list(self):
        tr = trace_from_jobs([])
        for key, arr in tr.items():
            assert arr.shape == (0,), key
        assert tr["n_gpus"].dtype == np.int32
        assert tr["arrival"].dtype == np.float32


class TestStackTraces:
    def test_rectangular_batch_with_valid_mask(self):
        jobs = get_scenario("smoke").job_list()
        t_full = trace_from_jobs(jobs)
        t_short = trace_from_jobs(jobs[:4])
        batch = stack_traces([t_full, t_short])
        n = len(jobs)
        for key in ("arrival", "iters", "t_iter", "msg_bytes", "n_gpus", "valid"):
            assert batch[key].shape == (2, n), key
        assert bool(batch["valid"].all(axis=1)[0])
        np.testing.assert_array_equal(
            np.asarray(batch["valid"][1]), [True] * 4 + [False] * 2
        )

    def test_empty_batch_raises(self):
        with pytest.raises(ValueError, match="at least one trace"):
            stack_traces([])

    @pytest.mark.parametrize("fusion", ["all", "none"])
    def test_planes_are_host_numpy(self, fusion):
        """The batch is built on the host: every plane is a numpy array
        (no device op in the build), with its fixed dtype."""
        jobs = get_scenario("smoke").job_list()
        tr = trace_from_jobs(jobs, fusion=fusion)
        batch = stack_traces([tr, trace_from_jobs(jobs[:3], fusion=fusion)])
        want = dict(arrival=np.float32, iters=np.float32, t_iter=np.float32,
                    msg_bytes=np.float32, n_gpus=np.int32,
                    bucket_bytes=np.float32, n_buckets=np.int32)
        assert set(batch) == set(tr) | {"valid"}
        for planes in (tr, batch):
            for key, arr in planes.items():
                assert type(arr) is np.ndarray, key
                assert arr.dtype == want.get(key, np.bool_), key

    def test_ragged_batch_equals_hand_built_planes(self):
        """Three ragged lanes, one of them jax arrays and one without
        bucket planes: each plane, pads and ``valid`` included, is the
        hand-written expected array."""
        import jax.numpy as jnp

        f32, i32 = np.float32, np.int32
        a = dict(arrival=np.array([0.0, 1.5], f32), iters=np.array([10, 20], f32),
                 t_iter=np.array([0.25, 0.5], f32),
                 msg_bytes=np.array([4e6, 8e6], f32), n_gpus=np.array([2, 4], i32),
                 bucket_bytes=np.array([[1e6, 3e6], [8e6, 0]], f32),
                 n_buckets=np.array([2, 1], i32))
        b = dict(arrival=jnp.array([2.0], f32), iters=jnp.array([30], f32),
                 t_iter=jnp.array([0.75], f32), msg_bytes=jnp.array([6e6], f32),
                 n_gpus=jnp.array([1], i32))
        c = dict(arrival=np.array([0.5, 1.0, 3.0], f32),
                 iters=np.array([5, 6, 7], f32), t_iter=np.array([1, 2, 3], f32),
                 msg_bytes=np.array([1e6, 2e6, 3e6], f32),
                 n_gpus=np.array([8, 1, 2], i32),
                 bucket_bytes=np.array([[1e6], [2e6], [3e6]], f32),
                 n_buckets=np.array([1, 1, 1], i32))
        batch = stack_traces([a, b, c])
        want = {
            "arrival": [[0.0, 1.5, 0.0], [2.0, 0.0, 0.0], [0.5, 1.0, 3.0]],
            "iters": [[10, 20, 1], [30, 1, 1], [5, 6, 7]],
            "t_iter": [[0.25, 0.5, 1], [0.75, 1, 1], [1, 2, 3]],
            "msg_bytes": [[4e6, 8e6, 0], [6e6, 0, 0], [1e6, 2e6, 3e6]],
            "n_gpus": [[2, 4, 1], [1, 1, 1], [8, 1, 2]],
            "valid": [[True, True, False], [True, False, False],
                      [True, True, True]],
            "bucket_bytes": [[[1e6, 3e6], [8e6, 0], [0, 0]],
                             [[6e6, 0], [0, 0], [0, 0]],
                             [[1e6, 0], [2e6, 0], [3e6, 0]]],
            "n_buckets": [[2, 1, 1], [1, 1, 1], [1, 1, 1]],
        }
        assert set(batch) == set(want)
        for key, rows in want.items():
            assert type(batch[key]) is np.ndarray, key
            np.testing.assert_array_equal(
                batch[key], np.asarray(rows, batch[key].dtype), err_msg=key)

    def test_batched_lanes_match_single_runs(self):
        """The padded vmap batch must reproduce each one-lane run
        exactly — including the ragged lane (padded jobs inert and
        excluded from `finished`) and the per-lane makespan (the loop
        clock keeps ticking for early-converged lanes; makespan must not)."""
        jobs = get_scenario("smoke").job_list()
        t_full = trace_from_jobs(jobs)
        t_short = trace_from_jobs(jobs[:4])
        out_b = simulate_traces_batched(stack_traces([t_full, t_short]), CFG)

        def one_lane(tr):
            out = simulate_traces_batched(stack_traces([tr]), CFG)
            return {k: np.asarray(out[k])[0]
                    for k in ("jct", "finished", "makespan")}

        out_full = one_lane(t_full)
        out_short = one_lane(t_short)
        np.testing.assert_array_equal(
            np.asarray(out_b["jct"])[0], np.asarray(out_full["jct"])
        )
        np.testing.assert_array_equal(
            np.asarray(out_b["jct"])[1][:4], np.asarray(out_short["jct"])
        )
        assert not np.asarray(out_b["finished"])[1][4:].any()
        np.testing.assert_allclose(
            np.asarray(out_b["makespan"]),
            [float(out_full["makespan"]), float(out_short["makespan"])],
        )
