"""The fluid simulator's TPU programs compile for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a v5e that
``jax.experimental.topologies`` describes, which catches what interpreter
mode cannot (tiling, VMEM limits, device memory).  Nothing runs, so these
tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around the
compiles, because an entry compiled for a described chip cannot be read
back without one.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.fluidstep import ops

#: one v5e chip's device memory
HBM_BYTES = 16 * 1024**3
#: the Monte-Carlo lane count the on-chip smoke run uses
LANES = 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture()
def on_tpu(monkeypatch):
    """The program picks its kernel from the default backend, which is the
    CPU here: steer it to the TPU branch for the duration of a test."""
    monkeypatch.setattr(ops, "backend_platform", lambda: "tpu")


def _lane_batch(name):
    """One lane of ``name`` at its registered size, stacked as the
    Monte-Carlo driver stacks seeds."""
    from repro.core.jaxsim import stack_traces, trace_from_jobs
    from repro.scenarios import get_scenario
    from repro.scenarios.sweep import fluid_config

    scn = get_scenario(name, seed=0)
    batch = stack_traces([trace_from_jobs(scn.job_list(), fusion=scn.fusion)])
    return scn, batch, fluid_config(scn, kernel="tpu")


def _spec(x, lanes, sharding):
    return jax.ShapeDtypeStruct((lanes,) + tuple(x.shape[1:]), x.dtype,
                                sharding=sharding)


@pytest.mark.parametrize("name", ["paper", "oversub_fabric"])
def test_step_kernel_compiles(name, one_chip, no_cache):
    from repro.core.topology import nic_topology
    from repro.kernels.fluidstep.kernel import fluid_step_core_pallas

    scn = _lane_batch(name)[0]
    topo = scn.topology if scn.topology is not None else nic_topology(scn.n_servers)
    n_jobs, n_servers = scn.n_jobs, scn.n_servers
    n_domains = np.asarray(topo.incidence()).shape[0]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (
        sds((n_jobs, n_domains), jnp.bool_),   # loads
        sds((n_jobs, n_servers), jnp.float32),  # member
        sds((n_jobs,), jnp.bool_),              # active
        sds((n_jobs,), jnp.float32),            # rem
        sds((n_servers,), jnp.float32),         # bw
        sds((n_domains,), jnp.float32),         # oversub
    )
    p = scn.params
    compiled = fluid_step_core_pallas.lower(
        *args, b=p.b, eta=p.eta, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["paper", "model_zoo"])
def test_chunk_program_compiles_with_kernel(name, one_chip, no_cache, on_tpu):
    from repro.core.jaxsim import _chunk_jit, _init_jit, _policy_args

    _, batch, cfg = _lane_batch(name)
    max_ways, gated, cfg_key = _policy_args(cfg)
    assert cfg_key.kernel == "tpu"
    traces = {k: _spec(v, LANES, one_chip) for k, v in batch.items()}
    state = jax.eval_shape(lambda tr: _init_jit(tr, cfg_key), traces)
    state = {k: _spec(v, LANES, one_chip) for k, v in state.items()}
    scalars = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
               for x in (max_ways, gated)]
    compiled = _chunk_jit.lower(traces, state, cfg_key, *scalars).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < HBM_BYTES, (
        f"{name} at {LANES} lanes needs {total / 2**30:.2f} GiB "
        f"(arguments {mem.argument_size_in_bytes}, "
        f"temporaries {mem.temp_size_in_bytes})"
    )
