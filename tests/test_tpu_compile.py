"""The fluid simulator's TPU programs compile for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a v5e that
``jax.experimental.topologies`` describes, which catches what a CPU
compile cannot (the TPU's layouts and its device memory).  Nothing runs, so these
tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around the
compiles, because an entry compiled for a described chip cannot be read
back without one.
"""

import collections
import os
import re

import pytest

import jax
from jax.sharding import SingleDeviceSharding

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


def _lane_batch(name):
    """One lane of ``name`` at its registered size, stacked as the
    Monte-Carlo driver stacks seeds."""
    from repro.core.jaxsim import stack_traces, trace_from_jobs
    from repro.scenarios import get_scenario
    from repro.scenarios.sweep import fluid_config

    scn = get_scenario(name, seed=0)
    batch = stack_traces([trace_from_jobs(scn.job_list(), fusion=scn.fusion)])
    return scn, batch, fluid_config(scn)


def _spec(x, lanes, sharding):
    return jax.ShapeDtypeStruct((lanes,) + tuple(x.shape[1:]), x.dtype,
                                sharding=sharding)


def _chunk_program(name, lanes, sharding):
    """``_chunk_jit`` for ``name`` at ``lanes`` lanes, compiled."""
    from repro.core.jaxsim import _chunk_jit, _init_jit, _policy_args

    _, batch, cfg = _lane_batch(name)
    max_ways, gated, cfg_key = _policy_args(cfg)
    traces = {k: _spec(v, lanes, sharding) for k, v in batch.items()}
    state = jax.eval_shape(lambda tr: _init_jit(tr, cfg_key), traces)
    state = {k: _spec(v, lanes, sharding) for k, v in state.items()}
    scalars = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
               for x in (max_ways, gated)]
    return _chunk_jit.lower(traces, state, cfg_key, *scalars).compile()


#: an HLO array type: element type, dimensions, minor-to-major layout
_ARRAY = re.compile(r"\b[a-z]+[0-9]*\[([0-9,]*)\]\{([0-9,]*)")


def _layouts(text, dims):
    """How often each minor-to-major layout is given to arrays of
    ``dims`` in compiled HLO ``text``."""
    want = ",".join(map(str, dims))
    return collections.Counter(
        layout for shape, layout in _ARRAY.findall(text) if shape == want)


@pytest.mark.parametrize("lanes", [256, LANES])
@pytest.mark.parametrize("name", ["paper", "oversub_fabric"])
def test_chunk_program_keeps_lane_minor_layouts(name, lanes, one_chip,
                                                no_cache):
    """Nothing in the step pins a layout: the chunk program launches no
    Pallas kernel, has no (lanes, jobs, 1) array, and every (lanes, jobs, servers) and
    (lanes, jobs) array of the scan has the lane axis minor, so that it
    fills whole 128-lane tiles."""
    scn = _lane_batch(name)[0]
    text = _chunk_program(name, lanes, one_chip).as_text()
    assert "tpu_custom_call" not in text
    jobs, servers = scn.n_jobs, scn.n_servers
    assert not _layouts(text, (lanes, jobs, 1))
    for dims in [(lanes, jobs, servers), (lanes, jobs)]:
        got = _layouts(text, dims)
        assert got, dims
        assert all(layout.split(",")[0] == "0" for layout in got), (dims, got)


@pytest.mark.parametrize("lanes", [1, 32, 256, LANES])
@pytest.mark.parametrize("name", ["paper", "oversub_fabric", "model_zoo"])
def test_chunk_program_compiles(name, lanes, one_chip, no_cache):
    """The chunk program at one lane, at fewer lanes than a tile, and at
    two and eight 128-lane tiles fits one chip's memory."""
    mem = _chunk_program(name, lanes, one_chip).memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < HBM_BYTES, (
        f"{name} at {lanes} lanes needs {total / 2**30:.2f} GiB "
        f"(arguments {mem.argument_size_in_bytes}, "
        f"temporaries {mem.temp_size_in_bytes})"
    )
