"""Shared fixtures.  NOTE: no xla_force_host_platform_device_count here —
smoke tests and benches must see the real single CPU device; only the
dry-run (a separate process) forces 512 devices.

The suite's wall time is dominated by XLA compiles (the first
fluid-simulator graph, the MoE train step, ...), so the session uses jax's
persistent compilation cache wherever ``repro.compile_cache`` places it:
``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache/`` in the checkout.
Entries are keyed on the HLO hash, so stale entries cannot leak across
code changes."""

import jax
import pytest

from repro.compile_cache import use_compile_cache

use_compile_cache()


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)
