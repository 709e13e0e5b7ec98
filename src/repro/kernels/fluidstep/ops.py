"""Impl-dispatching wrapper for the fluid step core.

``fluid_step_core`` is the single entry point the fluid simulator's hot
loop calls once per executed tick.  The implementation is chosen by the
``impl`` argument (``JaxSimConfig.kernel``):

* ``""``        — the backend's own: ``tpu`` when the default device is a
                  TPU, ``ref`` otherwise (:func:`default_impl`).
* ``ref``       — the historical lax composition (ref.py), the
                  bit-exactness anchor.
* ``interpret`` — the Pallas kernel in interpreter mode (runs on CPU; only
                  the parity tests name it).
* ``tpu``       — the compiled Pallas kernel; raises on any other backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fluidstep.kernel import _BIG, fluid_step_core_pallas
from repro.kernels.fluidstep.ref import fluid_step_core_ref

FLUID_KERNEL_IMPLS = ("ref", "interpret", "tpu")


def backend_platform() -> str:
    """Platform of the default device (honours ``jax.default_device``)."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def default_impl() -> str:
    return "tpu" if backend_platform() == "tpu" else "ref"


def resolve_impl(impl: str) -> str:
    """Validate ``impl`` and resolve ``""`` to :func:`default_impl`."""
    impl = impl or default_impl()
    if impl not in FLUID_KERNEL_IMPLS:
        raise ValueError(
            f"unknown fluid step impl {impl!r}; expected one of "
            f"{FLUID_KERNEL_IMPLS}"
        )
    if impl == "tpu" and backend_platform() != "tpu":
        raise ValueError(
            f"fluid step impl 'tpu' needs a TPU backend, but the default "
            f"device is {backend_platform()!r}; use 'ref' (or 'interpret' "
            "to run the kernel body on the CPU)"
        )
    return impl


def fluid_step_core(loads, member, active, rem, bw, oversub, *,
                    b: float, eta: float, need_overlap: bool = False,
                    impl: str = ""):
    """Contention/rate core of one fluid step (see ref.py for semantics).

    ``loads`` is the precomputed ``(J, D)`` domain-load mask (maintained
    incrementally by the simulator).  ``impl`` goes through
    :func:`resolve_impl`; outputs are dtype-identical across
    implementations (counts/k_would int32, rates float32, absent-old
    sentinel mapped back to +inf).  ``overlap`` is None when
    ``need_overlap`` is False on the reference path; the Pallas kernel
    computes it unconditionally (one MXU matmul, free on TPU).
    """
    impl = resolve_impl(impl)
    if impl == "ref":
        return fluid_step_core_ref(
            loads, member, active, rem, bw, oversub,
            b=b, eta=eta, need_overlap=need_overlap,
        )
    counts, k_eff, ratio, overlap, k_would, min_old = fluid_step_core_pallas(
        loads, member, active, rem, bw, oversub,
        b=b, eta=eta, interpret=(impl == "interpret"),
    )
    return {
        "counts": counts[0].astype(jnp.int32),
        "k_eff": k_eff[:, 0],
        "ratio": ratio[:, 0],
        "overlap": overlap > 0,
        "k_would": k_would[:, 0].astype(jnp.int32),
        "min_old_rem": jnp.where(
            min_old[:, 0] >= _BIG / 2, jnp.inf, min_old[:, 0]
        ),
    }
