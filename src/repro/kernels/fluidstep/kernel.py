"""Pallas fluid-step core: the whole per-tick contention/rate evaluation
as ONE kernel launch.

The lax reference path (ref.py) emits ~10 small XLA ops per evaluation —
per-domain counts, two masked max-reductions, the slowest-member min, the
Eq. 5 rate, the J×J overlap matmul and the two-stage masked min over
in-flight remainders.  On CPU the XLA thunk overhead per op dominates at
these sizes (J ≤ 128, S ≤ 32, D ≤ 40), and on TPU each op is a separate
VMEM round-trip; fusing them keeps every intermediate in VMEM/registers
for the lifetime of the step.

Problem sizes are far below one VMEM tile, so the kernel is a single
program (no grid): all operands land in VMEM whole, the overlap matmul
hits the MXU once, and everything else is VPU mask algebra.  The domain
load mask arrives precomputed (the simulator maintains it incrementally
in the scan carry — membership only changes at admission/completion
events).  Boolean masks travel as float {0,1} (TPU-friendly layout);
ops.py restores the reference dtypes and the ``inf`` sentinel so callers
cannot tell the paths apart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: f32-safe stand-in for +inf inside the kernel (inf * 0 would NaN in the
#: mask algebra; ops.py maps >= _BIG/2 back to inf).
_BIG = 1e30


def _fluid_step_kernel(loads_ref, member_ref, active_ref, rem_ref, bw_ref,
                       ov_ref, counts_ref, keff_ref, ratio_ref, overlap_ref,
                       kwould_ref, minold_ref, *, b: float, eta: float):
    loads = loads_ref[:]                # (J, D) float {0,1}
    member = member_ref[:]              # (J, S) float {0,1}
    active = active_ref[:]              # (J, 1) float {0,1}
    rem = rem_ref[:]                    # (J, 1)
    # Per-domain in-flight counts and the two contention levels.
    counts = jnp.sum(loads * active, axis=0, keepdims=True)  # (1, D)
    counts_ref[:] = counts
    k_eff = jnp.clip(
        jnp.max(loads * (counts * ov_ref[:]), axis=1, keepdims=True), 1.0, None
    )
    keff_ref[:] = k_eff
    kwould_ref[:] = jnp.clip(
        jnp.max(loads * (counts + 1.0), axis=1, keepdims=True), 1.0, None
    )
    # Slowest member server bottlenecks the ring (memberless jobs -> 1.0).
    masked_bw = member * bw_ref[:] + (1.0 - member) * _BIG
    lo = jnp.min(masked_bw, axis=1, keepdims=True)
    has = jnp.max(member, axis=1, keepdims=True)
    scale = lo * has + (1.0 - has)
    # Eq. 5 retained-bandwidth fraction at the effective contention.
    ratio_ref[:] = scale * (b / (k_eff * b + (k_eff - 1.0) * eta))
    # Jobs overlap iff they load a common domain; min_old_rem is the
    # smallest remainder among overlapping in-flight transfers (M_old),
    # via per-domain minima (bit-identical to the J×J form: f32 min is
    # exact, and min-of-mins over a cover equals the direct min).
    overlap = jnp.where(
        jnp.dot(loads, loads.T, preferred_element_type=jnp.float32) > 0,
        1.0, 0.0,
    )  # (J, J)
    overlap_ref[:] = overlap
    act_loads = loads * active
    dmin = jnp.min(
        act_loads * rem + (1.0 - act_loads) * _BIG, axis=0, keepdims=True
    )  # (1, D)
    minold_ref[:] = jnp.min(
        loads * dmin + (1.0 - loads) * _BIG, axis=1, keepdims=True
    )


@functools.partial(
    jax.jit, static_argnames=("b", "eta", "interpret")
)
def fluid_step_core_pallas(loads, member, active, rem, bw, oversub, *,
                           b: float, eta: float, interpret: bool):
    """Run the fused step core; returns raw float planes (see ops.py)."""
    n_jobs = member.shape[0]
    n_domains = loads.shape[1]
    kern = functools.partial(_fluid_step_kernel, b=b, eta=eta)
    f32 = jnp.float32
    out_shapes = (
        jax.ShapeDtypeStruct((1, n_domains), f32),       # counts
        jax.ShapeDtypeStruct((n_jobs, 1), f32),          # k_eff
        jax.ShapeDtypeStruct((n_jobs, 1), f32),          # ratio
        jax.ShapeDtypeStruct((n_jobs, n_jobs), f32),     # overlap
        jax.ShapeDtypeStruct((n_jobs, 1), f32),          # k_would
        jax.ShapeDtypeStruct((n_jobs, 1), f32),          # min_old_rem
    )
    return pl.pallas_call(kern, out_shape=out_shapes, interpret=interpret)(
        loads.astype(f32),
        member.astype(f32),
        active.astype(f32).reshape(n_jobs, 1),
        rem.astype(f32).reshape(n_jobs, 1),
        bw.astype(f32).reshape(1, -1),
        oversub.astype(f32).reshape(1, -1),
    )
