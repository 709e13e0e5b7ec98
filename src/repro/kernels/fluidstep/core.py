"""The fluid step core: one tick's contention/rate evaluation, in lax.

This is the contention/rate op sequence the fluid simulator's hot loop
runs once per executed tick (``core/jaxsim.py``), factored out so the
simulator has a single call site for the math:

* per-domain in-flight counts over the (precomputed) domain-load mask,
* the Eq. 5 contended rate at the oversub-weighted effective k,
* the slowest-member-server drain scale (per-server NIC heterogeneity),
* the gating-side quantities: ``k_would`` (contention a new start would
  see), ``min_old_rem`` (Theorem 2's M_old) and — on request — the job
  overlap matrix.

``loads`` arrives as an *input*: it only changes when ring membership
changes (admission / job completion), so the simulator maintains it
incrementally in the scan carry instead of re-deriving it via two
incidence matmuls every tick (which dominated the CPU per-tick profile).

``min_old_rem`` is computed as a min of per-domain minima instead of a
masked min over the J×J overlap matrix: ``min{rem[j] : j active,
overlaps i}`` equals ``min over domains d loaded by i of min{rem[j] : j
active, j loads d}`` (a min of mins over a cover of the same set), and
f32 ``min`` is exact, so the two forms are bit-identical while this one
is O(J·D) with no J×J intermediate.  The overlap matrix itself is only
materialized when ``need_overlap`` (WFBP gating closure / exact k-way
lookahead paths).

The same code runs on every backend.  Under the Monte-Carlo driver's
``vmap`` XLA is free to lay each lane-batched array out with the lane
axis minor, which on a TPU keeps the whole chunk scan in dense
128-lane tiles; no custom call pins a layout.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import netmodel


def fluid_step_core(loads, member, active, rem, bw, oversub, *,
                    b: float, eta: float, need_overlap: bool = False):
    """One evaluation of the contention/rate core.

    Args:
      loads: ``(J, D)`` bool — which contention domains each job's ring
        crosses (``netmodel.domain_loads``; maintained by the caller).
      member: ``(J, S)`` float {0,1} — GPUs-held-per-server occupancy mask.
      active: ``(J,)`` bool — transfers currently draining (started, rem>0).
      rem: ``(J,)`` float — remaining cost of each job's current phase.
      bw: ``(S,)`` float — per-server relative NIC bandwidth.
      oversub: ``(D,)`` float — per-domain oversubscription.
      b / eta: Eq. 5 per-byte cost and contention penalty (static).
      need_overlap: materialize the ``(J, J)`` overlap matrix (WFBP /
        exact k-way gating need it; the threshold fast path does not).

    Returns a dict with ``counts`` (D, int32), ``k_eff`` (J, float),
    ``ratio`` (J, float — slowest-member-scaled Eq. 5 rate fraction),
    ``k_would`` (J, int32), ``min_old_rem`` (J, float, inf where no
    overlapping in-flight task) and ``overlap`` ((J,J) bool, or None
    unless ``need_overlap``).
    """
    counts = netmodel.domain_counts(loads, active)  # (D,)
    k_eff = netmodel.domain_k(loads, counts.astype(jnp.float32) * oversub)
    scale = netmodel.slowest_member_scale(bw, member > 0)
    ratio = scale * netmodel.rate_ratio(k_eff, b, eta)
    k_would = netmodel.domain_k(loads, counts, extra=1)
    # per-domain minimum in-flight remainder, then min over loaded domains
    dmin = jnp.where(loads & active[:, None], rem[:, None], jnp.inf).min(axis=0)
    min_old_rem = jnp.where(loads, dmin[None, :], jnp.inf).min(axis=1)
    return {
        "counts": counts,
        "k_eff": k_eff,
        "ratio": ratio,
        "k_would": k_would,
        "min_old_rem": min_old_rem,
        "overlap": job_overlap(loads) if need_overlap else None,
    }


def job_overlap(loads):
    """``(J, J)`` bool: which jobs load a common domain (an exact product
    of {0,1} values compared with 0)."""
    loads_f = loads.astype(jnp.float32)
    return (loads_f @ loads_f.T) > 0
