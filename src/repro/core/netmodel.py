"""Backend-agnostic policy/network layer shared by both simulators.

This module is the single home of the paper's rate/gating math so the exact
event simulator (``core/simulator.py``) and the vectorized fluid simulator
(``core/jaxsim.py``) cannot drift apart:

* the Eq. (5) contended-rate model (:func:`rate_ratio`, :func:`rate`);
* per-server NIC bandwidth heterogeneity (:func:`server_bandwidth_array`,
  :func:`slowest_member_scale` — a ring all-reduce drains at the rate of
  its slowest member server);
* the communication gating predicates — AdaDUAL (Theorem 2), SRSF(n), and
  the k-way AdaDUAL generalization — expressed once as a
  :class:`PolicySpec` plus one branchless predicate (:func:`may_start`);
* the placement-mode ranking keys the fluid backend's gang placement
  shares with the event backend's Algorithm 1 family
  (:func:`placement_rank`).

Everything is a pure function of plain scalars/arrays: the same expression
evaluates on Python floats, numpy arrays, and traced ``jax.numpy`` arrays,
so the event backend calls these with scalars while the fluid backend maps
them over whole job vectors inside ``jit``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# Eq. (5) rate model
# ---------------------------------------------------------------------------


def rate_ratio(k, b: float, eta: float):
    """Fraction of the contention-free bandwidth one task retains under
    k-way contention: ``b / (k*b + (k-1)*eta)`` (Eq. 5 per-byte cost
    inverted and normalized by the k=1 cost).  ``k`` may be a scalar or an
    array; ``k=1`` gives exactly 1.0."""
    return b / (k * b + (k - 1) * eta)


def rate(k, b: float, eta: float):
    """Instantaneous drain rate [B/s] under k-way contention (Eq. 5)."""
    return 1.0 / (k * b + (k - 1) * eta)


def domain_loads(member_mask, incidence):
    """Which contention domains each task loads, as a boolean
    ``(..., n_domains)`` array: a task's ring crosses domain d's cut iff it
    has member servers both inside and outside d (``core/topology.py``'s
    one load rule, lowered to mask algebra).

    ``member_mask`` is a numeric {0,1} ``(..., n_servers)`` array;
    ``incidence`` a numeric {0,1} ``(n_domains, n_servers)`` matrix
    (:meth:`Topology.incidence`).  Works on numpy and jax arrays — two
    matmuls against static matrices, no branching.  For the NIC-only
    incidence (identity) this reduces to ``member & spans_multiple``,
    exactly the paper's per-server rule."""
    inside = member_mask @ incidence.T
    outside = member_mask @ (1.0 - incidence).T
    return (inside > 0) & (outside > 0)


def domain_counts(loads, active):
    """Per-domain count of in-flight tasks: ``loads`` is ``(jobs,
    n_domains)`` boolean, ``active`` ``(jobs,)`` boolean; returns
    ``(n_domains,)``."""
    return (loads & active[..., None]).sum(axis=-2)


def domain_k(loads, weighted_counts, extra=0):
    """Each task's contention level: the max of ``weighted_counts + extra``
    over the domains the task loads, clamped to >= 1 (a task loading no
    domain is uncontended).  Pass raw counts for the gating-side k, or
    ``counts * oversub`` for the Eq. (5) effective k (float)."""
    k = (loads * (weighted_counts + extra)[..., None, :]).max(axis=-1)
    return k.clip(1)


def server_bandwidth_array(
    server_bandwidth: Sequence[float], n_servers: int
) -> np.ndarray:
    """Per-server relative NIC bandwidth multipliers as a dense
    ``(n_servers,)`` float array: servers beyond the configured tuple are
    nominal (1.0), extra entries are dropped.  Empty input = homogeneous
    network (all ones), exactly the paper's model."""
    bw = np.ones((max(0, n_servers),), dtype=np.float64)
    for s, scale in enumerate(server_bandwidth[:n_servers]):
        bw[s] = scale
    return bw


def slowest_member_scale(bw, member_mask):
    """Drain-rate multiplier of each task: the slowest member server
    bottlenecks the ring.  ``bw`` is ``(n_servers,)``, ``member_mask`` a
    boolean ``(..., n_servers)``; tasks with no member servers get 1.0.

    Works on numpy and jax arrays (pure mask algebra — a large finite
    sentinel instead of ``inf`` keeps ``0 * sentinel`` NaN-free).
    """
    big = 1e30
    masked = member_mask * bw + (1 - member_mask) * big
    lo = masked.min(axis=-1)
    has_member = member_mask.any(axis=-1)
    return lo * has_member + 1.0 * (1 - has_member)


# ---------------------------------------------------------------------------
# Tensor fusion (wait-free backpropagation, WFBP)
# ---------------------------------------------------------------------------

#: ``fusion`` accepts ``"all"`` (one bucket = the paper's monolithic
#: all-reduce, today's behaviour bit-for-bit), ``"none"`` (one bucket per
#: layer, fully unfused WFBP), or a positive byte threshold (DDP-style
#: ``bucket_cap``: greedily accumulate layers until the bucket reaches the
#: threshold).
FUSION_ALL = "all"
FUSION_NONE = "none"


def fusion_threshold(fusion) -> float:
    """Normalize a fusion spec to a byte threshold: ``"all"`` -> inf,
    ``"none"``/0 -> 0.0 (per-layer buckets), a positive number -> itself."""
    if isinstance(fusion, str):
        f = fusion.lower()
        if f == FUSION_ALL:
            return float("inf")
        if f == FUSION_NONE:
            return 0.0
        raise ValueError(
            f"unknown fusion spec {fusion!r}; expected 'all', 'none' or bytes"
        )
    thr = float(fusion)
    if thr < 0:
        raise ValueError(f"fusion threshold must be >= 0, got {fusion}")
    return thr


def fusion_plan(
    layer_bytes: Sequence[float],
    layer_t_b: Sequence[float],
    threshold: float,
) -> Tuple[Tuple[float, ...], Tuple[float, ...]]:
    """Greedy WFBP tensor fusion over layers in *backward-ready* order
    (output layer first — the order gradients materialize during backprop).

    Layers accumulate into the current bucket until its size reaches
    ``threshold`` bytes, then the bucket seals (PyTorch-DDP ``bucket_cap``
    semantics: the threshold is a *lower* bound on a sealed bucket, so
    every bucket except possibly the last is >= threshold).  Returns
    ``(bucket_bytes, bucket_t_b)``: per-bucket gradient bytes and the
    backward-compute segment time that must elapse — beyond the previous
    bucket's segment — before the bucket is ready to all-reduce.
    ``threshold=inf`` yields one bucket (``fusion="all"``); ``threshold=0``
    one bucket per layer (fully unfused).  Sums are preserved exactly:
    ``sum(bucket_bytes) == sum(layer_bytes)`` and likewise for time.
    """
    if len(layer_bytes) != len(layer_t_b):
        raise ValueError(
            f"layer_bytes ({len(layer_bytes)}) and layer_t_b "
            f"({len(layer_t_b)}) must align"
        )
    if not layer_bytes:
        raise ValueError("fusion_plan needs at least one layer")
    sizes: list = []
    times: list = []
    acc_b = acc_t = 0.0
    for lb, lt in zip(layer_bytes, layer_t_b):
        acc_b += float(lb)
        acc_t += float(lt)
        if acc_b >= threshold:
            sizes.append(acc_b)
            times.append(acc_t)
            acc_b = acc_t = 0.0
    if acc_b > 0.0 or acc_t > 0.0 or not sizes:
        sizes.append(acc_b)
        times.append(acc_t)
    return tuple(sizes), tuple(times)


def plan_for_model(model, fusion) -> Optional[Tuple[Tuple[float, ...], Tuple[float, ...]]]:
    """The fusion plan of one ``ModelProfile`` under a fusion spec, or
    ``None`` when the monolithic (legacy iteration-level) path applies:
    ``fusion="all"``, or a model without per-layer data (the paper's
    Table III profiles carry none)."""
    thr = fusion_threshold(fusion)
    if thr == float("inf") or not getattr(model, "layer_grad_bytes", ()):
        return None
    return fusion_plan(model.layer_grad_bytes, model.layer_t_b, thr)


# ---------------------------------------------------------------------------
# Preemption / checkpoint-restore cost (preemptive & elastic scheduling)
# ---------------------------------------------------------------------------

#: Default checkpoint-storage bandwidths [B/s] (save to / restore from a
#: shared filesystem over the same 10 GbE class network as the paper's
#: all-reduce: ~1.2 GB/s effective per direction) and the fixed
#: orchestration overhead of stopping and relaunching a gang [s].
CHECKPOINT_SAVE_BPS = 1.2e9
CHECKPOINT_RESTORE_BPS = 1.2e9
CHECKPOINT_FIXED_S = 1.0


def preemption_cost(
    state_bytes: float,
    save_bps: float = CHECKPOINT_SAVE_BPS,
    restore_bps: float = CHECKPOINT_RESTORE_BPS,
    fixed_s: float = CHECKPOINT_FIXED_S,
) -> float:
    """Wall-clock penalty of preempting (or resizing) a job: checkpoint its
    ``state_bytes`` of model state, then restore it on the next placement,
    plus a fixed stop/relaunch overhead.  Shared by both the event engine
    and any analytic model so the penalty cannot drift between layers.

    The restore half is charged when the job next starts (it delays the
    first forward of every worker); modeling save+restore as one lump at
    restart keeps the preemption event itself instantaneous — the saved
    GPU time is what preemption frees, and the paper's cluster writes
    checkpoints out-of-band.
    """
    if state_bytes < 0:
        raise ValueError(f"state_bytes must be >= 0, got {state_bytes}")
    if save_bps <= 0 or restore_bps <= 0:
        raise ValueError("checkpoint bandwidths must be positive")
    return fixed_s + state_bytes / save_bps + state_bytes / restore_bps


# ---------------------------------------------------------------------------
# Communication gating policies
# ---------------------------------------------------------------------------

#: Canonical names of the gating policies both backends understand.
POLICY_PATTERN = re.compile(r"^(ada|srsf([1-9])|kway([2-9]))$")


@dataclasses.dataclass(frozen=True)
class PolicySpec:
    """One communication gating policy, reduced to two static parameters.

    ``max_ways``      — accept a start only if the resulting contention on
                        every touched server stays <= max_ways.
    ``threshold_gated`` — additionally require Theorem 2's ratio test
                        ``M_new < dual_threshold * min(M_old_remaining)``
                        when the start would contend (k_would > 1).

    AdaDUAL is (2, gated); SRSF(n) is (n, blind).  The k-way AdaDUAL
    generalization is (K, gated, exact): ``exact_lookahead`` routes the
    fluid backend to :func:`kway_exact_start` — the closed-form equivalent
    of the event backend's ``kway_adadual_should_start`` integrator —
    instead of the Theorem 2 pairwise-threshold approximation.
    """

    name: str
    max_ways: int
    threshold_gated: bool
    #: use the exact option-A/option-B average-finish-time comparison
    #: (k-way policies) instead of the pairwise ratio test
    exact_lookahead: bool = False


def parse_policy(name: str) -> PolicySpec:
    """'ada' | 'srsfN' | 'kwayK' -> a :class:`PolicySpec`."""
    m = POLICY_PATTERN.match(name)
    if not m:
        raise ValueError(
            f"unknown comm policy {name!r}; expected 'ada', 'srsfN' or 'kwayK'"
        )
    if name == "ada":
        return PolicySpec("ada", 2, True)
    if name.startswith("srsf"):
        return PolicySpec(name, int(m.group(2)), False)
    return PolicySpec(name, int(m.group(3)), True, exact_lookahead=True)


def may_start(
    k_would,
    new_cost,
    min_old_rem,
    *,
    max_ways: int,
    threshold_gated: bool,
    dual_threshold: float,
):
    """Branchless gating predicate shared by both backends.

    Args:
      k_would: contention level the new task *would* see if it started now
        (1 = uncontended); scalar or per-job array.
      new_cost: remaining size of the new task (bytes, or any unit
        proportional to bytes — the Theorem 2 test is a pure ratio).
      min_old_rem: smallest remaining size among the in-flight tasks that
        overlap the new one, in the same unit as ``new_cost``; pass
        ``inf`` when there is none.
      max_ways / threshold_gated: static policy parameters
        (:class:`PolicySpec`).
      dual_threshold: ``b / (2*(b + eta))`` (Theorem 2).

    Returns a boolean (array) — True where the task may start.  Uncontended
    starts are always allowed; a zero/negative ``min_old_rem`` fails the
    ratio test (matching the event backend's ``old_rem > 0`` guard, since
    ``new_cost`` is positive).
    """
    uncontended = k_would <= 1
    under_cap = k_would <= max_ways
    if threshold_gated:
        contended_ok = under_cap & (new_cost < dual_threshold * min_old_rem)
    else:
        contended_ok = under_cap
    return uncontended | contended_ok


def may_start_dynamic(
    k_would,
    new_cost,
    min_old_rem,
    max_ways,
    threshold_gated,
    dual_threshold: float,
    *,
    exact_kway_olds=None,
    rem=None,
    eta_over_b=None,
    exact_tol: float = 1e-9,
):
    """:func:`may_start` with the policy parameters as *runtime* values
    (arrays/traced scalars) instead of Python statics.

    Boolean-algebra-identical to :func:`may_start` for both values of
    ``threshold_gated`` (locked in tests/test_netmodel.py), but because
    nothing here is compile-time static, a jitted simulator can evaluate
    every gating policy through ONE compiled graph — the fluid backend
    uses this so AdaDUAL/SRSF(n)/k-way share a single XLA compilation per
    trace shape instead of recompiling per policy.

    ``threshold_gated`` must be a boolean *array* (numpy or jax; ``~`` is
    logical-not for those — a bare Python bool would bit-invert).

    Exact k-way lookahead: when ``exact_kway_olds`` (a ``(J, J)`` boolean
    matrix — row ``i`` marks the in-flight tasks overlapping candidate
    ``i``'s domains) is supplied together with ``rem`` (per-job remaining
    cost) and ``eta_over_b``, the threshold approximation above is replaced
    by :func:`kway_exact_start` — the closed form of the event backend's
    option-A/option-B average-finish-time comparison.  The fluid backend
    routes ``kwayK`` policies here (``PolicySpec.exact_lookahead``)."""
    if exact_kway_olds is not None:
        return kway_exact_start(
            new_cost, rem, exact_kway_olds, max_ways, eta_over_b, tol=exact_tol
        )
    uncontended = k_would <= 1
    under_cap = k_would <= max_ways
    ratio_ok = new_cost < dual_threshold * min_old_rem
    contended_ok = under_cap & (ratio_ok | ~threshold_gated)
    return uncontended | contended_ok


def gating_fixed_point(
    r1,
    priority,
    loads,
    counts,
    overlap,
    active,
    rem,
    new_cost,
    max_ways,
    threshold_gated,
    dual_threshold: float,
    *,
    exact_kway: bool = False,
    eta_over_b=None,
):
    """One-shot fixed point of the per-step greedy re-gating loop.

    The sequential loop starts the smallest-remaining-service eligible
    candidate, recomputes the contention state and repeats, mirroring the
    event backend's re-evaluate-after-each-start loop.  This computes the
    greedy closure in a single masked pass; the fluid backend gates its
    bucketed (WFBP) traces with it.

    Why a single pass suffices — monotonicity of the gating predicate in
    the active set.  Write the threshold predicate for candidate ``i``
    against an active in-flight set ``A``::

        P_i(A) = (k_i(A∪{i}) <= 1)
               | (k_i(A∪{i}) <= max_ways
                  & (~gated | new_cost_i < thr * min_old_rem_i(A)))

    Growing ``A`` by another started task ``j`` can only (a) *increase*
    every per-domain count, hence ``k_i`` is non-decreasing in ``A``, and
    (b) add one more term to the min over overlapping in-flight
    remainders, hence ``min_old_rem_i`` is non-increasing in ``A``.  The
    predicate is non-increasing in ``k_i`` and non-decreasing in
    ``min_old_rem_i``, so ``P_i`` is *antitone* in the active set: adding
    starts can flip a candidate True -> False but never False -> True.
    Consequences for the greedy loop seeded with candidates
    ``r1 = {i : P_i(A0)}`` against the base set ``A0``:

    * no candidate outside ``r1`` can enter in a later round (the active
      set only grows), so ``r1`` bounds the closure from above;
    * any candidate passing the *pessimistic* test
      ``r2_i = P_i(A0 ∪ r1 \\ {i})`` passes against every intermediate
      active set of every greedy order (each is a subset), so
      ``r1 & r2`` is a sound start set under any order;
    * the greedy head ``c1`` (smallest ``priority`` in ``r1``) is started
      first by the loop against ``A0`` itself — sound by construction.

    The returned set ``(r1 & r2) | c1`` therefore never violates a cap or
    threshold that the sequential loop enforces (checked on random states
    in tests/test_netmodel.py), and equals the loop's closure whenever the
    greedy outcome is order-independent.  Bit-exact agreement with a
    4-round loop across the fusion × policy grid is locked in
    tests/test_fastpath.py, where that loop is the test's oracle.

    For exact-lookahead k-way policies (``exact_kway=True``) the predicate
    is a cost *comparison* (option A vs option B), not an antitone
    threshold, so the same pessimistic construction is used but the
    monotonicity argument does not apply; the simulator compensates by
    never skipping gating re-evaluation steps under exact k-way (see
    core/jaxsim.py) and the same grid lock applies.

    Args:
      r1: ``(J,)`` bool — candidates passing the predicate vs the base
        active set (round 1's eligibility).
      priority: ``(J,)`` float — greedy order key, smallest first
        (remaining service).
      loads: ``(J, D)`` bool — per-job domain loads.
      counts: ``(D,)`` int — per-domain in-flight counts of the base set.
      overlap: ``(J, J)`` bool — jobs sharing a contention domain.
      active: ``(J,)`` bool — base in-flight set.
      rem: ``(J,)`` float — remaining cost of each job's current transfer.
      new_cost: ``(J,)`` float — cost of each candidate's next transfer.
      max_ways / threshold_gated / dual_threshold: runtime policy params
        (:func:`may_start_dynamic`).
      exact_kway: route the pessimistic re-test through
        :func:`kway_exact_start`.
      eta_over_b: required when ``exact_kway``.

    Returns the ``(J,)`` bool start set.
    """
    import numpy as _np

    n_jobs = r1.shape[-1]
    eye = _np.eye(n_jobs, dtype=bool)  # constant under jit
    # Pessimistic active set per candidate: base ∪ (r1 \ {self}).  Every
    # r1 member contributes 1 to each domain it loads; excluding self from
    # its own lookahead reduces, for i ∈ r1, to the raw counts2 (the +1 of
    # k_would and the -1 of self-exclusion cancel).
    counts2 = counts + domain_counts(loads, r1)
    k_would2 = domain_k(loads, counts2)
    olds2 = (overlap & (active | r1)[..., None, :]) & ~eye
    big = 1e30  # finite "absent" sentinel: 0 * big stays NaN-free
    o2 = olds2 * 1.0
    min_old2 = (o2 * rem[..., None, :] + (1.0 - o2) * big).min(-1)
    if exact_kway:
        r2 = kway_exact_start(new_cost, rem, olds2, max_ways, eta_over_b)
    else:
        r2 = may_start_dynamic(
            k_would2, new_cost, min_old2, max_ways, threshold_gated,
            dual_threshold,
        )
    # Greedy head: smallest-priority r1 candidate (round 1's start).
    head = (r1 * priority + (1.0 - r1 * 1.0) * big).argmin(-1)
    c1 = r1 & (_np.arange(n_jobs) == head)
    return (r1 & r2) | c1


def _pairwise_min(x, y):
    """Branchless elementwise min (broadcasting) that works identically on
    numpy and jax arrays: ``min(x, y) = (x + y - |x - y|) / 2``."""
    return 0.5 * (x + y - abs(x - y))


def kway_exact_start(
    new_cost,
    rem,
    olds_mask,
    max_ways,
    eta_over_b,
    tol: float = 1e-9,
):
    """Exact k-way AdaDUAL gate, vectorized over candidates — the closed
    form of ``core/adadual.py``'s ``kway_adadual_should_start`` integrator
    (locked against it in tests/test_netmodel.py).

    Under Eq. (5) fair sharing, a set ``S`` of tasks all active from one
    instant with remaining sizes ``s_x`` finishes (in units where ``b = 1``,
    with ``e = eta/b``) at::

        t_x = (1 + e) * sum_y min(s_x, s_y)  -  e * s_x

    (phase-by-phase telescoping of the piecewise-constant rates; the
    latency ``a`` cancels from the A-vs-B comparison).  Summing over ``x``
    turns the option averages into quadratic forms of the pairwise-min
    matrix, so one batched masked matmul evaluates every candidate's
    lookahead at once — no per-candidate integration loop, and it jits.

    * Option A (start now): ``S = olds ∪ {new}``.
    * Option B (wait): the olds run alone until the smallest finishes at
      ``t1 = m_min * (k + (k-1)e)``; every survivor has drained exactly
      ``m_min``, then ``{survivors - m_min} ∪ {new}`` are simultaneous —
      the same closed form, shifted (``min(a-c, b-c) = min(a,b) - c``).

    Args:
      new_cost: ``(J,)`` remaining cost of each candidate's next transfer
        (the current WFBP *bucket* for bucketed traces — the per-bucket
        check — or the whole message for monolithic ones).  Any unit
        proportional to bytes: the decision is scale-invariant.
      rem: ``(J,)`` remaining cost of each job's in-flight transfer.
      olds_mask: ``(J, J)`` boolean; row ``i`` marks in-flight tasks
        overlapping candidate ``i``'s contention domains.
      max_ways: cap K (scalar or array) — reject when ``k + 1 > K``.
      eta_over_b: the contention penalty ratio ``eta / b``.
      tol: survivor threshold matching the event integrator's 1e-9.

    Returns a boolean ``(J,)`` — True where starting now gives a strictly
    smaller average finish time (or the candidate is uncontended).
    """
    e = eta_over_b
    big = 1e30  # f32-safe "no old task" sentinel
    olds = olds_mask * 1.0  # (J, J) float mask
    k = olds.sum(-1)  # (J,) in-flight tasks overlapping each candidate
    m = _pairwise_min(rem[..., None], rem[None, :])  # (J, J) pairwise mins
    # Option A — olds ∪ {new} simultaneous from now:
    q_a = ((olds @ m) * olds).sum(-1)  # sum_{j,l in olds} min(m_j, m_l)
    cross_a = (olds * _pairwise_min(new_cost[..., None], rem[None, :])).sum(-1)
    pairmin_a = q_a + 2.0 * cross_a + new_cost
    sum_a = (olds * rem[None, :]).sum(-1) + new_cost
    avg_a = ((1.0 + e) * pairmin_a - e * sum_a) / (k + 1.0)
    # Option B — wait for the first old to finish, then start:
    m_min = (rem[None, :] * olds + big * (1.0 - olds)).min(-1) * (k > 0)
    t1 = m_min * (k + (k - 1.0) * e)
    shifted = rem[None, :] - m_min[..., None]  # survivor sizes after t1
    sv = olds * (shifted > tol)
    kp = sv.sum(-1)
    q_sv = ((sv @ m) * sv).sum(-1) - kp * kp * m_min  # shifted quadratic form
    cross_b = (sv * _pairwise_min(shifted, new_cost[..., None])).sum(-1)
    pairmin_b = q_sv + 2.0 * cross_b + new_cost
    sum_b = (sv * shifted).sum(-1) + new_cost
    f_b = (1.0 + e) * pairmin_b - e * sum_b
    avg_b = t1 + f_b / (k + 1.0)
    return (k <= 0) | ((k + 1.0 <= max_ways) & (avg_a < avg_b))


# ---------------------------------------------------------------------------
# Placement-mode ranking (fluid backend's gang analogue of Algorithm 1)
# ---------------------------------------------------------------------------

#: Gang placement modes of the fluid backend and the event-backend
#: placement each one mirrors (see docs/scenarios.md parity matrix).
PLACEMENT_MODES = ("consolidate", "first_fit", "least_loaded", "random", "rack_pack")

#: Event-backend placement names -> fluid gang analogue.
FLUID_PLACEMENT_ALIASES = {
    "lwf": "consolidate",
    "gang": "consolidate",
    "consolidate": "consolidate",
    "ff": "first_fit",
    "first_fit": "first_fit",
    "ls": "least_loaded",
    "least_loaded": "least_loaded",
    "rand": "random",
    "random": "random",
    "lwf_rack": "rack_pack",
    "rack_pack": "rack_pack",
}


def canonical_placement(name: str) -> str:
    """Map an event-backend placement name ('lwf', 'ff', 'ls', 'rand',
    'lwf_rack', ...) to the fluid gang placement mode."""
    try:
        return FLUID_PLACEMENT_ALIASES[name.lower()]
    except KeyError:
        raise ValueError(
            f"fluid backend supports placements {sorted(FLUID_PLACEMENT_ALIASES)}, "
            f"got {name!r}"
        ) from None


def rack_pack_rank(free, server_rack, n_racks: int, gpus_per_server: int):
    """Rank key for the ``rack_pack`` gang mode: fill the rack with the most
    free GPUs first (locality — a job that fits in one rack lands entirely
    inside it and never crosses the rack uplink), servers within a rack by
    most-free (the consolidate shape).  Both terms are small bounded
    integers, so the composite key is exact in float32.

    ``free`` is ``(n_servers,)``; ``server_rack`` the ``(n_servers,)`` rack
    index of each server (:meth:`Topology.server_rack`)."""
    one_hot = (server_rack[..., None] == np.arange(n_racks)).astype(
        free.dtype
    )  # (n_servers, n_racks); the numpy constant broadcasts under jax too
    rack_free = (one_hot * free[..., None]).sum(axis=-2)  # (n_racks,)
    rack_free_per_server = (one_hot * rack_free[..., None, :]).sum(axis=-1)
    return -(rack_free_per_server * (gpus_per_server + 1) + free)


def placement_rank(mode: str, free, load, server_index, rank_extra=None):
    """Primary sort key per server for gang placement — servers are filled
    in ascending key order (stable sort; ties break by server index):

    * ``consolidate``  — most free GPUs first (``-free``): whole servers
      first, the LWF-1 consolidation shape;
    * ``first_fit``    — server index order, regardless of load;
    * ``least_loaded`` — smallest remaining-service workload first
      (Algorithm 1's L_S ordering, the LWF/LS shape);
    * ``random``       — caller-supplied random key (``rank_extra``): a
      uniformly random server order per admission (the gang analogue of
      the event backend's per-GPU RAND);
    * ``rack_pack``    — caller-supplied :func:`rack_pack_rank` key
      (``rank_extra``): emptiest rack first, then consolidate within it.

    ``free``/``load``/``server_index`` are ``(n_servers,)`` arrays (numpy
    or jax); ``mode`` is static.
    """
    if mode == "consolidate":
        return -free
    if mode == "first_fit":
        return server_index
    if mode == "least_loaded":
        return load
    if mode in ("random", "rack_pack"):
        if rank_extra is None:
            raise ValueError(f"mode {mode!r} needs a caller-supplied rank_extra key")
        return rank_extra
    raise ValueError(f"unknown placement mode {mode!r}; expected {PLACEMENT_MODES}")


__all__ = [
    "FLUID_PLACEMENT_ALIASES",
    "FUSION_ALL",
    "FUSION_NONE",
    "PLACEMENT_MODES",
    "PolicySpec",
    "canonical_placement",
    "domain_counts",
    "domain_k",
    "domain_loads",
    "fusion_plan",
    "fusion_threshold",
    "gating_fixed_point",
    "kway_exact_start",
    "may_start",
    "may_start_dynamic",
    "parse_policy",
    "placement_rank",
    "plan_for_model",
    "preemption_cost",
    "rack_pack_rank",
    "rate",
    "rate_ratio",
    "server_bandwidth_array",
    "slowest_member_scale",
]
