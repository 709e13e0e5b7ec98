"""Communication cost and contention models (paper Sections II-B, III-A2).

Two models:

* Eq. (2): contention-free All-Reduce time  ``T_ar = a + b*M``.
* Eq. (5): k-way contended All-Reduce time ``T_ar(k) = a + k*b*M + (k-1)*eta*M``
  where ``k`` is the maximum number of concurrently running communication
  tasks over all servers the task touches.  ``k*b*M`` models fair bandwidth
  sharing; ``(k-1)*eta*M`` is the super-linear contention penalty the paper
  measures on 10 GbE.

Table I of the paper (cost of classic All-Reduce algorithms in the
alpha-beta-gamma model) is provided by :func:`allreduce_cost_terms` so the
simulator can be parameterized by algorithm instead of only by the fitted
``(a, b)`` constants.

Everything here is a pure function of its arguments so it can be used both
from the Python event-driven simulator and from the vectorized JAX simulator
(``core/jaxsim.py``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import numpy as np

from repro.core.cluster import left_sum

# ---------------------------------------------------------------------------
# Measured constants (paper Section III-A2, Fig. 2(a); 10 GbE, ring all-reduce)
# ---------------------------------------------------------------------------

#: Latency component fitted on real hardware [s].
PAPER_A = 6.69e-4
#: Per-byte transmission time fitted on real hardware [s/B] (~= 9.4 Gbps eff).
PAPER_B = 8.53e-10
#: Contention penalty per byte [s/B].  The paper plots the k-sweep (Fig. 2(b))
#: but never prints eta.  Calibration finding (EXPERIMENTS.md §Reproduction):
#: Ada-SRSF's pairwise-optimal gating is globally beneficial only for mild
#: eta — at eta >= b the externality on queued third tasks flips the
#: Ada-vs-SRSF(1) ordering on the paper workload; the paper's +20% claim is
#: therefore consistent with a small measured eta.  Default eta = 0.2*b
#: (threshold 0.417): reproduces SRSF(1)'s absolute avg JCT within 2% of the
#: paper's Table V and Ada-SRSF's improvement direction.  Exposed everywhere
#: as a parameter; benchmarks and EXPERIMENTS.md sweep it.
DEFAULT_ETA = 1.706e-10

#: TPU-pod flavoured constants used by the multi-job launcher demo: DCN-ish
#: latency and per-byte time for a 2-pod v5e slice (25 GB/s effective per host
#: pair).  Contention across pods behaves like the paper's shared NIC.
TPU_DCN_A = 2.0e-5
TPU_DCN_B = 4.0e-11
TPU_DCN_ETA = 8.0e-12


@dataclasses.dataclass(frozen=True)
class ContentionParams:
    """Parameters (a, b, eta) of the contended All-Reduce model, Eq. (5).

    ``server_bandwidth`` (beyond-paper, scenario engine) optionally assigns
    each server a relative NIC bandwidth multiplier (1.0 = nominal ``1/b``).
    A communication task spanning several servers drains at the rate of its
    slowest member; servers beyond the tuple's length are nominal.  Empty
    tuple (default) = homogeneous network, exactly the paper's model.
    """

    a: float = PAPER_A
    b: float = PAPER_B
    eta: float = DEFAULT_ETA
    server_bandwidth: Tuple[float, ...] = ()

    def __post_init__(self) -> None:
        if self.b <= 0:
            raise ValueError(f"b must be positive, got {self.b}")
        if self.a < 0 or self.eta < 0:
            raise ValueError("a and eta must be non-negative")
        if any(s <= 0 for s in self.server_bandwidth):
            raise ValueError("server_bandwidth multipliers must be positive")

    def bandwidth_scale(self, servers) -> float:
        """Relative drain-rate multiplier for a task touching ``servers``:
        the slowest member NIC bottlenecks the ring."""
        if not self.server_bandwidth:
            return 1.0
        n = len(self.server_bandwidth)
        return min((self.server_bandwidth[s] if s < n else 1.0) for s in servers)

    def mean_bandwidth_scale(self, n_servers: int) -> float:
        """Cluster-mean multiplier — the homogeneous-network equivalent.

        Kept as a diagnostic/summary statistic; the fluid (JAX) backend now
        models per-server rates directly (``core/netmodel.py``) and no
        longer collapses heterogeneity to this mean.  ``n_servers <= 0``
        returns the nominal 1.0.
        """
        if not self.server_bandwidth or n_servers <= 0:
            return 1.0
        n = len(self.server_bandwidth)
        return left_sum(
            (self.server_bandwidth[s] if s < n else 1.0) for s in range(n_servers)
        ) / n_servers

    # -- Eq. (5) -----------------------------------------------------------
    def allreduce_time(self, message_bytes: float, k: int = 1) -> float:
        """Total time of one All-Reduce of ``message_bytes`` under k-way
        contention (Eq. 5).  ``k=1`` reduces to Eq. (2)."""
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        return self.a + (k * self.b + (k - 1) * self.eta) * message_bytes

    def rate(self, k: float) -> float:
        """Instantaneous drain rate [B/s] of one task under k-way contention.

        Derived from Eq. (5): transferring M bytes takes
        ``(k*b + (k-1)*eta) * M`` seconds (excluding the one-off latency a),
        so each byte costs ``k*b + (k-1)*eta`` seconds.  ``k`` may be a
        float >= 1: the topology layer (``core/topology.py``) evaluates
        Eq. (5) at the *effective* contention ``k_raw * oversub`` of an
        oversubscribed domain.
        """
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        return 1.0 / (k * self.b + (k - 1) * self.eta)

    def seconds_per_byte(self, k: float) -> float:
        return k * self.b + (k - 1) * self.eta

    # -- AdaDUAL threshold (Theorem 2) --------------------------------------
    @property
    def dual_threshold(self) -> float:
        """``b / (2*(b + eta))`` — Theorem 2's ratio test.  A newly-ready task
        of size M_new should start against one existing task with remaining
        size M_old iff ``M_new / M_old < dual_threshold``."""
        return self.b / (2.0 * (self.b + self.eta))


# ---------------------------------------------------------------------------
# Table I — All-Reduce algorithm costs in the (alpha, beta, gamma) model
# ---------------------------------------------------------------------------

ALLREDUCE_ALGORITHMS = (
    "binary_tree",
    "recursive_doubling",
    "recursive_halving_doubling",
    "ring",
)


def allreduce_cost_terms(
    algorithm: str, n_nodes: int, alpha: float, beta: float, gamma: float
) -> Tuple[float, float]:
    """Return ``(a, b)`` of ``T = a + b*M`` for a classic All-Reduce algorithm
    (paper Table I).

    alpha: per-message latency [s]; beta: per-byte transfer time [s/B];
    gamma: per-byte reduction compute time [s/B]; n_nodes: number of nodes
    (power of two assumed by the paper).
    """
    if n_nodes < 2:
        return (0.0, 0.0)
    log_n = math.log2(n_nodes)
    n = float(n_nodes)
    if algorithm == "binary_tree":
        return (2 * alpha * log_n, (2 * beta + gamma) * log_n)
    if algorithm == "recursive_doubling":
        return (alpha * log_n, (beta + gamma) * log_n)
    if algorithm == "recursive_halving_doubling":
        return (2 * alpha * log_n, 2 * beta - (2 * beta + gamma) / n + gamma)
    if algorithm == "ring":
        return (
            2 * (n - 1) * alpha,
            2 * (n - 1) / n * beta + (n - 1) / n * gamma,
        )
    raise ValueError(
        f"unknown all-reduce algorithm {algorithm!r}; "
        f"expected one of {ALLREDUCE_ALGORITHMS}"
    )


# ---------------------------------------------------------------------------
# Model fitting (reproduces the Fig. 2(a) fit) — offline, float64 numpy.
# ---------------------------------------------------------------------------


def fit_linear_cost(message_bytes, times) -> Tuple[float, float]:
    """Least-squares fit of ``T = a + b*M`` (Fig. 2(a)).  Returns (a, b).

    float64 numpy: the design matrix columns span ~12 orders of magnitude
    (1 vs bytes), far beyond f32 conditioning; this is offline calibration,
    not part of a jitted path.
    """
    m = np.asarray(message_bytes, dtype=np.float64)
    t = np.asarray(times, dtype=np.float64)
    b, a = np.polyfit(m, t, 1)
    return float(a), float(b)


def fit_contention_penalty(ks, times, message_bytes: float, a: float, b: float) -> float:
    """Fit eta from a k-sweep at fixed message size (Fig. 2(b)).

    Model: T(k) = a + k*b*M + (k-1)*eta*M  ->  eta from least squares over k>1.
    """
    ks = np.asarray(ks, dtype=np.float64)
    times = np.asarray(times, dtype=np.float64)
    resid = times - (a + ks * b * message_bytes)
    x = (ks - 1.0) * message_bytes
    mask = ks > 1
    if not mask.any():
        return 0.0
    eta = float(np.dot(x[mask], resid[mask]) / np.dot(x[mask], x[mask]))
    return max(eta, 0.0)


def simulate_contention_sweep(
    params: ContentionParams, message_bytes: float, max_k: int
) -> np.ndarray:
    """Average per-task completion time for k identical concurrent tasks
    (the Fig. 2(b) experiment shape): all k tasks share every link, so each
    sees k-way contention for its entire transfer."""
    return np.asarray(
        [params.allreduce_time(message_bytes, k) for k in range(1, max_k + 1)]
    )
