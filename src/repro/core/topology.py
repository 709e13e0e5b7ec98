"""Pluggable network-fabric layer: contention domains beyond the server NIC.

The paper's Eq. (5) models contention only at the server NIC — every
communication task crossing a server loads that server's one shared 10 GbE
port.  Real fabrics add more shared resources: rack (ToR) uplinks, blocking
two-tier switches with an oversubscription factor.  This module lifts the
hard-coded NIC model into a declarative :class:`Topology` both simulation
backends consume:

* a **domain** is a *cut* of the fabric — a server set whose boundary is a
  shared resource.  A communication task with member-server set ``S`` loads
  domain ``D`` iff its ring crosses the cut: ``S ∩ D ≠ ∅ and S ∖ D ≠ ∅``.
  A per-server NIC is the cut around that single server, so the NIC-only
  topology reproduces the paper's model *exactly* (locked by regression
  tests in ``tests/test_topology.py``).
* each domain carries an ``oversub`` factor ``f ≥ 1``: the cut's usable
  bandwidth is ``1/f`` of a nominal NIC, so ``k`` tasks sharing it drain at
  the Eq. (5) rate evaluated at the *effective* contention ``k·f``
  (``netmodel.rate`` accepts float k).  Gating policies keep counting raw
  contenders ``k`` — AdaDUAL's Theorem 2 reasons about task counts, not
  link capacity.

The event backend (``core/simulator.py``) queries :meth:`Topology.
loaded_domains` per task; the fluid backend (``core/jaxsim.py``) lowers the
same rule to a static ``[domains, servers]`` incidence matrix
(:meth:`Topology.incidence`) so the per-step contention state stays
branchless and vmap-safe.  Constructors:

* :func:`nic_topology` — one domain per server NIC (the paper's model);
* :func:`two_tier` — NIC domains plus one oversubscribed uplink domain per
  rack (a blocking two-tier leaf/spine fabric);
* :func:`uplink_only` — rack uplinks without NIC domains (intra-rack
  traffic contention-free; an idealized full-bisection leaf).
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Domain:
    """One contention domain: the cut around ``servers``.

    ``oversub`` is the oversubscription factor of the shared resource at
    the cut (1.0 = a full-bandwidth NIC; an uplink with ``oversub=3`` has a
    third of nominal bandwidth, so k tasks crossing it behave like ``3k``
    tasks on a NIC).
    """

    name: str
    servers: Tuple[int, ...]
    oversub: float = 1.0

    def __post_init__(self) -> None:
        if not self.servers:
            raise ValueError(f"domain {self.name!r} covers no servers")
        if self.oversub <= 0:
            raise ValueError(
                f"domain {self.name!r}: oversub must be positive, got {self.oversub}"
            )
        object.__setattr__(self, "servers", tuple(sorted(set(self.servers))))


@dataclasses.dataclass(frozen=True)
class Topology:
    """A network fabric as a tuple of contention domains.

    Frozen and built from tuples only, so instances are hashable (they ride
    inside ``JaxSimConfig`` as a jit-static argument) and picklable (they
    cross the sweep runner's multiprocessing boundary).

    ``racks`` optionally groups servers for locality-aware placement
    (``PlacementPolicy('lwf_rack')`` / the fluid ``rack_pack`` gang mode);
    empty means one rack containing every server.
    """

    name: str
    n_servers: int
    domains: Tuple[Domain, ...]
    racks: Tuple[Tuple[int, ...], ...] = ()

    def __post_init__(self) -> None:
        if self.n_servers < 1:
            raise ValueError(f"n_servers must be >= 1, got {self.n_servers}")
        for d in self.domains:
            if d.servers[0] < 0 or d.servers[-1] >= self.n_servers:
                raise ValueError(
                    f"domain {d.name!r} references servers outside "
                    f"[0, {self.n_servers}): {d.servers}"
                )
        seen: set = set()
        for rack in self.racks:
            for s in rack:
                if s in seen:
                    raise ValueError(f"server {s} appears in two racks")
                if not 0 <= s < self.n_servers:
                    raise ValueError(f"rack server {s} out of range")
                seen.add(s)

    @property
    def n_domains(self) -> int:
        return len(self.domains)

    # -- the one load rule -------------------------------------------------
    def loaded_domains(self, servers: Iterable[int]) -> frozenset:
        """Indices of the domains a comm task with member-server set
        ``servers`` loads: the cuts its ring crosses (members both inside
        and outside).  A single-server task crosses no cut and loads
        nothing."""
        s = set(servers)
        return frozenset(
            i
            for i, d in enumerate(self.domains)
            if not s.isdisjoint(d.servers) and not s.issubset(d.servers)
        )

    def oversub_of(self, domain_index: int) -> float:
        return self.domains[domain_index].oversub

    # -- dense forms for the fluid backend ---------------------------------
    def incidence(self) -> np.ndarray:
        """Static ``(n_domains, n_servers)`` float incidence matrix:
        ``inc[d, s] = 1`` iff server s is inside domain d's cut.  The fluid
        backend derives per-step loads branchlessly as
        ``(m @ inc.T > 0) & (m @ (1-inc).T > 0)`` for occupancy mask m."""
        inc = np.zeros((self.n_domains, self.n_servers), dtype=np.float32)
        for i, d in enumerate(self.domains):
            inc[i, list(d.servers)] = 1.0
        return inc

    def oversub_array(self) -> np.ndarray:
        return np.asarray([d.oversub for d in self.domains], dtype=np.float32)

    def uplink_mask(self) -> np.ndarray:
        """``(n_domains,)`` bool: the uplink domains, those of more than
        one server (a NIC domain holds one).  A ring that loads one
        crosses a rack boundary."""
        return np.asarray([len(d.servers) > 1 for d in self.domains], dtype=bool)

    # -- rack helpers for locality-aware placement -------------------------
    def rack_groups(self) -> Tuple[Tuple[int, ...], ...]:
        """Rack server groups; servers not assigned to any rack form one
        trailing catch-all rack (so every server has a rack)."""
        if not self.racks:
            return (tuple(range(self.n_servers)),)
        assigned = {s for rack in self.racks for s in rack}
        rest = tuple(s for s in range(self.n_servers) if s not in assigned)
        return self.racks + ((rest,) if rest else ())

    def server_rack(self) -> np.ndarray:
        """``(n_servers,)`` int array: rack index of each server."""
        out = np.zeros((self.n_servers,), dtype=np.int32)
        for r, rack in enumerate(self.rack_groups()):
            out[list(rack)] = r
        return out


@dataclasses.dataclass(frozen=True)
class RingEdgeTopology(Topology):
    """The legacy ``contention_domain="link"`` reading — the paper's "each
    link between two nodes" wording — expressed as *dynamic* topology
    domains (closes the PR 3 ROADMAP leftover).

    A comm task over member-server set ``S`` loads the edges of the ring
    over ``sorted(S)``; two tasks contend iff they share a ring edge, so
    transfers over disjoint edge sets proceed in parallel even when they
    touch a common server.  Unlike the static fabric cuts above, the
    domains depend on the member set itself (the ring over {0,1,2} uses
    edge (0,2), the ring over {0,2,5} uses (0,5)), so there is no static
    incidence matrix: :meth:`incidence` raises, and the fluid backend
    cannot lower this reading (documented in the parity matrix).  Domains
    are ``("edge", u, v)`` tuples at unit oversubscription.
    """

    def __init__(self, n_servers: int) -> None:
        # bypass Topology's tuple-of-domains plumbing: domains are dynamic
        object.__setattr__(self, "name", "ring_edges")
        object.__setattr__(self, "n_servers", n_servers)
        object.__setattr__(self, "domains", ())
        object.__setattr__(self, "racks", ())
        Topology.__post_init__(self)

    @staticmethod
    def ring_edges(servers: Iterable[int]) -> frozenset:
        """The *directed* ring edges of a member-server set: consecutive
        pairs of the sorted ring, wrap-around included — exactly the edge
        set the event simulator used inline before this class existed.
        Direction matters: a ring all-reduce sends one way around the ring,
        so opposite directions of a full-duplex link are distinct domains
        (a 2-server ring loads both)."""
        ring = sorted(set(servers))
        return frozenset(
            ("edge", ring[i], ring[(i + 1) % len(ring)])
            for i in range(len(ring))
        )

    def loaded_domains(self, servers: Iterable[int]) -> frozenset:
        s = {x for x in servers if not 0 <= x < self.n_servers}
        if s:
            raise ValueError(f"servers {sorted(s)} outside [0, {self.n_servers})")
        members = set(servers)
        if len(members) < 2:
            return frozenset()  # single-server task: no shared link loaded
        return self.ring_edges(members)

    def oversub_of(self, domain) -> float:
        return 1.0  # every ring edge is a full-bandwidth link

    def incidence(self) -> np.ndarray:
        raise NotImplementedError(
            "ring-edge domains depend on each task's member set; there is no "
            "static [domains, servers] incidence matrix — the fluid backend "
            "does not support the legacy 'link' reading (use uplink_only/"
            "two_tier fabrics instead)"
        )


# ---------------------------------------------------------------------------
# Constructors
# ---------------------------------------------------------------------------


def nic_topology(n_servers: int) -> Topology:
    """The paper's model: one full-bandwidth NIC domain per server."""
    return Topology(
        name="nic",
        n_servers=n_servers,
        domains=tuple(
            Domain(name=f"nic{s}", servers=(s,)) for s in range(n_servers)
        ),
    )


def _rack_partition(n_servers: int, servers_per_rack: int) -> List[Tuple[int, ...]]:
    if servers_per_rack < 1:
        raise ValueError(f"servers_per_rack must be >= 1, got {servers_per_rack}")
    return [
        tuple(range(lo, min(lo + servers_per_rack, n_servers)))
        for lo in range(0, n_servers, servers_per_rack)
    ]


def two_tier(
    n_servers: int,
    servers_per_rack: int,
    oversub: float = 3.0,
    name: str = "",
) -> Topology:
    """Blocking two-tier fabric: per-server NIC domains plus one uplink
    domain per rack with oversubscription factor ``oversub``.  Cross-rack
    traffic loads the uplinks of every rack it touches; intra-rack traffic
    only the NICs.  With a single rack (``servers_per_rack >= n_servers``)
    the uplink is never a cut boundary, so the fabric degenerates to the
    NIC-only model (tested)."""
    racks = _rack_partition(n_servers, servers_per_rack)
    domains = list(nic_topology(n_servers).domains)
    domains += [
        Domain(name=f"uplink{r}", servers=rack, oversub=oversub)
        for r, rack in enumerate(racks)
    ]
    return Topology(
        name=name or f"two_tier:{servers_per_rack}x{oversub:g}",
        n_servers=n_servers,
        domains=tuple(domains),
        racks=tuple(racks),
    )


def uplink_only(
    n_servers: int, servers_per_rack: int, oversub: float = 3.0
) -> Topology:
    """Rack uplinks without NIC domains: intra-rack communication is
    contention-free (idealized non-blocking leaf), only cross-rack traffic
    contends on the oversubscribed uplinks."""
    racks = _rack_partition(n_servers, servers_per_rack)
    return Topology(
        name=f"uplink_only:{servers_per_rack}x{oversub:g}",
        n_servers=n_servers,
        domains=tuple(
            Domain(name=f"uplink{r}", servers=rack, oversub=oversub)
            for r, rack in enumerate(racks)
        ),
        racks=tuple(racks),
    )


__all__ = [
    "Domain",
    "RingEdgeTopology",
    "Topology",
    "nic_topology",
    "two_tier",
    "uplink_only",
]
