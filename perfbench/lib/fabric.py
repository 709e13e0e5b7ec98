"""The contention domains of the fabric that a configuration states.

A contention domain is a set of servers whose boundary is one shared
link: a transfer whose ring holds GPUs on servers both inside and outside
the set loads that link.  ``oversub`` is the link's oversubscription, the
share of a NIC's bandwidth it lacks: ``k`` transfers over a link of
``oversub`` 3 drain as ``3 k`` would over a NIC (arXiv 2002.10105 Eq. 5 at
the effective contention).

The configuration's ``topology`` is one of:

* ``"nic"``: a 10 GbE NIC per server, the paper's §III model: one domain
  per server, ``oversub`` 1;
* ``{"kind": "two_tier", "servers_per_rack": R, "oversub": x}``: a
  blocking two-tier (leaf/spine) fabric: those NIC domains, then one
  uplink domain per rack, racks of ``R`` consecutive servers (the last
  one shorter where ``R`` does not divide the servers), each uplink of
  ``oversub`` ``x``.
"""

from __future__ import annotations


def domains(cfg: dict) -> tuple:
    """``((servers, oversub), ...)``: the NIC domains in server order, then
    any uplink domains in rack order; ``servers`` a sorted tuple of server
    indices, ``oversub`` a float."""
    n = cfg["n_servers"]
    topo = cfg["topology"]
    nics = tuple(((s,), 1.0) for s in range(n))
    if topo == "nic":
        return nics
    if isinstance(topo, dict) and topo.get("kind") == "two_tier":
        per_rack, oversub = topo["servers_per_rack"], float(topo["oversub"])
        if per_rack < 1 or oversub <= 0:
            raise ValueError(f"two-tier fabric needs racks of at least one "
                             f"server and a positive oversub, not {topo!r}")
        racks = [tuple(range(lo, min(lo + per_rack, n)))
                 for lo in range(0, n, per_rack)]
        return nics + tuple((rack, oversub) for rack in racks)
    raise ValueError(f"topology is 'nic' or a two_tier fabric, not {topo!r}")
