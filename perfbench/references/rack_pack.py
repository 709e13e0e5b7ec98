"""Plain reference of rack-aware gang placement (``lwf_rack``) on a
two-tier fabric, with the fabric's counters.

Placement, from its description: an admission fills the servers of the
rack with the most free GPUs first, and inside a rack the servers with
the most free GPUs first; ties go to the lower server index.  The racks
are the uplink domains of the fabric (:mod:`perfbench.lib.fabric`), the
domains after the NICs, and every server lies in one.  The order is one
key over all servers, so where two racks are equally free their servers
interleave by their own free GPUs rather than rack by rack.

Everything else is the base reference (:mod:`perfbench.lib.reference`).
With ``counters`` it also counts, tick by tick, what the fabric did to
each rollout:

* ``uplink_jobs``: jobs whose ring loaded an uplink domain (a domain of
  more than one server), i.e. crossed a rack boundary;
* ``transfer_ticks``: transfers in flight at the end of each tick, summed
  over the ticks;
* ``uplink_transfer_ticks``: the same, of the transfers that load an
  uplink domain.

The counts are read where the base hands the tick's contention state to
the gating test, whose in-flight transfers are those of the previous
tick's end.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lib import reference

COUNTERS = ("uplink_jobs", "uplink_transfer_ticks", "transfer_ticks")


def rack_rank(free, m: "reference.Model"):
    """Ascending key: the servers of the freest rack first, inside it the
    freest servers first.  A server's own free GPUs lie in
    ``[0, gpus_per_server]``, so the rack's count scaled by one more than
    that orders first and the server's own breaks its ties, exactly."""
    racks = np.zeros((len(m.domains) - m.n_servers, m.n_servers), np.float32)
    for r, (members, _) in enumerate(m.domains[m.n_servers:]):
        racks[r, list(members)] = 1.0
    rack_free = (racks * free[None, :]).sum(axis=1)  # per rack
    server_rack_free = (racks * rack_free[:, None]).sum(axis=0)
    return -(server_rack_free * (m.gpus_per_server + 1) + free)


#: placement name -> rank of the servers at an admission
RANKS = {**reference.RANKS, "lwf_rack": rack_rank}


class _Tally:
    """The counts of one block of rollouts, added to tick by tick."""

    def __init__(self, n_lanes: int, n_jobs: int):
        self.transfer_ticks = np.zeros((n_lanes,), np.int64)
        self.uplink_transfer_ticks = np.zeros((n_lanes,), np.int64)
        self.crossed = np.zeros((n_lanes, n_jobs), bool)  # ring on an uplink

    def add(self, flying, on_uplink, crossing):
        """One tick of every lane of the block; returns the ``True`` per
        lane that keeps the host call in the program."""
        self.transfer_ticks += flying
        self.uplink_transfer_ticks += on_uplink
        self.crossed |= crossing
        return np.ones(flying.shape, bool)

    def counts(self, lane: int) -> dict:
        return {"uplink_jobs": int(self.crossed[lane].sum()),
                "uplink_transfer_ticks": int(self.uplink_transfer_ticks[lane]),
                "transfer_ticks": int(self.transfer_ticks[lane])}


def _counting(gate, tallies: list):
    """``gate`` that also hands each tick's counts to ``tallies[-1]``, in
    one host call for the whole block of lanes.  A lane whose rollout has
    ended adds nothing: no job of it holds servers or transfers."""

    def counted(c, m):
        uplink = np.asarray([len(members) > 1 for members, _ in m.domains])
        crossing = (c["loads"] & uplink[None, :]).any(axis=1)
        flying = c["active"]
        kept = jax.pure_callback(
            lambda *a: tallies[-1].add(*a), jax.ShapeDtypeStruct((), bool),
            flying.sum(), (flying & crossing).sum(), crossing,
            vmap_method="expand_dims")
        return gate(c, m) & kept

    return counted


def simulate(lanes: list, cfg: dict, ftype=jnp.float32, block: int = 64, *,
             counters: bool = False) -> list:
    """The base reference's ``simulate`` with :data:`RANKS`.  With
    ``counters`` each lane's dict also holds :data:`COUNTERS`, at the cost
    of a host call a tick for each block of lanes."""
    if not counters:
        return reference.simulate(lanes, cfg, ftype, block, ranks=RANKS)
    policy = cfg["policy"]
    tallies = []
    gates = {policy: _counting(
        reference.GATES.get(policy, reference.threshold_gate), tallies)}
    out = []
    for start in range(0, len(lanes), block):
        part = lanes[start:start + block]
        tallies.append(_Tally(len(part), len(part[0]["arrival"])))
        res = reference.simulate(part, cfg, ftype, block, ranks=RANKS,
                                 gates=gates)
        out.extend({**r, **tallies[-1].counts(i)} for i, r in enumerate(res))
    return out
