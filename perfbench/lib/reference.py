"""Plain reference of the fixed-dt fluid cluster model, tick by tick.

Written from the model's description (arXiv 2002.10105 §III and the
fluid approximation's rules) and independent of the program: it imports
nothing of it and takes nothing it made.  One tick of one rollout, in
this order:

1. admission: of the queued jobs that have arrived (``arrival < t``) and
   fit the free GPUs, the one with the smallest remaining service
   ``iters * t_iter * gpus`` takes GPUs, servers in the order of the
   configuration's placement rank (:data:`RANKS`; ties by server index);
   it starts computing;
2. contention: each job's ring loads every contention domain of the
   fabric (:mod:`perfbench.lib.fabric`) that it crosses, holding GPUs on
   servers both inside and outside it (on a NIC domain: the job's server,
   if it holds GPUs on more than one); a transfer in flight sees ``k`` =
   the most transfers in flight on any domain it loads, each count times
   that domain's oversubscription, and drains at the Eq. 5 fraction
   ``b / (k b + (k - 1) eta)`` of its nominal rate, scaled by its slowest
   member server's bandwidth;
3. compute drains by dt; a finished compute phase of a multi-server job
   waits for its all-reduce, that of a one-server job ends the iteration;
4. gating: a waiting all-reduce may start if the policy's gating test
   passes it (:func:`threshold_gate`: Ada-SRSF and SRSF(n)); the test
   reads the raw count of transfers it would share a domain with,
   ``k_new`` (the most in flight on a domain it loads, plus itself), and
   the smallest remainder among them; of those it passes, the one with
   the least remaining service starts;
5. transfers in flight drain; a finished one ends the iteration; a job
   whose iterations are done frees its GPUs at ``t``.

No step is skipped, no rollouts are batched together beyond ``vmap``, and
there is no kernel: this is the semantics that the program's chunked,
skipping, compacting driver has to reproduce.

A reference of another placement or gating policy
(``perfbench/references/``) imports this module and passes its own rank
or gating test to :func:`simulate` through ``ranks`` or ``gates``.

``ftype`` is the precision of the continuous quantities (remaining phase
time, phase lengths, drain fraction).  The clock, arrival and finish times
and the iteration counts stay float32 in every precision: they count, and
a coarser type would stop the clock rather than round the physics.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lib import fabric

QUEUED, COMPUTE, COMM, DONE = 0, 1, 2, 3


def policy_params(name: str) -> tuple:
    """(max_ways, ratio-tested) of a gating policy: 'ada' or 'srsfN'."""
    if name == "ada":
        return 2, True
    if name.startswith("srsf") and name[4:].isdigit():
        return int(name[4:]), False
    raise ValueError(f"the reference models 'ada' and 'srsfN', not {name!r}")


def lwf_rank(free, m: "Model"):
    """LWF: the servers with the most free GPUs first (ties by server
    index).  Admission fills servers in ascending order of the rank."""
    return -free


#: placement name -> rank of the servers at an admission
RANKS = {"lwf": lwf_rank}
#: gating policy name -> test; a policy not named takes :func:`threshold_gate`
GATES = {}


def threshold_gate(c: dict, m: "Model"):
    """Ada-SRSF (``ada``) and SRSF(n) (``srsfN``): a waiting all-reduce may
    start if uncontended, or under at most ``max_ways`` transfers if (for
    ``ada``) its contention-free time is below Theorem 2's ratio of the
    smallest remainder it would share a domain with.  ``c`` holds the
    tick's contention state (:func:`_lane`, step 2)."""
    max_ways, ratio_tested = policy_params(m.policy)
    threshold = m.b / (2.0 * (m.b + m.eta))  # Theorem 2
    k_new = c["k_new"]
    return (k_new <= 1) | (k_new <= max_ways) & (
        (c["comm"] < threshold * c["old_min"]) | (not ratio_tested)
    )


def _lane(lane, m: "Model", ftype):
    """Run one rollout to its end; ``lane`` holds its job arrays."""
    f32 = jnp.float32
    a, b, eta, dt = m.a, m.b, m.eta, m.dt
    n_servers, per_server = m.n_servers, m.gpus_per_server
    bw = np.ones((n_servers,), np.float32)
    bw[: len(m.server_bandwidth)] = m.server_bandwidth[:n_servers]
    bw = jnp.asarray(bw)
    inside = np.zeros((len(m.domains), n_servers), bool)  # domain x server
    for d, (members, _) in enumerate(m.domains):
        inside[d, list(members)] = True
    oversub = jnp.asarray([x for _, x in m.domains], ftype)

    arrival = lane["arrival"]
    gpus = lane["n_gpus"]
    gpus_f = gpus.astype(f32)
    t_iter = lane["t_iter"].astype(ftype)
    comm = (a + b * lane["msg_bytes"]).astype(ftype)  # contention-free s
    n_jobs = arrival.shape[0]
    jobs = jnp.arange(n_jobs)

    def tick(st):
        t = (st["i"] + 1).astype(f32) * dt
        phase, rem, iters = st["phase"], st["rem"], st["iters"]
        servers, free, started = st["servers"], st["free"], st["started"]

        multi_before = (servers > 0).sum(axis=1) > 1
        service = iters * (
            t_iter.astype(f32) + jnp.where(multi_before, comm.astype(f32), 0.0)
        ) * gpus_f

        # 1. admission
        queued = phase == QUEUED
        ready = queued & (arrival < t) & (gpus_f <= free.sum())
        key = jnp.where(queued, iters * t_iter.astype(f32) * gpus_f, jnp.inf)
        pick = jnp.argmin(jnp.where(ready, key, jnp.inf))
        admit = ready[pick] & (free.sum() >= gpus_f[pick])
        order = jnp.argsort(m.rank(free, m), stable=True)
        free_sorted = free[order]
        before = jnp.cumsum(free_sorted) - free_sorted
        take_sorted = jnp.clip(gpus_f[pick] - before, 0.0, free_sorted)
        take = jnp.zeros_like(free).at[order].set(take_sorted)
        take = jnp.where(admit, take, 0.0)
        chosen = (jobs == pick) & admit
        servers = jnp.where(chosen[:, None], take.astype(jnp.int32)[None, :],
                            servers)
        free = free - take
        phase = jnp.where(chosen, COMPUTE, phase)
        rem = jnp.where(chosen, t_iter, rem)

        # 2. contention
        holds = servers > 0
        multi = holds.sum(axis=1) > 1
        held_in = (holds[:, None, :] & inside[None]).any(axis=2)
        held_out = (holds[:, None, :] & ~inside[None]).any(axis=2)
        loads = held_in & held_out  # (job, domain): its ring crosses the cut
        in_comm = phase == COMM
        active = in_comm & started & (rem > 0)
        per_dom = (loads & active[:, None]).sum(axis=0)  # transfers per domain
        weighted = per_dom.astype(ftype) * oversub
        k_eff = jnp.maximum(
            jnp.where(loads, weighted[None, :], 0).max(axis=1), 1
        ).astype(ftype)
        k_new = jnp.maximum(jnp.where(loads, per_dom[None, :] + 1, 0).max(axis=1), 1)
        slowest = jnp.where(holds, bw[None, :], jnp.inf).min(axis=1)
        slowest = jnp.where(holds.any(axis=1), slowest, 1.0).astype(ftype)
        frac = slowest * (b / (k_eff * b + (k_eff - 1) * eta))
        dom_min = jnp.where(loads & active[:, None], rem[:, None], jnp.inf).min(axis=0)
        old_min = jnp.where(loads, dom_min[None, :], jnp.inf).min(axis=1)

        # 3. compute
        computing = phase == COMPUTE
        rem = jnp.where(computing, rem - dt, rem)
        computed = computing & (rem <= 0)
        to_comm = computed & multi
        iter_direct = computed & ~multi

        # 4. gating
        waiting = in_comm & ~started
        ok = waiting & m.gate(
            {"comm": comm, "k_new": k_new, "old_min": old_min, "loads": loads,
             "active": active, "rem": rem}, m)
        first = jnp.argmin(jnp.where(ok, service, jnp.inf))
        started = started | ((jobs == first) & ok)

        # 5. transfers
        draining = in_comm & started
        rem = jnp.where(draining, rem - dt * frac, rem)
        sent = draining & (rem <= 0)
        iter_done = iter_direct | sent
        iters = iters - iter_done.astype(f32)
        job_done = iter_done & (iters <= 0)
        again = iter_done & ~job_done
        phase = jnp.where(to_comm, COMM, phase)
        rem = jnp.where(to_comm, comm, rem)
        started = started & ~(to_comm | iter_done)
        phase = jnp.where(again, COMPUTE, phase)
        rem = jnp.where(again, t_iter, rem)
        phase = jnp.where(job_done, DONE, phase)
        finish = jnp.where(job_done, t, st["finish"])
        free = free + (servers * job_done[:, None]).sum(axis=0).astype(f32)
        servers = jnp.where(job_done[:, None], 0, servers)
        return {"phase": phase, "rem": rem, "iters": iters, "servers": servers,
                "free": free, "started": started, "finish": finish,
                "i": st["i"] + 1}

    def running(st):
        return ((st["phase"] != DONE).any()) & (st["i"] < m.max_steps)

    st0 = {
        "phase": jnp.full((n_jobs,), QUEUED, jnp.int32),
        "rem": jnp.zeros((n_jobs,), ftype),
        "iters": lane["iters"].astype(f32),
        "servers": jnp.zeros((n_jobs, n_servers), jnp.int32),
        "free": jnp.full((n_servers,), float(per_server), f32),
        "started": jnp.zeros((n_jobs,), bool),
        "finish": jnp.full((n_jobs,), jnp.inf, f32),
        "i": jnp.asarray(0, jnp.int32),
    }
    st = jax.lax.while_loop(running, tick, st0)
    done = st["phase"] == DONE
    t_end = st["i"].astype(f32) * dt
    return {
        "jct": st["finish"] - arrival,
        "finished": done,
        "makespan": jnp.where(done.any(), jnp.where(done, st["finish"], -jnp.inf).max(), t_end),
    }


class Model(NamedTuple):
    """The configuration's numbers that the model reads, with the rank
    and gating test of its placement and policy (hashable, so a compiled
    reference is reused across calls)."""

    a: float
    b: float
    eta: float
    server_bandwidth: tuple
    dt: float
    policy: str
    n_servers: int
    gpus_per_server: int
    max_steps: int
    domains: tuple  # fabric.domains: ((servers, oversub), ...)
    rank: Callable
    gate: Callable

    @classmethod
    def of(cls, cfg: dict, ranks: dict = RANKS, gates: dict = GATES) -> "Model":
        placement, policy = cfg["placement"], cfg["policy"]
        if placement not in ranks:
            raise ValueError(f"the reference models placements "
                             f"{sorted(ranks)}, not {placement!r}")
        if policy not in gates:
            policy_params(policy)  # raises for a policy it does not model
        c = cfg["contention"]
        return cls(c["a"], c["b"], c["eta"], tuple(c["server_bandwidth"]),
                   cfg["dt"], policy, cfg["n_servers"],
                   cfg["gpus_per_server"], cfg["max_steps"],
                   fabric.domains(cfg), ranks[placement],
                   gates.get(policy, threshold_gate))


@functools.partial(jax.jit, static_argnames=("model", "ftype"))
def _run(lanes, model, ftype):
    return jax.vmap(lambda lane: _lane(lane, model, ftype))(lanes)


def simulate(lanes: list, cfg: dict, ftype=jnp.float32, block: int = 64, *,
             ranks: dict = RANKS, gates: dict = GATES) -> list:
    """Reference rollouts of ``lanes`` (job-array dicts of equal job
    count), ``block`` at a time; returns per lane a dict of numpy
    ``jct``, ``finished`` and ``makespan``.  ``ranks`` and ``gates`` map
    the placement and policy names to their rules."""
    model = Model.of(cfg, ranks, gates)
    out = []
    for start in range(0, len(lanes), block):
        part = lanes[start:start + block]
        stacked = {k: jnp.asarray(np.stack([l[k] for l in part]))
                   for k in part[0]}
        res = jax.device_get(_run(stacked, model, ftype))
        out.extend({k: v[i] for k, v in res.items()} for i in range(len(part)))
    return out
